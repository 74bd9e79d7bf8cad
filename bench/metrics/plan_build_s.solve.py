"""Host plan build per solve: the benchmark's own clock around each
`plan.replan(points)` of the window (tree, interaction lists and the
plan's upload), over the solves of the window."""


def read(ctx):
    solves = ctx.layer.get("solves")
    if not solves:
        return None
    return ctx.layer["plan_s"] / solves
