"""Host plan build per solve, the source tree and the target batches:
the program's `plan.tree_build` spans in the traced window (their
`repro.obs` records), over the solves of the window."""

from bench import program_read


def read(ctx):
    solves = ctx.layer.get("solves")
    t = program_read.plan_phases_s().get("tree")
    if not solves or t is None:
        return None
    return t / solves
