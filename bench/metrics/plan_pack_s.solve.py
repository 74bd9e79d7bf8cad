"""Host plan build per solve, the NumPy packing of the plan's arrays and
their re-padding into the kept budget: the program's `plan.pack` and
`plan.pad` spans in the traced window (their `repro.obs` records) less
the copies in `plan.pad`, over the solves of the window. Nothing where
the program does not time its copies apart (`plan.copy`)."""

from bench import program_read


def read(ctx):
    solves = ctx.layer.get("solves")
    t = program_read.plan_phases_s().get("pack")
    if not solves or t is None:
        return None
    return t / solves
