"""Host plan build per solve, every copy between host and device the
planner makes: the program's `plan.copy` spans in the traced window
(their `repro.obs` records), over the solves of the window."""

from bench import program_read


def read(ctx):
    solves = ctx.layer.get("solves")
    t = program_read.plan_phases_s().get("copy")
    if not solves or t is None:
        return None
    return t / solves
