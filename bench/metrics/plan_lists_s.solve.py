"""Host plan build per solve, the MAC traversal that builds the
interaction lists: the program's `plan.interaction_lists` spans in the
traced window (their `repro.obs` records), over the solves of the
window."""

from bench import program_read


def read(ctx):
    solves = ctx.layer.get("solves")
    t = program_read.plan_phases_s().get("lists")
    if not solves or t is None:
        return None
    return t / solves
