"""Share of the traced window in which no operation ran on the device
(the mean over the cell's devices)."""


def read(ctx):
    if ctx.trace is None:
        return None
    return 100.0 * ctx.trace.idle_share
