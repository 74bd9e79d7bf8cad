"""Device time of the direct leaf sums per evaluation: the program's
Pallas kernel named `bltc_direct` (the `bltc.direct` site of the
executor) in the traced window, over the calls of the window."""

from bench import program_read


def read(ctx):
    if ctx.trace is None:
        return None
    t = program_read.kernel_device_s(ctx.trace, "bltc_direct")
    return None if t is None else t / ctx.layer["calls"]
