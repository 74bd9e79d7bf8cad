"""Share of the roofline of the Pallas kernels in an evaluation.

Time: the device time of every Pallas call in the traced window. Work:
the evaluations the paper's algorithm needs, counted on the benchmark's
own copy of the tree (bench/reference/worktree.py), times the calls of
the window. The least time is the larger of operations over the peak
FLOP/s and bytes over the peak bandwidth (bench/peaks.json).
"""


def read(ctx):
    t = ctx.trace
    if t is None or t.kernel_s <= 0.0 or ctx.peaks is None:
        return None
    work = ctx.traffic.work()
    if work is None:
        return None
    calls = ctx.layer["calls"]
    least = max(calls * work["flops"] / ctx.peaks["flops_per_s"],
                calls * work["bytes"] / ctx.peaks["bytes_per_s"])
    return 100.0 * least / t.kernel_s
