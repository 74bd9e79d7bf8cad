"""Share of the pair evaluations the batch_cluster kernels launch that
the algorithm needs: real targets x real sources (or x (n+1)^3 points
of an approximation) over the Pallas grids' rows x lanes x slots x
source width, both lists together (the program's `kernel_work` count of
the window's plan)."""

from bench import program_read


def read(ctx):
    work = program_read.eval_kernel_work(ctx.traffic)
    if not work:
        return None
    useful = sum(w["useful"] for w in work.values())
    launched = sum(w["launched"] for w in work.values())
    return 100.0 * useful / launched if launched else None
