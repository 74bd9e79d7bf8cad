#!/usr/bin/env python3
"""The treecode's chip benchmark: one run of one cell.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout, on a machine with the TPU chips the
cell asks for (BENCHMARK.json). It sets up the cell from the seed,
measures for `--seconds`, checks what the window produced against the
float64 reference, and prints one JSON line: the cell's end-to-end
metrics with ``--trace 0``, its per-layer metrics from a profiler trace
of the window with ``--trace 1``. Without a TPU, or with fewer chips than
the cell needs, it prints no result and exits with 2.

JAX's persistent compilation cache is the program's: ``.jax_cache/`` at
the root of the checkout, or where ``JAX_COMPILATION_CACHE_DIR`` puts it,
so only the first run of a cell there compiles.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def _seed(text: str) -> int:
    value = int(text)
    if value < 0:
        raise argparse.ArgumentTypeError("the seed is a whole number >= 0")
    return value


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=_seed, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--dump-ops", metavar="PATH",
                    help="with --trace 1: also write the traced device "
                    "operations by name to PATH")
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import harness

    harness.enable_compile_cache()
    cell = harness.load_cell(args.workload, ROOT)
    try:
        line = harness.run_cell(cell, args.seed, args.seconds,
                                bool(args.trace), t_start=T_START,
                                dump_ops=args.dump_ops)
    except harness.NoChip as e:
        print(f"bench: {e}; nothing was measured", file=sys.stderr)
        return 2
    harness.print_result(line)
    return 0


if __name__ == "__main__":
    sys.exit(main())
