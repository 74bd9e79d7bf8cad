#!/usr/bin/env python3
"""Readings for a cell's correctness limits, many seeds in one process.

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,3 \
        [--control-seeds 4,5,6] [--seconds S] [--out PATH]

For each of `--seeds` it runs the cell as `bench/run.py` does (set-up,
a window of `--seconds`, the check) and prints the numbers compared; for
each of `--control-seeds` it does the same with the lower-precision
control (bench/reference/direct.py) in the program's place. The largest
reading of the program over a dozen seeds or more, and the smallest of
the control, bound the limits in bench/limits/. It needs the chips the
cell asks for, like bench/run.py.
"""
import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--out", help="also append the readings to this file")
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import harness

    harness.enable_compile_cache()
    cell = harness.load_cell(args.workload, ROOT)
    runs = [(int(s), False) for s in args.seeds.split(",") if s] + \
        [(int(s), True) for s in args.control_seeds.split(",") if s]
    for seed, control in runs:
        try:
            line = harness.run_cell(cell, seed, args.seconds, False,
                                    t_start=time.perf_counter(),
                                    control=control)
        except harness.NoChip as e:
            print(f"calibrate: {e}", file=sys.stderr)
            return 2
        out = dict(workload=cell.name, seed=seed, control=control,
                   checks={k: v["value"] for k, v in line["checks"].items()},
                   attempted=line["attempted"], metrics={
                       k: v["value"] for k, v in line["metrics"].items()},
                   memory_peak_bytes=line["device"]["memory_peak_bytes"])
        print(json.dumps(out), flush=True)
        if args.out:
            Path(args.out).parent.mkdir(parents=True, exist_ok=True)
            with open(args.out, "a") as f:
                f.write(json.dumps(out) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
