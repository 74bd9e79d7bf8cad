"""Each cell of BENCHMARK.json rehearsed end to end on the CPU at a tiny
size: its traffic, its window, its trace reduction and its check, with
the keys of the line it would print."""
import json

import pytest

from bench import harness
from bench_tiny import run_tiny

SPEC = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in SPEC["workloads"] if w["chips"] == 1]


@pytest.mark.parametrize("cell", CELLS)
def test_rehearsal_line(cell):
    line = run_tiny(cell)
    assert list(line) == ["correct", "attempted", "failed", "metrics",
                          "device", "checks"]
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 1
    want = {m["name"] for m in SPEC["end_to_end"]
            if cell in m.get("workloads", [cell])}
    assert set(line["metrics"]) == want and "setup_s" in want
    for m in line["metrics"].values():
        assert m["value"] > 0 and m["unit"]
    assert set(line["device"]) == {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    for c in line["checks"].values():
        assert set(c) == {"value", "limit"} and c["value"] <= c["limit"]
    json.dumps(line)


@pytest.mark.parametrize("cell", CELLS)
def test_rehearsal_traced(cell):
    # The CPU trace has no TPU device plane: the device readers find
    # nothing and leave their metrics out; the program's spans and
    # counters are still read. (The XLA path: tracing the interpreted
    # kernels on the CPU takes minutes.)
    line = run_tiny(cell, trace=True, backend="xla")
    assert line["correct"] is True
    names = {m["name"] for m in SPEC["per_layer"]
             if cell in m.get("workloads", [cell])}
    assert set(line["metrics"]) <= names
    host_read = {"plan_build_s.solve"}
    assert names & host_read <= set(line["metrics"])


def test_every_seed_runs_the_budget_shapes():
    # Solves on different seeds are padded into the one budget of the
    # configuration, so a run on a new seed compiles nothing new.
    from bench import generator
    from bench_tiny import tiny_cell

    cell = tiny_cell("coulomb_1m.solve", backend="xla")
    budget = generator.shape_budget(cell.config)
    caps = []
    for seed in (5, 2**33 + 1):
        t = generator.make_traffic(cell.config, cell.traffic, cell.limits,
                                   seed)
        x, _ = generator.system_points(cell.config, seed)
        caps.append(t.plan(x, budget).capacities)
        assert not t.budget_grown
    assert caps[0] == caps[1] == budget


def test_eval_geometry_is_the_same_for_every_seed():
    # eval's work is the same on every seed: one geometry, new charges.
    from bench import generator
    from bench_tiny import tiny_cell

    cell = tiny_cell("coulomb_1m.eval", backend="xla")
    runs = []
    for seed in (5, 2**33 + 1):
        t = generator.make_traffic(cell.config, cell.traffic, cell.limits,
                                   seed)
        t.setup()
        runs.append((t.x, t.plan_.capacities, t.charges[0]))
    assert (runs[0][0] == runs[1][0]).all() and runs[0][1] is None
    assert not (runs[0][2] == runs[1][2]).all()
