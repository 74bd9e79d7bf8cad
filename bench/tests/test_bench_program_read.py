"""The per-layer readers of the program's own instruments: the host
planner's phases, kernels named by call site, and the count of kernel
work."""
import json
import time

import numpy as np
import pytest

from bench import harness, program_read, trace_reduce
from bench.reference import worktree


def _rec(name, t0, dur, parent=None, tid=1):
    return dict(name=name, t0=t0, dur=dur, depth=int(parent is not None),
                tid=tid, cat="phase", parent=parent)


# A replan as the program records it: the build's phases, then the
# re-padding into the budget with its own copies.
_REPLAN = [_rec("plan.build", 0.0, 2.4),
           _rec("plan.tree_build", 0.0, 1.0, "plan.build"),
           _rec("plan.interaction_lists", 1.0, 1.1, "plan.build"),
           _rec("plan.pack", 2.1, 0.2, "plan.build"),
           _rec("plan.copy", 2.3, 0.1, "plan.build"),
           _rec("plan.pad", 3.0, 0.8),
           _rec("plan.copy", 3.2, 0.4, "plan.pad")]


def test_plan_phases_split_the_replan(monkeypatch):
    from repro import obs

    monkeypatch.setattr(obs.trace, "_spans", _REPLAN)
    t = program_read.plan_phases_s()
    assert t == pytest.approx(dict(tree=1.0, lists=1.1,
                                   pack=0.2 + 0.8 - 0.4, copy=0.1 + 0.4))
    assert sum(t.values()) == pytest.approx(2.4 + 0.8)   # build + pad
    # A program that times its uploads inside `plan.pack`, with child
    # spans of its own under the tree and the lists: the whole tree and
    # lists read as they are, packing and copies not at all.
    old = [_rec("plan.build", 0.0, 2.5),
           _rec("plan.tree_build", 0.0, 1.0, "plan.build"),
           _rec("tree.build_tree", 0.1, 0.9, "plan.tree_build"),
           _rec("plan.interaction_lists", 1.0, 1.1, "plan.build"),
           _rec("interaction.build_lists", 1.0, 1.1,
                "plan.interaction_lists"),
           _rec("plan.pack", 2.1, 0.3, "plan.build"),
           _rec("plan.pad", 3.0, 0.8)]
    monkeypatch.setattr(obs.trace, "_spans", old)
    assert program_read.plan_phases_s() == pytest.approx(
        dict(tree=1.0, lists=1.1))


def _summary(ops, idle_gaps=(), top=10):
    """A reduced window whose operations take the given times; the
    reduction lists the `top` costliest of them."""
    return trace_reduce.TraceSummary(
        window_s=10.0, busy_s=9.0, devices=1, kernel_s=8.0,
        op_totals=dict(ops),
        device_ops=sorted(ops, key=lambda o: -o[1])[:top],
        idle_gaps=list(idle_gaps))


def test_kernel_device_s_reads_the_kernel_named_by_its_site():
    s = _summary([("%while.20 = (s32[]) while()", 6.0),
                  ("%bltc_direct.7 = f32[320,1,2048] custom-call()", 5.9),
                  ("%bltc_direct.9 = f32[8,1,2048] custom-call()", 0.1),
                  ("%bltc_approx.8 = f32[384,1,2048] custom-call()", 2.0),
                  ("%bltc_directory.1 = f32[1] fusion()", 9.0)])
    assert program_read.kernel_device_s(s, "bltc_direct") \
        == pytest.approx(6.0)
    assert program_read.kernel_device_s(s, "bltc_approx") \
        == pytest.approx(2.0)
    # A program that does not name its kernels gives nothing to read.
    old = _summary([("%closed_call.9 = f32[320,1,2048] custom-call()", 9.0)])
    assert program_read.kernel_device_s(old, "bltc_direct") is None


def test_kernel_device_s_reads_past_the_ten_costliest():
    # The window has more operations than the breakdown lists; the kernel
    # is read from the totals of all of them, whatever the list keeps.
    fill = [(f"%fusion.{i} = f32[8] fusion()", 0.5) for i in range(12)]
    ops = fill + [("%bltc_direct.7 = custom-call()", 5.0),
                  ("%bltc_approx.8 = custom-call()", 0.2),
                  ("%modified_charges.2 = custom-call()", 0.05)]
    s = _summary(ops)
    assert len(s.device_ops) == 10 and len(s.op_totals) == 15
    assert "%bltc_approx.8 = custom-call()" not in dict(s.device_ops)
    assert program_read.kernel_device_s(s, "bltc_direct") == 5.0
    assert program_read.kernel_device_s(s, "bltc_approx") == 0.2
    assert program_read.kernel_device_s(s, "modified_charges") == 0.05


def test_kernel_device_s_sums_a_kernel_split_into_calls():
    # A kernel run as many calls, each below the ten costliest, inside
    # loops that the trace lists too: every call counts once, the loops
    # not at all.
    calls = [(f"%bltc_direct.{i} = f32[40,1,2048] custom-call()", 0.25)
             for i in range(20)]
    loops = [(f"%while.{i} = (s32[]) while()", 0.9) for i in range(10)]
    s = _summary(calls + loops + [("%bltc_approx.99 = custom-call()", 1.0)])
    assert not any(n.startswith("%bltc_direct") for n, _ in s.device_ops)
    assert program_read.kernel_device_s(s, "bltc_direct") \
        == pytest.approx(20 * 0.25)
    assert program_read.kernel_device_s(s, "bltc_approx") == 1.0
    assert program_read.kernel_device_s(s, "bltc_missing") is None


def _ctx(trace=None, layer=None, traffic=None):
    return harness.Context(trace=trace, layer=layer or {}, units=2,
                           window_s=10.0, peaks=None, traffic=traffic)


def test_device_readers():
    s = _summary([("%bltc_direct.7 = custom-call()", 6.0),
                  ("%bltc_approx.8 = custom-call()", 2.0)])
    read = harness._reader
    assert read("direct_kernel_s.eval")(_ctx(s, dict(calls=2))) == 3.0
    assert read("approx_kernel_s.eval")(_ctx(s, dict(calls=2))) == 1.0
    for name in ("direct_kernel_s.eval", "approx_kernel_s.eval"):
        assert read(name)(_ctx(None, dict(calls=2))) is None


def test_span_readers(monkeypatch):
    from repro import obs

    monkeypatch.setattr(obs.trace, "_spans", _REPLAN)
    ctx = _ctx(layer=dict(solves=2))
    got = {m: harness._reader(f"plan_{m}_s.solve")(ctx)
           for m in ("tree", "lists", "pack", "copy")}
    assert got == pytest.approx(dict(tree=0.5, lists=0.55,
                                     pack=(0.2 + 0.4) / 2,
                                     copy=(0.1 + 0.4) / 2))
    # A window without the program's spans reads nothing.
    monkeypatch.setattr(obs.trace, "_spans", [])
    for m in ("tree", "lists", "pack", "copy"):
        assert harness._reader(f"plan_{m}_s.solve")(ctx) is None


def test_traced_solve_spans_cover_the_plan_build():
    # The program's planner spans account for the benchmark's own clock
    # around each replan, and never more. At 12,000 points the planner's
    # work, not the fixed cost of a call, fills the replan, as at the
    # cell's own size.
    from bench_tiny import tiny_cell

    cell = tiny_cell("coulomb_1m.solve", backend="xla")
    cell.config["system"]["n"] = 12_000
    line = harness.run_cell(cell, 2**33 + 7, 0.2, True,
                            t_start=time.perf_counter(), require_chip=False)
    m = {k: v["value"] for k, v in line["metrics"].items()}
    parts = sum(m[f"plan_{p}_s.solve"] for p in ("tree", "lists", "pack",
                                                 "copy"))
    assert 0.9 * m["plan_build_s.solve"] <= parts <= m["plan_build_s.solve"]
    json.dumps(line)


def test_traced_eval_reads_the_kernel_occupancy():
    from bench_tiny import run_tiny

    line = run_tiny("coulomb_1m.eval", trace=True, backend="xla")
    assert 0.0 < line["metrics"]["kernel_occupancy.eval"]["value"] <= 100.0


@pytest.mark.parametrize("theta,degree,leaf", [(0.7, 3, 200), (0.5, 2, 64)])
def test_program_useful_kernel_work_is_the_count(theta, degree, leaf):
    # The program's count of the kernel work it needs (the plan's
    # `kernel_work`, useful part) is the work counted here, pair by pair.
    from repro.core.api import TreecodeConfig, TreecodeSolver

    x = np.random.default_rng(1).uniform(-1, 1, (6000, 3))
    plan = TreecodeSolver(TreecodeConfig(
        theta=theta, degree=degree, leaf_size=leaf, batch_size=leaf,
        backend="xla")).plan(x, nranks=1)
    kw = plan.stats()["kernel_work"]
    w = worktree.count_work(x, x, theta=theta, degree=degree,
                            leaf_size=leaf, batch_size=leaf)
    assert kw["approx"]["useful"] == w.approx_evals
    assert kw["direct"]["useful"] == w.direct_evals
    assert kw["approx"]["launched"] > w.approx_evals
    assert kw["direct"]["launched"] > w.direct_evals
