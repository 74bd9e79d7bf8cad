"""Observability subsystem (`repro.obs`): phase-span tracer semantics,
Chrome-trace export, BenchReport schema validation, occupancy counters
against hand-counted plans, and the compile/retrace event log as the
single source of truth behind `Simulation.stats()` /
`ServeFrontend.stats()` (cross-checked against the legacy counters)."""
import json

import numpy as np
import pytest

from repro import obs


@pytest.fixture
def tracer():
    """Enabled tracer with a clean buffer; restores disabled+clean."""
    obs.clear()
    obs.enable()
    yield obs
    obs.disable()
    obs.clear()


# ---------------------------------------------------------------- tracer


def test_disabled_span_is_allocation_free_singleton():
    obs.disable()
    obs.clear()
    a = obs.span("x")
    b = obs.span("y")
    assert a is b  # the module singleton — no per-call allocation
    with a:
        with obs.span("nested"):
            pass
    assert obs.spans() == []


def test_span_nesting_depth_and_parent(tracer):
    with obs.span("outer"):
        with obs.span("inner"):
            pass
        with obs.span("inner"):
            pass
    recs = {(r["name"], r["depth"]) for r in obs.spans()}
    assert ("outer", 0) in recs and ("inner", 1) in recs
    inner = [r for r in obs.spans() if r["name"] == "inner"]
    assert all(r["parent"] == "outer" for r in inner)
    # exit order: children recorded before the enclosing span
    assert obs.spans()[-1]["name"] == "outer"


def test_reentrant_span_not_double_counted(tracer):
    def rec(depth):
        with obs.span("work"):
            if depth:
                rec(depth - 1)

    rec(3)
    assert len([r for r in obs.spans() if r["name"] == "work"]) == 4
    totals = obs.phase_totals()
    # only the outermost occurrence counts toward the total
    outer = [r for r in obs.spans()
             if r["name"] == "work" and r["parent"] != "work"]
    assert len(outer) == 1
    assert totals["work"] == pytest.approx(outer[0]["dur"] * 1e3)


def test_phase_totals_prefix_and_sibling_sum(tracer):
    with obs.span("md.advance"):
        pass
    with obs.span("md.advance"):
        pass
    with obs.span("plan.build"):
        pass
    totals = obs.phase_totals("md.")
    assert set(totals) == {"md.advance"}
    both = [r["dur"] for r in obs.spans() if r["name"] == "md.advance"]
    assert totals["md.advance"] == pytest.approx(sum(both) * 1e3)


def _host_plane(log_dir):
    """(host-plane events as (name, start, end) in realtime seconds) of
    the newest profile under `log_dir`."""
    import glob
    import os

    from jax.profiler import ProfileData

    path = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                            recursive=True), key=os.path.getmtime)[-1]
    data = ProfileData.from_file(path)
    # Event times count from the profile's start, a realtime stamp.
    t0 = [int(v) for p in data.planes for k, v in p.stats
          if k == "profile_start_time"][0] * 1e-9
    return [(e.name, t0 + e.start_ns * 1e-9,
             t0 + (e.start_ns + e.duration_ns) * 1e-9)
            for p in data.planes if p.name.startswith("/host:")
            for line in p.lines for e in line.events]


def test_enabled_span_lands_on_the_profiler_host_plane(tmp_path):
    import time

    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    obs.clear()
    obs.enable()
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        ref = time.perf_counter(), time.time_ns()
        with obs.span("plan.probe"):
            time.sleep(0.02)
        obs.disable()
        with obs.span("plan.hidden"):
            time.sleep(0.005)
    finally:
        jax.profiler.stop_trace()
        obs.disable()
    host = _host_plane(str(tmp_path))
    names = [e[0] for e in host]
    assert names.count("plan.probe") == 1
    assert "plan.hidden" not in names
    assert [r["name"] for r in obs.spans()] == ["plan.probe"]
    rec = obs.spans()[0]
    obs.clear()

    # The profiler's span lies within the perf_counter record, with the
    # two clocks tied at `ref` (slack for reading them one after another).
    def realtime(t_perf):
        return ref[1] * 1e-9 + (t_perf - ref[0])

    _, start, end = host[names.index("plan.probe")]
    slack = 2e-4
    assert start >= realtime(rec["t0"]) - slack
    assert end <= realtime(rec["t0"] + rec["dur"]) + slack
    assert 0.02 <= end - start <= rec["dur"]


def test_chrome_trace_round_trips_json(tracer, tmp_path):
    with obs.span("a", cat="phase"):
        with obs.span("b"):
            pass
    path = tmp_path / "trace.json"
    obs.write_chrome_trace(str(path), process_name="test")
    doc = json.loads(path.read_text())
    assert doc["displayTimeUnit"] == "ms"
    evs = doc["traceEvents"]
    meta = [e for e in evs if e["ph"] == "M"]
    assert meta and meta[0]["args"]["name"] == "test"
    spans = {e["name"]: e for e in evs if e["ph"] == "X"}
    assert set(spans) == {"a", "b"}
    # complete events: b nests inside a on the shared timeline
    assert spans["b"]["ts"] >= spans["a"]["ts"]
    assert spans["b"]["ts"] + spans["b"]["dur"] \
        <= spans["a"]["ts"] + spans["a"]["dur"] + 1e-3
    assert all(e["dur"] >= 0 for e in spans.values())


def test_clear_keeps_enabled_flag(tracer):
    with obs.span("x"):
        pass
    obs.clear()
    assert obs.enabled() and obs.spans() == []


# ------------------------------------------------------------- event log


def test_event_log_owner_scoping_and_counts():
    log = obs.EventLog()
    log.record("compile", "f", owner="A")
    log.record("compile", "g", owner="A", count=2)
    log.record("compile", "f", owner="B")
    log.record("capacity_grow", "f", owner="A")
    assert log.count(owner="A", kind="compile") == 3
    assert log.count(owner="B") == 1
    assert log.counters(owner="A") == {"compile": 3, "capacity_grow": 1}
    log.clear(owner="A")
    assert log.count(owner="A") == 0 and log.count(owner="B") == 1


def test_log_compiles_detects_jit_cache_growth():
    import jax
    import jax.numpy as jnp

    fn = jax.jit(lambda v: v * 2)
    log_before = obs.log.count(owner="test_obs")
    keys = []
    _, grew = obs.log_compiles(
        "double", fn, jnp.ones(4),
        key=lambda: keys.append("k") or "k", site="here",
        owner="test_obs")
    assert grew and keys == ["k"]  # lazy key materialized on compile
    _, grew = obs.log_compiles(
        "double", fn, jnp.ones(4),
        key=lambda: keys.append("k2"), owner="test_obs")
    assert not grew and keys == ["k"]  # warm call: no event, no key
    evs = obs.log.events(owner="test_obs")
    assert len(evs) - log_before == 1
    assert evs[-1]["fn"] == "double" and evs[-1]["wall_ms"] > 0


# ------------------------------------------------------------ BenchReport


def test_bench_report_schema_and_json_safety(tmp_path):
    rep = obs.bench_report(
        "demo",
        config=dict(n=10),
        metrics=dict(bad=float("inf"), arr=np.float32(1.5)),
        phases={"a": np.float64(2.0), "b": 1},
        counters={"compiles": np.int64(3)})
    assert rep["schema"] == "repro.bench/1"
    assert isinstance(rep["phases"]["a"], float)
    assert isinstance(rep["counters"]["compiles"], int)
    obs.validate_report(rep)
    path = tmp_path / "r.json"
    obs.write_report(str(path), rep)
    doc = json.loads(path.read_text())  # strict: rejects NaN/Inf tokens
    assert doc["metrics"]["bad"] is None
    assert doc["metrics"]["arr"] == 1.5
    assert obs.phase_coverage(rep, 4.0) == pytest.approx(0.75)


def test_bench_report_validation_rejects_drift():
    good = obs.bench_report("demo", config={}, metrics={},
                            phases={}, counters={})
    for breakage in (
            lambda r: r.update(schema="repro.bench/2"),
            lambda r: r.update(bench=""),
            lambda r: r.pop("counters"),
            lambda r: r["phases"].update(a=float("nan")),
            lambda r: r["phases"].update(a=-1.0),
            lambda r: r["phases"].update(a=True),
            lambda r: r["counters"].update(c=1.5),
    ):
        rep = json.loads(json.dumps(obs.json_safe(good)))
        breakage(rep)
        with pytest.raises(ValueError):
            obs.validate_report(rep)
    with pytest.raises(ValueError):
        obs.bench_report("demo", config={}, metrics={},
                         phases={"a": "fast"}, counters={})


# ------------------------------------------------------------- occupancy


def _plan(n=400, **kw):
    from repro.core.api import TreecodeConfig, TreecodeSolver

    rng = np.random.default_rng(7)
    x = rng.uniform(-1, 1, (n, 3)).astype(np.float32)
    cfg = dict(theta=0.7, degree=3, leaf_size=32)
    cfg.update(kw)
    return TreecodeSolver(TreecodeConfig(**cfg)).plan(x), x


def test_static_occupancy_hand_counted():
    plan, _ = _plan()
    occ = plan.stats()["occupancy"]
    arrays = plan.inner.arrays
    tgt = np.asarray(arrays["tgt_batched"])
    slots = int(np.prod(tgt.shape[:-1]))
    assert occ["target_slots"] == slots
    assert occ["target_slot_occupancy"] == pytest.approx(400 / slots)
    assert occ["target_slot_occupancy"] == pytest.approx(
        float(np.asarray(arrays["tgt_mask"]).mean()))
    ai = np.asarray(arrays["approx_idx"])
    assert occ["approx_lane_occupancy"] == pytest.approx(
        (ai >= 0).sum() / ai.size)
    di = np.asarray(arrays["direct_idx"])
    assert occ["direct_lane_occupancy"] == pytest.approx(
        (di >= 0).sum() / di.size)
    assert all(0.0 <= v <= 1.0 for k, v in occ.items()
               if k.endswith("occupancy"))


def test_device_occupancy_counters_match_hand_count():
    from repro.core.space import FreeSpace
    from repro.obs import occupancy_counters

    plan, _ = _plan()
    arrays = plan.inner.arrays
    occ = {k: float(v) for k, v in occupancy_counters(
        arrays, theta=0.7, space=FreeSpace()).items()}
    ai = np.asarray(arrays["approx_idx"])
    di = np.asarray(arrays["direct_idx"])
    assert occ["target_slot_occupancy"] == pytest.approx(
        float(np.asarray(arrays["tgt_mask"]).astype(np.float32).mean()))
    assert occ["approx_lane_occupancy"] == pytest.approx(
        (ai >= 0).sum() / ai.size)
    waste = 1.0 - ((ai >= 0).sum() + (di >= 0).sum()) / (ai.size + di.size)
    assert occ["masked_lane_waste"] == pytest.approx(waste, abs=1e-6)
    assert "skin_pairs" not in occ  # skin=0: no skin-routing counters


def test_device_occupancy_skin_rates_consistent():
    from repro.core.space import FreeSpace
    from repro.obs import occupancy_counters

    plan, _ = _plan(skin=0.1)
    arrays = plan.inner.arrays
    occ = {k: float(v) for k, v in occupancy_counters(
        arrays, theta=0.7, space=FreeSpace(), skin=0.1).items()}
    skin_slot = (np.asarray(arrays["approx_skin"]) != 0) \
        & (np.asarray(arrays["approx_idx"]) >= 0)
    assert occ["skin_pairs"] == skin_slot.sum()
    if occ["skin_pairs"]:
        assert occ["skin_accept_rate"] + occ["skin_demote_rate"] \
            == pytest.approx(1.0, abs=1e-6)
    # at build positions the skin band is exactly the set the tight MAC
    # rejected (passed only the skin-loosened gate): all demoted to
    # direct until the geometry drifts apart
    if occ["skin_pairs"]:
        assert occ["skin_demote_rate"] == pytest.approx(1.0)


# -------------------------------------------- engine/serve event parity


def test_simulation_compiles_derived_from_event_log():
    from repro.dynamics import Simulation

    plan, x = _plan(n=300, skin=0.05)
    q = np.random.default_rng(3).uniform(-1, 1, 300).astype(np.float32)
    sim = Simulation(plan, q, dt=1e-4, refit_interval=4)
    for _ in range(6):
        sim.step()
    s = sim.stats()
    # event log == legacy cache-size sum == the documented 3 closures
    assert s["compiles"] == s["compiles_cache"] == 3
    assert s["retraces"] == 0
    assert obs.log.count(owner=sim.obs_owner) == 3
    sites = {e["site"] for e in obs.log.events(owner=sim.obs_owner)}
    assert "Simulation.__init__" in sites and "Simulation.step" in sites


def test_serve_stats_derived_from_event_log():
    from repro.core.api import TreecodeConfig
    from repro.serve import ServeFrontend

    rng = np.random.default_rng(5)
    fe = ServeFrontend(TreecodeConfig(degree=2, leaf_size=16, theta=0.7,
                                      backend="xla"),
                       max_batch=4, flush_deadline=10.0)
    futs = [fe.submit(rng.random((12, 3)), rng.standard_normal(12))
            for _ in range(4)]
    fe.flush()
    [f.result() for f in futs]
    s = fe.stats()
    # derived counters match the lockstep legacy attributes
    assert s["compiles"] == fe.compiles >= 1
    assert s["retraces"] == fe.retraces == 0
    assert s["capacity_growths"] == s["capacity_grows"] \
        == fe.capacity_grows
    evs = obs.log.events(owner=fe.obs_owner)
    assert sum(e["count"] for e in evs if e["kind"] == "compile") \
        == s["compiles"]
    assert all(e["site"] == "ServeFrontend._flush_bucket" for e in evs)
