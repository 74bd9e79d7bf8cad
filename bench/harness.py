"""One run of one benchmark cell: set-up, the measured window, the
per-layer readings of a traced window, and the check against the
reference. Everything that belongs to one configuration, traffic mix,
cell or per-layer metric is data found by name:

    BENCHMARK.json             cells, metrics and their bounds
    bench/configs/<config>.json   the deployment, as run
    bench/traffic/<traffic>.json  the mix, read by bench/generator.py
    bench/limits/<cell>.json      the limits of the cell's comparison
    bench/metrics/<metric>.py     one reader per per-layer metric
    bench/peaks.json              the chips' published peaks

The result is one JSON line on standard output, last; the numbers
compared, each with its limit, are also the last lines on standard error.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import shutil
import sys
import tempfile
import time
from pathlib import Path
from typing import Optional

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


class NoChip(RuntimeError):
    """The machine lacks the accelerator the cell asks for."""


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    end_to_end: list          # BENCHMARK.json metric entries of this cell
    per_layer: list


def _for_cell(metrics: list, cell: str) -> list:
    return [m for m in metrics if cell in m.get("workloads", [cell])]


def load_cell(name: str, root: Path = ROOT) -> Cell:
    spec = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    w = cells[name]
    conf = {c["name"]: c for c in spec["configs"]}[w["config"]]
    bench = root / "bench"
    return Cell(
        name=name, chips=int(w["chips"]),
        config=json.loads((root / conf["file"]).read_text()),
        traffic=json.loads((bench / "traffic"
                            / f"{w['traffic']}.json").read_text()),
        limits=json.loads((bench / "limits" / f"{name}.json").read_text()),
        end_to_end=_for_cell(spec["end_to_end"], name),
        per_layer=_for_cell(spec["per_layer"], name))


def enable_compile_cache() -> None:
    """The program's persistent compilation cache (`.jax_cache/` in the
    checkout, or where ``JAX_COMPILATION_CACHE_DIR`` puts it), for every
    program, however quick to compile."""
    import jax
    from repro.launch.compile_cache import enable_compile_cache as enable

    enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


def load_peaks(kind: str) -> dict:
    table = json.loads((BENCH / "peaks.json").read_text())
    if kind not in table["devices"]:
        raise KeyError(f"device kind {kind!r} is not in bench/peaks.json")
    return table["devices"][kind]


def _reader(metric: str):
    path = BENCH / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + metric.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


@dataclasses.dataclass
class Context:
    """What a per-layer reader may read."""

    trace: Optional[object]       # trace_reduce.TraceSummary or None
    layer: dict                   # the traffic's spans and counters
    units: int
    window_s: float
    peaks: Optional[dict]
    traffic: object


class CompileCounter:
    """Counts JAX lowerings and backend compiles while `active`."""

    EVENTS = {"/jax/core/compile/jaxpr_to_mlir_module_duration": "lowerings",
              "/jax/core/compile/backend_compile_duration": "compiles"}

    def __init__(self):
        import jax

        self.counts = {"lowerings": 0, "compiles": 0}
        self.active = False
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, _secs, **_kw):
        if self.active and event in self.EVENTS:
            self.counts[self.EVENTS[event]] += 1


def device_info(devs) -> dict:
    return dict(platform=devs[0].platform, kind=devs[0].device_kind,
                count=len(devs))


def memory_peak(devs) -> Optional[int]:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devs]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, *,
             t_start: float, require_chip: bool = True,
             dump_ops: Optional[str] = None, control: bool = False,
             traffic_hook=None) -> dict:
    """Run one cell and return its result line (a dict).

    `control` puts the lower-precision control in the program's place
    for the check (bench/calibrate.py); `traffic_hook` sees the traffic
    before its set-up (the tests plant faults through it)."""
    import jax

    from bench import generator, trace_reduce

    devs = jax.devices()
    info = device_info(devs)
    if require_chip and (info["platform"] != "tpu"
                         or info["count"] < cell.chips):
        raise NoChip(f"{cell.name} needs {cell.chips} TPU chip(s); "
                     f"JAX found {info}")
    peaks = load_peaks(info["kind"]) if require_chip else None
    counter = CompileCounter()

    traffic = generator.make_traffic(cell.config, cell.traffic,
                                     cell.limits, seed)
    if traffic_hook is not None:
        traffic_hook(traffic)
    traffic.setup()
    setup_s = time.perf_counter() - t_start

    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    if trace:
        from repro import obs
        obs.clear()
        obs.enable()
        jax.profiler.start_trace(trace_dir, profiler_options=_profile_opts())
    counter.active = True
    units = 0
    with generator.annotate(trace_reduce.WINDOW_SPAN):
        t0 = time.perf_counter()
        while True:
            units += traffic.run_unit()
            if time.perf_counter() - t0 >= seconds:
                break
        window_s = time.perf_counter() - t0
    counter.active = False
    summary = None
    if trace:
        jax.profiler.stop_trace()
        obs.disable()
        devices, host = trace_reduce.load_profile(trace_dir)
        window = trace_reduce.window_of(host)
        if window is not None:
            summary = trace_reduce.reduce_trace(devices, host, window)
        if dump_ops:
            _dump_ops(devices, window, dump_ops)
        shutil.rmtree(trace_dir, ignore_errors=True)
    mem = memory_peak(devs[:cell.chips])

    metrics = {}
    e2e = traffic.end_to_end(window_s, units)
    e2e["setup_s"] = setup_s
    traffic.collect()
    if trace:
        ctx = Context(trace=summary, layer=traffic.layer(), units=units,
                      window_s=window_s, peaks=peaks, traffic=traffic)
        for m in cell.per_layer:
            value = _reader(m["name"])(ctx)
            if value is not None:
                metrics[m["name"]] = dict(value=float(value), unit=m["unit"])
    else:
        for m in cell.end_to_end:
            if m["name"] in e2e:
                metrics[m["name"]] = dict(value=float(e2e[m["name"]]),
                                          unit=m["unit"])
    print(json.dumps(dict(
        window=dict(units=units, unit=traffic.unit, window_s=window_s,
                    setup_s=setup_s, budget_grown=traffic.budget_grown,
                    **counter.counts,
                    **{k: v for k, v in traffic.layer().items()
                       if isinstance(v, (int, float))}))),
        file=sys.stderr, flush=True)

    checks = traffic.check(control=control)
    device = dict(info, memory_peak_bytes=mem)
    if trace and summary is not None:
        device.update(busy_s=summary.busy_s, window_s=summary.window_s)
    line = dict(correct=all(c.ok for c in checks), attempted=units,
                failed=int(traffic.failed), metrics=metrics, device=device)
    if trace and summary is not None:
        line["breakdown"] = dict(device_ops=[list(o) for o in
                                             summary.device_ops],
                                 idle_gaps=[list(g) for g in
                                            summary.idle_gaps])
    line["checks"] = {c.name: dict(value=c.value, limit=c.limit)
                      for c in checks}
    return line


def _profile_opts():
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    return opts


def _dump_ops(devices, window, path):
    """The device operations of a trace by name, with their total time:
    for reading by hand how a trace names its kernels."""
    out = {}
    for d, evs in devices.items():
        agg = {}
        for e in evs:
            a = agg.setdefault(e.name, [0, 0.0, e.meta[:300]])
            a[0] += 1
            a[1] += e.end - e.start
        out[str(d)] = sorted(([k] + v for k, v in agg.items()),
                             key=lambda r: -r[2])[:300]
    Path(path).parent.mkdir(parents=True, exist_ok=True)
    Path(path).write_text(json.dumps(dict(window=window, ops=out), indent=1))


def print_result(line: dict) -> None:
    print(json.dumps(line), flush=True)
    for name, c in line["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
