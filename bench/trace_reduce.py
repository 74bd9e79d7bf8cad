"""From a profiler trace to device metrics: busy and idle time, kernel
time, the costliest operations and the longest idle gaps.

The trace is JAX's own (`jax.profiler.start_trace`); `load_profile`
reads its `.xplane.pb` with `jax.profiler.ProfileData`. Device operations
come from each device plane's "XLA Ops" line; host spans are the
benchmark's own `jax.profiler.TraceAnnotation`s, whose names start with
``bench.``, on the host plane. `reduce_trace` is plain arithmetic on those
events, so it can be checked on a made-up event list.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

WINDOW_SPAN = "bench.window"
_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
_OPS_LINES = ("XLA Ops",)
# Words that mark a Pallas (Mosaic) kernel in an operation's name or
# metadata.
_PALLAS_WORDS = ("tpu_custom_call", "custom-call", "custom_call", "pallas",
                 "mosaic")


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start: float          # seconds, on the trace's clock
    end: float
    meta: str = ""        # the operation's metadata, as one string


def is_pallas(ev: Event) -> bool:
    text = f"{ev.name} {ev.meta}".lower()
    return any(w in text for w in _PALLAS_WORDS)


def _clip(ev: Event, lo: float, hi: float) -> float:
    return max(0.0, min(ev.end, hi) - max(ev.start, lo))


def busy_intervals(events: List[Event], lo: float,
                   hi: float) -> List[Tuple[float, float]]:
    """The union of the events' intervals inside [lo, hi], merged."""
    spans = sorted((max(e.start, lo), min(e.end, hi)) for e in events
                   if e.end > lo and e.start < hi)
    out: List[Tuple[float, float]] = []
    for s, e in spans:
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def idle_gaps(busy: List[Tuple[float, float]], lo: float,
              hi: float) -> List[Tuple[float, float]]:
    edges = [lo] + [t for iv in busy for t in iv] + [hi]
    return [(a, b) for a, b in zip(edges[::2], edges[1::2]) if b > a]


def host_label(spans: List[Event], t: float) -> str:
    """The innermost host span open at time t."""
    open_ = [s for s in spans if s.start <= t <= s.end]
    if not open_:
        return "outside bench spans"
    return max(open_, key=lambda s: s.start).name


@dataclasses.dataclass
class TraceSummary:
    window_s: float
    busy_s: float                  # mean over devices
    devices: int
    kernel_s: float                # Pallas kernels, mean over devices
    device_ops: List[Tuple[str, float]]
    idle_gaps: List[Tuple[str, float]]

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s


def reduce_trace(device_events: Dict[int, List[Event]],
                 host_spans: List[Event], window: Tuple[float, float],
                 top: int = 10) -> Optional[TraceSummary]:
    """Reduce the window [lo, hi] of a trace; None without device ops."""
    lo, hi = window
    devs = {d: evs for d, evs in device_events.items() if evs}
    if not devs or hi <= lo:
        return None
    nd = len(devs)
    busy = kernel = 0.0
    by_op: Dict[str, float] = defaultdict(float)
    gaps: List[Tuple[str, float]] = []
    for d in sorted(devs):
        evs = devs[d]
        merged = busy_intervals(evs, lo, hi)
        busy += sum(e - s for s, e in merged)
        for ev in evs:
            t = _clip(ev, lo, hi)
            if t <= 0.0:
                continue
            by_op[ev.name] += t / nd
            if is_pallas(ev):
                kernel += t
        gaps += [(host_label(host_spans, 0.5 * (a + b)), b - a)
                 for a, b in idle_gaps(merged, lo, hi)]
    ops = sorted(by_op.items(), key=lambda kv: -kv[1])[:top]
    gaps = sorted(gaps, key=lambda g: -g[1])[:top]
    return TraceSummary(window_s=hi - lo, busy_s=busy / nd, devices=nd,
                        kernel_s=kernel / nd, device_ops=ops,
                        idle_gaps=gaps)


def load_profile(log_dir: str):
    """(device events by device id, bench host spans) of the newest trace
    under `log_dir`."""
    from jax.profiler import ProfileData

    files = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        return {}, []
    data = ProfileData.from_file(files[-1])
    devices: Dict[int, List[Event]] = {}
    host: List[Event] = []
    for plane in data.planes:
        m = _DEVICE_PLANE.match(plane.name)
        if m:
            evs = devices.setdefault(int(m.group(1)), [])
            for line in plane.lines:
                if line.name in _OPS_LINES:
                    evs.extend(_event(e, meta=True) for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend(_event(e) for e in line.events
                            if e.name.startswith("bench."))
    return devices, host


def _event(e, meta: bool = False) -> Event:
    text = ""
    if meta:
        text = " ".join(str(v) for _, v in e.stats if isinstance(v, str))
    return Event(e.name, e.start_ns * 1e-9,
                 (e.start_ns + e.duration_ns) * 1e-9, text)


def window_of(host_spans: List[Event]) -> Optional[Tuple[float, float]]:
    spans = [s for s in host_spans if s.name == WINDOW_SPAN]
    if not spans:
        return None
    return spans[-1].start, spans[-1].end
