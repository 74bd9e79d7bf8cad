"""Phase-span tracer: nested spans on the host clock and the profiler's.

The tracer is a process-global, thread-aware span recorder. Design
constraints (DESIGN.md §9):

- **Allocation-free when disabled.** ``span(name)`` returns a singleton
  null context manager when tracing is off — no object is allocated, no
  clock is read. Hot loops (the MD step) may therefore leave their span
  calls in place permanently.
- **Nesting by thread-local stack.** Spans carry a depth and a parent
  name so the Chrome-trace export reconstructs the tree; reentrancy
  (same span name nested inside itself) is allowed and preserved.
- **One clock with the device.** An enabled span also opens a
  ``jax.profiler.TraceAnnotation`` of its name, inside its own
  ``perf_counter`` record. While a profiler trace runs, the span lands
  on the profiler's host plane, on the clock the device planes are
  aligned to, so a trace reader can name device time and idle gaps by
  the program's phases. jax dispatch is async, so a span around a jitted
  call holds its dispatch, not its device time: device time is read from
  the device plane, and spans need not sync to be honest.

Spans are recorded into a bounded global buffer (oldest dropped past
``MAX_SPANS``) and exported either as ``phase_totals()`` (flat
``{name: ms}`` aggregation, the form benches embed in BenchReport) or as
Chrome-trace JSON (``chrome_trace()`` / ``write_chrome_trace()``), which
loads in ``chrome://tracing`` and Perfetto.
"""
from __future__ import annotations

import json
import os
import threading
import time
from typing import Any, Dict, List

from jax.profiler import TraceAnnotation

__all__ = [
    "span", "enable", "disable", "enabled", "clear",
    "spans", "phase_totals", "chrome_trace", "write_chrome_trace",
    "MAX_SPANS",
]

# Bounded so a long-running traced service cannot grow without limit;
# oldest spans are dropped once the buffer is full.
MAX_SPANS = 200_000

_enabled = False
_lock = threading.Lock()
_spans: List[Dict[str, Any]] = []
_dropped = 0
_tls = threading.local()


class _NullSpan:
    """Singleton no-op context manager returned while tracing is off."""
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NULL = _NullSpan()


class _Span:
    __slots__ = ("name", "cat", "_t0", "_depth", "_parent", "_note")

    def __init__(self, name: str, cat: str):
        self.name = name
        self.cat = cat

    def __enter__(self):
        stack = getattr(_tls, "stack", None)
        if stack is None:
            stack = _tls.stack = []
        self._depth = len(stack)
        self._parent = stack[-1].name if stack else None
        stack.append(self)
        self._t0 = time.perf_counter()
        # Opened inside the perf_counter record, so the profiler's span
        # lies within it.
        self._note = TraceAnnotation(self.name)
        self._note.__enter__()
        return self

    def __exit__(self, *exc):
        self._note.__exit__(*exc)
        t1 = time.perf_counter()
        _tls.stack.pop()
        rec = {
            "name": self.name,
            "cat": self.cat,
            "t0": self._t0,
            "dur": t1 - self._t0,
            "depth": self._depth,
            "parent": self._parent,
            "tid": threading.get_ident(),
        }
        global _dropped
        with _lock:
            if len(_spans) >= MAX_SPANS:
                del _spans[0: MAX_SPANS // 10]
                _dropped += MAX_SPANS // 10
            _spans.append(rec)
        return False


def span(name: str, cat: str = "phase"):
    """Open a phase span. Returns a no-op singleton when tracing is off.

    Usage::

        with obs.span("md.finish"):
            arrays = finish(...)
    """
    if not _enabled:
        return _NULL
    return _Span(name, cat)


def enable() -> None:
    """Turn span recording on (process-global)."""
    global _enabled
    _enabled = True


def disable() -> None:
    """Turn span recording off. Already-recorded spans are kept."""
    global _enabled
    _enabled = False


def enabled() -> bool:
    return _enabled


def clear() -> None:
    """Drop all recorded spans (does not change the enabled flag)."""
    global _dropped
    with _lock:
        _spans.clear()
        _dropped = 0


def spans() -> List[Dict[str, Any]]:
    """Snapshot of recorded spans (copies the list, not the records)."""
    with _lock:
        return list(_spans)


def phase_totals(prefix: str = "") -> Dict[str, float]:
    """Aggregate recorded spans into flat ``{name: total_ms}``.

    Only **top-level occurrences** of each name are summed: a span whose
    parent has the same name (direct recursion) is skipped so reentrant
    phases are not double-counted. Different names nest freely —
    ``plan.build`` deliberately includes its ``plan.tree_build`` child,
    mirroring the call tree. ``prefix`` filters by name prefix.
    """
    totals: Dict[str, float] = {}
    for rec in spans():
        name = rec["name"]
        if prefix and not name.startswith(prefix):
            continue
        if rec.get("parent") == name:
            continue
        totals[name] = totals.get(name, 0.0) + rec["dur"] * 1e3
    return totals


def chrome_trace(process_name: str = "repro") -> Dict[str, Any]:
    """Render recorded spans as a Chrome-trace / Perfetto JSON object.

    Complete events (``ph: "X"``) with microsecond timestamps relative
    to the earliest recorded span; loads directly in ``chrome://tracing``
    or https://ui.perfetto.dev.
    """
    recs = spans()
    t_base = min((r["t0"] for r in recs), default=0.0)
    events: List[Dict[str, Any]] = [{
        "name": "process_name", "ph": "M", "pid": os.getpid(), "tid": 0,
        "args": {"name": process_name},
    }]
    for r in recs:
        ev = {
            "name": r["name"],
            "cat": r["cat"],
            "ph": "X",
            "ts": (r["t0"] - t_base) * 1e6,
            "dur": r["dur"] * 1e6,
            "pid": os.getpid(),
            "tid": r["tid"],
        }
        events.append(ev)
    meta = {"displayTimeUnit": "ms", "traceEvents": events}
    if _dropped:
        meta["metadata"] = {"dropped_spans": _dropped}
    return meta


def write_chrome_trace(path: str, process_name: str = "repro") -> str:
    """Write ``chrome_trace()`` JSON to ``path``; returns the path."""
    with open(path, "w") as f:
        json.dump(chrome_trace(process_name), f)
    return path
