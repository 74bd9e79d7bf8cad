"""`repro.obs` — observability: phase spans, compile events, occupancy.

Four small pieces, one measurement substrate (DESIGN.md §9):

- :mod:`repro.obs.trace` — nested phase-span tracer on the host clock
  and the profiler's, with Chrome-trace export; allocation-free no-ops
  while disabled.
- :mod:`repro.obs.events` — global compile/retrace event log; every jit
  compile records its static key, call site, and wall time.
- :mod:`repro.obs.occupancy` — device-side occupancy counters (fused
  into existing passes) + host-side padded-vs-real utilization.
- :mod:`repro.obs.report` — the ``repro.bench/1`` BenchReport schema
  all ``benchmarks/*.py`` emit, with the shared validator.

Typical use::

    from repro import obs
    obs.enable()
    ...                        # run the instrumented workload
    obs.write_chrome_trace("trace.json")
    print(obs.phase_totals())
"""
from repro.obs.trace import (  # noqa: F401
    span, enable, disable, enabled, clear,
    spans, phase_totals, chrome_trace, write_chrome_trace,
)
from repro.obs.events import (  # noqa: F401
    EventLog, log, log_compiles, record, cache_size,
)
from repro.obs.occupancy import (  # noqa: F401
    occupancy_counters, static_occupancy,
)
from repro.obs.report import (  # noqa: F401
    SCHEMA, bench_report, validate_report, write_report,
    phase_coverage, json_safe,
)

__all__ = [
    "span", "enable", "disable", "enabled", "clear",
    "spans", "phase_totals", "chrome_trace", "write_chrome_trace",
    "EventLog", "log", "log_compiles", "record", "cache_size",
    "occupancy_counters", "static_occupancy",
    "SCHEMA", "bench_report", "validate_report", "write_report",
    "phase_coverage", "json_safe",
]
