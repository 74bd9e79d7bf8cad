"""What the program's own instruments say about a traced window, for
the per-layer readers: the host planner's phases from its `repro.obs`
spans, the device time of a Pallas kernel it names by call site, and its
count of the work the `batch_cluster` kernels launch.

The harness turns the program's spans on for a traced window and keeps
their records (`repro.obs.spans()`, on the host's `perf_counter`) after
it. A program without these instruments gives nothing here, and the
readers then leave their metric out.
"""
from __future__ import annotations

import re
from typing import Dict, Optional


def plan_phases_s() -> Dict[str, float]:
    """Seconds of the host planner's phases in the window, from the
    program's span records, children included (`obs.phase_totals`):
    ``tree`` (`plan.tree_build`), ``lists`` (`plan.interaction_lists`),
    ``pack`` (`plan.pack`, and `plan.pad` less the `plan.copy` spans in
    it) and ``copy`` (every `plan.copy`). A phase whose spans the window
    lacks is left out; so are ``pack`` and ``copy`` in a window without
    `plan.copy` spans, since a program without them times its uploads
    inside `plan.pack`."""
    from repro import obs

    ms = obs.phase_totals("plan.")
    out = {k: ms[name] * 1e-3 for k, name in
           (("tree", "plan.tree_build"), ("lists", "plan.interaction_lists"))
           if name in ms}
    if "plan.copy" in ms:
        copy_in_pad = sum(r["dur"] for r in obs.spans()
                          if r["name"] == "plan.copy"
                          and r["parent"] == "plan.pad")
        out["pack"] = (ms.get("plan.pack", 0.0) + ms.get("plan.pad", 0.0)) \
            * 1e-3 - copy_in_pad
        out["copy"] = ms["plan.copy"] * 1e-3
    return out


def _op(kernel: str):
    # The trace names a kernel's operations ``%<kernel>.<n> = ...``.
    return re.compile(rf"%?{re.escape(kernel)}(\.\d+)?\b")


def kernel_device_s(trace, kernel: str) -> Optional[float]:
    """Device time in the traced window of the operations of the Pallas
    kernel named `kernel`, over every operation of the window (the
    reduction's `op_totals`): all its calls, and a loop that holds it not
    counted again. None where the window has none."""
    times = [t for name, t in trace.op_totals.items()
             if _op(kernel).match(name)]
    return sum(times) if times else None


def eval_kernel_work(traffic) -> Optional[dict]:
    """The program's `kernel_work` count of an `eval` window's plan. The
    window's plan is freed before the readers run, so the same points are
    planned again under the same configuration: the count is a function
    of the plan alone."""
    from bench import generator

    plan = generator.program_plan(traffic.config, traffic.x)
    return plan.stats().get("kernel_work")
