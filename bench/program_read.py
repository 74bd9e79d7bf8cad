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

# How many of the costliest operations `trace_reduce.reduce_trace` lists
# (its `top`).
LISTED_OPS = 10
# The program's Pallas kernels, by the names its kernel sites give them.
PROGRAM_KERNELS = ("bltc_direct", "bltc_approx", "modified_charges")


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
    kernel named `kernel`, among the costliest operations the reduction
    lists. None where none is listed, or where the list may have dropped
    some: it is full, and the window's Pallas time exceeds what the
    program's named kernels in it account for by more than 1%."""
    times = [t for name, t in trace.device_ops if _op(kernel).match(name)]
    if not times:
        return None
    if len(trace.device_ops) >= LISTED_OPS:
        named = sum(t for name, t in trace.device_ops
                    if any(_op(k).match(name) for k in PROGRAM_KERNELS))
        if trace.kernel_s - named > 0.01 * trace.kernel_s:
            return None
    return sum(times)


def eval_kernel_work(traffic) -> Optional[dict]:
    """The program's `kernel_work` count of an `eval` window's plan. The
    window's plan is freed before the readers run, so the same points are
    planned again under the same configuration: the count is a function
    of the plan alone."""
    from bench import generator
    from repro.core.api import TreecodeSolver

    plan = TreecodeSolver(generator.treecode_config(traffic.config)).plan(
        traffic.x, nranks=1)
    return plan.stats().get("kernel_work")
