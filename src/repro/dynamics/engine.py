"""Device-resident MD engine over treecode plans: refit when you can,
rebuild when you must, never retrace if the capacities hold.

One `Simulation.step()` is:

    1. `advance`   (jit): integrator pre-step — positions move to the
       force-evaluation point; returns the max particle displacement
       since the LAST force evaluation (one scalar leaves the device per
       step; minimum-image under periodic spaces).
    2. host decision: REFIT while that per-step drift fits BOTH live
       budgets refreshed from the previous refit's boxes (drift-budget
       v2, DESIGN.md §4):

           2*sqrt(3)*(1+theta) * drift < safety * theta_slack   and
           4 * drift                   < safety * fold_slack

       and the max interval K has not elapsed; otherwise REBUILD the
       tree on the host (the paper's CPU setup phase) — re-padded into
       the plan's fixed `Capacities`, so the compiled step is almost
       always reused. Verlet-skin pairs (plans built with ``skin > 0``)
       are runtime gated inside the executors and never constrain the
       budgets, which floors the drift budget at ``skin/2``.
    3. `finish`    (jit): device tree refit -> on-device slack refresh
       (exact margins from the refitted boxes, min-reduced across ranks
       for sharded plans) -> treecode forces (custom-VJP gradients) ->
       integrator post-step. Forces never visit the host.

    Rebuild count  <= steps/K + (drift-triggered rebuilds, rare at MD dt
                      because the budgets are refreshed every step)
    Retraces       == 0 unless a capacity grows (geometric, so O(log) in
                      the worst case) — on BOTH strategies: sharded plans
                      are budget-padded too (`ShardedCapacities`), so
                      their rebuilds reuse the compiled SPMD step.

`stats()` reports refit/rebuild/retrace counters and all three drift
budgets (theta / fold / skin); `run(record_every=)` logs
energy/momentum/temperature via one fused device reduction; the
`Checkpointer` integration snapshots (x, v, f, phi, key) atomically and
restores across processes.
"""
from __future__ import annotations

import math
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint.store import Checkpointer
from repro.core.interaction import (fold_drift_rate, theta_drift_rate,
                                    scaled_mac_slack as _scaled_slack)
from repro.dynamics import diagnostics as diag
from repro.dynamics.integrators import (MDState, get_integrator,
                                        initial_state)
from repro.dynamics.refit import make_adapter, max_drift
from repro.lint import runtime as _lint_runtime
from repro.obs import events as _events
from repro.obs import trace as _trace
from repro.obs.occupancy import occupancy_counters as _occ_counters

_REBUILD_POLICIES = ("auto", "always", "never")


def _cache_size(fn) -> int:
    try:
        return fn._cache_size()
    except Exception:
        return 0


class Simulation:
    """Time integration of N interacting particles with treecode forces.

    Args:
      plan: a `TreecodeSolver` execution plan built over the particle
        positions with targets == sources (`SingleDevicePlan` or
        `ShardedPlan`). Single-device plans without capacity padding are
        transparently re-padded (`capacities="auto"`) so replans reuse
        compiled executables; sharded plans are always built
        capacity-padded (`ShardedCapacities`) and need no re-pad.
      charges: (N,) source charges q_i (also the force weights).
      dt: time step.
      velocities: (N, 3) initial velocities (default zero).
      masses: scalar or (N,) particle masses.
      integrator: name ("velocity_verlet" | "leapfrog" | "langevin") or
        an `Integrator`; `integrator_params` forwards factory kwargs
        (e.g. friction/temperature for langevin).
      refit_interval: K — max steps between host tree rebuilds. With the
        v2 refreshed budgets the per-step drift trigger alone guards MAC
        validity, so K is a coarse safety net (and the explicit fallback
        cadence when a slack is NaN); the default is correspondingly
        loose.
      drift_safety: fraction of the refreshed slack budgets to spend
        before a drift-triggered rebuild (1.0 = the provable bound).
      rebuild: "auto" (drift trigger + interval), "always" (every step,
        the naive baseline), "never" (trust refit indefinitely —
        exact-direct configs or testing).
      checkpointer/checkpoint_every: trajectory snapshots via the
        fault-tolerant `Checkpointer` (atomic, async, elastic).
      profile: fuse device-side occupancy counters (`repro.obs`) into
        the finish pass as an extra aux output — skin accept/demote
        rates and masked-lane waste appear under
        ``stats()["occupancy"]``. Changes the finish closure's output
        pytree, so flipping it mid-run would retrace; set at
        construction. No extra kernel launches either way.
      async_replan: double-buffer tree rebuilds (device build backend
        only, rebuild="auto"). When a drift budget is
        `dispatch_fraction` spent — or the interval is one step from
        elapsing — the engine DISPATCHES a shadow device build over the
        current (wrapped) positions without blocking, keeps refitting on
        the live plan, and swaps the shadow in at the next step boundary
        (the `plan_swap` obs span). jax's async dispatch overlaps the
        shadow build with the live step's refit+force work — no threads.
        The swap counts as a rebuild with the cause recorded at dispatch
        time, so the stats partitions are unchanged; `stats()` splits
        the host time blocked on builds (``rebuild_wait_ms``) from the
        end-to-end build time (``rebuild_total_ms``).
      dispatch_fraction: fraction of a drift budget consumed before a
        shadow build is dispatched (the remaining fraction is the drift
        headroom that keeps the live plan valid while the shadow is in
        flight).
    """

    def __init__(self, plan, charges, *, dt: float,
                 velocities=None, masses=1.0,
                 integrator="velocity_verlet",
                 integrator_params: Optional[dict] = None,
                 seed: int = 0,
                 refit_interval: int = 100,
                 drift_safety: float = 1.0,
                 rebuild: str = "auto",
                 checkpointer: Optional[Checkpointer] = None,
                 checkpoint_every: int = 0,
                 profile: bool = False,
                 async_replan: bool = False,
                 dispatch_fraction: float = 0.5):
        if rebuild not in _REBUILD_POLICIES:
            raise ValueError(f"rebuild must be one of {_REBUILD_POLICIES}")
        if refit_interval < 1:
            raise ValueError("refit_interval must be >= 1")
        self.debug_nans = _lint_runtime.enable_debug_nans_if_requested()
        self.dt = float(dt)
        self.refit_interval = int(refit_interval)
        self.drift_safety = float(drift_safety)
        self.rebuild_policy = rebuild
        self.checkpointer = checkpointer
        self.checkpoint_every = int(checkpoint_every)
        self.profile = bool(profile)
        # Owner token scoping this engine's entries in the global
        # compile/retrace event log (repro.obs.events).
        self.obs_owner = _events.owner_token("Simulation")
        self._occ_dev = None

        self.adapter = make_adapter(plan)
        if getattr(plan, "capacities", "n/a") is None:
            # Single-device plan without capacity padding: re-pad now so
            # every later rebuild is shape-stable.
            plan = plan.replan(self.adapter.positions(), capacities="auto")
            self.adapter = make_adapter(plan)
        self.plan = self.adapter.plan
        self.async_replan = bool(async_replan)
        self.dispatch_fraction = float(dispatch_fraction)
        if self.async_replan:
            if rebuild != "auto":
                raise ValueError(
                    "async_replan requires rebuild='auto' (the shadow "
                    "dispatch rides the drift/interval triggers)")
            if not self.adapter.supports_async_rebuild:
                raise ValueError(
                    "async_replan requires a capacity-padded device-"
                    "backend plan (build_backend='device')")
            if not 0.0 < self.dispatch_fraction <= 1.0:
                raise ValueError("dispatch_fraction must be in (0, 1]")
        # Double-buffer state: the in-flight shadow build (an opaque
        # adapter handle), the rebuild cause recorded at dispatch time,
        # and the host milliseconds the dispatch call itself took.
        self._pending = None
        self._pending_cause = None
        self._pending_dispatch_ms = 0.0
        dtype = np.dtype(self.plan.dtype)

        n = self.plan.num_targets
        if self.plan.num_sources != n:
            raise ValueError("dynamics requires targets == sources")
        q = np.asarray(charges, dtype)
        if q.shape != (n,):
            raise ValueError(f"charges must be ({n},), got {q.shape}")
        self.charges = jnp.asarray(q)
        m = np.asarray(masses, dtype)
        self.masses = jnp.asarray(m)
        inv_m = jnp.asarray(1.0 / m)
        self._inv_m = inv_m[:, None] if inv_m.ndim == 1 else inv_m

        self.integrator = get_integrator(integrator,
                                         **(integrator_params or {}))
        # The space the plan was built in. Periodic boxes: integrate
        # UNWRAPPED coordinates between host rebuilds (minimum-image
        # kernels make out-of-cell coordinates exact, and continuous
        # positions keep refitted cluster boxes tight); wrap back into
        # the primary cell at every rebuild, where the fresh tree splits
        # boundary-straddling clusters by construction.
        self.space = self.plan.config.space
        # Jitted so the box lengths are compile-time constants: an eager
        # wrap would upload them on every rebuild (an implicit transfer).
        self._wrap = jax.jit(self.space.wrap)
        self.state: MDState = self.adapter.commit(initial_state(
            self.adapter.positions(), velocities, seed=seed, dtype=dtype))
        # Same placement as the state, so diagnostics of a sharded run
        # move nothing between devices.
        self.charges, self.masses = self.adapter.commit(
            (self.charges, self.masses))
        self._arrays = self.adapter.arrays
        # Reference for the per-step drift scalar: the positions of the
        # LAST force evaluation (where the budgets were refreshed from).
        self._x_eval_ref = self.state.x
        self._theta = float(self.plan.config.theta)
        self._skin = float(self.adapter.skin)
        # Live budgets: build-time values until the first finish/init
        # refresh replaces them with device-computed exact margins.
        self._theta_slack = float(self.adapter.theta_slack)
        self._fold_slack = float(self.adapter.fold_slack)
        self._slack_dev = None  # (theta, fold) device scalars, lazy-read
        self._slack_fallback = False  # NaN slack seen: interval cadence

        # Counters (stats() surface). Rebuild causes PARTITION the
        # rebuild count: rebuilds == drift + interval + forced.
        self.steps = 0
        self.refits = 0
        self.rebuilds = 0
        self.rebuilds_drift = 0
        self.rebuilds_interval = 0
        self.rebuilds_forced = 0
        # Backend partition of the same count: every rebuild is either a
        # host build or a device (devtree) build.
        self.rebuilds_host = 0
        self.rebuilds_device = 0
        # Rebuild wall-time split (ms): `total` is end-to-end build time
        # (sync rebuild wall, or async dispatch + commit wall); `wait`
        # is the part the host actually spent BLOCKED (for sync rebuilds
        # the two coincide; async hides total - wait behind live steps).
        self.rebuild_total_ms = 0.0
        self.rebuild_wait_ms = 0.0
        self.plan_swaps = 0
        self.force_evals = 0
        self.capacity_growths = 0
        self._steps_since_rebuild = 0
        self._last_drift = 0.0
        self._baseline_compiles: Optional[int] = None

        self._make_executables()
        self._finish_history_compiles = 0  # compiles in retired finish fns

        # Initial force evaluation (device): seeds f/phi for the first
        # kick and for step-0 diagnostics, plus the refreshed budgets.
        self._arrays, self.state, self._slack_dev, self._occ_dev = \
            self._call_logged("init_forces", self._init_forces,
                              "Simulation.__init__",
                              self._arrays, self.state)
        self.adapter.sync_arrays(self._arrays)
        self.force_evals += 1
        self.log = diag.EnergyLog()

    # ------------------------------------------------------------------
    # jitted executables
    # ------------------------------------------------------------------

    def _make_executables(self):
        integ, dt, inv_m = self.integrator, self.dt, self._inv_m
        space = self.space

        def advance(state, x_eval_ref):
            s1 = integ.pre(state, dt, inv_m)
            # Per-step drift since the last force evaluation (where the
            # budgets were refreshed). Minimum-image under periodic
            # spaces: a particle wrapped at the last rebuild must not
            # register a spurious box-length displacement.
            return s1, max_drift(s1.x, x_eval_ref, space)

        self._advance = jax.jit(advance)
        self._make_force_closures()

    def _make_force_closures(self):
        integ, dt, inv_m = self.integrator, self.dt, self._inv_m
        adapter, q = self.adapter, self.charges
        force = adapter.force_fn()
        slack = adapter.slack_fn()
        # Occupancy counters ride the finish pass as an aux output (no
        # extra launches; DESIGN.md §9). `occ` is {} (a leafless pytree)
        # when profiling is off, so the closure's trace signature — and
        # the compile counters tests assert — are independent of the
        # flag's value at any given construction. Skin-gate rates need
        # the unstacked batch-box layout, so they are single-device only.
        profile, theta, space = self.profile, self._theta, self.space
        occ_skin = self._skin if getattr(self.plan, "nranks", 1) == 1 else 0.0

        def occ_of(arrays):
            if not profile:
                return {}
            return _occ_counters(arrays, theta=theta, space=space,
                                 skin=occ_skin)

        def finish(arrays, state):
            arrays = adapter.refit(arrays, state.x)
            slacks = slack(arrays)  # on-device refresh from refit boxes
            phi, f = force(arrays, state.x, q, q)
            return (arrays, integ.post(state, phi, f, dt, inv_m), slacks,
                    occ_of(arrays))

        def init_forces(arrays, state):
            arrays = adapter.refit(arrays, state.x)
            slacks = slack(arrays)
            phi, f = force(arrays, state.x, q, q)
            return (arrays, state._replace(phi=phi, f=f), slacks,
                    occ_of(arrays))

        self._finish = jax.jit(finish)
        self._init_forces = jax.jit(init_forces)

    def _remake_finish(self):
        """A budget-growing sharded rebuild re-closes over the grown
        plan's new SPMD executable; retire the force-dependent jits
        (their compiles keep counting toward retraces — the `advance`
        jit is plan-independent and survives)."""
        self._finish_history_compiles += _cache_size(self._finish)
        self._finish_history_compiles += _cache_size(self._init_forces)
        self._make_force_closures()

    def _compile_key(self):
        """Static cache key recorded with compile events: the capacity
        budget (array shapes derive from it), lazily materialized."""
        caps = getattr(self.plan, "capacities", None)
        return repr(caps) if caps is not None else "unpadded"

    def _call_logged(self, label, fn, site, *args):
        """Call a jitted executable; log a compile event if its cache
        grew (key + call site + wall time; `repro.obs.events`)."""
        out, _ = _events.log_compiles(label, fn, *args,
                                      key=self._compile_key, site=site,
                                      owner=self.obs_owner)
        return out

    def _total_compiles(self) -> int:
        """Legacy jit-cache sum — kept as the cross-check for the event
        log (`compiles`); the tier-1 suite asserts they agree."""
        return (_cache_size(self._advance) + _cache_size(self._finish)
                + _cache_size(self._init_forces)
                + self._finish_history_compiles)

    @property
    def compiles(self) -> int:
        """Total jit compilations of the step executables, from the
        compile/retrace event log (the single source of truth; every
        executable call site routes through `_call_logged`)."""
        return _events.log.count(owner=self.obs_owner)

    @property
    def retraces(self) -> int:
        """Compilations beyond the ones paid by the end of step 1."""
        if self._baseline_compiles is None:
            return 0
        return max(0, self.compiles - self._baseline_compiles)

    # ------------------------------------------------------------------
    # stepping
    # ------------------------------------------------------------------

    def _refresh_budgets(self) -> None:
        """Pull the slacks computed by the last finish/init pass (exact
        margins from the refitted boxes) onto the host."""
        if self._slack_dev is not None:
            # one explicit d2h for both scalars (indexing a device array
            # under float() would launch a slice kernel per scalar and
            # hide the transfer from jax's transfer guard)
            slack = jax.device_get(self._slack_dev)
            self._theta_slack = float(slack[0])
            self._fold_slack = float(slack[1])
            self._slack_dev = None

    def _drift_exceeds_budget(self, drift: float) -> bool:
        """True when the per-step drift is NOT provably within budget.

        Validity bound (DESIGN.md §4): refit remains MAC-valid while,
        STRICTLY,

            2*sqrt(3)*(1 + theta) * drift < safety * theta_slack   and
            4 * drift                     < safety * fold_slack

        so this fires on ``>=`` of either budget — equality is not
        provably valid. +inf slack means the category has no safe approx
        pairs (no budget to exhaust: refits are exact). A NaN slack
        (possible when a degenerate build leaves the refresh with no
        information) means validity is UNKNOWN: instead of silently
        treating it as "no approx work", the engine falls back to
        rebuilding on the interval cadence explicitly (`slack_fallback`
        in `stats()`).
        """
        ts, fs = self._theta_slack, self._fold_slack
        if math.isnan(ts) or math.isnan(fs):
            self._slack_fallback = True
            return False  # unknown validity: interval cadence rebuilds
        exceeded = False
        if math.isfinite(ts):
            lhs = theta_drift_rate(self._theta) * drift
            exceeded |= lhs >= self.drift_safety * ts
        if math.isfinite(fs):
            exceeded |= fold_drift_rate() * drift >= self.drift_safety * fs
        return exceeded

    # ------------------------------------------------------------------
    # double-buffered replan (async_replan=True)
    # ------------------------------------------------------------------

    def _dispatch_cause(self, drift: float) -> Optional[str]:
        """Soft-trigger test: which rebuild cause (if any) warrants
        dispatching a shadow build NOW, while the live plan still has
        budget left to cover the in-flight window. Drift soft-fires at
        `dispatch_fraction` of either refreshed budget (NaN slack never
        soft-fires — the interval fallback owns that regime); the
        interval soft-fires one step before the hard K-step cadence."""
        ts, fs = self._theta_slack, self._fold_slack
        if not (math.isnan(ts) or math.isnan(fs)):
            frac = self.dispatch_fraction * self.drift_safety
            if math.isfinite(ts) and \
                    theta_drift_rate(self._theta) * drift >= frac * ts:
                return "drift"
            if math.isfinite(fs) and \
                    fold_drift_rate() * drift >= frac * fs:
                return "drift"
        if self._steps_since_rebuild + 1 >= self.refit_interval - 1:
            return "interval"
        return None

    def _dispatch_shadow(self, s1, cause: str) -> None:
        """Enqueue the shadow device build over the CURRENT wrapped
        positions. The wrap is a separate device copy — the live
        trajectory keeps integrating unwrapped coordinates until the
        swap re-anchors it. Nothing here blocks: the build runs in the
        device queue behind the step's refit+force work."""
        with _trace.span("md.rebuild_dispatch"):
            t0 = time.perf_counter()
            self._pending = self.adapter.rebuild_dispatch(
                self._wrap(s1.x))
            self._pending_dispatch_ms = (time.perf_counter() - t0) * 1e3
        self._pending_cause = cause

    def _swap_plan(self, s1):
        """Commit the in-flight shadow build at a step boundary: pay its
        deferred device sync, swap the live plan, and account the swap
        as a rebuild with the cause recorded at dispatch time (so the
        cause/backend partitions of the rebuild count stay exact)."""
        with _trace.span("plan_swap"):
            t0 = time.perf_counter()
            invalidated, wait_ms, _grew = self.adapter.rebuild_commit(
                self._pending)
            commit_ms = (time.perf_counter() - t0) * 1e3
        self._pending = None
        cause, self._pending_cause = self._pending_cause, None
        self.rebuild_wait_ms += wait_ms
        self.rebuild_total_ms += self._pending_dispatch_ms + commit_ms
        self._pending_dispatch_ms = 0.0
        self.plan_swaps += 1
        # The shadow was built over wrapped positions: re-anchor the
        # live trajectory on the same wrapped coordinates (a lattice
        # shift, exactly as at a synchronous rebuild).
        s1 = s1._replace(x=self._wrap(s1.x))
        if invalidated:
            # The shadow overflowed its budget: commit fell back to a
            # blocking growth loop and the new shapes force a retrace —
            # counted exactly like a synchronous capacity growth.
            self.capacity_growths += 1
            if self.adapter.recloses_on_rebuild:
                self._remake_finish()
        self.plan = self.adapter.plan
        self._arrays = self.adapter.arrays
        self._theta_slack = float(self.adapter.theta_slack)
        self._fold_slack = float(self.adapter.fold_slack)
        self._steps_since_rebuild = 0
        self.rebuilds += 1
        if cause == "drift":
            self.rebuilds_drift += 1
        elif cause == "interval":
            self.rebuilds_interval += 1
        else:
            self.rebuilds_forced += 1
        self.rebuilds_device += 1  # shadow builds are devtree builds
        return s1

    def step(self) -> MDState:
        """One integration step (one force evaluation)."""
        with _trace.span("md.advance"):
            s1, drift_dev = self._call_logged(
                "advance", self._advance, "Simulation.step",
                self.state, self._x_eval_ref)
            # The one host<->device sync of a refit step: the drift
            # scalar, as an explicit device_get so jax's transfer guard
            # sees it. Inside the span so enabled traces attribute the
            # device wait to the phase that caused it.
            drift = float(jax.device_get(drift_dev))
        self._last_drift = drift
        self._refresh_budgets()

        policy = self.rebuild_policy
        by_drift = policy == "auto" and self._drift_exceeds_budget(drift)
        by_interval = (policy == "auto"
                       and self._steps_since_rebuild + 1
                       >= self.refit_interval)
        do_rebuild = (policy == "always" or by_drift or by_interval)

        if self._pending is not None:
            # A shadow build is in flight: swap it in at this step
            # boundary. It is strictly newer than the live topology, so
            # the swap supersedes any hard trigger that fired this very
            # step — the finish pass refits the swapped arrays to the
            # CURRENT positions and refreshes their slacks, so residual
            # invalidity (drift since dispatch) re-fires the drift
            # trigger on the next step.
            s1 = self._swap_plan(s1)
        elif do_rebuild:
            # Wrap positions into the primary cell at rebuild time (a
            # per-particle lattice shift: velocities, forces and energies
            # are all minimum-image invariant, so the trajectory is
            # unchanged while coordinates stay bounded).
            on_device = self.adapter.device_rebuild
            _rb_span = _trace.span(
                "md.rebuild_device" if on_device else "md.rebuild_host")
            _rb_span.__enter__()
            _t0 = time.perf_counter()
            s1 = s1._replace(x=self._wrap(s1.x))
            # Device rebuilds consume the live device positions — no
            # host sync; only the needs vector crosses back. Host
            # rebuilds fetch the positions explicitly.
            invalidated = self.adapter.rebuild(
                s1.x if on_device else jax.device_get(s1.x))
            if invalidated:
                # A capacity budget grew: the new shapes force a retrace
                # (counted), deliberately — geometric growth bounds how
                # often this can ever happen.
                self.capacity_growths += 1
                if self.adapter.recloses_on_rebuild:
                    self._remake_finish()
            self.plan = self.adapter.plan
            self._arrays = self.adapter.arrays
            self._theta_slack = float(self.adapter.theta_slack)
            self._fold_slack = float(self.adapter.fold_slack)
            self._steps_since_rebuild = 0
            self.rebuilds += 1
            # Cause accounting PARTITIONS the rebuild count (asserted by
            # tests): drift wins ties with the interval, and rebuilds
            # with neither cause (policy "always", checkpoint restores)
            # count as forced.
            if by_drift:
                self.rebuilds_drift += 1
            elif by_interval:
                self.rebuilds_interval += 1
            else:
                self.rebuilds_forced += 1
            if on_device:
                self.rebuilds_device += 1
            else:
                self.rebuilds_host += 1
            # A synchronous rebuild blocks the host for its whole
            # duration: total and wait coincide.
            _wall = (time.perf_counter() - _t0) * 1e3
            self.rebuild_total_ms += _wall
            self.rebuild_wait_ms += _wall
            _rb_span.__exit__(None, None, None)
        else:
            self.refits += 1
            if self.async_replan and policy == "auto":
                cause = self._dispatch_cause(drift)
                if cause is not None:
                    self._dispatch_shadow(s1, cause)

        with _trace.span("md.finish"):
            self._arrays, self.state, self._slack_dev, self._occ_dev = \
                self._call_logged("finish", self._finish, "Simulation.step",
                                  self._arrays, s1)
            if _trace.enabled():
                # Honest device-time attribution: only when tracing, pay
                # the sync here so the span covers the device work this
                # call launched (disabled runs keep the async pipeline;
                # the next step's drift scalar is the natural sync).
                jax.block_until_ready(self.state)
        # The refit/refresh point is s1.x (position-Verlet moves x again
        # in post; the budgets were refreshed at the force point).
        self._x_eval_ref = s1.x
        self.adapter.sync_arrays(self._arrays)
        self.steps += 1
        self._steps_since_rebuild += 1
        self.force_evals += 1

        if self._baseline_compiles is None:
            self._baseline_compiles = self.compiles

        if (self.checkpointer is not None and self.checkpoint_every
                and self.steps % self.checkpoint_every == 0):
            self.save_checkpoint()
        return self.state

    def run(self, steps: int, *, record_every: int = 0,
            callback=None) -> "Simulation":
        """Advance `steps` steps; optionally log diagnostics every
        `record_every` steps (including the starting state)."""
        if record_every and not self.log.records:
            self.log.record(self.steps, self.diagnostics())
        for _ in range(steps):
            self.step()
            if record_every and self.steps % record_every == 0:
                self.log.record(self.steps, self.diagnostics())
            if callback is not None:
                callback(self)
        return self

    # ------------------------------------------------------------------
    # diagnostics / checkpointing
    # ------------------------------------------------------------------

    def diagnostics(self) -> dict:
        """Energy / momentum / temperature at the current state, computed
        in one fused device reduction (`repro.dynamics.diagnostics`).
        Integrators that leave phi/f at a midpoint get one extra force
        evaluation here so the reported energy is consistent."""
        with _trace.span("md.diagnostics"):
            if not self.integrator.phi_at_step_end and self.steps > 0:
                # Position-Verlet leaves phi/f at the midpoint; refresh
                # them at the current positions so the energy is
                # consistent (one extra force evaluation, only at
                # recording cadence). The refit/refresh point moves with
                # it, so the drift reference and the budgets stay paired.
                self._arrays, self.state, self._slack_dev, self._occ_dev \
                    = self._call_logged("init_forces", self._init_forces,
                                        "Simulation.diagnostics",
                                        self._arrays, self.state)
                self._x_eval_ref = self.state.x
                self.adapter.sync_arrays(self._arrays)
                self.force_evals += 1
            return diag.summarize(self.state, self.charges, self.masses)

    def stats(self) -> dict:
        """Engine counters and budgets. Semantics:

        - ``steps``: integration steps taken (one force evaluation each;
          ``force_evals`` additionally counts the initial evaluation and
          any diagnostics-driven refreshes).
        - ``refits``: steps serviced by the device tree refit alone — no
          host work beyond the one drift scalar.
        - ``rebuilds``: tree rebuilds, PARTITIONED by cause:
          ``rebuilds == rebuilds_drift + rebuilds_interval +
          rebuilds_forced`` always holds. ``rebuilds_drift`` — a drift
          budget was exhausted (wins ties with the interval);
          ``rebuilds_interval`` — the K-step fallback elapsed (and drift
          did not fire); ``rebuilds_forced`` — neither cause
          (``rebuild="always"`` steps, checkpoint restores). The same
          count is also partitioned by backend: ``rebuilds ==
          rebuilds_host + devtree_rebuilds`` (``devtree_rebuilds`` are
          device-resident builds; ``build_backend`` names the plan's
          configured backend).
        - ``compiles``: total jit compilations of the step executables
          (advance + force closures, including retired ones), counted
          from the compile/retrace event log (`repro.obs.events`;
          every executable call site routes through it). The legacy
          jit-cache sum is kept as ``compiles_cache`` — the two always
          agree (tier-1 asserted) and the alias exists only as the
          cross-check.
        - ``retraces``: compiles beyond the baseline paid by the end of
          step 1. This is 0 while every rebuild fits the plan's capacity
          budget — on BOTH strategies: single-device plans re-pad into
          `Capacities`, sharded plans into `ShardedCapacities`, and a
          sharded rebuild inside its budget reuses the compiled SPMD
          step. Retraces occur only when a budget grows.
        - ``capacity_growths``: rebuilds that overflowed a budget and
          re-padded into geometrically grown capacities — each one is a
          deliberate, counted retrace, and geometric growth bounds their
          total number over any run.
        - ``theta_slack`` / ``fold_slack``: the LIVE refreshed margins
          (exact on the last refit's boxes; DESIGN.md §4).
          ``drift_budget_theta`` / ``drift_budget_fold`` /
          ``drift_budget_skin``: the per-step drift each budget allows
          (theta rate 2√3(1+θ), fold rate 4, and the build-time
          guarantee skin/2); ``drift_budget`` is their effective min.
        - ``mac_slack``: v1 compatibility alias — both live margins
          folded into theta-rate units.
        - ``last_drift``: the per-step drift measured at the last step
          (since the previous force evaluation, minimum-image).
        - ``slack_fallback``: a NaN slack was seen — the engine is
          explicitly rebuilding on the interval cadence.
        - ``rebuild_total_ms`` / ``rebuild_wait_ms``: rebuild wall time,
          split into end-to-end build time and the part the host spent
          BLOCKED on it. Synchronous rebuilds contribute equally to
          both; with ``async_replan`` the shadow build's latency hides
          behind live steps and only the swap's residual sync lands in
          ``rebuild_wait_ms``. ``plan_swaps`` counts double-buffer
          swaps (each is also in ``rebuilds`` under its dispatch-time
          cause); ``pending_replan`` flags a shadow build in flight.
        - ``plan``: the underlying plan's own `stats()`.
        """
        self._refresh_budgets()
        b_theta = (self.drift_safety * self._theta_slack
                   / theta_drift_rate(self._theta))
        b_fold = self.drift_safety * self._fold_slack / fold_drift_rate()
        if math.isnan(b_theta) or math.isnan(b_fold):
            b_theta = b_fold = 0.0  # NaN slack: interval-cadence fallback
        return dict(
            steps=self.steps,
            refits=self.refits,
            rebuilds=self.rebuilds,
            rebuilds_drift=self.rebuilds_drift,
            rebuilds_interval=self.rebuilds_interval,
            rebuilds_forced=self.rebuilds_forced,
            rebuilds_host=self.rebuilds_host,
            devtree_rebuilds=self.rebuilds_device,
            build_backend=getattr(self.plan.config, "build_backend",
                                  "host"),
            retraces=self.retraces,
            compiles=self.compiles,
            compiles_cache=self._total_compiles(),
            capacity_growths=self.capacity_growths,
            capacity_grows=self.capacity_growths,  # serve-naming alias
            async_replan=self.async_replan,
            plan_swaps=self.plan_swaps,
            pending_replan=self._pending is not None,
            rebuild_total_ms=self.rebuild_total_ms,
            rebuild_wait_ms=self.rebuild_wait_ms,
            force_evals=self.force_evals,
            refit_interval=self.refit_interval,
            rebuild_policy=self.rebuild_policy,
            integrator=self.integrator.name,
            dt=self.dt,
            space=repr(self.space),
            mac_slack=_scaled_slack(self._theta, self._theta_slack,
                                    self._fold_slack),
            theta_slack=self._theta_slack,
            fold_slack=self._fold_slack,
            skin=self._skin,
            slack_fallback=self._slack_fallback,
            last_drift=self._last_drift,
            drift_budget_theta=b_theta,
            drift_budget_fold=b_fold,
            drift_budget_skin=0.5 * self._skin,
            drift_budget=min(b_theta, b_fold),
            plan=self.plan.stats(),
            **({"occupancy": {k: float(v) for k, v in jax.device_get(
                    self._occ_dev).items()}}
               if self.profile and self._occ_dev else {}),
        )

    def save_checkpoint(self, background: bool = True) -> None:
        """Snapshot (x, v, f, phi, key) atomically via the configured
        `Checkpointer` (asynchronously by default)."""
        if self.checkpointer is None:
            raise ValueError("Simulation built without a checkpointer")
        self.checkpointer.save(
            self.steps, self.state._asdict(),
            meta=dict(steps=self.steps, dt=self.dt,
                      integrator=self.integrator.name),
            background=background)

    def restore_checkpoint(self, step: Optional[int] = None) -> int:
        """Restore (x, v, f, phi, key) and re-anchor the tree at the
        restored positions (a host rebuild, counted as such)."""
        if self.checkpointer is None:
            raise ValueError("Simulation built without a checkpointer")
        if self._pending is not None:
            # Discard an in-flight shadow build: the restored positions
            # supersede the dispatch positions, and simply dropping the
            # handle abandons the enqueued device work.
            self._pending = None
            self._pending_cause = None
            self._pending_dispatch_ms = 0.0
        tree, step, _meta = self.checkpointer.restore(
            self.state._asdict(), step=step)
        self.state = self.adapter.commit(
            MDState(**{k: jnp.asarray(v) for k, v in tree.items()}))
        self.state = self.state._replace(x=self._wrap(self.state.x))
        on_device = self.adapter.device_rebuild
        invalidated = self.adapter.rebuild(
            self.state.x if on_device else jax.device_get(self.state.x))
        if invalidated:
            self.capacity_growths += 1
            if self.adapter.recloses_on_rebuild:
                self._remake_finish()
        self.rebuilds += 1
        self.rebuilds_forced += 1  # neither drift- nor interval-caused
        if on_device:
            self.rebuilds_device += 1
        else:
            self.rebuilds_host += 1
        self.plan = self.adapter.plan
        self._arrays = self.adapter.arrays
        self._x_eval_ref = self.state.x
        self._theta_slack = float(self.adapter.theta_slack)
        self._fold_slack = float(self.adapter.fold_slack)
        self._steps_since_rebuild = 0
        self.steps = int(step)
        self._arrays, self.state, self._slack_dev, self._occ_dev = \
            self._call_logged("init_forces", self._init_forces,
                              "Simulation.restore_checkpoint",
                              self._arrays, self.state)
        self.adapter.sync_arrays(self._arrays)
        self.force_evals += 1
        return self.steps
