"""Unified public API: one solver facade over every execution strategy.

`TreecodeSolver` is the single entry point for fast summation
phi_i = sum_j G(x_i, y_j) q_j. `solver.plan(...)` returns an execution
plan — `SingleDevicePlan` for one device, `ShardedPlan` (RCB domain
decomposition + locally essential trees via shard_map) for nranks >= 2 —
and every plan implements the same protocol:

    plan.execute(charges)               -> phi          (input order)
    plan.potential_and_forces(charges)  -> (phi, F)     F_i = -q_i grad phi_i
    plan.stats()                        -> dict of geometry/cost counters
    plan.replan(points)                 -> new plan, same config (MD)

Typical single-shot use::

    from repro.core.api import TreecodeConfig, TreecodeSolver
    solver = TreecodeSolver(TreecodeConfig(theta=0.8, degree=8))
    phi = solver(targets, sources, charges)

Iterative / boundary-element use (fixed geometry, many charge vectors —
the plan keeps everything geometric on device, and with
``donate_charges=True`` the executors recycle the charge buffer instead
of re-allocating)::

    plan = solver.plan(targets, sources)
    phi1 = plan.execute(charges1)
    phi2 = plan.execute(charges2)

Kernel parameter sweeps (kernel protocol v2: parameter VALUES are traced,
so every call below reuses ONE compiled executable)::

    solver = TreecodeSolver(TreecodeConfig(kernel="yukawa"))
    plan = solver.plan(points)
    for kappa in (0.1, 0.2, 0.5, 1.0):
        phi = plan.execute(charges, kernel_params={"kappa": kappa})

Periodic boundary conditions (minimum-image convention; see
`repro.core.space`)::

    from repro.core.space import PeriodicBox
    cfg = TreecodeConfig(kernel="yukawa", space=PeriodicBox((L, L, L)))
    plan = TreecodeSolver(cfg).plan(points)      # built on wrapped coords

Molecular dynamics (moving particles, forces)::

    plan = solver.plan(points)                  # targets == sources
    phi, forces = plan.potential_and_forces(charges)
    plan = plan.replan(new_points)              # rebuild tree, same config

Multi-device: pass ``nranks=P`` (or a one-axis ``mesh``) explicitly, or
let ``plan`` auto-detect from `jax.device_count()` when targets are the
sources. Kernels are pluggable: ``TreecodeConfig.kernel`` accepts a
registry name (see `repro.core.potentials.register_kernel`) or a
user-constructed `Kernel` instance.
"""
from __future__ import annotations

import dataclasses
import warnings
from typing import Optional, Protocol, Tuple, Union, runtime_checkable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import eval as _eval
from repro.core.potentials import Kernel, resolve_kernel
from repro.core.space import FreeSpace, PeriodicBox, resolve_space
from repro.kernels import ops
from repro.obs import events as _events
from repro.obs import trace as _trace
from repro.obs.occupancy import static_occupancy as _static_occupancy

_BACKENDS = ("auto", "pallas", "pallas_interpret", "xla")
_PRECOMPUTES = ("direct", "hierarchical")
_APPROX_R2 = ("diff", "matmul")
_DTYPES = ("auto", "float32", "float64")

# Deprecation warnings fire ONCE per process: sweep loops construct many
# configs and a per-construction warning floods logs (tests reset via
# `_reset_deprecation_warnings`).
_DEPRECATIONS_EMITTED = set()


def _warn_kappa_deprecated():
    if "kappa" in _DEPRECATIONS_EMITTED:
        return
    _DEPRECATIONS_EMITTED.add("kappa")
    # stacklevel: this helper -> __post_init__ -> dataclass __init__ ->
    # the caller's TreecodeConfig(...) line, which is what gets reported.
    warnings.warn(
        "TreecodeConfig.kappa is deprecated; pass "
        "kernel_params={'kappa': ...} instead (works for any "
        "registered kernel and keeps sweeps recompile-free)",
        DeprecationWarning, stacklevel=4)


def _reset_deprecation_warnings():
    """Re-arm the once-per-process deprecation warnings (test hook)."""
    _DEPRECATIONS_EMITTED.clear()


@dataclasses.dataclass(frozen=True)
class TreecodeConfig:
    """BLTC parameters (Sec. 2.4 / Eq. 13 notation).

    theta: MAC parameter; degree: interpolation degree n; leaf_size: N_L;
    batch_size: N_B (paper default N_B == N_L). `precompute` selects the
    paper-faithful per-cluster modified-charge computation ("direct") or the
    exact hierarchical upward pass ("hierarchical", beyond-paper).

    `kernel` is a registry name or a `Kernel` instance; `kernel_params`
    supplies its parameters (a dict of keyword arguments for registry
    factories, e.g. ``{"kappa": 0.7}``) — these become the plan's traced
    defaults, overridable per call via ``plan.execute(q, kernel_params=)``.
    `space` selects the geometry: `FreeSpace()` (default, the paper's
    setting) or `PeriodicBox(lengths)` for the minimum-image convention.
    `dtype` pins the working precision ("auto" follows the input arrays);
    `donate_charges` lets `execute` consume the device charge buffer so
    iterative loops don't re-allocate.

    `skin` >= 0 is the Verlet-skin radius (drift-budget v2, DESIGN.md
    §4): MAC-boundary pairs within the skin are dual-listed and routed
    by current distance at evaluation time, so the interaction lists
    stay exact while no particle moves more than ``skin/2`` and the MD
    drift budget is floored at ``skin/2``. 0 (default) disables the
    dual lists (the paper's frozen-list behavior).

    `kappa` is a deprecated alias for ``kernel_params={"kappa": ...}``
    (Yukawa only); passing it emits a DeprecationWarning (once per
    process, so sweep loops don't flood logs).
    """

    theta: float = 0.7
    degree: int = 8
    leaf_size: int = 256
    batch_size: int = 0          # 0 -> same as leaf_size (paper setting)
    kernel: Union[str, Kernel] = "coulomb"
    kernel_params: tuple = ()    # dict accepted; normalized in __post_init__
    space: object = FreeSpace()
    skin: float = 0.0            # Verlet-skin radius (0 = frozen lists)
    kappa: Optional[float] = None  # DEPRECATED: use kernel_params=
    backend: str = "auto"        # pallas | pallas_interpret | xla | auto
    kahan: bool = False
    precompute: str = "direct"   # direct | hierarchical
    approx_r2: str = "diff"      # diff | matmul (MXU form, beyond-paper)
    dtype: str = "auto"          # auto | float32 | float64
    donate_charges: bool = False
    # Plan construction backend: "host" is the paper's CPU setup phase
    # (`eval.prepare_plan`); "device" builds the whole plan on the
    # accelerator from a Morton ordering (`repro.devtree`) so rebuilds
    # never sync particle positions to the host.
    build_backend: str = "host"  # host | device

    def __post_init__(self):
        def bad(msg):
            raise ValueError(f"TreecodeConfig: {msg}")

        if not (isinstance(self.theta, (int, float))
                and 0.0 < float(self.theta) <= 1.0):
            bad(f"theta must be in (0, 1], got {self.theta!r}")
        if not (isinstance(self.degree, int) and self.degree >= 1):
            bad(f"degree must be an int >= 1, got {self.degree!r}")
        if not (isinstance(self.leaf_size, int) and self.leaf_size > 0):
            bad(f"leaf_size must be > 0, got {self.leaf_size!r}")
        if not (isinstance(self.batch_size, int) and self.batch_size >= 0):
            bad(f"batch_size must be >= 0 (0 = leaf_size), "
                f"got {self.batch_size!r}")
        if not (isinstance(self.skin, (int, float))
                and float(self.skin) >= 0.0):
            bad(f"skin must be a float >= 0, got {self.skin!r}")
        object.__setattr__(self, "skin", float(self.skin))
        if self.backend not in _BACKENDS:
            bad(f"unknown backend {self.backend!r}; choose from {_BACKENDS}")
        if self.precompute not in _PRECOMPUTES:
            bad(f"unknown precompute {self.precompute!r}; "
                f"choose from {_PRECOMPUTES}")
        if self.approx_r2 not in _APPROX_R2:
            bad(f"unknown approx_r2 {self.approx_r2!r}; "
                f"choose from {_APPROX_R2}")
        if self.dtype not in _DTYPES:
            bad(f"unknown dtype {self.dtype!r}; choose from {_DTYPES}")
        if self.build_backend not in ("host", "device"):
            bad(f"unknown build_backend {self.build_backend!r}; "
                f"choose from ('host', 'device')")
        if self.build_backend == "device" \
                and self.precompute == "hierarchical":
            bad("build_backend='device' does not support "
                "precompute='hierarchical' (the upward-pass tables are "
                "host-built); use precompute='direct'")
        if not isinstance(self.kernel, (str, Kernel)):
            bad(f"kernel must be a registry name or a Kernel instance, "
                f"got {type(self.kernel).__name__}")
        # Normalize kernel_params to a hashable form (the config stays a
        # valid static jit argument): dicts become sorted (name, value)
        # item tuples, reconstructed by make_kernel.
        kp = self.kernel_params
        if isinstance(kp, dict):
            if not all(isinstance(k, str) for k in kp):
                bad("kernel_params dict keys must be parameter names")
            kp = ("__named__",) + tuple(sorted(kp.items())) if kp else ()
            object.__setattr__(self, "kernel_params", kp)
        elif not isinstance(kp, tuple):
            bad(f"kernel_params must be a dict of named parameters or a "
                f"tuple, got {type(kp).__name__}")
        object.__setattr__(self, "space", resolve_space(self.space))
        if self.kappa is not None:
            _warn_kappa_deprecated()

    def resolved_batch_size(self) -> int:
        return self.batch_size or self.leaf_size

    def _named_params(self) -> Optional[dict]:
        """kernel_params as a dict when given as one, else None."""
        kp = self.kernel_params
        if kp and kp[0] == "__named__":
            return dict(kp[1:])
        return None

    def make_kernel(self) -> Kernel:
        named = self._named_params()
        if isinstance(self.kernel, str):
            params = dict(named) if named is not None else {}
            if (self.kappa is not None and self.kernel == "yukawa"
                    and "kappa" not in params):
                params["kappa"] = self.kappa  # deprecated shim
            if named is None and self.kernel_params:
                # positional tuple for a registry name: bind post-factory
                return resolve_kernel(self.kernel).with_params(
                    self.kernel_params)
            return resolve_kernel(self.kernel, **params)
        kernel = self.kernel
        if named is not None:
            return kernel.with_params(named)
        if self.kernel_params:
            return kernel.with_params(self.kernel_params)
        return kernel

    def exec_opts(self, kernel: Kernel) -> dict:
        """Static options consumed by the jitted executors.

        The kernel enters STRIPPED of its default parameters — parameter
        values travel as traced arguments (see `SingleDevicePlan.execute`),
        so the compile-cache key is parameter-free."""
        return dict(degree=self.degree, kernel=kernel.stripped(),
                    space=self.space, backend=self.backend,
                    kahan=self.kahan, precompute=self.precompute,
                    approx_r2=self.approx_r2, theta=self.theta,
                    skin=self.skin)


@runtime_checkable
class Plan(Protocol):
    """Common executor protocol implemented by every planning strategy."""

    def execute(self, charges, kernel_params=None) -> jnp.ndarray:
        """Potentials at the plan's targets, in input order.

        `kernel_params` overrides the plan's kernel parameter values for
        this call (same pytree structure => no recompilation)."""

    def potential_and_forces(self, charges, weights=None, kernel_params=None
                             ) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """(phi, F) with F_i = -w_i * grad_x phi(x_i), sources fixed."""

    def stats(self) -> dict:
        """Geometry / cost counters (strategy, sizes, padding waste...)."""

    def replan(self, targets, sources=None, **kwargs) -> "Plan":
        """Rebuild geometry for moved particles under the same config.

        Both implementations accept a keyword-only ``capacities=``
        extension for shape-stable MD replans; their default
        (``"keep"`` where the plan holds a budget) re-pads the new
        geometry into the current capacity budget — growing it
        geometrically on overflow — so compiled executors built against
        this plan are reused by the replanned one (see docs/API.md)."""


def _resolve_dtype(config: TreecodeConfig, arr: np.ndarray) -> np.dtype:
    if config.dtype == "auto":
        dt = np.dtype(arr.dtype)
        if dt == np.dtype(np.float64) and not jax.config.jax_enable_x64:
            # jax canonicalizes f64 to f32 when x64 is off; report the
            # precision the device will actually compute in.
            return np.dtype(np.float32)
        dt = dt if dt in (np.dtype(np.float32), np.dtype(np.float64)) \
            else np.dtype(np.float32)
    elif config.dtype == "float64" and not jax.config.jax_enable_x64:
        raise ValueError(
            "TreecodeConfig(dtype='float64') requires x64 mode: set "
            "jax.config.update('jax_enable_x64', True) before planning")
    else:
        dt = np.dtype(config.dtype)
    if dt == np.dtype(np.float64) \
            and ops.resolve_backend(config.backend) == "pallas":
        # Mosaic has no f64; refuse here rather than fail in the kernel
        # compile or quietly evaluate on another backend.
        raise ValueError(
            f"float64 plans cannot run the Pallas TPU kernels (backend="
            f"{config.backend!r} resolves to 'pallas' on this platform); "
            "use float32, or pass backend='xla' for the XLA path")
    return dt


def lift_params(kernel: Kernel, dtype) -> object:
    """Kernel defaults as traced-ready device arrays of the plan dtype
    (an explicit upload: plans are rebuilt inside transfer-guarded MD
    loops)."""
    return jax.tree.map(lambda v: jax.device_put(np.asarray(v, dtype)),
                        kernel.params)


class SingleDevicePlan:
    """Plan over the single-device pipeline (`repro.core.eval`)."""

    nranks = 1

    def __init__(self, config: TreecodeConfig, kernel: Kernel,
                 inner: _eval.Plan, dtype: np.dtype):
        self.config = config
        self.kernel = kernel
        self.inner = inner
        self.dtype = dtype
        self.kernel_params = lift_params(kernel, dtype)

    # -- convenience passthroughs kept from the old `eval.Plan` surface
    @property
    def arrays(self) -> dict:
        return self.inner.arrays

    @property
    def padding_waste(self) -> float:
        return self.inner.padding_waste

    @property
    def num_targets(self) -> int:
        return self.inner.num_targets

    @property
    def num_sources(self) -> int:
        return self.inner.num_sources

    @property
    def space(self):
        return self.config.space

    def _charges(self, charges) -> jnp.ndarray:
        q = jnp.asarray(charges)
        if q.dtype != self.dtype:
            q = q.astype(self.dtype)
        return q

    def _params(self, kernel_params):
        """Per-call parameter values: None -> the plan's lifted defaults.

        Dicts are normalized through the kernel's `param_names`, and every
        leaf is cast to the plan dtype, so any two sweeps share one traced
        structure (= one compiled executable)."""
        if kernel_params is None:
            return self.kernel_params
        p = self.kernel.normalize_params(kernel_params)
        return jax.tree.map(lambda v: jnp.asarray(v, dtype=self.dtype), p)

    def execute(self, charges, kernel_params=None) -> jnp.ndarray:
        """Potentials at the plan's targets, in input order.

        Geometry stays on device and is reused across calls; with
        `donate_charges` the device charge buffer is donated to the
        computation. `kernel_params` overrides the kernel parameter
        values for this call without recompiling."""
        fn = (_eval.execute_donating if self.config.donate_charges
              else _eval.execute)
        with _trace.span("eval.execute"):
            q = self._charges(charges)
            params = self._params(kernel_params)
            with _trace.span("eval.dispatch"):
                out, _ = _events.log_compiles(
                    "execute_donating" if self.config.donate_charges
                    else "execute",
                    fn, self.inner.arrays, q, params,
                    key=lambda: hash(_eval.plan_signature(self.inner)),
                    site="SingleDevicePlan.execute", owner="core.eval",
                    **self.config.exec_opts(self.kernel))
        return out

    def potential_and_forces(self, charges, weights=None,
                             kernel_params=None):
        """(phi, F) with F_i = -w_i * grad_x phi(x_i), input order.

        Gradients come from the custom-VJP executor (three forward JVPs;
        see `repro.core.eval`). `weights` defaults to the charges when
        targets == sources (the physical force on charge q_i); disjoint
        target/source sets must pass per-target weights explicitly."""
        q = self._charges(charges)
        if weights is None:
            if self.num_targets != self.num_sources:
                raise ValueError(
                    "potential_and_forces: targets != sources, so per-target "
                    "weights cannot default to the source charges; pass "
                    "weights= explicitly (q of each target)")
            w = q
        else:
            w = self._charges(weights)
        with _trace.span("eval.potential_and_forces"):
            out, _ = _events.log_compiles(
                "potential_and_forces", _eval.potential_and_forces,
                self.inner.arrays, q, w, self._params(kernel_params),
                key=lambda: hash(_eval.plan_signature(self.inner)),
                site="SingleDevicePlan.potential_and_forces",
                owner="core.eval",
                **self.config.exec_opts(self.kernel))
        return out

    @property
    def mac_slack(self) -> float:
        """Min over approx pairs of the drift-budget margin (theta margin
        and, for periodic spaces, the scaled fold margin): the budget
        within which a topology-preserving refit keeps the MAC valid.
        Compatibility alias folding both v2 budgets into theta-rate
        units; prefer `theta_slack` / `fold_slack` (DESIGN.md §4)."""
        return self.inner.mac_slack

    @property
    def theta_slack(self) -> float:
        """Min raw theta margin over SAFE approx pairs (shrinks at rate
        2*sqrt(3)*(1+theta) per unit of drift)."""
        return self.inner.theta_slack

    @property
    def fold_slack(self) -> float:
        """Min raw fold margin over SAFE approx pairs (shrinks at rate 4
        per unit of drift; +inf in free space)."""
        return self.inner.fold_slack

    @property
    def skin(self) -> float:
        """Verlet-skin radius the interaction lists were built with."""
        return self.inner.skin

    @property
    def capacities(self):
        """`repro.core.eval.Capacities` when capacity-padded, else None."""
        return self.inner.capacities

    def stats(self) -> dict:
        """Geometry / cost counters: tree and batch sizes, padding
        waste, the MAC slack (refit drift budget), the pair evaluations
        the `batch_cluster` kernels launch against those they need
        (`kernel_work`), and — when capacity-padded — the `Capacities`
        budget the arrays occupy."""
        tree = self.inner.tree
        caps = self.inner.capacities
        return dict(
            strategy="single_device",
            nranks=1,
            build_backend=getattr(self.inner, "build_backend", "host"),
            num_targets=self.inner.num_targets,
            num_sources=self.inner.num_sources,
            num_nodes=tree.num_nodes,
            num_leaves=tree.num_leaves,
            tree_depth=int(tree.level.max()),
            num_batches=self.inner.batches.num_batches,
            padding_waste=self.inner.padding_waste,
            dtype=str(self.dtype),
            space=repr(self.config.space),
            mac_slack=self.inner.mac_slack,
            theta_slack=self.inner.theta_slack,
            fold_slack=self.inner.fold_slack,
            skin=self.inner.skin,
            capacity_padded=caps is not None,
            # Observability (repro.obs): host build-phase wall times and
            # padded-vs-real utilization of the packed arrays.
            build_phases=dict(self.inner.build_ms),
            occupancy=_static_occupancy(self.inner),
            kernel_work=_eval.kernel_work(self.inner, self.config.degree),
            **({"capacities": dataclasses.asdict(caps)} if caps else {}),
        )

    def replan(self, targets, sources=None, *,
               capacities="keep") -> "SingleDevicePlan":
        """Rebuild geometry for moved particles under the same config.

        `capacities="keep"` (default) re-pads into this plan's own
        capacity budget when it has one (growing it geometrically if the
        new geometry no longer fits), so jitted executors compiled against
        this plan are reused by the replanned one. Pass `capacities=None`
        to drop capacity padding, or an explicit
        `repro.core.eval.Capacities`.
        """
        if capacities == "keep":
            capacities = self.inner.capacities
        dev = (self.inner.dev or {}
               if self.inner.build_backend == "device" else {})
        return _plan_single(self.config, self.kernel, targets,
                            targets if sources is None else sources,
                            capacities=capacities,
                            pair_caps=dev.get("pair_caps"),
                            # The capacity budget is bound to the octree
                            # depths, so replans that keep it must keep
                            # them too (pinned or derived alike).
                            depth=dev.get("depth") if capacities else None,
                            batch_depth=(dev.get("tdepth")
                                         if capacities else None))

    def replan_async(self, targets, sources=None) -> "PendingSingleDevicePlan":
        """Dispatch a shadow replan without blocking (device builds only).

        Enqueues the full sort/build/list pipeline at this plan's budget
        and returns immediately; this plan stays live and untouched. Call
        `finalize()` on the returned handle to block on the leftover
        device work and obtain the new plan — the double-buffered rebuild
        the MD engine swaps in at a step boundary (DESIGN.md §10).
        """
        if self.inner.build_backend != "device":
            raise ValueError(
                "replan_async requires build_backend='device' (host "
                "builds run on the host thread and cannot overlap)")
        if self.inner.capacities is None:
            raise ValueError(
                "replan_async requires a capacity-padded plan (the async "
                "path never probes budgets)")
        from repro.devtree import build as _devbuild
        dev = self.inner.dev or {}
        pending = _devbuild.dispatch_plan_device(
            targets, targets if sources is None else sources,
            theta=self.config.theta, degree=self.config.degree,
            leaf_size=self.config.leaf_size,
            batch_size=self.config.resolved_batch_size(),
            space=self.config.space, skin=self.config.skin,
            dtype=self.dtype, capacities=self.inner.capacities,
            pair_caps=dev.get("pair_caps"),
            depth=dev.get("depth"), batch_depth=dev.get("tdepth"))
        return PendingSingleDevicePlan(self, pending)


class PendingSingleDevicePlan:
    """An in-flight `SingleDevicePlan.replan_async`.

    Wraps the devtree `PendingDevicePlan`; `finalize()` blocks on the
    leftover device work and returns ``(plan, wait_ms, grew)`` — the new
    `SingleDevicePlan`, the milliseconds actually spent waiting, and
    whether the budget grew mid-flight (a deliberate retrace, exactly
    the synchronous path's `capacity_growth` contract).
    """

    def __init__(self, source: SingleDevicePlan, pending):
        self._source = source
        self._pending = pending

    def finalize(self):
        inner, wait_ms, grew = self._pending.finalize()
        s = self._source
        return (SingleDevicePlan(s.config, s.kernel, inner, s.dtype),
                wait_ms, grew)


def _plan_single(config: TreecodeConfig, kernel: Kernel, targets,
                 sources, capacities=None, pair_caps=None,
                 depth=None, batch_depth=None) -> SingleDevicePlan:
    if config.build_backend == "device":
        # Device build: positions stay wherever they are (jnp arrays are
        # NOT pulled to host), and the plan comes back capacity-padded.
        from repro.devtree import build as _devbuild
        dtype = _resolve_dtype(config, targets)
        inner = _devbuild.prepare_plan_device(
            targets, sources, theta=config.theta, degree=config.degree,
            leaf_size=config.leaf_size,
            batch_size=config.resolved_batch_size(),
            space=config.space, skin=config.skin, dtype=dtype,
            capacities=None if capacities == "auto" else capacities,
            pair_caps=pair_caps, depth=depth, batch_depth=batch_depth)
        return SingleDevicePlan(config, kernel, inner, dtype)
    targets = np.asarray(targets)
    sources = np.asarray(sources)
    dtype = _resolve_dtype(config, targets)
    inner = _eval.prepare_plan(
        targets.astype(dtype, copy=False), sources.astype(dtype, copy=False),
        theta=config.theta, degree=config.degree,
        leaf_size=config.leaf_size, batch_size=config.resolved_batch_size(),
        space=config.space, skin=config.skin)
    if config.precompute == "hierarchical":
        inner = _eval.add_hierarchical_tables(inner)
    if capacities is not None:
        if capacities == "auto":
            capacities = _eval.Capacities.for_plan(inner)
        else:
            capacities = capacities.grown_to_fit(inner)
        inner = _eval.pad_plan(inner, capacities)
    return SingleDevicePlan(config, kernel, inner, dtype)


class TreecodeSolver:
    """Fast summation phi_i = sum_j G(x_i, y_j) q_j in O(N log N)."""

    def __init__(self, config: TreecodeConfig = TreecodeConfig()):
        self.config = config
        self._kernel = config.make_kernel()

    @property
    def kernel(self) -> Kernel:
        return self._kernel

    @property
    def space(self):
        return self.config.space

    def plan(self, targets, sources=None, *, mesh=None,
             nranks: Optional[int] = None, capacities=None) -> Plan:
        """Build an execution plan for this geometry.

        sources defaults to targets (the N-body setting). Strategy choice:
        an explicit `mesh` (one sharding axis) or `nranks` wins; otherwise
        nranks is auto-detected from `jax.device_count()` when targets are
        the sources, and falls back to single-device for disjoint
        target/source sets (the sharded path assumes the paper's
        targets == sources test setting).

        `capacities` pads the plan into a fixed buffer budget so later
        `replan` calls keep identical array shapes and reuse compiled
        executables (the MD setting; see `repro.dynamics`).
        Single-device: None (default, no padding), "auto", or a
        `repro.core.eval.Capacities`. Sharded plans are ALWAYS
        capacity-padded — None/"auto" budget this build's own needs, or
        pass an explicit `repro.core.eval.ShardedCapacities` (see
        DESIGN.md §7).
        """
        same = sources is None or sources is targets
        if mesh is not None and nranks is not None:
            raise ValueError("pass either mesh= or nranks=, not both")
        axis = "data"
        if mesh is not None:
            if len(mesh.axis_names) != 1:
                raise ValueError(
                    f"sharded plans shard over exactly one mesh axis; got "
                    f"axes {tuple(mesh.axis_names)}")
            axis = mesh.axis_names[0]
            p = mesh.devices.size
        elif nranks is not None:
            p = int(nranks)
            if p < 1:
                raise ValueError(f"nranks must be >= 1, got {nranks}")
        else:
            # Auto-detect, clamped to what the geometry can feed: RCB
            # needs at least one particle per rank.
            p = jax.device_count() if same else 1
            n = np.asarray(targets).shape[0]
            if n < p:
                p = 1

        if p == 1:
            return _plan_single(self.config, self._kernel, targets,
                                targets if sources is None else sources,
                                capacities=capacities)

        if not same:
            raise ValueError(
                "sharded planning (nranks >= 2) requires targets == sources; "
                "pass nranks=1 for disjoint target/source sets")
        if mesh is None and p > jax.device_count():
            raise ValueError(
                f"nranks={p} exceeds the {jax.device_count()} visible "
                "device(s); pass a mesh spanning the target hardware or "
                "lower nranks")
        from repro.distributed.bltc import ShardedPlan
        points = np.asarray(targets)
        dtype = _resolve_dtype(self.config, points)
        return ShardedPlan.build(points.astype(dtype, copy=False),
                                 self.config, p, mesh=mesh, axis=axis,
                                 kernel=self._kernel,
                                 capacities=("auto" if capacities is None
                                             else capacities))

    # -- protocol delegations (kept so existing call sites read naturally)
    def execute(self, plan: Plan, charges) -> jnp.ndarray:
        return plan.execute(charges)

    def potential_and_forces(self, plan: Plan, charges, weights=None):
        return plan.potential_and_forces(charges, weights)

    def __call__(self, targets, sources, charges) -> jnp.ndarray:
        return self.plan(targets, sources).execute(charges)


# Re-exported for discoverability: the space types live in core.space.
__all__ = ["TreecodeConfig", "TreecodeSolver", "Plan", "SingleDevicePlan",
           "FreeSpace", "PeriodicBox"]
