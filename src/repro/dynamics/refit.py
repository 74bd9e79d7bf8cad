"""Device-side tree refit: moved particles, fixed topology.

A treecode plan is (topology, geometry): the permutation, particle ranges,
interaction lists and padded gather tables are topology; the packed
coordinates and node bounding boxes are geometry. When particles move a
little, only the geometry is stale — and all of it lives in the plan's
device arrays, derived from positions by gathers/scatters and masked
segment min/max. `refit_*` recomputes exactly that, on device, in O(N):

    src_sorted   <- x[perm]                  (tree-order source slab)
    tgt_batched  <- scatter x by gather_index (batch-packed target slab)
    node_lo/hi   <- masked min/max over each node's bucket-gather row

Chebyshev grids and modified charges are derived from node_lo/hi inside
the jitted executors on every call, so refitting the boxes refits them
for free. Every particle remains inside its refitted cluster box (the box
IS the particle bounding box), so barycentric interpolation stays
well-posed; the only thing drift can invalidate is the MAC inequality of
the frozen approx lists, which the engine guards with the per-step
drift-vs-refreshed-slack trigger (`refresh_slacks_*` below recompute the
exact theta/fold margins from the refitted boxes; DESIGN.md §4).

`PlanAdapter` gives the engine one interface over both plan strategies:
jit-safe `refit` and `force` (input-order positions in, input-order
forces out — device-resident end to end), plus host-side `rebuild`.
"""
from __future__ import annotations

from typing import Callable, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import eval as _eval
from repro.core.api import SingleDevicePlan
from repro.kernels import ops as _ops


def _masked_boxes(pts, valid, old_lo_rows, old_hi_rows):
    """(rows, pad, 3) points + validity -> (rows, 3) min/max boxes.

    Rows with no valid entries (pure padding) keep their old box, which
    the padding convention fixed at the non-degenerate [0, 1]."""
    big = jnp.asarray(jnp.finfo(pts.dtype).max, pts.dtype)
    lo = jnp.min(jnp.where(valid[..., None], pts, big), axis=1)
    hi = jnp.max(jnp.where(valid[..., None], pts, -big), axis=1)
    has = jnp.any(valid, axis=1)[..., None]
    return (jnp.where(has, lo, old_lo_rows),
            jnp.where(has, hi, old_hi_rows))


def refit_single_arrays(arrays: dict, x: jnp.ndarray) -> dict:
    """Refit a single-device plan's arrays to new positions (jit-safe).

    Assumes the MD setting: targets == sources == the N particles the
    plan was built over (gather_index covers every target exactly once).
    """
    x = x.astype(arrays["src_sorted"].dtype)
    src_sorted = x[arrays["src_perm"]]

    lo, hi = arrays["node_lo"], arrays["node_hi"]
    for gidx, nodes in zip(arrays["bucket_gather"], arrays["bucket_nodes"]):
        valid = gidx >= 0
        pts = src_sorted[jnp.maximum(gidx, 0)]
        lo_rows, hi_rows = _masked_boxes(pts, valid, lo[nodes], hi[nodes])
        lo = lo.at[nodes].set(lo_rows)
        hi = hi.at[nodes].set(hi_rows)

    b, nb, _ = arrays["tgt_batched"].shape
    flat = jnp.zeros((b * nb, 3), x.dtype).at[arrays["gather_index"]].set(x)
    return dict(arrays, src_sorted=src_sorted, node_lo=lo, node_hi=hi,
                tgt_batched=flat.reshape(b, nb, 3))


def refit_sharded_arrays(arrays: dict, x: jnp.ndarray,
                         depth: int) -> dict:
    """Refit a sharded plan's stacked (P, ...) arrays to new positions.

    `arrays` is the adapter's merged dict: the plan's stacked arrays PLUS
    the device rank tables (`rank_gather`, `input_pos`) — the tables ride
    through the jitted step as traced arguments, so a host rebuild swaps
    their VALUES without invalidating the compiled step (the retrace-free
    sharded-MD contract, DESIGN.md §7).

    The RCB rank assignment is frozen with the topology (particles may
    drift across slab boundaries; correctness only needs each rank's
    lists to stay MAC-valid, which the same slack bound guards). All ops
    are batched over the rank dimension — jit/shard-map friendly.
    """
    x = x.astype(arrays["src_sorted"].dtype)
    rank_gather = arrays["rank_gather"]                  # (P, per_pad)
    valid_slab = rank_gather >= 0
    x_rank = jnp.where(valid_slab[..., None],
                       x[jnp.maximum(rank_gather, 0)], 0.0)
    src_sorted = jnp.take_along_axis(
        x_rank, arrays["charges_perm"][..., None].astype(jnp.int32), axis=1)

    p = src_sorted.shape[0]
    rows = jnp.arange(p)[:, None]
    lo, hi = arrays["node_lo"], arrays["node_hi"]
    for lvl in range(depth):
        gidx = arrays[f"bucket_gather_{lvl}"]            # (P, C, G)
        nodes = arrays[f"bucket_nodes_{lvl}"]            # (P, C)
        c, g = gidx.shape[1], gidx.shape[2]
        pts = jnp.take_along_axis(
            src_sorted, jnp.maximum(gidx, 0).reshape(p, c * g, 1), axis=1
        ).reshape(p, c, g, 3)
        valid = gidx >= 0
        old_lo = jnp.take_along_axis(lo, nodes[..., None], axis=1)
        old_hi = jnp.take_along_axis(hi, nodes[..., None], axis=1)
        lo_rows, hi_rows = _masked_boxes(
            pts.reshape(p * c, g, 3), valid.reshape(p * c, g),
            old_lo.reshape(p * c, 3), old_hi.reshape(p * c, 3))
        lo = lo.at[rows, nodes].set(lo_rows.reshape(p, c, 3))
        hi = hi.at[rows, nodes].set(hi_rows.reshape(p, c, 3))

    _, b, nb, _ = arrays["tgt_batched"].shape
    gi = jnp.where(valid_slab, arrays["gather_index"], b * nb)
    flat = jnp.zeros((p, b * nb + 1, 3), x.dtype)
    flat = flat.at[rows, gi].set(x_rank)
    return dict(arrays, src_sorted=src_sorted, node_lo=lo, node_hi=hi,
                tgt_batched=flat[:, :-1].reshape(-1, b, nb, 3))


# ---------------------------------------------------------------------------
# On-device slack refresh (drift-budget v2, DESIGN.md §4)
# ---------------------------------------------------------------------------
#
# Refitted boxes are TRUE bounding boxes of the moved particles, so MAC
# margins recomputed from them are exact current margins — not the
# build-time values degraded by a worst-case bound. The engine therefore
# budgets only the drift since the LAST refit (one step) against these
# refreshed slacks, instead of cumulative drift against frozen build
# slack: boxes usually shrink under refit, so the live budget is larger
# and refit runs lengthen. Skin pairs are runtime gated (self-validating)
# and excluded from the minima.


def refresh_slacks_single(arrays: dict, *, theta: float,
                          space) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(theta_slack, fold_slack) device scalars of a refitted
    single-device plan (jit-safe; +inf when no safe approx pairs)."""
    bc, bhw, rb, has = _ops.batch_boxes(arrays["tgt_batched"],
                                        arrays["tgt_mask"])
    return _ops.refreshed_slacks(
        arrays["approx_idx"], arrays["approx_skin"], bc, bhw, rb, has,
        arrays["node_lo"], arrays["node_hi"], theta=theta, space=space)


def refresh_slacks_sharded(arrays: dict, *, theta: float,
                           space) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(theta_slack, fold_slack) over a sharded plan's stacked arrays.

    Local per-rank lists are offset into the flat (P*M) node axis and
    reduced together with the remote (LET) lists — whose entries already
    index the flat gathered node axis — so one jnp.min over the stacked
    arrays IS the cross-rank slack reduction (no collective beyond the
    gather jit emits for the cross-shard node reads). Remote skin pairs
    are demoted at build, so every remote entry is a safe pair."""
    lo, hi = arrays["node_lo"], arrays["node_hi"]        # (P, M, 3)
    p, m = lo.shape[0], lo.shape[1]
    lo_f = lo.reshape(p * m, 3)
    hi_f = hi.reshape(p * m, 3)
    tgt = arrays["tgt_batched"]                          # (P, B, NB, 3)
    _, b, nb, _ = tgt.shape
    bc, bhw, rb, has = _ops.batch_boxes(
        tgt.reshape(p * b, nb, 3), arrays["tgt_mask"].reshape(p * b, nb))
    off = (jnp.arange(p, dtype=jnp.int32) * m)[:, None, None]
    la = arrays["approx_idx"]
    la_f = jnp.where(la >= 0, la + off, -1).reshape(p * b, -1)
    ls_f = arrays["approx_skin"].reshape(p * b, -1)
    t_loc, f_loc = _ops.refreshed_slacks(
        la_f, ls_f, bc, bhw, rb, has, lo_f, hi_f, theta=theta, space=space)
    ra = arrays["remote_approx_idx"].reshape(p * b, -1)
    t_rem, f_rem = _ops.refreshed_slacks(
        ra, jnp.zeros_like(ra), bc, bhw, rb, has, lo_f, hi_f,
        theta=theta, space=space)
    return jnp.minimum(t_loc, t_rem), jnp.minimum(f_loc, f_rem)


def max_drift(x: jnp.ndarray, x_ref: jnp.ndarray,
              space=None) -> jnp.ndarray:
    """Max particle displacement since the reference build (jit-safe).

    With a periodic `space` the displacement is folded to the minimum
    image, so a particle wrapped across the cell boundary at the last
    rebuild does not register a spurious box-length drift."""
    d = x - x_ref
    if space is not None:
        d = space.min_image(d)
    return jnp.sqrt(jnp.max(jnp.sum(d ** 2, axis=-1)))


# ---------------------------------------------------------------------------
# Plan adapters: one engine interface over both execution strategies
# ---------------------------------------------------------------------------


class PlanAdapter:
    """Strategy-specific hooks the dynamics engine composes into its
    jitted step. `refit` and `force` must be jit-safe; `rebuild` is the
    host path (tree construction is a host phase, exactly as in the
    paper) and returns True when compiled executables were invalidated."""

    plan = None
    # True when an INVALIDATING rebuild (capacity-budget growth) swaps
    # the underlying compiled executable, so the engine must re-close its
    # force-dependent jits and count the recompilation as a retrace.
    # Budget-fitting rebuilds never invalidate on either strategy.
    recloses_on_rebuild = False
    # True when `rebuild` runs on device (the devtree backend): the
    # engine then passes the live device positions straight through
    # instead of syncing them to host first.
    device_rebuild = False
    # True when the strategy can dispatch a SHADOW rebuild without
    # blocking (`rebuild_dispatch` / `rebuild_commit`): the engine keeps
    # refitting on the live plan while the replacement builds in the
    # device queue, and swaps at the next step boundary.
    supports_async_rebuild = False

    def positions(self) -> np.ndarray:
        """Current particle positions in input order (host)."""
        raise NotImplementedError

    def commit(self, tree):
        """Pin a pytree of device arrays to the plan's canonical input
        sharding (identity for single-device plans). The engine commits
        the initial MD state through this so every step — including the
        first after a host rebuild — sees one stable jit signature; a
        committed/uncommitted or sharding flip would retrace the step."""
        return tree

    @property
    def arrays(self) -> dict:
        raise NotImplementedError

    @property
    def mac_slack(self) -> float:
        raise NotImplementedError

    @property
    def theta_slack(self) -> float:
        """Build-time raw theta-margin slack (drift rate 2√3(1+θ))."""
        return self.plan.theta_slack

    @property
    def fold_slack(self) -> float:
        """Build-time raw fold-margin slack (drift rate 4)."""
        return self.plan.fold_slack

    @property
    def skin(self) -> float:
        """Verlet-skin radius of the plan's interaction lists."""
        return self.plan.skin

    def signature(self) -> Tuple:
        raise NotImplementedError

    def refit(self, arrays: dict, x) -> dict:
        raise NotImplementedError

    def slack_fn(self) -> Callable:
        """Jit-safe (arrays) -> (theta_slack, fold_slack) device scalars
        recomputed from the REFITTED geometry (the on-device slack
        refresh the engine budgets per-step drift against)."""
        raise NotImplementedError

    def force_fn(self) -> Callable:
        """(arrays, x, q, w) -> (phi, F), all input order, jit-safe."""
        raise NotImplementedError

    def rebuild(self, x_host: np.ndarray) -> bool:
        """Host tree rebuild at new positions, re-padded into the plan's
        capacity budget; returns True only when a budget overflowed (the
        compiled executables were invalidated)."""
        raise NotImplementedError

    def rebuild_dispatch(self, x):
        """Enqueue a shadow rebuild at positions ``x`` WITHOUT blocking
        and without touching the live plan; returns an opaque pending
        handle for `rebuild_commit`. Only meaningful when
        `supports_async_rebuild` is True."""
        raise NotImplementedError

    def rebuild_commit(self, pending) -> Tuple[bool, float, bool]:
        """Swap the live plan for a dispatched shadow build. Pays the
        deferred device sync; returns ``(invalidated, wait_ms, grew)``
        where `invalidated` means compiled executables were lost (budget
        shapes changed), `wait_ms` is the host time spent waiting on the
        shadow build, and `grew` means a capacity budget overflowed (the
        handle fell back to a blocking growth loop)."""
        raise NotImplementedError

    def sync_arrays(self, arrays: dict) -> None:
        """Push engine-refitted arrays back onto the plan so direct plan
        use (plan.execute / stats) observes the current geometry."""
        raise NotImplementedError


class SingleDeviceAdapter(PlanAdapter):
    def __init__(self, plan: SingleDevicePlan):
        self.plan = plan

    @property
    def device_rebuild(self) -> bool:
        return getattr(self.plan.config, "build_backend", "host") == "device"

    def positions(self) -> np.ndarray:
        src = np.asarray(self.plan.inner.arrays["src_sorted"])
        out = np.empty_like(src)
        out[self.plan.inner.tree.perm] = src
        return out

    @property
    def arrays(self) -> dict:
        return self.plan.inner.arrays

    @property
    def mac_slack(self) -> float:
        return self.plan.mac_slack

    def signature(self) -> Tuple:
        return _eval.plan_signature(self.plan.inner)

    def refit(self, arrays: dict, x) -> dict:
        return refit_single_arrays(arrays, x)

    def slack_fn(self) -> Callable:
        cfg = self.plan.config

        def slack(arrays):
            return refresh_slacks_single(arrays, theta=cfg.theta,
                                         space=cfg.space)

        return slack

    def force_fn(self) -> Callable:
        opts = self.plan.config.exec_opts(self.plan.kernel)
        params = self.plan.kernel_params

        def force(arrays, x, q, w):
            del x  # already refitted into arrays
            return _eval.potential_and_forces(arrays, q, w, params, **opts)

        return force

    def rebuild(self, x_host: np.ndarray) -> bool:
        old_sig = self.signature()
        self.plan = self.plan.replan(x_host)   # keeps capacities, grows
        return self.signature() != old_sig

    @property
    def supports_async_rebuild(self) -> bool:
        # Needs the non-blocking devtree pipeline AND a locked capacity
        # budget to dispatch fixed shapes into.
        return (self.device_rebuild
                and self.plan.inner.capacities is not None)

    def rebuild_dispatch(self, x):
        return self.plan.replan_async(x)

    def rebuild_commit(self, pending) -> Tuple[bool, float, bool]:
        old_sig = self.signature()
        plan, wait_ms, grew = pending.finalize()
        self.plan = plan
        return self.signature() != old_sig, wait_ms, grew

    def sync_arrays(self, arrays: dict) -> None:
        self.plan.inner.arrays = arrays


class ShardedAdapter(PlanAdapter):
    """Adapter over `ShardedPlan`. The engine's jitted step must survive
    a host rebuild without retracing, so nothing rebuild-dependent may be
    a closure constant of the traced step:

      - the device rank tables (`rank_gather`, `input_pos`) are merged
        into the `arrays` pytree the engine threads through its jitted
        step — a rebuild swaps their VALUES as ordinary traced arguments;
      - the SPMD callable comes from the module executable cache keyed on
        budget-derived statics (`ShardedPlan._spmd_fn`), so a rebuild
        inside the same `ShardedCapacities` budget rebinds to the SAME
        object and the captured closure stays valid.

    Only a capacity-budget growth (shape/schedule change) invalidates the
    step; `rebuild` reports exactly that."""

    recloses_on_rebuild = True
    _IO_KEYS = ("rank_gather", "input_pos")

    def __init__(self, plan):
        self.plan = plan
        self._bind()

    def positions(self) -> np.ndarray:
        plan = self.plan
        src = np.asarray(plan.arrays["src_sorted"])      # (P, per_pad, 3)
        perm = np.asarray(plan.arrays["charges_perm"])   # (P, per_pad)
        rcb = plan.rcb
        out = np.empty((plan.num_points, 3), src.dtype)
        for r in range(plan.nranks):
            idx = rcb.perm[rcb.starts[r]:rcb.starts[r + 1]]
            slab = np.empty((len(idx), 3), src.dtype)
            # src_sorted[r, j] = slab[perm[r, j]] for real rows j.
            slab[perm[r, :len(idx)]] = src[r, :len(idx)]
            out[idx] = slab
        return out

    def _bind(self):
        self._fn = self.plan._spmd_fn(grad=True)

    def commit(self, tree):
        # Per-particle MD state is replicated over the mesh (the SPMD
        # program shards its own arrays; state enters through the rank
        # gather tables).
        rep = jax.sharding.NamedSharding(
            self.plan.mesh, jax.sharding.PartitionSpec())
        return jax.tree.map(lambda v: jax.device_put(v, rep), tree)

    @property
    def arrays(self) -> dict:
        # Plan arrays + device rank tables: one traced pytree argument.
        plan = self.plan
        return dict(plan.arrays, rank_gather=plan.rank_gather,
                    input_pos=plan.input_pos)

    @property
    def mac_slack(self) -> float:
        return self.plan.mac_slack

    def signature(self) -> Tuple:
        # The sharded arrays dict is a plain {name: array} mapping, so
        # the core signature helper applies as-is. Budget changes always
        # show up here: widths change shapes, halo-round or level-count
        # changes add/remove keys.
        return _eval.plan_signature(self.plan)

    def refit(self, arrays: dict, x) -> dict:
        return refit_sharded_arrays(arrays, x, self.plan.depth)

    def slack_fn(self) -> Callable:
        cfg = self.plan.config

        def slack(arrays):
            return refresh_slacks_sharded(arrays, theta=cfg.theta,
                                          space=cfg.space)

        return slack

    def force_fn(self) -> Callable:
        fn = self._fn                     # shared cached SPMD executable
        dtype = self.plan.dtype
        params = self.plan.kernel_params  # values fixed by the config
        io_keys = self._IO_KEYS

        def force(arrays, x, q, w):
            rank_gather = arrays["rank_gather"]
            valid = rank_gather >= 0
            q_rank = jnp.where(valid, q.astype(dtype)[
                jnp.maximum(rank_gather, 0)], 0.0)
            tgt = arrays["tgt_batched"]
            rest = {k: v for k, v in arrays.items()
                    if k != "tgt_batched" and k not in io_keys}

            def phi_of(t):
                return fn(dict(rest, tgt_batched=t), q_rank, params)

            phi_rank, grads = None, []
            for d in range(3):
                tangent = jnp.zeros_like(tgt).at[..., d].set(1.0)
                phi_rank, dphi = jax.jvp(phi_of, (tgt,), (tangent,))
                grads.append(dphi)
            g_rank = jnp.stack(grads, axis=-1)       # (P, per_pad, 3)
            pos = arrays["input_pos"]
            phi = phi_rank.reshape(-1)[pos]
            g = g_rank.reshape(-1, 3)[pos]
            return phi, -w[:, None].astype(dtype) * g

        return force

    def rebuild(self, x_host: np.ndarray) -> bool:
        old_sig = self.signature()
        self.plan = self.plan.replan(x_host)   # keeps capacities, grows
        if self.signature() == old_sig:
            # Budget held: with the config fixed, an equal signature
            # means equal budget statics, so the adapter's held `_fn`
            # (and every compiled trace closed over it) stays valid —
            # deliberately NOT re-fetched from the module cache, whose
            # FIFO eviction could hand back a fresh equivalent object.
            return False
        # The budget grew: new shapes/schedule mean a new SPMD
        # executable, so the engine re-closes and counts it.
        self._bind()
        return True

    def sync_arrays(self, arrays: dict) -> None:
        self.plan.arrays = {k: v for k, v in arrays.items()
                            if k not in self._IO_KEYS}


def make_adapter(plan) -> PlanAdapter:
    """Dispatch a plan to its dynamics adapter."""
    if isinstance(plan, SingleDevicePlan):
        return SingleDeviceAdapter(plan)
    from repro.distributed.bltc import ShardedPlan
    if isinstance(plan, ShardedPlan):
        return ShardedAdapter(plan)
    raise TypeError(f"no dynamics adapter for {type(plan).__name__}")
