"""Distributed BLTC: RCB domain decomposition + LET via shard_map (Sec. 3.1).

The paper's MPI/RMA construction maps onto two static collectives inside
one SPMD program (see DESIGN.md §3):

  phase 1 (tree array + cluster charges RMA gets)  ->  all_gather of each
      rank's padded node metadata (lo/hi) and modified charges q_hat;
  phase 2 (source particle RMA gets)               ->  collective_permute
      rounds exchanging boundary ("halo") leaves between nearby ranks.

The host (exactly like the paper's CPU side) builds all trees, batches and
interaction lists; the device SPMD program runs the four compute kernels
plus the two collectives. Per-rank structures are padded to common shapes
(see DESIGN.md on the static-LET tradeoff); every sentinel slot contributes
exactly zero. With targets == sources (the paper's test setting) the result
matches the single-device treecode to the same MAC error tolerance.

Capacity-padded LET schema (DESIGN.md §7): every stacked (P, ...) array —
per-rank tree/batch/list structures, the remote (LET) interaction lists,
and the halo exchange schedule — is padded into a fixed
`repro.core.eval.ShardedCapacities` budget (initial need x headroom,
geometric growth on overflow). The halo exchange runs a FIXED schedule of
`collective_permute` rounds, one per rank offset in the budget's symmetric
range; rounds a particular build does not need are fully masked (all -1
send tables exchange zeros that no interaction list references). Budgeted
builds therefore produce shape-identical pytrees with an identical static
closure, and the jitted SPMD executable is shared between them through a
module cache — `replan` after particle drift reuses the compiled program
instead of retracing (the MD contract; see `repro.dynamics`).

Space/params protocol v2: the cross-rank MAC runs on MINIMUM-IMAGE center
distances with the fold-free acceptance condition under a `PeriodicBox`
(RCB slabs tile the wrapped cell; a boundary slab's neighbors across the
cell edge are reached through the same remote lists as its geometric
neighbors), and kernel parameter values ride into the SPMD program as a
replicated traced argument — parameter sweeps reuse the compiled
executable.

Charges are staged on DEVICE through the plan's rank tables
(`rank_gather` / `input_pos` — the same tables the dynamics adapter uses),
not host-side; `TreecodeConfig.donate_charges` donates the staged
(P, per_pad) slab to the SPMD executable, whose phi output has the
identical shape and aliases it — iterative charge loops run
allocation-free.

`ShardedPlan` implements the solver-wide execution-plan protocol
(`execute` / `potential_and_forces` / `stats` / `replan`); build one via
``TreecodeSolver.plan(points, nranks=P)``. Arbitrary N is supported: RCB
produces near-balanced slabs and shorter slabs are zero-padded to the
common width (padded slots carry zero charge and are never gathered).
"""
from __future__ import annotations

import dataclasses
import time
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import cheby
from repro.core import eval as ceval
from repro.core.api import TreecodeConfig, lift_params
from repro.core import interaction
from repro.core.interaction import batch_half_extents, mac_accept
from repro.core.potentials import Kernel
from repro.core.tree import Tree
from repro.distributed.rcb import RCB, rcb_partition
from repro.kernels import ops
from repro.launch.mesh import auto_mesh
from repro.obs import events as _events
from repro.obs import trace as _trace
from repro.obs.occupancy import static_occupancy as _static_occ


def _traverse_remote(cfg: TreecodeConfig, tree: Tree, bc, br, bhw):
    """Traverse one remote tree for one batch under the space-aware MAC.

    Yields ("approx", node, theta_margin, fold_margin) (raw margins) and
    ("direct", leaf_slots) events. One traversal drives both the
    remote-approx lists and the remote-direct (halo) lists so both apply
    identical acceptance (min-image distances, fold-free approximation).

    Verlet skin: remote pairs within the skin of the MAC boundary are
    DEMOTED to direct (their leaves enter the halo lists) instead of
    being dual-listed — runtime gating a remote pair would require halo
    leaves for clusters that are usually served by the gathered q_hat,
    inflating permute traffic for pairs that rarely flip. Demotion keeps
    the exactness horizon (lists valid while drift <= skin/2) and keeps
    remote approx margins above the same slack floor as local ones."""
    npts = (cfg.degree + 1) ** 3
    space = cfg.space
    thr_theta = interaction.theta_drift_rate(cfg.theta) * 0.5 * cfg.skin
    thr_fold = interaction.fold_drift_rate() * 0.5 * cfg.skin
    stack = [0]
    while stack:
        node = stack.pop()
        d = bc - tree.center[node]
        chw = 0.5 * (tree.hi[node] - tree.lo[node])
        dist_ok, fold_ok, t_margin, f_margin = mac_accept(
            space, cfg.theta, d, br, tree.radius[node], bhw + chw)
        mac = dist_ok and fold_ok and npts < tree.count[node]
        if mac and t_margin > thr_theta and f_margin > thr_fold:
            yield ("approx", node, float(t_margin), float(f_margin))
        elif not mac and not tree.is_leaf[node] \
                and not (dist_ok and npts >= tree.count[node]):
            stack.extend(int(k) for k in tree.children[node] if k >= 0)
        else:  # leaf, small-but-separated cluster, or skin-demoted pair
            if tree.is_leaf[node]:
                slots = [int(tree.leaf_index[node])]
            else:
                slots = tree.leaves_in_range(
                    int(tree.start[node]),
                    int(tree.count[node])).tolist()
            yield ("direct", slots)


def _remote_lists(cfg: TreecodeConfig, plans, nranks: int):
    """One cross-rank traversal pass: for every rank r, traverse every
    other rank s's tree with the same uniform MAC.

    Returns (approx, direct, halo_need, theta_slack, fold_slack):
      approx[r]:   [(batch, src rank, node)] remote approx accepts
      direct[r]:   [(batch, src rank, leaf slot)] remote direct hits
      halo_need:   {(src s, dst r): set(leaf slots)} — the halo traffic
      theta/fold_slack: min RAW margins over remote approx accepts (the
                   cross-rank part of the v2 drift budgets; skin-demoted
                   pairs never enter the minima)."""
    approx: List[list] = [[] for _ in range(nranks)]
    direct: List[list] = [[] for _ in range(nranks)]
    halo_need: Dict[Tuple[int, int], set] = {}
    theta_slack = float("inf")
    fold_slack = float("inf")

    for r in range(nranks):
        batches = plans[r].batches
        bhw = batch_half_extents(batches)
        for s in range(nranks):
            if s == r:
                continue
            tree: Tree = plans[s].tree
            for b in range(batches.num_batches):
                for ev in _traverse_remote(cfg, tree, batches.center[b],
                                           batches.radius[b], bhw[b]):
                    if ev[0] == "approx":
                        _, node, t_margin, f_margin = ev
                        approx[r].append((b, s, node))
                        theta_slack = min(theta_slack, t_margin)
                        if np.isfinite(f_margin):
                            fold_slack = min(fold_slack, f_margin)
                    else:
                        halo_need.setdefault((s, r), set()).update(ev[1])
                        for sl in ev[1]:
                            direct[r].append((b, s, sl))
    return approx, direct, halo_need, theta_slack, fold_slack


def _rank_need(plans) -> dict:
    """Element-wise max of the per-rank single-device dims: the `rank`
    entry of the sharded needs dict (`ShardedCapacities.for_need`)."""
    dims = [ceval._plan_dims(pl) for pl in plans]
    need = {k: max(d[k] for d in dims)
            for k in ("num_batches", "batch_width", "num_leaves",
                      "leaf_width", "num_nodes", "approx_width",
                      "direct_width", "skin_direct_width", "depth")}
    rows = [1] * need["depth"]
    widths = [1] * need["depth"]
    for d in dims:
        for i, v in enumerate(d["bucket_rows"]):
            rows[i] = max(rows[i], v)
        for i, v in enumerate(d["bucket_widths"]):
            widths[i] = max(widths[i], v)
    need["bucket_rows"] = tuple(rows)
    need["bucket_widths"] = tuple(widths)
    need["upward_rows"] = ()
    # Hybrid-depth device builds carry per-sparse-level row budgets;
    # ranks share one depth, so element-wise max aligns level-for-level
    # (host builds leave the tuples empty).
    for key in ("sparse_rows", "batch_sparse_rows"):
        tups = [d.get(key, ()) for d in dims]
        ln = max((len(t) for t in tups), default=0)
        need[key] = tuple(max((t[i] for t in tups if len(t) > i),
                              default=1) for i in range(ln))
    return need


def _max_per_batch(events_per_rank) -> int:
    """Widest per-(rank, batch) event list — a remote list width need."""
    w = 1
    for events in events_per_rank:
        counts: Dict[int, int] = {}
        for b, *_ in events:
            counts[b] = counts.get(b, 0) + 1
            w = max(w, counts[b])
    return w


# ---------------------------------------------------------------------------
# SPMD executable cache
# ---------------------------------------------------------------------------
#
# The jitted shard_map program depends only on budget-derived statics:
# (mesh, axis, degree, level count, the fixed permute-round schedule, the
# stripped kernel, space, backend, the array-key set, the kernel-params
# tree structure, donation). Two plans padded into equal
# `ShardedCapacities` share every component, so they receive the SAME
# callable — and therefore the same jit cache — from this module cache.
# That identity is what lets `replan` (and the MD engine's jitted step
# that closes over the callable) survive a host rebuild without retracing.
#
# Bounded: each distinct config/budget pins a compiled program (and its
# mesh) for as long as it lives in the cache, so old entries are evicted
# FIFO beyond _SPMD_CACHE_MAX. Holders that rely on identity across
# rebuilds (the dynamics adapter) keep their own strong reference and
# re-fetch only when their budget grows, so eviction cannot hand them a
# fresh equivalent object mid-run.

_SPMD_CACHE: "Dict[tuple, object]" = {}
_SPMD_CACHE_MAX = 32


def _spmd_executable(*, mesh, axis: str, degree: int, depth: int,
                     perm_rounds, kernel: Kernel, space, backend: str,
                     keys: Tuple[str, ...], params_treedef, donate: bool,
                     theta: float, skin: float):
    key = (mesh, axis, degree, depth, perm_rounds, kernel, space, backend,
           keys, params_treedef, donate, theta, skin)
    fn = _SPMD_CACHE.get(key)
    if fn is None:
        fn = _build_spmd_fn(mesh=mesh, axis=axis, degree=degree,
                            depth=depth, perm_rounds=perm_rounds,
                            kernel=kernel, space=space, backend=backend,
                            keys=keys, params_treedef=params_treedef,
                            donate=donate, theta=theta, skin=skin)
        while len(_SPMD_CACHE) >= _SPMD_CACHE_MAX:
            _SPMD_CACHE.pop(next(iter(_SPMD_CACHE)))
        _SPMD_CACHE[key] = fn
        # A cache miss constructs a fresh jit wrapper; the XLA compile
        # itself happens at its first call (and is logged by that call
        # site, e.g. the MD engine's finish wrapper). Recording the miss
        # with the full statics key makes "why did this retrace" a
        # query: a second spmd_cache_miss for one budget IS the answer.
        _events.record(
            "spmd_cache_miss", "spmd",
            key=(degree, depth, len(perm_rounds), backend, donate,
                 theta, skin),
            site="distributed.bltc._spmd_executable",
            owner="distributed.bltc")
    return fn


def _build_spmd_fn(*, mesh, axis, degree, depth, perm_rounds, kernel,
                   space, backend, keys, params_treedef, donate,
                   theta=0.7, skin=0.0):
    def spmd(args, q, params):
        a = {k: v[0] for k, v in args.items()}  # strip sharded lead dim
        q_sorted = q[0][a["charges_perm"]]

        # local modified charges (scratch row stays zero: gather all -1)
        lo, hi = a["node_lo"], a["node_hi"]
        qhat = jnp.zeros((lo.shape[0], (degree + 1) ** 3),
                         q_sorted.dtype)
        for lvl in range(depth):
            gidx = a[f"bucket_gather_{lvl}"]
            nodes = a[f"bucket_nodes_{lvl}"]
            center = 0.5 * (lo[nodes] + hi[nodes])
            pts, qb = ceval._gathered(a["src_sorted"], q_sorted, gidx,
                                      fill=center)
            qh = ops.modified_charges(pts, qb, lo[nodes], hi[nodes],
                                      degree=degree, backend=backend)
            qhat = qhat.at[nodes].add(qh)  # scratch row may accumulate

        grids = cheby.cluster_grid(lo, hi, degree)
        tgt = a["tgt_batched"]
        if skin > 0.0:
            # Verlet-skin runtime gate over this rank's LOCAL dual lists
            # (remote skin pairs are demoted at build; DESIGN.md §4) —
            # the same routing the single-device executor applies.
            approx_idx, direct_idx = ceval._skin_routed_lists(
                a, theta, space)
        else:
            approx_idx, direct_idx = a["approx_idx"], a["direct_idx"]
        phi = ops.batch_cluster_eval(approx_idx, tgt, grids, qhat,
                                     params, kernel=kernel, space=space,
                                     backend=backend)
        leaf_pts, leaf_q = ceval._gathered(
            a["src_sorted"], q_sorted, a["leaf_gather"])
        phi += ops.batch_cluster_eval(direct_idx, tgt, leaf_pts,
                                      leaf_q, params, kernel=kernel,
                                      space=space, backend=backend)

        # LET phase 1: gather every rank's tree metadata + q_hat
        g_lo = jax.lax.all_gather(lo, axis)        # (P, M, 3)
        g_hi = jax.lax.all_gather(hi, axis)
        g_qhat = jax.lax.all_gather(qhat, axis)    # (P, M, K3)
        g_grids = cheby.cluster_grid(g_lo.reshape(-1, 3),
                                     g_hi.reshape(-1, 3), degree)
        phi += ops.batch_cluster_eval(
            a["remote_approx_idx"], tgt, g_grids,
            g_qhat.reshape(-1, (degree + 1) ** 3), params,
            kernel=kernel, space=space, backend=backend)

        # LET phase 2: halo leaf exchange — one permute round per budget
        # offset. Rounds this build does not need have all -1 send
        # tables: they permute zero buffers that remote_direct_idx never
        # references (the masked tail rounds of DESIGN.md §7).
        recv_pts, recv_q = [], []
        for i, (off, pairs) in enumerate(perm_rounds):
            send_idx = a[f"halo_send_{i}"]         # (H,) leaf slots
            safe = jnp.maximum(send_idx, 0)
            valid = (send_idx >= 0)[:, None]
            sp = jnp.where(valid[..., None], leaf_pts[safe], 0.0)
            sq = jnp.where(valid, leaf_q[safe], 0.0)
            rp = jax.lax.ppermute(sp, axis, pairs)
            rq = jax.lax.ppermute(sq, axis, pairs)
            recv_pts.append(rp)
            recv_q.append(rq)
        if recv_pts:
            halo_pts = jnp.concatenate(recv_pts, axis=0)
            halo_q = jnp.concatenate(recv_q, axis=0)
            phi += ops.batch_cluster_eval(
                a["remote_direct_idx"], tgt, halo_pts, halo_q, params,
                kernel=kernel, space=space, backend=backend)

        out = phi.reshape(-1)[a["gather_index"]]
        return out[None]

    spec = jax.sharding.PartitionSpec(axis)
    rep = jax.sharding.PartitionSpec()
    specs = {k: spec for k in keys}
    param_specs = jax.tree.unflatten(
        params_treedef, [rep] * params_treedef.num_leaves)
    return jax.jit(
        jax.shard_map(spmd, mesh=mesh,
                      in_specs=(specs, spec, param_specs),
                      out_specs=spec, check_vma=False),
        donate_argnums=(1,) if donate else ())


@jax.jit
def _stage_charges(rank_gather, q):
    """(P, per_pad) rank slabs from (N,) charges through the -1-padded
    gather table; padded slots carry exactly zero."""
    valid = rank_gather >= 0
    return jnp.where(valid, q[jnp.maximum(rank_gather, 0)], 0.0)


@dataclasses.dataclass
class ShardedPlan:
    """RCB + shard_map execution plan conforming to the solver protocol."""

    config: TreecodeConfig
    kernel: Kernel
    arrays: Dict[str, jnp.ndarray]      # leading dim P (shardable)
    perm_rounds: Tuple[Tuple[int, Tuple[Tuple[int, int], ...]], ...]
    depth: int                          # modified-charge level count
    nranks: int
    rcb: RCB
    scratch_node: int                   # padded node row (zero q_hat)
    per_pad: int                        # common padded slab width
    num_points: int
    padding_waste: float                # mean over per-rank local plans
    dtype: np.dtype
    # The fixed budget the stacked arrays are padded into; `replan` grows
    # it geometrically on overflow and otherwise reuses it unchanged, so
    # rebuilt plans share the compiled SPMD executable.
    capacities: "ceval.ShardedCapacities | None" = None
    # Device rank tables (shared with the dynamics adapter):
    #   rank_gather: (P, per_pad) input particle index per slab slot, -1 pad
    #   input_pos:   (N,) flat (rank * per_pad + slot) of each input index
    rank_gather: Optional[jnp.ndarray] = None
    input_pos: Optional[jnp.ndarray] = None
    # Traced kernel parameter defaults (lifted from the kernel; override
    # per call via execute(kernel_params=...)).
    kernel_params: object = ()
    # Min MAC slack over local AND remote approx lists: the drift budget
    # within which a topology-preserving refit keeps every list valid.
    # `mac_slack` is the v1 compat number; `theta_slack`/`fold_slack` are
    # the RAW v2 budgets (min over safe local + remote pairs of each
    # margin, skin-demoted/gated pairs excluded; DESIGN.md §4).
    mac_slack: float = float("inf")
    theta_slack: float = float("inf")
    fold_slack: float = float("inf")
    mesh: Optional[object] = None
    axis: str = "data"
    # Host build wall time per stage (ms): rcb / local_plans /
    # let_traversal / pad / commit — stats()["build_phases"].
    build_ms: Dict[str, float] = dataclasses.field(default_factory=dict)
    # Strong per-instance refs to the fetched SPMD executables, keyed by
    # (donate, grad): plans must not lose their compiled traces to
    # module-cache FIFO eviction (the module cache shares across plans;
    # these pin for this plan).
    _fns: Dict[Tuple[bool, bool], object] = dataclasses.field(
        default_factory=dict, repr=False)

    # -- protocol aliases
    @property
    def num_targets(self) -> int:
        return self.num_points

    @property
    def num_sources(self) -> int:
        return self.num_points

    @property
    def space(self):
        return self.config.space

    @property
    def skin(self) -> float:
        """Verlet-skin radius the interaction lists were built with."""
        return self.config.skin

    # ------------------------------------------------------------------
    # host-side construction
    # ------------------------------------------------------------------

    @classmethod
    def build(cls, points: np.ndarray, cfg: TreecodeConfig, nranks: int,
              *, mesh=None, axis: str = "data",
              kernel: Optional[Kernel] = None,
              capacities="auto") -> "ShardedPlan":
        """Host-side setup: RCB, per-rank local plans, cross-rank LET
        lists, and capacity padding of everything into one fixed budget.

        `capacities`: "auto" (default) budgets this build's own needs
        with headroom; an explicit `ShardedCapacities` (e.g. a previous
        plan's, via `replan`) is grown to fit and otherwise reused
        verbatim, keeping the padded pytree shape-identical."""
        with _trace.span("plan.build_sharded"):
            return cls._build_impl(points, cfg, nranks, mesh=mesh,
                                   axis=axis, kernel=kernel,
                                   capacities=capacities)

    @classmethod
    def _build_impl(cls, points, cfg, nranks, *, mesh, axis, kernel,
                    capacities):
        points = np.asarray(cfg.space.wrap(np.asarray(points)))
        dtype = points.dtype
        build_ms: Dict[str, float] = {}
        _t = time.perf_counter()
        with _trace.span("plan.rcb"):
            rcb = rcb_partition(points, nranks)
        build_ms["rcb"] = (time.perf_counter() - _t) * 1e3

        _t = time.perf_counter()
        with _trace.span("plan.local_plans"):
            slabs = [points[rcb.perm[rcb.starts[r]:rcb.starts[r + 1]]]
                     for r in range(nranks)]
            kw = dict(theta=cfg.theta, degree=cfg.degree,
                      leaf_size=cfg.leaf_size,
                      batch_size=cfg.resolved_batch_size(),
                      space=cfg.space, skin=cfg.skin)
            if cfg.build_backend == "device":
                # Per-rank LOCAL device builds. Pin ONE dense-octree
                # depth (source and target) across ranks, so every
                # rank's budget has the same level structure and the
                # per-rank arrays stack into one (P, ...) pytree.
                from repro.devtree import build as _devtree
                d_src = max(_devtree.depth_for(len(s), cfg.leaf_size)
                            for s in slabs)
                d_tgt = max(
                    _devtree.depth_for(len(s), cfg.resolved_batch_size())
                    for s in slabs)
                plans = [_devtree.prepare_plan_device(
                    slab, slab, depth=d_src, batch_depth=d_tgt, **kw)
                    for slab in slabs]
            else:
                plans = [ceval.prepare_plan(slab, slab, **kw)
                         for slab in slabs]
        build_ms["local_plans"] = (time.perf_counter() - _t) * 1e3

        _t = time.perf_counter()
        with _trace.span("plan.let_traversal"):
            remote_approx, remote_direct, halo_need, r_theta, r_fold = \
                _remote_lists(cfg, plans, nranks)
        build_ms["let_traversal"] = (time.perf_counter() - _t) * 1e3
        theta_slack = min([r_theta] + [pl.theta_slack for pl in plans])
        fold_slack = min([r_fold] + [pl.fold_slack for pl in plans])
        mac_slack = interaction.scaled_mac_slack(cfg.theta, theta_slack,
                                                 fold_slack)

        # ---- resolve the capacity budget from this build's needs
        need = dict(
            nranks=nranks,
            rank=_rank_need(plans),
            slab_width=rcb.max_count(),
            remote_approx_width=_max_per_batch(remote_approx),
            remote_direct_width=_max_per_batch(remote_direct),
            halo_offsets=tuple(sorted({r - s for (s, r) in halo_need})),
            halo_width=max([len(v) for v in halo_need.values()] + [1]),
        )
        if capacities is None or capacities == "auto":
            caps = ceval.ShardedCapacities.for_need(need)
        elif isinstance(capacities, ceval.ShardedCapacities):
            caps = capacities.grown_to_fit(need)
        else:
            raise TypeError(
                "sharded capacities must be 'auto' or a "
                f"repro.core.eval.ShardedCapacities, got "
                f"{type(capacities).__name__}")

        _t = time.perf_counter()
        _pad_span = _trace.span("plan.pad")
        _pad_span.__enter__()
        R = caps.rank
        b_pad, nb_pad = R.num_batches, R.batch_width
        l_pad, nl_pad = R.num_leaves, R.leaf_width
        m_pad, scratch = R.num_nodes, R.scratch_node
        a_pad, d_pad = R.approx_width, R.direct_width
        sd_pad = R.skin_direct_width
        depth = R.depth
        per_pad = caps.slab_width

        # ---- halo schedule: the budget's FIXED permute rounds; received
        # slot of each (s -> r) leaf indexes into round-major concatenated
        # buffers of the common budget width.
        halo_slot: Dict[Tuple[int, int], Dict[int, int]] = {}
        halo_send = []
        for i, off in enumerate(caps.halo_offsets):
            tbl = np.full((nranks, caps.halo_width), -1, np.int64)
            base = i * caps.halo_width
            for (s, r), slots in halo_need.items():
                if r - s != off:
                    continue
                ordered = sorted(slots)
                tbl[s, :len(ordered)] = ordered
                halo_slot[(s, r)] = {slot: base + j
                                     for j, slot in enumerate(ordered)}
            halo_send.append(tbl)

        perm_rounds = tuple(
            (off, tuple((s, s + off) for s in range(nranks)
                        if 0 <= s + off < nranks))
            for off in caps.halo_offsets)

        def _pad_events(events_per_rank, width, value_of):
            """(batch, ...) event lists -> (P, b_pad, width) -1-padded.

            `value_of(r, ev)` maps a destination rank + event to the
            stored index; widths are guaranteed by the budget."""
            out = np.full((nranks, b_pad, width), -1, np.int64)
            fill = np.zeros((nranks, b_pad), np.int64)
            for r, events in enumerate(events_per_rank):
                for ev in events:
                    b = ev[0]
                    out[r, b, fill[r, b]] = value_of(r, ev)
                    fill[r, b] += 1
            return out

        remote_approx_idx = _pad_events(
            remote_approx, caps.remote_approx_width,
            lambda r, ev: ev[1] * m_pad + ev[2])
        remote_direct_idx = _pad_events(
            remote_direct, caps.remote_direct_width,
            lambda r, ev: halo_slot[(ev[1], r)][ev[2]])

        # ---- stack per-rank padded arrays
        def stack(field, shape, value=0, recompute=None):
            outs = []
            for pl in plans:
                a = np.asarray(jax.device_get(pl.arrays[field]))
                if recompute is not None:
                    a = recompute(pl, a)
                outs.append(ceval._pad2(a, shape, value))
            return np.stack(outs)

        def fix_gather_index(pl, gi):
            old_nb = pl.arrays["tgt_batched"].shape[1]
            row, slot = gi // old_nb, gi % old_nb
            return (row * nb_pad + slot).astype(np.int32)

        arrays = {
            "src_sorted": stack("src_sorted", (per_pad, 3)),
            "charges_perm": stack("src_perm", (per_pad,)),
            "tgt_batched": stack("tgt_batched", (b_pad, nb_pad, 3)),
            "tgt_mask": stack("tgt_mask", (b_pad, nb_pad), value=False),
            "gather_index": stack("gather_index", (per_pad,),
                                  recompute=fix_gather_index),
            "leaf_gather": stack("leaf_gather", (l_pad, nl_pad), value=-1),
            "node_lo": stack("node_lo", (m_pad, 3)),
            "node_hi": stack("node_hi", (m_pad, 3), value=1),
            "approx_idx": stack("approx_idx", (b_pad, a_pad), value=-1),
            "direct_idx": stack("direct_idx", (b_pad, d_pad), value=-1),
            "approx_skin": stack("approx_skin", (b_pad, a_pad), value=0),
            "skin_direct": stack("skin_direct", (b_pad, sd_pad), value=-1),
            "skin_direct_node": stack("skin_direct_node", (b_pad, sd_pad),
                                      value=-1),
            "remote_approx_idx": remote_approx_idx.astype(np.int32),
            "remote_direct_idx": remote_direct_idx.astype(np.int32),
        }
        for lvl in range(depth):
            shape = (R.bucket_rows[lvl], R.bucket_widths[lvl])
            gs, ns = [], []
            for pl in plans:
                bg, bn = pl.arrays["bucket_gather"], pl.arrays["bucket_nodes"]
                if lvl < len(bg):
                    g = ceval._pad2(np.asarray(jax.device_get(bg[lvl])),
                                    shape, -1)
                    n = ceval._pad2(np.asarray(jax.device_get(bn[lvl])),
                                    shape[:1], scratch)
                else:
                    g = np.full(shape, -1, np.int32)
                    n = np.full(shape[:1], scratch, np.int32)
                gs.append(g)
                ns.append(n)
            arrays[f"bucket_gather_{lvl}"] = np.stack(gs).astype(np.int32)
            arrays[f"bucket_nodes_{lvl}"] = np.stack(ns).astype(np.int32)
        for i, tbl in enumerate(halo_send):
            arrays[f"halo_send_{i}"] = tbl.astype(np.int32)

        # ---- commit everything to its canonical mesh sharding at build
        # time. Fresh (uncommitted) arrays and the committed outputs of a
        # previously compiled step have different jit signatures, so a
        # rebuild that handed the MD engine uncommitted arrays would
        # retrace the step once even at identical shapes; committing here
        # keeps one stable signature across every rebuild.
        _pad_span.__exit__(None, None, None)
        build_ms["pad"] = (time.perf_counter() - _t) * 1e3
        if mesh is None:
            mesh = auto_mesh((nranks,), (axis,))
        sharded = jax.sharding.NamedSharding(
            mesh, jax.sharding.PartitionSpec(axis))
        replicated = jax.sharding.NamedSharding(
            mesh, jax.sharding.PartitionSpec())
        _t = time.perf_counter()
        with _trace.span("plan.commit"):
            arrays = {k: jax.device_put(v, sharded)
                      for k, v in arrays.items()}
        build_ms["commit"] = (time.perf_counter() - _t) * 1e3

        # ---- device rank tables (charge staging + dynamics adapter)
        rank_gather = np.full((nranks, per_pad), -1, np.int64)
        input_pos = np.empty(points.shape[0], np.int64)
        for r in range(nranks):
            idx = rcb.perm[rcb.starts[r]:rcb.starts[r + 1]]
            rank_gather[r, :len(idx)] = idx
            input_pos[idx] = r * per_pad + np.arange(len(idx))

        waste = float(np.mean([pl.padding_waste for pl in plans]))
        kernel = kernel or cfg.make_kernel()
        return cls(config=cfg, kernel=kernel,
                   arrays=arrays, perm_rounds=perm_rounds, depth=depth,
                   nranks=nranks, rcb=rcb, scratch_node=scratch,
                   per_pad=per_pad, num_points=points.shape[0],
                   padding_waste=waste, dtype=np.dtype(dtype),
                   capacities=caps,
                   rank_gather=jax.device_put(
                       rank_gather.astype(np.int32), sharded),
                   input_pos=jax.device_put(
                       input_pos.astype(np.int32), replicated),
                   kernel_params=lift_params(kernel, np.dtype(dtype)),
                   mesh=mesh, axis=axis, mac_slack=mac_slack,
                   theta_slack=theta_slack, fold_slack=fold_slack,
                   build_ms=build_ms)

    # ------------------------------------------------------------------
    # device execution
    # ------------------------------------------------------------------

    def _spmd_fn(self, donate: bool = False, grad: bool = False):
        """The shared jitted shard_map executable
        (arrays, q_rank, params) -> phi_rank.

        Resolved from the module SPMD cache by budget-derived statics, so
        every plan padded into the same `ShardedCapacities` (every
        `replan` in an MD run) receives the SAME callable and reuses its
        compiled traces across charge vectors, kernel parameter values,
        AND host rebuilds.

        `donate=True` donates the staged charge slab to the executable —
        phi_rank has the identical (P, per_pad) shape/dtype, so XLA
        aliases the output into it (the `donate_charges` contract for
        iterative loops). The forces path must NOT use the donating
        variant: it reuses one slab across three JVP evaluations.

        `backend="auto"` resolves by the platform rule of
        `ops.resolve_backend` (Pallas on a TPU). `grad=True` is the
        variant the forces path differentiates: the Pallas kernels have
        no JVP rule, so it runs `ops.autodiff_backend`, exactly as the
        single-device forces do."""
        variant = (donate, grad)
        held = self._fns.get(variant)
        if held is not None:
            return held
        cfg = self.config
        if self.mesh is None:
            self.mesh = auto_mesh((self.nranks,), (self.axis,))
        resolve = ops.autodiff_backend if grad else ops.resolve_backend
        fn = _spmd_executable(
            mesh=self.mesh, axis=self.axis, degree=cfg.degree,
            depth=self.depth, perm_rounds=self.perm_rounds,
            kernel=self.kernel.stripped(), space=cfg.space,
            backend=resolve(cfg.backend),
            keys=tuple(sorted(self.arrays)),
            params_treedef=jax.tree.structure(self.kernel_params),
            donate=donate, theta=cfg.theta, skin=cfg.skin)
        self._fns[variant] = fn
        return fn

    def _rank_charges(self, charges) -> jnp.ndarray:
        """(P, per_pad) rank-major charge slabs, zero-padded, ON DEVICE
        (the module-level `_stage_charges` jit: the gather table is a
        traced argument, so every plan — and every within-budget replan
        — shares its compiled traces). The (N,) input cannot alias the
        padded slab output, so no donation is requested here;
        `donate_charges` instead donates the STAGED slab to the SPMD
        executable (see `_spmd_fn`), whose phi output has the identical
        shape."""
        q = jnp.asarray(charges)
        if q.dtype != self.dtype:
            q = q.astype(self.dtype)
        return _stage_charges(self.rank_gather, q)

    def _params(self, kernel_params):
        if kernel_params is None:
            return self.kernel_params
        p = self.kernel.normalize_params(kernel_params)
        return jax.tree.map(lambda v: jnp.asarray(v, dtype=self.dtype), p)

    def _unrank(self, per_rank: jnp.ndarray) -> jnp.ndarray:
        """Gather (P, per_pad, ...) rank-major results to input order
        (a device gather through `input_pos` — no host round trip)."""
        flat = per_rank.reshape((-1,) + per_rank.shape[2:])
        return flat[self.input_pos]

    def execute(self, charges, kernel_params=None) -> jnp.ndarray:
        """Potentials at all points (input order), SPMD over the mesh.

        Charges are staged into rank-major padded slabs on device via the
        plan's rank tables; with `donate_charges` the staged slab is
        donated to the SPMD executable (phi aliases it, so iterative
        loops run allocation-free). `kernel_params` overrides the kernel
        parameter values for this call without recompiling."""
        fn = self._spmd_fn(donate=self.config.donate_charges)
        with _trace.span("eval.execute_sharded"):
            phi_rank, _ = _events.log_compiles(
                "spmd", fn, self.arrays, self._rank_charges(charges),
                self._params(kernel_params),
                key=lambda: repr(self.capacities),
                site="ShardedPlan.execute", owner="distributed.bltc")
        return self._unrank(phi_rank)

    def potential_and_forces(self, charges, weights=None,
                             kernel_params=None):
        """(phi, F) with F_i = -w_i * grad_x phi(x_i), input order.

        Forces come from three forward JVPs through the SPMD program
        w.r.t. the target slab (collectives are linear, so the tangents
        flow through all_gather/ppermute exactly). `weights` defaults to
        the charges (the physical force on charge q_i)."""
        fn = self._spmd_fn(grad=True)
        # weights first: with weights=None they default to the charges,
        # which must be read before anything could consume their buffer.
        w = jnp.asarray(charges if weights is None else weights,
                        self.dtype)
        q_rank = self._rank_charges(charges)
        params = self._params(kernel_params)
        rest = {k: v for k, v in self.arrays.items() if k != "tgt_batched"}
        tgt = self.arrays["tgt_batched"]

        def phi_of(t):
            return fn(dict(rest, tgt_batched=t), q_rank, params)

        phi_rank, grads = None, []
        for d in range(3):
            tangent = jnp.zeros_like(tgt).at[..., d].set(1.0)
            phi_rank, dphi = jax.jvp(phi_of, (tgt,), (tangent,))
            grads.append(dphi)
        g_rank = jnp.stack(grads, axis=-1)          # (P, per_pad, 3)
        phi = self._unrank(phi_rank)
        g = self._unrank(g_rank)
        return phi, -w[:, None] * g

    def stats(self) -> dict:
        """Geometry / cost / budget counters for the sharded strategy:
        rank balance, padded slab width, the fixed halo-round schedule
        (total rounds vs the rounds this build actually uses), padding
        waste, and the full `ShardedCapacities` budget."""
        counts = self.rcb.counts()
        caps = self.capacities
        active = sum(
            1 for i in range(len(self.perm_rounds))
            if bool((np.asarray(self.arrays[f"halo_send_{i}"]) >= 0).any()))
        return dict(
            strategy="sharded",
            nranks=self.nranks,
            num_targets=self.num_points,
            num_sources=self.num_points,
            rank_counts=counts.tolist(),
            slab_pad=self.per_pad,
            halo_rounds=len(self.perm_rounds),
            halo_rounds_active=active,
            padding_waste=self.padding_waste,
            dtype=str(self.dtype),
            space=repr(self.config.space),
            mac_slack=self.mac_slack,
            theta_slack=self.theta_slack,
            fold_slack=self.fold_slack,
            skin=self.config.skin,
            capacity_padded=caps is not None,
            # Observability (repro.obs): host build wall time per stage
            # and padded-vs-real utilization of the stacked arrays (all
            # ranks pooled).
            build_phases=dict(self.build_ms),
            occupancy=_static_occ(self),
            **({"capacities": dataclasses.asdict(caps)} if caps else {}),
        )

    def replan(self, targets, sources=None, *,
               capacities="keep") -> "ShardedPlan":
        """Rebuild geometry for moved particles under the same config.

        `capacities="keep"` (default) re-pads the new geometry into this
        plan's own budget (growing it geometrically if the new build no
        longer fits), so the rebuilt plan is pytree-shape-identical and
        shares the compiled SPMD executable — the sharded MD rebuild
        path. Pass "auto" to re-budget from the new build's needs, or an
        explicit `repro.core.eval.ShardedCapacities`."""
        if sources is not None and sources is not targets:
            raise ValueError("sharded plans require targets == sources")
        if capacities == "keep":
            capacities = self.capacities
        points = np.asarray(targets, self.dtype)
        return ShardedPlan.build(points, self.config, self.nranks,
                                 mesh=self.mesh, axis=self.axis,
                                 kernel=self.kernel, capacities=capacities)


# ---------------------------------------------------------------------------
# Back-compat aliases for the pre-unification API (PR 1). `DistPlan`,
# `prepare_distributed` and `distributed_execute` are thin shims over
# `ShardedPlan`; prefer `TreecodeSolver.plan(points, nranks=P)`.
# ---------------------------------------------------------------------------

DistPlan = ShardedPlan


def prepare_distributed(points: np.ndarray, cfg: TreecodeConfig,
                        nranks: int) -> ShardedPlan:
    """Deprecated alias: build a `ShardedPlan`."""
    return ShardedPlan.build(np.asarray(points), cfg, nranks)


def distributed_execute(plan: ShardedPlan, charges: np.ndarray,
                        cfg: TreecodeConfig = None, mesh=None,
                        axis: str = "data") -> jnp.ndarray:
    """Deprecated alias for ``plan.execute(charges)``.

    The plan executes with the config captured at build time; passing a
    *different* cfg here (the old API allowed varying it between prepare
    and execute) is rejected loudly instead of silently ignored.
    """
    if cfg is not None and cfg != plan.config:
        raise ValueError(
            "distributed_execute received a cfg that differs from the one "
            "the plan was built with; rebuild via TreecodeSolver.plan "
            "(plans now bind their config at build time)")
    if mesh is not None and plan.mesh is None:
        plan.mesh = mesh
        plan.axis = axis
    return plan.execute(charges)
