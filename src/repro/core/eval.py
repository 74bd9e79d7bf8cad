"""Device evaluation pipeline: plan (host) -> execute (jit).

The host packs the tree, batches, and interaction lists into static padded
arrays once (`prepare_plan`); the jitted `execute` then computes

    modified charges (per-level kernels)  ->  cluster Chebyshev grids
    ->  approx kernel over approx lists   ->  direct kernel over leaf lists
    ->  un-permutation back to input order.

Separating plan from execute mirrors real treecode usage: boundary-element
and iterative solvers re-apply the same geometry to many charge vectors, so
`execute` takes charges as a fresh argument and everything geometric is
reused (and stays on device).

Padded widths are rounded up (`_round_up`) so that re-planning over moving
particles (MD) mostly reuses compiled executables.
"""
from __future__ import annotations

import dataclasses
import functools
import time
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import cheby
from repro.core.interaction import build_interaction_lists
from repro.core.potentials import Kernel
from repro.core.space import FREE as _FREE
from repro.core.tree import Batches, Tree, build_batches, build_tree
from repro.kernels import batch_cluster as _bc
from repro.kernels import ops
from repro.obs import trace as _trace


def _round_up(x: int, base: int = 8) -> int:
    return max(base, -(-x // base) * base)


def _round_pow2(x: int) -> int:
    return 1 << max(0, int(np.ceil(np.log2(max(x, 1)))))


@dataclasses.dataclass
class Plan:
    """Geometry-dependent, charge-independent device arrays + host trees."""

    arrays: dict                 # jnp pytree consumed by `execute`
    meta: Tuple                  # static: (degree, n_bucket_shapes, ...)
    tree: Tree                   # host copies for diagnostics / distribution
    batches: Batches
    padding_waste: float         # sentinel-slot fraction of kernel work
    num_targets: int
    num_sources: int
    # Min MAC slack of the approx lists (see InteractionLists.mac_slack):
    # the drift budget for topology-preserving refits. `mac_slack` is the
    # v1 single number (fold folded in at the theta rate); drift-budget
    # v2 tracks the RAW theta/fold margins separately (their own shrink
    # rates) plus the Verlet-skin radius the lists were built with.
    mac_slack: float = float("inf")
    theta_slack: float = float("inf")
    fold_slack: float = float("inf")
    skin: float = 0.0
    # When capacity-padded (see `Capacities`), the capacities the arrays
    # were padded to, and the scratch node row absorbing sentinel writes.
    capacities: "Capacities | None" = None
    scratch_node: int = -1
    # The Space the plan was built in (geometry wrapped at build time for
    # periodic boxes; the executors fold displacements to minimum image).
    space: object = _FREE
    # Host build-phase wall times in ms (tree_build / interaction_lists /
    # pack), measured unconditionally — the build is heavy host work, so
    # a few perf_counter reads are free. Surfaced via plan.stats().
    build_ms: Dict[str, float] = dataclasses.field(default_factory=dict)
    # Which builder produced the plan ("host" | "device") and, for the
    # device path, the `repro.devtree` metadata (dense-octree occupancy
    # masks, leaf/batch tables, permutations) that backs the lazy
    # Tree/Batches proxies. Host consumers never touch `dev` directly.
    build_backend: str = "host"
    dev: "dict | None" = None


def prepare_plan(
    targets: np.ndarray,
    sources: np.ndarray,
    *,
    theta: float,
    degree: int,
    leaf_size: int,
    batch_size: int,
    space=_FREE,
    skin: float = 0.0,
) -> Plan:
    """Host-side setup phase (tree build + traversal + packing).

    With a periodic `space`, coordinates are wrapped into the primary
    cell before the tree/batch build (boundary-straddling clusters split
    by construction) and the MAC traversal uses minimum-image center
    distances with the fold-free acceptance condition (see
    `repro.core.interaction`). `skin` is the Verlet-skin radius: pairs
    within the skin of the MAC boundary are dual-listed and gated by
    current distance at evaluation time (drift-budget v2)."""
    with _trace.span("plan.build"):
        return _prepare_plan_timed(
            targets, sources, theta=theta, degree=degree,
            leaf_size=leaf_size, batch_size=batch_size, space=space,
            skin=skin)


def _prepare_plan_timed(targets, sources, *, theta, degree, leaf_size,
                        batch_size, space, skin):
    build_ms: Dict[str, float] = {}
    # the HOST build path: positions land on the host by design — an
    # explicit device_get (visible to jax's transfer guard) instead of
    # an implicit np.asarray copy. Device builds never take this path.
    targets = np.asarray(space.wrap(jax.device_get(targets)))
    sources = np.asarray(space.wrap(jax.device_get(sources)))
    dtype = targets.dtype

    t0 = time.perf_counter()
    with _trace.span("plan.tree_build"):
        tree = build_tree(sources, leaf_size)
        batches = build_batches(targets, batch_size)
    t1 = time.perf_counter()
    build_ms["tree_build"] = (t1 - t0) * 1e3
    with _trace.span("plan.interaction_lists"):
        lists = build_interaction_lists(tree, batches, theta, degree, space,
                                        skin=skin)
    t2 = time.perf_counter()
    build_ms["interaction_lists"] = (t2 - t1) * 1e3
    with _trace.span("plan.pack"):
        host = _pack_host(targets, sources, tree, batches, lists, dtype)
    # Every host-to-device copy of the plan, in one place.
    with _trace.span("plan.copy"):
        arrays = jax.tree.map(jnp.asarray, host)
    meta = (degree,)
    build_ms["pack"] = (time.perf_counter() - t2) * 1e3
    return Plan(
        arrays=arrays, meta=meta, tree=tree, batches=batches,
        padding_waste=float(lists.padding_waste),
        num_targets=targets.shape[0], num_sources=sources.shape[0],
        mac_slack=float(lists.mac_slack),
        theta_slack=float(lists.theta_slack),
        fold_slack=float(lists.fold_slack),
        skin=float(skin), space=space, build_ms=build_ms,
    )


def _pack_host(targets, sources, tree, batches, lists, dtype) -> dict:
    """The plan's arrays as NumPy: the padded target batches, gather
    tables, interaction lists and per-level cluster buckets."""
    nb_pad = _round_up(batches.max_count)
    nl_pad = _round_up(tree.max_leaf_count)
    a_pad = _round_up(lists.approx.shape[1])
    d_pad = _round_up(lists.direct.shape[1])
    sd_pad = _round_up(lists.skin_direct.shape[1])

    def _pad_cols(a, width):
        return np.pad(a, ((0, 0), (0, width - a.shape[1])),
                      constant_values=-1)

    approx_idx = _pad_cols(lists.approx, a_pad).astype(np.int32)
    direct_idx = _pad_cols(lists.direct, d_pad).astype(np.int32)
    approx_skin = np.pad(
        lists.approx_skin, ((0, 0), (0, a_pad - lists.approx_skin.shape[1])),
        constant_values=0).astype(np.uint8)
    skin_direct = _pad_cols(lists.skin_direct, sd_pad).astype(np.int32)
    skin_direct_node = _pad_cols(lists.skin_direct_node,
                                 sd_pad).astype(np.int32)

    def _range_table(starts, counts, width, fill=-1):
        """(rows, width) table of [start, start+count) runs, `fill`-padded.

        One broadcast per table instead of a Python loop per row — at
        10^5 particles the per-row loops dominated the pack phase
        (~150 ms flat), swamping the actual array materialization.
        """
        ar = np.arange(width, dtype=np.int64)
        return np.where(ar[None, :] < counts[:, None],
                        starts[:, None] + ar[None, :], fill)

    # Targets packed batch-contiguously, padded per row. Batches are in
    # start order, so batch b owns tgt_sorted[start[b] : start[b]+count].
    nb = batches.num_batches
    tgt_sorted = targets[batches.perm]
    b_counts = batches.count.astype(np.int64)
    rows = np.repeat(np.arange(nb, dtype=np.int64), b_counts)
    within = np.arange(targets.shape[0]) - np.repeat(
        batches.start.astype(np.int64), b_counts)
    tgt_b = np.zeros((nb, nb_pad, 3), dtype)
    tgt_mask = np.zeros((nb, nb_pad), bool)
    tgt_b[rows, within] = tgt_sorted
    tgt_mask[rows, within] = True
    pos_of_batchorder = rows * nb_pad + within
    # phi_input[j] = phi_flat[gather_index[j]] for input target index j.
    inv_perm = np.argsort(batches.perm, kind="stable")
    gather_index = pos_of_batchorder[inv_perm].astype(np.int32)

    # Leaf gather table (leaf slot -> padded particle indices, tree order).
    leaf_gather = _range_table(tree.start[tree.leaf_ids],
                               tree.count[tree.leaf_ids], nl_pad)

    # Per-level cluster buckets for the modified-charge kernels. Padded
    # particle counts are bucketed to powers of two so moving-particle
    # re-plans hit the jit cache.
    bucket_gather, bucket_nodes = [], []
    for node_ids in tree.levels():
        m_pad = _round_pow2(int(tree.count[node_ids].max()))
        g = _range_table(tree.start[node_ids], tree.count[node_ids], m_pad)
        bucket_gather.append(g.astype(np.int32))
        bucket_nodes.append(np.asarray(node_ids, np.int32))

    return dict(
        src_sorted=sources[tree.perm],
        src_perm=np.asarray(tree.perm, np.int32),
        tgt_batched=tgt_b,
        gather_index=gather_index,
        leaf_gather=leaf_gather.astype(np.int32),
        node_lo=tree.lo.astype(dtype),
        node_hi=tree.hi.astype(dtype),
        approx_idx=approx_idx,
        direct_idx=direct_idx,
        # Verlet-skin dual lists + the target validity mask feeding the
        # runtime MAC gate (all--1 / all-False beyond the real rows).
        approx_skin=approx_skin,
        skin_direct=skin_direct,
        skin_direct_node=skin_direct_node,
        tgt_mask=tgt_mask,
        bucket_gather=tuple(bucket_gather),
        bucket_nodes=tuple(bucket_nodes),
        # Hierarchical (upward-pass) precompute tables, built lazily.
        parent_of=np.asarray(tree.parent, np.int32),
    )


def _gathered(src_sorted, q_sorted, gather, fill=None):
    """(rows, pad, 3) points and charges from a -1-padded gather table.

    `fill` (rows, 3) replaces padded coordinates — the modified-charge
    kernels pass the cluster center so padded slots stay INSIDE the box:
    a padded point outside the box makes the alternating barycentric
    denominator cancel to exactly 0 in f32 (observed at degree 10), and
    0/0 = NaN. Charges on padding are always 0."""
    safe = jnp.maximum(gather, 0)
    valid = gather >= 0
    fill_b = 0.0 if fill is None else fill[:, None, :]
    pts = jnp.where(valid[..., None], src_sorted[safe], fill_b)
    q = jnp.where(valid, q_sorted[safe], 0.0)
    return pts, q


def compute_qhat_direct(arrays, q_sorted, *, degree, backend):
    """Paper-faithful q_hat: every cluster from its own particles (Eq. 12).

    Cost O((n+1)^3 N log N) — this is the paper's precompute phase. The
    hierarchical alternative below reduces it to O((n+1)^3 N) exactly.
    """
    lo, hi = arrays["node_lo"], arrays["node_hi"]
    n1 = degree + 1
    qhat = jnp.zeros((lo.shape[0], n1 ** 3), q_sorted.dtype)
    for gidx, nodes in zip(arrays["bucket_gather"], arrays["bucket_nodes"]):
        center = 0.5 * (lo[nodes] + hi[nodes])
        pts, qb = _gathered(arrays["src_sorted"], q_sorted, gidx,
                            fill=center)
        qh = ops.modified_charges(
            pts, qb, lo[nodes], hi[nodes], degree=degree, backend=backend)
        qhat = qhat.at[nodes].set(qh)
    return qhat


def compute_qhat_hierarchical(arrays, q_sorted, *, degree, backend):
    """Upward-pass q_hat (beyond-paper, mathematically exact).

    Leaves are computed from particles; every internal cluster is computed
    from its children by barycentric Chebyshev-to-Chebyshev restriction:
    since L^parent_k is a degree-n polynomial per dimension, interpolating
    it on the child grid is exact, so

        qhat_p[k] = sum_child sum_k' ( prod_l L^p_{k_l}(s^c_{k'_l}) ) qhat_c[k'].

    Cost O((n+1)^3 N) for leaves + O(nodes (n+1)^4) for the pass — removes
    the log N factor from the paper's precompute with zero accuracy loss.
    """
    lo, hi = arrays["node_lo"], arrays["node_hi"]
    n1 = degree + 1
    nnodes = lo.shape[0]
    qhat = jnp.zeros((nnodes, n1 ** 3), q_sorted.dtype)

    # Leaf level(s): from particles. The deepest bucket per level contains a
    # mix of leaves and internals; computing from particles is exact for
    # both, so we seed every level bottom-up but only from-particles for
    # leaves, then overwrite internals by restriction.
    leaf_rows = arrays["leaf_node_ids"]
    center = 0.5 * (lo[leaf_rows] + hi[leaf_rows])
    pts, qb = _gathered(arrays["src_sorted"], q_sorted,
                        arrays["leaf_gather"], fill=center)
    qh_leaf = ops.modified_charges(
        pts, qb, lo[leaf_rows], hi[leaf_rows], degree=degree, backend=backend)
    qhat = qhat.at[leaf_rows].set(qh_leaf)

    w = cheby.bary_weights_1d(degree, q_sorted.dtype)
    s01 = cheby.cheb_points_1d(degree, q_sorted.dtype)

    for pairs in arrays["upward_pairs"]:  # deepest level first
        parents, children = pairs[:, 0], pairs[:, 1]
        # Per-dimension transfer rows T_l[k', k] = L^p_k(s^c_{k'}).
        rows = []
        eps = jnp.finfo(q_sorted.dtype).eps
        for ax in range(3):
            child_nodes = cheby.map_points(
                s01, lo[children, ax:ax + 1], hi[children, ax:ax + 1])
            parent_nodes = cheby.map_points(
                s01, lo[parents, ax:ax + 1], hi[parents, ax:ax + 1])
            # Scale-aware hit tolerance: child grids share corners with the
            # parent box up to rounding; snap within ~64 ulp of the span.
            tol = (64.0 * eps) * (hi[parents, ax] - lo[parents, ax])
            # y = child grid coords (P, n1c), s = parent nodes (P, 1, n1p).
            t, den = cheby.bary_terms(child_nodes, parent_nodes[:, None, :],
                                      w, tol=tol[:, None, None])
            rows.append(t / den[..., None])  # (P, n1_child, n1_parent)
        qc = qhat[children].reshape(-1, n1, n1, n1)
        contrib = jnp.einsum("pxa,pyb,pzc,pxyz->pabc",
                             rows[0], rows[1], rows[2], qc,
                             precision=jax.lax.Precision.HIGHEST)
        contrib = contrib.reshape(-1, n1 ** 3)
        qhat = qhat.at[parents].add(contrib)
    return qhat


_EXEC_OPTS = ("degree", "kernel", "space", "backend", "kahan", "precompute",
              "approx_r2", "theta", "skin")


def _skin_routed_lists(arrays: dict, theta: float, space):
    """Current-distance routing of the Verlet-skin dual lists.

    Re-tests every skin pair's MAC on the refitted geometry (the batch
    boxes come from the current target slab, the cluster boxes from
    node_lo/hi) and masks the losing side to the -1 sentinel the kernels
    skip: the approx slot while the MAC fails, the skin-direct slots
    while it holds. Both sides evaluate the same predicate on the same
    inputs, so every skin pair is counted exactly once. Returns the
    effective (approx_idx, direct_idx) with the gated skin-direct slots
    concatenated onto the static direct list.
    """
    from repro.kernels import ops as _ops  # local: ops imports this module

    bc, bhw, rb, has = _ops.batch_boxes(arrays["tgt_batched"],
                                        arrays["tgt_mask"])
    gate_kw = dict(theta=theta, space=space)
    approx_idx = arrays["approx_idx"]
    gate_a = _ops.mac_gate(approx_idx, bc, bhw, rb, has,
                           arrays["node_lo"], arrays["node_hi"], **gate_kw)
    approx_idx = jnp.where((arrays["approx_skin"] != 0) & ~gate_a,
                           -1, approx_idx)
    gate_d = _ops.mac_gate(arrays["skin_direct_node"], bc, bhw, rb, has,
                           arrays["node_lo"], arrays["node_hi"], **gate_kw)
    skin_direct = jnp.where(gate_d, -1, arrays["skin_direct"])
    direct_idx = jnp.concatenate([arrays["direct_idx"], skin_direct],
                                 axis=1)
    return approx_idx, direct_idx


def _execute_impl(
    arrays: dict,
    charges: jnp.ndarray,
    params=None,
    *,
    degree: int,
    kernel: Kernel,
    space=_FREE,
    backend: str = "auto",
    kahan: bool = False,
    precompute: str = "direct",
    approx_r2: str = "diff",
    theta: float = 0.7,
    skin: float = 0.0,
) -> jnp.ndarray:
    """Potentials at the plan's targets, in the caller's input order.

    `params` (traced pytree, kernel protocol v2) carries kernel parameter
    VALUES through the trace; None falls back to the kernel's hashable
    defaults (the v1 behavior). The solver path always passes explicit
    params with a params-free (`Kernel.stripped`) static kernel, so
    parameter sweeps over an unchanged plan compile exactly once.

    `theta`/`skin` are static: with ``skin > 0`` the Verlet-skin dual
    lists are routed by the runtime MAC gate (`_skin_routed_lists`)
    before the kernels run.

    The three kernel sites run under named scopes, which a profiler
    trace carries in each device operation's metadata:
    ``bltc.modified_charges``, ``bltc.approx`` and ``bltc.direct``; the
    two `batch_cluster` kernels are also named by site (``bltc_approx``,
    ``bltc_direct``), which names their operations in the trace."""
    q_sorted = charges[arrays["src_perm"]]
    with jax.named_scope("bltc.modified_charges"):
        if precompute == "direct":
            qhat = compute_qhat_direct(
                arrays, q_sorted, degree=degree, backend=backend)
        elif precompute == "hierarchical":
            qhat = compute_qhat_hierarchical(
                arrays, q_sorted, degree=degree, backend=backend)
        else:
            raise ValueError(f"unknown precompute {precompute!r}")

    grids = cheby.cluster_grid(arrays["node_lo"], arrays["node_hi"], degree)
    tgt = arrays["tgt_batched"]
    if skin > 0.0:
        approx_idx, direct_idx = _skin_routed_lists(arrays, theta, space)
    else:
        approx_idx, direct_idx = arrays["approx_idx"], arrays["direct_idx"]
    # The approximation kernel may use the MXU matmul form of r^2: the MAC
    # guarantees target/cluster separation, so no cancellation risk there.
    with jax.named_scope("bltc.approx"):
        phi_a = ops.batch_cluster_eval(
            approx_idx, tgt, grids, qhat, params,
            kernel=kernel, space=space, backend=backend, kahan=kahan,
            r2_mode=approx_r2, name="bltc_approx")

    leaf_pts, leaf_q = _gathered(
        arrays["src_sorted"], q_sorted, arrays["leaf_gather"])
    with jax.named_scope("bltc.direct"):
        phi_d = ops.batch_cluster_eval(
            direct_idx, tgt, leaf_pts, leaf_q, params,
            kernel=kernel, space=space, backend=backend, kahan=kahan,
            name="bltc_direct")

    phi = (phi_a + phi_d).reshape(-1)
    return phi[arrays["gather_index"]]


#: Jitted executor (geometry reused across charge vectors).
execute = jax.jit(_execute_impl, static_argnames=_EXEC_OPTS)

#: Same, but the charges buffer is donated to the computation so iterative
#: (boundary-element) loops that feed device-resident charge vectors don't
#: re-allocate; the caller's array is invalidated after the call.
execute_donating = jax.jit(_execute_impl, static_argnames=_EXEC_OPTS,
                           donate_argnums=(1,))


# ---------------------------------------------------------------------------
# Differentiation w.r.t. target coordinates (forces)
# ---------------------------------------------------------------------------
#
# phi_i depends on the *target* slab only through target i's own coordinates
# (each padded batch slot holds exactly one target), so the Jacobian
# d phi / d tgt_batched is diagonal in the target index. Three forward-mode
# JVPs with per-axis unit tangents therefore recover the full per-target
# gradient; reverse mode through the pipeline would instead transpose every
# gather into a scatter-add over the padded tables — much more memory
# traffic for the same diagonal. The custom VJP below exploits this so
# `jax.grad` of any scalar in phi stays cheap.


def _target_gradient(arrays, charges, params, opts: dict):
    """(phi, g) with g_i = d phi_i / d x_i, sources held fixed.

    Space-correct under `PeriodicBox` for free: the minimum-image fold
    d - L*round(d/L) has zero derivative through `round` almost
    everywhere, so the JVP of the folded displacement is the identity —
    forces point along the minimum-image separation."""
    opts = dict(opts, backend=ops.autodiff_backend(opts["backend"]))
    tgt = arrays["tgt_batched"]

    def phi_of(t):
        return _execute_impl(dict(arrays, tgt_batched=t), charges, params,
                             **opts)

    phi, grads = None, []
    for d in range(3):
        tangent = jnp.zeros_like(tgt).at[..., d].set(1.0)
        phi, dphi = jax.jvp(phi_of, (tgt,), (tangent,))
        grads.append(dphi)
    return phi, jnp.stack(grads, axis=-1)


@functools.partial(jax.jit, static_argnames=_EXEC_OPTS)
def potential_and_gradient(arrays, charges, params=None, *, degree, kernel,
                           space=_FREE, backend="auto", kahan=False,
                           precompute="direct", approx_r2="diff",
                           theta=0.7, skin=0.0):
    """Potentials and their per-target spatial gradient, input order."""
    return _target_gradient(arrays, charges, params, dict(
        degree=degree, kernel=kernel, space=space, backend=backend,
        kahan=kahan, precompute=precompute, approx_r2=approx_r2,
        theta=theta, skin=skin))


def _zero_cotangent(x):
    if jnp.issubdtype(jnp.result_type(x), jnp.inexact):
        return jnp.zeros_like(x)
    return np.zeros(jnp.shape(x), jax.dtypes.float0)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _phi_from_targets(opts: Tuple, tgt_batched, arrays, charges, params):
    o = dict(zip(_EXEC_OPTS, opts))
    return _execute_impl(dict(arrays, tgt_batched=tgt_batched), charges,
                         params, **o)


def _phi_fwd(opts, tgt_batched, arrays, charges, params):
    o = dict(zip(_EXEC_OPTS, opts))
    phi = _execute_impl(dict(arrays, tgt_batched=tgt_batched), charges,
                        params, **o)
    return phi, (tgt_batched, arrays, charges, params)


def _phi_bwd(opts, res, u):
    tgt, arrays, charges, params = res
    o = dict(zip(_EXEC_OPTS, opts))
    _, g = _target_gradient(dict(arrays, tgt_batched=tgt), charges, params,
                            o)
    flat = jnp.zeros((tgt.shape[0] * tgt.shape[1], 3), g.dtype)
    tbar = flat.at[arrays["gather_index"]].set(u[:, None] * g)
    # phi is linear in the charges, so that cotangent is an exact transpose
    # (dead-code-eliminated under jit when the caller only needs d/d tgt).
    o_ad = dict(o, backend=ops.autodiff_backend(o["backend"]))
    _, q_vjp = jax.vjp(
        lambda q: _execute_impl(dict(arrays, tgt_batched=tgt), q, params,
                                **o_ad),
        charges)
    (qbar,) = q_vjp(u)
    arrays_bar = jax.tree.map(_zero_cotangent, arrays)
    # Kernel parameters are treated as fixed constants of the force
    # evaluation (their cotangent is zero by convention; differentiate
    # through `potential_and_gradient` for parameter sensitivities).
    params_bar = jax.tree.map(_zero_cotangent, params)
    return tbar.reshape(tgt.shape), arrays_bar, qbar, params_bar


_phi_from_targets.defvjp(_phi_fwd, _phi_bwd)


def differentiable_execute(arrays, charges, params=None, *, degree, kernel,
                           space=_FREE, backend="auto", kahan=False,
                           precompute="direct", approx_r2="diff",
                           theta=0.7, skin=0.0):
    """`execute` with an efficient custom VJP w.r.t. target coordinates.

    Differentiable in `arrays["tgt_batched"]` (forces, target-position
    optimization) and in `charges`; source geometry is treated as fixed,
    matching the treecode convention that the tree is rebuilt — not
    differentiated — when sources move.
    """
    opts = (degree, kernel, space, backend, kahan, precompute, approx_r2,
            theta, skin)
    return _phi_from_targets(opts, arrays["tgt_batched"], arrays, charges,
                             params)


@functools.partial(jax.jit, static_argnames=_EXEC_OPTS)
def potential_and_forces(arrays, charges, weights, params=None, *, degree,
                         kernel, space=_FREE, backend="auto", kahan=False,
                         precompute="direct", approx_r2="diff",
                         theta=0.7, skin=0.0):
    """(phi, F) with F_i = -weights_i * d phi_i / d x_i, input order.

    With targets == sources and weights == charges this is the physical
    force -q_i grad phi(x_i): by symmetry of G the source-side variation
    contributes exactly the target-side term, so holding sources fixed and
    doubling via the energy convention is not needed. Implemented as
    `jax.grad` of sum(weights * phi) through the custom-VJP executor.
    """
    opts = (degree, kernel, space, backend, kahan, precompute, approx_r2,
            theta, skin)

    def weighted(t):
        phi = _phi_from_targets(opts, t, arrays, charges, params)
        return jnp.sum(phi * weights), phi

    (_, phi), wg = jax.value_and_grad(weighted, has_aux=True)(
        arrays["tgt_batched"])
    forces = -wg.reshape(-1, 3)[arrays["gather_index"]]
    return phi, forces


# ---------------------------------------------------------------------------
# Capacity padding: shape-stable replans for moving particles (MD)
# ---------------------------------------------------------------------------
#
# `prepare_plan` pads every ragged structure to its immediate need, so a
# replan over moved particles produces slightly different shapes and
# retraces the jitted executors. `Capacities` fixes a budget per padded
# dimension (initial need x headroom, grown geometrically when exceeded)
# and `pad_plan` re-pads any plan into that budget: identical shapes =>
# identical trace => the compiled executable is reused across rebuilds.
#
# Padding conventions (every sentinel contributes exactly zero):
#   - node rows: lo = 0, hi = 1 (non-degenerate box), with one reserved
#     SCRATCH row (id = num_nodes - 1) absorbing sentinel scatter writes;
#   - gather tables (leaf_gather, bucket_gather): -1 (masked);
#   - interaction lists (approx_idx, direct_idx): -1 (masked);
#   - bucket_nodes / leaf_node_ids / upward_pairs: the scratch row;
#   - target slab: zero rows, never referenced by gather_index.


@dataclasses.dataclass(frozen=True)
class Capacities:
    """Fixed padded-dimension budget for shape-stable replans.

    `num_targets` / `num_sources` are OPT-IN point budgets (0, the MD
    default, leaves the particle axes unpadded — the particle count is
    fixed across MD replans). When set (the ensemble/serving setting,
    see `repro.serve`), `pad_plan` additionally pads the source slab,
    the source permutation, and the target `gather_index` so plans over
    DIFFERENT particle counts become shape-identical and can share one
    compiled (vmapped) executable. Point-budgeted plans reserve one
    SCRATCH BATCH row (the last row, never holding a real target) that
    absorbs the padded `gather_index` entries — the batch-row analogue
    of the scratch node — and their executors require charge vectors
    padded to `num_sources` (zeros beyond the real particles), which
    `repro.serve.EnsemblePlan` handles. Point budgets only ever enter
    through needs dicts that carry explicit ``num_targets`` /
    ``num_sources`` keys; `for_plan`/`grown_to_fit` never enable them.
    """

    num_batches: int
    batch_width: int
    num_leaves: int
    leaf_width: int
    num_nodes: int                    # includes the +1 scratch row
    approx_width: int
    direct_width: int
    skin_direct_width: int            # gated Verlet-skin direct list
    depth: int                        # modified-charge level count
    bucket_rows: Tuple[int, ...]      # len == depth
    bucket_widths: Tuple[int, ...]    # len == depth, powers of two
    upward_rows: Tuple[int, ...] = () # len == depth - 1 (hierarchical)
    # Device hybrid octree (repro.devtree): occupied-cell row budgets for
    # the source/target tree levels past the dense split depth. Empty on
    # host plans and on device trees shallow enough to stay fully dense.
    sparse_rows: Tuple[int, ...] = ()
    batch_sparse_rows: Tuple[int, ...] = ()
    num_targets: int = 0              # 0 = unbudgeted (fixed-N replans)
    num_sources: int = 0              # 0 = unbudgeted
    headroom: float = 1.15
    growth: float = 1.5

    @property
    def scratch_node(self) -> int:
        return self.num_nodes - 1

    @property
    def points_budgeted(self) -> bool:
        return self.num_targets > 0

    @property
    def scratch_batch(self) -> int:
        """Reserved batch row absorbing padded gather_index entries
        (point-budgeted plans only; its slots are never real targets)."""
        return self.num_batches - 1

    @classmethod
    def for_plan(cls, plan: "Plan", headroom: float = 1.15,
                 growth: float = 1.5) -> "Capacities":
        """Initial budget: the plan's own shapes inflated by `headroom`."""
        return cls.for_need(_plan_dims(plan), headroom, growth)

    @classmethod
    def for_need(cls, need: dict, headroom: float = 1.15,
                 growth: float = 1.5, base: int = 8) -> "Capacities":
        """Initial budget from a raw needs dict (`_plan_dims` keys).

        The sharded build aggregates its per-rank needs (element-wise max
        over ranks) into the same dict shape, so one schema serves both
        execution strategies (see `ShardedCapacities`). Needs dicts that
        carry explicit ``num_targets``/``num_sources`` keys (the
        ensemble setting) enable the point budgets and reserve the
        scratch batch row.

        `headroom`/`base` trade budget slack against padded kernel work.
        The MD default (1.15 / 8) buys drift room and replan stability;
        ensembles of small systems want TIGHT budgets (1.0 / 1, the
        `repro.serve` default) — padded slots there are pure memory
        traffic multiplied by the ensemble width, and re-submission
        reuse only needs budget EQUALITY, which sticky bucket budgets
        plus geometric growth provide without slack."""

        def h(x):
            return _round_up(int(np.ceil(x * headroom)), base)

        points = bool(need.get("num_targets", 0))
        return cls(
            num_targets=_round_up(need["num_targets"], base) if points else 0,
            num_sources=_round_up(need["num_sources"], base) if points else 0,
            num_batches=h(need["num_batches"]) + (1 if points else 0),
            batch_width=h(need["batch_width"]),
            num_leaves=h(need["num_leaves"]),
            leaf_width=h(need["leaf_width"]),
            num_nodes=h(need["num_nodes"]) + 1,
            approx_width=h(need["approx_width"]),
            direct_width=h(need["direct_width"]),
            skin_direct_width=h(need.get("skin_direct_width", 1)),
            depth=need["depth"],
            bucket_rows=tuple(h(r) for r in need["bucket_rows"]),
            bucket_widths=tuple(_round_pow2(w) for w in need["bucket_widths"]),
            upward_rows=tuple(h(r) for r in need["upward_rows"]),
            sparse_rows=tuple(h(r) for r in need.get("sparse_rows", ())),
            batch_sparse_rows=tuple(
                h(r) for r in need.get("batch_sparse_rows", ())),
            headroom=headroom, growth=growth,
        )

    def grown_to_fit(self, plan: "Plan") -> "Capacities":
        """Smallest capacities >= self that fit `plan`, growing any
        insufficient dimension geometrically (never shrinks)."""
        return self.grown_to_fit_need(_plan_dims(plan))

    def grown_to_fit_need(self, need: dict) -> "Capacities":
        """`grown_to_fit` from a raw needs dict (`_plan_dims` keys)."""

        def g(cap, n, rounder=_round_up):
            if n <= cap:
                return cap
            return rounder(max(n, int(np.ceil(cap * self.growth))))

        def gt(caps, needs, rounder=_round_up):
            caps = tuple(caps) + tuple(
                rounder(int(np.ceil(n * self.headroom)))
                for n in needs[len(caps):])
            return tuple(g(c, n, rounder) for c, n
                         in zip(caps, tuple(needs) + (0,) * len(caps)))

        # Point budgets grow only when active; the +1 keeps the scratch
        # batch row (the last one) clear of real target batches.
        points = self.points_budgeted
        return dataclasses.replace(
            self,
            num_targets=(g(self.num_targets, need.get("num_targets", 0))
                         if points else 0),
            num_sources=(g(self.num_sources, need.get("num_sources", 0))
                         if points else 0),
            num_batches=g(self.num_batches,
                          need["num_batches"] + (1 if points else 0)),
            batch_width=g(self.batch_width, need["batch_width"]),
            num_leaves=g(self.num_leaves, need["num_leaves"]),
            leaf_width=g(self.leaf_width, need["leaf_width"]),
            num_nodes=g(self.num_nodes, need["num_nodes"] + 1),
            approx_width=g(self.approx_width, need["approx_width"]),
            direct_width=g(self.direct_width, need["direct_width"]),
            skin_direct_width=g(self.skin_direct_width,
                                need.get("skin_direct_width", 1)),
            depth=max(self.depth, need["depth"]),
            bucket_rows=gt(self.bucket_rows, need["bucket_rows"]),
            bucket_widths=gt(self.bucket_widths, need["bucket_widths"],
                             _round_pow2),
            upward_rows=gt(self.upward_rows, need["upward_rows"]),
            sparse_rows=gt(self.sparse_rows, need.get("sparse_rows", ())),
            batch_sparse_rows=gt(self.batch_sparse_rows,
                                 need.get("batch_sparse_rows", ())),
        )

    def fits(self, plan: "Plan") -> bool:
        return self.grown_to_fit(plan) == self


@dataclasses.dataclass(frozen=True)
class ShardedCapacities:
    """Fixed budget for a `ShardedPlan`'s stacked (P, ...) arrays.

    Generalizes `Capacities` to the sharded setting (DESIGN.md §7): the
    per-rank padded dimensions reuse the single-device schema applied to
    the element-wise max over ranks (`rank`), and the cross-rank LET
    structures get budgets of their own:

      slab_width           particle slab width per rank (`per_pad`)
      remote_approx_width  gathered-cluster list width per batch
      remote_direct_width  received-halo-leaf list width per batch
      halo_offsets         the FIXED `collective_permute` round schedule:
                           one round per rank offset, symmetric contiguous
                           range ±D so the compiled SPMD program's
                           communication pattern survives RCB re-cuts;
                           rounds an actual build does not need run fully
                           masked (all -1 send tables exchange zeros)
      halo_width           leaf-slot budget per halo round (common)

    Two builds padded into equal `ShardedCapacities` produce
    shape-identical pytrees AND an identical static closure
    (`perm_rounds` derives from `halo_offsets` alone), so the jitted
    shard_map executable is shared between them — the sharded analogue
    of the `Capacities`/`pad_plan` contract, with the same headroom +
    geometric-growth overflow policy.
    """

    rank: Capacities                  # per-rank budget (num_nodes incl.
                                      # the scratch row, as single-device)
    nranks: int
    slab_width: int
    remote_approx_width: int
    remote_direct_width: int
    halo_offsets: Tuple[int, ...]
    halo_width: int
    headroom: float = 1.15
    growth: float = 1.5

    @property
    def scratch_node(self) -> int:
        return self.rank.scratch_node

    @property
    def halo_rounds(self) -> int:
        return len(self.halo_offsets)

    @staticmethod
    def _offset_range(offsets) -> Tuple[int, ...]:
        """Canonical symmetric round schedule covering `offsets`: every
        nonzero offset in [-D, D], D = max |offset| (at least 1, so even
        halo-free builds keep a usable budget for later drift)."""
        d = max([abs(int(o)) for o in offsets] + [1])
        return tuple(o for o in range(-d, d + 1) if o != 0)

    @classmethod
    def for_need(cls, need: dict, headroom: float = 1.15,
                 growth: float = 1.5) -> "ShardedCapacities":
        """Initial budget: the build's own needs inflated by `headroom`."""

        def h(x):
            return _round_up(int(np.ceil(x * headroom)))

        return cls(
            rank=Capacities.for_need(need["rank"], headroom, growth),
            nranks=int(need["nranks"]),
            slab_width=h(need["slab_width"]),
            remote_approx_width=h(need["remote_approx_width"]),
            remote_direct_width=h(need["remote_direct_width"]),
            halo_offsets=cls._offset_range(need["halo_offsets"]),
            halo_width=h(need["halo_width"]),
            headroom=headroom, growth=growth,
        )

    def grown_to_fit(self, need: dict) -> "ShardedCapacities":
        """Smallest capacities >= self fitting `need`; any insufficient
        width grows geometrically, and a rank offset outside the round
        schedule widens the symmetric range (both are deliberate,
        counted retraces — see `Simulation.stats`)."""
        if int(need["nranks"]) != self.nranks:
            raise ValueError(
                f"sharded capacities are bound to nranks={self.nranks}; "
                f"got a build over nranks={need['nranks']}")

        def g(cap, n):
            if n <= cap:
                return cap
            return _round_up(max(n, int(np.ceil(cap * self.growth))))

        offsets = self.halo_offsets
        if not set(need["halo_offsets"]) <= set(offsets):
            offsets = self._offset_range(
                tuple(offsets) + tuple(need["halo_offsets"]))
        return dataclasses.replace(
            self,
            rank=self.rank.grown_to_fit_need(need["rank"]),
            slab_width=g(self.slab_width, need["slab_width"]),
            remote_approx_width=g(self.remote_approx_width,
                                  need["remote_approx_width"]),
            remote_direct_width=g(self.remote_direct_width,
                                  need["remote_direct_width"]),
            halo_offsets=offsets,
            halo_width=g(self.halo_width, need["halo_width"]),
        )

    def fits(self, need: dict) -> bool:
        return self.grown_to_fit(need) == self


def _plan_dims(plan: Plan) -> dict:
    a = plan.arrays
    bg = a["bucket_gather"]
    up = a.get("upward_pairs", ())
    return dict(
        num_batches=a["tgt_batched"].shape[0],
        batch_width=a["tgt_batched"].shape[1],
        num_leaves=a["leaf_gather"].shape[0],
        leaf_width=a["leaf_gather"].shape[1],
        num_nodes=a["node_lo"].shape[0],
        approx_width=a["approx_idx"].shape[1],
        direct_width=a["direct_idx"].shape[1],
        skin_direct_width=(a["skin_direct"].shape[1]
                           if "skin_direct" in a else 1),
        depth=len(bg),
        bucket_rows=tuple(g.shape[0] for g in bg),
        bucket_widths=tuple(g.shape[1] for g in bg),
        upward_rows=tuple(p.shape[0] for p in up),
        sparse_rows=tuple((plan.dev or {}).get("sparse_occ", ())),
        batch_sparse_rows=tuple(
            (plan.dev or {}).get("batch_sparse_occ", ())),
    )


def _pad2(arr: np.ndarray, shape: Tuple[int, ...], value) -> np.ndarray:
    pads = [(0, s - d) for s, d in zip(shape, arr.shape)]
    if any(p[1] < 0 for p in pads):
        raise ValueError(f"cannot pad {arr.shape} into {shape}")
    return np.pad(arr, pads + [(0, 0)] * (arr.ndim - len(shape)),
                  constant_values=value)


def pad_plan(plan: Plan, caps: Capacities) -> Plan:
    """Re-pad a plan's device arrays into the fixed `caps` budget.

    The returned plan computes identical potentials (every padded slot is
    masked or scatters into the scratch node) but its array shapes depend
    only on `caps`, so jitted executors compiled for one capacity-padded
    plan are reused by every later one.
    """
    with _trace.span("plan.pad"):
        return _pad_plan_impl(plan, caps)


def _pad_plan_impl(plan: Plan, caps: Capacities) -> Plan:
    _t_pad = time.perf_counter()
    if not caps.fits(plan):
        raise ValueError(
            "capacities do not fit this plan; call caps.grown_to_fit(plan) "
            "first (the growth is a deliberate, counted retrace)")
    if caps.points_budgeted and (plan.num_targets > caps.num_targets
                                 or plan.num_sources > caps.num_sources):
        # `fits` can't see this: point budgets are grown only through
        # needs dicts with explicit num_targets/num_sources keys.
        raise ValueError(
            f"plan ({plan.num_targets} targets / {plan.num_sources} "
            f"sources) exceeds the point budget ({caps.num_targets} / "
            f"{caps.num_sources}); grow via grown_to_fit_need with "
            f"explicit num_targets/num_sources keys")
    # The plan's arrays back on the host, to be re-padded there.
    with _trace.span("plan.copy"):
        a = {k: (tuple(np.asarray(x) for x in v) if isinstance(v, tuple)
                 else np.asarray(v)) for k, v in plan.arrays.items()}
    scratch = caps.scratch_node

    nb_old = a["tgt_batched"].shape[1]
    gi = a["gather_index"].astype(np.int64)
    if nb_old != caps.batch_width:
        gi = (gi // nb_old) * caps.batch_width + gi % nb_old

    out = dict(
        src_sorted=a["src_sorted"],
        src_perm=a["src_perm"],
        tgt_batched=_pad2(a["tgt_batched"],
                          (caps.num_batches, caps.batch_width), 0),
        gather_index=gi.astype(np.int32),
        leaf_gather=_pad2(a["leaf_gather"],
                          (caps.num_leaves, caps.leaf_width), -1),
        node_lo=_pad2(a["node_lo"], (caps.num_nodes,), 0),
        node_hi=_pad2(a["node_hi"], (caps.num_nodes,), 1),
        approx_idx=_pad2(a["approx_idx"],
                         (caps.num_batches, caps.approx_width), -1),
        direct_idx=_pad2(a["direct_idx"],
                         (caps.num_batches, caps.direct_width), -1),
        approx_skin=_pad2(a["approx_skin"],
                          (caps.num_batches, caps.approx_width), 0),
        skin_direct=_pad2(a["skin_direct"],
                          (caps.num_batches, caps.skin_direct_width), -1),
        skin_direct_node=_pad2(a["skin_direct_node"],
                               (caps.num_batches, caps.skin_direct_width),
                               -1),
        tgt_mask=_pad2(a["tgt_mask"],
                       (caps.num_batches, caps.batch_width), False),
        parent_of=_pad2(a["parent_of"], (caps.num_nodes,), scratch),
    )

    if caps.points_budgeted:
        # Point budget (ensemble/serving): pad the particle axes so plans
        # over different N share one executable. Padded gather_index
        # entries all point at the FIRST slot of the scratch batch row —
        # masked, list-free, so the potentials there are exactly 0 and
        # the backward scatter never collides with a real target's slot.
        if a["tgt_batched"].shape[0] >= caps.num_batches:
            raise ValueError("point-budgeted capacities must keep the "
                             "scratch batch row free of real batches")
        nt, ns = plan.num_targets, plan.num_sources
        scratch_flat = caps.scratch_batch * caps.batch_width
        out["gather_index"] = np.concatenate([
            out["gather_index"],
            np.full(caps.num_targets - nt, scratch_flat, np.int32)])
        out["src_sorted"] = _pad2(a["src_sorted"], (caps.num_sources,), 0)
        # Padded permutation entries map padded source slots to padded
        # charge slots (charges arrive padded to num_sources, zeros
        # beyond the real particles), keeping the gather in bounds; the
        # padded rows are never referenced by any -1-masked table.
        out["src_perm"] = np.concatenate([
            a["src_perm"],
            np.arange(ns, caps.num_sources, dtype=np.int32)])

    bg_old = a["bucket_gather"]
    bn_old = a["bucket_nodes"]
    bgs, bns = [], []
    for lvl in range(caps.depth):
        shape = (caps.bucket_rows[lvl], caps.bucket_widths[lvl])
        if lvl < len(bg_old):
            g = _pad2(bg_old[lvl], shape, -1)
            n = _pad2(bn_old[lvl], shape[:1], scratch)
        else:
            g = np.full(shape, -1, np.int32)
            n = np.full(shape[:1], scratch, np.int32)
        bgs.append(np.asarray(g, np.int32))
        bns.append(np.asarray(n, np.int32))
    out["bucket_gather"] = tuple(bgs)
    out["bucket_nodes"] = tuple(bns)

    if "upward_pairs" in a:
        out["leaf_node_ids"] = _pad2(a["leaf_node_ids"],
                                     (caps.num_leaves,), scratch)
        up_old = a["upward_pairs"]
        ups = []
        for slot in range(len(caps.upward_rows)):
            shape = (caps.upward_rows[slot], 2)
            if slot < len(up_old):
                p = _pad2(up_old[slot], shape, scratch)
            else:
                p = np.full(shape, scratch, np.int32)
            ups.append(np.asarray(p, np.int32))
        out["upward_pairs"] = tuple(ups)

    with _trace.span("plan.copy"):
        arrays = jax.tree.map(jnp.asarray, out)
    build_ms = dict(plan.build_ms)
    build_ms["pad"] = build_ms.get("pad", 0.0) \
        + (time.perf_counter() - _t_pad) * 1e3
    return dataclasses.replace(plan, arrays=arrays, capacities=caps,
                               scratch_node=scratch, build_ms=build_ms)


def kernel_work(plan: Plan, degree: int) -> dict:
    """Pair evaluations the two `batch_cluster` lists of `plan` launch,
    skip and need, ``{"approx"|"direct": {"launched", "skipped",
    "useful"}}`` (`kernels.batch_cluster.kernel_work`). Host arithmetic:
    fetches the lists and masks once, off the hot path.

    With a Verlet skin the direct list runs with its skin-direct columns
    appended (`_skin_routed_lists`); at the build's own geometry the
    runtime gate leaves every one of them empty, so they count as
    skipped.
    """
    keys = ("approx_idx", "direct_idx", "tgt_mask", "leaf_gather")
    a = jax.device_get({k: plan.arrays[k] for k in keys})
    direct = a["direct_idx"]
    if plan.skin > 0.0:
        skin_cols = plan.arrays["skin_direct"].shape[1]
        direct = np.pad(direct, ((0, 0), (0, skin_cols)),
                        constant_values=-1)
    tgt = a["tgt_mask"].sum(1)
    width = a["tgt_mask"].shape[1]
    k3 = (degree + 1) ** 3
    points = np.full(plan.arrays["node_lo"].shape[0], k3)
    return dict(
        approx=_bc.kernel_work(a["approx_idx"], tgt, points,
                               target_width=width, source_width=k3),
        direct=_bc.kernel_work(direct, tgt, (a["leaf_gather"] >= 0).sum(1),
                               target_width=width,
                               source_width=a["leaf_gather"].shape[1]))


def plan_signature(plan: Plan) -> Tuple:
    """Hashable shape/dtype signature of a plan's device arrays — equal
    signatures mean a jitted executor compiled for one plan is reused by
    the other (the retrace counter in `dynamics` tracks distinct values)."""
    def leaf_sig(v):
        return (v.shape, str(v.dtype))

    return tuple(sorted(
        (k, tuple(leaf_sig(x) for x in v) if isinstance(v, tuple)
         else leaf_sig(v))
        for k, v in plan.arrays.items()))


# ---------------------------------------------------------------------------
# Ensemble executors: one launch over a leading systems axis
# ---------------------------------------------------------------------------
#
# Plans padded into one (point-budgeted) `Capacities` are shape-identical
# pytrees, so S of them stack along a leading axis and the whole pipeline
# vmaps over it: one compiled executable, one device launch, S systems.
# Per-system charges and kernel-parameter values ride as traced inputs
# (protocol v2), so replica ensembles, kappa scans and mixed many-small-
# box workloads all share the executable of their budget. This is the
# batching contract `repro.serve` builds on.


def _ensemble_execute_impl(arrays, charges, params=None, **opts):
    """Vmapped `_execute_impl`: every `arrays` leaf, `charges`, and every
    `params` leaf carries a leading systems axis."""
    return jax.vmap(
        lambda a, q, p: _execute_impl(a, q, p, **opts))(
            arrays, charges, params)


#: Jitted batched executor: potentials for S stacked systems in one
#: launch, (S, num_targets_capacity), padded target slots exactly 0.
ensemble_execute = jax.jit(_ensemble_execute_impl,
                           static_argnames=_EXEC_OPTS)

#: Same, donating the stacked charge slab (iterative ensemble loops).
ensemble_execute_donating = jax.jit(_ensemble_execute_impl,
                                    static_argnames=_EXEC_OPTS,
                                    donate_argnums=(1,))


def _ensemble_pf_impl(arrays, charges, weights, params=None, *, degree,
                      kernel, space=_FREE, backend="auto", kahan=False,
                      precompute="direct", approx_r2="diff",
                      theta=0.7, skin=0.0):
    opts = (degree, kernel, space, backend, kahan, precompute, approx_r2,
            theta, skin)

    def one(a, q, w, p):
        def weighted(t):
            phi = _phi_from_targets(opts, t, a, q, p)
            return jnp.sum(phi * w), phi

        (_, phi), wg = jax.value_and_grad(weighted, has_aux=True)(
            a["tgt_batched"])
        return phi, -wg.reshape(-1, 3)[a["gather_index"]]

    return jax.vmap(one)(arrays, charges, weights, params)


#: Jitted batched (phi, F) for S stacked systems in one launch. Padded
#: target slots carry zero weights, so their forces are exactly 0 (the
#: scratch-batch slot their gather entries share has no interaction
#: lists, hence no dependence on any coordinate).
ensemble_potential_and_forces = jax.jit(_ensemble_pf_impl,
                                        static_argnames=_EXEC_OPTS)


def ensemble_compile_count() -> int:
    """Total jit compilations of the ensemble executors (serving's
    compile/retrace counters difference these)."""
    total = 0
    for fn in (ensemble_execute, ensemble_execute_donating,
               ensemble_potential_and_forces):
        try:
            total += fn._cache_size()
        except Exception:
            pass
    return total


def add_hierarchical_tables(plan: Plan) -> Plan:
    """Extend a plan with upward-pass tables (parent/child pairs per level,
    deepest first, and the leaf gather rows' node ids)."""
    tree = plan.tree
    pairs_by_level = []
    max_level = int(tree.level.max())
    for lvl in range(max_level, 0, -1):
        nodes = np.nonzero((tree.level == lvl))[0]
        if len(nodes) == 0:
            continue
        parents = tree.parent[nodes]
        pairs_by_level.append(
            jnp.asarray(np.stack([parents, nodes], axis=1), jnp.int32))
    plan.arrays["upward_pairs"] = tuple(pairs_by_level)
    plan.arrays["leaf_node_ids"] = jnp.asarray(tree.leaf_ids, jnp.int32)
    return plan
