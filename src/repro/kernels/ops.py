"""Jitted wrappers around the Pallas kernels with backend dispatch.

Backends:
  - "pallas":            real TPU lowering (the production target);
  - "pallas_interpret":  the same kernel bodies executed in Python on CPU
                         (correctness validation in this container);
  - "xla":               memory-tiled pure-jnp implementation of identical
                         math. This is the fast path on CPU (interpret mode
                         is a Python loop over the grid) and doubles as an
                         independent large-shape check of the kernels.
  - "auto":              "pallas" on TPU, "xla" otherwise.

All wrappers accept the natural (..., P, 3) coordinate layout and transpose
to the kernels' coordinate-major layout internally (a one-time O(N) cost
against the O(N * m) kernel work).

Kernel protocol v2: `params` is a traced pytree of kernel parameter values
(None -> the kernel's hashable defaults, the v1 behavior) and `space` is a
static `Space` deciding the displacement convention (minimum image under
`PeriodicBox`). Both backends receive them; on the Pallas path the values
travel as a scalar-prefetch vector so sweeps reuse the compiled kernel.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core import cheby
from repro.core.potentials import Kernel, pack_params
from repro.core.space import FREE as _FREE
from repro.kernels import batch_cluster as _bc
from repro.kernels import modified_charges as _mc

# f32 contractions on the TPU default to one bf16 pass (~3 digits); the
# treecode's accuracy contract needs full f32 (a no-op on the CPU).
_HIGHEST = jax.lax.Precision.HIGHEST


def default_backend() -> str:
    return "pallas" if jax.default_backend() == "tpu" else "xla"


def resolve_backend(backend: str) -> str:
    """The backend a config's `backend` names: "auto" follows the
    platform (`default_backend`), anything else is taken as given."""
    return default_backend() if backend == "auto" else backend


def autodiff_backend(backend: str) -> str:
    """Backend to use under jvp/vjp: the Pallas kernel bodies have no AD
    rules, so derivative evaluations run the mathematically identical XLA
    path (same masking, same accumulation order up to reassociation)."""
    resolved = resolve_backend(backend)
    return "xla" if resolved in ("pallas", "pallas_interpret") else resolved


def _pad_axis(x: jnp.ndarray, axis: int, multiple: int, value=0):
    size = x.shape[axis]
    rem = (-size) % multiple
    if rem == 0:
        return x, size
    pads = [(0, 0)] * x.ndim
    pads[axis] = (0, rem)
    return jnp.pad(x, pads, constant_values=value), size


# ---------------------------------------------------------------------------
# Runtime MAC gate (Verlet-skin dual lists, DESIGN.md §4)
# ---------------------------------------------------------------------------
#
# Skin pairs are dual-listed at build time (repro.core.interaction): the
# executors re-test the pair's MAC on the CURRENT refitted geometry and
# route it to exactly one side by masking the losing side's index to the
# -1 sentinel the kernels already skip. Both sides evaluate the SAME
# predicate on the same inputs, so the routing is complementary by
# construction. These helpers are jit-safe and shared by the
# single-device executor (repro.core.eval) and the SPMD body
# (repro.distributed.bltc).


def batch_boxes(tgt: jnp.ndarray, mask: jnp.ndarray):
    """Current batch geometry from the padded target slab.

    tgt (B, NB, 3) refitted batch-packed targets, mask (B, NB) validity
    (False = padding). Returns (center (B, 3), half_extent (B, 3),
    radius (B,), has (B,)); fully padded rows collapse to a point box at
    the origin and are excluded via `has`.
    """
    big = jnp.asarray(jnp.finfo(tgt.dtype).max, tgt.dtype)
    m = mask[..., None]
    lo = jnp.min(jnp.where(m, tgt, big), axis=1)
    hi = jnp.max(jnp.where(m, tgt, -big), axis=1)
    has = jnp.any(mask, axis=1)
    lo = jnp.where(has[:, None], lo, 0.0)
    hi = jnp.where(has[:, None], hi, 0.0)
    hw = 0.5 * (hi - lo)
    return 0.5 * (lo + hi), hw, jnp.linalg.norm(hw, axis=-1), has


def mac_gate(node_idx: jnp.ndarray, bc, bhw, rb, has,
             node_lo: jnp.ndarray, node_hi: jnp.ndarray, *,
             theta: float, space=_FREE) -> jnp.ndarray:
    """(B, S) bool: MAC of (batch, node_idx[b, s]) holds on CURRENT boxes.

    `bc`/`bhw`/`rb`/`has` come from `batch_boxes`; node_lo/hi are the
    refitted cluster boxes. Space-aware: minimum-image center distance
    and the fold-free condition under a `PeriodicBox` (the same
    acceptance the host traversal applies, DESIGN.md §5). -1 (sentinel)
    node ids gate to False. The cluster-size condition (n+1)^3 < N_C is
    topological (drift-invariant) and needs no re-test.
    """
    safe = jnp.maximum(node_idx, 0)
    clo = node_lo[safe]                               # (B, S, 3)
    chi = node_hi[safe]
    cc = 0.5 * (clo + chi)
    chw = 0.5 * (chi - clo)
    rc = jnp.linalg.norm(chw, axis=-1)
    d = bc[:, None, :] - cc
    dm = space.min_image(d)
    R = jnp.sqrt(jnp.sum(dm * dm, axis=-1))
    ok = theta * R - (rb[:, None] + rc) > 0.0
    fold_ok = space.fold_margin(d, bhw[:, None, :] + chw) > 0.0
    return ok & fold_ok & has[:, None] & (node_idx >= 0)


def refreshed_slacks(approx_idx, approx_skin, bc, bhw, rb, has,
                     node_lo, node_hi, *, theta: float, space=_FREE):
    """(theta_slack, fold_slack) scalars over the SAFE approx pairs of a
    refitted plan — the on-device slack refresh (DESIGN.md §4).

    Margins are exact on the current geometry (refitted boxes are true
    bounding boxes), so the engine may budget future drift against them
    at the theta/fold rates. Skin pairs (approx_skin != 0) are runtime
    gated and excluded; empty categories reduce to +inf.
    """
    safe = jnp.maximum(approx_idx, 0)
    clo = node_lo[safe]
    chi = node_hi[safe]
    cc = 0.5 * (clo + chi)
    chw = 0.5 * (chi - clo)
    rc = jnp.linalg.norm(chw, axis=-1)
    d = bc[..., None, :] - cc
    dm = space.min_image(d)
    R = jnp.sqrt(jnp.sum(dm * dm, axis=-1))
    t_margin = theta * R - (rb[..., None] + rc)
    valid = (approx_idx >= 0) & (approx_skin == 0) & has[..., None]
    inf = jnp.asarray(jnp.inf, t_margin.dtype)
    theta_slack = jnp.min(jnp.where(valid, t_margin, inf))
    fold = space.fold_margin(d, bhw[..., None, :] + chw)
    fold = jnp.broadcast_to(jnp.asarray(fold, t_margin.dtype),
                            t_margin.shape)
    fold_slack = jnp.min(jnp.where(valid, fold, inf))
    return theta_slack, fold_slack


# ---------------------------------------------------------------------------
# batch-cluster evaluation (Eq. 9 / Eq. 11)
# ---------------------------------------------------------------------------

#: Element budget for the unscanned small-shape XLA path: the full
#: (B, S, NB, m) pairwise tensor (x3 for displacements) stays ~MBs.
_FLAT_MAX = 1 << 18


@functools.partial(
    jax.jit,
    static_argnames=("kernel", "space", "backend", "target_tile",
                     "batch_chunk", "kahan", "r2_mode", "name"))
def batch_cluster_eval(
    idx: jnp.ndarray,      # (B, S) int, -1 = empty slot
    tgt: jnp.ndarray,      # (B, NB, 3)
    src_pts: jnp.ndarray,  # (C, m, 3)
    src_q: jnp.ndarray,    # (C, m)
    params=None,           # traced kernel parameter pytree (None: defaults)
    *,
    kernel: Kernel,
    space=_FREE,
    backend: str = "auto",
    target_tile: int = 256,
    batch_chunk: int = 16,
    kahan: bool = False,
    r2_mode: str = "diff",
    name: str = "batch_cluster",
) -> jnp.ndarray:
    """phi (B, NB) = sum over list slots of batch-cluster interactions.

    `name` names the Pallas kernel, so a profiler trace tells the call
    sites apart."""
    backend = resolve_backend(backend)
    if backend in ("pallas", "pallas_interpret"):
        tgt_cm = jnp.swapaxes(tgt, -1, -2)          # (B, 3, NB)
        src_cm = jnp.swapaxes(src_pts, -1, -2)      # (C, 3, m)
        tgt_cm, nb = _pad_axis(tgt_cm, 2, target_tile)
        par, pspec = pack_params(
            kernel.params if params is None else params)
        phi = _bc.batch_cluster_eval_pallas(
            idx, par, tgt_cm, src_cm, src_q, kernel,
            pspec=pspec, space=space,
            target_tile=target_tile, kahan=kahan, r2_mode=r2_mode,
            interpret=(backend == "pallas_interpret"), name=name,
        )
        return phi[:, :nb]
    if backend != "xla":
        raise ValueError(f"unknown backend {backend!r}")

    # XLA small-shape path: when the full (B, S, NB, m) pairwise
    # intermediate is modest, one fused masked contraction beats the
    # scan — the scan's per-iteration bodies are too small to vectorize
    # and its chunk padding quantizes cost in batch_chunk-row steps.
    # This is the regime ensemble serving lives in (many small systems,
    # heavily capacity-padded lists), and it also speeds up small
    # single-system plans. Kahan accumulation needs the scan's ordered
    # sums, so it keeps the chunked path.
    if not kahan and idx.size * tgt.shape[1] * src_pts.shape[1] <= _FLAT_MAX:
        safe = jnp.maximum(idx, 0)
        pts = src_pts[safe]                         # (B, S, m, 3)
        qs = src_q[safe]                            # (B, S, m)
        pw = (kernel.pairwise_matmul if r2_mode == "matmul"
              else kernel.pairwise)
        g = pw(tgt[:, None], pts, params, space)    # (B, S, NB, m)
        valid = (idx >= 0).astype(tgt.dtype)
        return jnp.einsum("bsnm,bsm,bs->bn", g, qs, valid,
                          precision=_HIGHEST)

    # XLA path: scan over (batch-chunk, slot) to bound the (bc, NB, m)
    # pairwise intermediate. The chunk is rebalanced so padding never
    # adds a near-empty extra chunk (17 rows at chunk 16 would otherwise
    # pad to 32 — doubling the kernel work for one row over the
    # boundary; rebalanced, it runs 2 chunks of 9).
    bsz, nb = tgt.shape[0], tgt.shape[1]
    nchunk = -(-bsz // batch_chunk)
    batch_chunk = -(-bsz // nchunk)
    idx_p, _ = _pad_axis(idx, 0, batch_chunk, value=-1)
    tgt_p, _ = _pad_axis(tgt, 0, batch_chunk)
    nchunk = idx_p.shape[0] // batch_chunk
    idx_c = idx_p.reshape(nchunk, batch_chunk, -1)
    tgt_c = tgt_p.reshape(nchunk, batch_chunk, nb, 3)

    def chunk_step(_, args):
        idx_b, tgt_b = args  # (bc, S), (bc, NB, 3)

        def slot_step(phi, idx_s):  # idx_s (bc,)
            safe = jnp.maximum(idx_s, 0)
            pts = src_pts[safe]                     # (bc, m, 3)
            qs = src_q[safe]                        # (bc, m)
            pw = (kernel.pairwise_matmul if r2_mode == "matmul"
                  else kernel.pairwise)
            g = pw(tgt_b, pts, params, space)       # (bc, NB, m)
            valid = (idx_s >= 0).astype(tgt_b.dtype)
            return phi + jnp.einsum("bnm,bm,b->bn", g, qs, valid,
                                    precision=_HIGHEST), None

        phi0 = jnp.zeros((batch_chunk, nb), tgt_b.dtype)
        phi, _ = jax.lax.scan(slot_step, phi0, idx_b.T)
        return None, phi

    _, phis = jax.lax.scan(chunk_step, None, (idx_c, tgt_c))
    return phis.reshape(-1, nb)[:bsz]


# ---------------------------------------------------------------------------
# modified charges (Eq. 12 via the factored 14/15 form)
# ---------------------------------------------------------------------------
#
# Space-independent on purpose: barycentric interpolation is LOCAL to a
# cluster box, and particle coordinates are stored consistently with their
# own cluster (wrapped at build, continuous under refit), so no image
# folding can occur between a particle and its cluster's Chebyshev grid.


def _cluster_nodes(lo: jnp.ndarray, hi: jnp.ndarray, degree: int):
    """Per-dimension mapped Chebyshev nodes, (C, 3, n+1)."""
    s = cheby.cheb_points_1d(degree, lo.dtype)
    return cheby.map_points(s, lo[..., None], hi[..., None])


@functools.partial(
    jax.jit, static_argnames=("degree", "backend", "particle_tile"))
def modified_charges(
    pts: jnp.ndarray,  # (C, m, 3) cluster particles, padded (q = 0)
    q: jnp.ndarray,    # (C, m)
    lo: jnp.ndarray,   # (C, 3)
    hi: jnp.ndarray,   # (C, 3)
    *,
    degree: int,
    backend: str = "auto",
    particle_tile: int = 512,
) -> jnp.ndarray:
    """q_hat (C, (n+1)^3), flattened k3-fastest (cluster_grid ordering)."""
    backend = resolve_backend(backend)
    nodes = _cluster_nodes(lo, hi, degree)
    if backend in ("pallas", "pallas_interpret"):
        pts_cm = jnp.swapaxes(pts, -1, -2)  # (C, 3, m)
        m = pts_cm.shape[-1]
        tile = min(particle_tile, m)
        pts_cm, _ = _pad_axis(pts_cm, 2, tile)
        q_p, _ = _pad_axis(q, 1, tile)
        return _mc.modified_charges_pallas(
            pts_cm, q_p, nodes, degree, particle_tile=tile,
            interpret=(backend == "pallas_interpret"),
        )
    if backend != "xla":
        raise ValueError(f"unknown backend {backend!r}")

    n1 = degree + 1
    w = cheby.bary_weights_1d(degree, pts.dtype)
    t1, d1 = cheby.bary_terms(pts[..., 0], nodes[:, None, 0, :], w)
    t2, d2 = cheby.bary_terms(pts[..., 1], nodes[:, None, 1, :], w)
    t3, d3 = cheby.bary_terms(pts[..., 2], nodes[:, None, 2, :], w)
    den = d1 * d2 * d3
    # padded/degenerate slots can cancel den to 0 in f32; their q is 0
    qt = jnp.where(den != 0.0, q / jnp.where(den != 0.0, den, 1.0), 0.0)
    g2 = (t1[..., :, None] * t2[..., None, :]).reshape(*t1.shape[:-1], n1 * n1)
    r3 = t3 * qt[..., None]
    qhat = jnp.einsum("cmp,cmk->cpk", g2, r3, precision=_HIGHEST)
    return qhat.reshape(-1, n1 * n1 * n1)
