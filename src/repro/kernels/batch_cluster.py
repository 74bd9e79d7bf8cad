"""Pallas TPU kernel for batch-cluster interactions (Eq. 9 and Eq. 11).

This is the paper's central GPU insight adapted to TPU: the barycentric
particle-cluster approximation has the *same direct-sum form* as the exact
interaction, so ONE kernel evaluates both — against leaf source particles
(direct, Eq. 9) or against Chebyshev points with modified charges
(approximation, Eq. 11).

TPU mapping (vs. the paper's CUDA/OpenACC mapping):
  - paper: one kernel launch per (batch, cluster) pair, 4 async streams,
    1 thread block per target, threads over sources, atomics into phi.
  - here: a single `pallas_call` over grid (batch, target-tile, list-slot).
    The interaction list is a host-built padded index array delivered via
    scalar prefetch; the BlockSpec index_map gathers each cluster's block
    from HBM (the TPU analogue of the per-launch pointer argument), the
    grid pipeline double-buffers the next cluster while computing the
    current one (replacing async streams), and the output tile is revisited
    across list slots so accumulation happens in VMEM (replacing atomics).
  - pairwise kernel evaluations run on the VPU over a (tile, m) block; the
    charge contraction is a matvec on the MXU.

Space/params protocol v2: kernel parameters arrive as a SECOND
scalar-prefetch operand — a flat (1, P) vector in SMEM, rebuilt into the
kernel's params pytree by the static `pspec` — so parameter sweeps reuse
the compiled kernel (values are data, not code). The `space` is static
(box lengths are compile constants): under a `PeriodicBox` the pairwise
displacements are folded to the minimum image on the VPU, and the MXU
matmul form of r^2 (which cannot express the fold) falls back to the
difference form.

Layout: coordinates are coordinate-major (..., 3, P) so the particle axis
is the TPU lane dimension.

Sentinel contract: any negative slot in the interaction-list index
array contributes exactly zero and costs neither compute nor a copy, and
sentinels may appear at ANY position in a row, not only as trailing
padding. Each call reads its slot's index from SMEM and runs the tile
under ``pl.when(idx >= 0)``; the output tile (and the Kahan compensation)
is initialized at slot 0 regardless of that slot's validity, so a row of
sentinels writes zeros. Before the call every negative slot is rewritten
to ``-(1 + c)``, with ``c`` the row's last valid cluster before it (its
first valid one for leading sentinels, 0 for an empty row), and the
source and charge index maps decode it back to ``c``: the block stays the
one already resident, and the grid pipeline does not copy a block whose
index did not change. Interior sentinels are safe — the Verlet-skin
runtime gate (drift-budget v2, DESIGN.md §4) relies on this to switch
dual-listed pairs between the approx and direct kernels by current
distance without re-packing the lists. Host-built lists emit ``-1`` as
trailing padding only; the gate is the one producer of interior
sentinels.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.potentials import Kernel, unpack_params
from repro.core.space import FREE as _FREE


def _min_image_1d(d, length):
    return d - length * jnp.round(d * (1.0 / length))


def _pair_r2(tx, sy, mode: str, space=_FREE):
    """Pairwise squared distances, (NT, m). mode='diff' subtracts on the
    VPU (cancellation-free, used for the direct kernel); mode='matmul'
    uses |x|^2+|y|^2-2x.y so the cross term runs on the MXU (beyond-paper
    optimization, used for the MAC-separated approximation kernel).
    Periodic spaces always take the difference form (the minimum-image
    fold is elementwise) with per-dimension folding."""
    if mode == "matmul" and not space.periodic:
        xy = jax.lax.dot_general(tx, sy, (((0,), (0,)), ((), ())),
                                 precision=jax.lax.Precision.HIGHEST,
                                 preferred_element_type=tx.dtype)
        x2 = jnp.sum(tx * tx, axis=0)[:, None]
        y2 = jnp.sum(sy * sy, axis=0)[None, :]
        return jnp.maximum(x2 + y2 - 2.0 * xy, 0.0)
    d0 = tx[0][:, None] - sy[0][None, :]
    d1 = tx[1][:, None] - sy[1][None, :]
    d2 = tx[2][:, None] - sy[2][None, :]
    if space.periodic:
        lx, ly, lz = space.lengths
        d0 = _min_image_1d(d0, lx)
        d1 = _min_image_1d(d1, ly)
        d2 = _min_image_1d(d2, lz)
    return d0 * d0 + d1 * d1 + d2 * d2


def _read_params(par_ref, pspec):
    """Rebuild the params pytree from the SMEM prefetch vector."""
    if pspec is None:
        return None
    return unpack_params(lambda i: par_ref[0, i], pspec)


def _slot_potential(par_ref, tgt_ref, src_ref, q_ref, *, kernel: Kernel,
                    r2_mode: str, space, pspec, dtype):
    """(1, NT) contribution of the current list slot. The charge
    contraction runs as a (1, m) x (NT, m)^T matmul on the MXU, so every
    value stays 2-D (lane axis = particles)."""
    r2 = _pair_r2(tgt_ref[0], src_ref[0], r2_mode, space)    # (NT, m)
    g = kernel(r2, _read_params(par_ref, pspec))             # 0 at r2 == 0
    return jax.lax.dot_general(
        q_ref[0], g, dimension_numbers=(((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=dtype)                        # (1, NT)


def _valid_slot(idx_ref):
    return idx_ref[pl.program_id(0), pl.program_id(2)] >= 0


def _body(idx_ref, par_ref, tgt_ref, src_ref, q_ref, out_ref, **opts):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    @pl.when(_valid_slot(idx_ref))
    def _accumulate():
        out_ref[0] += _slot_potential(par_ref, tgt_ref, src_ref, q_ref,
                                      dtype=out_ref.dtype, **opts)


def _body_kahan(idx_ref, par_ref, tgt_ref, src_ref, q_ref, out_ref,
                comp_ref, **opts):
    # Compensated (Kahan) accumulation across list slots: pushes the f32
    # floor down ~1 digit for long interaction lists (beyond-paper accuracy
    # knob; see the hardware-adaptation table in DESIGN.md).
    @pl.when(pl.program_id(2) == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)
        comp_ref[...] = jnp.zeros_like(comp_ref)

    @pl.when(_valid_slot(idx_ref))
    def _accumulate():
        y = _slot_potential(par_ref, tgt_ref, src_ref, q_ref,
                            dtype=out_ref.dtype, **opts) - comp_ref[...]
        acc = out_ref[0]
        tsum = acc + y
        comp_ref[...] = (tsum - acc) - y
        out_ref[0] = tsum


def resident_sentinels(idx: jnp.ndarray) -> jnp.ndarray:
    """`idx` (B, S) with every negative slot rewritten to ``-(1 + c)``:
    `c` is the row's last valid cluster before the slot, else its first
    valid one, else 0. `cluster_block` maps it back to `c`, so a sentinel
    slot asks for the block a neighbouring valid slot already holds."""
    slots = idx.shape[1]
    valid = idx >= 0
    pos = jnp.where(valid, jnp.arange(slots, dtype=idx.dtype), -1)
    last = jax.lax.cummax(pos, axis=1)
    first = jnp.argmax(valid, axis=1).astype(idx.dtype)[:, None]
    fill = jnp.take_along_axis(idx, jnp.where(last >= 0, last, first),
                               axis=1)
    return jnp.where(valid, idx, -1 - jnp.maximum(fill, 0))


def cluster_block(b, t, s, idx_ref, par_ref):
    """Index map of the source points and charges at grid step (b, t, s):
    the slot's cluster, or for a negative slot the one it encodes."""
    del t, par_ref
    v = idx_ref[b, s]
    return (jnp.where(v >= 0, v, -1 - v), 0, 0)


#: SMEM bytes one call's scalar-prefetched interaction list may take. The
#: v5e core has 1 MiB of SMEM and Mosaic pads the list's rows to 8;
#: longer lists run as several calls (`batch_cluster_eval_pallas`).
LIST_SMEM_BYTES = 256 * 1024


def list_split(bsz: int, slots: int):
    """How `batch_cluster_eval_pallas` splits a (bsz, slots) list so each
    call prefetches at most `LIST_SMEM_BYTES` of it.

    Returns (slot_chunks, chunk_slots, row_chunks, rows): the list runs
    as `slot_chunks` chunks of `chunk_slots` slots (sentinel-padded),
    each as `row_chunks` calls over `rows` batch rows (one call over all
    `bsz` rows when they fit, else padded to `row_chunks * rows`)."""
    max_slots = LIST_SMEM_BYTES // (8 * 4)
    slot_chunks = -(-slots // max_slots) if slots > max_slots else 1
    chunk_slots = max_slots if slots > max_slots else slots
    rows = max(8, LIST_SMEM_BYTES // (4 * chunk_slots) // 8 * 8)
    if bsz <= rows:
        return slot_chunks, chunk_slots, 1, bsz
    return slot_chunks, chunk_slots, -(-bsz // rows), rows


def kernel_work(idx, tgt_counts, src_counts, *, target_width: int,
                source_width: int, target_tile: int = 256) -> dict:
    """Pair evaluations of one `batch_cluster` list: launched, skipped
    and useful.

    `idx` (B, S) is the list as the kernel runs it (negative = empty
    slot), `tgt_counts` (B,) the real targets of each batch row,
    `src_counts` (C,) the real sources of each cluster id. Every grid
    step covers target lanes (padded to `target_tile`) x `source_width`
    pairs. `launched` counts the steps the Pallas grids compute, one per
    non-sentinel slot and target tile; `skipped` counts the sentinel
    steps, which compute and copy nothing, over every chunk of
    `list_split` (its slot and row padding included). `useful` counts
    real targets x real sources over the non-sentinel slots."""
    idx = np.asarray(idx)
    bsz, slots = idx.shape
    slot_chunks, chunk_slots, row_chunks, rows = list_split(bsz, slots)
    lanes = -(-target_width // target_tile) * target_tile
    grid = slot_chunks * chunk_slots * row_chunks * rows * lanes * source_width
    launched = int((idx >= 0).sum()) * lanes * source_width
    src = np.asarray(src_counts, np.int64)
    per_row = np.where(idx >= 0, src[np.maximum(idx, 0)], 0).sum(1)
    useful = int((np.asarray(tgt_counts, np.int64) * per_row).sum())
    return dict(launched=launched, skipped=int(grid - launched),
                useful=useful)


def batch_cluster_eval_pallas(
    idx: jnp.ndarray,      # (B, S) int32 cluster ids, negative = empty
    par: jnp.ndarray,      # (1, P) packed kernel parameter values
    tgt: jnp.ndarray,      # (B, 3, NB) coordinate-major padded targets
    src_pts: jnp.ndarray,  # (C, 3, m) coordinate-major cluster points
    src_q: jnp.ndarray,    # (C, m) charges (0 = padding)
    kernel: Kernel,
    **opts,
) -> jnp.ndarray:
    """phi (B, NB): potentials of every batch against its interaction list.

    The list is split so each kernel call prefetches at most
    `LIST_SMEM_BYTES` of it: slots in chunks summed afterwards (sentinels
    contribute zero), batches in row chunks under `lax.map` (one compiled
    kernel for all chunks)."""
    idx = idx.astype(jnp.int32)
    bsz, slots = idx.shape
    k, chunk_slots, nchunk, rows = list_split(bsz, slots)
    if k > 1:
        idx_s = jnp.pad(idx, ((0, 0), (0, k * chunk_slots - slots)),
                        constant_values=-1)
        idx_s = jnp.moveaxis(idx_s.reshape(bsz, k, chunk_slots), 1, 0)
        return jax.lax.map(
            lambda i: batch_cluster_eval_pallas(
                i, par, tgt, src_pts, src_q, kernel, **opts),
            idx_s).sum(axis=0)
    if nchunk == 1:
        return _batch_cluster_call(idx, par, tgt, src_pts, src_q, kernel,
                                   **opts)
    pad = nchunk * rows - bsz
    idx_c = jnp.pad(idx, ((0, pad), (0, 0)), constant_values=-1)
    tgt_c = jnp.pad(tgt, ((0, pad), (0, 0), (0, 0)))
    phi = jax.lax.map(
        lambda a: _batch_cluster_call(a[0], par, a[1], src_pts, src_q,
                                      kernel, **opts),
        (idx_c.reshape(nchunk, rows, slots),
         tgt_c.reshape(nchunk, rows, *tgt.shape[1:])))
    return phi.reshape(nchunk * rows, -1)[:bsz]


def _batch_cluster_call(
    idx: jnp.ndarray,
    par: jnp.ndarray,
    tgt: jnp.ndarray,
    src_pts: jnp.ndarray,
    src_q: jnp.ndarray,
    kernel: Kernel,
    *,
    pspec=None,            # static (treedef, shapes) for `par`
    space=_FREE,
    target_tile: int = 256,
    kahan: bool = False,
    r2_mode: str = "diff",
    interpret: bool = False,
    name: str = "batch_cluster",
) -> jnp.ndarray:
    """One `pallas_call` over grid (batch, target-tile, list-slot)."""
    bsz, _, nb = tgt.shape
    _, _, m = src_pts.shape
    slots = idx.shape[1]
    nt = min(target_tile, nb)
    if nb % nt:
        raise ValueError(f"NB={nb} must be a multiple of target tile {nt}")
    ntiles = nb // nt

    grid = (bsz, ntiles, slots)

    def tgt_map(b, t, s, idx_ref, par_ref):
        del s, idx_ref, par_ref
        return (b, 0, t)

    def out_map(b, t, s, idx_ref, par_ref):
        del s, idx_ref, par_ref
        return (b, 0, t)

    kwargs = {}
    if not interpret:
        kwargs["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"))

    opts = dict(kernel=kernel, r2_mode=r2_mode, space=space, pspec=pspec)
    if kahan:
        body = functools.partial(_body_kahan, **opts)
        scratch = [pltpu.VMEM((1, nt), tgt.dtype)]
    else:
        body = functools.partial(_body, **opts)
        scratch = []

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 3, nt), tgt_map),
            pl.BlockSpec((1, 3, m), cluster_block),
            pl.BlockSpec((1, 1, m), cluster_block),
        ],
        out_specs=pl.BlockSpec((1, 1, nt), out_map),
        scratch_shapes=scratch,
    )
    # Charges and potentials carry a unit middle axis so each block's last
    # two dims are (1, full) -- Mosaic's tiling rule forbids a block of 1
    # on the second-to-last axis of a 2-D array.
    phi = pl.pallas_call(
        body,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((bsz, 1, nb), tgt.dtype),
        interpret=interpret,
        name=name,
        **kwargs,
    )(resident_sentinels(idx), par.astype(tgt.dtype), tgt, src_pts,
      src_q[:, None, :])
    return phi[:, 0, :]
