"""Adaptive-depth budgeted octree from Morton codes, entirely on device.

The host build is a recursive midpoint bisection; the device build is
the standard GPU alternative (Gaburov & Bedorf, arXiv:1005.5384): a
HYBRID octree over the Morton grid — a dense complete octree through a
static split depth, then one COMPACTED occupied-cell block per deeper
level. A cell at level l is a 3l-bit code prefix, so after the radix
sort every cell owns a contiguous particle run recoverable with one
segmented reduction — no recursion, no data-dependent shapes:

  * dense levels (l <= `SPLIT_DEPTH`): counts via sorted-run boundaries
    (one `searchsorted` over the code prefix), coarser levels by
    (cells/8, 8) reshape reductions, gid = OFF[l] + cell;
  * sparse levels (l > `SPLIT_DEPTH`): the occupied cells are found by
    boundary-mask compaction of the sorted prefixes (cumsum +
    searchsorted, the same scatter-free style as `lists.py`) into a
    `Capacities.sparse_rows`-budgeted table sorted by code; gid =
    block_base + row, child lookup is a `searchsorted` into the block;
  * boxes: ONE `segment_min`/`segment_max` at the deepest level, then
    exact upward aggregation (parents gather their children's
    contiguous code-window);
  * occupancy masks: a cell is ACTIVE if non-empty with an active
    internal parent; an active cell is a LEAF if its count fits
    `leaf_size` or it sits at the bottom level;
  * leaves/batches are enumerated into budgeted tables by an argsort
    on start (so leaf slots are in particle order, as on host), and
    every structure is padded to a `Capacities` budget with the same
    sentinel conventions as `eval.pad_plan` (-1 gathers, [0,1] boxes,
    scratch-node ids).

The dense block caps memory at OFF[SPLIT_DEPTH + 1] rows regardless of
depth, and the sparse blocks grow with the DATA (occupied cells), not
with 8^l — which is what lifts the old dense-storage cap (d <= 5) to
`MAX_DEPTH` = 8 within budget headroom; see DESIGN.md §10.

The produced `Plan` has the exact `arrays` schema of the host
`prepare_plan` (same keys, dtypes, sentinel rules), plus `plan.dev`
metadata backing lazy host `Tree`/`Batches` proxies — diagnostics and
the sharded/adapter paths materialize them on first touch; the step
loop never does, so a budgeted rebuild syncs only the needs vector
(a few dozen ints) and the two slack scalars.

`dispatch_plan_device` is the double-buffered variant of that rebuild:
it enqueues the sort/build/list passes WITHOUT the needs sync and
returns a `PendingDevicePlan`, so the caller keeps dispatching work on
its live plan while the shadow build runs behind it (plain jax async
dispatch — no threads); `finalize()` pays only the leftover wait.
"""
from __future__ import annotations

import dataclasses
import functools
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import eval as _eval
from repro.core import interaction as _interaction
from repro.core.space import FREE as _FREE
from repro.core.tree import Batches, Tree
from repro.devtree import lists as _lists
from repro.devtree import morton as _morton
from repro.obs import events as _events
from repro.obs import trace as _trace

#: Deepest level stored DENSELY: num dense rows = (8^(S+1) - 1)/7 and
#: the modified-charge table is O(num_nodes * (degree+1)^3), so S = 4
#: (4681 cells) keeps the dense block cheap; deeper levels switch to
#: compacted occupied-cell blocks whose size tracks the data.
SPLIT_DEPTH = 4

#: Adaptive-depth cap. Morton codes carry 3 * BITS = 30 bits, so 8
#: levels (24 bits) leave slack; the sparse blocks keep node storage
#: O(occupied cells), so depth is no longer a memory cliff.
MAX_DEPTH = 8


def depth_for(n: int, leaf_size: int, max_depth: int = MAX_DEPTH) -> int:
    """Smallest depth whose 8^d cells could hold n at leaf_size, capped."""
    d = 1
    while (8 ** d) * max(leaf_size, 1) < n and d < max_depth:
        d += 1
    return d


@functools.lru_cache(maxsize=None)
def _static_nodes(depth: int):
    """(offsets, M, level_of, cell_of, parent_of) for the dense block."""
    off = tuple((8 ** l - 1) // 7 for l in range(depth + 2))
    m = off[depth + 1]
    level = np.concatenate(
        [np.full(8 ** l, l, np.int32) for l in range(depth + 1)])
    cell = np.concatenate(
        [np.arange(8 ** l, dtype=np.int32) for l in range(depth + 1)])
    parent = np.full(m, -1, np.int32)
    for l in range(1, depth + 1):
        k = np.arange(8 ** l, dtype=np.int32)
        parent[off[l] + k] = off[l - 1] + (k >> 3)
    return off, m, level, cell, parent


@functools.lru_cache(maxsize=None)
def _level_spans(depth: int, srows):
    """Static ((base, length) per level, total rows) of the hybrid
    node-id space: dense levels first (gid = OFF[l] + cell), then one
    budgeted block per sparse level (gid = base + occupied row)."""
    sd = min(depth, SPLIT_DEPTH)
    off, m, _, _, _ = _static_nodes(sd)
    spans = [(off[l], 8 ** l) for l in range(sd + 1)]
    base = m
    for r in srows:
        spans.append((base, r))
        base += r
    return tuple(spans), base


def _clamp_nodes(caps: "_eval.Capacities", depth: int):
    """Grow `num_nodes` to cover the hybrid layout its sparse row
    budgets imply (+1 scratch row)."""
    _, m_tot = _level_spans(depth, caps.sparse_rows)
    if caps.num_nodes < m_tot + 1:
        caps = dataclasses.replace(caps, num_nodes=m_tot + 1)
    return caps


def _dense_levels(x_sorted, codes, *, depth, leaf_size, bits,
                  bottom_leaf=True, bottom_boxes=None):
    """Dense per-cell arrays for levels 0..depth, as per-level lists.

    Bottom counts come from the sorted-run boundaries (one
    `searchsorted` over the code prefix); every coarser level then
    aggregates its children with a (cells/8, 8) reshape reduction,
    exact because a parent's particle run is the concatenation of its
    children's runs and min/max ignore the empty-segment identities.
    Segmented box reductions run ONCE, at the deepest level — XLA's CPU
    backend lowers them to serial scatters, the slowest primitive in
    the build — unless a hybrid build injects `bottom_boxes` already
    aggregated from its sparse levels (empty cells must carry the
    +/-inf identities there). With ``bottom_leaf=False`` the bottom
    level keeps only the count-based leaf rule, so oversized bottom
    cells stay internal and the activity chain continues into the
    sparse levels (returned as the bottom `parent_internal` mask).
    """
    nseg = 8 ** depth
    seg = _morton.prefix(codes, depth, bits)
    bounds = jnp.searchsorted(
        seg, jnp.arange(nseg + 1, dtype=seg.dtype)).astype(jnp.int32)
    cnt = bounds[1:] - bounds[:-1]
    start = bounds[:-1]
    if bottom_boxes is None:
        lo = jax.ops.segment_min(x_sorted, seg, nseg,
                                 indices_are_sorted=True)
        hi = jax.ops.segment_max(x_sorted, seg, nseg,
                                 indices_are_sorted=True)
    else:
        lo, hi = bottom_boxes
    per = {depth: (cnt, start, lo, hi)}
    for l in range(depth - 1, -1, -1):
        cnt = cnt.reshape(-1, 8).sum(axis=1)
        start = start.reshape(-1, 8)[:, 0]
        lo = lo.reshape(-1, 8, 3).min(axis=1)
        hi = hi.reshape(-1, 8, 3).max(axis=1)
        per[l] = (cnt, start, lo, hi)
    out = {k: [] for k in ("count", "start", "lo", "hi", "active", "leaf")}
    parent_internal = None
    for l in range(depth + 1):
        cnt, start, lo, hi = per[l]
        nonempty = cnt > 0
        # Empty cells keep the [0, 1] sentinel box (pad_plan convention).
        lo = jnp.where(nonempty[:, None], lo, 0.0)
        hi = jnp.where(nonempty[:, None], hi, 1.0)
        act = nonempty if l == 0 else nonempty & jnp.repeat(
            parent_internal, 8)
        leaf = act & (cnt <= leaf_size)
        if bottom_leaf and l == depth:
            leaf = act
        parent_internal = act & ~leaf
        for k, v in zip(("count", "start", "lo", "hi", "active", "leaf"),
                        (cnt, start, lo, hi, act, leaf)):
            out[k].append(v)
    return out, parent_internal


def _child_boxes(par_code, kid_code, kid_lo, kid_hi):
    """Aggregate child boxes into parents by sorted-window gather: a
    parent's occupied children sit contiguously in the ascending child
    code table, at [searchsorted(kids, p*8), searchsorted(kids, p*8+8)).
    Childless parents come out at the +/-inf reduction identities."""
    r = kid_code.shape[0]
    clo = jnp.searchsorted(kid_code, par_code * 8).astype(jnp.int32)
    chi = jnp.searchsorted(kid_code, par_code * 8 + 8).astype(jnp.int32)
    k8 = jnp.arange(8, dtype=jnp.int32)[None, :]
    idx = jnp.clip(clo[:, None] + k8, 0, r - 1)
    has = k8 < (chi - clo)[:, None]
    inf = jnp.asarray(jnp.inf, kid_lo.dtype)
    lo = jnp.min(jnp.where(has[..., None], kid_lo[idx], inf), axis=1)
    hi = jnp.max(jnp.where(has[..., None], kid_hi[idx], -inf), axis=1)
    return lo, hi


def _hybrid_structs(x_sorted, codes, *, depth, rows, leaf_size, bits):
    """Flat per-node arrays over the hybrid node-id space.

    Returns (st, node_code, n_occ): `st` holds the per-node struct keys
    concatenated over dense-then-sparse blocks, `node_code` is every
    row's cell code at its own level (`PAD_CODE` on padded sparse
    rows), and `n_occ` the TRUE per-sparse-level occupied-cell counts —
    the needs-vector entries that detect row-budget overflow (truncated
    tables are then garbage, discarded by the growth loop, the same
    contract as the budgeted list lanes).
    """
    sd = min(depth, SPLIT_DEPTH)
    n = x_sorted.shape[0]
    if depth <= sd:
        out, _ = _dense_levels(x_sorted, codes, depth=depth,
                               leaf_size=leaf_size, bits=bits)
        st = {k: jnp.concatenate(v, axis=0) for k, v in out.items()}
        node_code = jnp.concatenate(
            [jnp.arange(8 ** l, dtype=jnp.int32)
             for l in range(depth + 1)])
        return st, node_code, ()

    assert len(rows) == depth - sd
    pad = jnp.int32(_morton.PAD_CODE)
    # Occupied-cell discovery per sparse level: boundary-mask
    # compaction of the sorted prefixes. A padded row gets
    # start = n (so its count is 0) and code = PAD_CODE; the last real
    # row's count runs to the next row's start, which is n at the end.
    lvls, occs = [], []
    for i, l in enumerate(range(sd + 1, depth + 1)):
        r = rows[i]
        seg = _morton.prefix(codes, l, bits)
        first = jnp.concatenate(
            [jnp.ones((1,), bool), seg[1:] != seg[:-1]])
        c = jnp.cumsum(first.astype(jnp.int32))
        sel = jnp.searchsorted(c, jnp.arange(1, r + 1, dtype=jnp.int32))
        idx = jnp.clip(sel, 0, n - 1).astype(jnp.int32)
        ok = jnp.arange(r, dtype=jnp.int32) < c[-1]
        start = jnp.where(ok, idx, n).astype(jnp.int32)
        code = jnp.where(ok, seg[idx], pad)
        nxt = jnp.concatenate([start[1:], jnp.full((1,), n, jnp.int32)])
        lvls.append(dict(code=code, start=start, count=nxt - start, ok=ok))
        occs.append(c[-1])

    # Boxes: one segmented reduction at the deepest level (row ids are
    # nondecreasing along the sorted particles), aggregated upward
    # through the code windows, then injected into the dense block.
    deep, rdeep = lvls[-1], rows[-1]
    row_of = jnp.clip(
        jnp.searchsorted(deep["code"], _morton.prefix(codes, depth, bits)),
        0, rdeep - 1).astype(jnp.int32)
    deep["lo"] = jax.ops.segment_min(x_sorted, row_of, rdeep,
                                     indices_are_sorted=True)
    deep["hi"] = jax.ops.segment_max(x_sorted, row_of, rdeep,
                                     indices_are_sorted=True)
    for i in range(len(lvls) - 2, -1, -1):
        lvls[i]["lo"], lvls[i]["hi"] = _child_boxes(
            lvls[i]["code"], lvls[i + 1]["code"],
            lvls[i + 1]["lo"], lvls[i + 1]["hi"])
    dlo, dhi = _child_boxes(jnp.arange(8 ** sd, dtype=jnp.int32),
                            lvls[0]["code"], lvls[0]["lo"], lvls[0]["hi"])
    out, par_int = _dense_levels(x_sorted, codes, depth=sd,
                                 leaf_size=leaf_size, bits=bits,
                                 bottom_leaf=False, bottom_boxes=(dlo, dhi))

    # Active/leaf chain continues top-down through the sparse levels:
    # a row's parent is a dense-bottom cell (block 0, bit arithmetic)
    # or the previous block's row holding code >> 3 (searchsorted, with
    # a code-match guard so padded rows never borrow a parent).
    parts = {k: list(v) for k, v in out.items()}
    code_parts = [jnp.arange(8 ** l, dtype=jnp.int32)
                  for l in range(sd + 1)]
    prev = None
    for i, l in enumerate(range(sd + 1, depth + 1)):
        d = lvls[i]
        pc = d["code"] >> 3
        if prev is None:
            par_internal = par_int[jnp.clip(pc, 0, 8 ** sd - 1)]
        else:
            pr = jnp.clip(jnp.searchsorted(prev["code"], pc),
                          0, rows[i - 1] - 1).astype(jnp.int32)
            par_internal = prev["internal"][pr] & (prev["code"][pr] == pc)
        act = d["ok"] & par_internal
        leaf = act & ((d["count"] <= leaf_size) | (l == depth))
        d["internal"] = act & ~leaf
        parts["count"].append(jnp.where(d["ok"], d["count"], 0))
        parts["start"].append(d["start"])
        parts["lo"].append(jnp.where(d["ok"][:, None], d["lo"], 0.0))
        parts["hi"].append(jnp.where(d["ok"][:, None], d["hi"], 1.0))
        parts["active"].append(act)
        parts["leaf"].append(leaf)
        code_parts.append(d["code"])
        prev = d
    st = {k: jnp.concatenate(v, axis=0) for k, v in parts.items()}
    return st, jnp.concatenate(code_parts), tuple(occs)


def _leaf_tables(st, *, cap, width):
    """Budgeted enumeration of the leaf cells of a level structure.

    Rows are in particle (start) order — the host `Tree.leaf_ids`
    convention — so leaf particle ranges tile [0, N) across valid rows.
    Serves both the source leaves and (applied to the target tree) the
    batches. Rows past the true leaf count are sentinel rows.
    """
    m = st["count"].shape[0]
    n = jnp.sum(st["leaf"].astype(jnp.int32))
    key = jnp.where(st["leaf"], st["start"], jnp.int32(2 ** 31 - 1))
    order = jnp.argsort(key).astype(jnp.int32)
    idx = jnp.arange(cap, dtype=jnp.int32)
    ids = order[jnp.clip(idx, 0, m - 1)]
    valid = (idx < m) & (idx < n)
    start = jnp.where(valid, st["start"][ids], 0)
    count = jnp.where(valid, st["count"][ids], 0)
    ar = jnp.arange(width, dtype=jnp.int32)
    gather = jnp.where(ar[None, :] < count[:, None],
                       start[:, None] + ar[None, :], -1)
    return dict(
        ids=jnp.where(valid, ids, -1), n=n, valid=valid,
        start=start, count=count, gather=gather,
        lo=jnp.where(valid[:, None], st["lo"][ids], 0.0),
        hi=jnp.where(valid[:, None], st["hi"][ids], 1.0),
        max_count=jnp.max(jnp.where(st["leaf"], st["count"], 0)),
    )


def _bucket_tables(st, *, spans, rows, widths, scratch):
    """Per-level active-node gather tables for the q_hat kernels."""
    gathers, nodes = [], []
    for (base, ln), rcap, w in zip(spans, rows, widths):
        act = st["active"][base:base + ln]
        n_act = jnp.sum(act.astype(jnp.int32))
        order = jnp.argsort(~act).astype(jnp.int32)  # active first
        idx = jnp.arange(rcap, dtype=jnp.int32)
        cells = order[jnp.clip(idx, 0, ln - 1)]
        valid = (idx < ln) & (idx < n_act)
        start = jnp.where(valid, st["start"][base + cells], 0)
        count = jnp.where(valid, st["count"][base + cells], 0)
        ar = jnp.arange(w, dtype=jnp.int32)
        gathers.append(jnp.where(ar[None, :] < count[:, None],
                                 start[:, None] + ar[None, :], -1))
        nodes.append(jnp.where(valid, base + cells, scratch)
                     .astype(jnp.int32))
    return tuple(gathers), tuple(nodes)


def _build_dims(caps: "_eval.Capacities"):
    """The subset of the budget the build phase shapes depend on —
    list-lane widths excluded, so the needs pass (widths still at their
    placeholder) and the final build share one compiled executable."""
    return (caps.num_leaves, caps.leaf_width, caps.num_batches,
            caps.batch_width, caps.num_nodes, caps.scratch_node,
            caps.bucket_rows, caps.bucket_widths,
            caps.sparse_rows, caps.batch_sparse_rows)


@functools.partial(jax.jit, static_argnames=(
    "dims", "depth", "tdepth", "leaf_size", "batch_size", "bits"))
def _build_phase(xs_sorted, codes_s, xt_sorted, codes_t, order_t, *,
                 dims, depth, tdepth, leaf_size, batch_size, bits):
    """Sorted particles -> budgeted tree/batch/pack arrays, one launch."""
    (n_leaf_cap, leaf_w, n_batch_cap, batch_w,
     num_nodes, scratch, bucket_rows, bucket_widths,
     srows, tsrows) = dims
    sd = min(depth, SPLIT_DEPTH)
    off, _, _, _, parent_np = _static_nodes(sd)
    spans, m = _level_spans(depth, srows)

    ss, scode, socc = _hybrid_structs(
        xs_sorted, codes_s, depth=depth, rows=srows,
        leaf_size=leaf_size, bits=bits)
    tt, _, tocc = _hybrid_structs(
        xt_sorted, codes_t, depth=tdepth, rows=tsrows,
        leaf_size=batch_size, bits=bits)
    leaf = _leaf_tables(ss, cap=n_leaf_cap, width=leaf_w)
    batch = _leaf_tables(tt, cap=n_batch_cap, width=batch_w)

    # Target slab packing + input-order gather, the device analogue of
    # the host pack: scatter each sorted target's padded slot, then
    # compose with the inverse sort permutation.
    n_t = xt_sorted.shape[0]
    g = batch["gather"]
    mask = g >= 0
    tgt_b = jnp.where(mask[..., None],
                      xt_sorted[jnp.clip(g, 0, n_t - 1)], 0.0)
    slots = jnp.arange(g.size, dtype=jnp.int32).reshape(g.shape)
    # lint: disable=DV001 — replan-time slab packing: one scatter per
    # rebuild composes the inverse sort permutation; the PR 8 scatter-free
    # contract covers the per-step traversal, which stays gather-only.
    pos_sorted = jnp.zeros((n_t,), jnp.int32).at[
        jnp.where(mask, g, n_t)].set(slots, mode="drop")
    # lint: disable=DV001 — replan-time inverse permutation (as above).
    inv_t = jnp.zeros((n_t,), jnp.int32).at[order_t].set(
        jnp.arange(n_t, dtype=jnp.int32))
    gather_index = pos_sorted[inv_t]

    bucket_gather, bucket_nodes = _bucket_tables(
        ss, spans=spans, rows=bucket_rows, widths=bucket_widths,
        scratch=scratch)

    dt = xs_sorted.dtype
    # lint: disable=DV001 — replan-time node-box init (scatter-free
    # contract covers traversal, not the build phase).
    node_lo = jnp.zeros((num_nodes, 3), dt).at[:m].set(ss["lo"].astype(dt))
    # lint: disable=DV001 — replan-time node-box init (as above).
    node_hi = jnp.ones((num_nodes, 3), dt).at[:m].set(ss["hi"].astype(dt))

    # Hybrid parent table, on device (sparse rows' parents depend on
    # which cells are occupied): dense parents are static, block 0
    # parents are dense-bottom bit arithmetic, deeper blocks find
    # code >> 3 in the previous block. Padded rows park on scratch.
    pparts = [jnp.asarray(parent_np)]
    for i, (base, r) in enumerate(spans[sd + 1:]):
        code = scode[base:base + r]
        pc = code >> 3
        if i == 0:
            par = off[sd] + jnp.clip(pc, 0, 8 ** sd - 1)
        else:
            pbase, pr = spans[sd + i]
            pcode = scode[pbase:pbase + pr]
            par = pbase + jnp.clip(
                jnp.searchsorted(pcode, pc), 0, pr - 1).astype(jnp.int32)
        pparts.append(jnp.where(code < jnp.int32(_morton.PAD_CODE),
                                par, scratch).astype(jnp.int32))
    # lint: disable=DV001 — replan-time parent-table init; the PR 8
    # scatter-free contract covers the per-step traversal, not the build.
    parent_of = jnp.full((num_nodes,), scratch, jnp.int32).at[:m].set(
        jnp.concatenate(pparts))

    busy_rows, busy_widths = [], []
    for base, ln in spans:
        act = ss["active"][base:base + ln]
        busy_rows.append(jnp.sum(act.astype(jnp.int32)))
        busy_widths.append(jnp.max(jnp.where(
            act, ss["count"][base:base + ln], 0)))

    return dict(
        node_count=ss["count"], node_start=ss["start"],
        node_active=ss["active"], node_leaf=ss["leaf"],
        node_lo=node_lo, node_hi=node_hi, node_code=scode,
        parent_of=parent_of,
        leaf=leaf, batch=batch,
        tgt_batched=tgt_b, tgt_mask=mask, gather_index=gather_index,
        bucket_gather=bucket_gather, bucket_nodes=bucket_nodes,
        need=dict(num_leaves=leaf["n"], leaf_width=leaf["max_count"],
                  num_batches=batch["n"], batch_width=batch["max_count"],
                  bucket_rows=tuple(busy_rows),
                  bucket_widths=tuple(busy_widths),
                  sparse_rows=socc, batch_sparse_rows=tocc),
    )


@functools.partial(jax.jit, static_argnames=("depth", "tdepth", "bits"))
def _occupancy_phase(codes_s, codes_t, *, depth, tdepth, bits):
    """Stage-0 probe: per-sparse-level occupied-cell counts for both
    trees — scalar boundary-mask sums, no budget-shaped arrays."""

    def occ(codes, d):
        res = []
        for l in range(min(d, SPLIT_DEPTH) + 1, d + 1):
            seg = _morton.prefix(codes, l, bits)
            res.append(1 + jnp.sum((seg[1:] != seg[:-1])
                                   .astype(jnp.int32)))
        return tuple(res)

    return occ(codes_s, depth), occ(codes_t, tdepth)


@functools.partial(jax.jit, static_argnames=(
    "depth", "tdepth", "leaf_size", "batch_size", "bits",
    "srows", "tsrows"))
def _needs_phase(xs_sorted, codes_s, xt_sorted, codes_t, *,
                 depth, tdepth, leaf_size, batch_size, bits,
                 srows, tsrows):
    """First-build probe: the structural needs, 1-D reductions only.

    Runs before the full budget exists — the sparse row budgets come
    from the stage-0 occupancy probe, so nothing here is sized by a
    guess that could truncate. Every output is a scalar.
    """
    ss, _, socc = _hybrid_structs(xs_sorted, codes_s, depth=depth,
                                  rows=srows, leaf_size=leaf_size,
                                  bits=bits)
    tt, _, tocc = _hybrid_structs(xt_sorted, codes_t, depth=tdepth,
                                  rows=tsrows, leaf_size=batch_size,
                                  bits=bits)
    spans, _ = _level_spans(depth, srows)
    rows, widths = [], []
    for base, ln in spans:
        act = ss["active"][base:base + ln]
        rows.append(jnp.sum(act.astype(jnp.int32)))
        widths.append(jnp.max(jnp.where(
            act, ss["count"][base:base + ln], 0)))
    return dict(
        num_leaves=jnp.sum(ss["leaf"].astype(jnp.int32)),
        leaf_width=jnp.max(jnp.where(ss["leaf"], ss["count"], 0)),
        num_batches=jnp.sum(tt["leaf"].astype(jnp.int32)),
        batch_width=jnp.max(jnp.where(tt["leaf"], tt["count"], 0)),
        bucket_rows=tuple(rows), bucket_widths=tuple(widths),
        sparse_rows=socc, batch_sparse_rows=tocc,
    )


def _qcap(x, floor: int = 1024) -> int:
    """Quantized pair budget: the ladder {1, 1.25, 1.5, 1.75} * 2^k.

    Coarse enough that replans at steady state never see a new static
    shape from need jitter, fine enough (+25% steps) that the padded
    traversal work tracks the true pair counts."""
    v = floor
    while v < int(x):
        v += (1 << (v.bit_length() - 1)) // 4
    return v


def _logged(label, fn, *args, **kwargs):
    out, _ = _events.log_compiles(label, fn, *args, owner="devtree",
                                  site="devtree.build", **kwargs)
    return out


def _ints(tree):
    """Device needs pytree -> host ints (the tiny per-rebuild sync)."""
    host = jax.device_get(tree)
    return jax.tree.map(lambda v: int(v), host)


class _LazyStruct:
    """Materialize-on-first-touch proxy for host `Tree`/`Batches`.

    The step loop never reads the host trees; diagnostics and the
    adapter init do. Deferring the device->host sync to that first
    access keeps the budgeted-rebuild path free of position syncs.
    Snapshot semantics match the host path: geometry is as of build
    time (host plans keep their build-time tree across refits too).
    """

    def __init__(self, thunk):
        self._thunk = thunk
        self._obj = None

    def _materialize(self):
        if self._obj is None:
            self._obj = self._thunk()
        return self._obj

    def __getattr__(self, name):
        if name.startswith("_"):
            raise AttributeError(name)
        return getattr(self._materialize(), name)


def _materialize_tree(dev, node_lo, node_hi) -> Tree:
    # The deferred device->host copy, explicit: a transfer guard refuses
    # an implicit one (np.asarray of a device array) on an accelerator.
    dev, node_lo, node_hi = jax.device_get((dev, node_lo, node_hi))
    depth = dev["depth"]
    srows = tuple(dev.get("sparse_rows", ()))
    occ = tuple(dev.get("sparse_occ", ()))
    sd = min(depth, SPLIT_DEPTH)
    off, md, level_d, _, parent_d = _static_nodes(sd)
    spans, m = _level_spans(depth, srows)
    count = np.asarray(dev["node_count"]).astype(np.int64)
    start = np.asarray(dev["node_start"]).astype(np.int64)
    active = np.asarray(dev["node_active"])
    leafm = np.asarray(dev["node_leaf"])
    code = np.asarray(dev["node_code"]).astype(np.int64)
    lo = np.asarray(node_lo)[:m]
    hi = np.asarray(node_hi)[:m]
    level = np.concatenate(
        [level_d.astype(np.int64)]
        + [np.full(r, sd + 1 + i, np.int64)
           for i, (_, r) in enumerate(spans[sd + 1:])])
    parent = np.full(m, -1, np.int64)
    parent[:md] = parent_d
    for i, (base, r) in enumerate(spans[sd + 1:]):
        no = int(occ[i])
        pc = code[base:base + no] >> 3
        if i == 0:
            parent[base:base + no] = off[sd] + pc
        else:
            pbase, _ = spans[sd + i]
            pcode = code[pbase:pbase + int(occ[i - 1])]
            parent[base:base + no] = pbase + np.searchsorted(pcode, pc)
    children = np.full((m, 8), -1, np.int64)
    for l in range(sd):
        k = np.arange(8 ** l)
        par = off[l] + k
        kids = off[l + 1] + (k[:, None] * 8 + np.arange(8)[None, :])
        link = (active[kids] & active[par][:, None]
                & ~leafm[par][:, None])
        children[par] = np.where(link, kids, -1)
    for i, (base, r) in enumerate(spans[sd + 1:]):
        no = int(occ[i])
        gid = base + np.arange(no)
        par = parent[base:base + no]
        slot = code[base:base + no] & 7
        link = active[gid] & active[par] & ~leafm[par]
        children[par[link], slot[link]] = gid[link]
    n_leaves = int(dev["n_leaves"])
    leaf_ids = np.asarray(dev["leaf_ids"])[:n_leaves].astype(np.int64)
    leaf_index = np.full(m, -1, np.int64)
    leaf_index[leaf_ids] = np.arange(n_leaves)
    return Tree(
        lo=lo, hi=hi, center=0.5 * (lo + hi),
        radius=0.5 * np.linalg.norm(hi - lo, axis=1),
        start=start, count=count, level=level,
        parent=parent, children=children,
        is_leaf=leafm, perm=np.asarray(dev["src_perm"]).astype(np.int64),
        leaf_ids=leaf_ids, leaf_index=leaf_index,
    )


def _materialize_batches(dev) -> Batches:
    dev = jax.device_get(dev)           # explicit, as in _materialize_tree
    nb = int(dev["n_batches"])
    lo = np.asarray(dev["b_lo"])[:nb]
    hi = np.asarray(dev["b_hi"])[:nb]
    return Batches(
        center=0.5 * (lo + hi),
        radius=0.5 * np.linalg.norm(hi - lo, axis=1),
        start=np.asarray(dev["b_start"])[:nb].astype(np.int64),
        count=np.asarray(dev["b_count"])[:nb].astype(np.int64),
        perm=np.asarray(dev["tgt_perm"]).astype(np.int64),
        half_extent=0.5 * (hi - lo),
    )


def prepare_plan_device(
    targets, sources, *, theta, degree, leaf_size, batch_size,
    space=_FREE, skin=0.0, dtype=None, capacities=None,
    headroom: float = 1.15, base: int = 8,
    depth=None, batch_depth=None, pair_caps=None,
) -> "_eval.Plan":
    """Device-resident `prepare_plan`: same contract, no host tree.

    With ``capacities=None`` (first build) a cheap occupancy + 1-D
    needs probe plus a count-only traversal size the budget; with an
    existing `Capacities` (the replan path) the build runs straight at
    the budgeted shapes and syncs only the needs vector — overflow
    grows the budget geometrically (a `capacity_growth` event +
    rebuild, the same deliberate-retrace contract as the host
    `pad_plan` path).

    `depth`/`batch_depth` override the derived octree depths — the
    sharded path pins a common depth across ranks so the per-rank plans
    stack into one budget. `pair_caps` carries the internal traversal
    budgets (frontier pairs, skin pairs) from a previous plan so
    replans hit the already-compiled list pass.
    """
    if skin < 0.0:
        raise ValueError(f"skin must be >= 0, got {skin}")
    with _trace.span("plan.build"):
        b = _DeviceBuild(
            targets, sources, theta=theta, degree=degree,
            leaf_size=leaf_size, batch_size=batch_size, space=space,
            skin=skin, dtype=dtype, headroom=headroom, base=base,
            depth=depth, batch_depth=batch_depth)
        return b.run_sync(capacities, pair_caps)


def dispatch_plan_device(
    targets, sources, *, theta, degree, leaf_size, batch_size,
    capacities, pair_caps, space=_FREE, skin=0.0, dtype=None,
    headroom: float = 1.15, base: int = 8,
    depth=None, batch_depth=None,
) -> "PendingDevicePlan":
    """Enqueue a full device replan and return without blocking.

    The double-buffered rebuild path: sort, build, and list passes are
    dispatched at the existing budget (`capacities`/`pair_caps` are
    REQUIRED — only a budgeted replan can skip the needs probe), and no
    `block_until_ready` or needs sync happens here. The caller keeps
    using its live plan; `PendingDevicePlan.finalize()` later pays
    whatever device time is still outstanding (reported as wait_ms) and
    assembles the shadow plan.
    """
    if skin < 0.0:
        raise ValueError(f"skin must be >= 0, got {skin}")
    if capacities is None or pair_caps is None:
        raise ValueError(
            "dispatch_plan_device requires an existing capacities budget "
            "and pair_caps (the async path never probes)")
    b = _DeviceBuild(
        targets, sources, theta=theta, degree=degree,
        leaf_size=leaf_size, batch_size=batch_size, space=space,
        skin=skin, dtype=dtype, headroom=headroom, base=base,
        depth=depth, batch_depth=batch_depth)
    return b.dispatch(capacities, pair_caps)


class _DeviceBuild:
    """One device build's context: sorted inputs, static dims, and the
    shared build/list/grow/assemble steps behind both the synchronous
    (`prepare_plan_device`) and double-buffered (`dispatch_plan_device`
    -> `PendingDevicePlan`) entry points."""

    def __init__(self, targets, sources, *, theta, degree, leaf_size,
                 batch_size, space, skin, dtype, headroom, base,
                 depth, batch_depth):
        shared = targets is sources
        xt = jnp.asarray(targets) if dtype is None else jnp.asarray(
            targets, dtype)
        xs = xt if shared else (jnp.asarray(sources) if dtype is None
                                else jnp.asarray(sources, dtype))
        self.xt, self.xs, self.shared = xt, xs, shared
        self.n_t, self.n_s = int(xt.shape[0]), int(xs.shape[0])
        if self.n_t == 0 or self.n_s == 0:
            raise ValueError("cannot build a tree over zero particles")
        self.d_src = (depth if depth is not None
                      else depth_for(self.n_s, leaf_size))
        self.d_tgt = (batch_depth if batch_depth is not None
                      else depth_for(self.n_t, batch_size))
        self.sd = min(self.d_src, SPLIT_DEPTH)
        self.tsd = min(self.d_tgt, SPLIT_DEPTH)
        self.bits = _morton.BITS
        self.off = _static_nodes(self.sd)[0]
        self.theta, self.skin = float(theta), float(skin)
        self.degree = int(degree)
        self.space = space
        self.headroom, self.base = headroom, base
        self.static_kw = dict(depth=self.d_src, tdepth=self.d_tgt,
                              leaf_size=int(leaf_size),
                              batch_size=int(batch_size), bits=self.bits)
        self.build_ms = {}

    # -- phases --------------------------------------------------------

    def sort(self, block: bool):
        t0 = time.perf_counter()
        with _trace.span("devtree.morton"):
            out = _logged("devtree.morton", _morton.sort_phase, self.xs,
                          space=self.space)
            self.xs_sorted, self.codes_s, self.order_s = out
            if self.shared:
                self.xt_sorted = self.xs_sorted
                self.codes_t, self.order_t = self.codes_s, self.order_s
            else:
                self.xt_sorted, self.codes_t, self.order_t = _logged(
                    "devtree.morton", _morton.sort_phase, self.xt,
                    space=self.space)
            if block:
                # lint: disable=OB001 — blocking is this path's contract:
                # run_sync's probe/growth loop asks for it explicitly
                # (block=True); the async dispatch path passes block=False.
                jax.block_until_ready((self.xs_sorted, self.xt_sorted))
        self.build_ms["morton"] = (time.perf_counter() - t0) * 1e3

    def run_build(self, caps):
        return _logged(
            "devtree.build", _build_phase, self.xs_sorted, self.codes_s,
            self.xt_sorted, self.codes_t, self.order_t,
            dims=_build_dims(caps), **self.static_kw)

    def run_lists(self, struct, widths, pcaps, caps):
        spans, _ = _level_spans(self.d_src, caps.sparse_rows)
        return _logged(
            "devtree.lists", _lists.lists_phase,
            struct["node_lo"], struct["node_hi"], struct["node_count"],
            struct["node_start"], struct["node_active"],
            struct["node_leaf"], struct["node_code"],
            struct["leaf"]["start"], struct["leaf"]["valid"],
            struct["batch"]["lo"], struct["batch"]["hi"],
            struct["batch"]["valid"],
            widths=widths, pair_caps=pcaps, depth=self.d_src,
            off=self.off, sparse=tuple(spans[self.sd + 1:]),
            theta=self.theta, skin=self.skin, degree=self.degree,
            space=self.space)

    def full_need(self, bneed, lneed, srows_layout):
        _, m_tot = _level_spans(self.d_src, tuple(srows_layout))
        return dict(
            bneed, num_nodes=m_tot, depth=self.d_src + 1, upward_rows=(),
            approx_width=lneed["approx_width"],
            direct_width=lneed["direct_width"],
            skin_direct_width=lneed["skin_direct_width"])

    def guess_pairs(self, nb_cap):
        return (tuple(_qcap(min(nb_cap * 8 ** l, 128 * nb_cap))
                      for l in range(self.d_src + 1)),
                _qcap(32 * nb_cap), _qcap(4 * nb_cap))

    def fit_pairs(self, pcaps, lneed):
        return (tuple(max(c, _qcap(self.headroom * f)) for c, f in
                      zip(pcaps[0], lneed["frontier_pairs"])),
                max(pcaps[1], _qcap(self.headroom * lneed["run_pairs"])),
                max(pcaps[2], _qcap(self.headroom * lneed["skin_pairs"])))

    def grow(self, caps, pair_caps, synced):
        grown = _clamp_nodes(
            caps.grown_to_fit_need(
                self.full_need(synced, synced, caps.sparse_rows)),
            self.d_src)
        grown_pairs = self.fit_pairs(pair_caps, synced)
        return grown, grown_pairs

    def record_growth(self, grown, grown_pairs):
        _events.record("capacity_growth", "devtree.prepare_plan_device",
                       owner="devtree", site="devtree.build",
                       key=repr((_build_dims(grown),) + grown_pairs))

    def validate(self, caps):
        if caps.depth != self.d_src + 1:
            raise ValueError(
                f"device capacities are bound to the octree depth: "
                f"budget has depth {caps.depth}, this build derives "
                f"{self.d_src + 1} (N={self.n_s})")
        if (len(caps.sparse_rows) != self.d_src - self.sd
                or len(caps.batch_sparse_rows) != self.d_tgt - self.tsd):
            raise ValueError(
                f"device capacities are bound to the hybrid split: "
                f"budget has {len(caps.sparse_rows)} source / "
                f"{len(caps.batch_sparse_rows)} target sparse levels, "
                f"this build derives {self.d_src - self.sd} / "
                f"{self.d_tgt - self.tsd} (split depth {SPLIT_DEPTH})")
        _, m_tot = _level_spans(self.d_src, caps.sparse_rows)
        if caps.num_nodes < m_tot + 1:
            raise ValueError(
                f"device capacities too small for the hybrid octree: "
                f"num_nodes budget {caps.num_nodes} < {m_tot} rows "
                f"+ scratch")

    # -- entry points --------------------------------------------------

    def probe(self):
        """First build: stage-0 occupancy -> structural needs -> probe
        build + count-only lists -> locked budget."""
        t1 = time.perf_counter()
        with _trace.span("devtree.needs"):
            rounder = functools.partial(_round_need, self.headroom,
                                        self.base)
            if self.d_src > self.sd or self.d_tgt > self.tsd:
                socc, tocc = _ints(_logged(
                    "devtree.needs", _occupancy_phase, self.codes_s,
                    self.codes_t, depth=self.d_src, tdepth=self.d_tgt,
                    bits=self.bits))
                srows0 = tuple(rounder(v) for v in socc)
                tsrows0 = tuple(rounder(v) for v in tocc)
            else:
                srows0, tsrows0 = (), ()
            bneed = _ints(_logged(
                "devtree.needs", _needs_phase, self.xs_sorted,
                self.codes_s, self.xt_sorted, self.codes_t,
                srows=srows0, tsrows=tsrows0, **self.static_kw))
            probe = _clamp_nodes(_eval.Capacities.for_need(
                self.full_need(bneed, dict(approx_width=1, direct_width=1,
                                           skin_direct_width=1), srows0),
                headroom=self.headroom, base=self.base), self.d_src)
            struct = self.run_build(probe)
            probe_pairs = self.guess_pairs(probe.num_batches)
            _, lneed, _, _ = self.run_lists(struct, (0, 0, 0),
                                            probe_pairs, probe)
            lneed = _ints(lneed)
            caps = _clamp_nodes(_eval.Capacities.for_need(
                self.full_need(bneed, lneed, probe.sparse_rows),
                headroom=self.headroom, base=self.base), self.d_src)
            pair_caps = self.fit_pairs(
                ((1,) * (self.d_src + 1), 1, 1), lneed)
        self.build_ms["needs"] = (time.perf_counter() - t1) * 1e3
        return caps, pair_caps

    def run_sync(self, capacities, pair_caps) -> "_eval.Plan":
        self.sort(block=True)
        caps = None if capacities == "auto" else capacities
        if caps is None:
            caps, pair_caps = self.probe()
        self.validate(caps)
        if pair_caps is None:
            pair_caps = self.guess_pairs(caps.num_batches)

        for _ in range(8):
            tb = time.perf_counter()
            with _trace.span("devtree.build"):
                struct = self.run_build(caps)
                # lint: disable=OB001 — growth-probe path (see above):
                # separates build from lists walltime in build_ms.
                jax.block_until_ready(struct["node_lo"])
            tl = time.perf_counter()
            self.build_ms["build"] = (self.build_ms.get("build", 0.0)
                                      + (tl - tb) * 1e3)
            with _trace.span("devtree.lists"):
                lists, lneed, t_slack, f_slack = self.run_lists(
                    struct, (caps.approx_width, caps.direct_width,
                             caps.skin_direct_width), pair_caps, caps)
                # lint: disable=OB001 — growth-probe path: the loop reads
                # the needs vector next anyway; the block attributes the
                # lists phase's walltime (build_ms) honestly. Steady-state
                # replans go through dispatch(), which never blocks.
                jax.block_until_ready(lists["approx_idx"])
            tn = time.perf_counter()
            self.build_ms["lists"] = (self.build_ms.get("lists", 0.0)
                                      + (tn - tl) * 1e3)

            # The ONLY per-rebuild device->host sync: the needs vector,
            # the two slack scalars, and the totals for the waste metric.
            synced = _ints(dict(struct["need"], **lneed))
            t_slack = float(jax.device_get(t_slack))
            f_slack = float(jax.device_get(f_slack))
            grown, grown_pairs = self.grow(caps, pair_caps, synced)
            if grown == caps and grown_pairs == pair_caps:
                break
            self.record_growth(grown, grown_pairs)
            caps, pair_caps = grown, grown_pairs
        else:
            raise RuntimeError("devtree capacity growth did not converge")
        return self.assemble(caps, pair_caps, struct, lists, synced,
                             t_slack, f_slack)

    def dispatch(self, caps, pair_caps) -> "PendingDevicePlan":
        t0 = time.perf_counter()
        with _trace.span("devtree.dispatch"):
            self.sort(block=False)
            self.validate(caps)
            struct = self.run_build(caps)
            lists, lneed, t_slack, f_slack = self.run_lists(
                struct, (caps.approx_width, caps.direct_width,
                         caps.skin_direct_width), pair_caps, caps)
        self.build_ms["dispatch"] = (time.perf_counter() - t0) * 1e3
        return PendingDevicePlan(self, caps, pair_caps, struct, lists,
                                 lneed, t_slack, f_slack)

    def assemble(self, caps, pair_caps, struct, lists, synced,
                 t_slack, f_slack) -> "_eval.Plan":
        tf = time.perf_counter()
        with _trace.span("devtree.finalize"):
            arrays = dict(
                src_sorted=self.xs_sorted,
                src_perm=self.order_s,
                tgt_batched=struct["tgt_batched"],
                gather_index=struct["gather_index"],
                leaf_gather=struct["leaf"]["gather"],
                node_lo=struct["node_lo"],
                node_hi=struct["node_hi"],
                approx_idx=lists["approx_idx"],
                direct_idx=lists["direct_idx"],
                approx_skin=lists["approx_skin"],
                skin_direct=lists["skin_direct"],
                skin_direct_node=lists["skin_direct_node"],
                tgt_mask=struct["tgt_mask"],
                bucket_gather=struct["bucket_gather"],
                bucket_nodes=struct["bucket_nodes"],
                parent_of=struct["parent_of"],
            )
            dev = dict(
                depth=self.d_src, tdepth=self.d_tgt,
                node_count=struct["node_count"],
                node_start=struct["node_start"],
                node_active=struct["node_active"],
                node_leaf=struct["node_leaf"],
                node_code=struct["node_code"],
                sparse_rows=caps.sparse_rows,
                sparse_occ=tuple(synced.get("sparse_rows", ())),
                batch_sparse_occ=tuple(
                    synced.get("batch_sparse_rows", ())),
                leaf_ids=struct["leaf"]["ids"],
                n_leaves=synced["num_leaves"],
                b_lo=struct["batch"]["lo"], b_hi=struct["batch"]["hi"],
                b_start=struct["batch"]["start"],
                b_count=struct["batch"]["count"],
                n_batches=synced["num_batches"],
                src_perm=self.order_s, tgt_perm=self.order_t,
                pair_caps=pair_caps,
            )
            used = synced["approx_total"] + synced["direct_total"]
            total = caps.num_batches * (caps.approx_width
                                        + caps.direct_width)
            plan = _eval.Plan(
                arrays=arrays, meta=(self.degree,),
                tree=_LazyStruct(functools.partial(
                    _materialize_tree, dev, arrays["node_lo"],
                    arrays["node_hi"])),
                batches=_LazyStruct(functools.partial(
                    _materialize_batches, dev)),
                padding_waste=1.0 - used / max(total, 1),
                num_targets=self.n_t, num_sources=self.n_s,
                mac_slack=_interaction.scaled_mac_slack(
                    self.theta, t_slack, f_slack),
                theta_slack=t_slack, fold_slack=f_slack, skin=self.skin,
                capacities=caps, scratch_node=caps.scratch_node,
                space=self.space, build_ms=self.build_ms,
                build_backend="device", dev=dev,
            )
        self.build_ms["finalize"] = (time.perf_counter() - tf) * 1e3
        return plan


def _round_need(headroom: float, base: int, v: int) -> int:
    """The `Capacities.for_need` h() rounding, exposed so the stage-0
    occupancy probe picks the SAME sparse row budgets `for_need` will
    derive (one compiled needs pass, no layout churn)."""
    return _eval._round_up(int(np.ceil(v * headroom)), base)


class PendingDevicePlan:
    """An in-flight shadow replan (see `dispatch_plan_device`).

    Holds device references to the enqueued build until `finalize()`,
    which performs the deferred needs sync — the only blocking point,
    reported as ``wait_ms`` — and assembles the `Plan`. If the budget
    overflowed mid-flight, finalize falls back to the synchronous
    growth loop (a `capacity_growth` event + blocking rebuild, exactly
    the sync path's contract); ``grew`` reports that so callers can
    count the deliberate retrace. The pending plan owns only its own
    freshly dispatched arrays — nothing aliases the live plan, so a
    growth here can never perturb it.
    """

    def __init__(self, build, caps, pair_caps, struct, lists, lneed,
                 t_slack, f_slack):
        self._b = build
        self._caps, self._pair_caps = caps, pair_caps
        self._struct, self._lists, self._lneed = struct, lists, lneed
        self._t_slack, self._f_slack = t_slack, f_slack
        self._done = False

    def finalize(self):
        """Block on the enqueued build; return (plan, wait_ms, grew)."""
        if self._done:
            raise RuntimeError("PendingDevicePlan already finalized")
        self._done = True
        b = self._b
        caps, pair_caps = self._caps, self._pair_caps
        struct, lists, lneed = self._struct, self._lists, self._lneed
        t0 = time.perf_counter()
        with _trace.span("devtree.wait"):
            synced = _ints(dict(struct["need"], **lneed))
            t_slack = float(jax.device_get(self._t_slack))
            f_slack = float(jax.device_get(self._f_slack))
        wait_ms = (time.perf_counter() - t0) * 1e3
        b.build_ms["wait"] = wait_ms
        grown, grown_pairs = b.grow(caps, pair_caps, synced)
        grew = grown != caps or grown_pairs != pair_caps
        if grew:
            # Mid-flight overflow: the dispatched arrays are truncated.
            # Re-run the growth loop synchronously at the grown budget
            # (the sync path's deliberate-retrace contract).
            b.record_growth(grown, grown_pairs)
            caps, pair_caps = grown, grown_pairs
            for _ in range(7):
                with _trace.span("devtree.build"):
                    struct = b.run_build(caps)
                with _trace.span("devtree.lists"):
                    lists, lneed, t_s, f_s = b.run_lists(
                        struct, (caps.approx_width, caps.direct_width,
                                 caps.skin_direct_width), pair_caps, caps)
                synced = _ints(dict(struct["need"], **lneed))
                t_slack = float(jax.device_get(t_s))
                f_slack = float(jax.device_get(f_s))
                grown, grown_pairs = b.grow(caps, pair_caps, synced)
                if grown == caps and grown_pairs == pair_caps:
                    break
                b.record_growth(grown, grown_pairs)
                caps, pair_caps = grown, grown_pairs
            else:
                raise RuntimeError(
                    "devtree capacity growth did not converge")
        plan = b.assemble(caps, pair_caps, struct, lists, synced,
                          t_slack, f_slack)
        return plan, wait_ms, grew
