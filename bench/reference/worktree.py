"""The work the paper's algorithm needs for one evaluation, counted on
a plain copy of its tree and interaction lists.

A copy, not a call: the host tree of the program (`core/tree.py`) and
its MAC traversal (`core/interaction.py`) may change in later PRs, and
the work a roofline share is measured against must not change with
them. Free space only.

The tree (Sec. 2.4): the root is the bounding box of the particles;
a node with more than `leaf_size` particles is bisected at the midpoint
of its box in every dimension whose extent is within a factor sqrt(2) of
the longest (8, 4 or 2 children), and each child box is shrunk to its
particles. Target batches are the same tree over the targets with
`batch_size`. A batch B and cluster C interact by approximation when

    (r_B + r_C) / R < theta   and   (n+1)^3 < N_C        (Eq. 13)

(r: half-diagonals, R: distance of the centers). Otherwise a leaf
interacts directly; an internal cluster that failed only on size is
taken directly leaf by leaf; any other internal cluster is descended.

Counted, with no padded or sentinel slot:
  - approximation evaluations: targets of B times (n+1)^3, for each
    accepted (B, C);
  - direct evaluations: targets of B times particles of the leaf, for
    each direct (B, leaf);
  - modified-charge terms: particles of C times (n+1)^3, for each
    cluster C that some batch approximates.
"""
from __future__ import annotations

import dataclasses

import numpy as np

_SPLIT_RATIO = 1.0 / np.sqrt(2.0)


@dataclasses.dataclass
class Tree:
    lo: np.ndarray        # (M, 3) shrunk boxes
    hi: np.ndarray
    count: np.ndarray     # (M,) particles per node
    start: np.ndarray     # (M,) first particle in tree order
    children: list        # per node: list of child ids
    leaf: np.ndarray      # (M,) bool

    @property
    def center(self):
        return 0.5 * (self.lo + self.hi)

    @property
    def radius(self):
        return 0.5 * np.linalg.norm(self.hi - self.lo, axis=1)

    def leaves_under(self, node: int) -> list:
        out, stack = [], [node]
        while stack:
            k = stack.pop()
            if self.leaf[k]:
                out.append(k)
            else:
                stack.extend(self.children[k])
        return out


def build_tree(points: np.ndarray, leaf_size: int) -> Tree:
    """Midpoint bisection with shrunk boxes (module docstring)."""
    pts_all = np.asarray(points, np.float64)
    perm = np.arange(len(pts_all))
    lo_l, hi_l, cnt, st, kids, leaf = [], [], [], [], [], []
    stack = [(0, len(pts_all), -1)]
    while stack:
        start, count, parent = stack.pop()
        idx = perm[start:start + count]
        pts = pts_all[idx]
        lo, hi = pts.min(0), pts.max(0)
        node = len(lo_l)
        lo_l.append(lo)
        hi_l.append(hi)
        cnt.append(count)
        st.append(start)
        kids.append([])
        if parent >= 0:
            kids[parent].append(node)
        ext = hi - lo
        if count <= leaf_size or ext.max() == 0.0:
            leaf.append(True)
            continue
        dims = np.nonzero(ext >= _SPLIT_RATIO * ext.max())[0]
        mid = 0.5 * (lo + hi)
        code = np.zeros(count, np.int64)
        for b, dim in enumerate(dims):
            code |= (pts[:, dim] > mid[dim]).astype(np.int64) << b
        order = np.argsort(code, kind="stable")
        perm[start:start + count] = idx[order]
        uniq, first = np.unique(code[order], return_index=True)
        if len(uniq) == 1:
            leaf.append(True)
            continue
        leaf.append(False)
        bounds = np.append(first, count)
        for b0, b1 in zip(bounds[:-1], bounds[1:]):
            stack.append((start + int(b0), int(b1 - b0), node))
    return Tree(lo=np.asarray(lo_l), hi=np.asarray(hi_l),
                count=np.asarray(cnt), start=np.asarray(st),
                children=kids, leaf=np.asarray(leaf))


@dataclasses.dataclass
class Work:
    """Kernel work of one evaluation (module docstring)."""

    approx_evals: int
    direct_evals: int
    modified_charge_terms: int
    approx_pairs: int         # accepted (batch, cluster) pairs
    direct_pairs: int         # (batch, leaf) pairs
    clusters: int             # clusters with modified charges
    approx_bytes: int
    direct_bytes: int
    target_bytes: int
    modified_charge_bytes: int

    def total_bytes(self) -> int:
        return (self.approx_bytes + self.direct_bytes + self.target_bytes
                + self.modified_charge_bytes)


# Bytes moved from and to device memory, at the least, in float32 words:
# for each approximated pair the cluster's interpolation points with their
# modified charges (4 words each); for each direct pair the leaf's
# particles (x, y, z, q); each target once (x, y, z, and its potential
# written back); for each cluster its particles read and its modified
# charges written.
_WORD = 4


def count_work(targets, sources, *, theta: float, degree: int,
               leaf_size: int, batch_size: int) -> Work:
    """Count the evaluations one potential evaluation needs."""
    tree = build_tree(sources, leaf_size)
    batches = build_tree(targets, batch_size)
    bl = np.nonzero(batches.leaf)[0]
    k3 = (degree + 1) ** 3
    c_ctr, c_rad = tree.center, tree.radius
    b_ctr, b_rad, b_cnt = batches.center[bl], batches.radius[bl], \
        batches.count[bl]

    approx = []          # (batch slot, node)
    direct = []          # (batch slot, leaf node)
    fb = np.arange(len(bl))
    fn = np.zeros(len(bl), np.int64)
    while fb.size:
        dist = np.linalg.norm(b_ctr[fb] - c_ctr[fn], axis=1)
        dist_ok = theta * dist - (b_rad[fb] + c_rad[fn]) > 0.0
        size_ok = k3 < tree.count[fn]
        mac = dist_ok & size_ok
        leaf = tree.leaf[fn]
        approx += zip(fb[mac], fn[mac])
        go_direct = ~mac & leaf
        direct += zip(fb[go_direct], fn[go_direct])
        small = ~mac & ~leaf & dist_ok & ~size_ok
        for b, node in zip(fb[small], fn[small]):
            direct += [(b, lf) for lf in tree.leaves_under(int(node))]
        down = ~mac & ~leaf & ~small
        nb, nn = [], []
        for b, node in zip(fb[down], fn[down]):
            for kid in tree.children[int(node)]:
                nb.append(b)
                nn.append(kid)
        fb = np.asarray(nb, np.int64)
        fn = np.asarray(nn, np.int64)

    a = np.asarray(approx, np.int64).reshape(-1, 2)
    d = np.asarray(direct, np.int64).reshape(-1, 2)
    clusters = np.unique(a[:, 1])
    a_tgt = b_cnt[a[:, 0]]
    d_tgt, d_src = b_cnt[d[:, 0]], tree.count[d[:, 1]]
    c_src = tree.count[clusters]
    return Work(
        approx_evals=int((a_tgt * k3).sum()),
        direct_evals=int((d_tgt * d_src).sum()),
        modified_charge_terms=int((c_src * k3).sum()),
        approx_pairs=len(a), direct_pairs=len(d), clusters=len(clusters),
        approx_bytes=_WORD * 4 * k3 * len(a),
        direct_bytes=_WORD * 4 * int(d_src.sum()),
        target_bytes=_WORD * 4 * int(b_cnt.sum()),
        modified_charge_bytes=_WORD * int((4 * c_src + k3).sum()),
    )


# Floating-point operations of one kernel evaluation (a target and a
# source or interpolation point): the displacement (3), r^2 (5), the
# kernel, the product with the charge and the sum (2). Coulomb's kernel
# is one rsqrt; Yukawa's adds r = r2 * rsqrt, -kappa*r, exp and a product.
PAIR_OPS = {"coulomb": 11, "yukawa": 15}


def modified_charge_ops(degree: int) -> float:
    """Operations per (particle, interpolation point) of the modified
    charges (Eq. 12): the product of three 1-D Lagrange factors with the
    charge, and the sum (4), plus the particle's share of its 1-D
    barycentric weights, 3 dimensions x (n+1) points x 3 operations,
    spread over the (n+1)^3 points."""
    k = degree + 1
    return 4.0 + 9.0 / k ** 2


def flops(work: Work, kernel: str, degree: int) -> float:
    pair = PAIR_OPS[kernel]
    return (pair * (work.approx_evals + work.direct_evals)
            + modified_charge_ops(degree) * work.modified_charge_terms)
