"""Per-kernel allclose vs the pure-jnp oracles, swept over shapes/dtypes.

Every Pallas kernel runs in interpret mode (the kernel body executed on
CPU) and is compared against the independent unfactored ref.py oracle, and
the xla backend (the production CPU path) is held to the same oracle.
"""
import numpy as np
import pytest
import jax.numpy as jnp

from repro.core.potentials import coulomb, yukawa
from repro.kernels import ops, ref

KERNELS = [coulomb(), yukawa(0.5)]


def _case(rng, B, S, NB, C, m, dtype):
    tgt = rng.uniform(-1, 1, (B, NB, 3)).astype(dtype)
    src = rng.uniform(-1, 1, (C, m, 3)).astype(dtype)
    q = rng.uniform(-1, 1, (C, m)).astype(dtype)
    idx = rng.integers(-1, C, (B, S)).astype(np.int32)
    return jnp.asarray(idx), jnp.asarray(tgt), jnp.asarray(src), jnp.asarray(q)


@pytest.mark.parametrize("backend", ["pallas_interpret", "xla"])
@pytest.mark.parametrize("B,S,NB,C,m", [
    (1, 1, 8, 1, 8),
    (3, 5, 16, 7, 32),
    (2, 4, 40, 3, 24),     # NB not a multiple of the tile
    (4, 2, 128, 2, 200),
])
def test_batch_cluster_eval_matches_ref(rng, backend, B, S, NB, C, m):
    idx, tgt, src, q = _case(rng, B, S, NB, C, m, np.float32)
    for kern in KERNELS:
        want = ref.ref_batch_cluster_eval(idx, tgt, src, q, kern)
        got = ops.batch_cluster_eval(
            idx, tgt, src, q, kernel=kern, backend=backend, target_tile=32)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-4)


def test_batch_cluster_eval_f64(rng, x64):
    idx, tgt, src, q = _case(rng, 2, 3, 16, 4, 16, np.float64)
    for backend in ("pallas_interpret", "xla"):
        for kern in KERNELS:
            want = ref.ref_batch_cluster_eval(idx, tgt, src, q, kern)
            got = ops.batch_cluster_eval(
                idx, tgt, src, q, kernel=kern, backend=backend, target_tile=16)
            np.testing.assert_allclose(
                np.asarray(got), np.asarray(want), rtol=1e-12)


def test_batch_cluster_eval_kahan(rng):
    idx, tgt, src, q = _case(rng, 2, 8, 16, 8, 64, np.float32)
    kern = coulomb()
    want = ref.ref_batch_cluster_eval(idx, tgt, src, q, kern)
    got = ops.batch_cluster_eval(
        idx, tgt, src, q, kernel=kern, backend="pallas_interpret",
        target_tile=16, kahan=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("kahan", [False, True])
def test_batch_cluster_split_list_matches_ref(rng, monkeypatch, kahan):
    """Lists longer than one call's SMEM budget run as slot chunks and
    batch-row chunks; a tiny budget here forces both splits, with
    padding on each axis."""
    from repro.kernels import batch_cluster

    monkeypatch.setattr(batch_cluster, "LIST_SMEM_BYTES", 8 * 4 * 4)
    idx, tgt, src, q = _case(rng, 19, 10, 16, 6, 24, np.float32)
    kern = yukawa(0.5)
    want = ref.ref_batch_cluster_eval(idx, tgt, src, q, kern)
    got = ops.batch_cluster_eval(idx, tgt, src, q, kernel=kern,
                                 backend="pallas_interpret", target_tile=16,
                                 kahan=kahan)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


def test_batch_cluster_all_empty_slots(rng):
    idx = jnp.full((2, 3), -1, jnp.int32)
    _, tgt, src, q = _case(rng, 2, 3, 8, 2, 8, np.float32)
    for backend in ("pallas_interpret", "xla"):
        got = ops.batch_cluster_eval(
            idx, tgt, src, q, kernel=coulomb(), backend=backend, target_tile=8)
        np.testing.assert_array_equal(np.asarray(got), 0.0)


def _compacted(idx):
    """Each row's valid slots moved to the front, in order, -1 after."""
    out = np.full_like(idx, -1)
    for b, row in enumerate(idx):
        keep = row[row >= 0]
        out[b, :keep.size] = keep
    return out


@pytest.mark.parametrize("pattern", [
    "leading", "interior", "trailing", "empty_row", "row_chunks"])
@pytest.mark.parametrize("kahan", [False, True])
def test_batch_cluster_sentinel_slots_skip(rng, monkeypatch, pattern,
                                           kahan):
    """A sentinel slot adds nothing and asks for the block a valid slot
    of its row already holds: the plain sum is the compacted list's to
    the bit, the Kahan sum close to it, an all-sentinel row is zero."""
    from repro.kernels import batch_cluster

    B, S, C = 5, 7, 6
    if pattern == "row_chunks":
        # 4 slots a call and 8 rows a call: 19 rows pad to 24, 7 slots to
        # 8, so whole padding rows and padding slots run.
        monkeypatch.setattr(batch_cluster, "LIST_SMEM_BYTES", 8 * 4 * 4)
        B = 19
    idx, tgt, src, q = _case(rng, B, S, 16, C, 24, np.float32)
    ids = rng.integers(0, C, (B, S))
    valid = {
        "leading": np.arange(S) >= 3,
        "interior": np.isin(np.arange(S), [1, 2, 4]),
        "trailing": np.arange(S) < 4,
        "empty_row": np.ones(S, bool),
        "row_chunks": np.isin(np.arange(S), [0, 2, 3, 5]),
    }[pattern]
    lst = np.where(valid[None, :], ids, -1).astype(np.int32)
    if pattern in ("empty_row", "row_chunks"):
        lst[1] = -1

    res = np.asarray(batch_cluster.resident_sentinels(jnp.asarray(lst)))
    assert (res[lst >= 0] == lst[lst >= 0]).all()
    assert (res[lst < 0] < 0).all()
    blocks = np.array([[int(batch_cluster.cluster_block(b, 0, s, res, None)[0])
                        for s in range(S)] for b in range(B)])
    for row, blk in zip(lst, blocks):
        # the row's blocks change only where a valid id changes them
        seq = row[row >= 0] if (row >= 0).any() else np.zeros(1, int)
        assert blk[0] == seq[0]
        changes = blk[1:][blk[1:] != blk[:-1]]
        np.testing.assert_array_equal(
            changes, seq[1:][seq[1:] != seq[:-1]])

    kern = coulomb()

    def run(lst):
        return np.asarray(ops.batch_cluster_eval(
            jnp.asarray(lst), tgt, src, q, kernel=kern,
            backend="pallas_interpret", target_tile=8, kahan=kahan))

    got, want = run(lst), run(_compacted(lst))
    if kahan:
        np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)
    else:
        np.testing.assert_array_equal(got, want)
    np.testing.assert_allclose(
        got, np.asarray(ref.ref_batch_cluster_eval(
            jnp.asarray(lst), tgt, src, q, kern)), rtol=2e-4, atol=2e-4)
    np.testing.assert_array_equal(got[~(lst >= 0).any(1)], 0.0)


def test_self_interaction_masked(rng):
    # A target coincident with a source must not produce inf/nan.
    tgt = jnp.asarray([[[0.0, 0.0, 0.0], [1.0, 0.0, 0.0]]])
    src = jnp.asarray([[[0.0, 0.0, 0.0], [0.0, 1.0, 0.0]]])
    q = jnp.ones((1, 2), jnp.float32)
    idx = jnp.zeros((1, 1), jnp.int32)
    for backend in ("pallas_interpret", "xla"):
        got = np.asarray(ops.batch_cluster_eval(
            idx, tgt, src, q, kernel=coulomb(), backend=backend, target_tile=8))
        assert np.isfinite(got).all()
        # target 0: only the off-origin source contributes (r = 1)
        np.testing.assert_allclose(got[0, 0], 1.0, rtol=1e-6)


@pytest.mark.parametrize("backend", ["pallas_interpret", "xla"])
@pytest.mark.parametrize("C,m,degree", [
    (1, 8, 1), (3, 32, 2), (5, 64, 4), (2, 100, 3),  # m not tile-multiple
])
def test_modified_charges_matches_ref(rng, backend, C, m, degree):
    pts = rng.uniform(0, 1, (C, m, 3)).astype(np.float32)
    q = rng.uniform(-1, 1, (C, m)).astype(np.float32)
    lo = pts.min(1) - 0.0
    hi = pts.max(1) + 0.0
    want = ref.ref_modified_charges(
        jnp.asarray(pts), jnp.asarray(q), jnp.asarray(lo), jnp.asarray(hi), degree)
    got = ops.modified_charges(
        jnp.asarray(pts), jnp.asarray(q), jnp.asarray(lo), jnp.asarray(hi),
        degree=degree, backend=backend, particle_tile=32)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=3e-3, atol=3e-4)


def test_modified_charges_exact_hits(rng, x64):
    """Sources ON the Chebyshev nodes (guaranteed by min bounding boxes) —
    the removable-singularity path of Sec. 2.3."""
    from repro.core import cheby
    degree = 4
    lo = np.zeros(3)
    hi = np.ones(3)
    grid = np.asarray(cheby.cluster_grid(jnp.asarray(lo), jnp.asarray(hi), degree))
    extra = rng.uniform(0, 1, (7, 3))
    pts = np.concatenate([grid, extra])[None].astype(np.float64)
    q = rng.uniform(-1, 1, (1, pts.shape[1])).astype(np.float64)
    want = ref.ref_modified_charges(
        jnp.asarray(pts), jnp.asarray(q), jnp.asarray(lo[None]), jnp.asarray(hi[None]), degree)
    for backend in ("pallas_interpret", "xla"):
        got = ops.modified_charges(
            jnp.asarray(pts), jnp.asarray(q), jnp.asarray(lo[None]),
            jnp.asarray(hi[None]), degree=degree, backend=backend, particle_tile=64)
        assert np.isfinite(np.asarray(got)).all()
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-10)


def test_modified_charges_reproduce_far_field(rng, x64):
    """End-to-end Eq. 11 check: sum_k G(x, s_k) qhat_k ~= sum_j G(x, y_j) q_j
    for a well-separated target (f64, high degree -> near machine epsilon)."""
    from repro.core import cheby
    degree = 12
    pts = rng.uniform(0, 1, (1, 64, 3))
    q = rng.uniform(-1, 1, (1, 64))
    lo, hi = pts.min(1), pts.max(1)
    qhat = ops.modified_charges(
        jnp.asarray(pts), jnp.asarray(q), jnp.asarray(lo), jnp.asarray(hi),
        degree=degree, backend="xla")
    x = jnp.asarray([[5.0, 4.0, 3.0]])
    kern = coulomb()
    exact = float((kern.pairwise(x, jnp.asarray(pts[0])) @ jnp.asarray(q[0]))[0])
    approx = float(ref.ref_cluster_approx_potential(
        x, jnp.asarray(lo[0]), jnp.asarray(hi[0]), qhat[0], degree, kern)[0])
    assert abs(approx - exact) / abs(exact) < 1e-12


# ---------------------------------------------------------------------------
# Long-interaction-list accuracy: Kahan and MXU (matmul-r2) Pallas paths
# vs f64 direct summation (the dynamics hot path: hundreds of list slots
# accumulated into one f32 target tile per step).
# ---------------------------------------------------------------------------


def _long_list_case(rng, slots=96, nb=8, m=8):
    """One batch against `slots` clusters: accumulation-depth stress."""
    tgt = rng.uniform(-1, 1, (1, nb, 3)).astype(np.float32)
    src = rng.uniform(-1, 1, (slots, m, 3)).astype(np.float32)
    q = rng.uniform(-1, 1, (slots, m)).astype(np.float32)
    idx = np.arange(slots, dtype=np.int32)[None, :]
    return jnp.asarray(idx), jnp.asarray(tgt), jnp.asarray(src), jnp.asarray(q)


def _f64_reference(idx, tgt, src, q, kern):
    return np.asarray(ref.ref_batch_cluster_eval(
        jnp.asarray(np.asarray(idx)),
        jnp.asarray(np.asarray(tgt, np.float64)),
        jnp.asarray(np.asarray(src, np.float64)),
        jnp.asarray(np.asarray(q, np.float64)), kern))


def test_kahan_long_list_beats_plain_f32(rng, x64):
    """Compensated accumulation across ~100 list slots (interpret mode)
    must not lose to plain f32 and must stay near the f32 roundoff floor
    of a single contribution."""
    idx, tgt, src, q = _long_list_case(rng)
    for kern in KERNELS:
        want = _f64_reference(idx, tgt, src, q, kern)
        scale = np.abs(want).max()
        errs = {}
        for kahan in (False, True):
            got = np.asarray(ops.batch_cluster_eval(
                idx, tgt, src, q, kernel=kern, backend="pallas_interpret",
                target_tile=8, kahan=kahan))
            errs[kahan] = np.abs(got - want).max() / scale
        assert errs[True] <= errs[False] * 1.05
        assert errs[True] < 5e-6


def test_matmul_r2_long_list_accuracy(rng, x64):
    """The MXU r^2 form on MAC-separated geometry: same accuracy class
    as the cancellation-free difference form, against the f64 oracle."""
    idx, tgt, src, q = _long_list_case(rng)
    # Separate sources from targets (the approximation-kernel setting —
    # the MAC guarantees separation, so |x|^2+|y|^2-2x.y cannot cancel).
    src = src + jnp.asarray([4.0, 0.0, 0.0], src.dtype)
    kern = coulomb()
    want = _f64_reference(idx, tgt, src, q, kern)
    scale = np.abs(want).max()
    for backend in ("pallas_interpret", "xla"):
        errs = {}
        for mode in ("diff", "matmul"):
            got = np.asarray(ops.batch_cluster_eval(
                idx, tgt, src, q, kernel=kern, backend=backend,
                target_tile=8, r2_mode=mode))
            errs[mode] = np.abs(got - want).max() / scale
        assert errs["matmul"] < 1e-4, errs
        assert errs["matmul"] <= 20.0 * errs["diff"] + 1e-6, errs


def test_kahan_matmul_compose(rng, x64):
    """Both beyond-paper knobs together (the fast+accurate approx-kernel
    configuration) stay within tolerance of the f64 oracle."""
    idx, tgt, src, q = _long_list_case(rng, slots=64)
    src = src + jnp.asarray([0.0, 4.0, 0.0], src.dtype)
    kern = yukawa(0.5)
    want = _f64_reference(idx, tgt, src, q, kern)
    got = np.asarray(ops.batch_cluster_eval(
        idx, tgt, src, q, kernel=kern, backend="pallas_interpret",
        target_tile=8, kahan=True, r2_mode="matmul"))
    assert np.abs(got - want).max() / np.abs(want).max() < 1e-5


def _hand_launched(idx, lanes, width, max_slots, rows_of):
    """Grid cells the Pallas path launches, enumerated call by call the
    way `batch_cluster_eval_pallas` splits a list."""
    bsz, slots = idx.shape
    if slots > max_slots:
        k = -(-slots // max_slots)
        chunks = [max_slots] * k
    else:
        chunks = [slots]
    total = 0
    for cs in chunks:
        rows = rows_of(cs)
        calls = [bsz] if bsz <= rows else [rows] * (-(-bsz // rows))
        total += sum(r * lanes * cs * width for r in calls)
    return total


def _hand_computed(idx, lanes, width, max_slots, rows_of):
    """(computed, skipped) grid cells: the list cut into the calls
    `batch_cluster_eval_pallas` makes, sentinel-padded on both axes, and
    each call's slots counted one by one."""
    bsz, slots = idx.shape
    cs = max_slots if slots > max_slots else slots
    k = -(-slots // cs)
    rows = rows_of(cs)
    nrows = bsz if bsz <= rows else rows * -(-bsz // rows)
    full = np.full((nrows, k * cs), -1)
    full[:bsz, :slots] = idx
    computed = skipped = 0
    for c in range(k):
        for r0 in range(0, nrows, min(rows, nrows)):
            for slot in full[r0:r0 + rows, c * cs:(c + 1) * cs].ravel():
                if slot >= 0:
                    computed += lanes * width
                else:
                    skipped += lanes * width
    return computed, skipped


def test_kernel_work_counts_a_split_plan_by_hand(monkeypatch):
    """`plan.stats()["kernel_work"]` against a count made slot by slot
    and call by call, on a plan whose lists split on both axes: the
    computed and skipped grid cells together are every cell launched."""
    from repro.core.api import TreecodeConfig, TreecodeSolver
    from repro.kernels import batch_cluster

    budget = 8 * 4 * 4
    monkeypatch.setattr(batch_cluster, "LIST_SMEM_BYTES", budget)
    x = np.random.default_rng(3).uniform(-1, 1, (3000, 3))
    degree = 2
    plan = TreecodeSolver(TreecodeConfig(
        theta=0.7, degree=degree, leaf_size=64, batch_size=64,
        backend="xla")).plan(x, nranks=1)
    work = plan.stats()["kernel_work"]

    a = {k: np.asarray(v) for k, v in plan.arrays.items()
         if not isinstance(v, tuple)}
    k3 = (degree + 1) ** 3
    tgt = a["tgt_mask"].sum(1)
    leaf_n = (a["leaf_gather"] >= 0).sum(1)
    lanes = 256 * -(-a["tgt_mask"].shape[1] // 256)
    max_slots = budget // 32

    def rows_of(slots):
        return max(8, budget // (4 * slots) // 8 * 8)

    for name, idx, width, src in (
            ("approx", a["approx_idx"], k3, lambda c: k3),
            ("direct", a["direct_idx"], a["leaf_gather"].shape[1],
             lambda c: leaf_n[c])):
        assert idx.shape[1] > max_slots and idx.shape[0] > rows_of(max_slots)
        useful = sum(int(tgt[b]) * int(src(c))
                     for b in range(idx.shape[0]) for c in idx[b] if c >= 0)
        computed, skipped = _hand_computed(idx, lanes, width, max_slots,
                                           rows_of)
        assert skipped > 0
        assert computed + skipped == _hand_launched(idx, lanes, width,
                                                    max_slots, rows_of)
        assert work[name] == dict(launched=computed, skipped=skipped,
                                  useful=useful)
