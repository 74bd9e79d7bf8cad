"""Differential fuzz: the device planner against the host planner.

Property-based draws over the full planner configuration space
(N, theta, leaf_size, degree, space, skin) pin the STRONG equivalence
property: the device interaction lists must decode to the SAME covered
(target, source) pair set as the host planner — every pair covered
exactly once on both backends, so the two coverage matrices are equal —
not merely produce forces that happen to agree. A second property
forces the hybrid sparse levels (adaptive depths 6-8, beyond the dense
SPLIT_DEPTH) and checks both coverage and float64-oracle force
equivalence there, in free and periodic space.

The examples are pinned with ``derandomize=True``, so the draws are
deterministic.
"""
import numpy as np
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core.api import SingleDevicePlan, TreecodeSolver, _resolve_dtype
from repro.core.space import FREE
from repro.devtree import build as devtree

from test_devtree import BOX, _cloud, _coverage, _oracle, _solver

# Coarse grids keep the number of distinct padded shapes — and hence
# jit compiles — bounded while still crossing every planner regime:
# single-leaf trees, MAC-heavy deep trees, skin demotion, both spaces.
_NS = (48, 320, 900)
_THETAS = (0.5, 0.8)
_LEAVES = (8, 32)
_DEGREES = (1, 3)
_SKINS = (0.0, 0.05)


def _forced_depth_plan(x, *, depth, space, skin, theta=0.7, degree=3,
                       leaf_size=8):
    """Device plan pinned at ``depth`` (past SPLIT_DEPTH: hybrid sparse
    levels engage even where `depth_for` would stop shallower)."""
    solver = _solver("device", theta=theta, degree=degree,
                     leaf_size=leaf_size, space=space, skin=skin)
    cfg, kern = solver.config, solver.kernel
    dtype = _resolve_dtype(cfg, x)
    inner = devtree.prepare_plan_device(
        x, x, theta=cfg.theta, degree=cfg.degree, leaf_size=cfg.leaf_size,
        batch_size=cfg.resolved_batch_size(), space=space, skin=skin,
        dtype=dtype, depth=depth, batch_depth=depth)
    return SingleDevicePlan(cfg, kern, inner, dtype)


@settings(max_examples=8, deadline=None, derandomize=True)
@given(n=st.sampled_from(_NS),
       theta=st.sampled_from(_THETAS),
       leaf_size=st.sampled_from(_LEAVES),
       degree=st.sampled_from(_DEGREES),
       periodic=st.booleans(),
       skin=st.sampled_from(_SKINS))
def test_fuzz_device_coverage_equals_host(n, theta, leaf_size, degree,
                                          periodic, skin):
    space = BOX if periodic else FREE
    rng = np.random.default_rng(
        abs(hash((n, theta, leaf_size, degree, periodic, skin))) % 2**32)
    x = _cloud(n, rng, space)
    ph = _solver("host", theta=theta, degree=degree, leaf_size=leaf_size,
                 space=space, skin=skin).plan(x)
    pd = _solver("device", theta=theta, degree=degree, leaf_size=leaf_size,
                 space=space, skin=skin).plan(x)
    Mh = _coverage(ph.inner)
    Md = _coverage(pd.inner)
    # Exactly-once coverage on both backends, hence equal pair sets:
    # every host MAC-accepted pair is covered by the device lists.
    assert (Mh == 1).all()
    assert (Md == 1).all()
    assert (Md == Mh).all()


@settings(max_examples=4, deadline=None, derandomize=True)
@given(depth=st.sampled_from((6, 7, 8)),
       periodic=st.booleans(),
       skin=st.sampled_from(_SKINS))
def test_fuzz_adaptive_depth_matches_f64_oracle(depth, periodic, skin):
    space = BOX if periodic else FREE
    rng = np.random.default_rng(abs(hash((depth, periodic, skin))) % 2**32)
    n = 700
    x = _cloud(n, rng, space)
    q = rng.uniform(0.5, 1.5, n).astype(np.float32)

    pd = _forced_depth_plan(x, depth=depth, space=space, skin=skin)
    dev = pd.inner.dev
    # The forced depth genuinely engaged the sparse levels...
    assert dev["depth"] == depth
    assert len(dev["sparse_occ"]) == depth - devtree.SPLIT_DEPTH
    assert all(r >= 1 for r in dev["sparse_occ"])
    # ...and coverage stays exactly-once through them.
    assert (_coverage(pd.inner) == 1).all()

    ref = _oracle(x, q, space)
    scale = np.abs(ref).max()
    ph = _solver("host", theta=0.7, degree=3, leaf_size=8, space=space,
                 skin=skin).plan(x)
    host_err = np.abs(np.asarray(ph.execute(q)) - ref).max() / scale
    dev_err = np.abs(np.asarray(pd.execute(q)) - ref).max() / scale
    # Same approximation order, so same error scale; the floor absorbs
    # f32 noise when both are tiny.
    assert dev_err <= max(2.0 * host_err, 1e-5), (host_err, dev_err)
