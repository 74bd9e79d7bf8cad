"""End-to-end treecode behaviour vs direct summation (the paper's Eq. 16)."""
import dataclasses

import numpy as np
import pytest
import jax.numpy as jnp
from hypothesis import given, settings, strategies as st

from repro.core.api import TreecodeConfig, TreecodeSolver
from repro.core.direct import direct_sum, direct_sum_kernel
from repro.core.potentials import coulomb, yukawa


def _particles(seed, n, dtype=np.float64):
    r = np.random.default_rng(seed)
    return (r.uniform(-1, 1, (n, 3)).astype(dtype),
            r.uniform(-1, 1, n).astype(dtype))


def _rel2(a, b):
    a, b = np.asarray(a), np.asarray(b)
    return np.linalg.norm(a - b) / np.linalg.norm(a)


@pytest.mark.parametrize("kernel", ["coulomb", "yukawa"])
def test_error_decreases_with_degree(x64, kernel):
    pts, q = _particles(0, 2500)
    kern = yukawa(0.5) if kernel == "yukawa" else coulomb()
    phi_ds = direct_sum(jnp.asarray(pts), jnp.asarray(pts), jnp.asarray(q),
                        kernel=kern)
    errs = []
    for deg in (1, 3, 5, 7):
        solver = TreecodeSolver(TreecodeConfig(
            theta=0.7, degree=deg, leaf_size=200, kernel=kernel,
            backend="xla"))
        errs.append(_rel2(phi_ds, solver(pts, pts, q)))
    assert errs[0] > errs[-1]
    assert all(e2 <= e1 * 1.5 for e1, e2 in zip(errs, errs[1:]))
    assert errs[-1] < 1e-5  # 5+ digits at degree 7


def test_theta_controls_accuracy(x64):
    pts, q = _particles(1, 2000)
    phi_ds = direct_sum(jnp.asarray(pts), jnp.asarray(pts), jnp.asarray(q),
                        kernel=coulomb())
    errs = {}
    for theta in (0.5, 0.9):
        solver = TreecodeSolver(TreecodeConfig(
            theta=theta, degree=3, leaf_size=128, backend="xla"))
        errs[theta] = _rel2(phi_ds, solver(pts, pts, q))
    assert errs[0.5] < errs[0.9]


def test_plan_reuse_new_charges(x64):
    pts, q1 = _particles(2, 1500)
    _, q2 = _particles(3, 1500)
    solver = TreecodeSolver(TreecodeConfig(degree=5, leaf_size=128,
                                           backend="xla"))
    plan = solver.plan(pts, pts)
    p1 = solver.execute(plan, q1)
    p2 = solver.execute(plan, q2)
    np.testing.assert_allclose(np.asarray(p1), np.asarray(solver(pts, pts, q1)))
    np.testing.assert_allclose(np.asarray(p2), np.asarray(solver(pts, pts, q2)))


def test_hierarchical_equals_direct_precompute(x64):
    pts, q = _particles(4, 2000)
    base = TreecodeConfig(degree=6, leaf_size=100, backend="xla")
    s_dir = TreecodeSolver(base)
    s_hier = TreecodeSolver(dataclasses.replace(base, precompute="hierarchical"))
    p1, p2 = s_dir(pts, pts, q), s_hier(pts, pts, q)
    np.testing.assert_allclose(np.asarray(p1), np.asarray(p2), rtol=1e-11)


def test_permutation_invariance(x64):
    pts, q = _particles(5, 1200)
    solver = TreecodeSolver(TreecodeConfig(degree=5, leaf_size=96,
                                           backend="xla"))
    phi = np.asarray(solver(pts, pts, q))
    perm = np.random.default_rng(0).permutation(len(pts))
    phi_p = np.asarray(solver(pts[perm], pts[perm], q[perm]))
    np.testing.assert_allclose(phi_p, phi[perm], rtol=1e-10)


def test_disjoint_targets_sources(x64):
    tgt, _ = _particles(6, 700)
    src, q = _particles(7, 900)
    tgt = tgt + 0.1  # generic offset, boxes overlap partially
    solver = TreecodeSolver(TreecodeConfig(degree=7, leaf_size=80,
                                           backend="xla"))
    phi = solver(tgt, src, q)
    phi_ds = direct_sum(jnp.asarray(tgt), jnp.asarray(src), jnp.asarray(q),
                        kernel=coulomb())
    assert _rel2(phi_ds, phi) < 1e-6


@settings(max_examples=8, deadline=None)
@given(seed=st.integers(0, 2**31 - 1))
def test_linearity_in_charges(seed):
    """phi is linear in q (treecode is a fixed linear operator per plan)."""
    pts, q1 = _particles(seed, 600, np.float32)
    _, q2 = _particles(seed + 1, 600, np.float32)
    solver = TreecodeSolver(TreecodeConfig(degree=4, leaf_size=64,
                                           backend="xla"))
    plan = solver.plan(pts, pts)
    lhs = np.asarray(solver.execute(plan, q1 + 2.0 * q2))
    rhs = np.asarray(solver.execute(plan, q1)) + 2.0 * np.asarray(
        solver.execute(plan, q2))
    np.testing.assert_allclose(lhs, rhs, rtol=5e-4, atol=5e-4)


def test_direct_sum_kernel_single_launch(x64):
    """Paper Sec. 4: GPU direct sum == one batch-cluster kernel launch."""
    pts, q = _particles(8, 500)
    a = direct_sum(jnp.asarray(pts), jnp.asarray(pts), jnp.asarray(q),
                   kernel=coulomb())
    b = direct_sum_kernel(jnp.asarray(pts), jnp.asarray(pts), jnp.asarray(q),
                          kernel=coulomb(), backend="xla")
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-12)


def test_padding_waste_reported():
    pts, q = _particles(9, 1000, np.float32)
    solver = TreecodeSolver(TreecodeConfig(degree=4, leaf_size=64,
                                           backend="xla"))
    plan = solver.plan(pts, pts)
    assert 0.0 <= plan.padding_waste < 0.9


@pytest.mark.parametrize("kernel,space", [
    (coulomb(), None), (yukawa(0.8), (2.0, 2.0, 2.0))])
def test_direct_oracle_sampled_rows_match_all_pairs(kernel, space):
    """The sampled f64 oracle (a subset of targets against every source)
    equals the all-pairs oracle on those rows, in the order given."""
    from repro.core.direct import direct_oracle_f64
    from repro.core.space import FREE, PeriodicBox

    rng = np.random.default_rng(11)
    x = rng.uniform(0, 2, (700, 3))
    q = rng.uniform(-1, 1, 700)
    sp = FREE if space is None else PeriodicBox(space)
    phi, f = direct_oracle_f64(x, q, kernel=kernel, space=sp, chunk=128)
    rows = rng.choice(700, 57, replace=False)
    phi_s, f_s = direct_oracle_f64(x, q, kernel=kernel, space=sp,
                                   chunk=96, targets=rows)
    np.testing.assert_allclose(phi_s, phi[rows], rtol=1e-12)
    np.testing.assert_allclose(f_s, f[rows], rtol=1e-10, atol=1e-12)
