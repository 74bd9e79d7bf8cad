"""Unified solver API: plan protocol, config validation, kernel registry,
forces, dtype/donation policy, and single-device vs sharded parity."""
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest
import jax.numpy as jnp

from repro.core.api import (Plan, SingleDevicePlan, TreecodeConfig,
                            TreecodeSolver)
from repro.core.direct import direct_sum
from repro.core.potentials import (Kernel, register_kernel,
                                   registered_kernels, resolve_kernel)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _particles(seed, n, dtype=np.float64):
    r = np.random.default_rng(seed)
    return (r.uniform(-1, 1, (n, 3)).astype(dtype),
            r.uniform(-1, 1, n).astype(dtype))


# ---------------------------------------------------------------------------
# config validation
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("kwargs,match", [
    (dict(theta=0.0), "theta"),
    (dict(theta=1.5), "theta"),
    (dict(degree=0), "degree"),
    (dict(leaf_size=0), "leaf_size"),
    (dict(batch_size=-1), "batch_size"),
    (dict(backend="cuda"), "backend"),
    (dict(precompute="heirarchical"), "precompute"),
    (dict(approx_r2="mat_mul"), "approx_r2"),
    (dict(dtype="f16"), "dtype"),
    (dict(kernel=42), "kernel"),
])
def test_config_validation_rejects_early(kwargs, match):
    with pytest.raises(ValueError, match=match):
        TreecodeConfig(**kwargs)


def test_config_valid_values_accepted():
    TreecodeConfig(theta=1.0, degree=1, leaf_size=1, batch_size=0,
                   backend="xla", precompute="hierarchical",
                   approx_r2="matmul", dtype="float32")


def test_unknown_kernel_name_fails_at_solver_construction():
    with pytest.raises(KeyError, match="no_such_kernel"):
        TreecodeSolver(TreecodeConfig(kernel="no_such_kernel"))


# ---------------------------------------------------------------------------
# plan protocol
# ---------------------------------------------------------------------------


def test_plan_conforms_to_protocol():
    pts, q = _particles(0, 400, np.float32)
    solver = TreecodeSolver(TreecodeConfig(degree=4, leaf_size=64,
                                           backend="xla"))
    plan = solver.plan(pts)
    assert isinstance(plan, Plan)
    assert isinstance(plan, SingleDevicePlan)
    st = plan.stats()
    assert st["strategy"] == "single_device"
    assert st["num_targets"] == st["num_sources"] == 400
    assert 0.0 <= st["padding_waste"] < 1.0


def test_plan_reuse_across_charge_vectors():
    pts, q1 = _particles(1, 900, np.float32)
    _, q2 = _particles(2, 900, np.float32)
    solver = TreecodeSolver(TreecodeConfig(degree=5, leaf_size=96,
                                           backend="xla"))
    plan = solver.plan(pts)
    p1 = np.asarray(plan.execute(q1))
    p2 = np.asarray(plan.execute(q2))
    np.testing.assert_allclose(p1, np.asarray(solver(pts, pts, q1)),
                               rtol=1e-6)
    np.testing.assert_allclose(p2, np.asarray(solver(pts, pts, q2)),
                               rtol=1e-6)
    # solver.execute delegates to the plan (old call style keeps working)
    np.testing.assert_array_equal(np.asarray(solver.execute(plan, q1)), p1)


def test_replan_moves_particles():
    pts, q = _particles(3, 700, np.float32)
    solver = TreecodeSolver(TreecodeConfig(degree=4, leaf_size=64,
                                           backend="xla"))
    plan = solver.plan(pts)
    moved = pts + 0.05 * np.random.default_rng(4).standard_normal(
        pts.shape).astype(np.float32)
    plan2 = plan.replan(moved)
    phi2 = plan2.execute(q)
    phi_ds = direct_sum(jnp.asarray(moved), jnp.asarray(moved),
                        jnp.asarray(q), kernel=solver.kernel)
    err = float(jnp.linalg.norm(phi2 - phi_ds) / jnp.linalg.norm(phi_ds))
    assert err < 1e-3


def test_donating_execute_reusable_loop():
    pts, q = _particles(5, 600, np.float32)
    solver = TreecodeSolver(TreecodeConfig(degree=4, leaf_size=64,
                                           backend="xla",
                                           donate_charges=True))
    plan = solver.plan(pts)
    ref = np.asarray(plan.execute(np.asarray(q)))
    # iterative-solver style: feed the previous device output back in
    x = jnp.asarray(q)
    for _ in range(3):
        x = plan.execute(x)          # donates x's buffer each round
    assert np.isfinite(np.asarray(x)).all()
    np.testing.assert_allclose(np.asarray(plan.execute(np.asarray(q))), ref,
                               rtol=1e-6)


def test_dtype_policy_float32_casts_inputs():
    pts, q = _particles(6, 500)      # f64 inputs
    solver = TreecodeSolver(TreecodeConfig(degree=4, leaf_size=64,
                                           backend="xla", dtype="float32"))
    plan = solver.plan(pts)
    phi = plan.execute(q)
    assert phi.dtype == jnp.float32
    assert plan.stats()["dtype"] == "float32"


def test_dtype_float64_requires_x64_mode():
    import jax
    if jax.config.jax_enable_x64:
        pytest.skip("x64 globally enabled")
    pts, _ = _particles(7, 100, np.float32)
    solver = TreecodeSolver(TreecodeConfig(dtype="float64"))
    with pytest.raises(ValueError, match="x64"):
        solver.plan(pts)


@pytest.mark.parametrize("dtype,backend", [
    ("float64", "pallas"), ("auto", "pallas"), ("float64", "auto")])
def test_float64_refused_where_pallas_runs(x64, monkeypatch, dtype,
                                           backend):
    """Mosaic has no f64: a float64 plan whose backend resolves to the
    Pallas TPU kernels fails at plan time with a clear error instead of
    in the kernel compile (or on another backend). "auto" resolves to
    Pallas on a TPU, which the platform rule is steered to here."""
    from repro.kernels import ops

    monkeypatch.setattr(ops, "default_backend", lambda: "pallas")
    pts, _ = _particles(7, 100)      # f64 inputs
    solver = TreecodeSolver(TreecodeConfig(dtype=dtype, backend=backend))
    with pytest.raises(ValueError, match="float64 plans cannot run"):
        solver.plan(pts)
    # the same config on the XLA backend plans in f64
    plan = TreecodeSolver(TreecodeConfig(
        dtype=dtype, backend="xla", degree=3, leaf_size=32)).plan(pts)
    assert plan.stats()["dtype"] == "float64"


# ---------------------------------------------------------------------------
# forces
# ---------------------------------------------------------------------------


def test_forces_match_finite_differences(x64):
    pts, q = _particles(8, 500)
    solver = TreecodeSolver(TreecodeConfig(theta=0.7, degree=7, leaf_size=64,
                                           backend="xla"))
    plan = solver.plan(pts)
    phi, F = plan.potential_and_forces(q)
    np.testing.assert_allclose(np.asarray(phi), np.asarray(plan.execute(q)),
                               rtol=1e-12)
    h = 1e-6
    rng = np.random.default_rng(9)
    for i in rng.integers(0, len(pts), 5):
        for d in range(3):
            pp, pm = pts.copy(), pts.copy()
            pp[i, d] += h
            pm[i, d] -= h
            # move target i only; sources stay fixed (the force convention)
            fp = np.asarray(solver.plan(pp, pts).execute(q))[i]
            fm = np.asarray(solver.plan(pm, pts).execute(q))[i]
            fd_force = -q[i] * (fp - fm) / (2 * h)
            rel = abs(float(F[i, d]) - fd_force) / max(abs(fd_force), 1e-12)
            assert rel < 1e-3, (i, d, float(F[i, d]), fd_force)


def test_forces_antisymmetric_two_body(x64):
    """Two equal charges: F_0 == -F_1 along the separation axis."""
    pts = np.array([[-0.3, 0.0, 0.0], [0.4, 0.0, 0.0]])
    q = np.array([1.0, 1.0])
    solver = TreecodeSolver(TreecodeConfig(degree=2, leaf_size=4,
                                           backend="xla"))
    _, F = solver.plan(pts).potential_and_forces(q)
    F = np.asarray(F)
    np.testing.assert_allclose(F[0], -F[1], atol=1e-12)
    assert F[0, 0] < 0.0  # like charges repel


def test_forces_disjoint_targets_need_weights():
    tgt, _ = _particles(10, 200, np.float32)
    src, q = _particles(11, 300, np.float32)
    solver = TreecodeSolver(TreecodeConfig(degree=3, leaf_size=32,
                                           backend="xla"))
    plan = solver.plan(tgt, src)
    with pytest.raises(ValueError, match="weights"):
        plan.potential_and_forces(q)
    w = np.ones(200, np.float32)
    phi, F = plan.potential_and_forces(q, weights=w)
    assert F.shape == (200, 3)


# ---------------------------------------------------------------------------
# kernel registry
# ---------------------------------------------------------------------------


def test_custom_kernel_object_round_trip(x64):
    """A user-constructed Kernel drives the full pipeline and matches the
    direct sum computed with the same kernel."""

    def _gauss(r2, params):
        (alpha,) = params
        return jnp.exp(-alpha * r2)

    gauss = Kernel("gaussian_test", _gauss, (2.0,))
    pts, q = _particles(12, 1200)
    solver = TreecodeSolver(TreecodeConfig(theta=0.7, degree=6, leaf_size=64,
                                           kernel=gauss, backend="xla"))
    assert solver.kernel is gauss
    phi = solver(pts, pts, q)
    phi_ds = direct_sum(jnp.asarray(pts), jnp.asarray(pts), jnp.asarray(q),
                        kernel=gauss)
    err = float(jnp.linalg.norm(phi - phi_ds) / jnp.linalg.norm(phi_ds))
    assert err < 1e-6


def test_registered_kernel_usable_by_name(x64):
    def _inv_quad(r2, params):
        return 1.0 / (1.0 + r2)

    name = "inv_quad_test"
    if name not in registered_kernels():
        register_kernel(name, lambda: Kernel(name, _inv_quad))
    pts, q = _particles(13, 800)
    solver = TreecodeSolver(TreecodeConfig(degree=5, leaf_size=64,
                                           kernel=name, backend="xla"))
    phi = solver(pts, pts, q)
    phi_ds = direct_sum(jnp.asarray(pts), jnp.asarray(pts), jnp.asarray(q),
                        kernel=resolve_kernel(name))
    err = float(jnp.linalg.norm(phi - phi_ds) / jnp.linalg.norm(phi_ds))
    assert err < 1e-6


def test_register_kernel_duplicate_rejected():
    with pytest.raises(KeyError, match="already registered"):
        register_kernel("coulomb", lambda: None)


# ---------------------------------------------------------------------------
# single-device vs sharded parity (multi-device subprocess)
# ---------------------------------------------------------------------------


def _run_sub(code: str, devices: int = 4, timeout: int = 900):
    env = dict(os.environ,
               PYTHONPATH=os.path.join(ROOT, "src"),
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}")
    p = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, timeout=timeout,
                       env=env, cwd=ROOT)
    assert p.returncode == 0, p.stderr[-3000:]
    return p.stdout


def test_single_vs_sharded_parity_and_forces():
    """Same points/charges through both strategies: potentials agree to
    MAC tolerance and forces agree in the same norm."""
    _run_sub("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.core.api import TreecodeConfig, TreecodeSolver
        rng = np.random.default_rng(0)
        N = 1536
        pts = rng.uniform(-1, 1, (N, 3)).astype(np.float32)
        q = rng.uniform(-1, 1, N).astype(np.float32)
        solver = TreecodeSolver(TreecodeConfig(
            theta=0.7, degree=5, leaf_size=64, backend="xla"))
        sharded = solver.plan(pts)            # auto-detects 4 devices
        single = solver.plan(pts, nranks=1)
        assert sharded.stats()["strategy"] == "sharded"
        assert single.stats()["strategy"] == "single_device"
        phi_s = np.asarray(sharded.execute(q))
        phi_1 = np.asarray(single.execute(q))
        err = np.linalg.norm(phi_s - phi_1) / np.linalg.norm(phi_1)
        assert err < 5e-5, err
        # plan reuse on the sharded path
        q2 = rng.uniform(-1, 1, N).astype(np.float32)
        np.testing.assert_allclose(
            np.asarray(sharded.execute(q2)),
            np.asarray(single.execute(q2)),
            rtol=0, atol=2e-2)
        # forces parity (f32: compare in norm)
        _, F_s = sharded.potential_and_forces(q)
        _, F_1 = single.potential_and_forces(q)
        ferr = (np.linalg.norm(np.asarray(F_s) - np.asarray(F_1))
                / np.linalg.norm(np.asarray(F_1)))
        assert ferr < 5e-5, ferr
        print("parity ok", err, ferr)
    """)
