"""Distributed BLTC (RCB + LET via shard_map) and elastic checkpointing.

Multi-device cases run in subprocesses with
XLA_FLAGS=--xla_force_host_platform_device_count=N so that the main pytest
process keeps its single default device."""
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro.distributed.rcb import rcb_partition

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_sub(code: str, devices: int = 4, timeout: int = 900):
    env = dict(os.environ,
               PYTHONPATH=os.path.join(ROOT, "src"),
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}")
    p = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, timeout=timeout,
                       env=env, cwd=ROOT)
    assert p.returncode == 0, p.stderr[-3000:]
    return p.stdout


def test_rcb_balance_and_disjoint():
    rng = np.random.default_rng(0)
    pts = rng.uniform(-1, 1, (1024, 3))
    r = rcb_partition(pts, 8)
    assert (r.counts() == 128).all()
    # perm is a permutation; every particle exactly one rank
    assert sorted(r.perm.tolist()) == list(range(1024))
    assert ((r.rank_of >= 0) & (r.rank_of < 8)).all()
    # slabs contain their particles
    for rank in range(8):
        idx = r.perm[r.starts[rank]:r.starts[rank + 1]]
        sub = pts[idx]
        assert (sub >= r.lo[rank] - 1e-12).all()
        assert (sub <= r.hi[rank] + 1e-12).all()


def test_rcb_uneven_rank_count():
    rng = np.random.default_rng(1)
    pts = rng.uniform(-1, 1, (600, 3))
    r = rcb_partition(pts, 6)
    assert (r.counts() == 100).all()


@pytest.mark.parametrize("n,p", [(1000, 4), (2047, 2), (101, 7)])
def test_rcb_arbitrary_n(n, p):
    """N % P != 0 splits near-balanced (counts within 1 of N/P)."""
    rng = np.random.default_rng(2)
    pts = rng.uniform(-1, 1, (n, 3))
    r = rcb_partition(pts, p)
    counts = r.counts()
    assert counts.sum() == n
    assert counts.min() >= n // p - 1 and counts.max() <= -(-n // p) + 1
    assert sorted(r.perm.tolist()) == list(range(n))


def test_rcb_rejects_empty_ranks():
    pts = np.zeros((3, 3))
    with pytest.raises(ValueError):
        rcb_partition(pts, 4)


@pytest.mark.parametrize("nranks", [2, 4])
def test_sharded_plan_matches_direct_sum(nranks):
    _run_sub(f"""
        import numpy as np, jax, jax.numpy as jnp
        from repro.core.api import TreecodeConfig, TreecodeSolver
        from repro.core.direct import direct_sum
        rng = np.random.default_rng(0)
        N = 2048
        pts = rng.uniform(-1, 1, (N, 3)).astype(np.float32)
        q = rng.uniform(-1, 1, N).astype(np.float32)
        solver = TreecodeSolver(TreecodeConfig(
            theta=0.7, degree=5, leaf_size=64, backend="xla"))
        phi_ds = direct_sum(jnp.asarray(pts), jnp.asarray(pts), jnp.asarray(q),
                            kernel=solver.kernel)
        plan = solver.plan(pts, nranks={nranks})
        st = plan.stats()
        assert st["strategy"] == "sharded" and st["nranks"] == {nranks}, st
        phi = plan.execute(q)
        err = float(jnp.linalg.norm(phi_ds - phi) / jnp.linalg.norm(phi_ds))
        print("err", err)
        assert err < 5e-4, err
    """, devices=nranks)


def test_sharded_plan_uneven_particle_count():
    """N % P != 0 goes through the padded-slab path end to end."""
    _run_sub("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.core.api import TreecodeConfig, TreecodeSolver
        from repro.core.direct import direct_sum
        rng = np.random.default_rng(7)
        N = 1999   # prime; 4 ranks get 500/500/500/499
        pts = rng.uniform(-1, 1, (N, 3)).astype(np.float32)
        q = rng.uniform(-1, 1, N).astype(np.float32)
        solver = TreecodeSolver(TreecodeConfig(
            theta=0.7, degree=5, leaf_size=64, backend="xla"))
        phi_ds = direct_sum(jnp.asarray(pts), jnp.asarray(pts), jnp.asarray(q),
                            kernel=solver.kernel)
        plan = solver.plan(pts, nranks=4)
        phi = plan.execute(q)
        err = float(jnp.linalg.norm(phi_ds - phi) / jnp.linalg.norm(phi_ds))
        print("err", err)
        assert err < 5e-4, err
    """)


def test_distributed_yukawa_via_legacy_alias():
    """The pre-unification entry points still work as thin shims."""
    _run_sub("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.core.api import TreecodeConfig
        from repro.core.direct import direct_sum
        from repro.distributed.bltc import prepare_distributed, distributed_execute
        rng = np.random.default_rng(3)
        pts = rng.uniform(-1, 1, (2048, 3)).astype(np.float32)
        q = rng.uniform(-1, 1, 2048).astype(np.float32)
        cfg = TreecodeConfig(theta=0.8, degree=6, leaf_size=64,
                             kernel="yukawa", kappa=0.5, backend="xla")
        phi_ds = direct_sum(jnp.asarray(pts), jnp.asarray(pts), jnp.asarray(q),
                            kernel=cfg.make_kernel())
        plan = prepare_distributed(pts, cfg, 4)
        phi = distributed_execute(plan, q, cfg)
        err = float(jnp.linalg.norm(phi_ds - phi) / jnp.linalg.norm(phi_ds))
        assert err < 5e-4, err
    """)


def test_elastic_checkpoint_reshard():
    """Save params sharded over a (2,2) mesh, restore onto (4,1)."""
    _run_sub("""
        import jax, jax.numpy as jnp, numpy as np, tempfile
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.checkpoint.store import Checkpointer
        mesh_a = jax.make_mesh((2, 2), ("data", "model"))
        mesh_b = jax.make_mesh((4, 1), ("data", "model"))
        x = jnp.arange(64.0).reshape(8, 8)
        xa = jax.device_put(x, NamedSharding(mesh_a, P("data", "model")))
        d = tempfile.mkdtemp()
        ck = Checkpointer(d)
        ck.save(1, {"x": xa}, background=False)
        sb = NamedSharding(mesh_b, P("data", None))
        restored, step, _ = ck.restore({"x": x}, shardings={"x": sb})
        assert restored["x"].sharding == sb
        np.testing.assert_array_equal(np.asarray(restored["x"]), np.asarray(x))
        print("elastic ok")
    """)


def test_compressed_psum_dp_training():
    """Pure-DP shard_map step with int8+EF gradient all-reduce converges
    like the f32 baseline (distributed-optimization trick, testable)."""
    _run_sub("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.optim.compression import compressed_psum_tree
        mesh = jax.make_mesh((4,), ("data",))
        rng = np.random.default_rng(0)
        Xg = jnp.asarray(rng.standard_normal((64, 8)).astype(np.float32))
        w_true = jnp.asarray(rng.standard_normal((8,)).astype(np.float32))
        yg = Xg @ w_true

        def local_grad(w, X, y):
            r = X @ w - y
            return X.T @ r / X.shape[0]

        def step(w, err, X, y):
            g = local_grad(w, X, y)
            g_mean, new_err = compressed_psum_tree(
                {"w": g}, {"w": err[0]}, "data")
            return w - 0.1 * g_mean["w"], new_err["w"][None]

        fn = jax.jit(jax.shard_map(
            step, mesh=mesh,
            in_specs=(P(), P("data"), P("data"), P("data")),
            out_specs=(P(), P("data")), check_vma=False))
        w = jnp.zeros(8)
        err = jnp.zeros((4, 8))   # per-rank EF buffers
        for _ in range(300):
            w, err = fn(w, err, Xg, yg)
        final = float(jnp.abs(w - w_true).max())
        assert final < 1e-2, final
        print("compressed DP ok", final)
    """)
