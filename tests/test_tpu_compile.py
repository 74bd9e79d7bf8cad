"""Compile rehearsals for the TPU v5e, with no chip attached.

The TPU compiler is installed and compiles for a described topology:
these tests lower the Pallas kernels of the main path at the paper's
widths, and the sharded SPMD step on a four-chip mesh, exactly as the
chip would receive them. Nothing runs, so they say nothing about results
or times; they catch what interpret mode cannot (Mosaic's tiling rules,
in-kernel layouts, fast-memory limits) before any chip time is spent.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and every test worker
imports this file.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec, SingleDeviceSharding

from repro.core.api import TreecodeConfig
from repro.core.potentials import coulomb, yukawa
from repro.core.space import PeriodicBox
from repro.distributed.bltc import ShardedPlan
from repro.kernels import ops
from repro.launch.mesh import auto_mesh


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure to describe
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # Entries compiled for a described chip cannot be read back without
    # one: keep them out of any persistent cache.
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", prev)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _params(sharding, kernel):
    return jax.tree.map(lambda v: _sds(sharding, np.shape(v)), kernel.params)


def _compile_batch_cluster(one_chip, kernel, *, B=4, S=8, NB=4000, C=64,
                           m=4000, vmap=0, lst=None, **opts):
    """`lst`, if given, is a fixed (B, S) list compiled in as a constant
    in place of the list argument."""
    kw = dict(kernel=kernel.stripped(), backend="pallas", **opts)

    def f(idx, tgt, src, q, params):
        if lst is not None:
            idx = jnp.asarray(lst)
        return ops.batch_cluster_eval(idx, tgt, src, q, params, **kw)

    lead = (vmap,) if vmap else ()
    args = [_sds(one_chip, lead + (B, S), jnp.int32),
            _sds(one_chip, lead + (B, NB, 3)),
            _sds(one_chip, lead + (C, m, 3)),
            _sds(one_chip, lead + (C, m)),
            jax.tree.map(lambda v: _sds(one_chip, lead + np.shape(v)),
                         kernel.params)]
    return jax.jit(jax.vmap(f) if vmap else f).lower(*args).compile()


@pytest.mark.parametrize("case", [
    "coulomb_m4000", "yukawa_periodic", "matmul_r2", "kahan", "vmap",
    "long_list", "sentinels"])
def test_batch_cluster_compiles_for_v5e(one_chip, case):
    if case == "coulomb_m4000":
        compiled = _compile_batch_cluster(one_chip, coulomb())
    elif case == "long_list":
        # An MD-sized list (370 batches x 1024 slots, 1.5 MB) is more
        # than the 1 MiB of SMEM one call may prefetch: it runs split.
        compiled = _compile_batch_cluster(
            one_chip, yukawa(0.8), B=370, S=1024, NB=512, C=600, m=343,
            space=PeriodicBox((48.0, 48.0, 48.0)))
    elif case == "sentinels":
        # The paper cell's direct list (1023 batches x 200 slots of 2000
        # sources, run as row chunks) with interior sentinels, as the
        # skin gate leaves them, and trailing padding of varying length.
        lst = np.random.default_rng(0).integers(0, 1000, (1023, 200))
        lst[:, 5:9] = -1
        lst[np.arange(200) >= np.arange(1023)[:, None] % 180 + 20] = -1
        compiled = _compile_batch_cluster(
            one_chip, coulomb(), B=1023, S=200, NB=2048, C=1000, m=2000,
            lst=lst.astype(np.int32))
    elif case == "yukawa_periodic":
        compiled = _compile_batch_cluster(
            one_chip, yukawa(0.8), NB=512, m=512,
            space=PeriodicBox((48.0, 48.0, 48.0)))
    elif case == "matmul_r2":
        compiled = _compile_batch_cluster(one_chip, coulomb(), NB=4000,
                                          m=729, r2_mode="matmul")
    elif case == "kahan":
        compiled = _compile_batch_cluster(one_chip, coulomb(), NB=4000,
                                          m=4000, kahan=True)
    else:  # the ensemble serving path vmaps the kernel over systems
        compiled = _compile_batch_cluster(one_chip, yukawa(0.5), NB=256,
                                          m=256, vmap=8)
    assert "tpu_custom_call" in compiled.as_text()


def test_modified_charges_compiles_for_v5e(one_chip):
    def f(pts, q, lo, hi):
        return ops.modified_charges(pts, q, lo, hi, degree=8,
                                    backend="pallas")

    c, m = 16, 4096
    compiled = jax.jit(f).lower(
        _sds(one_chip, (c, m, 3)), _sds(one_chip, (c, m)),
        _sds(one_chip, (c, 3)), _sds(one_chip, (c, 3))).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_sharded_step_compiles_for_v5e_mesh(topo, monkeypatch):
    """The SPMD step of a 4-rank plan, on a mesh of the four described
    chips. `backend="auto"` must resolve to Pallas there: the platform
    rule is steered to the TPU answer, since this process sees a CPU."""
    rng = np.random.default_rng(0)
    x = rng.uniform(-1, 1, (4000, 3)).astype(np.float32)
    cfg = TreecodeConfig(theta=0.8, degree=4, leaf_size=128)
    host_mesh = auto_mesh((1,), ("data",))
    plan = ShardedPlan.build(x, cfg, 4, mesh=host_mesh)

    monkeypatch.setattr(ops, "default_backend", lambda: "pallas")
    plan.mesh = jax.sharding.Mesh(
        np.array(topo.devices[:4]), ("data",),
        axis_types=(jax.sharding.AxisType.Auto,))
    sharded = NamedSharding(plan.mesh, PartitionSpec("data"))
    replicated = NamedSharding(plan.mesh, PartitionSpec())
    arrays = {k: _sds(sharded, v.shape, v.dtype)
              for k, v in plan.arrays.items()}
    q = _sds(sharded, (4, plan.per_pad))
    compiled = plan._spmd_fn().lower(
        arrays, q, _params(replicated, plan.kernel)).compile()
    hlo = compiled.as_text()
    assert "tpu_custom_call" in hlo
    assert "collective-permute" in hlo and "all-gather" in hlo
