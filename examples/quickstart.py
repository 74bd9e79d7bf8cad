"""Quickstart: the unified plan/execute/forces API on 20k Coulomb particles.

One solver facade covers every execution strategy:

  plan = solver.plan(points)             # SingleDevicePlan or ShardedPlan
  phi  = plan.execute(charges)           # potentials, input order
  phi, F = plan.potential_and_forces(q)  # + forces F_i = -q_i grad phi_i
  plan = plan.replan(new_points)         # moving particles (MD)

Run on N devices (e.g. a forced-host-device CPU check) and `solver.plan`
auto-shards via RCB + locally essential trees:

    XLA_FLAGS=--xla_force_host_platform_device_count=4 \\
        PYTHONPATH=src python examples/quickstart.py

    PYTHONPATH=src python examples/quickstart.py
"""
import time

import numpy as np
import jax.numpy as jnp

from repro.core.api import TreecodeConfig, TreecodeSolver
from repro.core.direct import direct_sum
from repro.launch.compile_cache import enable_compile_cache


def main():
    enable_compile_cache()
    rng = np.random.default_rng(0)
    n = 20_000
    # random particles in the [-1,1]^3 cube, charges uniform on [-1,1]
    # (the paper's Sec. 4 test setting)
    points = rng.uniform(-1, 1, (n, 3)).astype(np.float32)
    charges = rng.uniform(-1, 1, n).astype(np.float32)

    solver = TreecodeSolver(TreecodeConfig(
        theta=0.8, degree=8, leaf_size=512, kernel="coulomb"))

    t0 = time.time()
    plan = solver.plan(points)            # sources default to the targets
    phi = plan.execute(charges)
    phi.block_until_ready()
    t_tree = time.time() - t0
    stats = plan.stats()

    t0 = time.time()
    phi_ds = direct_sum(jnp.asarray(points), jnp.asarray(points),
                        jnp.asarray(charges), kernel=solver.kernel)
    phi_ds.block_until_ready()
    t_direct = time.time() - t0

    err = float(jnp.linalg.norm(phi - phi_ds) / jnp.linalg.norm(phi_ds))
    print(f"N = {n}   strategy = {stats['strategy']} "
          f"(nranks = {stats['nranks']})")
    print(f"treecode: {t_tree:.2f}s (incl. tree build)   "
          f"direct sum: {t_direct:.2f}s")
    print(f"relative 2-norm error (paper Eq. 16): {err:.2e}")
    print(f"interaction-list padding waste: {stats['padding_waste']:.1%}")

    # plan reuse with new charges (boundary-element / iterative-solver use;
    # set donate_charges=True to recycle the device buffer in such loops)
    charges2 = rng.uniform(-1, 1, n).astype(np.float32)
    t0 = time.time()
    plan.execute(charges2).block_until_ready()
    print(f"re-execute with new charges: {time.time() - t0:.2f}s")

    # forces through the same plan (differentiable entry point)
    t0 = time.time()
    _, forces = plan.potential_and_forces(charges)
    jnp.asarray(forces).block_until_ready()
    print(f"potential + forces: {time.time() - t0:.2f}s  "
          f"|F| max = {float(jnp.abs(forces).max()):.3g}")


if __name__ == "__main__":
    main()
