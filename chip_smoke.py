#!/usr/bin/env python3
"""Chip smoke check: the treecode's main paths, end to end, on a TPU.

    python chip_smoke.py               # one chip: one-shot sums, MD, serving
    python chip_smoke.py --chips 4     # four chips: the sharded RCB+LET paths

One process drives every phase through the entry points a user calls
(`TreecodeSolver`/`Plan`, `Simulation`, `ServeFrontend`); no phase starts
a child process. Each phase prints one JSON line: the backend it
resolved, N, its checks with their values and limits, and wall times
under ``smoke_timings_s`` -- smoke timings, not a benchmark. The last
line is

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

and it is printed only on a TPU, after every phase ran and passed every
check. Otherwise the script exits nonzero: when the platform is not a
TPU, when a phase raises, or when a check fails.

``--rehearse`` runs the same phases at tiny sizes with the Pallas kernels
in interpret mode, for the CPU (with ``--chips 4``, on four virtual
devices: ``XLA_FLAGS=--xla_force_host_platform_device_count=4``). It
checks the control flow only, and never prints the ok line.
"""
import argparse
import dataclasses
import json
import os
import sys
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))
sys.path.insert(0, os.path.join(ROOT, "benchmarks"))

from repro.launch.compile_cache import enable_compile_cache  # noqa: E402

# Accuracy limits of the one-shot sums against the sampled f64 oracle:
# relative 2-norm errors of the potentials and of the forces.
PHI_TOL = 1e-5
F_TOL = 1e-4
# Served results against the single-system plan of the same request.
SERVE_TOL = 1e-5
# Relative total-energy drift of the MD phases.
DRIFT_TOL = 1e-3
TIMING_NOTE = "smoke timings, not a benchmark"


@dataclasses.dataclass(frozen=True)
class Sizes:
    oneshot_n: int = 1_000_000
    oneshot_leaf: int = 0          # 0: the paper config's own N_L = N_B
    sample: int = 1000             # oracle targets
    md_m: int = 48                 # salt box m^3 particles
    md_leaf: int = 512
    md_steps: int = 40
    md_refit: int = 10
    serve_sizes: tuple = (2000, 5000, 10000)
    serve_requests: int = 16
    serve_degree: int = 6
    serve_leaf: int = 256
    sharded_n: int = 4_000_000


REHEARSAL = Sizes(oneshot_n=3000, oneshot_leaf=64, sample=200, md_m=8,
                  md_leaf=32, md_steps=12, md_refit=4,
                  serve_sizes=(100, 200, 300), serve_requests=6,
                  serve_degree=3, serve_leaf=32, sharded_n=4000)


class Phase:
    """Collects one phase's checks and smoke timings; `emit` prints its
    JSON line and returns whether every check passed."""

    def __init__(self, name: str, **info):
        self.line = dict(phase=name, **info)
        self.checks = {}
        self.times = {}

    def time(self, label: str, fn, *args, **kwargs):
        import jax

        t0 = time.perf_counter()
        out = jax.block_until_ready(fn(*args, **kwargs))
        self.times[label] = time.perf_counter() - t0
        return out

    def check(self, name: str, value, ok: bool, limit=None):
        self.checks[name] = dict(value=value, limit=limit, ok=bool(ok))

    def emit(self) -> bool:
        ok = all(c["ok"] for c in self.checks.values())
        print(json.dumps(dict(self.line, ok=ok, checks=self.checks,
                              smoke_timings_s=self.times,
                              timing_note=TIMING_NOTE)), flush=True)
        return ok


def _rel2(got, want) -> float:
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


def _cloud(n: int, seed: int):
    """The paper's test setting: uniform in [-1,1]^3, charges U[-1,1]."""
    rng = np.random.default_rng(seed)
    return (rng.uniform(-1, 1, (n, 3)).astype(np.float32),
            rng.uniform(-1, 1, n).astype(np.float32))


def _start_oracle(pool, kernel, x, q, sample, seed):
    """Start the f64 NumPy oracle on sampled targets, independent of
    every device kernel, on host threads: it overlaps the device work of
    the phase (NumPy releases the GIL in its array loops)."""
    from repro.core.direct import direct_oracle_f64

    rows = np.random.default_rng(seed + 1).choice(len(x), sample,
                                                  replace=False)
    blocks = np.array_split(rows, min(32, os.cpu_count() or 1))
    return rows, [pool.submit(direct_oracle_f64, x, q, kernel=kernel,
                              targets=r, chunk=512) for r in blocks]


def _sampled_errors(ph: Phase, oracle, phi, forces):
    """phi (and forces) against the oracle started by `_start_oracle`."""
    import jax

    rows, futures = oracle
    t0 = time.perf_counter()
    parts = [f.result() for f in futures]
    ph.times["oracle_wait"] = time.perf_counter() - t0
    ref_phi = np.concatenate([p for p, _ in parts])
    ref_f = np.concatenate([f for _, f in parts])
    phi = np.asarray(jax.device_get(phi))
    e_phi = _rel2(phi[rows], ref_phi)
    finite = bool(np.isfinite(phi).all())
    ph.check("finite_phi", finite, finite)
    ph.check("phi_rel_err_vs_f64", e_phi, e_phi <= PHI_TOL, PHI_TOL)
    if forces is not None:
        forces = np.asarray(jax.device_get(forces))
        e_f = _rel2(forces[rows], ref_f)
        finite = bool(np.isfinite(forces).all())
        ph.check("finite_F", finite, finite)
        ph.check("F_rel_err_vs_f64", e_f, e_f <= F_TOL, F_TOL)
    return phi


def _backends(cfg) -> dict:
    from repro.kernels import ops

    return dict(backend=ops.resolve_backend(cfg.backend),
                forces_backend=ops.autodiff_backend(cfg.backend))


def _check_backend(ph: Phase, cfg, want: str):
    from repro.kernels import ops

    got = ops.resolve_backend(cfg.backend)
    ph.check("backend", got, got == want, want)


def phase_oneshot(name, cfg, sizes: Sizes, seed, want_backend) -> bool:
    """A one-shot potential + force sum at the paper's widths."""
    from repro.core.api import TreecodeSolver

    x, q = _cloud(sizes.oneshot_n, seed)
    ph = Phase(name, n=len(x), theta=cfg.theta, degree=cfg.degree,
               leaf_size=cfg.leaf_size, kernel=cfg.kernel,
               **_backends(cfg))
    solver = TreecodeSolver(cfg)
    with ThreadPoolExecutor(os.cpu_count() or 1) as pool:
        oracle = _start_oracle(pool, solver.kernel, x, q, sizes.sample,
                               seed)
        plan = ph.time("plan_build_host", solver.plan, x, nranks=1)
        ph.time("execute_first", plan.execute, q)
        phi = ph.time("execute_warm", plan.execute, q)
        ph.time("forces_first", plan.potential_and_forces, q)
        _, forces = ph.time("forces_warm", plan.potential_and_forces, q)
        _check_backend(ph, cfg, want_backend)
        _sampled_errors(ph, oracle, phi, forces)
    return ph.emit()


def _md_config(sizes: Sizes, backend: str):
    from pbc_md import salt_box
    from repro.core.api import TreecodeConfig
    from repro.core.space import PeriodicBox

    x, q, box = salt_box(sizes.md_m, jitter=0.08)
    cfg = TreecodeConfig(
        theta=0.7, degree=6, leaf_size=sizes.md_leaf, kernel="yukawa",
        kernel_params={"kappa": 0.8}, space=PeriodicBox((box,) * 3),
        build_backend="device", backend=backend)
    return x, q, cfg


def _run_md(ph: Phase, plan, q, sizes: Sizes):
    """First step (compiles) outside the guard, the rest under
    `no_implicit_transfers`; the log records every step."""
    import jax
    from repro.dynamics import Simulation
    from repro.lint.runtime import no_implicit_transfers

    sim = Simulation(plan, q, dt=2e-3, refit_interval=sizes.md_refit)
    ph.time("first_step", sim.run, 1, record_every=1)
    t0 = time.perf_counter()
    with no_implicit_transfers():
        sim.run(sizes.md_steps - 1, record_every=1)
        jax.block_until_ready(sim.state)
    ph.times["steady_steps"] = time.perf_counter() - t0
    s = sim.stats()
    drift = sim.log.drift()
    ph.line.update(steps=s["steps"], refits=s["refits"])
    ph.check("energy_drift", drift, drift < DRIFT_TOL, DRIFT_TOL)
    ph.check("rebuilds", s["rebuilds"], s["rebuilds"] >= 3, ">= 3")
    ph.check("retraces_after_first_step", s["retraces"],
             s["retraces"] == 0, 0)
    return sim


def phase_md(sizes: Sizes, backend: str, want_backend: str) -> bool:
    """Periodic Yukawa MD (molten-salt box) with device rebuilds."""
    from repro.core.api import TreecodeSolver

    x, q, cfg = _md_config(sizes, backend)
    ph = Phase("md_periodic_yukawa", n=len(x), build_backend="device",
               refit_interval=sizes.md_refit, **_backends(cfg))
    plan = ph.time("plan_build_device", TreecodeSolver(cfg).plan, x,
                   nranks=1)
    _check_backend(ph, cfg, want_backend)
    _run_md(ph, plan, q, sizes)
    return ph.emit()


def phase_serve(sizes: Sizes, backend: str, want_backend: str) -> bool:
    """Served requests, with and without forces, against single-system
    plans of the same requests; a second, warm round must not compile."""
    import jax
    from repro.core.api import TreecodeConfig, TreecodeSolver
    from repro.serve import ServeFrontend
    from repro.serve.service import bucket_key

    cfg = TreecodeConfig(degree=sizes.serve_degree,
                         leaf_size=sizes.serve_leaf, backend=backend)
    ph = Phase("serve", requests=sizes.serve_requests,
               sizes=list(sizes.serve_sizes), **_backends(cfg))
    reqs = [_cloud(sizes.serve_sizes[i % len(sizes.serve_sizes)], 100 + i)
            for i in range(sizes.serve_requests)]
    fe = ServeFrontend(cfg, max_batch=8)

    def serve_round(forces):
        futs = [fe.submit(x, q, forces=forces) for x, q in reqs]
        fe.flush()
        return [f.result() for f in futs]

    cold_phi = ph.time("round_potentials_cold", serve_round, False)
    cold_pf = ph.time("round_forces_cold", serve_round, True)
    compiles_cold = fe.stats()["compiles"]
    warm_phi = ph.time("round_potentials_warm", serve_round, False)
    ph.time("round_forces_warm", serve_round, True)
    s = fe.stats()

    # Reference: the single-system plan of each request, padded into its
    # bucket's budget so each bucket compiles its executors once.
    solver = TreecodeSolver(cfg)
    t0 = time.perf_counter()
    e_phi = e_pf = e_f = e_warm = 0.0
    for (x, q), phi, (phi2, f), phiw in zip(reqs, cold_phi, cold_pf,
                                            warm_phi):
        caps = fe.buckets[bucket_key(cfg, len(x))].capacities
        plan = solver.plan(x, capacities=caps)
        n = len(x)
        ref = np.asarray(jax.device_get(plan.execute(q)))[:n]
        ref_phi2, ref_f = jax.device_get(plan.potential_and_forces(q))
        e_phi = max(e_phi, _rel2(phi, ref))
        e_pf = max(e_pf, _rel2(phi2, np.asarray(ref_phi2)[:n]))
        e_f = max(e_f, _rel2(f, np.asarray(ref_f)[:n]))
        e_warm = max(e_warm, _rel2(phiw, phi))
    ph.times["single_system_reference"] = time.perf_counter() - t0
    finite = bool(all(np.isfinite(p).all() for p in cold_phi))
    ph.line.update(buckets=s["num_buckets"], flushes=s["flushes"],
                   compiles=s["compiles"])
    _check_backend(ph, cfg, want_backend)
    ph.check("finite_phi", finite, finite)
    ph.check("phi_max_rel_err_vs_single", e_phi, e_phi <= SERVE_TOL,
             SERVE_TOL)
    ph.check("forces_round_phi_max_rel_err_vs_single", e_pf,
             e_pf <= SERVE_TOL, SERVE_TOL)
    ph.check("F_max_rel_err_vs_single", e_f, e_f <= SERVE_TOL, SERVE_TOL)
    ph.check("warm_round_phi_max_rel_diff", e_warm, e_warm <= SERVE_TOL,
             SERVE_TOL)
    ph.check("warm_round_compiles", s["compiles"] - compiles_cold,
             s["compiles"] == compiles_cold, 0)
    ph.check("retraces", s["retraces"], s["retraces"] == 0, 0)
    return ph.emit()


def _shard_devices(ph: Phase, plan, ndev: int):
    """Device of each per-rank shard of the sharded plan's arrays."""
    arr = plan.arrays["src_sorted"]
    shards = sorted(arr.addressable_shards, key=lambda s: s.index[0].start)
    devices = [s.device.id for s in shards]
    rows = [s.data.shape[0] for s in shards]
    ph.line.update(shard_devices=devices, shard_rows=rows)
    ok = sorted(devices) == list(range(ndev)) and rows == [1] * ndev
    ph.check("one_shard_per_device", devices, ok, f"{ndev} distinct")


def phase_sharded_oneshot(cfg, sizes: Sizes, seed, ndev,
                          want_backend) -> bool:
    """The paper's sum weak-scaled over the chips (RCB + LET), against
    the one-device plan of the same points and the sampled f64 oracle."""
    import jax
    from repro.core.api import TreecodeSolver

    x, q = _cloud(sizes.sharded_n, seed)
    ph = Phase("sharded_oneshot_coulomb", n=len(x), theta=cfg.theta,
               degree=cfg.degree, leaf_size=cfg.leaf_size, nranks=ndev,
               **_backends(cfg))
    solver = TreecodeSolver(cfg)
    with ThreadPoolExecutor(os.cpu_count() or 1) as pool:
        oracle = _start_oracle(pool, solver.kernel, x, q, sizes.sample,
                               seed)
        plan = ph.time("plan_build_host", solver.plan, x)
        ph.line["build_phases_ms"] = plan.stats()["build_phases"]
        ph.check("nranks", plan.nranks, plan.nranks == ndev, ndev)
        _shard_devices(ph, plan, ndev)
        ph.time("execute_first", plan.execute, q)
        phi = ph.time("execute_warm", plan.execute, q)
        _check_backend(ph, cfg, want_backend)
        if want_backend == "pallas":
            text = plan._spmd_fn().lower(
                plan.arrays, plan._rank_charges(q),
                plan.kernel_params).as_text()
            ph.check("pallas_in_spmd_program", "tpu_custom_call" in text,
                     "tpu_custom_call" in text)
        one = ph.time("plan_build_host_one_device", solver.plan, x,
                      nranks=1)
        phi1 = ph.time("execute_one_device_first", one.execute, q)
        phi = _sampled_errors(ph, oracle, phi, None)
    d = _rel2(phi, jax.device_get(phi1))
    ph.check("phi_rel_diff_vs_one_device", d, d <= 2 * PHI_TOL,
             2 * PHI_TOL)
    return ph.emit()


def phase_sharded_md(sizes: Sizes, backend: str, ndev,
                     want_backend) -> bool:
    """The periodic MD run over the chips: host RCB/LET rebuilds that
    re-pad into the plan's budget and reuse the compiled SPMD step."""
    from repro.core.api import TreecodeSolver

    x, q, cfg = _md_config(sizes, backend)
    ph = Phase("sharded_md_periodic_yukawa", n=len(x), nranks=ndev,
               build_backend="device", refit_interval=sizes.md_refit,
               **_backends(cfg))
    plan = ph.time("plan_build", TreecodeSolver(cfg).plan, x)
    ph.check("nranks", plan.nranks, plan.nranks == ndev, ndev)
    _shard_devices(ph, plan, ndev)
    _check_backend(ph, cfg, want_backend)
    _run_md(ph, plan, q, sizes)
    return ph.emit()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: one-shot, MD and serving phases on one chip; "
                         "4: only the sharded phases, on four chips")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes, Pallas in interpret mode (CPU); "
                         "never prints the ok line")
    args = ap.parse_args(argv)
    enable_compile_cache()

    import jax
    from repro.configs.bltc import SCALING, SCALING_YUKAWA

    devs = jax.devices()
    device = dict(platform=devs[0].platform, kind=devs[0].device_kind,
                  count=len(devs))
    if not args.rehearse and device["platform"] != "tpu":
        print(f"chip_smoke: no TPU ({device}); nothing was run",
              file=sys.stderr)
        return 2
    if len(devs) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, found {device}", file=sys.stderr)
        return 2
    if args.rehearse:
        sizes, backend, want = REHEARSAL, "pallas_interpret", \
            "pallas_interpret"
    else:
        sizes, backend, want = Sizes(), "auto", "pallas"

    def paper(cfg):
        leaf = sizes.oneshot_leaf or cfg.leaf_size
        return dataclasses.replace(cfg, leaf_size=leaf, backend=backend)

    results = []
    if args.chips == 1:
        results.append(phase_md(sizes, backend, want))
        results.append(phase_serve(sizes, backend, want))
        results.append(phase_oneshot("oneshot_coulomb", paper(SCALING),
                                     sizes, args.seed, want))
        results.append(phase_oneshot("oneshot_yukawa",
                                     paper(SCALING_YUKAWA), sizes,
                                     args.seed, want))
    else:
        results.append(phase_sharded_md(sizes, backend, args.chips, want))
        results.append(phase_sharded_oneshot(paper(SCALING), sizes,
                                             args.seed, args.chips, want))

    if not all(results):
        print("chip_smoke: a check failed", file=sys.stderr)
        return 1
    if args.rehearse:
        print(json.dumps(dict(rehearsal_passed=True, device=device)))
        return 0
    print(json.dumps(dict(ok=True, device=device)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
