"""The general traffic generator: drives the system under test through
the entry points a user calls, for every traffic mix in `bench/traffic/`.

A traffic file names its `kind` and the parameters of that kind:

  - ``eval``: fixed geometry. The points are the configuration's draw of
    seed 0 in every run, so every seed does the same work on the same
    plan; set-up builds that plan, and each call of the window evaluates
    potentials for a new device-resident charge vector (one of
    `charge_vectors`, drawn from the seed at set-up) and waits for them.
    A closed loop, one call at a time.
  - ``solve``: one-shot sums over new points. Set-up draws `point_sets`
    particle sets and their charges from the seed and builds a plan;
    each solve of the window replans on the next set's points (the host
    planner, on the plan's kept budget) and evaluates its potentials. A
    closed loop, one solve at a time.

Every seed runs the same shapes, so after a cell's first run in a
checkout every program is in the compile cache: ``eval`` plans the same
points, and ``solve`` pads every plan into one budget that depends on
the configuration alone (`shape_budget`).

Each kind keeps what the timed path produced, and after the window
compares a sample of it, drawn from the seed, with the float64 reference
of `bench/reference/direct.py`; `control=True` puts the lower-precision
control in the program's place for that comparison.
"""
from __future__ import annotations

import dataclasses
import gc
import time
from typing import Dict, List

import numpy as np

from bench.reference import direct, generators

# Streams of a seed: which draw each is for.
_POINTS, _CHARGES, _ROWS = 0, 1, 2
# The draw that is the fixed geometry of `eval` and sets the shape
# budget of `solve`.
_FIXED_SEED = 0


@dataclasses.dataclass
class Check:
    """One number compared with its limit."""

    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return bool(np.isfinite(self.value) and self.value <= self.limit)


def rel2(got, want) -> float:
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


def annotate(name: str):
    import jax

    return jax.profiler.TraceAnnotation(name)


def system_points(config: dict, seed: int, stream: int = _POINTS):
    """(x, q) of a configuration's system for one seed and stream."""
    sysc = config["system"]
    if sysc["generator"] == "uniform_cloud":
        return generators.uniform_cloud(sysc["n"], seed, stream)
    raise ValueError(f"unknown generator {sysc['generator']!r}")


def treecode_config(config: dict):
    """The program's `TreecodeConfig` for a configuration file: the keys
    its ``treecode`` group sets, every other field at its default."""
    from repro.core.api import TreecodeConfig

    return TreecodeConfig(**config["treecode"])


def kernel_args(config: dict) -> dict:
    t = config["treecode"]
    return dict(kernel=t["kernel"],
                kappa=float(t.get("kernel_params", {}).get("kappa", 0.0)))


def shape_budget(config: dict):
    """The plan budget of a configuration: the program's own padded
    budget (`capacities="auto"`, its default headroom) of the plan over
    the draw of seed 0. A seed whose plan needs more grows it, in set-up."""
    from repro.core.api import TreecodeSolver

    x, _ = system_points(config, _FIXED_SEED)
    plan = TreecodeSolver(treecode_config(config)).plan(
        x, nranks=1, capacities="auto")
    budget = plan.capacities
    del plan
    gc.collect()
    return budget


def _seed32(seed: int, stream: int) -> int:
    return int(np.random.SeedSequence([int(seed), stream])
               .generate_state(1)[0])


def device_charges(seed: int, n: int, count: int):
    """`count` charge vectors U[-1, 1] of length n, made on the device in
    one jitted call, as separate arrays."""
    import jax

    key = jax.random.key(_seed32(seed, _CHARGES))
    return jax.jit(lambda k: tuple(
        jax.random.uniform(kk, (n,), minval=-1.0, maxval=1.0)
        for kk in jax.random.split(k, count)))(key)


class Traffic:
    """One kind of traffic: drives the system through a cell's window
    and checks what it produced (module docstring)."""

    unit = "unit"

    def __init__(self, config: dict, params: dict, seed: int):
        self.config = config
        self.params = params            # the traffic file's parameters
        self.seed = int(seed)
        self.rows_n = int(params.get("check_rows", 1024))
        self.failed = 0
        self.budget_grown = False

    def rows(self, n: int) -> np.ndarray:
        k = min(self.rows_n, n)
        return np.sort(generators.rng(self.seed, _ROWS).choice(
            n, k, replace=False))

    def plan(self, x, budget=None):
        """The program's plan of `x`, padded into `budget` if one is given."""
        from repro.core.api import TreecodeSolver

        with annotate("bench.plan"):
            plan = TreecodeSolver(treecode_config(self.config)).plan(
                x, nranks=1, capacities=budget)
        self.budget_grown |= plan.capacities != budget
        return plan

    # setup / run_unit / collect / check are each kind's protocol.
    def setup(self) -> None:
        raise NotImplementedError

    def run_unit(self) -> int:
        raise NotImplementedError

    def end_to_end(self, window_s: float, units: int) -> Dict[str, float]:
        raise NotImplementedError

    def collect(self) -> None:
        """Copy to the host what the check needs, then free the program's
        state: the reference runs after the program has let go."""
        raise NotImplementedError

    def check(self, control: bool = False) -> List[Check]:
        raise NotImplementedError

    def layer(self) -> dict:
        """Program spans and counters of the window, for the readers."""
        return {}

    def _phi_checks(self, sets, control):
        """phi_err over the window's outputs: `sets` maps a key to
        (points, {charge index: charges}) and `self.got` holds
        (key, charge index, phi at the rows) for every unit."""
        args = kernel_args(self.config)
        errs = []
        for key, (x, qs) in sets.items():
            used = sorted(qs)
            q = np.stack([qs[i] for i in used], 1)
            ref = direct.direct_f64(x, q, self.rows_idx, **args)
            col = {i: c for c, i in enumerate(used)}
            ctl = direct.control(x, q, self.rows_idx, **args) \
                if control else None
            for k, i, phi in self.got:
                if k != key:
                    continue
                mine = ctl[:, col[i]] if control else phi
                errs.append(rel2(mine, ref[:, col[i]]))
        limit = self.limits["phi_err"]
        self.failed = sum(e > limit for e in errs)
        return [Check("phi_err", max(errs), limit)]


class EvalTraffic(Traffic):
    unit = "call"

    def setup(self):
        import jax

        self.x, _ = system_points(self.config, _FIXED_SEED)
        self.plan_ = self.plan(self.x)
        self.charges = device_charges(self.seed, len(self.x),
                                      int(self.params["charge_vectors"]))
        jax.block_until_ready(self.plan_.execute(self.charges[0]))
        self.outputs = []

    def run_unit(self):
        i = len(self.outputs) % len(self.charges)
        with annotate("bench.execute"):
            phi = self.plan_.execute(self.charges[i])
            phi.block_until_ready()
        self.outputs.append((i, phi))
        return 1

    def end_to_end(self, window_s, units):
        return {"eval_s": window_s / units}

    def collect(self):
        import jax

        self.calls = len(self.outputs)
        self.rows_idx = self.rows(len(self.x))
        idx = np.asarray(self.rows_idx)
        self.got = [(0, i, np.asarray(jax.device_get(p[idx])))
                    for i, p in self.outputs]
        self.q_used = {i: np.asarray(jax.device_get(self.charges[i]))
                       for i in sorted({i for i, _ in self.outputs})}
        del self.plan_, self.outputs, self.charges
        gc.collect()

    def check(self, control=False):
        return self._phi_checks({0: (self.x, self.q_used)}, control)

    def layer(self):
        return dict(calls=self.calls)

    def work(self):
        """The algorithm's work for one call (bench/reference/worktree)."""
        from bench.reference import worktree

        t = self.config["treecode"]
        w = worktree.count_work(
            self.x, self.x, theta=t["theta"], degree=t["degree"],
            leaf_size=t["leaf_size"],
            batch_size=t.get("batch_size") or t["leaf_size"])
        return dict(flops=worktree.flops(w, t["kernel"], t["degree"]),
                    bytes=w.total_bytes())


class SolveTraffic(Traffic):
    unit = "solve"

    def setup(self):
        import jax

        n = int(self.config["system"]["n"])
        count = int(self.params["point_sets"])
        self.sets = [system_points(self.config, self.seed, 100 + k)[0]
                     for k in range(count)]
        self.x = self.sets[0]
        self.charges = device_charges(self.seed, n, count)
        self.plan_ = self.plan(self.sets[-1], shape_budget(self.config))
        # The warm-up replans once, as every solve does.
        plan = self.plan_.replan(self.sets[0])
        jax.block_until_ready(plan.execute(self.charges[0]))
        self.budget_grown |= plan.capacities != self.plan_.capacities
        del plan
        self.outputs, self.plan_s = [], 0.0

    def run_unit(self):
        k = len(self.outputs) % len(self.sets)
        with annotate("bench.replan"):
            t = time.perf_counter()
            plan = self.plan_.replan(self.sets[k])
            self.plan_s += time.perf_counter() - t
        with annotate("bench.execute"):
            phi = plan.execute(self.charges[k])
            phi.block_until_ready()
        self.outputs.append((k, phi))
        return 1

    def end_to_end(self, window_s, units):
        return {"solve_s": window_s / units}

    def collect(self):
        import jax

        self.solves = len(self.outputs)
        self.rows_idx = self.rows(len(self.x))
        idx = np.asarray(self.rows_idx)
        self.got = [(k, k, np.asarray(jax.device_get(p[idx])))
                    for k, p in self.outputs]
        self.q_used = {k: np.asarray(jax.device_get(self.charges[k]))
                       for k in sorted({k for k, _ in self.outputs})}
        del self.plan_, self.outputs, self.charges
        gc.collect()

    def check(self, control=False):
        return self._phi_checks({k: (self.sets[k], {k: q})
                                 for k, q in self.q_used.items()}, control)

    def layer(self):
        return dict(solves=self.solves, plan_s=self.plan_s)


KINDS = {"eval": EvalTraffic, "solve": SolveTraffic}


def make_traffic(config, traffic, limits, seed) -> Traffic:
    d = KINDS[traffic["kind"]](config, traffic, seed)
    d.limits = limits
    return d
