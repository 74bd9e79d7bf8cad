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
    particle sets, the configuration's draws of seed 0 in every run, and
    their charges from the seed, and builds a plan; each solve of the
    window replans on the next set's points (the host planner, on the
    plan's kept budget) and evaluates its potentials. A closed loop, one
    solve at a time. The points are the same for every seed because the
    device's work follows the tree of the draw: two uniform draws of
    10^6 points differ by up to a tenth in kernel time on a TPU v5e.

Every seed runs the same shapes and the same work, so after a cell's
first run in a checkout every program is in the compile cache: ``eval``
plans the same points, and ``solve`` the same point sets, each padded
into one budget that depends on the configuration alone
(`shape_budget`).

A configuration names its point generator (`system.generator`, a file
`bench/reference/systems/<generator>.py`) and its ranks (`layout.ranks`):
one rank plans on one device, more plan the program's sharded plan over
that many devices (`program_plan`).

Each kind keeps what the timed path produced, and after the window
compares a sample of it, drawn from the seed, with the float64 reference
of `bench/reference/direct.py`; `control=True` puts the lower-precision
control in the program's place for that comparison.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib.util
import time
from pathlib import Path
from typing import Dict, List

import numpy as np

from bench.reference import direct, generators

# Streams of a seed: which draw each is for.
_POINTS, _CHARGES, _ROWS = 0, 1, 2
# The draw that is the fixed geometry of `eval` and `solve` and sets the
# shape budget of `solve`.
_FIXED_SEED = 0
# The checkout whose `bench/` holds the cells' files.
ROOT = Path(__file__).resolve().parents[1]


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


def _points_fn(generator: str, root: Path):
    path = root / "bench" / "reference" / "systems" / f"{generator}.py"
    if not path.exists():
        raise ValueError(f"unknown generator {generator!r}: no {path}")
    spec = importlib.util.spec_from_file_location(
        "bench_system_" + generator, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.points


def system_points(config: dict, seed: int, stream: int = _POINTS,
                  root: Path = ROOT):
    """(x, q) of a configuration's system for one seed and stream: the
    `points(n, seed, stream, **params)` of the generator file that
    `system.generator` names, with the group's other keys but `n` as its
    parameters."""
    params = dict(config["system"])
    points = _points_fn(params.pop("generator"), root)
    return points(int(params.pop("n")), seed, stream, **params)


def treecode_config(config: dict):
    """The program's `TreecodeConfig` for a configuration file: the keys
    its ``treecode`` group sets, every other field at its default."""
    from repro.core.api import TreecodeConfig

    return TreecodeConfig(**config["treecode"])


def kernel_args(config: dict) -> dict:
    t = config["treecode"]
    return dict(kernel=t["kernel"],
                kappa=float(t.get("kernel_params", {}).get("kappa", 0.0)))


def program_plan(config: dict, x, capacities=None):
    """The program's plan of `x` under a configuration: on one device
    with one rank, else the program's sharded plan over the first
    `layout.ranks` devices."""
    import jax
    from repro.core.api import TreecodeSolver

    solver = TreecodeSolver(treecode_config(config))
    ranks = int(config["layout"]["ranks"])
    if ranks == 1:
        return solver.plan(x, nranks=1, capacities=capacities)
    mesh = jax.make_mesh((ranks,), ("data",),
                         axis_types=(jax.sharding.AxisType.Auto,),
                         devices=jax.devices()[:ranks])
    return solver.plan(x, mesh=mesh, capacities=capacities)


def shape_budget(config: dict, root: Path = ROOT):
    """The plan budget of a configuration: the program's own padded
    budget (`capacities="auto"`, its default headroom) of the plan over
    the draw of seed 0. A seed whose plan needs more grows it, in set-up."""
    x, _ = system_points(config, _FIXED_SEED, root=root)
    plan = program_plan(config, x, capacities="auto")
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

    def __init__(self, config: dict, params: dict, seed: int,
                 root: Path = ROOT):
        self.config = config
        self.params = params            # the traffic file's parameters
        self.seed = int(seed)
        self.root = root                # where the generator files are
        self.rows_n = int(params.get("check_rows", 1024))
        self.failed = 0
        self.budget_grown = False

    def rows(self, n: int) -> np.ndarray:
        k = min(self.rows_n, n)
        return np.sort(generators.rng(self.seed, _ROWS).choice(
            n, k, replace=False))

    def plan(self, x, budget=None):
        """The program's plan of `x`, padded into `budget` if one is given."""
        with annotate("bench.plan"):
            plan = program_plan(self.config, x, capacities=budget)
        if budget is not None:
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

        self.x, _ = system_points(self.config, _FIXED_SEED, root=self.root)
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

        ranks = int(self.config["layout"]["ranks"])
        if ranks != 1:
            raise NotImplementedError(
                "EvalTraffic.work (bench/reference/worktree.py) has no "
                f"sharded form (layout.ranks {ranks})")
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
        self.sets = [system_points(self.config, _FIXED_SEED, 100 + k,
                                   root=self.root)[0]
                     for k in range(count)]
        self.x = self.sets[0]
        self.charges = device_charges(self.seed, n, count)
        self.plan_ = self.plan(self.sets[-1],
                               shape_budget(self.config, self.root))
        # The warm-up replans once, as every solve does.
        plan = self.plan_.replan(self.sets[0])
        jax.block_until_ready(plan.execute(self.charges[0]))
        self.budget_grown |= plan.capacities != self.plan_.capacities
        del plan
        self.outputs, self.plan_s = [], 0.0

    def run_unit(self):
        # The window starts past the set that set-up replanned.
        k = (len(self.outputs) + 1) % len(self.sets)
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


def make_traffic(config, traffic, limits, seed, root=ROOT) -> Traffic:
    d = KINDS[traffic["kind"]](config, traffic, seed, root)
    d.limits = limits
    return d
