"""A configuration's deployment comes from its own files: the point
generator its `system.generator` names, and the ranks its
`layout.ranks` sets. A cell of a new configuration, on its own
generator and on four ranks, needs only new files and entries."""
import json
import os
import subprocess
import sys
import textwrap

import pytest

from bench import generator, harness
from bench.reference import generators

SPEC = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
_SOLVE_STREAM = 100     # the first of `solve`'s point sets


@pytest.mark.parametrize("seed", [0, 2**33 + 7])
@pytest.mark.parametrize("stream", [generator._POINTS, _SOLVE_STREAM])
def test_uniform_cloud_draws_the_same_bits_by_name(seed, stream):
    config = harness.load_cell("coulomb_1m.eval").config
    config = dict(config, system=dict(config["system"], n=5000))
    x, q = generator.system_points(config, seed, stream)
    want_x, want_q = generators.uniform_cloud(5000, seed, stream)
    assert x.dtype == want_x.dtype and q.dtype == want_q.dtype
    assert x.tobytes() == want_x.tobytes()
    assert q.tobytes() == want_q.tobytes()


def test_an_unknown_generator_is_refused():
    config = dict(system=dict(generator="no_such_cloud", n=10))
    with pytest.raises(ValueError, match="no_such_cloud"):
        generator.system_points(config, 1)


_UNIT_METRIC = {"eval": "eval_s", "solve": "solve_s"}


def _root(tmp_path, layout_ranks, chips, extra_system=None):
    """A checkout that holds the repository's benchmark data and a new
    configuration `blob4` with its cells `blob4.eval` and `blob4.solve`,
    as a later change adds them: a configuration file, limits files, a
    generator file and entries in BENCHMARK.json."""
    spec = json.loads(json.dumps(SPEC))
    spec["configs"].append(dict(
        name="blob4", source="test", file="bench/configs/blob4.json",
        reduced=[], why="test"))
    bench = tmp_path / "bench"
    for sub in ("configs", "limits", "traffic", "reference/systems"):
        (bench / sub).mkdir(parents=True)
    for kind, metric in _UNIT_METRIC.items():
        spec["workloads"].append(dict(name=f"blob4.{kind}", config="blob4",
                                      traffic=kind, chips=chips, why="test"))
        for m in spec["end_to_end"]:
            if m["name"] == metric:
                m["workloads"].append(f"blob4.{kind}")
        (bench / "traffic" / f"{kind}.json").write_text(
            (harness.BENCH / "traffic" / f"{kind}.json").read_text())
        (bench / "limits" / f"blob4.{kind}.json").write_text(
            json.dumps(dict(phi_err=1.5e-6)))
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    (bench / "configs" / "blob4.json").write_text(json.dumps(dict(
        name="blob4", reduced=[],
        system=dict(generator="gaussian_blob", n=4000, **(extra_system or
                                                           {})),
        treecode=dict(theta=0.7, degree=8, leaf_size=64, batch_size=64,
                      kernel="coulomb", backend="xla"),
        layout=dict(chips=chips, ranks=layout_ranks))))
    (bench / "reference" / "systems" / "gaussian_blob.py").write_text(
        textwrap.dedent('''
        import numpy as np


        def points(n, seed, stream, scale=1.0):
            g = np.random.default_rng([int(seed), int(stream)])
            x = (scale * g.standard_normal((n, 3))).astype(np.float32)
            q = g.uniform(-1.0, 1.0, n).astype(np.float32)
            return x, q
        '''))
    return tmp_path


def test_load_cell_refuses_ranks_that_are_not_the_chips(tmp_path):
    root = _root(tmp_path, layout_ranks=1, chips=4)
    with pytest.raises(ValueError, match=r"4 chip.*layout\.ranks 1"):
        harness.load_cell("blob4.eval", root=root)


def test_a_count_without_a_sharded_form_names_itself(tmp_path):
    # The benchmark's work count is of one tree: on four ranks it refuses,
    # and does not count one rank's work instead.
    cell = harness.load_cell("blob4.eval", root=_root(tmp_path, 4, 4))
    traffic = generator.make_traffic(cell.config, cell.traffic, cell.limits,
                                     1, cell.root)
    with pytest.raises(NotImplementedError, match="worktree.*ranks 4"):
        traffic.work()


@pytest.mark.parametrize("kind", sorted(_UNIT_METRIC))
def test_a_four_rank_cell_of_new_files_runs_and_checks(tmp_path, kind):
    # The new cell runs the program's sharded plan over four (virtual)
    # devices, through the harness as it stands, and passes its check.
    root = _root(tmp_path, layout_ranks=4, chips=4,
                 extra_system=dict(scale=0.5))
    code = textwrap.dedent(f'''
        import json, sys, time
        from pathlib import Path
        from bench import harness

        cell = harness.load_cell("blob4.{kind}", root=Path({str(root)!r}))
        plans = []

        def hook(traffic):
            plan = traffic.plan

            def recorded(x, budget=None):
                p = plan(x, budget)
                plans.append([type(p).__name__, p.nranks,
                              len(p.mesh.devices.flat)])
                return p
            traffic.plan = recorded

        line = harness.run_cell(cell, 2**33 + 11, 0.2, False,
                                t_start=time.perf_counter(),
                                require_chip=False, traffic_hook=hook)
        print(json.dumps(dict(line=line, plans=plans)))
        ''')
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([str(harness.ROOT),
                                           str(harness.ROOT / "src")]))
    p = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=55, env=env, cwd=harness.ROOT)
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    line = out["line"]
    assert out["plans"] == [["ShardedPlan", 4, 4]]
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 1 and line["device"]["count"] == 4
    assert set(line["metrics"]) == {_UNIT_METRIC[kind], "setup_s"}
    assert line["checks"]["phi_err"]["value"] <= 1.5e-6
