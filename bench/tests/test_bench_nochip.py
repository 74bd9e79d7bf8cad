"""The entry point without a TPU: it measures nothing, prints no result
and exits nonzero; a checkout that holds only the benchmark fails too."""
import os
import shutil
import subprocess
import sys

from bench import harness


def _run(cwd, root):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, str(root / "bench" / "run.py"), "--workload",
         "coulomb_1m.eval", "--seed", "3", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_no_tpu_prints_no_result():
    out = _run(harness.ROOT, harness.ROOT)
    assert out.returncode == 2
    assert out.stdout.strip() == ""
    assert "nothing was measured" in out.stderr


def test_benchmark_files_alone_fail(tmp_path):
    root = harness.ROOT
    shutil.copy(root / "BENCHMARK.json", tmp_path)
    shutil.copytree(root / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    out = _run(tmp_path, tmp_path)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
