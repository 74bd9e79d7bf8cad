"""The lower-precision control, put in the program's place, comes out
not correct; the reference it is judged by is the float64 direct sum."""
import numpy as np
import pytest

from bench.reference import direct, generators
from bench_tiny import run_tiny


@pytest.mark.parametrize("cell", ["coulomb_1m.eval", "coulomb_1m.solve"])
def test_control_is_not_correct(cell):
    line = run_tiny(cell, control=True, backend="xla")
    assert line["correct"] is False


@pytest.mark.parametrize("kernel,kappa", [("coulomb", 0.0), ("yukawa", 0.5)])
def test_reference_matches_a_plain_double_loop(kernel, kappa):
    x, q = generators.uniform_cloud(40, seed=4)
    x, q = x.astype(np.float64), q.astype(np.float64)
    rows = np.array([0, 5, 26])
    phi = direct.direct_f64(x, q, rows, kernel=kernel, kappa=kappa, chunk=7)
    for k, i in enumerate(rows):
        p = 0.0
        for j in range(len(x)):
            if j == i:
                continue
            r = np.linalg.norm(x[i] - x[j])
            p += np.exp(-kappa * r) / r * q[j]
        assert phi[k] == pytest.approx(p, rel=1e-12)


def test_control_error_is_that_of_three_bf16_products():
    x, q = generators.uniform_cloud(5000, seed=9)
    rows = np.arange(0, 5000, 50)
    ref = direct.direct_f64(x, q, rows, kernel="coulomb")
    ctl = direct.control(x, q, rows, kernel="coulomb")
    err = np.linalg.norm(ctl - ref) / np.linalg.norm(ref)
    assert 3e-7 < err < 3e-5
