"""The plain reference: direct summation in float64 NumPy, and the control.

`direct_f64` is a copy of the program's float64 oracle
(`core/direct.py:direct_oracle_f64`), widened to several charge vectors
at once: phi[r, c] = sum_j G(x_r - x_j) q[j, c] over every source j != r,
in free space. Only the sampled target rows are evaluated, against every
source, on host threads.

`control` is the same sum put in the program's place one precision step
down: float32 pair terms whose contraction with the charges is taken as
three bfloat16 products (hi*hi + hi*lo + lo*hi, what XLA's `HIGH`
precision computes on a TPU), where the program asks for `HIGHEST`. It
runs in NumPy on host threads, with the bfloat16 rounding done on the
bits, so no compiler decides what it computes: a JAX copy of it, run on
a TPU v5e, read the program's own error for one charge vector and a
single-pass error for three.
"""
from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

KERNELS = ("coulomb", "yukawa")


def _check_kernel(kernel: str):
    if kernel not in KERNELS:
        raise NotImplementedError(f"no reference for kernel {kernel!r}")


def _block_f64(xt, y, qs, kernel, kappa):
    """Contribution of the sources `y` (charges `qs`, (c, C)) to the
    potentials at the target rows `xt`, (R, C)."""
    d = [xt[:, k:k + 1] - y[None, :, k] for k in range(3)]
    r2 = d[0] * d[0] + d[1] * d[1] + d[2] * d[2]
    hit = r2 == 0.0                       # the i == j term is excluded
    r2[hit] = 1.0
    rinv = 1.0 / np.sqrt(r2)
    g = rinv if kernel == "coulomb" else np.exp(-kappa * r2 * rinv) * rinv
    g[hit] = 0.0
    return g @ qs


def direct_f64(points, charges, rows, *, kernel: str, kappa: float = 0.0,
               chunk: int = 2048, workers: int = 0):
    """phi at `rows` in float64.

    points (N, 3); charges (N,) or (N, C); rows: target indices. phi has
    shape (R,) or (R, C) like the charges."""
    _check_kernel(kernel)
    x = np.asarray(points, np.float64)
    q = np.asarray(charges, np.float64)
    single = q.ndim == 1
    q2 = q[:, None] if single else q
    xt = x[np.asarray(rows, np.int64)]

    def part(s):
        return _block_f64(xt, x[s:s + chunk], q2[s:s + chunk], kernel,
                          float(kappa))

    workers = workers or min(16, os.cpu_count() or 1)
    phi = np.zeros((len(xt), q2.shape[1]))
    with ThreadPoolExecutor(workers) as pool:
        for p in pool.map(part, range(0, x.shape[0], chunk)):
            phi += p
    return phi[:, 0] if single else phi


def control(points, charges, rows, *, kernel: str, kappa: float = 0.0,
            chunk: int = 2048, workers: int = 0):
    """The same sum as `direct_f64`, in float32 with `HIGH`-precision
    contractions (see the module docstring)."""
    _check_kernel(kernel)
    x = np.asarray(points, np.float32)
    q = np.asarray(charges, np.float32)
    single = q.ndim == 1
    q2 = q[:, None] if single else q
    qh, ql = _split(q2)
    xt = x[np.asarray(rows, np.int64)]
    kappa = np.float32(kappa)

    def part(s):
        y = x[s:s + chunk]
        d = [xt[:, k:k + 1] - y[None, :, k] for k in range(3)]
        r2 = d[0] * d[0] + d[1] * d[1] + d[2] * d[2]
        hit = r2 == 0.0
        r2[hit] = 1.0
        rinv = np.float32(1.0) / np.sqrt(r2)
        g = rinv if kernel == "coulomb" else np.exp(-kappa * r2 * rinv) * rinv
        g[hit] = 0.0
        gh, gl = _split(g)
        b = slice(s, s + chunk)
        return gh @ qh[b] + gh @ ql[b] + gl @ qh[b]

    workers = workers or min(16, os.cpu_count() or 1)
    phi = np.zeros((len(xt), q2.shape[1]), np.float32)
    with ThreadPoolExecutor(workers) as pool:
        for p in pool.map(part, range(0, x.shape[0], chunk)):
            phi += p
    phi = phi.astype(np.float64)
    return phi[:, 0] if single else phi


def _bf16(a):
    """float32 `a` rounded to bfloat16 (to nearest, ties to even), as
    float32."""
    u = np.ascontiguousarray(a, np.float32).view(np.uint32)
    u = (u + np.uint32(0x7FFF) + ((u >> 16) & np.uint32(1))) \
        & np.uint32(0xFFFF0000)
    return u.view(np.float32)


def _split(a):
    hi = _bf16(a)
    return hi, _bf16(a - hi)
