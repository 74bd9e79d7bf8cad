"""The plain reference: direct summation in float64 NumPy, and the control.

`direct_f64` is a copy of the program's float64 oracle
(`core/direct.py:direct_oracle_f64`), widened to several charge vectors
at once: phi[r, c] = sum_j G(x_r - x_j) q[j, c] over every source j != r,
in free space. Only the sampled target rows are evaluated, against every
source, on host threads.

`control` is the same sum put in the program's place one precision step
down: float32 pair terms whose contraction with the charges is taken as
three bfloat16 products (hi*hi + hi*lo + lo*hi, what XLA's `HIGH`
precision computes on a TPU), where the program asks for `HIGHEST`. The
split is written out, so the control reads the same on every platform.
"""
from __future__ import annotations

import functools
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
            block: int = 8192):
    """The same sum as `direct_f64`, in float32 with `HIGH`-precision
    contractions, on the default JAX device (see the module docstring)."""
    import jax
    import jax.numpy as jnp

    _check_kernel(kernel)
    x = np.asarray(points, np.float32)
    q = np.asarray(charges, np.float32)
    single = q.ndim == 1
    q2 = q[:, None] if single else q
    n, nc = q2.shape
    pad = (-n) % block
    # Padded sources carry zero charge and sit far from every target.
    far = np.full((pad, 3), 1e6, np.float32)
    xs = np.concatenate([x, far]).reshape(-1, block, 3)
    qs = np.concatenate([q2, np.zeros((pad, nc), np.float32)]).reshape(
        -1, block, nc)
    rows = np.asarray(rows, np.int64)
    fn = _control_fn(kernel, float(kappa))
    phi = np.asarray(jax.device_get(fn(jnp.asarray(x[rows]), jnp.asarray(xs),
                                       jnp.asarray(qs))), np.float64)
    return phi[:, 0] if single else phi


def _split(a):
    import jax.numpy as jnp

    hi = a.astype(jnp.bfloat16).astype(jnp.float32)
    lo = (a - hi).astype(jnp.bfloat16).astype(jnp.float32)
    return hi, lo


def _dot_high(a, b):
    """a @ b with bfloat16 hi/lo splits and three exact products."""
    import jax
    import jax.numpy as jnp

    ah, al = _split(a)
    bh, bl = _split(b)
    hp = jax.lax.Precision.HIGHEST
    return (jnp.dot(ah, bh, precision=hp) + jnp.dot(ah, bl, precision=hp)
            + jnp.dot(al, bh, precision=hp))


@functools.lru_cache(maxsize=None)
def _control_fn(kernel, kappa):
    import jax
    import jax.numpy as jnp

    def body(xt, xs, qs):
        def step(phi, blk):
            y, qb = blk
            d = xt[:, None, :] - y[None, :, :]
            r2 = jnp.sum(d * d, axis=-1)
            hit = r2 == 0.0
            rinv = jax.lax.rsqrt(jnp.where(hit, 1.0, r2))
            g = rinv if kernel == "coulomb" else \
                jnp.exp(-kappa * r2 * rinv) * rinv
            g = jnp.where(hit, 0.0, g)
            return phi + _dot_high(g, qb), None

        init = jnp.zeros((xt.shape[0], qs.shape[-1]), jnp.float32)
        phi, _ = jax.lax.scan(step, init, (xs, qs))
        return phi

    return jax.jit(body)
