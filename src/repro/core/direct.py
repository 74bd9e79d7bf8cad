"""O(N^2) direct summation (Eq. 1) — the paper's comparison baseline.

Blocked over source chunks with lax.scan so memory stays O(NT * chunk).
On the GPU the paper computes this as a single launch of the batch-cluster
direct-sum kernel with one batch of all targets and one cluster of all
sources; `direct_sum_kernel` reproduces exactly that configuration through
the same ops entry point used by the treecode.

Space/params protocol v2: pass `space=PeriodicBox(...)` for the
minimum-image direct sum (the f64 oracle the periodic treecode is
validated against) and `params=` for traced kernel parameters.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.potentials import Kernel
from repro.core.space import FREE as _FREE
from repro.kernels import ops


@functools.partial(jax.jit,
                   static_argnames=("kernel", "space", "source_chunk"))
def direct_sum(
    targets: jnp.ndarray,  # (NT, 3)
    sources: jnp.ndarray,  # (NS, 3)
    charges: jnp.ndarray,  # (NS,)
    params=None,
    *,
    kernel: Kernel,
    space=_FREE,
    source_chunk: int = 2048,
) -> jnp.ndarray:
    """phi (NT,) by blocked direct summation; the i == j singular term is
    excluded by the kernel's r2 > 0 mask (treecode convention)."""
    ns = sources.shape[0]
    pad = (-ns) % source_chunk
    src = jnp.pad(sources, ((0, pad), (0, 0)))
    q = jnp.pad(charges, (0, pad))
    src = src.reshape(-1, source_chunk, 3)
    q = q.reshape(-1, source_chunk)

    def step(phi, args):
        s, qs = args
        # (NT, chunk), masked at r2 == 0; minimum-image per pair when the
        # space is periodic (the exact convention, no interpolation).
        g = kernel.pairwise(targets, s, params, space)
        # Padded sources may coincide at the origin with r2 > 0 against real
        # targets, so their contribution is removed via qs == 0.
        pot = jnp.dot(g, qs, precision=jax.lax.Precision.HIGHEST)
        return phi + pot, None

    phi0 = jnp.zeros(targets.shape[0], targets.dtype)
    phi, _ = jax.lax.scan(step, phi0, (src, q))
    return phi


def direct_oracle_f64(points, charges, *, kernel: Kernel, params=None,
                      space=_FREE, chunk: int = 1024, targets=None):
    """(phi, F) by float64 NumPy direct summation — the accuracy oracle.

    Host-side f64 regardless of the jax x64 mode, so refit/skin
    trajectories can be validated against a true double-precision
    envelope from inside f32 test processes and benchmarks (the
    acceptance check of drift-budget v2). Supports the built-in
    coulomb/yukawa kernels (the analytic dG/dr2 is needed for forces);
    minimum-image displacements under a periodic `space`.

    `targets` (optional index array) evaluates only those rows, against
    every source: the sampled reference for sizes where all pairs are
    out of reach. The result rows follow the order of `targets`.
    """
    x = np.asarray(points, np.float64)
    q = np.asarray(charges, np.float64)
    rows = np.arange(x.shape[0]) if targets is None \
        else np.asarray(targets, np.int64)
    xt, qt = x[rows], q[rows]
    name = kernel.name
    if name == "yukawa":
        p = kernel.normalize_params(params) if params is not None \
            else kernel.params
        (kappa,) = (float(v) for v in p)
    elif name != "coulomb":
        raise NotImplementedError(
            f"direct_oracle_f64 supports coulomb/yukawa, got {name!r}")
    periodic = getattr(space, "periodic", False)
    phi = np.zeros(len(rows))
    force = np.zeros((len(rows), 3))
    for s in range(0, x.shape[0], chunk):
        y, qs = x[s:s + chunk], q[s:s + chunk]
        # per-coordinate (targets, chunk) displacement planes
        d = [xt[:, k:k + 1] - y[None, :, k] for k in range(3)]
        if periodic:
            d = [dk - Lk * np.round(dk / Lk)
                 for dk, Lk in zip(d, space.lengths)]
        r2 = d[0] * d[0] + d[1] * d[1] + d[2] * d[2]
        hit = r2 == 0.0                      # the i == j term is excluded
        r2[hit] = 1.0
        rinv = 1.0 / np.sqrt(r2)
        if name == "coulomb":
            g = rinv
            dgr = g * rinv * rinv            # -2 dG/dr2 = 1/r^3
        else:
            r = r2 * rinv
            g = np.exp(-kappa * r) * rinv
            dgr = g * (kappa * r + 1.0) * rinv * rinv
        g[hit] = 0.0
        dgr[hit] = 0.0
        phi += g @ qs
        # F_i = -q_i grad_i phi = q_i sum_j q_j (-2 dG/dr2) d_ij
        w = dgr * qs
        for k in range(3):
            force[:, k] += np.einsum("nc,nc->n", w, d[k])
    force *= qt[:, None]
    return phi, force


def direct_sum_kernel(
    targets: jnp.ndarray,
    sources: jnp.ndarray,
    charges: jnp.ndarray,
    params=None,
    *,
    kernel: Kernel,
    space=_FREE,
    backend: str = "auto",
) -> jnp.ndarray:
    """Direct sum as ONE batch-cluster kernel call (paper's GPU reference).

    One batch = all targets, one cluster = all sources (Sec. 4: "the direct
    sum is computed by one launch of the batch-cluster direct sum kernel").
    """
    idx = jnp.zeros((1, 1), jnp.int32)
    phi = ops.batch_cluster_eval(
        idx, targets[None], sources[None], charges[None], params,
        kernel=kernel, space=space, backend=backend)
    return phi[0]
