"""Pallas TPU kernel for the modified charges q_hat (Eq. 12 via 14/15).

The paper's two preprocessing kernels are fused into one Pallas kernel:
stage 1 (Eq. 14) computes the intermediate q_tilde_j = q_j / (D_j1 D_j2 D_j3)
where D_jl are the barycentric denominators, and stage 2 (Eq. 15)
accumulates the rank-1 tensor products into q_hat. On the GPU the paper
parallelizes stage 1 over source particles and stage 2 over Chebyshev
points, with reductions over threads; on TPU both stages become one block
program per (cluster, particle-tile):

  - barycentric term rows  w_k / (y - s_k)  are built on the VPU with the
    exact-hit (removable singularity) handling of Sec. 2.3;
  - the rows are laid out (n+1, MT), particles on the lanes, and the
    3-way tensor contraction  q_hat[k1,k2,k3] = sum_j t1 t2 t3 q~  runs
    as one MXU matmul  ( (n+1) x MT ) @ ( MT x (n+1) )  per k1;
  - particle tiles accumulate into the revisited (1, n+1, n+1, n+1)
    output block, flattened (k3 fastest) outside the kernel.

Clusters at the same tree level have similar particle counts, so the host
groups clusters level-by-level and calls this kernel once per level with a
static padded particle count (padding has q = 0 and contributes nothing).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import cheby


def _bary_rows(y, s, w):
    """Barycentric terms laid out (n+1, MT): the transpose of
    `cheby.bary_terms` (same Sec. 2.3 exact-hit rule), so the particle
    axis stays on the lanes and nothing is transposed in-kernel.

    y (1, MT) one coordinate row, s (n+1, 1) mapped nodes, w (n+1, 1)."""
    d = y - s
    hit = d == 0.0
    any_hit = jnp.any(hit, axis=0, keepdims=True)
    t = jnp.where(any_hit, hit.astype(y.dtype), w / jnp.where(hit, 1.0, d))
    return t, jnp.sum(t, axis=0, keepdims=True)


def _body(pts_ref, q_ref, nodes_ref, w_ref, out_ref, *, degree: int):
    @pl.when(pl.program_id(1) == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    w = w_ref[...]                                   # (n1, 1)
    # per-dimension barycentric rows (n1, MT); nodes_ref[0, k] is (n1, 1)
    t1, d1 = _bary_rows(pts_ref[0, 0:1, :], nodes_ref[0, 0], w)
    t2, d2 = _bary_rows(pts_ref[0, 1:2, :], nodes_ref[0, 1], w)
    t3, d3 = _bary_rows(pts_ref[0, 2:3, :], nodes_ref[0, 2], w)
    den = d1 * d2 * d3                               # (1, MT)
    # guard f32 cancellation of the denominator on padded slots (q == 0)
    qt = jnp.where(den != 0.0,
                   q_ref[0] / jnp.where(den != 0.0, den, 1.0),
                   0.0)                              # stage 1 (Eq. 14)
    r3 = t3 * qt                                     # (n1, MT)
    # stage 2 (Eq. 15): one (n1, MT) x (MT, n1) MXU matmul per k1 slab,
    # written to the k1-th leading slice of the (n1, n1, n1) block.
    for k1 in range(degree + 1):
        out_ref[0, k1] += jax.lax.dot_general(
            t2 * t1[k1:k1 + 1, :], r3,
            dimension_numbers=(((1,), (1,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=out_ref.dtype)    # (k2, k3)


def modified_charges_pallas(
    pts: jnp.ndarray,    # (C, 3, m) coordinate-major cluster particles
    q: jnp.ndarray,      # (C, m) charges, 0 on padding
    nodes: jnp.ndarray,  # (C, 3, n+1) mapped per-dimension Chebyshev nodes
    degree: int,
    *,
    particle_tile: int = 512,
    interpret: bool = False,
) -> jnp.ndarray:
    """q_hat (C, (n+1)^3) for every cluster, k3 fastest."""
    c, _, m = pts.shape
    n1 = degree + 1
    mt = min(particle_tile, m)
    if m % mt:
        raise ValueError(f"m={m} must be a multiple of particle tile {mt}")
    w = cheby.bary_weights_1d(degree, pts.dtype)

    kwargs = {}
    if not interpret:
        kwargs["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"))

    # Unit/column axes keep every block's last two dims full-width (the
    # Mosaic tiling rule) and the output is never flattened in-kernel.
    qhat = pl.pallas_call(
        functools.partial(_body, degree=degree),
        grid=(c, m // mt),
        in_specs=[
            pl.BlockSpec((1, 3, mt), lambda ci, ti: (ci, 0, ti)),
            pl.BlockSpec((1, 1, mt), lambda ci, ti: (ci, 0, ti)),
            pl.BlockSpec((1, 3, n1, 1), lambda ci, ti: (ci, 0, 0, 0)),
            pl.BlockSpec((n1, 1), lambda ci, ti: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, n1, n1, n1),
                               lambda ci, ti: (ci, 0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((c, n1, n1, n1), pts.dtype),
        interpret=interpret,
        name="modified_charges",
        **kwargs,
    )(pts, q[:, None, :], nodes[..., None], w[:, None])
    return qhat.reshape(c, n1 * n1 * n1)
