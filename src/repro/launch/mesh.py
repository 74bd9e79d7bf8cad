"""Production mesh construction.

Mesh shapes (TPU v5e):
  - single pod:  (16, 16)    axes ("data", "model")    = 256 chips
  - multi pod:   (2, 16, 16) axes ("pod", "data", "model") = 512 chips

Functions (not module constants) so importing never touches jax device
state; the dry-run sets XLA_FLAGS before any jax import.
"""
from __future__ import annotations

import jax


def auto_mesh(shape, axes):
    """`jax.make_mesh` with Auto axis types (its default is Explicit)."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return auto_mesh(shape, axes)


def make_host_mesh(model_axis: int = 1):
    """Small mesh over whatever devices exist (CPU tests / examples)."""
    n = jax.device_count()
    data = n // model_axis
    return auto_mesh((data, model_axis), ("data", "model"))
