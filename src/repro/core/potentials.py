"""Interaction kernels G(x, y) (Eq. 2) in a kernel-independent registry.

The BLTC is kernel-independent: it only ever *evaluates* G. Each kernel is
a pure function of the squared distance (plus parameters), which is the
form both the Pallas kernels and the jnp oracles consume. Self-interaction
and padded-slot contributions are removed by the `r2 > 0` mask, matching
the treecode convention of excluding the singular i == j term.

Kernel protocol v2 (space-aware, traced parameters):

  - `of_r2(r2, params)` receives `params` as a pytree whose *leaves may be
    traced arrays*. The `Kernel` object itself stays a frozen (hashable)
    dataclass and rides through `jax.jit` as a static argument, while the
    parameter VALUES flow through the executors as ordinary traced inputs
    — so a Yukawa `kappa` sweep over an unchanged plan hits the compile
    cache instead of recompiling per value.
  - `params` on the Kernel holds hashable DEFAULTS (used when a caller
    passes no explicit values, preserving the v1 call style
    ``kernel(r2)`` / ``kernel.pairwise(x, y)``).
  - `param_names` optionally names the entries of a tuple-structured
    `params`, letting user-facing APIs accept ``{"kappa": 0.7}`` dicts.
  - pairwise evaluation takes displacements from an explicit `Space`
    (see `repro.core.space`): free-space differences by default,
    minimum-image differences under `PeriodicBox`.
"""
from __future__ import annotations

import dataclasses
from typing import Callable

import jax
import jax.numpy as jnp

from repro.core.space import FREE as _FREE


def _hashable(tree):
    """Normalize a params pytree into a hashable default (tuples, floats)."""
    if isinstance(tree, dict):
        raise TypeError("use param_names + a tuple for named defaults "
                        "(dict params are accepted by with_params)")
    return jax.tree.map(
        lambda v: float(v) if jnp.ndim(v) == 0 else tuple(map(float, v)),
        tree)


@dataclasses.dataclass(frozen=True)
class Kernel:
    """A smooth, non-oscillatory interaction kernel.

    Attributes:
      name: registry name.
      of_r2: (r2, params) -> G; must be finite for r2 > 0. Values at
        r2 == 0 are ignored (masked by callers). `params` may carry
        traced leaves.
      params: hashable default parameters (e.g. Yukawa kappa). The
        executors lift these into traced arrays at plan build, so the
        defaults never enter a compile-cache key on the solver path.
      param_names: optional names aligned with a tuple `params`, enabling
        ``with_params({"kappa": 0.7})`` and the `TreecodeConfig`
        ``kernel_params=`` dict form.
    """

    name: str
    of_r2: Callable
    params: tuple = ()
    param_names: tuple = ()

    def __call__(self, r2: jnp.ndarray, params=None) -> jnp.ndarray:
        """Masked evaluation: G(r) for r2 > 0, exactly 0 at r2 == 0."""
        if params is None:
            params = self.params
        safe = jnp.where(r2 > 0.0, r2, 1.0)
        return jnp.where(r2 > 0.0, self.of_r2(safe, params), 0.0)

    def normalize_params(self, params):
        """Dict params -> the tuple structure `of_r2` expects."""
        if params is None:
            return self.params
        if isinstance(params, dict):
            if not self.param_names:
                raise ValueError(
                    f"kernel {self.name!r} declares no param_names; pass "
                    f"params with the pytree structure of_r2 expects")
            unknown = set(params) - set(self.param_names)
            if unknown:
                raise ValueError(
                    f"kernel {self.name!r} has no parameter(s) "
                    f"{sorted(unknown)}; have {list(self.param_names)}")
            defaults = dict(zip(self.param_names, self.params))
            defaults.update(params)
            return tuple(defaults[k] for k in self.param_names)
        return params

    def with_params(self, params) -> "Kernel":
        """New kernel with different hashable defaults (dict or pytree)."""
        return dataclasses.replace(
            self, params=_hashable(self.normalize_params(params)))

    def stripped(self) -> "Kernel":
        """Default-free copy: THE static compile-cache key on the solver
        path (two kernels differing only in default params share it)."""
        if not self.params:
            return self
        return dataclasses.replace(self, params=())

    def pairwise(self, x: jnp.ndarray, y: jnp.ndarray, params=None,
                 space=_FREE) -> jnp.ndarray:
        """G(x_i, y_j) for x (..., nx, 3), y (..., ny, 3) -> (..., nx, ny).

        Displacements come from `space` (minimum-image under a
        `PeriodicBox`)."""
        d = space.displacement(x[..., :, None, :], y[..., None, :, :])
        return self(jnp.sum(d * d, axis=-1), params)

    def pairwise_matmul(self, x: jnp.ndarray, y: jnp.ndarray, params=None,
                        space=_FREE) -> jnp.ndarray:
        """G via r^2 = |x|^2 + |y|^2 - 2 x.y — the cross term is a matmul,
        so the distance computation runs on the MXU instead of the VPU
        (beyond-paper §Perf optimization). Safe for MAC-separated
        target/cluster pairs (the approximation kernel); the direct-sum
        kernel keeps the cancellation-free difference form. Minimum-image
        displacements do not factor through a Gram matrix, so periodic
        spaces fall back to the difference form."""
        if getattr(space, "periodic", False):
            return self.pairwise(x, y, params, space)
        xy = jnp.einsum("...nd,...md->...nm", x, y,
                        precision=jax.lax.Precision.HIGHEST)
        x2 = jnp.sum(x * x, axis=-1)[..., :, None]
        y2 = jnp.sum(y * y, axis=-1)[..., None, :]
        return self(jnp.maximum(x2 + y2 - 2.0 * xy, 0.0), params)


def _coulomb(r2, params):
    del params
    return jnp.reciprocal(jnp.sqrt(r2))


def _yukawa(r2, params):
    (kappa,) = params
    r = jnp.sqrt(r2)
    return jnp.exp(-kappa * r) / r


def coulomb() -> Kernel:
    """G(x,y) = 1/|x-y| (Eq. 2, left)."""
    return Kernel("coulomb", _coulomb)


def yukawa(kappa: float = 0.5) -> Kernel:
    """G(x,y) = exp(-kappa |x-y|)/|x-y| (Eq. 2, right)."""
    return Kernel("yukawa", _yukawa, (float(kappa),), ("kappa",))


_REGISTRY = {"coulomb": coulomb, "yukawa": yukawa}


def register_kernel(name: str, factory: Callable[..., Kernel],
                    overwrite: bool = False) -> None:
    """Register a user kernel factory under `name`.

    The factory is called as ``factory(**params)`` and must return a
    `Kernel`. Once registered the name is accepted anywhere a built-in
    kernel name is (e.g. ``TreecodeConfig(kernel="my_kernel")``), and
    ``TreecodeConfig(kernel_params={...})`` forwards keyword parameters
    to the factory for ANY registered name. The treecode only ever
    *evaluates* G, so any smooth non-oscillatory kernel works at the
    same MAC/degree accuracy tradeoffs.
    """
    if name in _REGISTRY and not overwrite:
        raise KeyError(f"kernel {name!r} already registered "
                       "(pass overwrite=True to replace)")
    _REGISTRY[name] = factory


def registered_kernels() -> tuple:
    return tuple(sorted(_REGISTRY))


def get_kernel(name: str, **params) -> Kernel:
    if name not in _REGISTRY:
        raise KeyError(f"unknown kernel {name!r}; have {sorted(_REGISTRY)}")
    kern = _REGISTRY[name](**params)
    if not isinstance(kern, Kernel):
        raise TypeError(f"kernel factory {name!r} returned "
                        f"{type(kern).__name__}, expected Kernel")
    return kern


def resolve_kernel(kernel, **params) -> Kernel:
    """Accept either a registry name or a ready `Kernel` instance.

    `Kernel` is a frozen dataclass (hashable, compared by fields), so a
    user-constructed instance is jit-stable: passing an equal kernel to a
    jitted entry point hits the compile cache.
    """
    if isinstance(kernel, Kernel):
        if params:
            return kernel.with_params(params)
        return kernel
    if isinstance(kernel, str):
        return get_kernel(kernel, **params)
    raise TypeError(f"kernel must be a name or Kernel, got "
                    f"{type(kernel).__name__}")


# ---------------------------------------------------------------------------
# Traced-parameter packing (shared by the Pallas executors)
# ---------------------------------------------------------------------------
#
# The Pallas kernels receive parameters as ONE flat scalar-prefetch vector
# (values in SMEM before the body runs) plus a static spec describing how
# to rebuild the pytree. The spec is hashable, so it rides in the jit key
# next to the (stripped) kernel while the values stay traced.


def pack_params(params):
    """Flatten a params pytree into (vector, static spec).

    Returns (vec, spec): vec a (1, max(P, 1)) float array (padded with one
    zero when the tree is empty so the kernel signature is uniform), and
    spec = (treedef, shapes) — hashable, consumed by `unpack_params`.
    """
    leaves, treedef = jax.tree.flatten(params)
    shapes = tuple(tuple(jnp.shape(leaf)) for leaf in leaves)
    # lint: disable=TS004 — branches on the pytree STRUCTURE (a host
    # list's emptiness), which is static under jit; the leaves themselves
    # are never coerced.
    if leaves:
        vec = jnp.concatenate(
            [jnp.ravel(jnp.asarray(leaf)) for leaf in leaves])
    else:
        vec = jnp.zeros((1,))
    return vec[None, :], (treedef, shapes)


def unpack_params(read, spec):
    """Rebuild the params pytree from scalar reads.

    `read(i)` must return the i-th packed scalar (an SMEM ref read inside
    a Pallas body, or an indexed array element on the jnp path)."""
    treedef, shapes = spec
    leaves, offset = [], 0
    for shape in shapes:
        size = 1
        for s in shape:
            size *= s
        vals = [read(offset + i) for i in range(size)]
        leaf = vals[0] if shape == () else jnp.stack(vals).reshape(shape)
        leaves.append(leaf)
        offset += size
    return jax.tree.unflatten(treedef, leaves)
