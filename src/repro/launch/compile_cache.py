"""JAX's persistent compile cache, placed from outside or at a fixed path.

Entry points call `enable_compile_cache()` first, before any compile:

  - with ``JAX_COMPILATION_CACHE_DIR`` set, JAX reads the variable itself
    and nothing is set here;
  - otherwise the cache goes to ``.jax_cache/`` at the root of this
    checkout, resolved from this file's own path: the same directory on
    every run, so a later run finds what an earlier one compiled.

Library modules never call it on import, and tests never call it.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: ``<checkout>/.jax_cache`` (this file is ``<checkout>/src/repro/launch/``).
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compile cache on; returns its directory."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
