"""Inputs of the benchmark's configurations, made from a seed.

Copied from the program's own sound generator (the paper's uniform cube
of `chip_smoke._cloud`) so that a change to the program cannot move it. `stream` separates
independent draws from one seed.
"""
from __future__ import annotations

import numpy as np


def rng(seed: int, stream: int) -> np.random.Generator:
    """A generator for one stream of one seed (any seed >= 0, 64 bits)."""
    return np.random.default_rng([int(seed), int(stream)])


def uniform_cloud(n: int, seed: int, stream: int = 0):
    """The paper's test setting (Sec. 4): points uniform in [-1, 1]^3,
    charges uniform in [-1, 1], float32."""
    g = rng(seed, stream)
    x = g.uniform(-1.0, 1.0, (n, 3)).astype(np.float32)
    q = g.uniform(-1.0, 1.0, n).astype(np.float32)
    return x, q
