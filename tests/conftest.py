import numpy as np
import pytest


@pytest.fixture
def rng():
    return np.random.default_rng(1234)


@pytest.fixture
def no_implicit_transfers():
    """Factory for a `jax.transfer_guard("disallow")` context.

    Yields the context-manager factory (not an active guard) so tests
    can build plans / warm caches OUTSIDE the guard and wrap only the
    steady-state step loop. On the CPU backend the guard fires for
    implicit host-to-device uploads but lets device-to-host reads pass
    (shared buffers); GPU/TPU runs of the same suite enforce both
    directions, and the HLO `count_transfers` tests pin the CPU-side
    d2h equivalent.
    """
    from repro.lint.runtime import no_implicit_transfers as guard

    yield guard


@pytest.fixture
def x64():
    """Enable f64 for a test and restore the previous mode afterwards."""
    import jax

    prev = jax.config.jax_enable_x64
    jax.config.update("jax_enable_x64", True)
    yield
    jax.config.update("jax_enable_x64", prev)
