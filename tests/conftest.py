"""Shared fixtures.  NOTE: no XLA_FLAGS here — tests run on the single
real CPU device; only launch/dryrun.py fakes 512 devices."""
import jax
import numpy as np
import pytest


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(0)


@pytest.fixture(scope="session")
def key():
    return jax.random.PRNGKey(0)
