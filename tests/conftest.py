"""Shared fixtures.  NOTE: no XLA device-count flags here by design —
smoke tests and benches must see the real (single) CPU device; only
launch/dryrun.py forces 512 host devices (in its own process).

The suite always runs on the CPU, also on a host with a TPU: a test
process must never take a chip that only one process may hold, and
child processes inherit the setting.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402
import numpy as np
import pytest


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(0)


@pytest.fixture(scope="session")
def key():
    return jax.random.PRNGKey(0)


def assert_trees_close(a, b, rtol=1e-5, atol=1e-5):
    import jax
    for la, lb in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_allclose(np.asarray(la), np.asarray(lb),
                                   rtol=rtol, atol=atol)
