# Must run before the first jax operation in the test process: the
# shard-engine parity suite is exercised with REPRO_FORCE_DEVICES=4, which
# splits the host CPU into N virtual devices.
from repro.utils.force_devices import apply_force_devices
apply_force_devices()

import numpy as np
import pytest
import jax


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: compiles production-size programs (tens of s)")


@pytest.fixture
def rng_np():
    return np.random.default_rng(0)


@pytest.fixture
def key():
    return jax.random.PRNGKey(0)


@pytest.fixture(autouse=True, scope="module")
def _clear_jax_caches():
    """XLA-CPU's JIT accumulates dylib symbols across hundreds of
    compilations and eventually fails with 'Failed to materialize symbols'
    in long single-process runs; clearing compiled-function caches between
    test modules keeps the full suite stable."""
    yield
    jax.clear_caches()
