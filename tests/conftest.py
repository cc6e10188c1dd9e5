"""Test configuration: force an 8-device virtual CPU platform.

Real multi-chip TPU hardware is not available in CI; sharding/parallelism tests run
on `--xla_force_host_platform_device_count=8` CPU devices, which exercises the same
GSPMD partitioner and collective lowering XLA uses on a TPU mesh.

This must run before the first `import jax` anywhere in the test process.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture
def rng():
    import jax

    return jax.random.PRNGKey(0)


@pytest.fixture
def np_rng():
    return np.random.default_rng(0)
