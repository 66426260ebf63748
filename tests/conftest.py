import os
import sys

# Tests run on CPU, but the scale-out suite needs a real (simulated)
# device mesh: force 8 host CPU devices BEFORE jax initializes its
# backend, merged with any XLA_FLAGS the caller already set.
os.environ.setdefault("JAX_PLATFORMS", "cpu")
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import pytest


@pytest.fixture
def key():
    return jax.random.PRNGKey(0)

