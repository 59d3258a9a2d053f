"""Test configuration: force an 8-device virtual CPU platform.

Multi-chip sharding (data x spatial meshes) is tested on virtual CPU
devices, mirroring how the driver dry-runs the multi-chip path
(``xla_force_host_platform_device_count``).
"""

import os

# Overwrite, not setdefault: whatever the caller's environment names,
# tests run on virtual CPU devices so the sharded paths can be exercised
# without a pod (the chip is reached only through chip_smoke.py).
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

# jax reads the variable when it is first imported; if a plugin imported it
# before this file ran, the config must be updated directly too.
import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture
def rng():
    return np.random.default_rng(1234)


@pytest.fixture
def forbid_host_transfers():
    """The runtime guard as a fixture: a context-manager factory.
    ``with forbid_host_transfers() as stats: ...`` raises GuardViolation
    on any implicit device->host pull inside the scope (explicit
    jax.device_get stays sanctioned)."""
    from raft_ncup_tpu.analysis.guards import forbid_host_transfers as fht

    return fht


@pytest.fixture
def max_recompiles():
    """Compile-budget guard as a fixture: ``with max_recompiles(1): ...``
    raises GuardViolation when the scope compiles more than n times."""
    from raft_ncup_tpu.analysis.guards import max_recompiles as mr

    return mr


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "reference: tests that import the read-only reference repo"
    )
    config.addinivalue_line("markers", "slow: long-running tests")
