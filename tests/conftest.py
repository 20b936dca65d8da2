"""Test configuration: force an 8-device virtual CPU mesh.

Multi-chip hardware is not available in CI; all sharding/mesh tests run
on ``xla_force_host_platform_device_count=8`` CPU devices, per the
repo's test strategy (SURVEY.md §4's "fake topology backend" gap in the
reference).  Must run before the first ``import jax``.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture(scope="session")
def cpu_devices():
    devices = jax.devices()
    # >= 2 proves the forced virtual mesh is live; the default CI run
    # gets 8, `make overlap` runs its TP=2 smoke under an explicit 4
    assert len(devices) >= 2
    return devices
