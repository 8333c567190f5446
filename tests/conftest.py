"""Test configuration: force an 8-device virtual CPU platform before JAX init.

Mirrors the reference's "tests need no hardware" strategy (SURVEY.md §4): the
reference runs routing/scheduling tests against mock engines and in-memory
stores; here every sharding-aware test runs on a virtual 8-device CPU mesh so
multi-chip code paths (tp/dp/pp shardings, collectives) execute in CI without
TPUs.
"""

import os

# Must be set before the first `import jax` anywhere in the test process.
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()
if not os.environ.get("DYNTPU_TEST_ON_TPU"):
    os.environ["JAX_PLATFORMS"] = "cpu"

import pytest  # noqa: E402


@pytest.fixture(scope="session")
def cpu_mesh_devices():
    import jax

    devs = jax.devices()
    assert len(devs) >= 8, f"expected >=8 virtual devices, got {len(devs)}"
    return devs
