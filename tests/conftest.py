"""Test fixtures: force an 8-device virtual CPU platform before jax imports.

Mirrors the reference test strategy (reference test/run_tests.sh boots a
2-worker local Spark Standalone cluster): we test multi-chip sharding with
multiple *virtual* devices on one host, and multi-node behavior with
multiple executor *processes* on one host.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

# the env above is read at import; say it again in the config so a test
# session started with jax already configured is still on the CPU
jax.config.update("jax_platforms", "cpu")

import pytest  # noqa: E402


@pytest.fixture(scope="session")
def eight_devices():
    devs = jax.devices()
    assert len(devs) == 8, f"expected 8 virtual devices, got {len(devs)}"
    return devs
