"""Test configuration.

Tests run on a virtual 8-device CPU mesh so multi-chip sharding paths are
exercised without TPU hardware. Env vars must be set before jax imports.
"""
import os
import sys

# Tests run on the virtual CPU mesh, whatever the ambient platform.
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402

jax.config.update("jax_default_device", jax.devices("cpu")[0])


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: long-running suites (process-fleet spawns) — deselected "
        "by the tier-1 run's -m 'not slow'; `make fleet-proc-smoke` "
        "runs them explicitly")


def cpu_devices(n: int = 8):
    devs = jax.devices("cpu")
    return devs[:n] if len(devs) >= n else None
