"""Test environment: JAX on a virtual 8-device CPU mesh.

Tests exercise the same ``pjit``/sharding paths as a v5e-8 slice
(SURVEY.md §4) but on CPU: the platform comes from the environment and
the device count from ``jax_num_cpu_devices``, both fixed before the
first backend exists.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402
import pytest  # noqa: E402

jax.config.update("jax_num_cpu_devices", 8)
assert len(jax.devices()) == 8, jax.devices()


@pytest.fixture(scope="session")
def cpu_peaks():
    """Peak rates for tests that want utilization ratios on the CPU: the
    device telemetry layer refuses a device kind it has no row for, so a
    test states what it divides by (arbitrary round numbers — the ratios
    are exercised, not believed)."""
    from tpumlops.server.device_telemetry import DevicePeaks

    return DevicePeaks(
        kind="test-cpu", flops_per_s=1e12, hbm_bytes_per_s=1e11,
        hbm_bytes=16 * 2**30, source="test",
    )
