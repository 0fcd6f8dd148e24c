"""Test configuration: force an 8-device virtual CPU platform.

Multi-chip hardware isn't available in CI; all sharding tests run against
8 virtual CPU devices (the driver separately dry-runs the multichip path via
__graft_entry__.dryrun_multichip).

Note: something may have imported jax before this file runs, so setting
JAX_PLATFORMS via os.environ here can be too late — but backends
initialize lazily, so a config update before first device use still wins.
"""

import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")
xla_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in xla_flags:
    os.environ["XLA_FLAGS"] = (
        xla_flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402
import pytest  # noqa: E402

jax.config.update("jax_platforms", "cpu")


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: long-running; tier-1 deselects these (-m 'not slow')")


@pytest.fixture
def rec():
    """The process-wide flight recorder, on and empty; restored afterwards."""
    from jepsen_tpu.obs.recorder import RECORDER
    was = RECORDER.enabled
    RECORDER.enable()
    RECORDER.clear()
    yield RECORDER
    RECORDER.enabled = was
    RECORDER.clear()
