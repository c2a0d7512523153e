"""Test bootstrap: force the CPU backend with a virtual 8-device host
platform BEFORE jax is imported anywhere, so multi-device/sharding code
paths run without TPU hardware (SURVEY.md §4 idiom 4; the driver separately
dry-runs the multi-chip path via __graft_entry__.dryrun_multichip)."""

import os

# Unit tests always run on the virtual 8-device CPU host platform, also on a
# machine that holds a chip (the chip is chip_smoke.py's, one process).
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: multi-minute tests outside the tier-1 budget "
        "(run with `pytest -m slow` or ci/run.sh's full stage_unit)")
