"""Test configuration: the CPU backend with an 8-device virtual mesh.

Mirrors the reference's trick of running the full multi-node suite in one
process (tests/lib/UnitTestFabric.h): multi-chip sharding is validated on a
virtual CPU mesh. Tests pin the CPU whatever the machine holds; what runs on
a chip is chip_smoke.py and perfbench/run.py, never pytest.
"""

import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("JAX_ENABLE_X64", "0")


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: long soaks excluded from the tier-1 run")
