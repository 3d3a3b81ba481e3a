"""Test harness: run JAX on a virtual 8-device CPU mesh.

Multi-chip hardware is not available in CI; sharding correctness is
validated on host-platform virtual devices (the driver separately
dry-run-compiles the multi-chip path via __graft_entry__.dryrun_multichip).
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"  # before jax is imported
# the suite validates the XLA kernel ON the cpu backend — keep the
# platform-aware host-matcher dispatch out of the way except in the
# tests that opt back in (test_host_dispatch)
os.environ.setdefault("EMQX_TPU_CPU_KERNEL", "xla")
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import random

import pytest


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: long-running soaks excluded from tier-1 (-m 'not slow')")


@pytest.fixture
def rng():
    return random.Random(0xE19)
