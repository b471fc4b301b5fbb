"""Test configuration: force an 8-device virtual CPU mesh.

Multi-chip hardware is unavailable in CI; sharding tests run on XLA's
host-platform device virtualization (the same mechanism the driver's
dryrun_multichip uses).

Tests are CPU-only: ``JAX_PLATFORMS=cpu`` is set here, before jax is
imported anywhere, and that is all it takes. (tests/test_chip_compile.py
compiles for a DESCRIBED chip through the installed TPU compiler; it
still runs nothing off the CPU.)
"""

import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()
os.environ["JAX_PLATFORMS"] = "cpu"

import numpy as np
import pytest


@pytest.fixture
def rng():
    return np.random.default_rng(1234)
