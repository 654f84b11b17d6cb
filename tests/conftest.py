"""Test harness: force a virtual 8-device CPU mesh before JAX imports.

Mirrors the reference's local-cluster distribution testing
(test/SparkSuite.scala:8-50 spins local[4]): no real pod, but the sharding
/ collective paths are exercised for real across 8 XLA host devices.
"""

import os

# The suite runs on the CPU (the driver also sets JAX_PLATFORMS=cpu);
# jax.config.update below pins it for direct pytest runs too.  XLA_FLAGS
# is read lazily at CPU-client init, so setting it here works.
import re

flags = os.environ.get("XLA_FLAGS", "")
flags = re.sub(r"--xla_force_host_platform_device_count=\d+", "", flags)
os.environ["XLA_FLAGS"] = (
    flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_x64", True)

assert jax.device_count() == 8, jax.devices()

import numpy as np  # noqa: E402
import pytest  # noqa: E402

from mosaic_tpu.resilience.testing import (fault_plan,  # noqa: E402,F401
                                           no_faults)


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(42)
