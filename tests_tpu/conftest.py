"""TPU-device test lane.

The main suite (tests/) pins an 8-device virtual CPU mesh; nothing there
ever exercises real-device numerics, so a TPU-specific drift (matmul
precision defaults, transcendental lowering, compiler contraction of the
double-single transforms) would ship invisibly.  This lane runs the same
exactness contracts on the real chip:

    python -m pytest tests_tpu -q

It touches the chip in the pytest process itself: every test is
skipped when JAX's first device is not a TPU.
"""

import pytest


@pytest.fixture(scope="session", autouse=True)
def tpu_device():
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        pytest.skip(f"needs a TPU; JAX sees {dev.platform!r}")
    return dev
