"""The benchmark's own tests run on the CPU: ``python -m pytest benchmark/tests``."""
import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 4)    # the data-axis-of-4 cell

import pytest  # noqa: E402

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


@pytest.fixture
def tiny_cell():
    """``cell(name)`` for the test-size cells under ``tests/data``."""
    from benchmark import harness

    return lambda name: harness.Cell(name, root=DATA)
