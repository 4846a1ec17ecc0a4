"""Shared test config.

NOTE: no --xla_force_host_platform_device_count here — smoke tests and
benches must see exactly 1 device (task spec).  Multi-device SPMD tests
spawn subprocesses (tests/test_pipeline_spmd.py) that set the flag
themselves before importing jax.
"""
import os
import sys

import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

try:
    from hypothesis import settings
    settings.register_profile("repro", deadline=None, max_examples=50,
                              derandomize=True)
    settings.load_profile("repro")
except ImportError:  # pragma: no cover
    pass


@pytest.fixture(scope="session")
def rng():
    import numpy as np
    return np.random.default_rng(0)


@pytest.fixture(scope="session", autouse=True)
def pallas_interpret():
    """The suite runs on the CPU, so it runs Pallas kernels in interpret
    mode — an explicit choice, never inferred from the backend."""
    from repro.kernels import ops
    old = ops.set_interpret(True)
    yield
    ops.set_interpret(old)
