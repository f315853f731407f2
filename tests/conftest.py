"""Shared pytest fixtures.

NOTE: deliberately does NOT set ``--xla_force_host_platform_device_count``:
smoke tests and benchmarks must see the real single CPU device.  The
tests that need fake devices spawn subprocesses with their own XLA_FLAGS
(see tests/test_distributed.py, tests/test_hlo_cost.py).
"""
import numpy as np
import pytest


@pytest.fixture
def rng():
    return np.random.default_rng(0)
