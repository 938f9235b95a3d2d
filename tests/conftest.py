"""Test configuration.

Tests run on CPU with 8 virtual XLA devices so multi-tablet sharding
(Mesh/shard_map/psum over the tablet axis) is exercised without TPU hardware,
per the standard JAX testing recipe. This must happen before jax initializes
a backend, hence the env mutation at module import time (conftest imports
before any test module).

Reference test-strategy analog: the in-process MiniCluster
(src/yb/integration-tests/mini_cluster.h) — "multi-node" behavior validated
inside one process.
"""

import os
import sys

# Force CPU: tier-1 runs without an accelerator, and on a machine that has
# one a test run must not take the chip from the process that owns it. The
# one canonical copy of this order-sensitive recipe lives in
# __graft_entry__._pin_cpu_platform (the driver gate uses the same one); its
# module top-level imports only stdlib+numpy, so it is safe to import before
# jax initializes.
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from __graft_entry__ import _pin_cpu_platform

_pin_cpu_platform(8)

import numpy as np
import pytest


@pytest.fixture
def rng():
    return np.random.default_rng(1234)


@pytest.fixture(autouse=True)
def _reset_fault_injection():
    """Leak containment for the fault-injection plane: a test that arms
    a fault flag or a sync point and then fails (or forgets cleanup)
    must not poison the next test — armed one-shot faults would fire in
    whatever unrelated code path calls maybe_fault() next."""
    yield
    from yugabyte_db_tpu.utils.fault_injection import clear_faults
    from yugabyte_db_tpu.utils.sync_point import SYNC_POINT

    clear_faults()
    SYNC_POINT.disable_and_clear()
