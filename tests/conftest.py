"""Test configuration.

Tests run on a virtual 8-device CPU mesh (SURVEY §4 pattern: single-host
multi-process + mocked mesh for CI, real pod for nightly).

CPU tests must never load the TPU library (one process at a time may hold
it), so ``_cpu_guard`` removes the TPU factory from jax's backend registry
before any backend initializes. This must run before any test imports
mxnet_tpu/jax ops.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

if os.environ.get('MXNET_TEST_DEVICE', 'cpu') == 'cpu':
    import _cpu_guard
    _cpu_guard.force_cpu(8)

import numpy as _np
import pytest


@pytest.fixture(autouse=True)
def _seed_rngs():
    """Reproducible RNG per test (reference tests common.py:164 with_seed)."""
    import mxnet_tpu as mx
    seed = int(os.environ.get('MXNET_TEST_SEED', '42'))
    _np.random.seed(seed)
    mx.random.seed(seed)
    yield
