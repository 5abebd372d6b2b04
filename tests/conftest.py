"""Test configuration: force an 8-device virtual CPU mesh.

Mirrors the reference's strategy of re-running the CPU suite on other devices
(tests/python/gpu/test_operator_gpu.py does `from test_operator import *` with
a GPU default ctx): here the suite runs on the CPU backend with 8 virtual
devices so sharding/collective paths are exercised without TPU hardware.
Must run before jax is imported anywhere.
"""
import os
import sys

_plat = os.environ.get("MXTPU_TEST_PLATFORM", "cpu")
# MXTPU_TEST_PLATFORM=tpu is the real-chip rerun (the reference's
# test_operator_gpu.py trick): pinning the platform makes jax fail at
# start-up when there is no chip instead of quietly testing the CPU.
os.environ["JAX_PLATFORMS"] = _plat
flags = os.environ.get("XLA_FLAGS", "")
if "host_platform_device_count" not in flags and _plat == "cpu":
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np
import pytest


@pytest.fixture(autouse=True)
def _seed_rngs(request):
    """Per-test deterministic seeding (reference tests/python/unittest/common.py:117
    @with_seed). Honors MXTPU_TEST_SEED for reproduction."""
    import zlib
    seed = int(os.environ.get("MXTPU_TEST_SEED", "0"))
    if seed == 0:
        seed = zlib.crc32(request.node.nodeid.encode()) % (2**31 - 1)
    np.random.seed(seed)
    import incubator_mxnet_tpu as mx
    mx.random.seed(seed)
    yield
