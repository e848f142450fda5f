"""Test harness: run everything on an 8-device virtual CPU mesh.

Reference analogue: ``apex/transformer/testing/distributed_test_base.py``
spawns N NCCL processes; on JAX a single process with
``--xla_force_host_platform_device_count=8`` provides 8 CPU devices for full
mesh/pjit/shard_map/collective coverage (SURVEY.md §4.2.4). The mechanism
lives in `apex1_tpu.testing.force_virtual_cpu_devices`.
"""

from apex1_tpu.testing import (enable_persistent_compilation_cache,
                               force_virtual_cpu_devices)

force_virtual_cpu_devices(8)
enable_persistent_compilation_cache()

import jax  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture(scope="session")
def devices():
    devs = jax.devices()
    assert len(devs) == 8, f"expected 8 virtual CPU devices, got {devs}"
    return devs


@pytest.fixture()
def rng():
    return np.random.default_rng(1234)
