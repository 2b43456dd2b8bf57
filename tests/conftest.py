"""Test configuration: two lanes.

* Default lane — everything on a fake 8-device CPU mesh.  Apex's
  distributed tests spawn one process per GPU
  (``apex/transformer/testing/distributed_test_base.py``) and skip without
  hardware; XLA can emulate N devices on CPU, so every TP/PP/DP test runs
  hardware-free in one process.  These env vars must be set before JAX
  initializes, hence at conftest import time.
* On-chip lane — ``APEX_TPU_ON_CHIP=1 pytest -m tpu`` leaves the real TPU
  backend in place and runs the hardware-marked tests (Pallas kernel
  parity, amp x Pallas composition, train-step smoke) where the kernels
  actually run.  The reference runs every kernel test on real hardware;
  this is the equivalent gate (CPU interpret mode does not enforce TPU
  tiling/VMEM limits).
"""

import os

ON_CHIP = os.environ.get("APEX_TPU_ON_CHIP") == "1"

if not ON_CHIP:
    os.environ["JAX_PLATFORMS"] = "cpu"
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

from apex_tpu.utils.platform import setup_compile_cache  # noqa: E402

if not ON_CHIP:
    jax.config.update("jax_enable_x64", False)
    assert jax.default_backend() == "cpu"
    # Persistent XLA compilation cache: the suite is compile-bound, so
    # warm reruns of the tier-1 command drop well under its time budget.
    # Every compile is kept (threshold 0): most of the suite's programs
    # compile in under half a second each and a cache read beats any of
    # them.  The variables are exported as well so the example-script
    # subprocesses in test_examples.py share the cache.
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          setup_compile_cache())
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS",
                          "0.0")
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)

import pytest  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "tpu: requires the real TPU chip "
                   "(run via APEX_TPU_ON_CHIP=1 pytest -m tpu)")


def pytest_collection_modifyitems(config, items):
    skip_tpu = pytest.mark.skip(
        reason="on-chip lane only (APEX_TPU_ON_CHIP=1 pytest -m tpu)")
    for item in items:
        if "tpu" in item.keywords and not ON_CHIP:
            item.add_marker(skip_tpu)


@pytest.fixture
def rng():
    import numpy as np
    return np.random.RandomState(1234)
