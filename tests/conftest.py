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


@pytest.fixture
def assert_ulp_close():
    """``check(got, want)``: every element of float32 ``got`` within 8 ulp
    of ``want``'s LARGEST magnitude (``rtol=0``).

    For results of two DIFFERENT compiled programs that replay one
    arithmetic: pp=S against pp=1, a ring of GEMMs against the one GEMM.
    jaxlib 0.9.0's XLA:CPU fuses and vectorizes the same reduction
    differently in the two, so they are not bit-equal (measured: at most
    4 ulp of the leaf's largest value, 2.4e-6 relative on the elements that
    count; an element far smaller than the terms it sums is off by
    thousands of its own ulp, which is why the unit is the leaf's).  A
    dropped microbatch or a missing reduce is a fraction of the leaf's
    size: millions of ulp.  Where the two programs ARE bit-equal today
    (every loss), the tests keep ``tobytes()`` / ``assert_array_equal``.
    """
    import numpy as np

    def check(got, want):
        got, want = np.asarray(got), np.asarray(want)
        assert got.dtype == want.dtype == np.float32, (got.dtype, want.dtype)
        np.testing.assert_allclose(
            got, want, rtol=0,
            atol=8 * np.spacing(np.abs(want).max(), dtype=np.float32))

    return check


@pytest.fixture
def linear_elastic():
    """The smallest thing an ``ElasticTrainer`` can train, for tests of
    what drives one: ``loss_fn``, ``batch_fn(step, plan)`` (a replicated
    global batch, so a change of dp resumes bitwise), a per-leaf
    FusedAdam ``factory(plan, ckpt, inj)`` and ``flat(trainer)``, its
    params and optimizer slots as numpy leaves."""
    from types import SimpleNamespace

    import jax.numpy as jnp
    import numpy as np

    from apex_tpu.optimizers import FusedAdam
    from apex_tpu.resilience import ElasticComponents, GuardedTrainStep

    def loss_fn(p, x, y):
        return jnp.mean(jnp.square(x @ p["w"] + p["b"] - y))

    def batch_fn(step, plan):
        r = np.random.RandomState(60_000 + step)
        return (jnp.asarray(r.randn(8, 8).astype(np.float32)),
                jnp.asarray(r.randn(8, 4).astype(np.float32)))

    def factory(plan, ckpt, inj):
        opt = FusedAdam(lr=1e-2)
        guard = GuardedTrainStep(loss_fn, opt, warmup_steps=1,
                                 checkpoint=ckpt, fault_injector=inj,
                                 plan=plan.parallel)
        r = np.random.RandomState(3)
        params = plan.put(
            {"w": jnp.asarray(r.randn(8, 4).astype(np.float32)),
             "b": jnp.zeros((4,), jnp.float32)})
        return ElasticComponents(guard, params, opt.init(params),
                                 guard.init_state())

    def flat(trainer):
        out = list(jax.tree_util.tree_leaves(trainer.params))
        for _, slots in sorted(trainer.opt_state["buckets"].items()):
            for _, v in sorted(slots.items()):
                out.extend(v if isinstance(v, list) else [v])
        return [np.asarray(x) for x in out]

    return SimpleNamespace(loss_fn=loss_fn, batch_fn=batch_fn,
                           factory=factory, flat=flat)
