"""Data-parallel layer tests on the fake 8-device CPU mesh.

Apex pattern (``tests/distributed/DDP``, ``tests/distributed/
synced_batchnorm``): every parallel feature is checked against its serial
equivalent on the same total batch.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from jax import shard_map
from apex_tpu.parallel import (DistributedDataParallel, SyncBatchNorm,
                               sync_batch_norm, allreduce_gradients, LARC,
                               Reducer)
from apex_tpu.parallel.distributed import _has_axis

from apex_tpu.parallel.sync_batchnorm import BatchNormState
from apex_tpu.contrib.clip_grad import clip_grad_norm_
from apex_tpu.optimizers import FusedSGD


@pytest.fixture
def mesh():
    return jax.make_mesh((8,), ("data",))


def loss_fn(params, x, y):
    pred = x @ params["w"] + params["b"]
    return jnp.mean((pred - y) ** 2)


class TestDDP:
    def test_sharded_training_matches_serial(self, rng, mesh):
        """GSPMD path: jit with a batch-sharded input must produce the same
        grads as single-device full batch."""
        params = {"w": jnp.asarray(rng.randn(16, 4).astype(np.float32)),
                  "b": jnp.zeros((4,), jnp.float32)}
        x = jnp.asarray(rng.randn(64, 16).astype(np.float32))
        y = jnp.asarray(rng.randn(64, 4).astype(np.float32))
        serial = jax.grad(loss_fn)(params, x, y)

        ddp = DistributedDataParallel(mesh=mesh)
        params_r = ddp.broadcast_params(params)
        x_s, y_s = ddp.scatter(x), ddp.scatter(y)
        sharded = jax.jit(jax.grad(loss_fn))(params_r, x_s, y_s)
        for a, b in zip(jax.tree_util.tree_leaves(serial),
                        jax.tree_util.tree_leaves(sharded)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-5, atol=1e-6)

    @pytest.mark.parametrize("check_vma", [True, False])
    def test_shard_map_reduce_matches_serial(self, rng, mesh, check_vma):
        """Explicit-collective path: per-device grads + ddp.reduce =
        full-batch grads, with and without vma tracking."""
        params = {"w": jnp.asarray(rng.randn(8, 2).astype(np.float32)),
                  "b": jnp.zeros((2,), jnp.float32)}
        x = jnp.asarray(rng.randn(32, 8).astype(np.float32))
        y = jnp.asarray(rng.randn(32, 2).astype(np.float32))
        ddp = DistributedDataParallel(mesh=mesh)

        @jax.jit
        def per_device_grads(params, x, y):
            def step(params, x, y):
                params = ddp.mark_local(params)   # apex staging: local grads
                g = jax.grad(loss_fn)(params, x, y)
                return ddp.reduce(g)              # ONE explicit allreduce
            return shard_map(step, mesh=mesh,
                             in_specs=(P(), P("data"), P("data")),
                             out_specs=P(),
                             check_vma=check_vma)(params, x, y)

        got = per_device_grads(params, x, y)
        ref = jax.grad(loss_fn)(params, x, y)
        for a, b in zip(jax.tree_util.tree_leaves(got),
                        jax.tree_util.tree_leaves(ref)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-5, atol=1e-6)

    @pytest.mark.parametrize("check_vma", [True, False])
    def test_reduce_of_invariant_grads_no_double_count(self, rng, mesh,
                                                       check_vma):
        """Under vma tracking, grads computed WITHOUT mark_local come out
        device-invariant (jax.grad already psummed them); reduce() must
        not multiply them by world size again.  Without tracking they are
        local and reduce() sums them — the same result either way."""
        params = {"w": jnp.asarray(rng.randn(8, 2).astype(np.float32)),
                  "b": jnp.zeros((2,), jnp.float32)}
        x = jnp.asarray(rng.randn(32, 8).astype(np.float32))
        y = jnp.asarray(rng.randn(32, 2).astype(np.float32))
        ddp = DistributedDataParallel(mesh=mesh)

        @jax.jit
        def run(params, x, y):
            def step(params, x, y):
                g = jax.grad(loss_fn)(params, x, y)  # invariant (auto-psum)
                return ddp.reduce(g)
            return shard_map(step, mesh=mesh,
                             in_specs=(P(), P("data"), P("data")),
                             out_specs=P(),
                             check_vma=check_vma)(params, x, y)

        got = run(params, x, y)["w"]
        # auto-psum sums the 8 per-shard mean-grads; average divides by 8,
        # recovering the full-batch grad — NOT 8x it.
        ref = jax.grad(loss_fn)(params, x, y)["w"]
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=1e-5, atol=1e-6)

    def test_allreduce_under_vmap_axis(self):
        """vmap axes have no vma tracking; the invariant-skip must not
        fire there — psum runs normally."""
        out = jax.vmap(lambda g: allreduce_gradients(g, "data",
                                                     average=False),
                       axis_name="data")(jnp.arange(4.0))
        np.testing.assert_allclose(np.asarray(out), 6.0)

    def test_gradient_average_off(self, rng, mesh):
        params = {"w": jnp.ones((4, 2), jnp.float32)}
        grads = {"w": jnp.ones((8, 4, 2), jnp.float32)}  # per-device stack

        @jax.jit
        def run(g):
            ddp = DistributedDataParallel(mesh=mesh,
                                          gradient_average=False)
            return shard_map(lambda g: ddp.reduce(g[0]), mesh=mesh,
                             in_specs=(P("data"),), out_specs=P(),
                             check_vma=False)(g)

        out = run(grads["w"])
        np.testing.assert_allclose(np.asarray(out), 8.0)

    def test_predivide_factor(self, rng, mesh):
        g = jnp.ones((8, 4, 128), jnp.float32)

        @jax.jit
        def run(g):
            ddp = DistributedDataParallel(mesh=mesh,
                                          gradient_predivide_factor=4.0)
            return shard_map(lambda g: ddp.reduce(g[0]), mesh=mesh,
                             in_specs=(P("data"),), out_specs=P(),
                             check_vma=False)(g)

        np.testing.assert_allclose(np.asarray(run(g)), 1.0, rtol=1e-6)

    def test_predivide_factor_sum_mode(self, rng, mesh):
        """gradient_predivide_factor with gradient_average=False: apex's
        staging nets out to sum/factor (pre-divide runs unconditionally,
        the post-scale only fires when averaging)."""
        g = jnp.ones((8, 4, 128), jnp.float32)

        @jax.jit
        def run(g):
            ddp = DistributedDataParallel(mesh=mesh,
                                          gradient_predivide_factor=4.0,
                                          gradient_average=False)
            return shard_map(lambda g: ddp.reduce(g[0]), mesh=mesh,
                             in_specs=(P("data"),), out_specs=P(),
                             check_vma=False)(g)

        # sum(1/4 each of 8 devices) = 2.0, no post-scale
        np.testing.assert_allclose(np.asarray(run(g)), 2.0, rtol=1e-6)

    def test_predivide_factor_fp32_sum_mode(self, rng, mesh):
        """Both post-scale-skipping knobs together: bf16 grads upcast by
        allreduce_always_fp32, predivided, summed — never rescaled."""
        g = jnp.full((8, 4, 128), 0.5, jnp.bfloat16)

        @jax.jit
        def run(g):
            ddp = DistributedDataParallel(mesh=mesh,
                                          gradient_predivide_factor=2.0,
                                          gradient_average=False,
                                          allreduce_always_fp32=True)
            return shard_map(lambda g: ddp.reduce(g[0]), mesh=mesh,
                             in_specs=(P("data"),), out_specs=P(),
                             check_vma=False)(g)

        out = run(g)
        assert out.dtype == jnp.float32
        np.testing.assert_allclose(np.asarray(out), 2.0, rtol=1e-6)

    @pytest.mark.parametrize("mode,tol", [("f32", 0.0), ("bf16", 5e-3),
                                          ("int8", 2e-2)])
    def test_allreduce_dtype_modes(self, rng, mesh, mode, tol):
        """allreduce_dtype transport knob on ddp.reduce: f32 bitwise-
        equal to the default psum, bf16/int8 within documented error."""
        g = jnp.asarray(rng.randn(8, 16, 128).astype(np.float32))
        ref = np.mean(np.asarray(g), axis=0)

        @jax.jit
        def run(g):
            ddp = DistributedDataParallel(mesh=mesh, allreduce_dtype=mode)
            return shard_map(lambda g: ddp.reduce(g[0]), mesh=mesh,
                             in_specs=(P("data"),), out_specs=P(),
                             check_vma=False)(g)

        out = np.asarray(run(g))
        if mode == "f32":
            base = DistributedDataParallel(mesh=mesh)

            @jax.jit
            def run_base(g):
                return shard_map(lambda g: base.reduce(g[0]), mesh=mesh,
                                 in_specs=(P("data"),), out_specs=P(),
                                 check_vma=False)(g)

            np.testing.assert_array_equal(out, np.asarray(run_base(g)))
        else:
            err = np.max(np.abs(out - ref)) / np.max(np.abs(ref))
            assert err < tol, err

    def test_allreduce_dtype_requires_mesh(self):
        with pytest.raises(ValueError, match="mesh"):
            DistributedDataParallel(allreduce_dtype="int8")

    def test_reducer(self, mesh):
        r = Reducer()
        vals = jnp.arange(8.0)

        @jax.jit
        def run(v):
            return shard_map(lambda v: r.reduce(v, average=False),
                             mesh=mesh, in_specs=(P("data"),),
                             out_specs=P(), check_vma=False)(v)

        np.testing.assert_allclose(float(run(vals)[0]), 28.0)


class TestSyncBatchNorm:
    def test_matches_full_batch_bn(self, rng, mesh):
        """SyncBN over 8 shards == plain BN over the full batch (apex
        tests/distributed/synced_batchnorm)."""
        n, c, h, w = 32, 6, 4, 4
        x = jnp.asarray(rng.randn(n, c, h, w).astype(np.float32))
        bn = SyncBatchNorm(c, process_group="data")
        params = bn.init_params()
        state = bn.init_state()

        @jax.jit
        def sharded(x):
            def f(x):
                y, st = bn(params, state, x, training=True)
                return y, st
            return shard_map(f, mesh=mesh, in_specs=(P("data"),),
                             out_specs=(P("data"), P()), check_vma=False)(x)

        y_sync, st_sync = sharded(x)
        bn_serial = SyncBatchNorm(c, process_group=None)
        y_ref, st_ref = bn_serial(params, state, x, training=True)
        np.testing.assert_allclose(np.asarray(y_sync), np.asarray(y_ref),
                                   rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(np.asarray(st_sync.running_mean),
                                   np.asarray(st_ref.running_mean),
                                   rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(np.asarray(st_sync.running_var),
                                   np.asarray(st_ref.running_var),
                                   rtol=1e-4, atol=1e-5)

    def test_eval_uses_running_stats(self, rng):
        bn = SyncBatchNorm(3)
        params, state = bn.init_params(), bn.init_state()
        state = BatchNormState(jnp.asarray([1.0, 2.0, 3.0]),
                               jnp.asarray([4.0, 4.0, 4.0]),
                               jnp.ones((), jnp.int32))
        x = jnp.zeros((2, 3, 2, 2))
        y, st = bn(params, state, x, training=False)
        # (0 - mean)/2
        np.testing.assert_allclose(np.asarray(y[0, :, 0, 0]),
                                   [-0.5, -1.0, -1.5], rtol=1e-5)

    def test_no_track_running_stats_uses_batch_stats(self, rng):
        """track_running_stats=False in training: normalize with BATCH
        stats (torch/apex semantics), state untouched."""
        x = jnp.asarray(rng.randn(16, 4, 3, 3).astype(np.float32))
        bn = SyncBatchNorm(4, track_running_stats=False)
        params, state = bn.init_params(), bn.init_state()
        y, st = bn(params, state, x, training=True)
        m = np.asarray(y).transpose(0, 2, 3, 1).reshape(-1, 4).mean(0)
        np.testing.assert_allclose(m, 0.0, atol=1e-5)  # batch-normalized
        np.testing.assert_allclose(np.asarray(st.running_mean),
                                   np.asarray(state.running_mean))
        assert int(st.num_batches_tracked) == 0
        # eval mode: torch still uses BATCH stats when not tracking
        y_ev, _ = bn(params, state, x, training=False)
        m_ev = np.asarray(y_ev).transpose(0, 2, 3, 1).reshape(-1, 4).mean(0)
        np.testing.assert_allclose(m_ev, 0.0, atol=1e-5)

    def test_channel_last(self, rng):
        x = jnp.asarray(rng.randn(8, 4, 4, 6).astype(np.float32))
        bn = SyncBatchNorm(6, channel_last=True)
        y, _ = bn(bn.init_params(), bn.init_state(), x, training=True)
        m = np.asarray(y).reshape(-1, 6).mean(0)
        np.testing.assert_allclose(m, 0.0, atol=1e-5)

    def test_grad_flows(self, rng):
        x = jnp.asarray(rng.randn(8, 4, 2, 2).astype(np.float32))
        bn = SyncBatchNorm(4)
        params, state = bn.init_params(), bn.init_state()
        g = jax.grad(lambda p: jnp.sum(bn(p, state, x)[0] ** 2))(params)
        assert np.all(np.isfinite(np.asarray(g["weight"])))


class TestLARCAndClipGrad:
    def test_larc_clips_adaptive_lr(self, rng):
        params = {"w": jnp.asarray(rng.randn(32, 32).astype(np.float32))}
        grads = {"w": jnp.asarray(
            rng.randn(32, 32).astype(np.float32) * 100.0)}
        base = FusedSGD(lr=0.1)
        opt = LARC(base, trust_coefficient=0.001)
        state = opt.init(params)
        p1, _ = opt.step(grads, params, state)
        # huge grads → adaptive lr ≪ base lr → small update
        delta = float(jnp.max(jnp.abs(p1["w"] - params["w"])))
        p_ref, _ = base.step(grads, params, base.init(params))
        delta_ref = float(jnp.max(jnp.abs(p_ref["w"] - params["w"])))
        assert delta < delta_ref * 0.1

    def test_larc_scale_formula(self, rng):
        p = jnp.ones((4, 4)) * 2.0
        g = jnp.ones((4, 4)) * 0.5
        params, grads = {"w": p}, {"w": g}
        base = FusedSGD(lr=0.1)
        opt = LARC(base, trust_coefficient=0.02, clip=True)
        p1, _ = opt.step(grads, params, opt.init(params))
        pn, gn = float(jnp.linalg.norm(p)), float(jnp.linalg.norm(g))
        adaptive = 0.02 * pn / gn
        scale = min(adaptive / 0.1, 1.0)
        ref = np.asarray(p) - 0.1 * scale * np.asarray(g)
        np.testing.assert_allclose(np.asarray(p1["w"]), ref, rtol=1e-5)

    def test_clip_grad_norm(self, rng):
        grads = {"a": jnp.asarray(rng.randn(100).astype(np.float32) * 10),
                 "b": jnp.asarray(rng.randn(50).astype(np.float32) * 10)}
        clipped, norm = clip_grad_norm_(grads, max_norm=1.0)
        total = np.sqrt(sum(float(jnp.sum(g ** 2))
                            for g in jax.tree_util.tree_leaves(grads)))
        np.testing.assert_allclose(float(norm), total, rtol=1e-5)
        new_norm = np.sqrt(sum(float(jnp.sum(g ** 2))
                               for g in
                               jax.tree_util.tree_leaves(clipped)))
        np.testing.assert_allclose(new_norm, 1.0, rtol=1e-3)

    def test_clip_noop_when_small(self, rng):
        grads = {"a": jnp.asarray([0.1, 0.1], dtype=jnp.float32)}
        clipped, norm = clip_grad_norm_(grads, max_norm=10.0)
        np.testing.assert_allclose(np.asarray(clipped["a"]),
                                   np.asarray(grads["a"]), rtol=1e-6)


class TestMainGradAccumulation:
    """apex gradient_accumulation_fusion / main_grad contract: microbatch
    grads accumulate in fp32 regardless of model dtype
    (reference fused_weight_gradient_mlp_cuda)."""

    def test_accumulate_fp32_main_grad(self):
        g_bf16 = {"w": jnp.full((4,), 0.1, jnp.bfloat16)}
        acc = DistributedDataParallel.accumulate(
            None, g_bf16, main_grad_dtype=jnp.float32)
        assert acc["w"].dtype == jnp.float32
        for _ in range(63):
            acc = DistributedDataParallel.accumulate(
                acc, g_bf16, main_grad_dtype=jnp.float32)
        # 64 accumulations of bf16(0.1): fp32 accumulation keeps the sum
        # accurate to bf16(0.1)*64, bf16 accumulation would have drifted
        expect = 64 * float(jnp.bfloat16(0.1))
        np.testing.assert_allclose(np.asarray(acc["w"]), expect,
                                   rtol=1e-6)

    def test_accumulate_default_keeps_dtype(self):
        g = {"w": jnp.ones((4,), jnp.bfloat16)}
        acc = DistributedDataParallel.accumulate(None, g)
        assert acc["w"].dtype == jnp.bfloat16


class TestHasAxis:
    """_has_axis must treat every 'unbound axis name' exception flavor —
    NameError classically, but newer JAX generations raise KeyError /
    ValueError / TypeError from the axis-env lookup — as False."""

    def test_unbound_axis_outside_trace(self):
        assert _has_axis("no_such_axis") is False

    def test_bound_axis_inside_shard_map(self, mesh):
        seen = []

        def body(x):
            seen.append((_has_axis("data"), _has_axis("bogus")))
            return x

        shard_map(body, mesh=mesh, in_specs=(P("data"),),
                  out_specs=P("data"), check_vma=False)(jnp.arange(8.0))
        assert seen and seen[0] == (True, False)

    def test_bound_axis_under_vmap(self):
        seen = []

        def body(x):
            seen.append(_has_axis("batch"))
            return x

        jax.vmap(body, axis_name="batch")(jnp.arange(4.0))
        assert seen == [True]


class TestContribOptimizerShims:
    def test_deprecated_reexports(self):
        from apex_tpu.contrib import optimizers as co
        from apex_tpu.fp16_utils import FP16_Optimizer
        from apex_tpu.optimizers import FusedAdam, FusedLAMB
        assert co.FusedAdam is FusedAdam
        assert co.FusedLamb is FusedLAMB
        assert co.FP16_Optimizer is FP16_Optimizer
