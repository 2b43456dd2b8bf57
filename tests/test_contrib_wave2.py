"""Contrib wave 2 + RNN tier (reference: ``apex/contrib/{conv_bias_relu,
cudnn_gbn,nccl_p2p,nccl_allocator,openfold_triton}``, ``apex/RNN``) —
each surface against a composed jnp reference, shard_map paths on the
8-device mesh."""

import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import PartitionSpec as P


@pytest.fixture
def rng():
    return np.random.RandomState(0)


class TestConvBiasReLU:
    def _ref_conv(self, x, w, stride, padding):
        return jax.lax.conv_general_dilated(
            x, w, (stride, stride), ((padding, padding),) * 2,
            dimension_numbers=("NHWC", "HWIO", "NHWC"))

    def test_conv_bias_relu(self, rng):
        from apex_tpu.contrib.conv_bias_relu import ConvBias, ConvBiasReLU
        x = jnp.asarray(rng.randn(2, 8, 8, 3), jnp.float32)
        w = jnp.asarray(rng.randn(3, 3, 3, 16) * 0.1, jnp.float32)
        b = jnp.asarray(rng.randn(16) * 0.1, jnp.float32)
        got = ConvBiasReLU(x, w, b, padding=1, stride=2)
        ref = jax.nn.relu(self._ref_conv(x, w, 2, 1) + b)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=1e-6)
        got_nb = ConvBias(x, w, b, padding=1, stride=1)
        assert got_nb.shape == (2, 8, 8, 16)
        assert float(jnp.min(got)) >= 0.0

    def test_mask_and_frozen_scale(self, rng):
        from apex_tpu.contrib.conv_bias_relu import (
            ConvBiasMaskReLU, ConvFrozenScaleBiasReLU)
        x = jnp.asarray(rng.randn(1, 6, 6, 2), jnp.float32)
        w = jnp.asarray(rng.randn(1, 1, 2, 4) * 0.3, jnp.float32)
        b = jnp.zeros((4,), jnp.float32)
        mask = jnp.asarray(rng.rand(1, 6, 6, 4) > 0.5, jnp.float32)
        y = ConvBiasMaskReLU(x, w, b, mask, padding=0, stride=1)
        np.testing.assert_array_equal(
            np.asarray(y == 0.0) | np.asarray(mask > 0), True)
        scale = jnp.asarray(rng.rand(4) + 0.5, jnp.float32)
        bias = jnp.asarray(rng.randn(4), jnp.float32)
        z = ConvFrozenScaleBiasReLU(x, w, scale, bias)
        ref = jax.nn.relu(self._ref_conv(x, w, 1, 0) * scale + bias)
        np.testing.assert_allclose(np.asarray(z), np.asarray(ref),
                                   rtol=1e-6)

    def test_grad_flows(self, rng):
        from apex_tpu.contrib.conv_bias_relu import ConvBiasReLU
        x = jnp.asarray(rng.randn(1, 4, 4, 2), jnp.float32)
        w = jnp.asarray(rng.randn(3, 3, 2, 2) * 0.1, jnp.float32)
        b = jnp.zeros((2,), jnp.float32)
        g = jax.grad(lambda w: ConvBiasReLU(x, w, b, 1, 1).sum())(w)
        assert bool(jnp.any(g != 0))


class TestCudnnGBN:
    def test_matches_groupbn(self, rng):
        from apex_tpu.contrib.cudnn_gbn import GroupBatchNorm2d
        from apex_tpu.contrib.groupbn import BatchNorm2d_NHWC
        x = jnp.asarray(rng.randn(4, 4, 4, 8), jnp.float32)
        a = GroupBatchNorm2d(8)
        b = BatchNorm2d_NHWC(8)
        pa, sa = a.init_params(), a.init_state()
        pb, sb = b.init_params(), b.init_state()
        ya, _ = a(pa, sa, x, training=True)
        yb, _ = b(pb, sb, x, training=True)
        np.testing.assert_allclose(np.asarray(ya), np.asarray(yb))

    def test_group_requires_axis(self):
        from apex_tpu.contrib.cudnn_gbn import GroupBatchNorm2d
        with pytest.raises(ValueError):
            GroupBatchNorm2d(8, group_size=4)
        GroupBatchNorm2d(8, group_size=4, axis_name="data")  # ok

    def test_cross_device_stats(self, rng):
        from apex_tpu.contrib.cudnn_gbn import GroupBatchNorm2d
        mesh = jax.make_mesh((4,), ("data",))
        m = GroupBatchNorm2d(8, group_size=4, axis_name="data")
        params, state = m.init_params(), m.init_state()
        x = jnp.asarray(rng.randn(8, 4, 4, 8), jnp.float32)

        y = jax.jit(shard_map(
            lambda p, s, x: m(p, s, x, training=True)[0],
            mesh=mesh, in_specs=(P(), P(), P("data")),
            out_specs=P("data"), check_vma=False))(params, state, x)
        # group stats == global-batch stats: output is exactly the
        # serial BN over the full batch
        serial = GroupBatchNorm2d(8)
        y_ref, _ = serial(params, state, x, training=True)
        np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                                   rtol=2e-4, atol=2e-4)


class TestNcclP2P:
    def test_left_right_halo_exchange(self, rng):
        from apex_tpu.contrib.nccl_p2p import left_right_halo_exchange
        mesh = jax.make_mesh((4,), ("spatial",))
        x = jnp.asarray(rng.randn(4, 3, 5), jnp.float32)  # rank-major

        def step(x):
            left_out = x[:, :1]          # my top rows
            right_out = x[:, -1:]        # my bottom rows
            li, ri = left_right_halo_exchange(left_out, right_out,
                                              "spatial")
            return li, ri

        li, ri = jax.jit(shard_map(
            step, mesh=mesh, in_specs=P("spatial"),
            out_specs=(P("spatial"), P("spatial")), check_vma=False))(x)
        li, ri = np.asarray(li), np.asarray(ri)
        x = np.asarray(x)
        # rank r's left input == rank r-1's right output; edge rank gets 0
        np.testing.assert_array_equal(li[0], 0.0)
        for r in range(1, 4):
            np.testing.assert_array_equal(li[r], x[r - 1, -1:])
        np.testing.assert_array_equal(ri[3], 0.0)
        for r in range(3):
            np.testing.assert_array_equal(ri[r], x[r + 1, :1])

    def test_nccl_allocator_shim(self):
        import apex_tpu.contrib.nccl_allocator as na
        with pytest.raises(RuntimeError):
            with na.nccl_mem():
                pass
        na.init()
        pool = na.create_nccl_mem_pool()
        with na.nccl_mem(pool):
            buf = jnp.zeros((8,))
        assert buf.shape == (8,)


class TestOpenfold:
    def test_attention_core_no_bias_matches_reference(self, rng):
        from apex_tpu.contrib.openfold_triton import attention_core
        q = jnp.asarray(rng.randn(2, 2, 16, 8), jnp.float32)
        k = jnp.asarray(rng.randn(2, 2, 16, 8), jnp.float32)
        v = jnp.asarray(rng.randn(2, 2, 16, 8), jnp.float32)
        got = attention_core(q, k, v)
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * 8 ** -0.5
        ref = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), v)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

    def test_attention_core_bias_mask(self, rng):
        from apex_tpu.contrib.openfold_triton import attention_core
        # extra leading (evoformer row) batch dim + pair bias + mask
        q = jnp.asarray(rng.randn(2, 3, 2, 8, 4), jnp.float32)
        k = jnp.asarray(rng.randn(2, 3, 2, 8, 4), jnp.float32)
        v = jnp.asarray(rng.randn(2, 3, 2, 8, 4), jnp.float32)
        bias = jnp.asarray(rng.randn(2, 1, 2, 8, 8), jnp.float32)
        mask = jnp.ones((2, 3, 1, 1, 8)).at[..., 6:].set(0)
        got = attention_core(q, k, v, mask=mask, bias=bias)
        s = jnp.einsum("...qd,...kd->...qk", q, k) * 4 ** -0.5 + bias
        s = s - (1 - mask) * 1e9
        ref = jnp.einsum("...qk,...kd->...qd", jax.nn.softmax(s, -1), v)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

    def test_layer_norm_impl(self, rng):
        from apex_tpu.contrib.openfold_triton import (
            LayerNormSmallShapeOptImpl)
        x = jnp.asarray(rng.randn(4, 7, 64), jnp.float32)
        w = jnp.asarray(rng.rand(64) + 0.5, jnp.float32)
        b = jnp.asarray(rng.randn(64), jnp.float32)
        got = LayerNormSmallShapeOptImpl.apply(x, w, b)
        mu = x.mean(-1, keepdims=True)
        var = x.var(-1, keepdims=True)
        ref = (x - mu) / jnp.sqrt(var + 1e-5) * w + b
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

    def test_fused_adam_swa(self, rng):
        from apex_tpu.contrib.openfold_triton import FusedAdamSWA
        params = {"w": jnp.asarray(rng.randn(16, 16), jnp.float32)}
        grads = {"w": jnp.asarray(rng.randn(16, 16) * 0.1, jnp.float32)}
        opt = FusedAdamSWA(lr=1e-2, swa_start=2, swa_freq=1)
        state = opt.init(params)
        p = params
        snapshots = []
        for _ in range(5):
            p, state = opt.step(grads, p, state)
            snapshots.append(np.asarray(p["w"]))
        # swa averages steps 3..5 (count 3)
        assert int(state["n_avg"]) == 3
        swa = opt.swa_params(state, like=params)
        ref = np.mean(snapshots[2:], axis=0)
        np.testing.assert_allclose(np.asarray(swa["w"]), ref,
                                   rtol=1e-5, atol=1e-6)


class TestRNN:
    def test_lstm_matches_torch_formula(self, rng):
        with warnings.catch_warnings():
            warnings.simplefilter("ignore", DeprecationWarning)
            from apex_tpu.RNN import LSTM
            m = LSTM(4, 6, num_layers=2)
        params = m.init_params(jax.random.PRNGKey(0))
        x = jnp.asarray(rng.randn(5, 3, 4), jnp.float32)
        out, states = m.apply(params, x)
        assert out.shape == (5, 3, 6)
        assert len(states) == 2 and len(states[0]) == 2

        # manual recurrence for layer 0, step 0
        p = params[0]
        g = x[0] @ p["i2h"]["weight"] + p["i2h"]["bias"] \
            + jnp.zeros((3, 6)) @ p["h2h"]["weight"] + p["h2h"]["bias"]
        i, f, gc, o = jnp.split(g, 4, -1)
        c = jax.nn.sigmoid(i) * jnp.tanh(gc)
        h0 = jax.nn.sigmoid(o) * jnp.tanh(c)
        # layer-0 output at t=0 feeds layer 1; verify via re-running scan
        out1, _ = m.apply(params[:1], x)
        np.testing.assert_allclose(np.asarray(out1[0]), np.asarray(h0),
                                   rtol=1e-5, atol=1e-6)

    def test_gru_and_rnn_run(self, rng):
        with warnings.catch_warnings():
            warnings.simplefilter("ignore", DeprecationWarning)
            from apex_tpu.RNN import GRU, RNNReLU, RNNTanh
            for factory in (GRU, RNNReLU, RNNTanh):
                m = factory(3, 5)
                params = m.init_params(jax.random.PRNGKey(1))
                out, _ = m.apply(params,
                                 jnp.asarray(rng.randn(4, 2, 3),
                                             jnp.float32))
                assert out.shape == (4, 2, 5)
                assert bool(jnp.all(jnp.isfinite(out)))

    def test_deprecation_warning(self):
        from apex_tpu.RNN import LSTM
        with pytest.warns(DeprecationWarning):
            LSTM(2, 2)

    def test_grad_through_scan(self, rng):
        with warnings.catch_warnings():
            warnings.simplefilter("ignore", DeprecationWarning)
            from apex_tpu.RNN import LSTM
            m = LSTM(3, 4)
        params = m.init_params(jax.random.PRNGKey(2))
        x = jnp.asarray(rng.randn(6, 2, 3), jnp.float32)

        def loss(params):
            out, _ = m.apply(params, x)
            return jnp.mean(out ** 2)

        g = jax.jit(jax.grad(loss))(params)
        assert all(bool(jnp.any(l != 0))
                   for l in jax.tree_util.tree_leaves(g))
