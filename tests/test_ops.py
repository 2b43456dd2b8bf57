"""Fused op library tests (apex ``tests/L0/run_fused_layer_norm``,
``run_mlp``, contrib xentropy tests).  Every fused op is compared against a
plain-jnp reference (values and grads)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.normalization import (FusedLayerNorm, FusedRMSNorm,
                                    MixedFusedLayerNorm,
                                    fused_layer_norm_affine,
                                    fused_rms_norm_affine)
from apex_tpu.ops.softmax import (scaled_masked_softmax, scaled_softmax,
                                  scaled_upper_triang_masked_softmax)
from apex_tpu.ops.rope import (fused_apply_rotary_pos_emb, rope_freqs,
                               fused_apply_rotary_pos_emb_thd)
from apex_tpu.ops.xentropy import softmax_cross_entropy_loss, \
    SoftmaxCrossEntropyLoss
from apex_tpu.mlp import MLP
from apex_tpu.fused_dense import FusedDense, FusedDenseGeluDense
from apex_tpu.utils import set_force_pallas


def ref_layer_norm(x, w, b, eps=1e-5):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.var(x, axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * w + b


def ref_rms_norm(x, w, eps=1e-5):
    ms = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(ms + eps) * w


class TestFusedLayerNorm:
    @pytest.mark.parametrize("shape,hidden", [((4, 8, 256), 256),
                                              ((16, 100), 100),
                                              ((3, 384), 384)])
    def test_forward_matches_reference(self, rng, shape, hidden):
        x = jnp.asarray(rng.randn(*shape).astype(np.float32))
        w = jnp.asarray(rng.rand(hidden).astype(np.float32) + 0.5)
        b = jnp.asarray(rng.randn(hidden).astype(np.float32) * 0.1)
        out = fused_layer_norm_affine(x, w, b, (hidden,))
        ref = ref_layer_norm(x, w, b)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)

    @pytest.mark.parametrize("memory_efficient", [False, True])
    def test_grads_match_autodiff(self, rng, memory_efficient):
        hidden = 192
        x = jnp.asarray(rng.randn(8, hidden).astype(np.float32))
        w = jnp.asarray(rng.rand(hidden).astype(np.float32) + 0.5)
        b = jnp.asarray(rng.randn(hidden).astype(np.float32) * 0.1)

        def fused_loss(x, w, b):
            return jnp.sum(fused_layer_norm_affine(
                x, w, b, (hidden,), memory_efficient=memory_efficient) ** 2)

        def ref_loss(x, w, b):
            return jnp.sum(ref_layer_norm(x, w, b) ** 2)

        g1 = jax.grad(fused_loss, argnums=(0, 1, 2))(x, w, b)
        g2 = jax.grad(ref_loss, argnums=(0, 1, 2))(x, w, b)
        for a, r in zip(g1, g2):
            np.testing.assert_allclose(np.asarray(a), np.asarray(r),
                                       rtol=2e-4, atol=2e-4)

    def test_rms_norm(self, rng):
        hidden = 256
        x = jnp.asarray(rng.randn(6, hidden).astype(np.float32))
        w = jnp.asarray(rng.rand(hidden).astype(np.float32) + 0.5)
        out = fused_rms_norm_affine(x, w, (hidden,))
        np.testing.assert_allclose(np.asarray(out),
                                   np.asarray(ref_rms_norm(x, w)),
                                   rtol=1e-5, atol=1e-5)
        g1 = jax.grad(lambda x: jnp.sum(
            fused_rms_norm_affine(x, w, (hidden,)) ** 2))(x)
        g2 = jax.grad(lambda x: jnp.sum(ref_rms_norm(x, w) ** 2))(x)
        np.testing.assert_allclose(np.asarray(g1), np.asarray(g2),
                                   rtol=2e-4, atol=2e-4)

    def test_modules(self, rng):
        m = FusedLayerNorm(64)
        p = m.init_params()
        x = jnp.asarray(rng.randn(4, 64).astype(np.float32))
        y = m(p, x)
        assert y.shape == x.shape
        mm = MixedFusedLayerNorm(64)
        y2 = mm(mm.init_params(), x.astype(jnp.bfloat16))
        assert y2.dtype == jnp.bfloat16
        r = FusedRMSNorm(64)
        pr = r.init_params()
        assert "bias" not in pr
        assert r(pr, x).shape == x.shape

    def test_pallas_interpret_parity(self, rng):
        hidden = 256
        x = jnp.asarray(rng.randn(16, hidden).astype(np.float32))
        w = jnp.asarray(rng.rand(hidden).astype(np.float32) + 0.5)
        b = jnp.asarray(rng.randn(hidden).astype(np.float32) * 0.1)

        def loss(x, w, b, me):
            return jnp.sum(fused_layer_norm_affine(
                x, w, b, (hidden,), memory_efficient=me) ** 2)

        for me in (False, True):
            set_force_pallas(False)
            ref = jax.grad(loss, argnums=(0, 1, 2))(x, w, b, me)
            refy = fused_layer_norm_affine(x, w, b, (hidden,),
                                           memory_efficient=me)
            set_force_pallas(True)
            try:
                got = jax.grad(loss, argnums=(0, 1, 2))(x, w, b, me)
                goty = fused_layer_norm_affine(x, w, b, (hidden,),
                                               memory_efficient=me)
            finally:
                set_force_pallas(None)
            np.testing.assert_allclose(np.asarray(goty), np.asarray(refy),
                                       rtol=1e-5, atol=1e-5)
            for a, r in zip(got, ref):
                np.testing.assert_allclose(np.asarray(a), np.asarray(r),
                                           rtol=1e-4, atol=1e-4)


class TestFusedSoftmax:
    def test_masked_matches_reference(self, rng):
        x = jnp.asarray(rng.randn(2, 4, 8, 16).astype(np.float32))
        mask = jnp.asarray(rng.rand(2, 1, 8, 16) > 0.7)
        out = scaled_masked_softmax(x, mask, scale=0.5)
        ref = jax.nn.softmax(jnp.where(mask, -10000.0, x * 0.5), axis=-1)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5, atol=1e-6)

    def test_grad_uses_saved_output(self, rng):
        x = jnp.asarray(rng.randn(2, 4, 8, 16).astype(np.float32))
        g1 = jax.grad(lambda x: jnp.sum(scaled_softmax(x, 2.0) ** 2))(x)
        g2 = jax.grad(lambda x: jnp.sum(
            jax.nn.softmax(x * 2.0, axis=-1) ** 2))(x)
        np.testing.assert_allclose(np.asarray(g1), np.asarray(g2),
                                   rtol=1e-4, atol=1e-5)

    def test_causal(self, rng):
        x = jnp.asarray(rng.randn(3, 8, 8).astype(np.float32))
        out = scaled_upper_triang_masked_softmax(x, 1.0)
        out = np.asarray(out)
        for q in range(8):
            assert np.allclose(out[:, q, q + 1:], 0.0, atol=1e-4)
            np.testing.assert_allclose(out[:, q, :q + 1].sum(-1), 1.0,
                                       rtol=1e-5)

    def test_causal_grad(self, rng):
        x = jnp.asarray(rng.randn(2, 6, 6).astype(np.float32))

        def ref(x):
            m = np.triu(np.ones((6, 6), bool), 1)
            return jax.nn.softmax(jnp.where(jnp.asarray(m), -10000.0, x),
                                  axis=-1)

        g1 = jax.grad(lambda x: jnp.sum(
            scaled_upper_triang_masked_softmax(x, 1.0) ** 2))(x)
        g2 = jax.grad(lambda x: jnp.sum(ref(x) ** 2))(x)
        np.testing.assert_allclose(np.asarray(g1), np.asarray(g2),
                                   rtol=1e-4, atol=1e-5)


class TestRoPE:
    def test_matches_reference(self, rng):
        s, b, h, d = 12, 2, 4, 32
        t = jnp.asarray(rng.randn(s, b, h, d).astype(np.float32))
        freqs = rope_freqs(s, d)
        out = fused_apply_rotary_pos_emb(t, freqs)
        cos, sin = jnp.cos(freqs), jnp.sin(freqs)

        def rotate_half(u):
            u1, u2 = u[..., :d // 2], u[..., d // 2:]
            return jnp.concatenate([-u2, u1], axis=-1)

        ref = t * cos + rotate_half(t) * sin
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)

    def test_norm_preserved(self, rng):
        # rotations preserve pairwise norms
        s, b, h, d = 8, 1, 2, 16
        t = jnp.asarray(rng.randn(s, b, h, d).astype(np.float32))
        out = fused_apply_rotary_pos_emb(t, rope_freqs(s, d))
        np.testing.assert_allclose(
            np.linalg.norm(np.asarray(out), axis=-1),
            np.linalg.norm(np.asarray(t), axis=-1), rtol=1e-4)

    def test_analytic_grad_matches_autodiff(self, rng):
        s, b, h, d = 6, 2, 2, 8
        t = jnp.asarray(rng.randn(s, b, h, d).astype(np.float32))
        freqs = rope_freqs(s, d)
        cos, sin = jnp.cos(freqs), jnp.sin(freqs)

        def rotate_half(u):
            u1, u2 = u[..., :d // 2], u[..., d // 2:]
            return jnp.concatenate([-u2, u1], axis=-1)

        g1 = jax.grad(lambda t: jnp.sum(
            fused_apply_rotary_pos_emb(t, freqs) ** 2))(t)
        g2 = jax.grad(lambda t: jnp.sum(
            (t * cos + rotate_half(t) * sin) ** 2))(t)
        np.testing.assert_allclose(np.asarray(g1), np.asarray(g2),
                                   rtol=1e-4, atol=1e-5)

    def test_partial_rotary_dim(self, rng):
        s, b, h, d = 6, 1, 2, 32
        t = jnp.asarray(rng.randn(s, b, h, d).astype(np.float32))
        freqs = rope_freqs(s, 16)
        out = fused_apply_rotary_pos_emb(t, freqs)
        np.testing.assert_array_equal(np.asarray(out[..., 16:]),
                                      np.asarray(t[..., 16:]))

    def test_thd_restarts_positions(self, rng):
        d = 16
        freqs = rope_freqs(10, d)
        t = jnp.asarray(rng.randn(7, 2, d).astype(np.float32))
        cu = jnp.asarray([0, 3, 7], jnp.int32)
        out = fused_apply_rotary_pos_emb_thd(t, cu, freqs.reshape(10, 1, d))
        # second sequence's first token (index 3) uses position 0 → identity
        np.testing.assert_allclose(np.asarray(out[3]), np.asarray(t[3]),
                                   rtol=1e-5)


class TestXentropy:
    def test_matches_reference(self, rng):
        logits = jnp.asarray(rng.randn(32, 50).astype(np.float32) * 3)
        labels = jnp.asarray(rng.randint(0, 50, 32))
        loss = softmax_cross_entropy_loss(logits, labels)
        ref = -jax.nn.log_softmax(logits)[jnp.arange(32), labels]
        np.testing.assert_allclose(np.asarray(loss), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)

    def test_label_smoothing(self, rng):
        logits = jnp.asarray(rng.randn(8, 10).astype(np.float32))
        labels = jnp.asarray(rng.randint(0, 10, 8))
        s = 0.1
        loss = softmax_cross_entropy_loss(logits, labels, s)
        logp = jax.nn.log_softmax(logits)
        nll = -logp[jnp.arange(8), labels]
        smooth = -jnp.mean(logp, axis=-1)
        ref = (1 - s) * nll + s * smooth
        np.testing.assert_allclose(np.asarray(loss), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)

    def test_grad_matches(self, rng):
        logits = jnp.asarray(rng.randn(16, 20).astype(np.float32))
        labels = jnp.asarray(rng.randint(0, 20, 16))
        for s in (0.0, 0.2):
            g1 = jax.grad(lambda l: jnp.sum(
                softmax_cross_entropy_loss(l, labels, s)))(logits)
            logp = jax.nn.log_softmax
            if s == 0.0:
                ref_fn = lambda l: jnp.sum(
                    -logp(l)[jnp.arange(16), labels])
            else:
                ref_fn = lambda l: jnp.sum(
                    (1 - s) * -logp(l)[jnp.arange(16), labels]
                    + s * -jnp.mean(logp(l), axis=-1))
            g2 = jax.grad(ref_fn)(logits)
            np.testing.assert_allclose(np.asarray(g1), np.asarray(g2),
                                       rtol=1e-4, atol=1e-5)

    def test_ignore_index(self, rng):
        logits = jnp.asarray(rng.randn(4, 10).astype(np.float32))
        labels = jnp.asarray([1, -100, 3, -100])
        loss = softmax_cross_entropy_loss(logits, labels)
        assert float(loss[1]) == 0.0 and float(loss[3]) == 0.0
        g = jax.grad(lambda l: jnp.sum(
            softmax_cross_entropy_loss(l, labels)))(logits)
        np.testing.assert_array_equal(np.asarray(g[1]), 0.0)

    def test_half_to_float(self, rng):
        logits = jnp.asarray(rng.randn(4, 10)).astype(jnp.bfloat16)
        labels = jnp.asarray([1, 2, 3, 4])
        loss = SoftmaxCrossEntropyLoss.apply(logits, labels,
                                             half_to_float=True)
        assert loss.dtype == jnp.float32


class TestMLPAndFusedDense:
    def test_mlp_matches_reference(self, rng):
        m = MLP([16, 32, 8], activation="relu")
        params = m.init_params(jax.random.PRNGKey(0))
        x = jnp.asarray(rng.randn(4, 16).astype(np.float32))
        y = m(params, x)
        h = jax.nn.relu(x @ params["weights"][0].T + params["biases"][0])
        ref = h @ params["weights"][1].T + params["biases"][1]
        np.testing.assert_allclose(np.asarray(y), np.asarray(ref),
                                   rtol=1e-6)

    def test_fused_dense_gelu_dense(self, rng):
        m = FusedDenseGeluDense(16, 64, 16)
        params = m.init_params(jax.random.PRNGKey(1))
        x = jnp.asarray(rng.randn(4, 16).astype(np.float32))
        y = m(params, x)
        h = jax.nn.gelu(x @ params["weight1"].T + params["bias1"],
                        approximate=True)
        ref = h @ params["weight2"].T + params["bias2"]
        np.testing.assert_allclose(np.asarray(y), np.asarray(ref),
                                   rtol=1e-6)

    def test_fused_dense_no_bias(self, rng):
        m = FusedDense(8, 8, bias=False)
        p = m.init_params(jax.random.PRNGKey(2))
        assert "bias" not in p
        x = jnp.ones((2, 8))
        np.testing.assert_allclose(np.asarray(m(p, x)),
                                   np.asarray(x @ p["weight"].T), rtol=1e-6)


class TestPlatformHelpers:
    def test_backend_init_failure_propagates(self, monkeypatch):
        """A chip held by another process must not become a quiet CPU
        run: ``is_tpu_backend`` (and so ``use_pallas``) raises."""
        from apex_tpu.utils import platform

        def boom():
            raise RuntimeError("TPU is already in use by process 1234")

        monkeypatch.setattr(jax, "default_backend", boom)
        monkeypatch.delenv("APEX_TPU_FORCE_PALLAS", raising=False)
        with pytest.raises(RuntimeError, match="already in use"):
            platform.use_pallas()

    def test_compile_cache_is_placed_from_outside(self, monkeypatch):
        import os

        from apex_tpu.utils import platform

        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
        before = jax.config.jax_compilation_cache_dir
        assert platform.setup_compile_cache() == "/somewhere/else"
        assert jax.config.jax_compilation_cache_dir == before  # untouched
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        assert platform.default_compile_cache_dir() == os.path.join(
            root, ".jax_cache")

