"""Flash attention: Pallas kernel vs materialized-scores reference.

Mirrors the reference's contrib attention tests
(``apex/contrib/test/fmha/test_fmha.py``,
``test/multihead_attn/test_self_multihead_attn.py``): the fused op is
compared against the unfused reference on the same inputs, fwd and bwd,
at dtype-appropriate tolerances.  The Pallas path runs in interpret mode
on CPU; the same tests re-run on hardware via the on-chip lane.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.ops.flash_attention import (
    flash_attention,
    flash_attention_reference,
)
from apex_tpu.utils import set_force_pallas


@pytest.fixture(autouse=True)
def _force_pallas():
    set_force_pallas(True)
    yield
    set_force_pallas(None)


def _inputs(rng, b, h, sq, sk, d, dtype):
    q = jnp.asarray(rng.randn(b, h, sq, d), dtype)
    k = jnp.asarray(rng.randn(b, h, sk, d), dtype)
    v = jnp.asarray(rng.randn(b, h, sk, d), dtype)
    return q, k, v


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 \
        else dict(rtol=2e-5, atol=2e-5)


class TestFlashForward:
    @pytest.mark.parametrize("causal", [False, True])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_matches_reference(self, rng, causal, dtype):
        q, k, v = _inputs(rng, 2, 3, 256, 256, 64, dtype)
        out = flash_attention(q, k, v, causal=causal)
        ref = flash_attention_reference(q, k, v, causal=causal)
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(ref, np.float32),
                                   **_tol(dtype))

    def test_non_multiple_seq(self, rng):
        # seq not a multiple of the 128 block: padding must wash out
        q, k, v = _inputs(rng, 1, 2, 200, 200, 48, jnp.float32)
        out = flash_attention(q, k, v, causal=True)
        ref = flash_attention_reference(q, k, v, causal=True)
        np.testing.assert_allclose(out, ref, rtol=2e-5, atol=2e-5)

    def test_cross_attention_seqs(self, rng):
        q, k, v = _inputs(rng, 2, 2, 128, 384, 64, jnp.float32)
        out = flash_attention(q, k, v)
        ref = flash_attention_reference(q, k, v)
        np.testing.assert_allclose(out, ref, rtol=2e-5, atol=2e-5)

    def test_kv_seqlens_padding(self, rng):
        q, k, v = _inputs(rng, 3, 2, 128, 256, 32, jnp.float32)
        lens = jnp.asarray([256, 100, 17], jnp.int32)
        out = flash_attention(q, k, v, kv_seqlens=lens)
        ref = flash_attention_reference(q, k, v, kv_seqlens=lens)
        np.testing.assert_allclose(out, ref, rtol=2e-5, atol=2e-5)

    def test_custom_scale(self, rng):
        q, k, v = _inputs(rng, 1, 2, 128, 128, 64, jnp.float32)
        out = flash_attention(q, k, v, softmax_scale=0.5)
        ref = flash_attention_reference(q, k, v, softmax_scale=0.5)
        np.testing.assert_allclose(out, ref, rtol=2e-5, atol=2e-5)


class TestFlashBackward:
    @pytest.mark.parametrize("causal", [False, True])
    def test_grads_match_reference(self, rng, causal):
        q, k, v = _inputs(rng, 2, 2, 256, 256, 64, jnp.float32)

        def fused(q, k, v):
            return jnp.sum(flash_attention(q, k, v, causal=causal) ** 2)

        def ref(q, k, v):
            return jnp.sum(
                flash_attention_reference(q, k, v, causal=causal) ** 2)

        g_fused = jax.grad(fused, argnums=(0, 1, 2))(q, k, v)
        g_ref = jax.grad(ref, argnums=(0, 1, 2))(q, k, v)
        for gf, gr in zip(g_fused, g_ref):
            np.testing.assert_allclose(gf, gr, rtol=5e-5, atol=5e-5)

    def test_grads_non_multiple_seq(self, rng):
        q, k, v = _inputs(rng, 1, 2, 200, 200, 48, jnp.float32)

        def fused(q, k, v):
            return jnp.sum(flash_attention(q, k, v, causal=True) ** 2)

        def ref(q, k, v):
            return jnp.sum(
                flash_attention_reference(q, k, v, causal=True) ** 2)

        g_fused = jax.grad(fused, argnums=(0, 1, 2))(q, k, v)
        g_ref = jax.grad(ref, argnums=(0, 1, 2))(q, k, v)
        for gf, gr in zip(g_fused, g_ref):
            np.testing.assert_allclose(gf, gr, rtol=5e-5, atol=5e-5)

    def test_grads_kv_seqlens(self, rng):
        q, k, v = _inputs(rng, 2, 2, 128, 256, 32, jnp.float32)
        lens = jnp.asarray([256, 77], jnp.int32)

        def fused(q, k, v):
            return jnp.sum(flash_attention(q, k, v, kv_seqlens=lens) ** 2)

        def ref(q, k, v):
            return jnp.sum(
                flash_attention_reference(q, k, v, kv_seqlens=lens) ** 2)

        g_fused = jax.grad(fused, argnums=(0, 1, 2))(q, k, v)
        g_ref = jax.grad(ref, argnums=(0, 1, 2))(q, k, v)
        for gf, gr in zip(g_fused, g_ref):
            np.testing.assert_allclose(gf, gr, rtol=5e-5, atol=5e-5)

    def test_grads_bf16(self, rng):
        q, k, v = _inputs(rng, 1, 2, 128, 128, 64, jnp.bfloat16)

        def fused(q, k, v):
            return jnp.sum(
                flash_attention(q, k, v, causal=True).astype(jnp.float32))

        def ref(q, k, v):
            return jnp.sum(flash_attention_reference(
                q, k, v, causal=True).astype(jnp.float32))

        g_fused = jax.grad(fused, argnums=(0, 1, 2))(q, k, v)
        g_ref = jax.grad(ref, argnums=(0, 1, 2))(q, k, v)
        for gf, gr in zip(g_fused, g_ref):
            np.testing.assert_allclose(np.asarray(gf, np.float32),
                                       np.asarray(gr, np.float32),
                                       rtol=5e-2, atol=5e-2)

    def test_jit_grad_composes(self, rng):
        q, k, v = _inputs(rng, 1, 1, 128, 128, 64, jnp.float32)
        g = jax.jit(jax.grad(
            lambda q: jnp.sum(flash_attention(q, k, v, causal=True))))(q)
        assert np.all(np.isfinite(g))


class TestFusedDropout:
    """Fused probability dropout (reference: apex's philox-fused attention
    dropout, ``apex/contrib/csrc/multihead_attn/dropout.cuh``): the keep
    mask is a counter-hash pure function of (seed, bh, q_pos, k_pos), so
    the kernel's mask can be replayed densely and the fused path compared
    EXACTLY (not just statistically) against the materialized reference."""

    RATE, SEED = 0.2, 987

    def _mask(self, b, h, sq, sk):
        from apex_tpu.ops.flash_attention import dropout_keep_scale
        return dropout_keep_scale(self.SEED, b * h, sq, sk,
                                  self.RATE).reshape(b, h, sq, sk)

    @pytest.mark.parametrize("causal", [False, True])
    def test_forward_matches_replayed_mask(self, rng, causal):
        q, k, v = _inputs(rng, 2, 3, 256, 256, 64, jnp.float32)
        out = flash_attention(q, k, v, causal=causal, dropout=self.RATE,
                              dropout_seed=self.SEED)
        ref = flash_attention_reference(q, k, v, causal=causal,
                                        dropout_mask=self._mask(2, 3, 256,
                                                                256))
        np.testing.assert_allclose(out, ref, rtol=5e-5, atol=5e-5)

    def test_grads_match_replayed_mask(self, rng):
        q, k, v = _inputs(rng, 2, 2, 256, 256, 64, jnp.float32)
        mask = self._mask(2, 2, 256, 256)

        def fused(q, k, v):
            return jnp.sum(flash_attention(
                q, k, v, causal=True, dropout=self.RATE,
                dropout_seed=self.SEED) ** 2)

        def ref(q, k, v):
            return jnp.sum(flash_attention_reference(
                q, k, v, causal=True, dropout_mask=mask) ** 2)

        g_fused = jax.grad(fused, argnums=(0, 1, 2))(q, k, v)
        g_ref = jax.grad(ref, argnums=(0, 1, 2))(q, k, v)
        for gf, gr in zip(g_fused, g_ref):
            np.testing.assert_allclose(gf, gr, rtol=1e-4, atol=1e-4)

    def test_deterministic_and_seed_sensitive(self, rng):
        q, k, v = _inputs(rng, 1, 2, 128, 128, 32, jnp.float32)
        a = flash_attention(q, k, v, dropout=self.RATE, dropout_seed=7)
        b = flash_attention(q, k, v, dropout=self.RATE, dropout_seed=7)
        c = flash_attention(q, k, v, dropout=self.RATE, dropout_seed=8)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        assert float(jnp.max(jnp.abs(a - c))) > 0.0

    def test_block_size_invariant(self, rng):
        # the mask hashes GLOBAL positions, so retiling cannot change it
        q, k, v = _inputs(rng, 1, 2, 256, 256, 64, jnp.float32)
        a = flash_attention(q, k, v, dropout=self.RATE,
                            dropout_seed=self.SEED, block_q=128,
                            block_k=128)
        b = flash_attention(q, k, v, dropout=self.RATE,
                            dropout_seed=self.SEED, block_q=64,
                            block_k=128)
        np.testing.assert_allclose(a, b, rtol=5e-5, atol=5e-5)

    def test_keep_statistics(self):
        from apex_tpu.ops.flash_attention import dropout_keep_scale
        m = dropout_keep_scale(42, 4, 512, 512, 0.3)
        keep = float(jnp.mean(m > 0))
        assert abs(keep - 0.7) < 0.01, keep
        # inverted dropout: E[D] == 1
        assert abs(float(jnp.mean(m)) - 1.0) < 0.02

    @pytest.mark.slow
    def test_mean_preserving_vs_no_dropout(self, rng):
        # E over masks of the dropped output == undropped output, row by
        # row (inverted dropout scales keeps by 1/(1-r)); with many seeds
        # the average converges
        q, k, v = _inputs(rng, 1, 1, 128, 128, 32, jnp.float32)
        base = flash_attention(q, k, v)
        acc = jnp.zeros_like(base)
        n = 32
        for s in range(n):
            acc = acc + flash_attention(q, k, v, dropout=0.5,
                                        dropout_seed=s)
        err = float(jnp.max(jnp.abs(acc / n - base)))
        assert err < 0.35, err    # 1/sqrt(32) Monte-Carlo band

    def test_dropout_needs_seed(self, rng):
        q, k, v = _inputs(rng, 1, 1, 128, 128, 32, jnp.float32)
        with pytest.raises(ValueError, match="dropout_seed"):
            flash_attention(q, k, v, dropout=0.5)
        with pytest.raises(ValueError, match="dropout must be"):
            flash_attention(q, k, v, dropout=1.5, dropout_seed=0)

    def test_fallback_path_identical_mask(self, rng):
        # the jnp fallback replays the SAME hash mask the kernel uses —
        # bit-identical dropout pattern on every backend
        q, k, v = _inputs(rng, 1, 2, 128, 128, 32, jnp.float32)
        fused = flash_attention(q, k, v, dropout=self.RATE,
                                dropout_seed=self.SEED)
        set_force_pallas(False)
        try:
            fallback = flash_attention(q, k, v, dropout=self.RATE,
                                       dropout_seed=self.SEED)
        finally:
            set_force_pallas(True)
        np.testing.assert_allclose(fused, fallback, rtol=5e-5, atol=5e-5)
