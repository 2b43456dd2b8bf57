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


def _pallas_operand_shapes(fn, *args):
    """Shapes of q as each ``pallas_call`` under ``fn`` receives it."""
    def calls(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                yield eqn
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from calls(sub)
    # operands: kv lengths, seed, then q
    return [eqn.invars[2].aval.shape
            for eqn in calls(jax.make_jaxpr(fn)(*args).jaxpr)]


def _bshd(t):
    return t.transpose(0, 2, 1, 3)


class TestFlashRows:
    """``flash_attention_bshd``: ``(b, s, h, d)`` operands read as
    ``(b, s, h*d)`` rows, ``128 // d`` heads to a 128-lane tile, no pad
    and no transpose; every shape outside that rule takes
    ``flash_attention``'s operands and kernels."""

    # b, h, s, d, causal, kv_seqlens, block, dtype
    CASES = [
        # one block each way: dense (BERT's form), causal (a prompt's)
        (2, 4, 256, 64, False, None, 1024, jnp.float32),
        (2, 4, 256, 64, True, None, 1024, jnp.float32),
        # several blocks each way, lengths ragged per batch row
        (2, 2, 256, 64, False, (256, 77), 128, jnp.float32),
        (2, 2, 256, 64, True, (200, 31), 128, jnp.float32),
        # 640 is five blocks of 128 and no multiple of a larger block
        (1, 2, 640, 64, True, None, 1024, jnp.float32),
        (1, 2, 640, 64, False, (333,), 1024, jnp.float32),
        # 200 is padded to 256: the padded query rows are masked
        (1, 2, 200, 64, True, None, 1024, jnp.float32),
        # four heads of 32 to a tile
        (2, 4, 256, 32, False, None, 1024, jnp.float32),
        (2, 4, 256, 32, True, (256, 100), 128, jnp.float32),
        (1, 8, 640, 32, False, (500,), 1024, jnp.float32),
        (2, 2, 256, 64, True, None, 128, jnp.bfloat16),
        (2, 2, 256, 64, False, (256, 90), 1024, jnp.bfloat16),
        (2, 4, 128, 32, False, (128, 50), 1024, jnp.bfloat16),
    ]

    @pytest.mark.parametrize(
        "b,h,s,d,causal,lens,block,dtype", CASES,
        ids=[f"b{c[0]}h{c[1]}s{c[2]}d{c[3]}{'c' if c[4] else 'n'}"
             f"{'r' if c[5] else 'f'}blk{c[6]}{c[7].__name__}"
             for c in CASES])
    def test_rows_match_reference(self, rng, b, h, s, d, causal, lens,
                                  block, dtype):
        from apex_tpu.ops.flash_attention import flash_attention_bshd
        q, k, v = (_bshd(t) for t in _inputs(rng, b, h, s, s, d, dtype))
        lens = None if lens is None else jnp.asarray(lens, jnp.int32)
        assert _pallas_operand_shapes(
            lambda q, k, v: flash_attention_bshd(
                q, k, v, causal=causal, kv_seqlens=lens, block_q=block,
                block_k=block), q, k, v)[0][::2] == (b, h * d)

        def fused(q, k, v):
            out = flash_attention_bshd(q, k, v, causal=causal,
                                       kv_seqlens=lens, block_q=block,
                                       block_k=block)
            return jnp.sum(out.astype(jnp.float32) ** 2), out

        def ref(q, k, v):
            out = _bshd(flash_attention_reference(
                _bshd(q), _bshd(k), _bshd(v), causal=causal,
                kv_seqlens=lens))
            return jnp.sum(out.astype(jnp.float32) ** 2), out

        (_, out), grads = jax.value_and_grad(
            fused, argnums=(0, 1, 2), has_aux=True)(q, k, v)
        (_, want), want_grads = jax.value_and_grad(
            ref, argnums=(0, 1, 2), has_aux=True)(q, k, v)
        tol = dict(rtol=5e-2, atol=5e-2) if dtype == jnp.bfloat16 \
            else dict(rtol=5e-5, atol=5e-5)
        assert out.shape == (b, s, h, d) and out.dtype == dtype
        for got, exp in zip((out, *grads), (want, *want_grads)):
            np.testing.assert_allclose(np.asarray(got, np.float32),
                                       np.asarray(exp, np.float32), **tol)

    @pytest.mark.parametrize("h,d,rows", [
        (2, 64, True), (4, 32, True), (8, 16, True), (16, 64, True),
        # a head as wide as a tile or wider, a head that straddles two
        # tiles, a row that is no whole number of tiles
        (2, 128, False), (1, 256, False), (4, 96, False), (8, 80, False),
        (3, 64, False), (2, 32, False)])
    def test_the_shape_chooses_the_tile(self, rng, h, d, rows):
        from apex_tpu.ops.flash_attention import flash_attention_bshd
        b, s = 2, 128
        q, k, v = (_bshd(t)
                   for t in _inputs(rng, b, h, s, s, d, jnp.float32))

        def fwd_bwd(entry, *operands):
            return jax.vjp(lambda *a: entry(*a, causal=True), *operands)[1](
                jnp.ones_like(operands[0]))

        shapes = _pallas_operand_shapes(
            lambda *a: fwd_bwd(flash_attention_bshd, *a), q, k, v)
        assert len(shapes) == 3                # forward, dq, dk and dv
        want = (b, s, h * d) if rows else (b * h, s, -(-d // 128) * 128)
        assert set(shapes) == {want}
        # both entries, the same operands, the same answers
        for got, exp in zip(
                fwd_bwd(flash_attention_bshd, q, k, v),
                fwd_bwd(flash_attention, _bshd(q), _bshd(k), _bshd(v))):
            np.testing.assert_allclose(got, _bshd(exp), rtol=5e-5,
                                       atol=5e-5)

    @pytest.mark.parametrize("h,d", [(4, 64), (8, 32)])
    @pytest.mark.parametrize("causal", [False, True])
    def test_dropout_mask_is_keyed_on_batch_and_head(self, rng, h, d,
                                                     causal):
        """The kept positions are ``dropout_keep_scale``'s for every
        ``(b, head)``: inside a tile the head is ``tile * (128 // d) + j``,
        not the grid index."""
        from apex_tpu.ops.flash_attention import (dropout_keep_scale,
                                                  flash_attention_bshd)
        b, s, rate, seed = 2, 256, 0.25, 4321
        q, k, v = (_bshd(t)
                   for t in _inputs(rng, b, h, s, s, d, jnp.float32))
        mask = dropout_keep_scale(seed, b * h, s, s, rate).reshape(
            b, h, s, s)

        def fused(q, k, v):
            return jnp.sum(flash_attention_bshd(
                q, k, v, causal=causal, dropout=rate, dropout_seed=seed,
                block_q=128, block_k=128) ** 2)

        def ref(q, k, v):
            return jnp.sum(flash_attention_reference(
                _bshd(q), _bshd(k), _bshd(v), causal=causal,
                dropout_mask=mask) ** 2)

        got = jax.value_and_grad(fused, argnums=(0, 1, 2))(q, k, v)
        want = jax.value_and_grad(ref, argnums=(0, 1, 2))(q, k, v)
        for g, w in zip(jax.tree_util.tree_leaves(got),
                        jax.tree_util.tree_leaves(want)):
            np.testing.assert_allclose(g, w, rtol=1e-4, atol=1e-4)
