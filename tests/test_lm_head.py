"""Fused logit-free LM-head cross entropy vs the materialized reference
(pattern: the flash-attention suite — fused op against the unfused
baseline on identical inputs, fwd and bwd; Pallas runs in interpret mode
on CPU, the on-chip lane re-runs the parity on hardware)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.ops.lm_head import (
    fused_linear_cross_entropy,
    fused_linear_cross_entropy_reference,
)
from apex_tpu.utils import set_force_pallas


@pytest.fixture(autouse=True)
def _force_pallas():
    set_force_pallas(True)
    yield
    set_force_pallas(None)


def _case(rng, n, h, v, dtype=jnp.float32):
    x = jnp.asarray(rng.randn(n, h).astype(np.float32) * 0.5, dtype)
    w = jnp.asarray(rng.randn(v, h).astype(np.float32) * 0.1, dtype)
    t = jnp.asarray(rng.randint(0, v, (n,)))
    return x, w, t


class TestFusedLMHead:
    def test_forward_matches_reference(self, rng):
        x, w, t = _case(rng, 256, 128, 1024)
        out = fused_linear_cross_entropy(x, w, t, block_t=64, block_v=256)
        ref = fused_linear_cross_entropy_reference(x, w, t)
        np.testing.assert_allclose(out, ref, rtol=2e-5, atol=2e-5)

    def test_non_multiple_shapes(self, rng):
        # N, V, H all off the block grid: padding must wash out
        x, w, t = _case(rng, 200, 96, 1000)
        out = fused_linear_cross_entropy(x, w, t, block_t=64, block_v=128)
        ref = fused_linear_cross_entropy_reference(x, w, t)
        np.testing.assert_allclose(out, ref, rtol=2e-5, atol=2e-5)

    def test_grads_match_reference(self, rng):
        x, w, t = _case(rng, 192, 128, 512)

        def f(x, w):
            return jnp.mean(fused_linear_cross_entropy(
                x, w, t, block_t=64, block_v=128))

        def r(x, w):
            return jnp.mean(fused_linear_cross_entropy_reference(x, w, t))

        gx, gw = jax.grad(f, argnums=(0, 1))(x, w)
        rx, rw = jax.grad(r, argnums=(0, 1))(x, w)
        np.testing.assert_allclose(gx, rx, rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(gw, rw, rtol=1e-5, atol=1e-6)

    def test_weighted_cotangent(self, rng):
        # non-uniform upstream cotangent (e.g. masked-mean losses)
        x, w, t = _case(rng, 128, 64, 256)
        coef = jnp.asarray(rng.rand(128).astype(np.float32))

        def f(x, w):
            return jnp.sum(coef * fused_linear_cross_entropy(
                x, w, t, block_t=64, block_v=128))

        def r(x, w):
            return jnp.sum(
                coef * fused_linear_cross_entropy_reference(x, w, t))

        gx, gw = jax.grad(f, argnums=(0, 1))(x, w)
        rx, rw = jax.grad(r, argnums=(0, 1))(x, w)
        np.testing.assert_allclose(gx, rx, rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(gw, rw, rtol=1e-5, atol=1e-6)

    def test_bf16_inputs(self, rng):
        x, w, t = _case(rng, 128, 128, 512, jnp.bfloat16)
        out = fused_linear_cross_entropy(x, w, t, block_t=64, block_v=128)
        ref = fused_linear_cross_entropy_reference(x, w, t)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-2, atol=2e-2)
        gx = jax.grad(lambda x: jnp.mean(fused_linear_cross_entropy(
            x, w, t, block_t=64, block_v=128)))(x)
        assert gx.dtype == jnp.bfloat16
        assert bool(jnp.all(jnp.isfinite(gx.astype(jnp.float32))))

    def test_jit_grad_composes(self, rng):
        x, w, t = _case(rng, 128, 64, 256)
        g = jax.jit(jax.grad(lambda x: jnp.sum(fused_linear_cross_entropy(
            x, w, t, block_t=64, block_v=128))))(x)
        assert np.all(np.isfinite(g))


class TestGPTFusedHead:
    """The flagship integration: fused_lm_head=True (default) must match
    the materialized head exactly, serial and pipelined."""

    def _cfg(self, fused, **kw):
        from apex_tpu.models.gpt import GPTConfig
        base = dict(vocab_size=64, hidden_size=32, num_layers=2,
                    num_attention_heads=2, max_seq_len=16,
                    fused_lm_head=fused)
        base.update(kw)
        return GPTConfig(**base)

    @pytest.mark.slow
    def test_serial_loss_and_grads_match(self, rng):
        from apex_tpu.models.gpt import GPTModel

        tokens = jnp.asarray(rng.randint(0, 64, (2, 16)))
        out = {}
        for fused in (True, False):
            m = GPTModel(self._cfg(fused))
            p = m.init_params(jax.random.PRNGKey(0))
            loss, g = jax.value_and_grad(m.loss)(p, tokens, tokens)
            out[fused] = (float(loss), g)
        np.testing.assert_allclose(out[True][0], out[False][0], rtol=1e-5)
        for a, b in zip(jax.tree_util.tree_leaves(out[True][1]),
                        jax.tree_util.tree_leaves(out[False][1])):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=1e-6)

    def test_pipeline_head_matches_serial(self, rng):
        from jax.sharding import PartitionSpec as P

        from jax import shard_map

        from apex_tpu.models.gpt import (GPTModel, pack_for_shard_map,
                                         pipeline_step)

        # fallback path: interpret-mode Pallas inside the pipeline's
        # shard_map trips kernel-INTERIOR vma strictness (a CPU-lane
        # artifact — compiled kernels are opaque inside; operand/output
        # vma is declared via sds_like and exercised by the ring/on-chip
        # lanes).  This lane pins the pipeline+fused-head integration.
        set_force_pallas(False)
        m = GPTModel(self._cfg(True))
        params = m.init_params(jax.random.PRNGKey(1))
        M, mb, seq = 2, 2, 16
        tokens = jnp.asarray(rng.randint(0, 64, (M * mb, seq)))
        ref = float(jax.jit(m.loss)(params, tokens, tokens))
        packed, in_specs, local_fn, repack_fn = pack_for_shard_map(
            m, params, n_stages=2, tensor_axis=None)
        mesh = jax.make_mesh((2,), ("pipe",), devices=jax.devices()[:2])
        loss = float(jax.jit(shard_map(
            lambda sp, tk, tg: pipeline_step(
                m, local_fn(sp), tk.reshape(M, mb, seq),
                tg.reshape(M, mb, seq), pipe_axis="pipe")[0],
            mesh=mesh, in_specs=(in_specs, P(), P()),
            out_specs=P(), check_vma=False))(packed, tokens, tokens))
        np.testing.assert_allclose(loss, ref, rtol=1e-5)


class TestBertFusedHead:
    @pytest.mark.slow
    def test_mlm_loss_fused_matches_materialized(self, rng):
        from apex_tpu.models.bert import BertConfig, BertModel

        kw = dict(vocab_size=128, hidden_size=32, num_layers=2,
                  num_attention_heads=2, max_seq_len=16)
        tokens = jnp.asarray(rng.randint(0, 128, (2, 16)))
        labels = np.where(rng.rand(2, 16) < 0.3,
                          rng.randint(0, 128, (2, 16)), -1)
        labels = jnp.asarray(labels)
        out = {}
        for fused in (True, False):
            m = BertModel(BertConfig(fused_lm_head=fused, **kw))
            p = m.init_params(jax.random.PRNGKey(0))
            loss, g = jax.value_and_grad(m.loss)(p, tokens, labels)
            out[fused] = (float(loss), g)
        np.testing.assert_allclose(out[True][0], out[False][0], rtol=1e-5)
        for a, b in zip(jax.tree_util.tree_leaves(out[True][1]),
                        jax.tree_util.tree_leaves(out[False][1])):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=1e-6)
