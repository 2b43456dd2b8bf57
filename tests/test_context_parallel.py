"""Context parallelism: ring attention + Ulysses all-to-all
(beyond-reference — SURVEY §5 long-context extension).  Parity vs
serial attention on the 8-device mesh, forward AND gradients."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import PartitionSpec as P

from apex_tpu.ops.flash_attention import flash_attention_reference
from apex_tpu.transformer.context_parallel import (ring_attention,
                                                   ulysses_attention)


@pytest.fixture
def rng():
    return np.random.RandomState(0)


def make_qkv(rng, b=1, h=4, s=64, d=16):
    def one():
        return jnp.asarray(rng.randn(b, h, s, d) * 0.3, jnp.float32)
    return one(), one(), one()


def run_sharded(fn, mesh, q, k, v):
    """Shard the sequence dim (axis 2) over 'context' and run fn."""
    spec = P(None, None, "context", None)
    return jax.jit(shard_map(
        fn, mesh=mesh, in_specs=(spec, spec, spec),
        out_specs=spec, check_vma=False))(q, k, v)


class TestRingAttention:
    @pytest.mark.parametrize("causal", [False, True])
    @pytest.mark.parametrize("n_dev", [2, 4])
    def test_matches_serial(self, rng, causal, n_dev):
        q, k, v = make_qkv(rng)
        ref = flash_attention_reference(q, k, v, causal=causal)
        mesh = jax.make_mesh((n_dev,), ("context",))
        got = run_sharded(
            lambda q, k, v: ring_attention(q, k, v, "context",
                                           causal=causal),
            mesh, q, k, v)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=2e-4, atol=2e-5)

    @pytest.mark.parametrize("causal", [False, True])
    def test_grads_match_serial(self, rng, causal):
        q, k, v = make_qkv(rng, s=32)
        mesh = jax.make_mesh((4,), ("context",))

        def serial_loss(q, k, v):
            out = flash_attention_reference(q, k, v, causal=causal)
            return jnp.sum(out ** 2)

        ref_grads = jax.grad(serial_loss, argnums=(0, 1, 2))(q, k, v)

        def ring_loss(q, k, v):
            out = ring_attention(q, k, v, "context", causal=causal)
            return jax.lax.psum(jnp.sum(out.astype(jnp.float32) ** 2),
                                "context")

        spec = P(None, None, "context", None)
        grads = jax.jit(shard_map(
            lambda q, k, v: jax.grad(ring_loss, argnums=(0, 1, 2))(
                q, k, v),
            mesh=mesh, in_specs=(spec, spec, spec),
            out_specs=(spec, spec, spec), check_vma=True))(q, k, v)
        for g, r in zip(grads, ref_grads, strict=True):
            np.testing.assert_allclose(np.asarray(g), np.asarray(r),
                                       rtol=5e-4, atol=5e-5)

    def test_single_device_axis(self, rng):
        q, k, v = make_qkv(rng, s=32)
        mesh = jax.make_mesh((1,), ("context",))
        ref = flash_attention_reference(q, k, v, causal=True)
        got = run_sharded(
            lambda q, k, v: ring_attention(q, k, v, "context",
                                           causal=True),
            mesh, q, k, v)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=2e-4, atol=2e-5)

    def test_remat_off_matches(self, rng):
        q, k, v = make_qkv(rng, s=32)
        mesh = jax.make_mesh((4,), ("context",))
        a = run_sharded(
            lambda q, k, v: ring_attention(q, k, v, "context",
                                           remat=False), mesh, q, k, v)
        b = run_sharded(
            lambda q, k, v: ring_attention(q, k, v, "context",
                                           remat=True), mesh, q, k, v)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-6)


class TestGPTContextParallel:
    """The flagship model with its sequence sharded over a context axis:
    loss AND grads must match the serial model on the same batch."""

    @pytest.mark.parametrize("mechanism", ["ring", "ulysses"])
    def test_loss_and_grads_match_serial(self, rng, mechanism):
        from apex_tpu.models.gpt import GPTConfig, GPTModel

        kw = dict(vocab_size=32, hidden_size=16, num_layers=2,
                  num_attention_heads=4, max_seq_len=32)
        serial = GPTModel(GPTConfig(**kw))
        params = serial.init_params(jax.random.PRNGKey(0))
        tokens = jnp.asarray(rng.randint(0, 32, (2, 32)))
        targets = jnp.asarray(rng.randint(0, 32, (2, 32)))
        ref_loss = float(jax.jit(serial.loss)(params, tokens, targets))
        ref_grads = jax.jit(jax.grad(serial.loss))(params, tokens, targets)

        cp = GPTModel(GPTConfig(context_axis="context",
                                context_mechanism=mechanism, **kw))
        mesh = jax.make_mesh((4,), ("context",))
        seq_spec = P(None, "context")

        from apex_tpu.utils.collectives import psum_if_varying

        def step(params, tokens, targets):
            loss, grads = jax.value_and_grad(cp.loss)(params, tokens,
                                                      targets)
            # leaves still varying over the ring hold partial sums; the
            # invariant ones were auto-psummed (same staging as DP)
            return loss, psum_if_varying(grads, "context")

        loss, grads = jax.jit(shard_map(
            step, mesh=mesh, in_specs=(P(), seq_spec, seq_spec),
            out_specs=(P(), P()), check_vma=True))(params, tokens, targets)
        np.testing.assert_allclose(float(loss), ref_loss, rtol=1e-5)
        for g, r in zip(jax.tree_util.tree_leaves(grads),
                        jax.tree_util.tree_leaves(ref_grads),
                        strict=True):
            np.testing.assert_allclose(np.asarray(g), np.asarray(r),
                                       rtol=5e-4, atol=1e-5)

    def test_learned_positions_cp(self, rng):
        """Non-rotary (learned position embedding) path under CP: the
        shard offset must select the right embedding rows."""
        from apex_tpu.models.gpt import GPTConfig, GPTModel

        kw = dict(vocab_size=32, hidden_size=16, num_layers=1,
                  num_attention_heads=4, max_seq_len=32, rotary=False)
        serial = GPTModel(GPTConfig(**kw))
        params = serial.init_params(jax.random.PRNGKey(1))
        tokens = jnp.asarray(rng.randint(0, 32, (2, 32)))
        targets = jnp.asarray(rng.randint(0, 32, (2, 32)))
        ref = float(jax.jit(serial.loss)(params, tokens, targets))

        cp = GPTModel(GPTConfig(context_axis="context", **kw))
        mesh = jax.make_mesh((4,), ("context",))
        seq_spec = P(None, "context")
        loss = jax.jit(shard_map(
            cp.loss, mesh=mesh, in_specs=(P(), seq_spec, seq_spec),
            out_specs=P(), check_vma=False))(params, tokens, targets)
        np.testing.assert_allclose(float(loss), ref, rtol=1e-5)


class TestUlyssesAttention:
    @pytest.mark.parametrize("causal", [False, True])
    def test_matches_serial(self, rng, causal):
        q, k, v = make_qkv(rng, h=8)
        ref = flash_attention_reference(q, k, v, causal=causal)
        mesh = jax.make_mesh((4,), ("context",))
        got = run_sharded(
            lambda q, k, v: ulysses_attention(q, k, v, "context",
                                              causal=causal),
            mesh, q, k, v)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=2e-4, atol=2e-5)

    def test_grads_match_serial(self, rng):
        q, k, v = make_qkv(rng, h=4, s=32)
        mesh = jax.make_mesh((2,), ("context",))

        def serial_loss(q, k, v):
            out = flash_attention_reference(q, k, v, causal=True)
            return jnp.sum(out ** 2)

        ref_grads = jax.grad(serial_loss, argnums=(0, 1, 2))(q, k, v)

        def ul_loss(q, k, v):
            out = ulysses_attention(q, k, v, "context", causal=True)
            return jax.lax.psum(jnp.sum(out.astype(jnp.float32) ** 2),
                                "context")

        spec = P(None, None, "context", None)
        grads = jax.jit(shard_map(
            lambda q, k, v: jax.grad(ul_loss, argnums=(0, 1, 2))(q, k, v),
            mesh=mesh, in_specs=(spec, spec, spec),
            out_specs=(spec, spec, spec), check_vma=True))(q, k, v)
        for g, r in zip(grads, ref_grads, strict=True):
            np.testing.assert_allclose(np.asarray(g), np.asarray(r),
                                       rtol=5e-4, atol=5e-5)

    def test_heads_must_divide(self, rng):
        q, k, v = make_qkv(rng, h=2)
        mesh = jax.make_mesh((4,), ("context",))
        with pytest.raises(ValueError,
                           match="divisible by the context axis"):
            run_sharded(
                lambda q, k, v: ulysses_attention(q, k, v, "context"),
                mesh, q, k, v)
