"""Sequence parallelism + chunked overlap rings vs the replicated TP path.

Megatron SP (the ISSUE 3 tentpole): activations between TP regions stay
sequence-sharded (LayerNorm/dropout/residual on ``(b, s/t, h)``), the
column edge all-gathers along seq and the row edge reduce-scatters; the
``overlap_chunks`` knob replaces each gather→GEMM / GEMM→reduce-scatter
pair with a ``ppermute`` ring whose custom VJP rings the backward too.

Gradient references are the SERIAL model, not the replicated-TP
shard_map run: on this JAX generation the cotangents of replicated
(``P()``) leaves come back as per-device partials from a shard_map body
(no automatic psum of invariant grads), so replicated-TP grads-in-body
are themselves unreliable — the SP path carries explicit
identity-fwd/psum-bwd syncs on the sequence-region LN/bias params
(Megatron's SP grad allreduce) and matches the serial model exactly.
Forward losses ARE compared bitwise against the replicated TP run.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from apex_tpu.models.gpt import GPTConfig, GPTModel, pack_for_shard_map
from apex_tpu.transformer.tensor_parallel import mappings as M
from jax import shard_map


def tiny_cfg(**kw):
    base = dict(vocab_size=32, hidden_size=16, num_layers=2,
                num_attention_heads=4, max_seq_len=8)
    base.update(kw)
    return GPTConfig(**base)


def make_data(rng, cfg, batch, seq):
    tokens = jnp.asarray(rng.randint(0, cfg.vocab_size, (batch, seq)))
    targets = jnp.asarray(rng.randint(0, cfg.vocab_size, (batch, seq)))
    return tokens, targets


def tree_allclose(a, b, rtol=1e-5, atol=1e-6):
    for x, y in zip(jax.tree_util.tree_leaves(a),
                    jax.tree_util.tree_leaves(b)):
        np.testing.assert_allclose(np.asarray(x, np.float32),
                                   np.asarray(y, np.float32),
                                   rtol=rtol, atol=atol)


# -- sequence mappings --------------------------------------------------------

class TestSequenceMappings:
    def test_scatter_gather_round_trip(self, rng):
        x = jnp.asarray(rng.randn(2, 8, 6).astype(np.float32))
        mesh = jax.make_mesh((4,), ("model",))

        def body(x):
            s = M.scatter_to_sequence_parallel_region(x, "model", 1)
            assert s.shape == (2, 2, 6)
            return M.gather_from_sequence_parallel_region(s, "model", 1)

        y = jax.jit(shard_map(body, mesh=mesh, in_specs=(P(),),
                              out_specs=P(), check_vma=False))(x)
        np.testing.assert_array_equal(np.asarray(y), np.asarray(x))

    def test_gather_bwd_is_reduce_scatter(self, rng):
        """d(sum over devices of <gather(x), c_dev>)/dx = seq shard of the
        summed cotangents — the reduce-scatter pairing."""
        t = 4
        x = jnp.asarray(rng.randn(t, 2, 6).astype(np.float32))
        c = jnp.asarray(rng.randn(t, t * 2, 6).astype(np.float32))
        mesh = jax.make_mesh((t,), ("model",))

        def body(x, c):
            x, c = x[0], c[0]
            f = lambda x: jnp.sum(
                M.gather_from_sequence_parallel_region(x, "model", 0) * c)
            return jax.grad(f)(x)[None]

        g = jax.jit(shard_map(body, mesh=mesh,
                              in_specs=(P("model"), P("model")),
                              out_specs=P("model"), check_vma=False))(x, c)
        ref = np.sum(np.asarray(c), axis=0).reshape(t, 2, 6)
        np.testing.assert_allclose(np.asarray(g), ref, rtol=1e-6,
                                   atol=1e-6)


# -- overlap rings vs monolithic GEMM+collective ------------------------------

class TestOverlapRings:
    """Ring forms must match the (collective, GEMM) pair they replace —
    forward and backward, at every chunk count."""

    @pytest.mark.parametrize("t", [2, 4])
    @pytest.mark.parametrize("chunks", [1, 2])
    def test_column_ring_fwd_bitwise(self, rng, assert_ulp_close, t, chunks):
        x = jnp.asarray(rng.randn(2, 8, 16).astype(np.float32))
        w = jnp.asarray(rng.randn(24, 16).astype(np.float32))
        ref = np.asarray(x @ w.T)      # (2, 8, 24)
        mesh = jax.make_mesh((t,), ("model",))

        y = jax.jit(shard_map(
            lambda xs, ws: M.column_parallel_linear_overlap(
                xs, ws, "model", 1, chunks),
            mesh=mesh, in_specs=(P(None, "model"), P("model")),
            out_specs=P(None, None, "model"), check_vma=False))(x, w)
        # each ring step writes gather-shard @ W_local verbatim — the
        # decomposition reorders no contraction; the ring's GEMMs and the
        # one GEMM are still two programs (``conftest.py``)
        assert_ulp_close(y, ref)

    @pytest.mark.parametrize("t", [2, 4])
    @pytest.mark.parametrize("chunks", [1, 2])
    def test_column_ring_bwd(self, rng, t, chunks):
        x = jnp.asarray(rng.randn(2, 8, 16).astype(np.float32))
        w = jnp.asarray(rng.randn(24, 16).astype(np.float32))
        c = jnp.asarray(rng.randn(2, 8, 24).astype(np.float32))
        mesh = jax.make_mesh((t,), ("model",))

        def body(xs, ws, cs):
            f = lambda xs, ws: jnp.sum(
                M.column_parallel_linear_overlap(xs, ws, "model", 1,
                                                 chunks) * cs)
            return jax.grad(f, argnums=(0, 1))(xs, ws)

        dx, dw = jax.jit(shard_map(
            body, mesh=mesh,
            in_specs=(P(None, "model"), P("model"),
                      P(None, None, "model")),
            out_specs=(P(None, "model"), P("model")),
            check_vma=False))(x, w, c)
        ref_dx, ref_dw = jax.grad(
            lambda x, w: jnp.sum((x @ w.T) * c), argnums=(0, 1))(x, w)
        np.testing.assert_allclose(np.asarray(dx), np.asarray(ref_dx),
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(np.asarray(dw), np.asarray(ref_dw),
                                   rtol=1e-5, atol=1e-5)

    @pytest.mark.parametrize("t", [2, 4])
    @pytest.mark.parametrize("chunks", [1, 2])
    def test_row_ring_fwd(self, rng, t, chunks):
        x = jnp.asarray(rng.randn(2, 8, 16).astype(np.float32))
        w = jnp.asarray(rng.randn(24, 16).astype(np.float32))
        ref = np.asarray(x @ w.T)
        mesh = jax.make_mesh((t,), ("model",))

        y = jax.jit(shard_map(
            lambda xs, ws: M.row_parallel_linear_overlap(
                xs, ws, "model", 1, chunks),
            mesh=mesh, in_specs=(P(None, None, "model"),
                                 P(None, "model")),
            out_specs=P(None, "model"), check_vma=False))(x, w)
        # cross-device partials sum in ring order — epsilon, not bitwise
        np.testing.assert_allclose(np.asarray(y), ref, rtol=1e-5,
                                   atol=1e-5)

    @pytest.mark.parametrize("t", [2, 4])
    @pytest.mark.parametrize("chunks", [1, 2])
    def test_row_ring_bwd(self, rng, t, chunks):
        x = jnp.asarray(rng.randn(2, 8, 16).astype(np.float32))
        w = jnp.asarray(rng.randn(24, 16).astype(np.float32))
        c = jnp.asarray(rng.randn(2, 8, 24).astype(np.float32))
        mesh = jax.make_mesh((t,), ("model",))

        def body(xs, ws, cs):
            f = lambda xs, ws: jnp.sum(
                M.row_parallel_linear_overlap(xs, ws, "model", 1,
                                              chunks) * cs)
            return jax.grad(f, argnums=(0, 1))(xs, ws)

        dx, dw = jax.jit(shard_map(
            body, mesh=mesh,
            in_specs=(P(None, None, "model"), P(None, "model"),
                      P(None, "model")),
            out_specs=(P(None, None, "model"), P(None, "model")),
            check_vma=False))(x, w, c)
        ref_dx, ref_dw = jax.grad(
            lambda x, w: jnp.sum((x @ w.T) * c), argnums=(0, 1))(x, w)
        np.testing.assert_allclose(np.asarray(dx), np.asarray(ref_dx),
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(np.asarray(dw), np.asarray(ref_dw),
                                   rtol=1e-5, atol=1e-5)


# -- GPT end-to-end -----------------------------------------------------------

def _run_gpt_tp(par, params, tokens, targets):
    t = par.cfg.tensor_parallel_size
    mesh = jax.make_mesh((t,), ("model",))
    packed, in_specs, local_fn, repack_fn = pack_for_shard_map(par, params)

    def step(sp, tokens, targets):
        loss, g = jax.value_and_grad(par.loss)(local_fn(sp), tokens,
                                               targets)
        return loss, repack_fn(g)

    loss, grads = jax.jit(shard_map(
        step, mesh=mesh, in_specs=(in_specs, P(), P()),
        out_specs=(P(), in_specs), check_vma=False))(packed, tokens, targets)
    return loss, grads


class TestGPTSequenceParallel:
    @pytest.mark.parametrize("t", [2, 4])
    @pytest.mark.parametrize("chunks", [0, 2])
    def test_sp_matches_serial_and_replicated(self, rng, t, chunks):
        """Forward loss: SP == replicated TP bitwise (f32).  Grads: SP ==
        serial (see module docstring for why serial is the reference)."""
        cfg_s = tiny_cfg()
        serial = GPTModel(cfg_s)
        params = serial.init_params(jax.random.PRNGKey(1))
        tokens, targets = make_data(rng, cfg_s, 2, 8)
        ref_loss = float(jax.jit(serial.loss)(params, tokens, targets))
        ref_grads = jax.jit(jax.grad(serial.loss))(params, tokens,
                                                   targets)

        rep = GPTModel(tiny_cfg(tensor_parallel_size=t,
                                axis_name="model"))
        rep_loss, _ = _run_gpt_tp(rep, params, tokens, targets)

        par = GPTModel(tiny_cfg(tensor_parallel_size=t, axis_name="model",
                                sequence_parallel=True,
                                overlap_chunks=chunks))
        sp_loss, sp_grads = _run_gpt_tp(par, params, tokens, targets)

        if chunks == 0:
            # monolithic SP reorders no contraction vs replicated TP
            assert float(sp_loss) == float(rep_loss) == ref_loss
        else:
            np.testing.assert_allclose(float(sp_loss), ref_loss,
                                       rtol=1e-6)
        ref_packed, _, _, _ = pack_for_shard_map(par, ref_grads)
        tree_allclose(sp_grads, ref_packed, rtol=5e-4, atol=1e-5)

    @pytest.mark.slow
    def test_sp_remat_compat(self, rng):
        """remat=True + sequence_parallel=True: the seq-sharded residual
        stream must checkpoint/replay cleanly through the rings."""
        cfg_s = tiny_cfg()
        serial = GPTModel(cfg_s)
        params = serial.init_params(jax.random.PRNGKey(2))
        tokens, targets = make_data(rng, cfg_s, 2, 8)
        ref_loss = float(jax.jit(serial.loss)(params, tokens, targets))
        ref_grads = jax.jit(jax.grad(serial.loss))(params, tokens,
                                                   targets)

        par = GPTModel(tiny_cfg(tensor_parallel_size=2, axis_name="model",
                                sequence_parallel=True, overlap_chunks=2,
                                remat=True))
        loss, grads = _run_gpt_tp(par, params, tokens, targets)
        np.testing.assert_allclose(float(loss), ref_loss, rtol=1e-6)
        ref_packed, _, _, _ = pack_for_shard_map(par, ref_grads)
        tree_allclose(grads, ref_packed, rtol=5e-4, atol=1e-5)

    def test_sp_bf16_allclose(self, rng):
        """bf16 activations: SP vs replicated forward within bf16 noise
        (collective orders differ, so not bitwise in half precision)."""
        kw = dict(tensor_parallel_size=2, axis_name="model",
                  dtype=jnp.bfloat16)
        params = GPTModel(tiny_cfg()).init_params(jax.random.PRNGKey(3))
        tokens, targets = make_data(rng, tiny_cfg(), 2, 8)
        rep_loss, _ = _run_gpt_tp(GPTModel(tiny_cfg(**kw)), params,
                                  tokens, targets)
        sp_loss, _ = _run_gpt_tp(
            GPTModel(tiny_cfg(sequence_parallel=True, overlap_chunks=2,
                              **kw)), params, tokens, targets)
        np.testing.assert_allclose(float(sp_loss), float(rep_loss),
                                   rtol=2e-2, atol=2e-2)

    def test_seq_len_must_divide(self, rng):
        par = GPTModel(tiny_cfg(tensor_parallel_size=4, axis_name="model",
                                sequence_parallel=True))
        params = GPTModel(tiny_cfg()).init_params(jax.random.PRNGKey(4))
        tokens, targets = make_data(rng, tiny_cfg(), 2, 6)  # 6 % 4 != 0
        with pytest.raises(ValueError, match="divisible"):
            _run_gpt_tp(par, params, tokens, targets)


# -- BERT end-to-end ----------------------------------------------------------

class TestBertSequenceParallel:
    @pytest.mark.parametrize("chunks", [0, 2])
    def test_sp_matches_serial(self, rng, chunks):
        from apex_tpu.models.bert import BertConfig, BertModel

        def mk(**kw):
            base = dict(vocab_size=64, hidden_size=16, num_layers=2,
                        num_attention_heads=4, ffn_hidden_size=32,
                        max_seq_len=16)
            base.update(kw)
            return BertModel(BertConfig(**base))

        serial = mk()
        params = serial.init_params(jax.random.PRNGKey(5))
        tokens = jnp.asarray(rng.randint(0, 64, (2, 16)))
        mask = rng.rand(2, 16) < 0.3
        labels = jnp.asarray(np.where(mask, np.asarray(tokens), -1))
        ref_loss = float(jax.jit(serial.loss)(params, tokens, labels))
        ref_grads = jax.jit(jax.grad(serial.loss))(params, tokens, labels)

        par = mk(tensor_parallel_size=2, axis_name="model",
                 sequence_parallel=True, overlap_chunks=chunks)
        mesh = jax.make_mesh((2,), ("model",))
        specs = par.partition_specs()
        loss, grads = jax.jit(shard_map(
            jax.value_and_grad(par.loss), mesh=mesh,
            in_specs=(specs, P(), P()),
            out_specs=(P(), specs), check_vma=False))(params, tokens, labels)
        np.testing.assert_allclose(float(loss), ref_loss, rtol=1e-6)
        tree_allclose(grads, ref_grads, rtol=5e-4, atol=1e-5)


# -- config validation --------------------------------------------------------

class TestConfigValidation:
    def test_overlap_chunks_requires_sp(self):
        with pytest.raises(ValueError, match="sequence_parallel"):
            tiny_cfg(tensor_parallel_size=2, axis_name="model",
                     overlap_chunks=2)

    def test_sp_excludes_context_parallel(self):
        with pytest.raises(ValueError, match="context"):
            tiny_cfg(tensor_parallel_size=2, axis_name="model",
                     sequence_parallel=True, context_axis="context")

    def test_sp_excludes_moe(self):
        with pytest.raises(ValueError, match="MoE"):
            tiny_cfg(tensor_parallel_size=2, axis_name="model",
                     sequence_parallel=True, n_experts=2,
                     expert_axis=None)

    def test_layer_overlap_requires_sp(self):
        from apex_tpu.transformer import tensor_parallel as tp
        with pytest.raises(RuntimeError, match="sequence_parallel"):
            tp.ColumnParallelLinear(16, 32, gather_output=False,
                                    world_size=2, axis_name="model",
                                    overlap_chunks=2)
        with pytest.raises(RuntimeError, match="sequence_parallel"):
            tp.RowParallelLinear(32, 16, input_is_parallel=True,
                                 world_size=2, axis_name="model",
                                 overlap_chunks=2)

    def test_decode_rejects_sp(self, rng):
        cfg = tiny_cfg(tensor_parallel_size=2, axis_name="model",
                       sequence_parallel=True)
        model = GPTModel(cfg)
        params = GPTModel(tiny_cfg()).init_params(jax.random.PRNGKey(6))
        tokens = jnp.asarray(rng.randint(0, 32, (1, 8)))
        with pytest.raises(ValueError, match="sequence_parallel"):
            model.prefill(params, tokens)
