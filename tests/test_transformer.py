"""Transformer stack tests (apex ``tests/L0/run_transformer`` analogue).

Every parallel feature is validated against its serial equivalent on the
fake 8-device CPU mesh: TP layers vs dense layers, vocab-parallel xent vs
plain xent, mappings fwd+bwd, SPMD pipeline vs no-pipelining.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import PartitionSpec as P

from apex_tpu.transformer import parallel_state
from apex_tpu.transformer import tensor_parallel as tp
from apex_tpu.transformer.pipeline_parallel import (
    pipeline_forward, pipeline_value_and_grad,
    forward_backward_no_pipelining, get_forward_backward_func)
from apex_tpu.transformer.pipeline_parallel import p2p_communication as p2p
from apex_tpu.transformer import (ConstantNumMicroBatches,
                                  build_num_microbatches_calculator)

TP_SIZE = 8


@pytest.fixture
def tp_mesh():
    return jax.make_mesh((TP_SIZE,), ("model",))


@pytest.fixture
def pp_mesh():
    return jax.make_mesh((4,), ("pipe",))


def _rep(y, axis="model"):
    """Convert a value that is identical on all devices (e.g. all-gather
    output) into a provably-replicated one so out_specs=P() type-checks."""
    return jax.lax.psum(y, axis) / jax.lax.axis_size(axis)


def shard_tp(fn, mesh, in_specs, out_specs):
    # jit-wrapped: eager shard_map + advanced indexing trips a mesh-context
    # bug in this JAX version
    return jax.jit(shard_map(fn, mesh=mesh, in_specs=in_specs,
                             out_specs=out_specs, check_vma=False))


class TestParallelState:
    def test_initialize_and_sizes(self):
        parallel_state.initialize_model_parallel(2, 2)
        assert parallel_state.model_parallel_is_initialized()
        assert parallel_state.get_tensor_model_parallel_world_size() == 2
        assert parallel_state.get_pipeline_model_parallel_world_size() == 2
        assert parallel_state.get_data_parallel_world_size() == 2
        parallel_state.destroy_model_parallel()
        assert not parallel_state.model_parallel_is_initialized()

    def test_invalid_sizes_raise(self):
        with pytest.raises(RuntimeError):
            parallel_state.initialize_model_parallel(3, 1)
        parallel_state.destroy_model_parallel()

    def test_virtual_rank(self):
        parallel_state.initialize_model_parallel(
            1, 2, virtual_pipeline_model_parallel_size_=2)
        assert parallel_state.\
            get_virtual_pipeline_model_parallel_world_size() == 2
        parallel_state.set_virtual_pipeline_model_parallel_rank(1)
        assert parallel_state.\
            get_virtual_pipeline_model_parallel_rank() == 1
        parallel_state.destroy_model_parallel()


class TestMappings:
    """apex tests/L0/run_transformer/test_mappings.py: each mapping fwd and
    its grad."""

    def test_copy_fwd_identity_bwd_allreduce(self, tp_mesh):
        x = jnp.arange(8.0)

        def f(x):
            y = tp.copy_to_tensor_model_parallel_region(x[0] * jnp.ones(()))
            return jax.lax.psum(y * 0, "model") + y  # keep varying

        def g(x):
            # grad of sum over devices of x → allreduced grad = world size
            def inner(x):
                y = tp.copy_to_tensor_model_parallel_region(x)
                return y  # per-device scalar
            # total = sum over devices handled via psum of per-device loss
            val = inner(x[0])
            return jax.lax.psum(val * 0, "model") + val

        grad = shard_tp(
            lambda x: jax.grad(
                lambda v: tp.copy_to_tensor_model_parallel_region(v).sum()
            )(x[0])[None],
            tp_mesh, (P("model"),), P("model"))(x)
        # each device's bwd all-reduces the per-device cotangent of 1
        np.testing.assert_allclose(np.asarray(grad), TP_SIZE)

    def test_reduce_fwd(self, tp_mesh):
        x = jnp.arange(8.0)
        out = shard_tp(
            lambda x: tp.reduce_from_tensor_model_parallel_region(x),
            tp_mesh, (P("model"),), P())(x)
        np.testing.assert_allclose(float(out[0]), 28.0)

    def test_scatter_gather_roundtrip(self, tp_mesh):
        x = jnp.arange(16.0).reshape(2, 8)

        def f(x):
            local = tp.scatter_to_tensor_model_parallel_region(x)
            assert local.shape == (2, 1)
            return _rep(tp.gather_from_tensor_model_parallel_region(local))

        out = shard_tp(f, tp_mesh, (P(),), P())(x)
        np.testing.assert_allclose(np.asarray(out), np.asarray(x))

    def test_sequence_scatter_gather_roundtrip(self, tp_mesh):
        x = jnp.arange(32.0).reshape(8, 4)

        def f(x):
            local = tp.scatter_to_sequence_parallel_region(x)
            return _rep(tp.gather_from_sequence_parallel_region(local))

        out = shard_tp(f, tp_mesh, (P(),), P())(x)
        np.testing.assert_allclose(np.asarray(out), np.asarray(x))

    def test_reduce_scatter_matches_manual(self, tp_mesh):
        x = jnp.ones((8, 2))

        def f(x):
            return tp.reduce_scatter_to_sequence_parallel_region(x)

        out = shard_tp(f, tp_mesh, (P(),), P("model"))(x)
        # each row: sum over 8 devices of 1 = 8
        np.testing.assert_allclose(np.asarray(out), 8.0)


def _dense_forward(w, b, x):
    return x @ w.T + b


class TestTensorParallelLayers:
    """apex test_layers.py: Column/RowParallelLinear vs dense reference."""

    def test_column_parallel_matches_dense(self, rng, tp_mesh):
        in_f, out_f, batch = 16, 32, 4
        col = tp.ColumnParallelLinear(in_f, out_f, world_size=TP_SIZE,
                                      gather_output=True)
        w = jnp.asarray(rng.randn(out_f, in_f).astype(np.float32))
        b = jnp.asarray(rng.randn(out_f).astype(np.float32))
        x = jnp.asarray(rng.randn(batch, in_f).astype(np.float32))
        ref = _dense_forward(w, b, x)

        def f(w, b, x):
            y, _ = col({"weight": w, "bias": b}, x)
            return _rep(y)

        out = shard_tp(f, tp_mesh, (P("model", None), P("model"), P()),
                       P())(w, b, x)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)

    def test_column_parallel_grads_match(self, rng, tp_mesh):
        in_f, out_f, batch = 8, 16, 4
        col = tp.ColumnParallelLinear(in_f, out_f, world_size=TP_SIZE,
                                      gather_output=True)
        w = jnp.asarray(rng.randn(out_f, in_f).astype(np.float32))
        b = jnp.zeros((out_f,), jnp.float32)
        x = jnp.asarray(rng.randn(batch, in_f).astype(np.float32))

        def sharded_grads(w, b, x):
            def loss(w, b, x):
                y, _ = col({"weight": w, "bias": b}, x)
                return jnp.sum(y ** 2)
            gw, gb, gx = jax.grad(loss, argnums=(0, 1, 2))(w, b, x)
            return gw, gb, gx

        gw, gb, gx = shard_tp(
            sharded_grads, tp_mesh,
            (P("model", None), P("model"), P()),
            (P("model", None), P("model"), P()))(w, b, x)
        ref_gw, ref_gb, ref_gx = jax.grad(
            lambda w, b, x: jnp.sum(_dense_forward(w, b, x) ** 2),
            argnums=(0, 1, 2))(w, b, x)
        np.testing.assert_allclose(np.asarray(gw), np.asarray(ref_gw),
                                   rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(np.asarray(gb), np.asarray(ref_gb),
                                   rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(np.asarray(gx), np.asarray(ref_gx),
                                   rtol=1e-4, atol=1e-4)

    def test_row_parallel_matches_dense(self, rng, tp_mesh):
        in_f, out_f, batch = 32, 16, 4
        row = tp.RowParallelLinear(in_f, out_f, world_size=TP_SIZE,
                                   input_is_parallel=False)
        w = jnp.asarray(rng.randn(out_f, in_f).astype(np.float32))
        b = jnp.asarray(rng.randn(out_f).astype(np.float32))
        x = jnp.asarray(rng.randn(batch, in_f).astype(np.float32))
        ref = _dense_forward(w, b, x)

        def f(w, b, x):
            y, _ = row({"weight": w, "bias": b}, x)
            return y

        out = shard_tp(f, tp_mesh, (P(None, "model"), P(), P()),
                       P())(w, b, x)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-4, atol=1e-4)

    def test_column_row_mlp_sequence_parallel(self, rng, tp_mesh):
        """Col(+SP gather) → gelu → Row(+SP reduce-scatter) round trip vs
        dense (the Megatron SP block edge pattern)."""
        seq, hidden, ffn = 16, 8, 32
        col = tp.ColumnParallelLinear(hidden, ffn, world_size=TP_SIZE,
                                      gather_output=False,
                                      sequence_parallel_enabled=True)
        row = tp.RowParallelLinear(ffn, hidden, world_size=TP_SIZE,
                                   input_is_parallel=True,
                                   sequence_parallel_enabled=True)
        w1 = jnp.asarray(rng.randn(ffn, hidden).astype(np.float32))
        b1 = jnp.zeros((ffn,), jnp.float32)
        w2 = jnp.asarray(rng.randn(hidden, ffn).astype(np.float32))
        b2 = jnp.zeros((hidden,), jnp.float32)
        x = jnp.asarray(rng.randn(seq, hidden).astype(np.float32))

        def f(w1, b1, w2, b2, x):
            h, _ = col({"weight": w1, "bias": b1}, x)
            h = jax.nn.gelu(h, approximate=True)
            y, _ = row({"weight": w2, "bias": b2}, h)
            return y

        out = shard_tp(
            f, tp_mesh,
            (P("model", None), P("model"), P(None, "model"), P(),
             P("model", None)),
            P("model", None))(w1, b1, w2, b2, x)
        ref = jax.nn.gelu(x @ w1.T + b1, approximate=True) @ w2.T + b2
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-4, atol=1e-4)

    def test_vocab_parallel_embedding(self, rng, tp_mesh):
        vocab, dim = 64, 16
        emb = tp.VocabParallelEmbedding(vocab, dim, world_size=TP_SIZE)
        w = jnp.asarray(rng.randn(vocab, dim).astype(np.float32))
        ids = jnp.asarray(rng.randint(0, vocab, (4, 6)))

        out = shard_tp(lambda w, i: emb({"weight": w}, i),
                       tp_mesh, (P("model", None), P()), P())(w, ids)
        ref = jnp.take(w, ids, axis=0)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5, atol=1e-6)


class TestVocabParallelCrossEntropy:
    """apex test_cross_entropy.py: vocab-parallel vs plain xent."""

    @pytest.mark.parametrize("smoothing", [0.0, 0.1])
    def test_matches_serial(self, rng, tp_mesh, smoothing):
        n, vocab = 8, 32
        logits = jnp.asarray(rng.randn(n, vocab).astype(np.float32) * 2)
        target = jnp.asarray(rng.randint(0, vocab, n))

        out = shard_tp(
            lambda l, t: tp.vocab_parallel_cross_entropy(l, t, smoothing),
            tp_mesh, (P(None, "model"), P()), P())(logits, target)
        logp = jax.nn.log_softmax(logits)
        nll = -logp[jnp.arange(n), target]
        if smoothing > 0:
            # apex scales the mix by V/(V-1)
            s_adj = smoothing * vocab / (vocab - 1)
            smooth = -jnp.mean(logp, axis=-1)
            ref = (1 - s_adj) * nll + s_adj * smooth
        else:
            ref = nll
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-4, atol=1e-5)

    def test_grad_matches_serial(self, rng, tp_mesh):
        n, vocab = 4, 16
        logits = jnp.asarray(rng.randn(n, vocab).astype(np.float32))
        target = jnp.asarray(rng.randint(0, vocab, n))

        def sharded(l, t):
            return jax.grad(
                lambda l: jnp.sum(
                    tp.vocab_parallel_cross_entropy(l, t)))(l)

        g = shard_tp(sharded, tp_mesh, (P(None, "model"), P()),
                     P(None, "model"))(logits, target)
        ref = jax.grad(lambda l: jnp.sum(
            -jax.nn.log_softmax(l)[jnp.arange(n), target]))(logits)
        np.testing.assert_allclose(np.asarray(g), np.asarray(ref),
                                   rtol=1e-4, atol=1e-5)


def _stage_fn(params, x):
    return jnp.tanh(x @ params["w"] + params["b"])


def _loss_fn(y, t):
    return jnp.mean((y - t) ** 2)


def _stack_stage_params(rng, n_stages, width):
    return {
        "w": jnp.asarray(rng.randn(n_stages, width, width)
                         .astype(np.float32)) / np.sqrt(width),
        "b": jnp.zeros((n_stages, width), jnp.float32),
    }


class TestPipeline:
    """apex test_pipeline_parallel_fwd_bwd.py: pipelined loss/grads vs the
    no-pipelining reference on the same data."""

    def _serial_loss(self, params, microbatches, targets, n_stages):
        def full(x):
            for i in range(n_stages):
                x = _stage_fn({"w": params["w"][i], "b": params["b"][i]}, x)
            return x
        per = [
            _loss_fn(full(microbatches[m]), targets[m])
            for m in range(microbatches.shape[0])
        ]
        return jnp.mean(jnp.stack(per))

    def test_forward_matches_serial(self, rng, pp_mesh):
        S, width, M, mb = 4, 8, 4, 2
        params = _stack_stage_params(rng, S, width)
        x = jnp.asarray(rng.randn(M, mb, width).astype(np.float32))

        def f(params, x):
            local = jax.tree_util.tree_map(lambda p: p[0], params)
            return pipeline_forward(
                lambda p, z, info: _stage_fn(p, z), local, x,
                axis_name="pipe")

        # outputs come back (M, mb, width), replicated over the pipe axis
        got = np.asarray(jax.jit(shard_map(
            f, mesh=pp_mesh,
            in_specs=({"w": P("pipe", None, None),
                       "b": P("pipe", None)}, P()),
            out_specs=P(), check_vma=False))(params, x))
        def full(xx):
            for i in range(S):
                xx = _stage_fn({"w": params["w"][i], "b": params["b"][i]},
                               xx)
            return xx
        for m in range(M):
            np.testing.assert_allclose(got[m], np.asarray(full(x[m])),
                                       rtol=1e-5, atol=1e-5)

    def test_value_and_grad_matches_serial(self, rng, pp_mesh):
        S, width, M, mb = 4, 8, 4, 2
        params = _stack_stage_params(rng, S, width)
        x = jnp.asarray(rng.randn(M, mb, width).astype(np.float32))
        t = jnp.asarray(rng.randn(M, mb, width).astype(np.float32))

        def f(params, x, t):
            local = jax.tree_util.tree_map(lambda p: p[0], params)
            loss, grads = pipeline_value_and_grad(
                _stage_fn, _loss_fn, local, x, t, axis_name="pipe")
            return loss, jax.tree_util.tree_map(lambda g: g[None], grads)

        loss, grads = jax.jit(shard_map(
            f, mesh=pp_mesh,
            in_specs=({"w": P("pipe", None, None), "b": P("pipe", None)},
                      P(), P()),
            out_specs=(P(), {"w": P("pipe", None, None),
                             "b": P("pipe", None)}),
            check_vma=False))(params, x, t)
        ref_loss = self._serial_loss(params, x, t, S)
        np.testing.assert_allclose(float(loss), float(ref_loss), rtol=1e-5)
        ref_grads = jax.grad(
            lambda p: self._serial_loss(p, x, t, S))(params)
        for k in ("w", "b"):
            np.testing.assert_allclose(np.asarray(grads[k]),
                                       np.asarray(ref_grads[k]),
                                       rtol=1e-4, atol=1e-5)

    def test_interleaved_matches_serial(self, rng):
        # 2 devices x 2 virtual chunks = 4 logical stages; the
        # interleaved schedule needs M % S == 0
        mesh = jax.make_mesh((2,), ("pipe",))
        S, v, width, M, mb = 2, 2, 8, 4, 2
        rng2 = np.random.RandomState(7)
        params = _stack_stage_params(rng2, S * v, width)
        x = jnp.asarray(rng2.randn(M, mb, width).astype(np.float32))
        t = jnp.asarray(rng2.randn(M, mb, width).astype(np.float32))
        # interleaved placement: device s holds chunks [s, s+S]
        # logical stage c*S + s ⇒ device s's chunk c is logical c*S+s
        w_dev = jnp.stack([params["w"][jnp.asarray([s, s + S])]
                           for s in range(S)])   # (S, v, width, width)
        b_dev = jnp.stack([params["b"][jnp.asarray([s, s + S])]
                           for s in range(S)])

        def f(w, b, x, t):
            local = {"w": w[0], "b": b[0]}     # (v, ...)
            loss, grads = pipeline_value_and_grad(
                _stage_fn, _loss_fn, local, x, t, axis_name="pipe",
                n_virtual=v)
            return loss, jax.tree_util.tree_map(lambda g: g[None], grads)

        loss, grads = jax.jit(shard_map(
            f, mesh=mesh,
            in_specs=(P("pipe"), P("pipe"), P(), P()),
            out_specs=(P(), {"w": P("pipe"), "b": P("pipe")}),
            check_vma=False))(
                w_dev, b_dev, x, t)
        ref_loss = self._serial_loss(params, x, t, S * v)
        np.testing.assert_allclose(float(loss), float(ref_loss), rtol=1e-5)
        ref_grads = jax.grad(
            lambda p: self._serial_loss(p, x, t, S * v))(params)
        got_w = np.asarray(grads["w"]).reshape(S, v, width, width)
        for s in range(S):
            for c in range(v):
                np.testing.assert_allclose(
                    got_w[s, c], np.asarray(ref_grads["w"][c * S + s]),
                    rtol=1e-4, atol=1e-5)

    def test_no_pipelining_schedule(self, rng):
        width, M, mb = 8, 4, 2
        params = {"w": jnp.asarray(
            rng.randn(width, width).astype(np.float32)) / 3,
            "b": jnp.zeros((width,), jnp.float32)}
        x = jnp.asarray(rng.randn(M, mb, width).astype(np.float32))
        t = jnp.asarray(rng.randn(M, mb, width).astype(np.float32))
        loss, grads = forward_backward_no_pipelining(
            _stage_fn, _loss_fn, params, x, t)
        per = jnp.mean(jnp.stack([
            _loss_fn(_stage_fn(params, x[m]), t[m]) for m in range(M)]))
        np.testing.assert_allclose(float(loss), float(per), rtol=1e-5)
        ref = jax.grad(lambda p: jnp.mean(jnp.stack([
            _loss_fn(_stage_fn(p, x[m]), t[m])
            for m in range(M)])))(params)
        for k in ("w", "b"):
            np.testing.assert_allclose(np.asarray(grads[k]),
                                       np.asarray(ref[k]), rtol=1e-4,
                                       atol=1e-5)

    def test_get_forward_backward_func_dispatch(self):
        from apex_tpu.transformer.pipeline_parallel.schedules import (
            forward_backward_pipelining_without_interleaving as f1f1b,
        )
        assert get_forward_backward_func(None, 1) is \
            forward_backward_no_pipelining
        assert get_forward_backward_func(None, 4) is f1f1b
        fn = get_forward_backward_func(2, 4)
        assert fn.func.__name__ == \
            "forward_backward_pipelining_with_interleaving"


class TestP2P:
    def test_forward_shift(self, pp_mesh):
        x = jnp.arange(4.0)
        out = jax.jit(shard_map(
            lambda x: p2p.send_forward_recv_forward(x, axis_name="pipe"),
            mesh=pp_mesh, in_specs=(P("pipe"),),
            out_specs=P("pipe"), check_vma=False))(x)
        np.testing.assert_allclose(np.asarray(out), [0, 0, 1, 2])

    def test_backward_shift(self, pp_mesh):
        x = jnp.arange(4.0)
        out = jax.jit(shard_map(
            lambda x: p2p.send_backward_recv_backward(x, axis_name="pipe"),
            mesh=pp_mesh, in_specs=(P("pipe"),),
            out_specs=P("pipe"), check_vma=False))(x)
        np.testing.assert_allclose(np.asarray(out), [1, 2, 3, 0])


class TestMicrobatches:
    def test_constant(self):
        c = build_num_microbatches_calculator(0, None, 64, 4, 2)
        assert isinstance(c, ConstantNumMicroBatches)
        assert c.get() == 8
        assert c.get_current_global_batch_size() == 64

    def test_rampup(self):
        c = build_num_microbatches_calculator(0, [16, 16, 1000], 64, 4, 2)
        assert c.get_current_global_batch_size() == 16
        c.update(500, True)
        assert 16 <= c.get_current_global_batch_size() <= 64
        c.update(2000, True)
        assert c.get_current_global_batch_size() == 64

    def test_indivisible_raises(self):
        with pytest.raises(ValueError):
            build_num_microbatches_calculator(0, None, 30, 4, 2)
