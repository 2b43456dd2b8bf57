"""apex_tpu.ops.quant_gemm: int8 decode weights (ISSUE 18).

The subsystem's correctness contract:

* :func:`quantize_weight` is per-OUTPUT-channel symmetric int8: the
  reconstruction error is ``<= scale / 2`` per element, an all-zero
  row gets scale 1.0 (zeros round-trip bitwise), and quantization is
  a pure function of the values (bitwise-deterministic across loads);
* the Pallas kernel (interpret mode) matches the unfused
  dequantize-then-matmul reference at dtype-appropriate tolerances,
  and off-TPU the public :func:`quant_gemm` IS the reference, bitwise;
* quantization commutes with :func:`shard_params_for_tp`: BITWISE on
  the ColumnParallel / vocab row-shard direction, and on the
  RowParallel column-shard direction per-shard scales never exceed
  the full-tensor scale (local amax <= full amax) except all-zero
  shard rows, which reconstruct exactly anyway;
* a TP=2 shard_map decode over per-shard-quantized trees greedily
  matches the tp=1 quantized decode;
* the int8 decode path agrees greedily with f32 on the contiguous and
  paged engines at the CI config, within a pinned logits tolerance,
  at < 0.30x the f32 weight bytes;
* every training entry point rejects quantized trees with an
  actionable message: ``GPTConfig`` (fused_ffn / MoE composition),
  ``pipeline_step``, ``GuardedTrainStep``, and the autotuner's
  ``cfg_kw``.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from jax.sharding import PartitionSpec as P

from apex_tpu.models.gpt import (GPTConfig, GPTModel, pipeline_step,
                                 quantize_decode_params,
                                 shard_params_for_tp)
from apex_tpu.ops.quant_gemm import (dequantize_weight, quant_gemm,
                                     quant_gemm_reference, quantize_weight)
from apex_tpu.utils import set_force_pallas

# int8 weights must keep decode logits this close to f32 on the CI
# config (measured worst |dlogits| is ~7e-3; ~7x margin)
WEIGHT_QUANT_LOGITS_TOL = 5e-2

# big enough that greedy argmax is stable under quantization error and
# the LN/bias f32 remainder is < 30% of the weight bytes (measured
# ratio 0.274)
CI_KW = dict(vocab_size=256, hidden_size=64, num_layers=2,
             num_attention_heads=4, max_seq_len=64)


@pytest.fixture(scope="module")
def ci_model():
    model = GPTModel(GPTConfig(**CI_KW))
    return model, model.init_params(jax.random.PRNGKey(0))


# ---------------------------------------------------------------------------
# quantize_weight
# ---------------------------------------------------------------------------

class TestQuantizeWeight:
    def test_error_bound_half_scale(self):
        w = jax.random.normal(jax.random.PRNGKey(0), (64, 48))
        w8, scale = quantize_weight(w)
        assert w8.dtype == jnp.int8 and scale.dtype == jnp.float32
        assert w8.shape == w.shape and scale.shape == (64,)
        err = np.abs(np.asarray(dequantize_weight(w8, scale)) -
                     np.asarray(w, np.float32))
        bound = np.asarray(scale)[:, None] / 2 * (1 + 1e-6)
        assert (err <= bound).all()

    def test_zero_row_scale_one_roundtrips(self):
        w = jnp.zeros((4, 8)).at[1].set(jnp.arange(8, dtype=jnp.float32))
        w8, scale = quantize_weight(w)
        assert float(scale[0]) == 1.0
        np.testing.assert_array_equal(
            np.asarray(dequantize_weight(w8, scale))[0], np.zeros(8))

    def test_bitwise_deterministic(self):
        w = jax.random.normal(jax.random.PRNGKey(3), (32, 32))
        a8, asc = quantize_weight(w)
        b8, bsc = quantize_weight(jnp.array(np.asarray(w)))
        assert np.asarray(a8).tobytes() == np.asarray(b8).tobytes()
        assert np.asarray(asc).tobytes() == np.asarray(bsc).tobytes()

    def test_rejects_non_2d(self):
        with pytest.raises(ValueError, match="2D"):
            quantize_weight(jnp.zeros((2, 3, 4)))


# ---------------------------------------------------------------------------
# kernel vs reference
# ---------------------------------------------------------------------------

class TestKernel:
    @pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5),
                                           (jnp.bfloat16, 2e-2)])
    @pytest.mark.parametrize("m,n,k", [(5, 130, 200), (16, 512, 512)])
    def test_interpret_matches_reference(self, dtype, tol, m, n, k):
        kx, kw = jax.random.split(jax.random.PRNGKey(1))
        x = jax.random.normal(kx, (m, k)).astype(dtype)
        w8, scale = quantize_weight(jax.random.normal(kw, (n, k)) * 0.1)
        ref = quant_gemm_reference(x, w8, scale)
        set_force_pallas(True)
        try:
            out = quant_gemm(x, w8, scale, block_n=128, block_k=128)
        finally:
            set_force_pallas(None)
        assert out.dtype == jnp.float32 and out.shape == (m, n)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=tol, atol=tol)

    def test_off_tpu_dispatch_is_reference_bitwise(self):
        x = jax.random.normal(jax.random.PRNGKey(2), (4, 64),
                              dtype=jnp.float32)
        w8, scale = quantize_weight(
            jax.random.normal(jax.random.PRNGKey(5), (96, 64)))
        out = quant_gemm(x, w8, scale)
        ref = quant_gemm_reference(x, w8, scale)
        assert np.asarray(out).tobytes() == np.asarray(ref).tobytes()

    def test_leading_dims_flatten(self):
        x = jax.random.normal(jax.random.PRNGKey(4), (2, 3, 32))
        w8, scale = quantize_weight(
            jax.random.normal(jax.random.PRNGKey(6), (48, 32)))
        out = quant_gemm(x, w8, scale)
        assert out.shape == (2, 3, 48)
        np.testing.assert_array_equal(
            np.asarray(out).reshape(6, 48),
            np.asarray(quant_gemm(x.reshape(6, 32), w8, scale)))

    def test_rejects_bad_operands(self):
        x = jnp.zeros((2, 8))
        with pytest.raises(ValueError, match="int8"):
            quant_gemm(x, jnp.zeros((4, 8), jnp.float32), jnp.ones(4))
        with pytest.raises(ValueError, match="features"):
            quant_gemm(x, jnp.zeros((4, 9), jnp.int8), jnp.ones(4))
        with pytest.raises(ValueError, match="scale"):
            quant_gemm(x, jnp.zeros((4, 8), jnp.int8), jnp.ones(5))


# ---------------------------------------------------------------------------
# TP sharding: quantize/shard commutation
# ---------------------------------------------------------------------------

class TestTensorParallel:
    @pytest.mark.parametrize("sp", [False, True])
    def test_column_shard_quantize_commutes_bitwise(self, ci_model, sp):
        model, params = ci_model
        cfg_tp = GPTConfig(tensor_parallel_size=2, axis_name="model",
                           sequence_parallel=sp, **CI_KW)
        qfull = quantize_decode_params(params)
        for rank in range(2):
            a = shard_params_for_tp(cfg_tp, qfull, rank)
            b = quantize_decode_params(
                shard_params_for_tp(cfg_tp, params, rank))
            for (pa, xa), (pb, xb) in zip(
                    jax.tree_util.tree_leaves_with_path(a),
                    jax.tree_util.tree_leaves_with_path(b), strict=True):
                key = jax.tree_util.keystr(pa)
                assert xa.shape == xb.shape, key
                if "proj" in key or "fc2" in key:
                    continue          # RowParallel: scale-bound test below
                assert np.asarray(xa).tobytes() == \
                    np.asarray(xb).tobytes(), key

    def test_row_shard_scales_only_tighten(self, ci_model):
        model, params = ci_model
        cfg_tp = GPTConfig(tensor_parallel_size=2, axis_name="model",
                           **CI_KW)
        full = quantize_decode_params(params)
        for rank in range(2):
            local = quantize_decode_params(
                shard_params_for_tp(cfg_tp, params, rank))
            for li, lp in enumerate(local["layers"]):
                for group in (("attention", "proj"), ("mlp", "fc2")):
                    ls = np.asarray(lp[group[0]][group[1]]["weight_scale"])
                    fs = np.asarray(
                        full["layers"][li][group[0]][group[1]]
                        ["weight_scale"])
                    # local amax <= full amax, except an all-zero shard
                    # row snaps to scale 1.0 (and reconstructs exactly)
                    ok = (ls <= fs + 1e-12) | (ls == 1.0)
                    assert ok.all(), (li, group, rank)

    def test_tp2_quantized_decode_matches_tp1_greedy(self, ci_model):
        model, params = ci_model
        cfg = model.cfg
        cfg_tp = GPTConfig(tensor_parallel_size=2, axis_name="model",
                           **CI_KW)
        qmodel = GPTModel(GPTConfig(weight_quant="int8", **CI_KW))
        par = GPTModel(GPTConfig(weight_quant="int8",
                                 tensor_parallel_size=2,
                                 axis_name="model", **CI_KW))
        shards = [quantize_decode_params(
            shard_params_for_tp(cfg_tp, params, r)) for r in range(2)]
        stacked = jax.tree_util.tree_map(
            lambda *xs: jnp.stack(xs), *shards)
        specs = jax.tree_util.tree_map(lambda _: P("model"), stacked)
        mesh = jax.make_mesh((2,), ("model",))
        qparams = quantize_decode_params(params)
        tokens = jnp.asarray([[1, 2, 3, 4]])
        b, p = 1, 4

        lg, kv = jax.jit(qmodel.prefill)(qparams, tokens)

        def local_prefill(sp, toks):
            lp = jax.tree_util.tree_map(lambda a: a[0], sp)
            return par.prefill(lp, toks)

        lg2, _ = jax.jit(jax.shard_map(
            local_prefill, mesh=mesh, in_specs=(specs, P()),
            out_specs=(P(None, None, "model"),
                       P(None, None, None, None, "model")),
            check_vma=False))(stacked,
                                                             tokens)
        np.testing.assert_allclose(np.asarray(lg), np.asarray(lg2),
                                   atol=WEIGHT_QUANT_LOGITS_TOL)
        assert int(np.argmax(np.asarray(lg)[0, -1])) == \
            int(np.argmax(np.asarray(lg2)[0, -1]))

        cache = jnp.zeros((b, cfg.num_layers, 2, cfg.max_seq_len,
                           cfg.num_attention_heads, cfg.head_dim),
                          jnp.float32)
        cache = cache.at[:, :, :, :p].set(kv.transpose(2, 0, 1, 3, 4, 5))
        cache2 = cache.copy()

        def local_decode(sp, toks, cache, pos):
            lp = jax.tree_util.tree_map(lambda a: a[0], sp)
            return par.decode_step(lp, toks, cache, pos)

        cache_spec = P(None, None, None, None, "model")
        step2 = jax.jit(jax.shard_map(
            local_decode, mesh=mesh,
            in_specs=(specs, P(), cache_spec, P()),
            out_specs=(P(None, "model"), cache_spec), check_vma=False))
        step1 = jax.jit(qmodel.decode_step)
        tok = jnp.asarray([int(np.argmax(np.asarray(lg)[0, -1]))])
        tok2 = tok
        for i in range(p, p + 5):
            pos = jnp.full((b,), i, jnp.int32)
            l1, cache = step1(qparams, tok, cache, pos)
            l2, cache2 = step2(stacked, tok2, cache2, pos)
            np.testing.assert_allclose(
                np.asarray(l1), np.asarray(l2),
                atol=WEIGHT_QUANT_LOGITS_TOL)
            tok = jnp.asarray([int(np.argmax(np.asarray(l1)[0]))])
            tok2 = jnp.asarray([int(np.argmax(np.asarray(l2)[0]))])
            assert int(tok[0]) == int(tok2[0]), i


# ---------------------------------------------------------------------------
# engine integration
# ---------------------------------------------------------------------------

def _greedy(model, params, reqs):
    import dataclasses as _dc

    from apex_tpu.inference import InferenceEngine
    eng = InferenceEngine(model, params, max_slots=4)
    for r in reqs:
        eng.submit(_dc.replace(r))
    return {r.request_id: r.tokens for r in eng.run()}, eng


def _greedy_paged(model, params, reqs):
    import dataclasses as _dc

    from apex_tpu.serving import PagedInferenceEngine
    eng = PagedInferenceEngine(model, params, max_slots=4, block_size=8,
                               chunked_prefill=True)
    for r in reqs:
        eng.submit(_dc.replace(r))
    return {r.request_id: r.tokens for r in eng.run()}, eng


def _greedy_disagg(model, params, reqs):
    """One prefill-only replica hands every request's KV to one decode
    replica: the quantized weights sit on both sides of the handoff."""
    import dataclasses as _dc

    from apex_tpu.serving import (DisaggregatedFleet, PagedInferenceEngine,
                                  VirtualClock)
    from apex_tpu.utils.profiling import ServingMetrics
    clock = VirtualClock()

    def eng(prefill_only=False):
        return PagedInferenceEngine(
            model, params, max_slots=4, block_size=8, chunked_prefill=True,
            prefill_only=prefill_only, metrics=ServingMetrics(clock),
            clock=clock)

    fleet = DisaggregatedFleet([eng(prefill_only=True)], [eng()],
                               clock=clock, handoff_retry_ticks=64)
    for r in reqs:
        fleet.submit(_dc.replace(r))
    for _ in range(800):
        busy = fleet.step()
        clock.advance(0.01)
        if not busy and fleet.pending == 0:
            break
    assert fleet.pending == 0 and fleet.handoffs == len(reqs)
    return {r.request_id: r.tokens for r in fleet.completed}, fleet


class TestEngineIntegration:
    @pytest.fixture(scope="class")
    def reqs(self):
        from apex_tpu.inference import Request
        rng = np.random.RandomState(7)
        return [Request(i, list(rng.randint(1, 256, 6 + i)),
                        max_new_tokens=8) for i in range(4)]

    @pytest.fixture(scope="class")
    def contiguous(self, ci_model, reqs):
        model, params = ci_model
        qmodel = GPTModel(dataclasses.replace(model.cfg,
                                              weight_quant="int8"))
        ref, feng = _greedy(model, params, reqs)
        got, qeng = _greedy(qmodel, params, reqs)
        return ref, got, feng, qeng

    def test_contiguous_greedy_matches_f32(self, contiguous):
        ref, got, _, qeng = contiguous
        assert got == ref
        # the engine quantized at init: int8 leaves in its tree
        leaves = jax.tree_util.tree_leaves(qeng.params)
        assert any(l.dtype == jnp.int8 for l in leaves)

    def test_paged_greedy_matches_f32(self, ci_model, reqs):
        model, params = ci_model
        qmodel = GPTModel(dataclasses.replace(model.cfg,
                                              weight_quant="int8"))
        ref, _ = _greedy_paged(model, params, reqs)
        got, _ = _greedy_paged(qmodel, params, reqs)
        assert got == ref

    def test_disagg_handoff_greedy_matches_f32(self, ci_model, reqs,
                                               contiguous):
        model, params = ci_model
        qmodel = GPTModel(dataclasses.replace(model.cfg,
                                              weight_quant="int8"))
        ref = contiguous[0]
        assert _greedy_disagg(model, params, reqs)[0] == ref
        assert _greedy_disagg(qmodel, params, reqs)[0] == ref

    def test_weight_bytes_ratio(self, contiguous):
        _, _, feng, qeng = contiguous
        ratio = qeng.weight_bytes / feng.weight_bytes
        assert ratio < 0.30, ratio

    def test_pinned_logits_tolerance(self, ci_model):
        model, params = ci_model
        qparams = quantize_decode_params(params)
        qmodel = GPTModel(dataclasses.replace(model.cfg,
                                              weight_quant="int8"))
        toks = jnp.asarray([[1, 2, 3, 4, 5]])
        lf, _ = jax.jit(model.prefill)(params, toks)
        lq, _ = jax.jit(qmodel.prefill)(qparams, toks)
        delta = float(np.max(np.abs(np.asarray(lf) - np.asarray(lq))))
        assert delta < WEIGHT_QUANT_LOGITS_TOL, delta

    def test_quantized_tree_bitwise_deterministic(self, ci_model):
        model, params = ci_model
        a = quantize_decode_params(params)
        b = quantize_decode_params(
            jax.tree_util.tree_map(lambda l: jnp.array(np.asarray(l)),
                                   params))
        for (pa, xa), (pb, xb) in zip(
                jax.tree_util.tree_leaves_with_path(a),
                jax.tree_util.tree_leaves_with_path(b), strict=True):
            assert np.asarray(xa).tobytes() == np.asarray(xb).tobytes(), \
                jax.tree_util.keystr(pa)


# ---------------------------------------------------------------------------
# training rejections
# ---------------------------------------------------------------------------

class TestTrainingRejections:
    def test_config_rejects_bad_mode(self):
        with pytest.raises(ValueError, match="weight_quant"):
            GPTConfig(weight_quant="fp8", **CI_KW)

    def test_config_rejects_fused_ffn(self):
        with pytest.raises(ValueError, match="fused_ffn"):
            GPTConfig(weight_quant="int8", fused_ffn=True, **CI_KW)

    def test_config_rejects_moe(self):
        kw = dict(CI_KW)
        with pytest.raises(ValueError, match="expert"):
            GPTConfig(weight_quant="int8", n_experts=2, **kw)

    def test_pipeline_step_rejects(self):
        cfg = GPTConfig(weight_quant="int8", **CI_KW)
        model = GPTModel(cfg)
        with pytest.raises(ValueError,
                           match="decode/prefill-only"):
            pipeline_step(model, {}, jnp.zeros((1, 1, 8), jnp.int32),
                          jnp.zeros((1, 1, 8), jnp.int32))

    def test_guarded_train_step_rejects_int8_leaves(self, ci_model):
        from apex_tpu.optimizers import FusedAdam
        from apex_tpu.resilience import GuardedTrainStep
        model, params = ci_model
        qparams = quantize_decode_params(params)
        guard = GuardedTrainStep(model.loss, FusedAdam(lr=1e-3))
        opt = guard.optimizer.init(params)
        state = guard.init_state()
        tk = jnp.zeros((1, 8), jnp.int32)
        with pytest.raises(ValueError, match="int8 leaves"):
            guard(qparams, opt, state, tk, tk)

    def test_autotune_rejects_weight_quant_cfg(self):
        from tools.autotune import autotune
        with pytest.raises(ValueError, match="decode/prefill-only"):
            autotune(2, cfg_kw=dict(weight_quant="int8", **CI_KW))

    def test_quantize_rejects_moe_tree(self):
        cfg = GPTConfig(n_experts=2, **CI_KW)
        params = GPTModel(cfg).init_params(jax.random.PRNGKey(0))
        with pytest.raises(ValueError, match="MoE"):
            quantize_decode_params(params)
