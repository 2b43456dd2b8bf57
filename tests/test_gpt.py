"""GPT flagship tests (apex ``tests/L0/run_transformer``'s
``test_pipeline_parallel_fwd_bwd.py`` + ``standalone_gpt.py`` pattern):
serial golden vs an independent jnp reference, TP parity vs serial, GSPMD
parity, and the combined dp x pp x tp step vs serial loss+grads.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from apex_tpu.models.gpt import (GPTConfig, GPTModel, make_stage_fn,
                                 pack_for_shard_map, pipeline_step,
                                 shard_params_for_tp,
                                 stack_layers_for_pipeline)
from apex_tpu.models.reference import gpt_reference_logits
from apex_tpu.transformer import parallel_state
from apex_tpu.transformer.pipeline_parallel import JobInfo


def tiny_cfg(**kw):
    base = dict(vocab_size=32, hidden_size=16, num_layers=2,
                num_attention_heads=2, max_seq_len=8)
    base.update(kw)
    return GPTConfig(**base)


def make_data(rng, cfg, batch, seq):
    tokens = jnp.asarray(rng.randint(0, cfg.vocab_size, (batch, seq)))
    targets = jnp.asarray(rng.randint(0, cfg.vocab_size, (batch, seq)))
    return tokens, targets


# -- independent jnp reference (no apex_tpu ops) -----------------------------

def _ref_gpt_loss(params, tokens, targets, cfg):
    """Mean CE over the package's plain float32 reference forward."""
    logits = gpt_reference_logits(params, tokens, cfg)
    logp = jax.nn.log_softmax(logits.reshape(-1, logits.shape[-1]), axis=-1)
    nll = -jnp.take_along_axis(logp, targets.reshape(-1, 1), axis=1)
    return float(jnp.mean(nll))


class TestGPTSerial:
    def test_loss_matches_independent_reference(self, rng):
        cfg = tiny_cfg()
        model = GPTModel(cfg)
        params = model.init_params(jax.random.PRNGKey(0))
        tokens, targets = make_data(rng, cfg, 2, 8)
        got = float(jax.jit(model.loss)(params, tokens, targets))
        ref = _ref_gpt_loss(params, tokens, targets, cfg)
        np.testing.assert_allclose(got, ref, rtol=2e-4)

    def test_grads_finite_and_nonzero(self, rng):
        cfg = tiny_cfg()
        model = GPTModel(cfg)
        params = model.init_params(jax.random.PRNGKey(0))
        tokens, targets = make_data(rng, cfg, 2, 8)
        grads = jax.jit(jax.grad(model.loss))(params, tokens, targets)
        leaves = jax.tree_util.tree_leaves(grads)
        assert all(np.all(np.isfinite(np.asarray(g))) for g in leaves)
        assert any(np.abs(np.asarray(g)).max() > 0 for g in leaves)

    def test_learns(self, rng):
        """Few SGD steps on a fixed batch must reduce the loss."""
        cfg = tiny_cfg()
        model = GPTModel(cfg)
        params = model.init_params(jax.random.PRNGKey(0))
        tokens, targets = make_data(rng, cfg, 2, 8)

        @jax.jit
        def step(params):
            loss, g = jax.value_and_grad(model.loss)(params, tokens,
                                                     targets)
            new = jax.tree_util.tree_map(lambda p, gg: p - 0.1 * gg,
                                         params, g)
            return new, loss

        params, first = step(params)
        for _ in range(4):
            params, last = step(params)
        assert float(last) < float(first)


class TestGPTTensorParallel:
    def test_tp2_shard_map_matches_serial(self, rng):
        cfg_s = tiny_cfg()
        serial = GPTModel(cfg_s)
        params = serial.init_params(jax.random.PRNGKey(1))
        tokens, targets = make_data(rng, cfg_s, 2, 8)
        ref_loss = float(jax.jit(serial.loss)(params, tokens, targets))
        ref_grads = jax.jit(jax.grad(serial.loss))(params, tokens, targets)

        cfg_p = tiny_cfg(tensor_parallel_size=2, axis_name="model")
        par = GPTModel(cfg_p)
        mesh = jax.make_mesh((2,), ("model",))
        packed, in_specs, local_fn, repack_fn = pack_for_shard_map(
            par, params)

        def step(sp, tokens, targets):
            loss, g = jax.value_and_grad(par.loss)(local_fn(sp), tokens,
                                                   targets)
            return loss, repack_fn(g)

        # check_vma=True: the non-SP TP path leaves the reduction of
        # replicated-leaf cotangents to vma tracking
        loss, grads = jax.jit(shard_map(
            step, mesh=mesh, in_specs=(in_specs, P(), P()),
            out_specs=(P(), in_specs),
            check_vma=True))(packed, tokens, targets)

        np.testing.assert_allclose(float(loss), ref_loss, rtol=1e-5)
        # pack the serial grads identically and compare leaf-for-leaf
        ref_packed, _, _, _ = pack_for_shard_map(par, ref_grads)
        for got, ref in zip(jax.tree_util.tree_leaves(grads),
                            jax.tree_util.tree_leaves(ref_packed)):
            np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                       rtol=5e-4, atol=1e-5)

    def test_gspmd_jit_matches_serial(self, rng):
        """Idiomatic TPU path: jit the serial form with partition_specs —
        the compiler inserts the collectives."""
        cfg = tiny_cfg()
        model = GPTModel(cfg)
        params = model.init_params(jax.random.PRNGKey(2))
        tokens, targets = make_data(rng, cfg, 4, 8)
        ref = float(jax.jit(model.loss)(params, tokens, targets))

        mesh = jax.make_mesh((2,), ("model",),
                             axis_types=(jax.sharding.AxisType.Auto,))
        specs = model.partition_specs()
        sharded = jax.tree_util.tree_map(
            lambda x, s: jax.device_put(x, NamedSharding(mesh, s)),
            params, specs,
            is_leaf=lambda x: isinstance(x, P))
        got = float(jax.jit(model.loss)(sharded, tokens, targets))
        np.testing.assert_allclose(got, ref, rtol=1e-5)


class TestGPTCombinedParallel:
    @pytest.mark.parametrize("pp,M,seq,kw", [
        pytest.param(2, 2, 8, {}, marks=pytest.mark.slow,
                     id="dp2-pp2-tp2-toy"),
        # vocab-parallel cross entropy, the attention blocking and the
        # sequence-parallel edges at extents where a lane (128) is full:
        # the toy shapes prove the wiring, this that the numerics
        # survive size
        pytest.param(1, 1, 128,
                     dict(vocab_size=2048, hidden_size=128,
                          num_attention_heads=4, max_seq_len=128),
                     id="dp2-tp2-real-extents"),
    ])
    def test_dp_pp_tp_step_matches_serial(self, rng, pp, M, seq, kw):
        """The combined 3-axis step: dp=2 x pp x tp=2 + SP, loss AND
        grads vs the serial model on the same global batch
        (apex test_pipeline_parallel_fwd_bwd.py, extended to 3 axes)."""
        parallel_state.destroy_model_parallel()
        try:
            mesh = parallel_state.initialize_model_parallel(
                2, pp, devices=jax.devices()[:4 * pp])
            assert parallel_state.get_data_parallel_world_size() == 2

            cfg_s = tiny_cfg(num_layers=2, **kw)
            serial = GPTModel(cfg_s)
            params = serial.init_params(jax.random.PRNGKey(3))
            mb = 2                        # per-device microbatches: M x mb
            # global batch: dp=2 shards of (M*mb) rows each
            tokens, targets = make_data(rng, cfg_s, 2 * M * mb, seq)

            # serial reference: mean loss over the same global batch
            def serial_loss(p):
                return serial.loss(p, tokens, targets)
            ref_loss = float(jax.jit(serial_loss)(params))
            ref_grads = jax.jit(jax.grad(serial_loss))(params)

            cfg_p = tiny_cfg(num_layers=2, tensor_parallel_size=2,
                             axis_name="model", sequence_parallel=True,
                             **kw)
            par = GPTModel(cfg_p)
            packed, in_specs, local_fn, repack_fn = pack_for_shard_map(
                par, params, n_stages=pp)

            def step(sp, tokens, targets):
                # local batch (M*mb, s) -> (M, mb, s) microbatches
                tk = tokens.reshape(M, mb, seq)
                tg = targets.reshape(M, mb, seq)
                loss, g = pipeline_step(par, local_fn(sp), tk, tg,
                                        pipe_axis="pipe",
                                        data_axis="data")
                return loss, repack_fn(g)

            loss, grads = jax.jit(shard_map(
                step, mesh=mesh,
                in_specs=(in_specs, P("data"), P("data")),
                out_specs=(P(), in_specs),
                check_vma=False))(packed, tokens, targets)

            np.testing.assert_allclose(float(loss), ref_loss, rtol=1e-5)

            # reference grads, packed identically
            ref_packed, _, _, _ = pack_for_shard_map(par, ref_grads,
                                                     n_stages=pp)
            for got, ref in zip(jax.tree_util.tree_leaves(grads),
                                jax.tree_util.tree_leaves(ref_packed),
                                strict=True):
                np.testing.assert_allclose(np.asarray(got),
                                           np.asarray(ref),
                                           rtol=5e-4, atol=1e-5)
        finally:
            parallel_state.destroy_model_parallel()


class TestPipelineBitwise:
    """1F1B and interleaved schedules reproduce the same model run at
    pp=1 — the engine replays the exact per-microbatch accumulation order
    of the no-pipelining reference: the f32 loss bitwise, every gradient
    leaf to the few ulp two XLA:CPU programs differ by
    (``assert_ulp_close``, ``conftest.py``)."""

    def _run(self, model, params, tokens, targets, S, v):
        packed, in_specs, local_fn, repack_fn = pack_for_shard_map(
            model, params, n_stages=S, tensor_axis=None, n_virtual=v)
        mesh = jax.make_mesh((S,), ("pipe",), devices=jax.devices()[:S])

        def step(sp, tk, tg):
            loss, g = pipeline_step(model, local_fn(sp), tk, tg,
                                    pipe_axis="pipe", n_virtual=v)
            return loss, repack_fn(g)

        return jax.jit(shard_map(
            step, mesh=mesh, in_specs=(in_specs, P(), P()),
            out_specs=(P(), in_specs),
            check_vma=False))(packed, tokens, targets)

    @staticmethod
    def _logical_layers(gl, S, v, num_layers):
        """Packed layer leaves -> logical (num_layers, ...) order."""
        def f(a):
            a = np.asarray(a)
            k, p = 0, 1
            while p < num_layers:      # leading dims multiply to L
                p *= a.shape[k]
                k += 1
            while k < a.ndim - 1 and a.shape[k] == 1:
                k += 1
            a = a.reshape((S, v, -1) + a.shape[k:])
            lpc = a.shape[2]
            out = np.zeros((num_layers,) + a.shape[3:], a.dtype)
            for s in range(S):
                for c in range(v):
                    for j in range(lpc):
                        out[(c * S + s) * lpc + j] = a[s, c, j]
            return out
        return jax.tree_util.tree_map(f, gl)

    @pytest.mark.parametrize("S,v", [(2, 1), (4, 1), (2, 2)])
    def test_pp_matches_pp1_bitwise(self, rng, assert_ulp_close, S, v):
        cfg = tiny_cfg(num_layers=4)
        model = GPTModel(cfg)
        params = model.init_params(jax.random.PRNGKey(7))
        M, mb, seq = 4, 2, 8
        tokens = jnp.asarray(rng.randint(0, 32, (M, mb, seq)))
        targets = jnp.asarray(rng.randint(0, 32, (M, mb, seq)))

        loss1, g1 = self._run(model, params, tokens, targets, 1, 1)
        loss, g = self._run(model, params, tokens, targets, S, v)

        assert np.asarray(loss1).tobytes() == np.asarray(loss).tobytes()
        a = self._logical_layers(g["layers"], S, v, 4)
        b = self._logical_layers(g1["layers"], 1, 1, 4)
        for x, y in zip(jax.tree_util.tree_leaves(a),
                        jax.tree_util.tree_leaves(b), strict=True):
            assert_ulp_close(x, y)
        for k in ("embedding", "final_layernorm"):
            for x, y in zip(jax.tree_util.tree_leaves(g[k]),
                            jax.tree_util.tree_leaves(g1[k]), strict=True):
                assert_ulp_close(x, y)

    def test_dp_tp_pp_sp_composition_bitwise_in_pp(self, rng,
                                                   assert_ulp_close):
        """dp=2 x tp=2 x pp=2 with sequence parallelism: the pp=2 run
        reproduces pp=1 on the same dp x tp submesh (loss bitwise)."""
        cfg = tiny_cfg(num_layers=4, tensor_parallel_size=2,
                       axis_name="model", sequence_parallel=True)
        model = GPTModel(cfg)
        serial = GPTModel(tiny_cfg(num_layers=4))
        params = serial.init_params(jax.random.PRNGKey(8))
        M, mb, seq = 2, 2, 8
        tokens = jnp.asarray(rng.randint(0, 32, (2, M, mb, seq)))
        targets = jnp.asarray(rng.randint(0, 32, (2, M, mb, seq)))

        def run(pp):
            packed, in_specs, local_fn, repack_fn = pack_for_shard_map(
                model, params, n_stages=pp)
            mesh = jax.make_mesh((2, 2, pp), ("data", "model", "pipe"),
                                 devices=jax.devices()[:4 * pp])

            def step(sp, tk, tg):
                loss, g = pipeline_step(
                    model, local_fn(sp), tk[0], tg[0],
                    pipe_axis="pipe", data_axis="data", n_virtual=1)
                return loss, repack_fn(g)

            out = jax.jit(shard_map(
                step, mesh=mesh,
                in_specs=(in_specs, P("data"), P("data")),
                out_specs=(P(), in_specs),
                check_vma=False))(packed, tokens, targets)
            return out[0], out[1], in_specs

        def canon(gl, specs):
            """Merge the (S, lpc) packing dims (located via the leaf's
            pipe-axis spec position) into one logical layer axis so
            pp=1 and pp=2 packings compare leaf-for-leaf."""
            sp_leaves = jax.tree_util.tree_leaves(
                specs, is_leaf=lambda x: isinstance(x, P))
            out = []
            for a, sp in zip(jax.tree_util.tree_leaves(gl), sp_leaves):
                a = np.asarray(a)
                i = list(sp).index("pipe")
                out.append(a.reshape(a.shape[:i] + (-1,)
                                     + a.shape[i + 2:]))
            return out

        loss1, g1, specs1 = run(1)
        loss2, g2, specs2 = run(2)
        assert np.asarray(loss1).tobytes() == np.asarray(loss2).tobytes()
        for x, y in zip(canon(g2["layers"], specs2["layers"]),
                        canon(g1["layers"], specs1["layers"]), strict=True):
            assert_ulp_close(x, y)


class TestStageStacking:
    def test_stack_shapes(self, rng):
        cfg = tiny_cfg(num_layers=4)
        model = GPTModel(cfg)
        params = model.init_params(jax.random.PRNGKey(4))
        stacked = stack_layers_for_pipeline(params["layers"], 2)
        w = stacked["attention"]["qkv"]["weight"]
        assert w.shape[:2] == (2, 2)
        np.testing.assert_array_equal(
            np.asarray(w[1, 0]),
            np.asarray(params["layers"][2]["attention"]["qkv"]["weight"]))

    def test_indivisible_raises(self, rng):
        cfg = tiny_cfg(num_layers=2)
        model = GPTModel(cfg)
        params = model.init_params(jax.random.PRNGKey(5))
        with pytest.raises(ValueError):
            stack_layers_for_pipeline(params["layers"], 3)

    def test_stage_fn_matches_layer_loop(self, rng):
        cfg = tiny_cfg(num_layers=2)
        model = GPTModel(cfg)
        params = model.init_params(jax.random.PRNGKey(6))
        x = jnp.asarray(rng.randn(2, 8, cfg.hidden_size).astype(np.float32))
        stacked = stack_layers_for_pipeline(params["layers"], 1)
        info = JobInfo(jnp.int32(0), jnp.int32(0), jnp.int32(0))
        got = make_stage_fn(model)(
            jax.tree_util.tree_map(lambda p: p[0], stacked), x, info)
        ref, _ = model.backbone(params, x)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=1e-5, atol=1e-6)


class TestAttentionDropout:
    """Train-time attention dropout through the fused flash path end to
    end in the flagship (VERDICT r3 weak item 5): config plumbing,
    eval determinism, per-step mask freshness, a short convergence run,
    and the pipeline seed-carry."""

    def test_config_validation(self):
        with pytest.raises(ValueError, match="attention_dropout"):
            tiny_cfg(attention_dropout=1.5)
        with pytest.raises(ValueError, match="context"):
            tiny_cfg(attention_dropout=0.1, context_axis="context")

    @pytest.mark.slow
    def test_eval_ignores_dropout_and_train_differs(self, rng):
        cfg = tiny_cfg(attention_dropout=0.3, hidden_size=32,
                       num_attention_heads=2, max_seq_len=16)
        plain = GPTModel(tiny_cfg(hidden_size=32, num_attention_heads=2,
                                  max_seq_len=16))
        model = GPTModel(cfg)
        params = model.init_params(jax.random.PRNGKey(0))
        tokens, targets = make_data(rng, cfg, 2, 16)
        # no seed => eval semantics, identical to a dropout-free config
        eval_loss = float(model.loss(params, tokens, targets))
        plain_loss = float(plain.loss(params, tokens, targets))
        np.testing.assert_allclose(eval_loss, plain_loss, rtol=1e-6)
        # seeded train losses: deterministic per seed, fresh across seeds
        l7a = float(model.loss(params, tokens, targets, dropout_seed=7))
        l7b = float(model.loss(params, tokens, targets, dropout_seed=7))
        l8 = float(model.loss(params, tokens, targets, dropout_seed=8))
        assert l7a == l7b
        assert l7a != l8
        assert l7a != eval_loss

    def test_short_training_run_converges(self, rng):
        from apex_tpu.optimizers import FusedAdam

        cfg = tiny_cfg(attention_dropout=0.1, hidden_size=32,
                       num_attention_heads=2, max_seq_len=16)
        model = GPTModel(cfg)
        params = model.init_params(jax.random.PRNGKey(1))
        tokens, targets = make_data(rng, cfg, 4, 16)
        adam = FusedAdam(lr=1e-2)
        state = adam.init(params)

        @jax.jit
        def step(params, state, seed):
            loss, g = jax.value_and_grad(model.loss)(
                params, tokens, targets, dropout_seed=seed)
            params, state = adam.step(g, params, state)
            return loss, params, state

        losses = []
        for i in range(8):
            # the step counter IS the seed: layer streams stride the
            # seed space, so +1 per step gives fresh masks
            loss, params, state = step(params, state, jnp.int32(i))
            losses.append(float(loss))
        assert all(np.isfinite(losses))
        assert losses[-1] < losses[0], losses

    def test_pipeline_seed_carry(self, rng):
        """Per-job dropout seeds are derived arithmetically from
        (microbatch, stage): a 2-stage pipelined step with dropout runs,
        is deterministic per seed, and differs from the dropout-free
        pipeline."""
        cfg = tiny_cfg(attention_dropout=0.3, num_layers=2,
                       hidden_size=32, num_attention_heads=2,
                       max_seq_len=16)
        model = GPTModel(cfg)
        params = model.init_params(jax.random.PRNGKey(2))
        M, mb, seq = 2, 2, 16
        tokens = jnp.asarray(rng.randint(0, 32, (M, mb, seq)))
        targets = jnp.asarray(rng.randint(0, 32, (M, mb, seq)))
        pp = 2
        packed, in_specs, local_fn, repack_fn = pack_for_shard_map(
            model, params, n_stages=pp, tensor_axis=None)
        mesh = jax.make_mesh((pp,), ("pipe",),
                             devices=jax.devices()[:pp])

        def run(seed):
            def fn(sp, tk, tg):
                loss, _ = pipeline_step(model, local_fn(sp), tk, tg,
                                        pipe_axis="pipe",
                                        dropout_seed=seed)
                return loss
            return float(jax.jit(shard_map(
                fn, mesh=mesh, in_specs=(in_specs, P(), P()),
                out_specs=P(), check_vma=False))(packed, tokens, targets))

        a, b, c, none = run(5), run(5), run(6), run(None)
        assert a == b
        assert a != c
        assert a != none
        assert np.isfinite([a, c, none]).all()

    def test_tp_ranks_draw_independent_masks(self, rng):
        """Under tensor parallelism each rank holds DIFFERENT global
        heads, so the attention-dropout streams must differ per rank
        (ADVICE r4: the counter hash keys on the LOCAL head index; the
        model folds a per-rank stride into the seed, like Megatron's
        per-TP-rank dropout RNG offset)."""
        cfg = tiny_cfg(attention_dropout=0.5, hidden_size=32,
                       num_attention_heads=4, max_seq_len=16,
                       tensor_parallel_size=2, axis_name="model")
        model = GPTModel(cfg)
        layer_attn = model.layers[0].attention
        serial = GPTModel(tiny_cfg(hidden_size=32, num_attention_heads=4,
                                   max_seq_len=16))
        params = serial.layers[0].attention.init_params(
            jax.random.PRNGKey(3))
        mesh = jax.make_mesh((2,), ("model",))
        x = jnp.asarray(rng.randn(2, 16, 32).astype(np.float32))

        # give BOTH ranks the same local qkv/proj shard: any output
        # difference between ranks can then only come from the dropout
        # mask stream
        half = {"qkv": {"weight": params["qkv"]["weight"][:48],
                        "bias": params["qkv"]["bias"][:48]},
                "proj": {"weight": params["proj"]["weight"][:, :16],
                         "bias": params["proj"]["bias"]}}

        def fn(p, x):
            return layer_attn(p, x, None, None, dropout_seed=jnp.int32(9))

        out = jax.jit(shard_map(
            fn, mesh=mesh, in_specs=(P(), P()), out_specs=P(),
            check_vma=False))(half, x)

        # serial twin on the same half shard draws rank-0's stream
        # (offset 0, seed 9); with IDENTICAL masks across ranks the
        # RowParallel psum would make the TP output exactly 2x the
        # serial partial (bias is zero) — independence breaks that
        scfg = tiny_cfg(attention_dropout=0.5, hidden_size=32,
                        num_attention_heads=4, max_seq_len=16)
        twin = GPTModel(scfg).layers[0].attention
        ref = twin(half, x, None, None, dropout_seed=jnp.int32(9))
        assert not np.allclose(np.asarray(out), 2 * np.asarray(ref)), (
            "identical dropout masks across TP ranks")


class TestSelectiveRemat:
    """Megatron 'selective activation recompute' parity: remat_policy=
    'dots' saves GEMM outputs through jax.checkpoint while 'full' saves
    nothing; numerics must be identical, memory residency must differ."""

    def test_policies_numerically_identical(self, rng):
        cfg_kw = dict(vocab_size=32, hidden_size=32, num_layers=2,
                      num_attention_heads=2, max_seq_len=16, remat=True)
        tokens, targets = make_data(
            rng, GPTConfig(**cfg_kw), 2, 16)
        out = {}
        for pol in ("full", "dots"):
            m = GPTModel(GPTConfig(remat_policy=pol, **cfg_kw))
            p = m.init_params(jax.random.PRNGKey(0))
            loss, g = jax.jit(jax.value_and_grad(m.loss))(p, tokens,
                                                          targets)
            out[pol] = (float(loss), g)
        np.testing.assert_allclose(out["full"][0], out["dots"][0],
                                   rtol=1e-6)
        for a, b in zip(jax.tree_util.tree_leaves(out["full"][1]),
                        jax.tree_util.tree_leaves(out["dots"][1])):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-6, atol=1e-7)

    def test_dots_policy_saves_more(self, rng):
        from apex_tpu.utils.profiling import memory_stats

        cfg_kw = dict(vocab_size=64, hidden_size=64, num_layers=4,
                      num_attention_heads=4, max_seq_len=64, remat=True)
        tokens, targets = make_data(rng, GPTConfig(**cfg_kw), 4, 64)
        temps = {}
        for pol in ("full", "dots"):
            m = GPTModel(GPTConfig(remat_policy=pol, **cfg_kw))
            p = m.init_params(jax.random.PRNGKey(0))
            stats = memory_stats(
                lambda p: jax.value_and_grad(m.loss)(p, tokens, targets),
                p)
            if not stats:
                pytest.skip("backend lacks memory_analysis")
            temps[pol] = stats["temp"]
        # saving dot outputs must change the compiled residency
        assert temps["full"] != temps["dots"], temps

    def test_invalid_policy_rejected(self):
        with pytest.raises(ValueError, match="remat_policy"):
            GPTConfig(vocab_size=8, hidden_size=16, num_layers=1,
                      num_attention_heads=2, max_seq_len=8,
                      remat_policy="everything")


# -- the seam between a model and its layers' kinds ---------------------------
# The plain block and a tiny preset of each pattern configuration, with what
# their layers' kinds gave them: the param tree's paths and shapes (the
# distinct layer trees, and which each layer has), the cache's records, and
# the paths each runs (it refuses every other by name).

_PATHS = ("training forward", "prefill", "decode_step_paged", "decode_chunk",
          "decode_step")

_SEAM = {
    "plain": dict(
        config=dict(vocab_size=64, hidden_size=32, num_layers=2,
            num_attention_heads=4, max_seq_len=32, rotary=False),
        top={"embedding/weight": (64, 32), "final_layernorm/bias": (32,),
             "final_layernorm/weight": (32,), "position_embedding": (32, 32)},
        kinds=[
            {"attention/proj/bias": (32,), "attention/proj/weight": (32, 32),
             "attention/qkv/bias": (96,), "attention/qkv/weight": (96, 32),
             "input_layernorm/bias": (32,), "input_layernorm/weight": (32,),
             "mlp/fc1/bias": (128,), "mlp/fc1/weight": (128, 32),
             "mlp/fc2/bias": (32,), "mlp/fc2/weight": (32, 128),
             "post_attention_layernorm/bias": (32,),
             "post_attention_layernorm/weight": (32,)},
        ], layers=[0, 0],
        cache=((2, 2, 32),), window=None,
        runs=_PATHS),
    "served_attention": dict(
        config=dict(vocab_size=64, hidden_size=32, num_attention_heads=4,
            max_seq_len=32, layer_pattern="*D*E", norm="rmsnorm", bias=False,
            tie_head=False, ffn_activation="swiglu", ffn_hidden_size=16,
            n_experts=4, moe_top_k=2, moe_router="sigmoid"),
        top={"embedding/weight": (64, 32), "final_layernorm/weight": (32,),
             "lm_head/weight": (64, 32)},
        kinds=[
            {"mixer/proj/weight": (32, 32), "mixer/qkv/weight": (96, 32),
             "norm/weight": (32,)},
            {"mixer/fc1/weight": (32, 32), "mixer/fc2/weight": (32, 16),
             "norm/weight": (32,)},
            {"mixer/router/bias": (4,), "mixer/router/weight": (4, 32),
             "mixer/w1": (4, 32, 32), "mixer/w2": (4, 16, 32),
             "norm/weight": (32,)},
        ], layers=[0, 1, 0, 2],
        cache=((2, 2, 32),), window=None,
        runs=("training forward", "prefill", "decode_step_paged")),
    "nemotron_h": dict(
        config=dict(vocab_size=64, hidden_size=32, num_attention_heads=4,
            num_kv_heads=2, head_dim=16, max_seq_len=32, ffn_hidden_size=24,
            n_experts=8, moe_top_k=2, moe_router="sigmoid", moe_shared_ffn=48,
            moe_held=(2, 4), norm="rmsnorm", ffn_activation="relu2",
            bias=False, tie_head=False, rotary=False, layer_pattern="ME*E",
            mamba_num_heads=4, mamba_head_dim=16, mamba_state_size=8,
            mamba_groups=2, mamba_chunk_size=8),
        top={"embedding/weight": (64, 32), "final_layernorm/weight": (32,),
             "lm_head/weight": (64, 32)},
        kinds=[
            {"mixer/A_log": (4,), "mixer/D": (4,), "mixer/conv/bias": (96,),
             "mixer/conv/weight": (96, 4), "mixer/dt_bias": (4,),
             "mixer/in_proj/weight": (164, 32), "mixer/norm/weight": (64,),
             "mixer/out_proj/weight": (32, 64), "norm/weight": (32,)},
            {"mixer/router/bias": (8,), "mixer/router/weight": (8, 32),
             "mixer/shared/fc1/weight": (48, 32),
             "mixer/shared/fc2/weight": (32, 48), "mixer/w1": (4, 32, 24),
             "mixer/w2": (4, 24, 32), "norm/weight": (32,)},
            {"mixer/proj/weight": (32, 64), "mixer/qkv/weight": (128, 32),
             "norm/weight": (32,)},
        ], layers=[0, 1, 2, 1],
        cache=((1, 2, 32),), window=None,
        runs=("training forward",)),
    "lfm2": dict(
        config=dict(vocab_size=64, hidden_size=32, num_attention_heads=4,
            num_kv_heads=2, head_dim=16, max_seq_len=32, ffn_hidden_size=24,
            dense_ffn_hidden_size=48, n_experts=8, moe_top_k=2,
            moe_router="sigmoid", moe_held=(2, 4), norm="rmsnorm",
            ffn_activation="swiglu", bias=False, rope_base=1000000.0,
            qk_norm=True, layer_pattern="CD*E"),
        top={"embedding/weight": (64, 32), "final_layernorm/weight": (32,)},
        kinds=[
            {"mixer/conv/weight": (32, 3), "mixer/in_proj/weight": (96, 32),
             "mixer/out_proj/weight": (32, 32), "norm/weight": (32,)},
            {"mixer/fc1/weight": (96, 32), "mixer/fc2/weight": (32, 48),
             "norm/weight": (32,)},
            {"mixer/k_norm/weight": (16,), "mixer/proj/weight": (32, 64),
             "mixer/q_norm/weight": (16,), "mixer/qkv/weight": (128, 32),
             "norm/weight": (32,)},
            {"mixer/router/bias": (8,), "mixer/router/weight": (8, 32),
             "mixer/w1": (4, 32, 48), "mixer/w2": (4, 24, 32),
             "norm/weight": (32,)},
        ], layers=[0, 1, 2, 3],
        cache=((1, 2, 32),), window=None,
        runs=("training forward",)),
    "glm_dsa": dict(
        config=dict(vocab_size=64, hidden_size=32, num_attention_heads=2,
            max_seq_len=32, layer_pattern="*D*E",
            indexer_types=("full", "shared"), norm="rmsnorm", bias=False,
            tie_head=False, ffn_activation="swiglu", ffn_hidden_size=16,
            dense_ffn_hidden_size=48, n_experts=8, moe_top_k=2,
            moe_router="sigmoid", moe_shared_ffn=16, moe_held=(0, 4),
            kv_lora_rank=32, q_lora_rank=24, qk_nope_head_dim=16,
            qk_rope_head_dim=8, v_head_dim=16, index_topk=4, index_n_heads=2,
            index_head_dim=16),
        top={"embedding/weight": (64, 32), "final_layernorm/weight": (32,),
             "lm_head/weight": (64, 32)},
        kinds=[
            {"mixer/index_k/weight": (16, 32),
             "mixer/index_k_norm/bias": (16,),
             "mixer/index_k_norm/weight": (16,),
             "mixer/index_q/weight": (32, 24), "mixer/index_w/weight": (2, 32),
             "mixer/kv_a/weight": (40, 32), "mixer/kv_b/weight": (64, 32),
             "mixer/kv_norm/weight": (32,), "mixer/proj/weight": (32, 32),
             "mixer/q_a/weight": (24, 32), "mixer/q_b/weight": (48, 24),
             "mixer/q_norm/weight": (24,), "norm/weight": (32,)},
            {"mixer/fc1/weight": (96, 32), "mixer/fc2/weight": (32, 48),
             "norm/weight": (32,)},
            {"mixer/kv_a/weight": (40, 32), "mixer/kv_b/weight": (64, 32),
             "mixer/kv_norm/weight": (32,), "mixer/proj/weight": (32, 32),
             "mixer/q_a/weight": (24, 32), "mixer/q_b/weight": (48, 24),
             "mixer/q_norm/weight": (24,), "norm/weight": (32,)},
            {"mixer/router/bias": (8,), "mixer/router/weight": (8, 32),
             "mixer/shared/fc1/weight": (32, 32),
             "mixer/shared/fc2/weight": (32, 16), "mixer/w1": (4, 32, 32),
             "mixer/w2": (4, 16, 32), "norm/weight": (32,)},
        ], layers=[0, 1, 2, 3],
        cache=((2, 1, 128), (1, 1, 16, "float32")), window=None,
        runs=("prefill", "decode_step_paged")),
    "command_a": dict(
        config=dict(vocab_size=64, hidden_size=32, num_attention_heads=8,
            num_kv_heads=2, head_dim=16, max_seq_len=32, layer_pattern="PPP",
            attention_types=("sliding", "sliding", "full"), sliding_window=8,
            norm="layernorm", norm_bias=False, bias=False,
            ffn_activation="swiglu", ffn_hidden_size=16, n_experts=8,
            moe_top_k=2, moe_router="sigmoid", moe_router_bias=False,
            moe_shared_ffn=32, moe_shared_combine="average", moe_held=(0, 4)),
        top={"embedding/weight": (64, 32), "final_layernorm/weight": (32,)},
        kinds=[
            {"attention/proj/weight": (32, 128),
             "attention/qkv/weight": (192, 32), "mlp/router/weight": (8, 32),
             "mlp/shared/fc1/weight": (64, 32),
             "mlp/shared/fc2/weight": (32, 32), "mlp/w1": (4, 32, 32),
             "mlp/w2": (4, 16, 32), "norm/weight": (32,)},
        ], layers=[0, 0, 0],
        cache=((1, 2, 32),), window=(2, 2, 32, 8),
        runs=("prefill", "decode_step_paged")),
    "smallthinker": dict(
        config=dict(vocab_size=64, hidden_size=32, num_attention_heads=4,
            num_kv_heads=1, head_dim=32, max_seq_len=32, norm="rmsnorm",
            ffn_activation="reglu", bias=False, tie_head=False,
            moe_router="softmax_sorted", moe_router_bias=False, moe_top_k=2,
            ffn_hidden_size=16, n_experts=8, moe_held=(0, 2),
            moe_route_before_attention=True, layer_pattern="*E*E",
            attention_types=("full", "sliding"), rotary_layers=(0, 1),
            sliding_window=8),
        top={"embedding/weight": (64, 32), "final_layernorm/weight": (32,),
             "lm_head/weight": (64, 32)},
        kinds=[
            {"mixer/proj/weight": (32, 128), "mixer/qkv/weight": (192, 32),
             "norm/weight": (32,)},
            {"mixer/router/weight": (8, 32), "mixer/w1": (2, 32, 32),
             "mixer/w2": (2, 16, 32), "norm/weight": (32,)},
        ], layers=[0, 1, 0, 1],
        cache=((1, 2, 32),), window=(1, 2, 32, 8),
        runs=("training forward",)),
}

# from one seed, the params' values: each leaf's sum of magnitudes, weighted
# by its place (a change to any leaf's values, or to their order, moves it)
_VALUES = {"plain": 9192.214800116375, "served_attention": 3751.181616459821,
           "nemotron_h": 11536.840634798964, "lfm2": 5914.758714322942,
           "glm_dsa": 12825.110488336606, "command_a": 12700.969996170606,
           "smallthinker": 4577.454688643726}


def _shapes(tree):
    return {"/".join(str(k.key) for k in path): tuple(x.shape)
            for path, x in jax.tree_util.tree_leaves_with_path(tree)}


@pytest.mark.parametrize("name", list(_SEAM))
def test_a_configuration_keeps_its_params_and_its_cache_records(name):
    seam = _SEAM[name]
    model = GPTModel(GPTConfig(**seam["config"]))
    params = dict(jax.eval_shape(model.init_params, jax.random.PRNGKey(0)))
    layers = params.pop("layers")
    assert _shapes(params) == seam["top"]
    assert [_shapes(lp) for lp in layers] == [seam["kinds"][i]
                                              for i in seam["layers"]]
    assert tuple(tuple(e if isinstance(e, int) else jnp.dtype(e).name
                       for e in record)
                 for record in model.cache_record()) == seam["cache"]
    assert model.window_record() == seam["window"]


@pytest.mark.parametrize("name", list(_SEAM))
def test_a_configuration_keeps_its_param_values(name):
    model = GPTModel(GPTConfig(**_SEAM[name]["config"]))
    leaves = jax.tree_util.tree_leaves(
        jax.jit(model.init_params)(jax.random.PRNGKey(0)))
    assert sum((k + 1) * np.abs(np.asarray(leaf, np.float64)).sum()
               for k, leaf in enumerate(leaves)) == pytest.approx(
                   _VALUES[name], rel=1e-6)


def _trace(model, path):
    """Trace ``path`` of ``model`` on two rows of eight tokens (shapes only:
    nothing is compiled)."""
    cfg = model.cfg
    params = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
    toks, ints = (jax.ShapeDtypeStruct(shape, jnp.int32)
                  for shape in ((2, 8), (2,)))
    if path == "training forward":
        return jax.eval_shape(model.loss, params, toks, toks)
    if path == "prefill":
        return jax.eval_shape(model.prefill, params, toks)
    if path == "decode_step":
        ring = jax.ShapeDtypeStruct((2, cfg.num_layers, 2, 16,
                                     cfg.num_kv_heads, cfg.head_dim),
                                    jnp.float32)
        return jax.eval_shape(model.decode_step, params, ints, ring, ints)
    arrays = tuple(jax.ShapeDtypeStruct((9, layers, parts, 4, width),
                                        (*dtype, jnp.float32)[0])
                   for layers, parts, width, *dtype in model.cache_record())
    pool = arrays[0] if len(arrays) == 1 else arrays
    tables = jax.ShapeDtypeStruct((2, 4), jnp.int32)
    if model.window_record() is not None:
        layers, parts, width, _ = model.window_record()
        pool = (pool, jax.ShapeDtypeStruct((9, layers, parts, 4, width),
                                           jnp.float32))
        tables = (tables, tables)
    if path == "decode_step_paged":
        return jax.eval_shape(model.decode_step_paged, params, ints, pool,
                              tables, ints)
    return jax.eval_shape(model.decode_chunk, params, toks, pool, tables,
                          toks, toks, toks)


@pytest.mark.parametrize("name,path",
                         [(name, path) for name in _SEAM for path in _PATHS])
def test_a_path_runs_or_is_refused_by_name(name, path):
    model = GPTModel(GPTConfig(**_SEAM[name]["config"]))
    if path in _SEAM[name]["runs"]:
        _trace(model, path)
    else:
        with pytest.raises(NotImplementedError, match=path):
            _trace(model, path)
