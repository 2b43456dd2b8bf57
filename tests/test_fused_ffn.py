"""Fused FFN Pallas kernel (ISSUE 17): kernel-vs-reference parity fwd+bwd
(interpret-mode Pallas at flash tolerances; off-TPU dispatch is bitwise),
and the ``fused_ffn`` knob threaded through every parallelism tier —
serial, remat, TP=2 + sequence parallel, pipeline pp=2, MPMD dp2 x pp2 —
plus the config/plan validation surface.

Mirrors ``tests/test_flash_attention.py`` for the kernel half and
``tests/test_gpt.py`` for the tier parity half.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import PartitionSpec as P

from apex_tpu.models.bert import BertConfig, BertModel
from apex_tpu.models.gpt import (GPTConfig, GPTModel, pack_for_shard_map,
                                 pipeline_step)
from apex_tpu.ops.fused_ffn import (fused_ffn, fused_ffn_reference,
                                    fused_ffn_tp)
from apex_tpu.parallel.plan import ParallelPlan
from apex_tpu.utils import set_force_pallas


def _inputs(rng, m, k, f, n, dtype):
    x = jnp.asarray(rng.randn(m, k), dtype)
    w1 = jnp.asarray(rng.randn(f, k) * 0.05, dtype)
    b1 = jnp.asarray(rng.randn(f) * 0.05, dtype)
    w2 = jnp.asarray(rng.randn(n, f) * 0.05, dtype)
    b2 = jnp.asarray(rng.randn(n) * 0.05, dtype)
    return x, w1, b1, w2, b2


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 \
        else dict(rtol=2e-5, atol=2e-5)


def _grads(ffn, args):
    def f(*a):
        return jnp.sum(ffn(*a).astype(jnp.float32))
    return jax.grad(f, argnums=tuple(range(len(args))))(*args)


# ---------------------------------------------------------------------------
# kernel vs reference — Pallas forced on (interpret mode on CPU)
# ---------------------------------------------------------------------------


class TestKernelParity:
    @pytest.fixture(autouse=True)
    def _force_pallas(self):
        set_force_pallas(True)
        yield
        set_force_pallas(None)

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_forward_matches_reference(self, rng, dtype):
        args = _inputs(rng, 256, 128, 512, 128, dtype)
        out = fused_ffn(*args)
        ref = fused_ffn_reference(*args)
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(ref, np.float32),
                                   **_tol(dtype))

    def test_forward_odd_shapes(self, rng):
        # every extent off the 128-lane / block grid: padding must wash out
        args = _inputs(rng, 200, 96, 300, 80, jnp.float32)
        out = fused_ffn(*args, block_m=128, block_f=128)
        ref = fused_ffn_reference(*args)
        np.testing.assert_allclose(out, ref, rtol=2e-5, atol=2e-5)

    def test_forward_no_b2(self, rng):
        x, w1, b1, w2, _ = _inputs(rng, 128, 64, 256, 64, jnp.float32)
        out = fused_ffn(x, w1, b1, w2)
        ref = fused_ffn_reference(x, w1, b1, w2)
        np.testing.assert_allclose(out, ref, rtol=2e-5, atol=2e-5)

    def test_leading_batch_dims(self, rng):
        x, w1, b1, w2, b2 = _inputs(rng, 4 * 64, 64, 256, 64, jnp.float32)
        x3 = x.reshape(4, 64, 64)
        out = fused_ffn(x3, w1, b1, w2, b2)
        assert out.shape == (4, 64, 64)
        ref = fused_ffn_reference(x3, w1, b1, w2, b2)
        np.testing.assert_allclose(out, ref, rtol=2e-5, atol=2e-5)

    def test_grads_match_reference_f32(self, rng):
        args = _inputs(rng, 256, 128, 512, 128, jnp.float32)
        got = _grads(fused_ffn, args)
        ref = _grads(fused_ffn_reference, args)
        for g, r in zip(got, ref, strict=True):
            np.testing.assert_allclose(np.asarray(g), np.asarray(r),
                                       rtol=5e-5, atol=5e-5)

    def test_grads_odd_shapes(self, rng):
        args = _inputs(rng, 200, 96, 300, 80, jnp.float32)
        got = _grads(lambda *a: fused_ffn(*a, block_m=128, block_f=128),
                     args)
        ref = _grads(fused_ffn_reference, args)
        for g, r in zip(got, ref, strict=True):
            np.testing.assert_allclose(np.asarray(g), np.asarray(r),
                                       rtol=5e-5, atol=5e-5)

    def test_grads_bf16_norm_relative(self, rng):
        # the kernel accumulates f32 where the unfused bf16 chain rounds
        # per-op, so element-wise rtol on near-zero entries is meaningless;
        # bound the error relative to the gradient's own magnitude instead
        args = _inputs(rng, 256, 128, 512, 128, jnp.bfloat16)
        got = _grads(fused_ffn, args)
        ref = _grads(fused_ffn_reference, args)
        for g, r in zip(got, ref, strict=True):
            g = np.asarray(g, np.float32)
            r = np.asarray(r, np.float32)
            assert np.abs(g - r).max() / (np.abs(r).max() + 1e-6) < 2e-2

    def test_jit_grad_composes(self, rng):
        args = _inputs(rng, 128, 64, 256, 64, jnp.float32)

        @jax.jit
        def f(*a):
            return jnp.sum(fused_ffn(*a) ** 2)

        g = jax.jit(jax.grad(f, argnums=(0, 1)))(*args)
        assert all(np.all(np.isfinite(np.asarray(t))) for t in g)


# ---------------------------------------------------------------------------
# off-TPU dispatch contract — knob on must be BITWISE the unfused chain
# ---------------------------------------------------------------------------


class TestOffTpuDispatch:
    def test_forward_bitwise(self, rng):
        args = _inputs(rng, 64, 32, 128, 32, jnp.float32)
        set_force_pallas(None)
        out = fused_ffn(*args)
        ref = fused_ffn_reference(*args)
        assert np.asarray(out).tobytes() == np.asarray(ref).tobytes()

    def test_grads_bitwise(self, rng):
        args = _inputs(rng, 64, 32, 128, 32, jnp.float32)
        got = _grads(fused_ffn, args)
        ref = _grads(fused_ffn_reference, args)
        for g, r in zip(got, ref, strict=True):
            assert np.asarray(g).tobytes() == np.asarray(r).tobytes()

    def test_force_toggle_switches_paths(self, rng):
        # both paths agree within interpret-mode tolerance on the same
        # inputs, proving the dispatch toggle selects real alternatives
        args = _inputs(rng, 128, 64, 128, 64, jnp.float32)
        try:
            set_force_pallas(False)
            ref = fused_ffn(*args)
            set_force_pallas(True)
            out = fused_ffn(*args)
        finally:
            set_force_pallas(None)
        np.testing.assert_allclose(out, ref, rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# validation surface
# ---------------------------------------------------------------------------


class TestValidation:
    def test_shape_mismatch_raises(self, rng):
        x, w1, b1, w2, b2 = _inputs(rng, 64, 32, 128, 32, jnp.float32)
        with pytest.raises(ValueError, match="w2"):
            fused_ffn(x, w1, b1, w2[:, :100], b2)

    def test_gpt_moe_conflict_raises(self):
        with pytest.raises(ValueError, match="one or the other"):
            GPTConfig(vocab_size=32, hidden_size=16, num_layers=2,
                      num_attention_heads=2, max_seq_len=8,
                      fused_ffn=True, n_experts=2)

    def test_mlp_forward_wrong_shape_raises(self, rng):
        from apex_tpu.mlp import MLP, mlp_forward
        m = MLP([16, 32, 32, 16], activation="gelu")
        params = m.init_params(jax.random.PRNGKey(0))
        x = jnp.asarray(rng.randn(4, 16), jnp.float32)
        with pytest.raises(ValueError,
                           match="2-layer biased GELU"):
            mlp_forward(params, x, activation="gelu", fused_ffn=True)
        with pytest.raises(ValueError,
                           match="2-layer biased GELU"):
            m2 = MLP([16, 32, 16], activation="relu")
            mlp_forward(m2.init_params(jax.random.PRNGKey(0)), x,
                        activation="relu", fused_ffn=True)

    def test_plan_roundtrip(self):
        plan = ParallelPlan(tp=2, sequence_parallel=True, fused_ffn=True)
        d = plan.to_dict()
        assert d["fused_ffn"] is True
        assert ParallelPlan.from_dict(d) == plan
        assert "ffn=fused" in plan.describe()
        # default plans must serialize byte-identically to pre-knob writers
        assert "fused_ffn" not in ParallelPlan().to_dict()

    def test_plan_applies_to_config(self):
        cfg = BertConfig(vocab_size=64, hidden_size=32, num_layers=2,
                         num_attention_heads=4, max_seq_len=16,
                         plan=ParallelPlan(fused_ffn=True))
        assert cfg.fused_ffn is True

    def test_plan_conflict_warns(self):
        with pytest.warns(DeprecationWarning):
            GPTConfig(vocab_size=32, hidden_size=16, num_layers=2,
                      num_attention_heads=2, max_seq_len=8,
                      fused_ffn=True, plan=ParallelPlan())


# ---------------------------------------------------------------------------
# module rewire: fused_dense / mlp route onto the same kernel
# ---------------------------------------------------------------------------


class TestModuleRewire:
    def test_fused_dense_gelu_dense_bitwise(self, rng):
        from apex_tpu.fused_dense import FusedDenseGeluDense
        off = FusedDenseGeluDense(32, 128, 32)
        on = FusedDenseGeluDense(32, 128, 32, fused_ffn=True)
        params = off.init_params(jax.random.PRNGKey(3))
        x = jnp.asarray(rng.randn(8, 32), jnp.float32)
        assert np.asarray(on(params, x)).tobytes() \
            == np.asarray(off(params, x)).tobytes()

    def test_mlp_bitwise(self, rng):
        from apex_tpu.mlp import MLP
        off = MLP([16, 64, 16], activation="gelu")
        on = MLP([16, 64, 16], activation="gelu", fused_ffn=True)
        params = off.init_params(jax.random.PRNGKey(4))
        x = jnp.asarray(rng.randn(8, 16), jnp.float32)
        assert np.asarray(on(params, x)).tobytes() \
            == np.asarray(off(params, x)).tobytes()


# ---------------------------------------------------------------------------
# model threading: serial / remat / TP+SP / pipeline / MPMD
# ---------------------------------------------------------------------------

_GPT_KW = dict(vocab_size=32, hidden_size=16, num_layers=2,
               num_attention_heads=2, max_seq_len=8)


def _gpt_data(rng, batch=4, seq=8):
    tokens = jnp.asarray(rng.randint(0, 32, (batch, seq)))
    targets = jnp.asarray(rng.randint(0, 32, (batch, seq)))
    return tokens, targets


def _loss_and_grads(model, params, tokens, targets):
    return jax.jit(jax.value_and_grad(model.loss))(params, tokens, targets)


class TestModelThreading:
    def test_gpt_serial_bitwise(self, rng):
        params = GPTModel(GPTConfig(**_GPT_KW)).init_params(
            jax.random.PRNGKey(0))
        tokens, targets = _gpt_data(rng)
        l0, g0 = _loss_and_grads(GPTModel(GPTConfig(**_GPT_KW)),
                                 params, tokens, targets)
        l1, g1 = _loss_and_grads(
            GPTModel(GPTConfig(fused_ffn=True, **_GPT_KW)),
            params, tokens, targets)
        assert np.asarray(l0).tobytes() == np.asarray(l1).tobytes()
        for a, b in zip(jax.tree_util.tree_leaves(g0),
                        jax.tree_util.tree_leaves(g1), strict=True):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_gpt_remat_bitwise(self, rng):
        params = GPTModel(GPTConfig(**_GPT_KW)).init_params(
            jax.random.PRNGKey(1))
        tokens, targets = _gpt_data(rng)
        l0, g0 = _loss_and_grads(
            GPTModel(GPTConfig(remat=True, **_GPT_KW)),
            params, tokens, targets)
        l1, g1 = _loss_and_grads(
            GPTModel(GPTConfig(fused_ffn=True, remat=True, **_GPT_KW)),
            params, tokens, targets)
        assert np.asarray(l0).tobytes() == np.asarray(l1).tobytes()
        for a, b in zip(jax.tree_util.tree_leaves(g0),
                        jax.tree_util.tree_leaves(g1), strict=True):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_bert_serial_bitwise(self, rng):
        kw = dict(vocab_size=64, hidden_size=32, num_layers=2,
                  num_attention_heads=4, max_seq_len=16)
        params = BertModel(BertConfig(**kw)).init_params(
            jax.random.PRNGKey(2))
        tokens = jnp.asarray(rng.randint(0, 64, (2, 16)))
        labels = tokens
        l0, g0 = _loss_and_grads(BertModel(BertConfig(**kw)),
                                 params, tokens, labels)
        l1, g1 = _loss_and_grads(
            BertModel(BertConfig(fused_ffn=True, **kw)),
            params, tokens, labels)
        assert np.asarray(l0).tobytes() == np.asarray(l1).tobytes()
        for a, b in zip(jax.tree_util.tree_leaves(g0),
                        jax.tree_util.tree_leaves(g1), strict=True):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_tp2_sp_parity(self, rng):
        serial = GPTModel(GPTConfig(**_GPT_KW))
        params = serial.init_params(jax.random.PRNGKey(5))
        tokens, targets = _gpt_data(rng)
        ref_loss = float(jax.jit(serial.loss)(params, tokens, targets))
        ref_grads = jax.jit(jax.grad(serial.loss))(params, tokens, targets)

        par = GPTModel(GPTConfig(tensor_parallel_size=2, axis_name="model",
                                 sequence_parallel=True, fused_ffn=True,
                                 **_GPT_KW))
        mesh = jax.make_mesh((2,), ("model",), devices=jax.devices()[:2])
        packed, in_specs, local_fn, repack_fn = pack_for_shard_map(
            par, params)

        def step(sp, tk, tg):
            loss, g = jax.value_and_grad(par.loss)(local_fn(sp), tk, tg)
            return loss, repack_fn(g)

        loss, grads = jax.jit(shard_map(
            step, mesh=mesh, in_specs=(in_specs, P(), P()),
            out_specs=(P(), in_specs),
            check_vma=False))(packed, tokens, targets)

        assert abs(float(loss) - ref_loss) <= 7e-7
        ref_packed, _, _, _ = pack_for_shard_map(par, ref_grads)
        for got, ref in zip(jax.tree_util.tree_leaves(grads),
                            jax.tree_util.tree_leaves(ref_packed),
                            strict=True):
            np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                       rtol=5e-4, atol=1e-5)

    def _pp_run(self, model, params, tokens, targets, S):
        packed, in_specs, local_fn, repack_fn = pack_for_shard_map(
            model, params, n_stages=S, tensor_axis=None)
        mesh = jax.make_mesh((S,), ("pipe",), devices=jax.devices()[:S])

        def step(sp, tk, tg):
            loss, g = pipeline_step(model, local_fn(sp), tk, tg,
                                    pipe_axis="pipe")
            return loss, repack_fn(g)

        return jax.jit(shard_map(
            step, mesh=mesh, in_specs=(in_specs, P(), P()),
            out_specs=(P(), in_specs),
            check_vma=False))(packed, tokens, targets)

    def test_pp2_bitwise(self, rng, assert_ulp_close):
        model = GPTModel(GPTConfig(fused_ffn=True, **_GPT_KW))
        params = model.init_params(jax.random.PRNGKey(7))
        M, mb, seq = 4, 2, 8
        tokens = jnp.asarray(rng.randint(0, 32, (M, mb, seq)))
        targets = jnp.asarray(rng.randint(0, 32, (M, mb, seq)))

        loss1, g1 = self._pp_run(model, params, tokens, targets, 1)
        loss2, g2 = self._pp_run(model, params, tokens, targets, 2)
        assert np.asarray(loss1).tobytes() == np.asarray(loss2).tobytes()
        # pp packs layers per stage; compare leaf bytes after sorting by
        # shape-erased flattening per key, stage dim first
        for k in ("embedding", "final_layernorm"):
            for a, b in zip(jax.tree_util.tree_leaves(g1[k]),
                            jax.tree_util.tree_leaves(g2[k]),
                            strict=True):
                assert_ulp_close(a, b)
        for a, b in zip(jax.tree_util.tree_leaves(g1["layers"]),
                        jax.tree_util.tree_leaves(g2["layers"]),
                        strict=True):
            a, b = np.asarray(a), np.asarray(b)
            assert_ulp_close(a.reshape(b.shape), b)

    @pytest.mark.slow
    def test_mpmd_dp2_pp2_bitwise(self, rng):
        from apex_tpu.mpmd import MpmdPipeline
        params = GPTModel(GPTConfig(**_GPT_KW)).init_params(
            jax.random.PRNGKey(9))
        plan = ParallelPlan(dp=2, pp=2, n_microbatches=2)
        tokens = jnp.asarray(rng.randint(0, 32, (8, 8)))
        targets = jnp.asarray(rng.randint(0, 32, (8, 8)))

        runs = []
        for fused in (False, True):
            kw = dict(_GPT_KW, fused_ffn=fused)
            eng = MpmdPipeline(kw, params, plan,
                               devices=jax.devices()[:4])
            runs.append(eng.loss_and_grads(tokens, targets, step=0))
        (l0, g0), (l1, g1) = runs
        assert np.float32(l0).tobytes() == np.float32(l1).tobytes()
        for a, b in zip(jax.tree_util.tree_leaves(g0),
                        jax.tree_util.tree_leaves(g1), strict=True):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
