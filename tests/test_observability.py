"""Profiling/observability utilities + pipeline memory accounting
(reference: SURVEY §5 — nvtx ranges -> named scopes, pyprof -> jax
profiler traces, race detection -> program-hash assertion; plus the
pipeline engine's remat memory claim, measured here instead of asserted
in a docstring)."""

import logging

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import PartitionSpec as P

from apex_tpu.models.gpt import (GPTConfig, GPTModel, pack_for_shard_map,
                                 pipeline_step)
from apex_tpu.transformer import parallel_state
from apex_tpu.transformer.log_util import (get_transformer_logger,
                                           set_logging_level)
from apex_tpu.utils import profiling


class TestLogUtil:
    def test_logger_namespace(self):
        lg = get_transformer_logger("pipeline_parallel.py")
        assert lg.name == "apex_tpu.transformer.pipeline_parallel"

    def test_set_level(self):
        set_logging_level(logging.DEBUG)
        assert logging.getLogger("apex_tpu").level == logging.DEBUG
        set_logging_level(logging.WARNING)


class TestNamedScopes:
    def test_annotate_in_hlo_metadata(self):
        def f(x):
            with profiling.annotate("my_hot_block"):
                return jnp.sin(x) * 2

        # scope names live in HLO op metadata (the compiled text), which
        # is what xprof reads
        text = jax.jit(f).lower(jnp.ones((4,))).compile().as_text()
        assert "my_hot_block" in text

    def test_range_push_pop(self):
        def f(x):
            profiling.range_push("pushed_range")
            y = x + 1
            profiling.range_pop()
            return y

        text = jax.jit(f).lower(jnp.ones((4,))).compile().as_text()
        assert "pushed_range" in text

    def test_model_scopes_present(self):
        cfg = GPTConfig(vocab_size=32, hidden_size=16, num_layers=1,
                        num_attention_heads=2, max_seq_len=8)
        model = GPTModel(cfg)
        params = model.init_params(jax.random.PRNGKey(0))
        tokens = jnp.zeros((1, 8), jnp.int32)
        text = jax.jit(model.loss).lower(params, tokens,
                                         tokens).compile().as_text()
        assert "attention" in text and "mlp" in text


class TestProgramHash:
    def test_deterministic(self):
        def f(x):
            return x * 2 + 1

        x = jnp.ones((8,))
        assert profiling.program_hash(f, x) == profiling.program_hash(f, x)

    def test_differs_across_programs(self):
        x = jnp.ones((8,))
        h1 = profiling.program_hash(lambda v: v * 2, x)
        h2 = profiling.program_hash(lambda v: v * 3, x)
        assert h1 != h2

    def test_assert_same_program_single_controller(self):
        x = jnp.ones((8,))
        h = profiling.assert_same_program(lambda v: v + 1, x)
        assert isinstance(h, str) and len(h) == 64
        # precomputed-hash form
        assert profiling.assert_same_program(h) == h


class TestMemoryStats:
    def test_basic_fields(self):
        stats = profiling.memory_stats(
            lambda x: jnp.sin(x @ x).sum(), jnp.ones((64, 64)))
        if not stats:
            pytest.skip("backend lacks memory_analysis")
        assert stats["argument"] == 64 * 64 * 4
        assert stats["temp"] >= 0

    def test_remat_cuts_grad_residency(self):
        """Per-layer jax.checkpoint trades temp memory for recompute —
        measured.  (Wrapping a whole scan in checkpoint does NOT cut the
        peak: the recomputed forward's residuals are all live at once;
        the win comes from remat at layer granularity.)"""
        w = jnp.ones((128, 128))

        def deep(w, x, ckpt):
            def layer(h, _):
                def f(h):
                    h = jnp.tanh(h @ w)
                    h = jnp.tanh(h @ w)
                    h = jnp.tanh(h @ w)
                    return h
                if ckpt:
                    f = jax.checkpoint(f)
                return f(h), None
            return jax.lax.scan(layer, x, None, length=16)[0].sum()

        x = jnp.ones((256, 128))
        grad_plain = lambda w, x: jax.grad(deep)(w, x, False)
        grad_remat = lambda w, x: jax.grad(deep)(w, x, True)
        plain = profiling.memory_stats(grad_plain, w, x)
        remat = profiling.memory_stats(grad_remat, w, x)
        if not plain:
            pytest.skip("backend lacks memory_analysis")
        assert remat["temp"] < plain["temp"], (remat, plain)


class TestPipelineMemoryProfile:
    """The round-1/2 open question, re-measured on the ring engine: the
    scan saves only stage INPUTS in a fixed ``2L-1`` ring buffer and
    recomputes each stage forward inside the per-tick vjp, so activation
    residency is bounded in M — temp grows only by the ``(M, ...)``
    microbatch I/O buffers — and ``remat`` (per-layer checkpoint inside
    the tick vjp) cuts the within-tick residuals.  Measured via XLA's own
    accounting."""

    def _pipeline_grad_temp(self, M, remat):
        parallel_state.destroy_model_parallel()
        try:
            mesh = parallel_state.initialize_model_parallel(1, 2)
            cfg_kw = dict(vocab_size=32, hidden_size=64, num_layers=4,
                          num_attention_heads=4, max_seq_len=32)
            model = GPTModel(GPTConfig(**cfg_kw))
            params = model.init_params(jax.random.PRNGKey(0))
            packed, in_specs, local_fn, repack_fn = pack_for_shard_map(
                model, params, n_stages=2, tensor_axis=None)
            mb, seq = 2, 32
            tokens = jnp.zeros((M * mb, seq), jnp.int32)

            def step(sp, tokens):
                tk = tokens.reshape(M, mb, seq)
                loss, g = pipeline_step(model, local_fn(sp), tk, tk,
                                        pipe_axis="pipe", remat=remat)
                return loss, repack_fn(g)

            fn = shard_map(step, mesh=mesh,
                           in_specs=(in_specs, P()),
                           out_specs=(P(), in_specs), check_vma=False)
            stats = profiling.memory_stats(fn, packed, tokens)
            return stats.get("temp")
        finally:
            parallel_state.destroy_model_parallel()

    @pytest.mark.slow
    def test_remat_cuts_tick_residuals_and_growth_stays_io_bound(self):
        t2_plain = self._pipeline_grad_temp(2, remat=False)
        if t2_plain is None:
            pytest.skip("backend lacks memory_analysis")
        t6_plain = self._pipeline_grad_temp(6, remat=False)
        t2_remat = self._pipeline_grad_temp(2, remat=True)
        t6_remat = self._pipeline_grad_temp(6, remat=True)
        print(f"\npipeline grad temp bytes: M=2 plain={t2_plain} "
              f"remat={t2_remat}; M=6 plain={t6_plain} remat={t6_remat}")
        # remat shrinks the per-tick residual set at fixed M
        assert t2_remat < t2_plain, (t2_remat, t2_plain)
        assert t6_remat < t6_plain, (t6_remat, t6_plain)
        # residency growth with M is the microbatch I/O term only — the
        # saved-activation set is the fixed ring buffer, so the growth is
        # no larger under plain than under remat (both ~= the I/O term)
        assert (t6_plain - t2_plain) <= (t6_remat - t2_remat) * 2, (
            (t2_plain, t6_plain), (t2_remat, t6_remat))

    def _interleaved_grad_temp(self, M, remat):
        from apex_tpu.transformer.pipeline_parallel.schedules import (
            forward_backward_pipelining_with_interleaving)

        width, S, v, mb = 64, 2, 2, 2
        mesh = jax.make_mesh((S,), ("pipe",))
        rng = np.random.RandomState(0)
        w = jnp.asarray(rng.randn(S, v, width, width) * 0.1, jnp.float32)
        b = jnp.zeros((S, v, width), jnp.float32)
        x = jnp.asarray(rng.randn(M, mb, width), jnp.float32)
        t = jnp.asarray(rng.randn(M, mb, width), jnp.float32)

        def stage(p, x):
            return jnp.tanh(x @ p["w"] + p["b"])

        def loss(y, t):
            return jnp.mean((y - t) ** 2)

        def f(w, b, x, t):
            local = {"w": w[0], "b": b[0]}
            lv, g = forward_backward_pipelining_with_interleaving(
                stage, loss, local, x, t, axis_name="pipe",
                n_virtual=v, remat=remat)
            return lv, jax.tree_util.tree_map(lambda g: g[None], g)

        fn = shard_map(f, mesh=mesh,
                       in_specs=(P("pipe"), P("pipe"), P(), P()),
                       out_specs=(P(), {"w": P("pipe"), "b": P("pipe")}),
                       check_vma=False)
        return profiling.memory_stats(fn, w, b, x, t).get("temp")

    def test_interleaved_residency_bounded_in_m(self):
        """Same measurement for the interleaved (virtual-chunk) schedule:
        the ring buffer is sized by L = S*v, not by M, so tripling M must
        not triple the temp residency."""
        t2 = self._interleaved_grad_temp(2, remat=False)
        if t2 is None:
            pytest.skip("backend lacks memory_analysis")
        t6 = self._interleaved_grad_temp(6, remat=False)
        print(f"\ninterleaved grad temp bytes: M=2 {t2}; M=6 {t6}")
        assert t6 < 3 * t2, (t2, t6)
