"""On-chip lane: Pallas kernels + amp composition on the real TPU.

Run with ``APEX_TPU_ON_CHIP=1 python -m pytest tests/test_on_chip.py -m tpu``
in ONE process on the machine that has the chip.  The default (CPU) lane
skips these — interpret mode cannot enforce TPU tiling or VMEM limits,
which is exactly what this lane exists to catch (the round-2 amp x
Pallas breakage survived a green CPU suite).  With the lane asked for and
no TPU found, every test fails.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import chip_smoke

pytestmark = pytest.mark.tpu


@pytest.fixture(scope="module", autouse=True)
def _require_tpu():
    # the lane was asked for (APEX_TPU_ON_CHIP=1): no chip is a failure
    dev = jax.devices()[0]
    assert dev.platform == "tpu", (
        f"on-chip lane needs a TPU, JAX found {dev.platform!r} "
        f"({dev.device_kind})")


class TestKernelParityAtModelShapes:
    """Every Pallas kernel against its reference at the BERT-large /
    GPT-350M shapes — the checks ``chip_smoke.py`` runs (one
    implementation), including the decode kernels of PRs 1 and 10, the
    fused FFN (PR 17) and the int8 GEMM (PR 18)."""

    @pytest.mark.parametrize("name", list(chip_smoke.KERNEL_CHECKS))
    def test_matches_reference(self, name):
        chip_smoke.KERNEL_CHECKS[name]()


class TestKernelParityOnChip:
    def test_layer_norm_fwd_bwd(self, rng):
        from apex_tpu.ops.layer_norm import fused_layer_norm_affine

        x = jnp.asarray(rng.randn(64, 1024).astype(np.float32))
        w = jnp.asarray(rng.randn(1024).astype(np.float32))
        b = jnp.asarray(rng.randn(1024).astype(np.float32))

        def ref(x, w, b):
            m = x.mean(-1, keepdims=True)
            v = x.var(-1, keepdims=True)
            return (x - m) / jnp.sqrt(v + 1e-5) * w + b

        out = fused_layer_norm_affine(x, w, b)
        np.testing.assert_allclose(out, ref(x, w, b), rtol=1e-4, atol=1e-4)
        g = jax.grad(lambda x, w, b: jnp.sum(
            fused_layer_norm_affine(x, w, b) ** 2), (0, 1, 2))(x, w, b)
        gr = jax.grad(lambda x, w, b: jnp.sum(ref(x, w, b) ** 2),
                      (0, 1, 2))(x, w, b)
        for a, r in zip(g, gr):
            np.testing.assert_allclose(a, r, rtol=1e-3, atol=1e-3)

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("causal", [False, True])
    def test_flash_attention_fwd_bwd(self, rng, dtype, causal):
        from apex_tpu.ops.flash_attention import (
            flash_attention, flash_attention_reference)

        q = jnp.asarray(rng.randn(2, 4, 256, 64), dtype)
        k = jnp.asarray(rng.randn(2, 4, 256, 64), dtype)
        v = jnp.asarray(rng.randn(2, 4, 256, 64), dtype)
        # on-chip f32 matmuls ride the MXU at bf16-pass precision (the
        # jnp reference drifts the same ~0.2% from a HIGHEST-precision
        # run), so tolerances are set to that floor, not CPU f32
        out = flash_attention(q, k, v, causal=causal)
        ref = flash_attention_reference(q, k, v, causal=causal)
        tol = 5e-2 if dtype == jnp.bfloat16 else 2e-2
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(ref, np.float32),
                                   rtol=tol, atol=tol)
        gf = jax.grad(lambda q: jnp.sum(flash_attention(
            q, k, v, causal=causal).astype(jnp.float32)))(q)
        gr = jax.grad(lambda q: jnp.sum(flash_attention_reference(
            q, k, v, causal=causal).astype(jnp.float32)))(q)
        tol = 1e-1 if dtype == jnp.bfloat16 else 5e-2
        np.testing.assert_allclose(np.asarray(gf, np.float32),
                                   np.asarray(gr, np.float32),
                                   rtol=tol, atol=tol)

    def test_multi_tensor_adam_step(self, rng):
        from apex_tpu.optimizers import FusedAdam

        params = [jnp.asarray(rng.randn(257, 130).astype(np.float32)),
                  jnp.asarray(rng.randn(33).astype(np.float32))]
        grads = [jnp.asarray(rng.randn(257, 130).astype(np.float32)),
                 jnp.asarray(rng.randn(33).astype(np.float32))]
        adam = FusedAdam(lr=1e-3)
        state = adam.init(params)
        new_params, _ = jax.jit(adam.step)(grads, params, state)
        import optax
        opt = optax.adamw(1e-3, b1=0.9, b2=0.999, eps=1e-8,
                          weight_decay=0.0)
        ostate = opt.init(params)
        upd, _ = opt.update(grads, ostate, params)
        ref = optax.apply_updates(params, upd)
        for a, r in zip(new_params, ref):
            np.testing.assert_allclose(a, r, rtol=1e-5, atol=1e-5)

    def test_xentropy_and_softmax(self, rng):
        from apex_tpu.ops.softmax import scaled_upper_triang_masked_softmax
        from apex_tpu.ops.xentropy import softmax_cross_entropy_loss

        x = jnp.asarray(rng.randn(8, 128, 128).astype(np.float32))
        y = scaled_upper_triang_masked_softmax(x, 0.5)
        assert bool(jnp.all(jnp.isfinite(y)))
        logits = jnp.asarray(rng.randn(32, 512).astype(np.float32))
        labels = jnp.asarray(rng.randint(0, 512, (32,)))
        loss = softmax_cross_entropy_loss(logits, labels)
        ref = -jax.nn.log_softmax(logits)[jnp.arange(32), labels]
        np.testing.assert_allclose(loss, ref, rtol=1e-5, atol=1e-5)


class TestAmpComposition:
    def test_grad_autocast_over_pallas_layer_norm(self, rng):
        """THE round-2 breakage: grad(autocast(loss)) over FusedLayerNorm
        on the chip."""
        from apex_tpu import amp
        from apex_tpu.normalization import FusedLayerNorm

        ln = FusedLayerNorm(256)
        params = {"ln": ln.init_params(),
                  "w": jnp.asarray(rng.randn(256, 256).astype(np.float32))}
        x = jnp.asarray(rng.randn(8, 256).astype(np.float32))

        def loss(params, x):
            return jnp.sum(ln(params["ln"], x @ params["w"]) ** 2)

        g = jax.grad(amp.autocast(loss))(params, x)
        for leaf in jax.tree_util.tree_leaves(g):
            assert np.all(np.isfinite(np.asarray(leaf, np.float32)))


class TestTrainStepSmoke:
    def test_gpt_2layer_train_step(self, rng):
        from apex_tpu.models.gpt import GPTConfig, GPTModel
        from apex_tpu.optimizers import FusedAdam

        cfg = GPTConfig(vocab_size=512, hidden_size=256, num_layers=2,
                        num_attention_heads=4, max_seq_len=256,
                        dtype=jnp.bfloat16)
        model = GPTModel(cfg)
        params = model.init_params(jax.random.PRNGKey(0))
        adam = FusedAdam(lr=1e-3)
        opt_state = adam.init(params)
        tokens = jnp.asarray(rng.randint(0, 512, (4, 256)))
        targets = jnp.asarray(rng.randint(0, 512, (4, 256)))

        @jax.jit
        def step(params, opt_state):
            loss, grads = jax.value_and_grad(model.loss)(params, tokens,
                                                         targets)
            params, opt_state = adam.step(grads, params, opt_state)
            return loss, params, opt_state

        losses = []
        for _ in range(5):
            loss, params, opt_state = step(params, opt_state)
            losses.append(float(loss))
        assert all(np.isfinite(losses)), losses
        assert losses[-1] < losses[0], losses


class TestRound3SurfacesOnChip:
    """New round-3 surfaces exercised where they actually run."""

    def test_moe_fwd_bwd(self, rng):
        from apex_tpu.transformer.expert_parallel import MoEConfig, MoEMLP

        m = MoEMLP(MoEConfig(hidden_size=256, ffn_hidden_size=1024,
                             n_experts=8))
        params = m.init_params(jax.random.PRNGKey(0))
        x = jnp.asarray(rng.randn(512, 256), jnp.bfloat16)
        out, aux = jax.jit(m)(params, x)
        assert out.shape == x.shape
        assert bool(jnp.isfinite(aux))
        g = jax.jit(jax.grad(
            lambda p: m(p, x)[0].astype(jnp.float32).sum()))(params)
        for leaf in jax.tree_util.tree_leaves(g):
            assert bool(jnp.all(jnp.isfinite(leaf)))

    def test_openfold_attention_flash_path(self, rng):
        from apex_tpu.contrib.openfold_triton import attention_core
        from apex_tpu.ops.flash_attention import flash_attention_reference

        q = jnp.asarray(rng.randn(2, 4, 256, 64) * 0.3, jnp.bfloat16)
        out = jax.jit(attention_core)(q, q, q)
        ref = flash_attention_reference(q.astype(jnp.float32),
                                        q.astype(jnp.float32),
                                        q.astype(jnp.float32))
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(ref), rtol=2e-2, atol=2e-2)

    def test_flash_attention_varlen(self, rng):
        from apex_tpu.ops.flash_attention import (flash_attention,
                                                  flash_attention_reference)

        q = jnp.asarray(rng.randn(3, 2, 256, 64) * 0.3, jnp.bfloat16)
        lens = jnp.asarray([256, 192, 64])
        out = jax.jit(lambda q: flash_attention(
            q, q, q, kv_seqlens=lens))(q)
        ref = flash_attention_reference(q.astype(jnp.float32),
                                        q.astype(jnp.float32),
                                        q.astype(jnp.float32),
                                        kv_seqlens=lens)
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(ref), rtol=2e-2, atol=2e-2)

    def test_gds_roundtrip_device_arrays(self, rng, tmp_path):
        from apex_tpu.contrib import gpu_direct_storage as gds

        tree = {"w": jnp.asarray(rng.randn(512, 512), jnp.bfloat16),
                "b": jnp.asarray(rng.randn(512), jnp.float32)}
        p = str(tmp_path / "ck.apxt")
        gds.save(p, tree)
        out = gds.load(p, tree_like=tree)
        np.testing.assert_array_equal(
            np.asarray(tree["w"]).view(np.uint8), out["w"].view(np.uint8))

    def test_ring_attention_single_device_path(self, rng):
        """n=1 context axis falls through to the flash kernel on chip."""
        from jax.sharding import PartitionSpec as P

        from apex_tpu.ops.flash_attention import flash_attention_reference
        from apex_tpu.transformer.context_parallel import ring_attention

        mesh = jax.make_mesh((1,), ("context",))
        q = jnp.asarray(rng.randn(1, 2, 256, 64) * 0.3, jnp.bfloat16)
        spec = P(None, None, "context", None)
        out = jax.jit(jax.shard_map(
            lambda q, k, v: ring_attention(q, k, v, "context",
                                           causal=True),
            mesh=mesh, in_specs=(spec,) * 3, out_specs=spec))(q, q, q)
        ref = flash_attention_reference(
            q.astype(jnp.float32), q.astype(jnp.float32),
            q.astype(jnp.float32), causal=True)
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(ref), rtol=2e-2, atol=2e-2)


class TestXlaFusionClaim:
    """SURVEY sanctions mlp/fused_dense as jnp-only because 'XLA already
    fuses GEMM+bias+activation'; this pins the claim to the compiled
    program: the ENTRY computation may contain only GEMMs, fusions and
    plumbing — any standalone elementwise kernel (bias add, gelu, relu)
    means an un-fused epilogue and fails here."""

    # any of these appearing as a standalone ENTRY instruction means an
    # un-fused elementwise kernel (HLO type grammar is too gnarly to
    # whitelist-parse robustly, so assert the negative directly)
    _ELEMENTWISE = ("add", "subtract", "multiply", "divide", "maximum",
                    "minimum", "exponential", "tanh", "logistic", "rsqrt",
                    "power", "select", "compare")

    def _entry_strays(self, compiled_text):
        import re
        blocks = re.split(r"\n\s*\n", compiled_text)
        entry = next(b for b in blocks if "ENTRY" in b)
        pat = re.compile(
            r"= .*? (%s)\(" % "|".join(self._ELEMENTWISE))
        return [l.strip()[:120] for l in entry.splitlines()
                if " = " in l and pat.search(l)]

    def test_mlp_forward_epilogues_fused(self):
        from apex_tpu.mlp import MLP

        m = MLP([1024, 4096, 1024], activation="relu")
        params = m.init_params(jax.random.PRNGKey(0))
        x = jnp.ones((512, 1024), jnp.bfloat16)
        hlo = jax.jit(m.apply).lower(params, x).compile().as_text()
        strays = self._entry_strays(hlo)
        assert not strays, f"unfused entry ops: {strays}"
        # the chain compiles to fused kernels (GEMMs absorbed into
        # fusions on this backend), never standalone elementwise ops
        assert " fusion(" in hlo

    def test_fused_dense_gelu_dense_grad_fused(self):
        from apex_tpu.fused_dense import FusedDenseGeluDense

        m = FusedDenseGeluDense(1024, 4096, 1024)
        params = m.init_params(jax.random.PRNGKey(0))
        x = jnp.ones((256, 1024), jnp.bfloat16)

        def loss(params, x):
            return m(params, x).astype(jnp.float32).sum()

        hlo = jax.jit(jax.grad(loss)).lower(params,
                                            x).compile().as_text()
        strays = self._entry_strays(hlo)
        assert not strays, f"unfused entry ops: {strays}"


class TestRound4SurfacesOnChip:
    """Round-4 surfaces on the real chip: fused flash dropout (compiled
    Mosaic incl. the uint32 counter-hash), selective remat, GPT dropout
    end-to-end, bf16 TP GEMM dtype, and the big-bucket bf16 packing that
    OOMed compile before the per-leaf reshape fix."""

    def test_flash_dropout_parity_and_determinism(self, rng):
        from apex_tpu.ops.flash_attention import (
            dropout_keep_scale, flash_attention, flash_attention_reference)

        b, h, s, d = 2, 4, 256, 64
        q = jnp.asarray(rng.randn(b, h, s, d).astype(np.float32))
        k = jnp.asarray(rng.randn(b, h, s, d).astype(np.float32))
        v = jnp.asarray(rng.randn(b, h, s, d).astype(np.float32))
        rate, seed = 0.2, 321
        out = flash_attention(q, k, v, causal=True, dropout=rate,
                              dropout_seed=seed)
        mask = dropout_keep_scale(seed, b * h, s, s,
                                  rate).reshape(b, h, s, s)
        ref = flash_attention_reference(q, k, v, causal=True,
                                        dropout_mask=mask)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-2, atol=2e-2)  # MXU f32 tol
        again = flash_attention(q, k, v, causal=True, dropout=rate,
                                dropout_seed=seed)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(again))
        # backward compiles and is finite with the regenerated mask
        g = jax.jit(jax.grad(lambda q: flash_attention(
            q, k, v, causal=True, dropout=rate,
            dropout_seed=seed).astype(jnp.float32).sum()))(q)
        assert bool(jnp.all(jnp.isfinite(g)))

    def test_gpt_dropout_train_step(self, rng):
        from apex_tpu.models.gpt import GPTConfig, GPTModel
        from apex_tpu.optimizers import FusedAdam

        cfg = GPTConfig(vocab_size=512, hidden_size=256, num_layers=2,
                        num_attention_heads=4, max_seq_len=128,
                        attention_dropout=0.1, dtype=jnp.bfloat16)
        model = GPTModel(cfg)
        params = model.init_params(jax.random.PRNGKey(0))
        adam = FusedAdam(lr=1e-3)
        state = adam.init(params)
        tokens = jnp.asarray(rng.randint(0, 512, (4, 128)))

        @jax.jit
        def step(params, state, seed):
            loss, g = jax.value_and_grad(model.loss)(
                params, tokens, tokens, dropout_seed=seed)
            params, state = adam.step(g, params, state)
            return loss, params, state

        losses = []
        for i in range(4):
            loss, params, state = step(params, state, jnp.int32(i))
            losses.append(float(loss))
        assert all(np.isfinite(losses)), losses

    def test_selective_remat_compiles_and_matches(self, rng):
        from apex_tpu.models.gpt import GPTConfig, GPTModel

        kw = dict(vocab_size=512, hidden_size=256, num_layers=2,
                  num_attention_heads=4, max_seq_len=128, remat=True,
                  dtype=jnp.bfloat16)
        tokens = jnp.asarray(rng.randint(0, 512, (4, 128)))
        out = {}
        for pol in ("full", "dots"):
            m = GPTModel(GPTConfig(remat_policy=pol, **kw))
            p = m.init_params(jax.random.PRNGKey(0))
            # the policy only changes the BACKWARD (which residuals are
            # saved vs recomputed) — grads are the real comparison
            loss, g = jax.jit(jax.value_and_grad(m.loss))(p, tokens,
                                                          tokens)
            out[pol] = (float(loss), g)
        np.testing.assert_allclose(out["full"][0], out["dots"][0],
                                   rtol=1e-3)
        for a, b in zip(jax.tree_util.tree_leaves(out["full"][1]),
                        jax.tree_util.tree_leaves(out["dots"][1])):
            np.testing.assert_allclose(np.asarray(a, np.float32),
                                       np.asarray(b, np.float32),
                                       rtol=2e-2, atol=2e-2)

    def test_tp_linear_bf16_gemm_dtype(self, rng):
        """The serial TP linear must emit a bf16 dot for bf16 activations
        (the round-4 dtype-contract fix) — checked in the optimized HLO."""
        from apex_tpu.transformer import tensor_parallel as tp

        lin = tp.ColumnParallelLinear(256, 512, axis_name=None)
        params = lin.init_params(jax.random.PRNGKey(0))
        x = jnp.asarray(rng.randn(8, 256), jnp.bfloat16)
        hlo = jax.jit(lambda p, x: lin(p, x)[0]).lower(params, x)\
            .compile().as_text()
        # the dot/convolution op itself must produce bf16 (not merely
        # mention bf16 somewhere — the input declaration already does);
        # a silent f32 promotion would emit "f32[...] dot|convolution"
        import re
        ops = re.findall(r"(\w+)\[[^\]]*\]\S* (?:dot|convolution)\(", hlo)
        assert ops and all(o == "bf16" for o in ops), (ops, hlo[:500])
        out, _ = jax.jit(lambda p, x: lin(p, x))(params, x)
        assert out.dtype == jnp.bfloat16

    def test_large_bf16_bucket_flatten_unflatten(self, rng):
        """~50M-element bf16 bucket round-trips through the packing (the
        pre-fix concat-then-reshape compile would OOM at this scale on
        larger models; per-leaf packing must stay layout-safe)."""
        from apex_tpu.multi_tensor_apply import bucketing as B

        shapes = [(4096, 4096), (4096,), (4096, 4096), (16384, 1024),
                  (1000, 333)]
        meta = B.bucket_meta(shapes, jnp.bfloat16)
        leaves = [jnp.asarray(rng.randn(*s).astype(np.float32),
                              jnp.bfloat16) for s in shapes]
        packed = jax.jit(lambda ls: B.flatten_bucket(ls, meta))(leaves)
        assert packed.shape == (meta.nrows, 128)
        outs = jax.jit(lambda p: B.unflatten_bucket(p, meta))(packed)
        for a, b in zip(outs, leaves):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_fused_lm_head_parity(self, rng):
        """Logit-free LM-head CE (ops/lm_head.py) compiled on Mosaic:
        fwd + both grads against the materialized reference."""
        from apex_tpu.ops.lm_head import (
            fused_linear_cross_entropy, fused_linear_cross_entropy_reference)

        N, H, V = 1024, 512, 8192
        x = jnp.asarray(rng.randn(N, H).astype(np.float32) * 0.5)
        w = jnp.asarray(rng.randn(V, H).astype(np.float32) * 0.1)
        t = jnp.asarray(rng.randint(0, V, (N,)))
        out = fused_linear_cross_entropy(x, w, t)
        ref = fused_linear_cross_entropy_reference(x, w, t)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-3, atol=2e-3)
        gx, gw = jax.jit(jax.grad(
            lambda x, w: jnp.mean(fused_linear_cross_entropy(x, w, t)),
            argnums=(0, 1)))(x, w)
        rx, rw = jax.jit(jax.grad(
            lambda x, w: jnp.mean(
                fused_linear_cross_entropy_reference(x, w, t)),
            argnums=(0, 1)))(x, w)
        np.testing.assert_allclose(np.asarray(gx), np.asarray(rx),
                                   rtol=2e-3, atol=2e-4)
        np.testing.assert_allclose(np.asarray(gw), np.asarray(rw),
                                   rtol=2e-3, atol=2e-4)


class TestScheduledCollectiveEvidence:
    """VERDICT r4 item 5: pin the 'XLA does the overlap/bucketing' claims
    (transformer/tensor_parallel/layers.py module docstring) with
    compiled evidence instead of assertion.

    One real chip cannot EXECUTE a 4-device program, but libtpu can
    COMPILE for a v5e:2x2 topology (jax.experimental.topologies);
    ``compiled.as_text()`` is the post-scheduling TPU module.  TPU HLO keeps all-reduce as one
    synchronous instruction (the ICI pipelining lives inside the ring
    emitter), so the checkable facts are:

    * TP psums lower to ``all-reduce`` with an ICI RING strategy;
    * the backward's per-weight gradient psums are COMBINED into one
      bucketed all-reduce (apex DDP's flattened-bucket allreduce,
      performed by XLA's combiner);
    * the schedule interleaves async data movement (slice/copy
      start..done) with compute fusions — at least one async pair has
      compute scheduled between start and done.
    """

    def _compiled_tp_block_text(self):
        from jax.experimental import topologies
        from jax.sharding import Mesh, NamedSharding
        from jax.sharding import PartitionSpec as P
        from jax import shard_map

        from apex_tpu.transformer import tensor_parallel as tp

        topo = topologies.get_topology_desc("v5e:2x2", platform="tpu")
        mesh = Mesh(np.array(topo.devices[:4]).reshape(2, 2),
                    ("data", "model"))

        col = tp.ColumnParallelLinear(1024, 4096, gather_output=False,
                                      world_size=2, axis_name="model")
        row = tp.RowParallelLinear(4096, 1024, input_is_parallel=True,
                                   world_size=2, axis_name="model")

        def block(p, x):
            h, _ = col(p["c"], x)
            h = jax.nn.gelu(h, approximate=True)
            y, _ = row(p["r"], h)
            h2, _ = col(p["c2"], y)
            h2 = jax.nn.gelu(h2, approximate=True)
            y2, _ = row(p["r2"], h2)
            return jnp.sum(y2.astype(jnp.float32))

        def grad_fn(p, x):
            return jax.grad(block, argnums=0)(p, x)

        cspec = {"weight": P("model", None), "bias": P("model")}
        rspec = {"weight": P(None, "model"), "bias": P()}
        pspec = {"c": cspec, "r": rspec, "c2": cspec, "r2": rspec}
        f = shard_map(grad_fn, mesh=mesh,
                      in_specs=(pspec, P("data", None)), out_specs=pspec)

        def sds(shape, spec):
            return jax.ShapeDtypeStruct(
                shape, jnp.bfloat16, sharding=NamedSharding(mesh, spec))

        p = {k: {"weight": sds((4096, 1024) if k.startswith("c")
                               else (1024, 4096), pspec[k]["weight"]),
                 "bias": sds((4096,) if k.startswith("c") else (1024,),
                             pspec[k]["bias"])}
             for k in ("c", "r", "c2", "r2")}
        x = sds((512, 1024), P("data", None))
        return jax.jit(f).lower(p, x).compile().as_text()

    def test_ring_collectives_bucketed_allreduce_and_async_interleave(self):
        import re

        txt = self._compiled_tp_block_text()

        # (1) psum -> all-reduce on an ICI ring (whole lines: the
        # combined op's result-tuple dtypes precede the op name)
        ars = re.findall(r"[^\n]*= [^\n]*all-reduce\([^\n]*", txt)
        assert ars, "no all-reduce in the compiled TP block"
        assert any("RingStrategy" in a or "StrategyRing" in a
                   for a in ars), "no ICI ring strategy on any all-reduce"

        # (2) the data-parallel wgrad psums are COMBINED: one all-reduce
        # carries multiple weight-shaped operands (XLA's combiner = the
        # bucketed flattened allreduce apex DDP hand-rolls)
        assert any(a.count("bf16[") >= 4 for a in ars), (
            "gradient all-reduces were not combined/bucketed")

        # (3) async data movement interleaved with compute: some
        # start..done pair — matched BY NAME, the done op consumes its
        # start op as an operand — has a fusion scheduled between (a
        # loose cross-pair regex would pass even on a fully serialized
        # schedule)
        lines = txt.splitlines()
        interleaved = False
        for i, ln in enumerate(lines):
            m = re.match(r"\s*(%\S*-start\S*) = ", ln)
            if not m:
                continue
            name = m.group(1)
            for j in range(i + 1, len(lines)):
                if "-done" in lines[j] and (
                        name + ")" in lines[j] or name + "," in lines[j]):
                    if any("%fusion" in lines[k] for k in range(i + 1, j)):
                        interleaved = True
                    break
            if interleaved:
                break
        assert interleaved, (
            "no async start/compute/done interleaving in the schedule")
