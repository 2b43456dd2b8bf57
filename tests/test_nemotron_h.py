"""The hybrid a ``GPTConfig.layer_pattern`` builds (Mamba-2 layers,
sigmoid-routed experts with a shared expert, grouped attention; the
``nemotron_h`` family) against its plain float32 reference
(``apex_tpu/models/reference.py::nemotron_h_reference``), at tiny sizes on
the CPU; the recipe ``examples/nemotron_h/pretrain_nemotron_h.py``; and what
must refuse such a model.  ``benchmarks/tests/test_nemotron_config.py`` (the
configuration's own cases) is collected here too, by path, so that
``pytest tests/`` runs it.
"""

import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.models import reference as ref
from apex_tpu.models.attention import ParallelAttention
from apex_tpu.models.ffn import MoEFFN
from apex_tpu.models.gpt import GPTConfig, GPTModel, pipeline_step
from apex_tpu.models.mamba2 import Mamba2Mixer

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load(relpath, name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(ROOT, relpath))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


_own = _load("benchmarks/tests/test_nemotron_config.py",
             "nemotron_config_tests")
globals().update({k: v for k, v in vars(_own).items()
                  if k.startswith("test_") or k == "cfg"})
recipe = _load("examples/nemotron_h/pretrain_nemotron_h.py",
               "pretrain_nemotron_h")

TINY = dict(
    vocab_size=256, hidden_size=64, num_attention_heads=4, num_kv_heads=2,
    head_dim=32, max_seq_len=64, ffn_hidden_size=48, n_experts=16,
    moe_top_k=6, moe_router="sigmoid", moe_routed_scale=2.5,
    moe_shared_ffn=96, moe_held=(4, 4), norm="rmsnorm",
    ffn_activation="relu2", bias=False, tie_head=False, rotary=False,
    layer_pattern="MEMEM*EME", mamba_num_heads=8, mamba_head_dim=16,
    mamba_state_size=16, mamba_groups=2, mamba_chunk_size=16)


def tiny(**kw):
    return GPTConfig(**{**TINY, **kw})


def _close(got, want, tol):
    """Largest difference over the reference's largest magnitude, per leaf:
    float32 against float32 on the CPU, so only the order of the sums
    differs (the chunked scan, the grouped products, the flash kernel's
    blocks); 1e-5 is ten float32 roundings of a unit-scale sum."""
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(got),
                            jax.tree_util.tree_leaves(want), strict=True):
        g, w = np.asarray(g, np.float64), np.asarray(w, np.float64)
        assert g.shape == w.shape, jax.tree_util.keystr(path)
        err = np.abs(g - w).max() / max(np.abs(w).max(), 1e-30)
        assert err <= tol, (jax.tree_util.keystr(path), err)


def _x(seed, *shape):
    return jax.random.normal(jax.random.PRNGKey(seed), shape, jnp.float32)


# -- the Mamba-2 mixer ----------------------------------------------------------

@pytest.mark.parametrize("seq", [40, 32, 7])     # chunk 16: ragged, even, short
def test_mamba_mixer_matches_the_sequential_scan(seq):
    cfg = tiny()
    mixer = Mamba2Mixer(cfg)
    p = mixer.init_params(jax.random.PRNGKey(0))
    # a dt of a whole unit, so that the decays are far from 1 and a wrong
    # chunk boundary shows
    p["dt_bias"] = p["dt_bias"] + 4.0
    u = _x(1, 2, seq, cfg.hidden_size)
    _close(mixer(p, u), ref.nemotron_h_mamba(p, u, cfg), 1e-5)


def test_mamba_mixer_gradients_match_at_a_ragged_length():
    cfg = tiny()
    mixer = Mamba2Mixer(cfg)
    p = mixer.init_params(jax.random.PRNGKey(0))
    p["dt_bias"] = p["dt_bias"] + 4.0
    u = _x(1, 2, 40, cfg.hidden_size)
    ct = _x(2, 2, 40, cfg.hidden_size)
    got = jax.grad(lambda p, u: jnp.sum(mixer(p, u) * ct), (0, 1))(p, u)
    want = jax.grad(lambda p, u: jnp.sum(
        ref.nemotron_h_mamba(p, u, cfg) * ct), (0, 1))(p, u)
    # A_log's gradient is a sum over 40 steps of decays of very unlike
    # sizes (dt near 4): 2.6e-5 of its largest entry, from the sums' order
    _close(got, want, 5e-5)


# -- the expert layer ------------------------------------------------------------

def _experts(case, cfg):
    layer = MoEFFN(cfg)
    p = layer.init_params(jax.random.PRNGKey(3))
    bias = np.zeros(cfg.n_experts, np.float32)
    if case == "all_to_one":
        # every token takes experts 4-9: the four held ones see every
        # token, four chunks of sorted pairs where a balanced batch fills
        # half of one
        bias[4:10] = 10.0
    elif case == "one_empty":
        bias[5] = -10.0
    p["router"]["bias"] = jnp.asarray(bias)
    return layer, p


@pytest.mark.parametrize("case", ["as_routed", "all_to_one", "one_empty"])
def test_expert_layer_matches_the_loop_over_experts(case):
    cfg = tiny()
    layer, p = _experts(case, cfg)
    u = _x(4, 2, 40, cfg.hidden_size)
    y, load = layer(p, u)
    _close(y, ref.nemotron_h_experts(p, u, cfg), 1e-5)
    load = np.asarray(load)
    assert load.shape == (4,) and load.sum() <= 80 * 6
    if case == "all_to_one":
        assert (load == 80).all()
    if case == "one_empty":
        assert load[1] == 0 and load.sum() > 0


@pytest.mark.parametrize("case", ["as_routed", "all_to_one", "one_empty"])
def test_expert_layer_gradients_match(case):
    cfg = tiny()
    layer, p = _experts(case, cfg)
    u = _x(4, 2, 40, cfg.hidden_size)
    ct = _x(5, 2, 40, cfg.hidden_size)
    got = jax.grad(lambda p, u: jnp.sum(layer(p, u)[0] * ct), (0, 1))(p, u)
    want = jax.grad(lambda p, u: jnp.sum(
        ref.nemotron_h_experts(p, u, cfg) * ct), (0, 1))(p, u)
    _close(got, want, 2e-5)
    # the correction bias is a buffer: it chooses, it is not trained
    assert not np.asarray(got[0]["router"]["bias"]).any()


def test_the_shares_add_up_to_the_uncut_layer():
    """16 experts as 4 shares of 4: the routed parts of all shares, plus
    the shared expert once, are the uncut reference's layer output."""
    whole_cfg = tiny(moe_held=None)
    whole = MoEFFN(whole_cfg).init_params(jax.random.PRNGKey(6))
    u = _x(7, 2, 24, whole_cfg.hidden_size)
    total = jnp.zeros_like(u).reshape(-1, u.shape[-1])
    for share in range(4):
        cfg = tiny(moe_held=(4 * share, 4))
        layer = MoEFFN(cfg)
        p = dict(whole, w1=whole["w1"][4 * share:4 * share + 4],
                 w2=whole["w2"][4 * share:4 * share + 4])
        routed, _ = layer.moe(p, u.reshape(-1, u.shape[-1]))
        total = total + routed
    total = total.reshape(u.shape) + layer.shared(whole["shared"], u)
    _close(total, ref.nemotron_h_experts(whole, u, whole_cfg), 1e-5)


# -- grouped attention -------------------------------------------------------------

def test_grouped_attention_matches_the_reference():
    cfg = tiny()
    attn = ParallelAttention(cfg)
    p = attn.init_params(jax.random.PRNGKey(8))
    assert p["qkv"]["weight"].shape == ((4 + 2 * 2) * 32, 64)
    assert "bias" not in p["qkv"] and "bias" not in p["proj"]
    u = _x(9, 2, 40, cfg.hidden_size)
    ct = _x(10, 2, 40, cfg.hidden_size)
    _close(attn(p, u), ref.nemotron_h_attention(p, u, cfg), 1e-5)
    got = jax.grad(lambda p, u: jnp.sum(attn(p, u) * ct), (0, 1))(p, u)
    want = jax.grad(lambda p, u: jnp.sum(
        ref.nemotron_h_attention(p, u, cfg) * ct), (0, 1))(p, u)
    _close(got, want, 2e-5)


# -- the whole pattern ---------------------------------------------------------------

@pytest.fixture(scope="module")
def whole():
    cfg = tiny()
    model = GPTModel(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 40), 0, 256)
    return cfg, model, params, tokens, jnp.roll(tokens, -1, axis=1)


def test_pattern_builds_one_mixer_per_symbol(whole):
    cfg, model, params, _, _ = whole
    assert cfg.num_layers == 9
    kinds = [("in_proj" in lp["mixer"], "router" in lp["mixer"],
              "qkv" in lp["mixer"]) for lp in params["layers"]]
    assert kinds == [{"M": (True, False, False), "E": (False, True, False),
                      "*": (False, False, True)}[s] for s in "MEMEM*EME"]
    assert "position_embedding" not in params          # no position code
    assert params["lm_head"]["weight"].shape == (256, 64)   # untied
    assert all("bias" not in lp["norm"] for lp in params["layers"])


def test_pattern_logits_and_loss_match_the_reference(whole):
    cfg, model, params, tokens, targets = whole
    logits, loss = ref.nemotron_h_reference(params, tokens, cfg, targets)
    _close(model(params, tokens), logits, 1e-5)
    got, load = model.loss(params, tokens, targets, return_expert_load=True)
    np.testing.assert_allclose(float(got), float(loss), rtol=1e-6)
    assert load.shape == (4, 4) and load.dtype == jnp.int32


@pytest.mark.parametrize("remat", [False, True])
def test_pattern_gradient_of_every_leaf_matches(whole, remat):
    cfg, _, params, tokens, targets = whole
    model = GPTModel(tiny(remat=remat))
    got = jax.grad(lambda p: model.loss(p, tokens, targets))(params)
    want = jax.grad(lambda p: ref.nemotron_h_reference(
        p, tokens, cfg, targets)[1])(params)
    _close(got, want, 2e-5)


def test_the_default_block_is_what_it_was():
    """Every new field's default is the GPT-2 block: LayerNorm with a bias,
    biased linears, one fused qkv of three equal parts, a tied head, a
    position table when rotary is off."""
    cfg = GPTConfig(vocab_size=64, hidden_size=32, num_layers=2,
                    num_attention_heads=4, max_seq_len=16, rotary=False)
    assert cfg.head_dim == 8 and cfg.num_kv_heads == 4
    p = GPTModel(cfg).init_params(jax.random.PRNGKey(0))
    lp = p["layers"][0]
    assert set(p) == {"embedding", "layers", "final_layernorm",
                      "position_embedding"}
    assert set(lp) == {"input_layernorm", "attention",
                       "post_attention_layernorm", "mlp"}
    assert set(lp["input_layernorm"]) == {"weight", "bias"}
    assert lp["attention"]["qkv"]["weight"].shape == (96, 32)
    assert set(lp["mlp"]["fc1"]) == {"weight", "bias"}


# -- the recipe: amp O2, FusedAdam with masters, counters ---------------------------

def _build(*extra):
    args = recipe.parse_args(["--config", "tiny", "--batch-size", "2",
                              "--seq-len", "64", "--lr", "3e-3", *extra])
    return recipe.build(args, devices=jax.devices()[:1])


def test_o2_step_learns_and_keeps_float32_masters():
    train_step, state, make_batch, n_params = _build()
    params, opt_state = state[0], state[1]
    assert n_params == sum(int(np.prod(leaf.shape))
                           for leaf in jax.tree_util.tree_leaves(params))
    dtypes = {jax.tree_util.keystr(k): v.dtype for k, v in
              jax.tree_util.tree_leaves_with_path(params)}
    for path, dtype in dtypes.items():
        keep = any(k in path for k in ("norm", "router", "A_log",
                                       "dt_bias", "'D'"))
        assert dtype == (jnp.float32 if keep else jnp.bfloat16), path
    batch = make_batch()                # one batch, again and again
    losses = []
    for _ in range(20):
        *state, loss = train_step(*state, *batch)
        losses.append(float(loss))
    assert np.isfinite(losses).all()
    assert abs(losses[0] / np.log(512) - 1) < 0.05     # the job's check
    assert losses[-1] < losses[0] - 0.05, losses
    assert int(state[1]["step"]) == 20
    masters = [m for b in state[1]["buckets"].values()
               for m in b.get("master", [])]
    assert masters and all(m.dtype == jnp.float32 for m in masters)
    assert all(leaf.dtype == dtypes[jax.tree_util.keystr(k)] for k, leaf
               in jax.tree_util.tree_leaves_with_path(state[0]))


def test_the_step_counts_the_expert_load():
    train_step, state, make_batch, _ = _build()
    router = jax.tree_util.tree_map(
        np.asarray, state[0]["layers"][1]["mixer"]["router"])
    shared = np.asarray(
        state[0]["layers"][1]["mixer"]["shared"]["fc1"]["weight"], np.float32)
    for _ in range(3):
        *state, _ = train_step(*state, *make_batch())
    # the replicated router is not stepped on one rank's term of its
    # gradient; everything this rank owns is
    after = state[0]["layers"][1]["mixer"]
    np.testing.assert_array_equal(router["weight"],
                                  np.asarray(after["router"]["weight"]))
    assert (np.asarray(after["shared"]["fc1"]["weight"], np.float32)
            != shared).any()
    load = recipe.expert_load(state[3], steps=3, n_expert_layers=4, held=4)
    routed = 3 * 4 * 2 * 64 * 6
    assert int(state[3]["routed_pairs"]) == routed
    assert 0 < int(state[3]["held_pairs"]) < routed
    assert 0.05 < load["held_pair_share"] < 0.6        # 4 of 16 held
    assert load["expert_tokens_mean"] * 3 * 4 * 4 \
        == pytest.approx(int(state[3]["held_pairs"]))
    assert load["expert_tokens_mean"] <= load["expert_tokens_max"] <= 128


def test_the_recipe_is_one_ranks_share():
    args = recipe.parse_args(["--config", "tiny"])
    with pytest.raises(SystemExit, match="one device"):
        recipe.build(args, devices=jax.devices()[:2])


# -- what must refuse ------------------------------------------------------------------

def _serving_calls(model, params):
    ints = jnp.zeros((2,), jnp.int32)
    chunk = jnp.zeros((2, 4), jnp.int32)
    pool = jnp.zeros((9, 9, 2, 8, 64), jnp.float32)
    scales = jnp.zeros((9, 9, 2, 2), jnp.float32)
    tables = jnp.zeros((2, 4), jnp.int32)
    cache = jnp.zeros((2, 9, 2, 32, 2, 32), jnp.float32)
    return {
        "prefill": lambda: model.prefill(params, chunk),
        "decode_step": lambda: model.decode_step(params, ints, cache, ints),
        "decode_step_paged": lambda: model.decode_step_paged(
            params, ints, pool, tables, ints),
        "decode_chunk": lambda: model.decode_chunk(
            params, chunk, pool, tables, chunk, chunk, chunk),
        "decode_step_paged_quant": lambda: model.decode_step_paged_quant(
            params, ints, pool, scales, tables, ints),
        "decode_chunk_quant": lambda: model.decode_chunk_quant(
            params, chunk, pool, scales, tables, chunk, chunk, chunk),
    }


@pytest.mark.parametrize("entry", [
    "prefill", "decode_step", "decode_step_paged", "decode_chunk",
    "decode_step_paged_quant", "decode_chunk_quant", "InferenceEngine",
    "PagedInferenceEngine"])
def test_serving_a_pattern_raises(whole, entry):
    _, model, params, _, _ = whole
    if entry.endswith("Engine"):
        from apex_tpu.inference import InferenceEngine
        from apex_tpu.serving import PagedInferenceEngine
        engine = {"InferenceEngine": InferenceEngine,
                  "PagedInferenceEngine": PagedInferenceEngine}[entry]
        call = lambda: engine(model, params, max_slots=2)     # noqa: E731
    else:
        call = _serving_calls(model, params)[entry]
    with pytest.raises(NotImplementedError, match="per-request state"):
        call()


@pytest.mark.parametrize("layout", [
    dict(tensor_parallel_size=2, axis_name="model", num_kv_heads=4),
    dict(tensor_parallel_size=2, axis_name="model", sequence_parallel=True,
         num_kv_heads=4),
    dict(context_axis="context"),
    dict(plan="pp2"),
    dict(fused_ffn=True),
    dict(weight_quant="int8"),
    dict(expert_axis="expert", expert_parallel_size=2),
], ids=["tp", "sp", "cp", "pp", "fused_ffn", "weight_quant", "expert_axis"])
def test_a_pattern_refuses_layouts_it_was_not_written_for(layout):
    if layout.get("plan") == "pp2":
        from apex_tpu.parallel.plan import ParallelPlan
        layout = dict(plan=ParallelPlan(pp=2))
    with pytest.raises(ValueError, match="layer_pattern|grouped attention"):
        tiny(**layout)


def test_pipeline_step_refuses_a_pattern(whole):
    _, model, params, tokens, targets = whole
    with pytest.raises(ValueError, match="layer_pattern"):
        pipeline_step(model, params, tokens[None], targets[None])


def test_the_tensor_parallel_packing_refuses_a_pattern(whole):
    from apex_tpu.models.gpt import pack_for_shard_map
    _, model, params, _, _ = whole
    with pytest.raises(ValueError, match="layer_pattern"):
        model.partition_specs()
    with pytest.raises(ValueError, match="layer_pattern"):
        pack_for_shard_map(model, params)


@pytest.mark.parametrize("bad", [
    dict(layer_pattern="MEX"), dict(layer_pattern=""),
    dict(mamba_num_heads=0), dict(n_experts=0), dict(norm="batchnorm"),
    dict(num_kv_heads=3)])
def test_config_names_what_is_wrong(bad):
    with pytest.raises(ValueError):
        tiny(**bad)


def test_the_plain_block_refuses_what_its_cache_paths_cannot_hold():
    for field in (dict(num_kv_heads=2), dict(head_dim=32),
                  dict(tie_head=False), dict(moe_router="sigmoid")):
        with pytest.raises(ValueError, match="layer_pattern"):
            GPTConfig(vocab_size=64, hidden_size=64, num_layers=2,
                      num_attention_heads=4, **field)
