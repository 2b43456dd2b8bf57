"""The model a ``GPTConfig.layer_pattern`` of ``C``, ``*``, ``D`` and ``E``
builds (gated short convolutions, QK-norm grouped attention with rotary
positions, a dense gated FFN, gated sigmoid-routed experts with no shared
expert; the ``lfm2_moe`` family) against its plain float32 reference
(``apex_tpu/models/reference.py::lfm2_reference``), at tiny sizes on the
CPU; the recipe ``examples/lfm2/pretrain_lfm2.py``; and what must refuse such
a model.  ``benchmarks/tests/test_lfm2_config.py`` (the configuration's own
cases) is collected here too, by path, so that ``pytest tests/`` runs it.
"""

import dataclasses
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.models import reference as ref
from apex_tpu.models.attention import ParallelAttention
from apex_tpu.models.ffn import MoEFFN, ParallelMLP
from apex_tpu.models.gpt import GPTConfig, GPTModel, pipeline_step
from apex_tpu.models.short_conv import GatedShortConv

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load(relpath, name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(ROOT, relpath))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


_own = _load("benchmarks/tests/test_lfm2_config.py", "lfm2_config_tests")
globals().update({k: v for k, v in vars(_own).items()
                  if k.startswith("test_") or k == "cfg"})
recipe = _load("examples/lfm2/pretrain_lfm2.py", "pretrain_lfm2")


def test_the_cell_resolves_by_name_with_its_metrics():
    """The configuration's own case of this name holds its entries to be
    the manifest's LAST, which PR 35's appended cell ended; a file under
    ``benchmarks/`` is a ``benchmark`` PR's to edit (``PERF.md``, section
    7), so tier-1 runs the same checks here without "last"."""
    man = _own.manifest.Manifest(ROOT)
    c = man.cell(_own.CELL)
    assert c.chips == 1 and c.traffic["job"] == "train"
    assert c.config["name"] == _own.NAME
    assert {m["name"] for m in c.end_to_end} == {"train_tokens_per_s",
                                                 "setup_s"}
    assert {m["name"] for m in c.per_layer} == _own.NEW | {
        "step_time_p50_ms.train", "mosaic_time_share.train",
        "device_idle_share.train", "hbm_peak_share.train",
        "optimizer_time_share.train", "attention_time_share.train",
        "mlp_time_share.train"}
    assert all(callable(getattr(_own.readers, m["reader"]))
               for m in c.per_layer)
    # the new metrics are this cell's, and the grouped products' also the
    # window-attention trainer's, whose experts run the same dispatch
    mine = [m for m in man.data["per_layer"] if m["name"] in _own.NEW]
    assert len(mine) == 2
    for m in mine:
        assert m["workloads"] == [_own.CELL] + (
            ["smallthinker-21ba3b-instruct.pretrain-1chip"]
            if m["name"] == "grouped_dot_time_share.train" else [])
        assert m["layer"] == "kernels" and m["unit"] == "%"
    entry = next(e for e in man.data["configs"] if e["name"] == _own.NAME)
    assert sorted(entry["reduced"]) == sorted(c.config["reduced"])

PATTERN = "CD*ECECECE*ECE"
TINY = dict(
    vocab_size=256, hidden_size=64, num_attention_heads=4, num_kv_heads=2,
    head_dim=16, max_seq_len=64, ffn_hidden_size=48,
    dense_ffn_hidden_size=96, n_experts=16, moe_top_k=4,
    moe_router="sigmoid", moe_routed_scale=1.0, moe_held=(4, 4),
    norm="rmsnorm", ffn_activation="swiglu", bias=False, tie_head=True,
    rotary=True, rope_base=1e6, qk_norm=True, short_conv_kernel=3,
    layer_pattern=PATTERN)


def tiny(**kw):
    return GPTConfig(**{**TINY, **kw})


def _close(got, want, tol):
    """Largest difference over the reference's largest magnitude, per leaf:
    float32 against float32 on the CPU, so only the order of the sums
    differs (the grouped products, the flash kernel's blocks, a fused
    ``[gate | up]`` product); 1e-5 is ten float32 roundings of a unit-scale
    sum."""
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(got),
                            jax.tree_util.tree_leaves(want), strict=True):
        g, w = np.asarray(g, np.float64), np.asarray(w, np.float64)
        assert g.shape == w.shape, jax.tree_util.keystr(path)
        err = np.abs(g - w).max() / max(np.abs(w).max(), 1e-30)
        assert err <= tol, (jax.tree_util.keystr(path), err)


def _x(seed, *shape):
    return jax.random.normal(jax.random.PRNGKey(seed), shape, jnp.float32)


def _fwd_bwd(f, *args):
    """``f``'s value and, for a fixed cotangent, its gradients in every
    argument."""
    y = f(*args)
    ct = _x(99, *y.shape)
    return y, jax.grad(lambda *a: jnp.sum(f(*a) * ct),
                       tuple(range(len(args))))(*args)


# -- the gated short convolution ------------------------------------------------

def _conv(seq):
    cfg = tiny()
    mixer = GatedShortConv(cfg)
    p = mixer.init_params(jax.random.PRNGKey(0))
    assert p["in_proj"]["weight"].shape == (192, 64)
    assert p["conv"]["weight"].shape == (64, 3) and "bias" not in p["conv"]
    return cfg, mixer, p, _x(1, 2, seq, cfg.hidden_size)


@pytest.mark.parametrize("seq", [41, 32, 2])    # ragged, even, under 3 taps
def test_conv_mixer_matches_the_sequential_loop(seq):
    cfg, mixer, p, u = _conv(seq)
    want = ref.lfm2_conv(p, u, cfg)
    _close(mixer(p, u), want, 1e-5)
    # and the loop is the equation: c_t = sum_j k[:, j] z_{t-2+j}
    B, C, x = np.split(np.asarray(u @ p["in_proj"]["weight"].T), 3, -1)
    z = np.concatenate([np.zeros((2, 2, 64), np.float32), B * x], 1)
    k = np.asarray(p["conv"]["weight"])
    c = sum(k[:, j] * z[:, j:j + seq] for j in range(3))
    _close(jnp.asarray((C * c) @ np.asarray(p["out_proj"]["weight"]).T),
           want, 1e-5)


@pytest.mark.parametrize("seq", [41, 32, 2])
def test_conv_mixer_gradients_match(seq):
    cfg, mixer, p, u = _conv(seq)
    _, got = _fwd_bwd(mixer, p, u)
    _, want = _fwd_bwd(lambda p, u: ref.lfm2_conv(p, u, cfg), p, u)
    _close(got, want, 2e-5)


def test_the_convolution_is_the_one_mamba_runs():
    from apex_tpu.models import mamba2, short_conv
    assert short_conv.causal_depthwise_conv is mamba2.causal_depthwise_conv
    x, w = _x(2, 2, 9, 8), _x(3, 8, 4)
    b = _x(4, 8)
    y = mamba2.causal_depthwise_conv(x, w, b)
    padded = np.concatenate([np.zeros((2, 3, 8), np.float32), x], 1)
    want = sum(padded[:, j:j + 9] * np.asarray(w)[:, j] for j in range(4))
    np.testing.assert_allclose(y, want + np.asarray(b), rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(mamba2.causal_depthwise_conv(x, w), want,
                               rtol=1e-6, atol=1e-6)


# -- the gated experts -----------------------------------------------------------

def _experts(case, cfg):
    layer = MoEFFN(cfg)
    p = layer.init_params(jax.random.PRNGKey(3))
    assert set(p) == {"router", "w1", "w2"}            # no shared expert
    assert p["w1"].shape == (4, 64, 96) and p["w2"].shape == (4, 48, 64)
    bias = np.zeros(cfg.n_experts, np.float32)
    if case == "all_to_one":
        # every token takes experts 4-7, the four held ones: four chunks of
        # sorted pairs where a balanced batch fills half of one
        bias[4:8] = 10.0
    elif case == "one_empty":
        bias[5] = -10.0
    p["router"]["bias"] = jnp.asarray(bias)
    return layer, p


@pytest.mark.parametrize("case", ["as_routed", "all_to_one", "one_empty"])
def test_gated_experts_match_the_loop_over_experts(case):
    cfg = tiny()
    layer, p = _experts(case, cfg)
    u = _x(4, 2, 40, cfg.hidden_size)
    y, load = layer(p, u)
    _close(y, ref.lfm2_experts(p, u, cfg), 1e-5)
    load = np.asarray(load)
    assert load.shape == (4,) and load.sum() <= 80 * 4
    if case == "all_to_one":
        assert (load == 80).all()
    if case == "one_empty":
        assert load[1] == 0 and load.sum() > 0


@pytest.mark.parametrize("case", ["as_routed", "all_to_one", "one_empty"])
def test_gated_experts_gradients_match(case):
    cfg = tiny()
    layer, p = _experts(case, cfg)
    u = _x(4, 2, 40, cfg.hidden_size)
    _, got = _fwd_bwd(lambda p, u: layer(p, u)[0], p, u)
    _, want = _fwd_bwd(lambda p, u: ref.lfm2_experts(p, u, cfg), p, u)
    _close(got, want, 2e-5)
    # the correction bias is a buffer: it chooses, it is not trained
    assert not np.asarray(got[0]["router"]["bias"]).any()


def test_the_shares_add_up_to_the_uncut_layer():
    """16 experts as 4 shares of 4: the routed parts of all shares (there
    is no shared expert to count once) are the uncut reference's layer
    output."""
    whole_cfg = tiny(moe_held=None)
    whole = MoEFFN(whole_cfg).init_params(jax.random.PRNGKey(6))
    u = _x(7, 2, 24, whole_cfg.hidden_size)
    total = jnp.zeros_like(u)
    for share in range(4):
        layer = MoEFFN(tiny(moe_held=(4 * share, 4)))
        p = dict(whole, w1=whole["w1"][4 * share:4 * share + 4],
                 w2=whole["w2"][4 * share:4 * share + 4])
        total = total + layer(p, u)[0]
    _close(total, ref.lfm2_experts(whole, u, whole_cfg), 1e-5)


def test_the_renormalisation_departs_by_the_sources_epsilon_only():
    """The reference divides by ``sum + 1e-6`` as the source does, the
    program by ``sum + 1e-20``: the same weights to a part in a million."""
    cfg = tiny()
    layer, p = _experts("as_routed", cfg)
    u = _x(4, 80, cfg.hidden_size)
    choice, w = layer.moe.route_sigmoid(p, u)
    _, ref_choice, ref_w = ref.lfm2_route(p, u, cfg)
    np.testing.assert_array_equal(choice, ref_choice)
    np.testing.assert_allclose(w, ref_w, rtol=2e-6)
    np.testing.assert_allclose(np.asarray(w).sum(-1), 1.0, rtol=1e-6)


# -- attention and the dense FFN --------------------------------------------------

def test_qk_norm_rotary_grouped_attention_matches_the_reference():
    cfg = tiny()
    attn = ParallelAttention(cfg)
    p = attn.init_params(jax.random.PRNGKey(8))
    assert p["qkv"]["weight"].shape == ((4 + 2 * 2) * 16, 64)
    assert p["q_norm"]["weight"].shape == p["k_norm"]["weight"].shape == (16,)
    # weights away from one, so that a norm applied to the wrong operand
    # or after the rotation shows
    p["q_norm"]["weight"] = 1.0 + 0.5 * _x(20, 16)
    p["k_norm"]["weight"] = 1.0 + 0.5 * _x(21, 16)
    u = _x(9, 2, 40, cfg.hidden_size)
    cos, sin = GPTModel(cfg).rope_tables(40)
    y, got = _fwd_bwd(lambda p, u: attn(p, u, cos, sin), p, u)
    want_y, want = _fwd_bwd(lambda p, u: ref.lfm2_attention(p, u, cfg), p, u)
    _close(y, want_y, 1e-5)
    _close(got, want, 2e-5)
    # the base is the configuration's: at 10 000 the result is another
    other = GPTModel(tiny(rope_base=10000.0)).rope_tables(40)
    assert np.abs(np.asarray(attn(p, u, *other) - y)).max() > 1e-3


def test_rope_tables_take_the_base_from_the_configuration():
    from apex_tpu.ops.rope import rope_freqs
    plain = GPTConfig(vocab_size=64, hidden_size=32, num_layers=1,
                      num_attention_heads=4, max_seq_len=16)
    assert plain.rope_base == 10000.0
    cos, _ = GPTModel(plain).rope_tables(16)
    np.testing.assert_array_equal(cos, jnp.cos(rope_freqs(16, 8)))
    far = GPTModel(dataclasses.replace(plain, rope_base=1e6)).rope_tables(16)
    np.testing.assert_array_equal(far[0], jnp.cos(rope_freqs(16, 8, 1e6)))


def test_dense_gated_ffn_matches_the_reference():
    cfg = tiny()
    mlp = ParallelMLP(cfg, cfg.dense_ffn_hidden_size)
    p = mlp.init_params(jax.random.PRNGKey(11))
    assert p["fc1"]["weight"].shape == (192, 64)      # [gate | up]
    assert p["fc2"]["weight"].shape == (64, 96) and "bias" not in p["fc1"]
    u = _x(12, 2, 24, cfg.hidden_size)
    y, got = _fwd_bwd(mlp, p, u)
    want_y, want = _fwd_bwd(lambda p, u: ref.lfm2_dense(p, u, cfg), p, u)
    _close(y, want_y, 1e-5)
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
    assert cfg.num_layers == 14
    kinds = ["".join(k for k, leaf in (
        ("C", "conv"), ("*", "qkv"), ("D", "fc1"), ("E", "router"))
        if leaf in lp["mixer"]) for lp in params["layers"]]
    assert "".join(kinds) == PATTERN
    assert [layer.scope for layer in model.layers] == [
        {"C": "conv", "*": "attention", "D": "mlp", "E": "mlp"}[s]
        for s in PATTERN]
    assert "position_embedding" not in params and "lm_head" not in params
    assert model.head == "embedding"                         # tied
    assert all(set(lp["norm"]) == {"weight"} for lp in params["layers"])
    # the tied matrix is the head: half the other matrices' deviation
    assert float(jnp.std(params["embedding"]["weight"])) \
        == pytest.approx(0.01, rel=0.05)


def test_pattern_logits_and_loss_match_the_reference(whole):
    cfg, model, params, tokens, targets = whole
    logits, loss = ref.lfm2_reference(params, tokens, cfg, targets)
    _close(model(params, tokens), logits, 1e-5)
    got, load = model.loss(params, tokens, targets, return_expert_load=True)
    np.testing.assert_allclose(float(got), float(loss), rtol=1e-6)
    assert load.shape == (6, 4) and load.dtype == jnp.int32


@pytest.mark.parametrize("remat", [False, True])
def test_pattern_gradient_of_every_leaf_matches(whole, remat):
    cfg, _, params, tokens, targets = whole
    model = GPTModel(tiny(remat=remat))
    got = jax.grad(lambda p: model.loss(p, tokens, targets))(params)
    want = jax.grad(lambda p: ref.lfm2_reference(
        p, tokens, cfg, targets)[1])(params)
    _close(got, want, 2e-5)


# -- the recipe: amp O2, FusedAdam with masters, counters ---------------------------

def _build(*extra):
    args = recipe.parse_args(["--config", "tiny", "--batch-size", "2",
                              "--seq-len", "64", "--lr", "3e-3", *extra])
    return recipe.build(args, devices=jax.devices()[:1])


def test_o2_step_learns_and_keeps_float32_masters():
    train_step, state, make_batch, n_params = _build()
    params = state[0]
    assert n_params == sum(int(np.prod(leaf.shape))
                           for leaf in jax.tree_util.tree_leaves(params))
    dtypes = {jax.tree_util.keystr(k): v.dtype for k, v in
              jax.tree_util.tree_leaves_with_path(params)}
    assert any("q_norm" in path for path in dtypes)
    for path, dtype in dtypes.items():
        keep = "norm" in path or "router" in path
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
    first = PATTERN.index("E")
    router = np.asarray(
        state[0]["layers"][first]["mixer"]["router"]["weight"])
    w1 = np.asarray(state[0]["layers"][first]["mixer"]["w1"], np.float32)
    for _ in range(3):
        *state, _ = train_step(*state, *make_batch())
    # the replicated router is not stepped on one rank's term of its
    # gradient; everything this rank owns is
    after = state[0]["layers"][first]["mixer"]
    np.testing.assert_array_equal(router,
                                  np.asarray(after["router"]["weight"]))
    assert (np.asarray(after["w1"], np.float32) != w1).any()
    load = recipe.expert_load(state[3], steps=3, n_expert_layers=6, held=4)
    routed = 3 * 6 * 2 * 64 * 4
    assert int(state[3]["routed_pairs"]) == routed
    assert 0 < int(state[3]["held_pairs"]) < routed
    assert 0.05 < load["held_pair_share"] < 0.6        # 4 of 16 held
    assert load["expert_tokens_mean"] * 3 * 6 * 4 \
        == pytest.approx(int(state[3]["held_pairs"]))
    assert load["expert_tokens_mean"] <= load["expert_tokens_max"] <= 128


def test_both_recipes_run_one_body():
    hybrid = _load("examples/nemotron_h/pretrain_nemotron_h.py",
                   "pretrain_nemotron_h_again")
    for name in ("parse_args", "model_config", "init_params", "build"):
        assert getattr(recipe, name).func is getattr(hybrid, name).func
    assert recipe.expert_load is hybrid.expert_load
    args = recipe.parse_args(["--config", "tiny"])
    with pytest.raises(SystemExit, match="one device"):
        recipe.build(args, devices=jax.devices()[:2])


# -- what must refuse ------------------------------------------------------------------

def _serving_calls(model, params):
    ints = jnp.zeros((2,), jnp.int32)
    chunk = jnp.zeros((2, 4), jnp.int32)
    pool = jnp.zeros((9, 14, 2, 8, 32), jnp.float32)
    scales = jnp.zeros((9, 14, 2, 2), jnp.float32)
    tables = jnp.zeros((2, 4), jnp.int32)
    cache = jnp.zeros((2, 14, 2, 32, 2, 16), jnp.float32)
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
def test_serving_the_pattern_raises(whole, entry):
    _, model, params, _, _ = whole
    if entry.endswith("Engine"):
        from apex_tpu.inference import InferenceEngine
        from apex_tpu.serving import PagedInferenceEngine
        engine = {"InferenceEngine": InferenceEngine,
                  "PagedInferenceEngine": PagedInferenceEngine}[entry]
        call = lambda: engine(model, params, max_slots=2)     # noqa: E731
    else:
        call = _serving_calls(model, params)[entry]
    with pytest.raises(NotImplementedError, match="short convolution"):
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
def test_the_pattern_refuses_layouts_it_was_not_written_for(layout):
    if layout.get("plan") == "pp2":
        from apex_tpu.parallel.plan import ParallelPlan
        layout = dict(plan=ParallelPlan(pp=2))
    with pytest.raises(ValueError, match="layer_pattern|grouped attention"):
        tiny(**layout)


def test_pipeline_and_packing_refuse_the_pattern(whole):
    from apex_tpu.models.gpt import pack_for_shard_map
    _, model, params, tokens, targets = whole
    with pytest.raises(ValueError, match="layer_pattern"):
        pipeline_step(model, params, tokens[None], targets[None])
    with pytest.raises(ValueError, match="layer_pattern"):
        model.partition_specs()
    with pytest.raises(ValueError, match="layer_pattern"):
        pack_for_shard_map(model, params)


@pytest.mark.parametrize("bad,names", [
    (dict(layer_pattern="CDX"), "'C' \\(gated short convolution\\), 'D'"),
    (dict(layer_pattern=""), "'D' \\(dense FFN\\)"),
    (dict(n_experts=0), "'E' layer needs n_experts"),
    (dict(moe_router="softmax", moe_held=None), "swiglu.*sigmoid"),
    (dict(short_conv_kernel=0), "short_conv_kernel"),
    (dict(ffn_activation="geglu"), "swiglu"),
    (dict(num_kv_heads=3), "num_kv_heads"),
])
def test_config_names_what_is_wrong(bad, names):
    with pytest.raises(ValueError, match=names):
        tiny(**bad)


@pytest.mark.parametrize("field", [
    dict(ffn_activation="swiglu"), dict(qk_norm=True)],
    ids=["swiglu", "qk_norm"])
def test_the_plain_block_refuses_a_gated_ffn_and_qk_norm(field):
    with pytest.raises(ValueError, match="layer_pattern"):
        GPTConfig(vocab_size=64, hidden_size=64, num_layers=2,
                  num_attention_heads=4, **field)


def test_gated_experts_need_the_sorted_dispatch():
    from apex_tpu.transformer.expert_parallel import MoEConfig
    with pytest.raises(ValueError, match="swiglu.*sigmoid"):
        MoEConfig(hidden_size=8, ffn_hidden_size=8, n_experts=4,
                  activation="swiglu")
    with pytest.raises(ValueError, match="swiglu"):
        MoEConfig(hidden_size=8, ffn_hidden_size=8, n_experts=4,
                  activation="glu")
