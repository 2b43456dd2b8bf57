"""SmallThinker's mechanisms at a tiny size on the CPU (seeded random
weights): window and full layers over grouped heads through the training
kernel (forward and its three gradients, interpreted), no position code on
the full layer, a router that reads the layer's input before attention,
softmax top-k ReGLU experts of which this share holds some, held to the
plain float32 reference (``apex_tpu/models/reference.py::
smallthinker_reference``, whose copy ``benchmarks/configs/smallthinker-
21ba3b-instruct.reference.py`` the chip comparison uses).  The
configuration's own cases are collected from ``benchmarks/tests/``."""

import dataclasses
import importlib.util
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.models import reference as ref
from apex_tpu.models.ffn import ParallelMLP
from apex_tpu.models.gpt import GPTConfig, GPTModel
from apex_tpu.ops import flash_attention as fa
from apex_tpu.transformer.expert_parallel import MoEConfig, MoEMLP
from apex_tpu.utils.platform import set_force_pallas

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load(relpath, name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(ROOT, relpath))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


_own = _load("benchmarks/tests/test_smallthinker_config.py",
             "smallthinker_config_tests")
globals().update({k: v for k, v in vars(_own).items()
                  if k.startswith("test_") or k == "cfg"})
recipe = _load("examples/smallthinker/pretrain_smallthinker.py",
               "pretrain_smallthinker")

WINDOW = 16
TINY = dict(
    vocab_size=128, hidden_size=64, num_attention_heads=7, num_kv_heads=1,
    head_dim=128, max_seq_len=64, norm="rmsnorm", norm_eps=1e-6,
    ffn_activation="reglu", bias=False, tie_head=False, rotary=True,
    rope_base=1.5e6, moe_router="softmax_sorted", moe_router_bias=False,
    moe_top_k=2, ffn_hidden_size=32, n_experts=8, moe_held=(0, 2),
    moe_route_before_attention=True, layer_pattern="*E" * 8,
    attention_types=("full", "sliding", "sliding", "sliding") * 2,
    rotary_layers=(0, 1, 1, 1) * 2, sliding_window=WINDOW)


def build(seed=0, **over):
    cfg = GPTConfig(**{**TINY, **over})
    model = GPTModel(cfg)
    return cfg, model, jax.jit(model.init_params)(jax.random.PRNGKey(seed))


def tokens(n, seed=0, batch=1):
    return jnp.asarray(np.random.default_rng(seed).integers(
        0, TINY["vocab_size"], (batch, n)), jnp.int32)


def _rel(got, want):
    return float(jnp.abs(jnp.asarray(got, jnp.float32) - want).max()
                 / jnp.abs(want).max())


# -- the kernel, interpreted --------------------------------------------------

@pytest.mark.parametrize("heads,kv,s,window,dtype", [
    (7, 1, 384, None, jnp.float32),        # a group of 7, no window
    (7, 1, 384, 100, jnp.float32),         # a window inside a block
    (7, 1, 300, 128, jnp.float32),         # a block's width; ends mid-block
    (4, 2, 300, 300, jnp.float32),         # the sequence's length
    (4, 2, 256, 1000, jnp.float32),        # longer than the sequence
    (4, 2, 512, 200, jnp.float32),         # across blocks, a group of 2
    (7, 1, 200, None, jnp.float32),        # no window, ends mid-block
    (4, 2, 384, 200, jnp.bfloat16),
], ids=["g7-full", "g7-w100", "g7-w128-s300", "g2-w300-s300",
        "g2-w1000-s256", "g2-w200-s512", "g7-full-s200", "g2-w200-bf16"])
def test_grouped_flash_against_the_reference(heads, kv, s, window, dtype):
    """The training kernel in interpret mode, blocks of 128, K and V read
    through the index maps: the output and its three gradients against the
    materialised reference's (autodiff).  float32: sums in another order
    (1e-5 of the range); bf16: P and dS rounded before their products, as
    the kernels do (2e-2)."""
    d = 128
    keys = jax.random.split(jax.random.PRNGKey(s + heads), 4)
    q, k, v = (jax.random.normal(key, (1, s, n * d), dtype)
               for key, n in zip(keys, (heads, kv, kv)))
    ct = jax.random.normal(keys[3], q.shape, dtype)

    def both(f, *args):
        out, vjp = jax.vjp(f, *args)
        return (out,) + vjp(ct.astype(out.dtype))
    f32 = [a.astype(jnp.float32) for a in (q, k, v)]
    want = both(lambda q, k, v: fa.grouped_attention_reference(
        q, k, v, heads, window), *f32)
    got = both(lambda q, k, v: fa._grouped_flash(
        q, k, v, heads, window, d ** -0.5, 128, 128, True), q, k, v)
    limit = 1e-5 if dtype == jnp.float32 else 2e-2
    for g, w in zip(got, want):
        assert g.shape == w.shape and g.dtype == dtype
        assert _rel(g, w) < limit


def test_every_grid_visits_its_band_alone():
    """At the cell's 16 384 positions, blocks of 1 024 and the window of
    4 096, a row block sees at most five key blocks and a key block is seen
    by at most five row blocks (sixteen without the window); the dK/dV
    grid's row blocks of a key block are exactly those whose band holds it,
    so each pair of blocks is visited once each way; the visited blocks are
    0.515 of the causal triangle's (the band's pairs are 0.4375)."""
    s, b, w = 16384, 1024, 4096
    assert fa._band_steps(s, b, b, w) == fa._band_row_steps(s, b, b, w) == 5
    assert fa._band_steps(s, b, b, None) == fa._band_row_steps(
        s, b, b, None) == 16
    for window in (w, None, 1500):
        seen = {(qi, kb) for qi in range(s // b)
                for kb in range(int(fa._band(qi, b, b, window)[0]),
                                int(fa._band(qi, b, b, window)[1]) + 1)}
        rows = set()
        for ki in range(s // b):
            first, last = fa._band_rows(ki, b, b, window, s // b)
            rows |= {(qi, ki) for qi in range(int(first), int(last) + 1)}
        assert rows == seen
    band = sum(min(qi + 1, 5) for qi in range(16))
    assert band == 70 and band / 136 == pytest.approx(0.515, abs=1e-3)


# -- the model against its reference -----------------------------------------

@pytest.mark.parametrize("kernels", [False, True], ids=["jnp", "interpret"])
def test_loss_and_gradients_against_the_reference(kernels):
    """Two periods (16 symbols), 2 of 8 experts held, 7 query heads over 1
    KV head of 128, window 16, float32: the loss within 1e-6 and every
    leaf's gradient within 1e-4 of its largest entry (the same sums in
    other orders; measured 1.3e-6).  ``interpret``: the training kernel,
    the norms and the head's kernels run as Pallas in interpret mode."""
    cfg, model, params = build()
    toks, = [tokens(40, 1)]
    targets = jnp.roll(toks, -1, 1)
    want, want_g = jax.value_and_grad(
        lambda p: ref.smallthinker_reference(p, toks, cfg, targets)[1])(
            params)
    set_force_pallas(True if kernels else None)
    try:
        with jax.default_matmul_precision("highest"):
            got, got_g = jax.value_and_grad(
                lambda p: model.loss(p, toks, targets))(params)
    finally:
        set_force_pallas(None)
    assert abs(float(got) / float(want) - 1) < 1e-6
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(got_g),
                            jax.tree_util.tree_leaves(want_g)):
        assert _rel(g, w) < 1e-4, jax.tree_util.keystr(path)


def test_the_shares_add_up_to_the_uncut_layer():
    """4 shares of 2 of 8 experts, one expert layer routed on the input of
    the attention layer below it: each share's part of the layer (its
    output less the residual) summed, with the residual counted once, is the
    uncut reference's layer."""
    cfg, model, params = build(moe_held=(0, 8))
    whole = dataclasses.replace(cfg, moe_held=None)
    lp = params["layers"][1]
    x0 = params["embedding"]["weight"][tokens(24, 6)]
    with jax.default_matmul_precision("highest"):
        x1 = ref.smallthinker_layer(("*", None, 0), params["layers"][0], x0,
                                    cfg)
        want = ref.smallthinker_layer(("E",), lp, x1, whole, below=x0)
        routed = 0.0
        for k in range(4):
            share = GPTModel(dataclasses.replace(cfg, moe_held=(2 * k, 2)))
            held = {**lp, "mixer": {
                **lp["mixer"], "w1": lp["mixer"]["w1"][2 * k:2 * k + 2],
                "w2": lp["mixer"]["w2"][2 * k:2 * k + 2]}}
            layer = share.layers[1]
            y, _ = layer(held, x1, routing=layer.kind.route(
                held["mixer"], x0))
            routed = routed + (y - x1)
    np.testing.assert_allclose(np.asarray(x1 + routed), np.asarray(want),
                               atol=2e-6)


def _first_choice(model, params, toks, monkeypatch):
    """The first expert layer's choice ``(tokens, k)`` in the forward."""
    seen = []
    route = MoEMLP.route

    def spy(self, p, x):
        out = route(self, p, x)
        seen.append(np.asarray(out[0]))
        return out
    monkeypatch.setattr(MoEMLP, "route", spy)
    model.loss(params, toks, jnp.roll(toks, -1, 1))
    monkeypatch.setattr(MoEMLP, "route", route)
    return seen[0]


@pytest.mark.parametrize("ahead", [True, False])
def test_the_router_reads_the_layers_input(ahead, monkeypatch):
    """A change to the expert layer's own input that leaves the attention
    layer's input alone (the attention output projection scaled) moves no
    choice of a router placed before attention, and moves some where the
    router reads its own layer's norm; a change to the attention layer's
    input (the embedding) moves some either way."""
    cfg, model, params = build(moe_route_before_attention=ahead)
    toks = tokens(48, 3)
    base = _first_choice(model, params, toks, monkeypatch)
    proj = params["layers"][0]["mixer"]["proj"]["weight"]
    louder = jax.tree_util.tree_map(lambda a: a, params)
    louder["layers"][0]["mixer"]["proj"]["weight"] = 40 * proj
    moved = _first_choice(model, louder, toks, monkeypatch)
    assert np.array_equal(moved, base) == ahead
    other = jax.tree_util.tree_map(lambda a: a, params)
    other["embedding"]["weight"] = params["embedding"]["weight"][::-1]
    assert not np.array_equal(
        _first_choice(model, other, toks, monkeypatch), base)


def test_softmax_scores_and_reglu_against_plain_formulas():
    """The sorted dispatch with softmax scores and ``held=(2, 3)`` of 8,
    top 3, ReGLU experts, against the dense formula: softmax over all 8
    logits, the top 3, renormalised, each held expert's ``(relu(x G) * (x
    U)) D`` weighted and summed; and the dense ReGLU FFN."""
    m = MoEMLP(MoEConfig(hidden_size=16, ffn_hidden_size=12, n_experts=8,
                         top_k=3, router="softmax_sorted", held=(2, 3),
                         activation="reglu", router_bias=False))
    params = m.init_params(jax.random.PRNGKey(0))
    params["router"]["weight"] = 30 * params["router"]["weight"]
    x = jax.random.normal(jax.random.PRNGKey(1), (40, 16))
    with jax.default_matmul_precision("highest"):
        got, load = m(params, x)
        logits = x @ params["router"]["weight"].T
        probs = jax.nn.softmax(logits, -1)
        top, choice = jax.lax.top_k(probs, 3)
        w = top / top.sum(-1, keepdims=True)
        want = jnp.zeros_like(x)
        for e in range(3):
            gate = jnp.sum(jnp.where(choice == 2 + e, w, 0.0), -1)
            g, u = jnp.split(x @ params["w1"][e], 2, -1)
            want = want + gate[:, None] * (
                (jnp.maximum(g, 0) * u) @ params["w2"][e])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)
    assert [int(n) for n in load] == [int(jnp.sum(choice == 2 + e))
                                      for e in range(3)]
    # the routing handed in is the routing made here
    np.testing.assert_array_equal(
        np.asarray(m(params, x, m.route(params, x))[0]), np.asarray(got))
    cfg = GPTConfig(vocab_size=64, hidden_size=16, num_attention_heads=2,
                    ffn_hidden_size=12, ffn_activation="reglu", bias=False,
                    layer_pattern="D")
    mlp = ParallelMLP(cfg)
    p = mlp.init_params(jax.random.PRNGKey(2))
    with jax.default_matmul_precision("highest"):
        g, u = jnp.split(x @ p["fc1"]["weight"].T, 2, -1)
        np.testing.assert_allclose(
            np.asarray(mlp(p, x)),
            np.asarray((jnp.maximum(g, 0) * u) @ p["fc2"]["weight"].T),
            atol=1e-5)


def test_only_the_window_layers_rotate():
    """The full layer has no position code: its output does not move when
    the rotary base does; a window layer's does."""
    cfg, model, params = build()
    other = GPTModel(dataclasses.replace(cfg, rope_base=7.0))
    x = params["embedding"]["weight"][tokens(24, 5)]
    cos, sin = model.rope_tables(24)
    cos7, sin7 = other.rope_tables(24)
    for i in (0, 2):
        a = model.layers[i](params["layers"][i], x, cos, sin)
        b = other.layers[i](params["layers"][i], x, cos7, sin7)
        assert bool(jnp.any(a != b)) == (i == 2)


def test_the_share_trains_from_the_recipe():
    """The recipe's tiny configuration, one step on the CPU: the first loss
    within 5 % of ln(512), as the train job holds the cell's, and the
    parameters move."""
    args = recipe.parse_args(["--config", "tiny", "--seq-len", "128",
                              "--lr", "1e-3"])
    step, state, make_batch, n_params = recipe.build(
        args, devices=jax.devices()[:1])
    before = jax.tree_util.tree_leaves(state[0])[3].copy()
    *state, loss = step(*state, *make_batch())
    assert abs(float(loss) / math.log(512) - 1) < 0.05
    assert not np.array_equal(np.asarray(before),
                              np.asarray(jax.tree_util.tree_leaves(
                                  state[0])[3]))
    assert int(state[1]["step"]) == 1 and n_params > 0


@pytest.mark.parametrize("bad,names", [
    (dict(num_kv_heads=7), "grouped"),
    (dict(attention_dropout=0.1), "dropout"),
    (dict(attention_types=("full", "sliding", "sliding", "sliding"),
          rotary_layers=None), "attention_types"),
    (dict(rotary_layers=(0, 1, 1)), "rotary_layers"),
    (dict(rotary_layers=(0, 2, 1, 1) * 2), "rotary_layers"),
    (dict(rotary_layers=(0, 1, 1, 1) * 2, rotary=False), "rotary_layers"),
    (dict(layer_pattern="*EE" * 5 + "*", attention_types=("full",) * 6,
          rotary_layers=None, sliding_window=0), "route_before_attention"),
    (dict(moe_router="softmax", moe_held=None,
          moe_route_before_attention=False), "reglu.*sorted"),
])
def test_the_config_names_what_is_wrong(bad, names):
    with pytest.raises(ValueError, match=names):
        GPTConfig(**{**TINY, **bad})


def test_held_and_gated_experts_name_the_sorted_dispatch():
    with pytest.raises(ValueError, match="swiglu.*sigmoid.*softmax_sorted"):
        MoEConfig(hidden_size=8, ffn_hidden_size=8, n_experts=4,
                  activation="reglu")
    with pytest.raises(ValueError, match="held=.*sorted dispatch"):
        MoEConfig(hidden_size=8, ffn_hidden_size=8, n_experts=4,
                  held=(0, 2))


@pytest.mark.parametrize("entry", ["prefill", "training_of_P"])
def test_what_is_not_built_says_so(entry):
    """Serving this model raises by name (grouped heads, a window and
    routing ahead in a '*' layer); a parallel block still refuses the
    training forward, naming what it lacks."""
    if entry == "prefill":
        cfg, model, params = build()
        with pytest.raises(NotImplementedError, match="grouped KV heads"):
            model.prefill(params, tokens(8))
        return
    cfg = GPTConfig(
        vocab_size=64, hidden_size=64, num_attention_heads=4, num_kv_heads=2,
        head_dim=16, layer_pattern="P", attention_types=("full",),
        norm="layernorm", norm_bias=False, bias=False,
        ffn_activation="swiglu", ffn_hidden_size=32, n_experts=4,
        moe_top_k=2, moe_router="sigmoid")
    model = GPTModel(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    with pytest.raises(NotImplementedError, match="training forward"):
        model.loss(params, tokens(8), tokens(8))
