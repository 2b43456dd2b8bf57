"""GLM-5.2's mechanisms at rehearsal sizes on the CPU (seeded random
weights): latent attention in its two forms, the sparse-attention indexer
whose selection shared layers reuse, the paged pool of latent records and
index keys, and the expert layer's share, held to the plain float32
reference (``apex_tpu/models/reference.py::glm_dsa_reference``, whose copy
``benchmarks/configs/glm-5.2.reference.py`` decides the cell's ``correct``).
The configuration's own cases are collected from ``benchmarks/tests/``."""

import dataclasses
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.inference import InferenceEngine, Request
from apex_tpu.models import reference as ref
from apex_tpu.models.gpt import GPTConfig, GPTModel, LatentAttention
from apex_tpu.ops.latent_attention import (masked_attention, masked_flash,
                                           rotary_pairs, topk_mask,
                                           topk_positions)
from apex_tpu.serving import PagedInferenceEngine, PagedKVCache
from apex_tpu.serving.paged_kv import QuantizedPagedKVCache
from apex_tpu.serving.speculative import SpeculativeConfig
from apex_tpu.utils.platform import set_force_pallas

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load(relpath, name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(ROOT, relpath))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


_own = _load("benchmarks/tests/test_glm_config.py", "glm_config_tests")
globals().update({k: v for k, v in vars(_own).items()
                  if k.startswith("test_") or k == "config"})
bench_ref = _load("benchmarks/configs/glm-5.2.reference.py", "glm_bench_ref")


# PR 37: the tick's host phases as medians and the stall an admission puts
# on a reply in flight, read from the engine's own spans
TICK_PHASES = {f"tick_{phase}_p50_ms.tpot"
               for phase in ("dispatch", "wait", "grow", "inputs", "launch")} \
    | {"decode_stalled_share.tpot", "decode_stall_p50_ms.tpot"}


def test_the_cell_resolves_by_name_with_its_metrics():
    """The configuration's own case of this name holds the cell's per-layer
    metrics to be PR 35's, which PR 36's appended entry ended
    (``masked_flash_time_share.ttft``) and PR 37's seven span metrics
    again; a file under ``benchmarks/`` is a ``benchmark`` PR's to edit
    (``PERF.md``, section 7), so tier-1 runs the same checks here with the
    names since."""
    man = _own.manifest.Manifest(ROOT)
    c = man.cell(_own.CELL)
    assert c.chips == 1 and c.traffic["job"] == "serve_open"
    assert c.config["name"] == _own.NAME and c.config["job"] == "serve"
    assert {m["name"] for m in c.end_to_end} == {"ttft_p90_s", "tpot_p90_s",
                                                 "setup_s"}
    assert {m["name"] for m in c.per_layer} == _own.NEW | _own.JOINED | {
        "masked_flash_time_share.ttft"} | TICK_PHASES
    assert all(callable(getattr(_own.readers, m["reader"]))
               for m in c.per_layer)
    mine = [m for m in man.data["per_layer"]
            if m["name"] in _own.NEW | {"masked_flash_time_share.ttft"}]
    assert len(mine) == 6
    for m in mine:
        assert m["workloads"] == [_own.CELL] and m["unit"] == "%"
        assert m["moves"] == {"ttft": "ttft_p90_s", "tpot": "tpot_p90_s"}[
            m["name"].rsplit(".", 1)[1]]
    roofline = next(m for m in man.data["per_layer"]
                    if m["name"] == "paged_decode_roofline.tpot")
    assert _own.CELL not in roofline["workloads"]
    cell = next(w for w in man.data["workloads"] if w["name"] == _own.CELL)
    config = next(e for e in man.data["configs"] if e["name"] == _own.NAME)
    assert len(cell["why"]) <= 200 and len(config["why"]) <= 200
    assert set(c.config["check"]) == {"near_tie_margin", "min_compared"}


TOPK = 8
TINY = dict(
    vocab_size=128, hidden_size=64, num_attention_heads=2, max_seq_len=128,
    layer_pattern="*D*E*E", indexer_types=("full", "shared", "full"),
    norm="rmsnorm", bias=False, tie_head=False, ffn_activation="swiglu",
    ffn_hidden_size=32, dense_ffn_hidden_size=96, n_experts=8, moe_top_k=2,
    moe_router="sigmoid", moe_routed_scale=2.5, moe_shared_ffn=32,
    moe_held=(0, 4), rope_base=8e6, kv_lora_rank=32, q_lora_rank=48,
    qk_nope_head_dim=24, qk_rope_head_dim=8, v_head_dim=32, index_topk=TOPK,
    index_n_heads=2, index_head_dim=16)


def build(dtype=jnp.float32, seed=0, **over):
    cfg = GPTConfig(**{**TINY, **over}, dtype=dtype, param_dtype=dtype)
    model = GPTModel(cfg)
    return cfg, model, jax.jit(model.init_params)(jax.random.PRNGKey(seed))


def tokens(n, seed=0, rows=1):
    return jnp.asarray(np.random.default_rng(seed).integers(
        0, TINY["vocab_size"], (rows, n)), jnp.int32)


def of_range(got, want):
    return float(jnp.abs(got.astype(jnp.float32) - want).max()
                 / jnp.abs(want).max())


# -- the pieces -----------------------------------------------------------------

def test_rotary_turns_adjacent_pairs_of_the_rotary_lanes_only():
    x = jnp.asarray(np.random.default_rng(0).normal(size=(3, 2 * 8)),
                    jnp.float32)
    pos = np.array([0, 1, 7])
    y = np.asarray(rotary_pairs(x, pos, 4, 100.0, head_dim=8))
    for h in range(2):
        seg, out = np.asarray(x)[:, h * 8:(h + 1) * 8], y[:, h * 8:(h + 1) * 8]
        np.testing.assert_array_equal(out[:, :4], seg[:, :4])
        for i in range(2):
            a = pos * 100.0 ** (-2 * i / 4)
            x0, x1 = seg[:, 4 + 2 * i], seg[:, 5 + 2 * i]
            np.testing.assert_allclose(out[:, 4 + 2 * i],
                                       x0 * np.cos(a) - x1 * np.sin(a),
                                       atol=1e-5)
            np.testing.assert_allclose(out[:, 5 + 2 * i],
                                       x1 * np.cos(a) + x0 * np.sin(a),
                                       atol=1e-5)
    first = np.asarray(rotary_pairs(x, pos, 4, 100.0, head_dim=8, first=True))
    np.testing.assert_array_equal(first[:, 4:8], np.asarray(x)[:, 4:8])


@pytest.mark.parametrize("case", ["random", "ties_at_the_cut", "all_equal",
                                  "fewer_than_k"])
def test_the_selection_is_exact_and_ties_go_to_the_lower_position(case):
    rng = np.random.default_rng(1)
    scores = rng.normal(size=(4, 40)).astype(np.float32)
    allowed = np.ones((4, 40), bool)
    if case == "ties_at_the_cut":
        scores[:, 3:30] = -0.25
    elif case == "all_equal":
        scores[:] = 0.0
    elif case == "fewer_than_k":
        allowed = np.arange(40)[None, :] < np.array([3, 8, 1, 9])[:, None]
    got = np.asarray(topk_mask(jnp.asarray(scores), jnp.asarray(allowed), 8))
    idx, valid = topk_positions(jnp.asarray(scores),
                                jnp.asarray(allowed.sum(-1)), 8)
    for r in range(4):
        v = np.where(allowed[r], scores[r], -np.inf)
        want = {int(i) for i in np.argsort(-v, kind="stable")[:8]
                if allowed[r, i]}
        assert set(np.flatnonzero(got[r])) == want
        assert {int(i) for i, ok in zip(np.asarray(idx[r]),
                                        np.asarray(valid[r])) if ok} == want


# -- a prefill's masked attention: the flash kernel, interpreted (PR 36) ----------

def _allowed(case, s):
    """``(1, s, s)`` bool; every row allows its own position."""
    rows, keys = np.arange(s)[:, None], np.arange(s)[None, :]
    causal = keys <= rows
    if case == "a_key_tile_forbidden_to_some_rows":
        # 128-row blocks.  Block 1: even rows see keys 0-63 and themselves
        # (nothing new in their own tile but the diagonal), odd rows nothing
        # of tile 0.  Block 2: a third of the rows see themselves alone (two
        # whole tiles forbidden), a third keys 0-31 (tile 1 forbidden: the
        # running state must stay), a third keys 130-139 (tile 0 forbidden)
        own = keys == rows
        one = np.where(rows % 2 == 0, keys < 64, keys >= 128)
        two = np.select([rows % 3 == 1, rows % 3 == 2],
                        [keys < 32, (keys >= 130) & (keys < 140)], False)
        mask = np.select([rows < 128, rows < 256], [causal, one], two)
        return jnp.asarray((mask & causal) | own)[None]
    if case == "causal_only":
        return jnp.asarray(causal)[None]
    scores = jax.random.normal(jax.random.PRNGKey(5), (1, s, s))
    return topk_mask(scores, jnp.asarray(causal)[None], s // 4)


def _dense_attention(q, k, v, mask, scale):
    q, k, v = (x.astype(jnp.float32) for x in (q, k, v))
    with jax.default_matmul_precision("highest"):
        sc = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
        p = jax.nn.softmax(jnp.where(mask[:, None], sc, -jnp.inf), -1)
        o = jnp.einsum("bhqk,bhkd->bqhd", p, v)
    return o.reshape(*o.shape[:2], -1)


def _holds_a_kernel(scale, *args):
    # a fresh function each time: ``make_jaxpr`` remembers a function's
    # trace, and what ``masked_attention`` picks is not among its arguments
    return "pallas_call" in str(jax.make_jaxpr(
        lambda *a: masked_attention(*a, scale))(*args))


# s, heads, q/k width, v width: rows under and past the selection's s / 4
_ATTENTION_CASES = {
    "causal_only": (256, 1, 128, 128),
    "a_selection_smaller_than_the_sequence": (256, 2, 128, 128),
    "a_key_tile_forbidden_to_some_rows": (384, 1, 128, 128),
    "several_blocks_at_the_published_head": (512, 2, 256, 256),
    "shorter_than_a_row_block": (64, 2, 128, 128),
}


@pytest.mark.parametrize("dtype,limit", [(jnp.float32, 1e-5),
                                         (jnp.bfloat16, 0.01)])
@pytest.mark.parametrize("case", list(_ATTENTION_CASES))
def test_masked_flash_against_the_loop_and_a_dense_reference(case, dtype,
                                                             limit):
    """The kernel in interpret mode, 128-row and 128-key blocks, against the
    ``jax.numpy`` loop it stands in for and a dense float32 softmax of the
    same operands: no NaN where a tile allows a row nothing, a forbidden key
    contributes exactly zero; a sequence shorter than a row block takes the
    loop, by its shape."""
    s, h, d, dv = _ATTENTION_CASES[case]
    keys = jax.random.split(jax.random.PRNGKey(len(case)), 3)
    q, k, v = (jax.random.normal(key, (1, h, s, w), dtype)
               for key, w in zip(keys, (d, d, dv)))
    mask, scale = _allowed(case, s), d ** -0.5
    want = _dense_attention(q, k, v, mask, scale)
    loop = masked_attention(q, k, v, mask, scale)
    assert not _holds_a_kernel(scale, q, k, v, mask)
    assert loop.dtype == dtype and of_range(loop, want) < limit
    set_force_pallas(True)
    try:
        kernel = s >= 128
        assert _holds_a_kernel(scale, q, k, v, mask) == kernel
        got = [masked_attention(q, k, v, mask, scale)]
    finally:
        set_force_pallas(None)
    if kernel:
        got += [masked_flash(q, k, v, mask, scale=scale, block_q=bq,
                             block_k=bk, interpret=True)
                for bq, bk in ((128, 128), (128, s // 2))]
    for o in got:
        assert o.shape == (1, s, h * dv) and o.dtype == dtype
        assert not bool(jnp.isnan(o.astype(jnp.float32)).any())
        assert of_range(o, want) < limit
        assert of_range(o, loop.astype(jnp.float32)) < limit


def test_masked_flash_gives_a_forbidden_key_no_weight():
    """The mask is discrete: moving a forbidden key's value to 1e4 moves
    no output, and a row that allows one key returns that key's value."""
    s, d = 256, 128
    keys = jax.random.split(jax.random.PRNGKey(9), 3)
    q, k, v = (jax.random.normal(key, (1, 1, s, d), jnp.float32)
               for key in keys)
    mask = _allowed("a_key_tile_forbidden_to_some_rows", 384)[:, :s, :s]
    # key 200 is allowed to row 200 alone
    mask = mask.at[0, :, 200].set(jnp.arange(s) == 200)

    def run(v):
        return masked_flash(q, k, v, mask, scale=d ** -0.5, block_q=128,
                            block_k=128, interpret=True)
    base, moved = run(v), run(v.at[0, 0, 200].set(1e4))
    others = np.arange(s) != 200
    np.testing.assert_array_equal(np.asarray(base[0, others]),
                                  np.asarray(moved[0, others]))
    # rows 129, 131, ... allow keys 128..r; row 129 sees two, row 128 one
    lone = mask[0].sum(-1) == 1
    assert bool(lone.any())
    np.testing.assert_allclose(np.asarray(base[0, lone]),
                               np.asarray(v[0, 0, lone]), rtol=1e-6)


@pytest.mark.parametrize("topk", [TOPK, 64])
def test_expanded_and_absorbed_attention_agree(topk):
    """One layer, float32: the expanded prefill's output at the last
    position against the absorbed tick's for the same token over the
    records the prefill left (sparse at ``topk`` 8, every position at
    64)."""
    cfg, _, _ = build(index_topk=topk)
    layer = LatentAttention(cfg, indexer=True)
    p = layer.init_params(jax.random.PRNGKey(3))
    n, bs = 40, 8
    x = jnp.asarray(np.random.default_rng(2).normal(size=(1, n, 64)),
                    jnp.float32)
    out, records, _ = layer.prefill(p, x, None, x)
    pools = [jnp.zeros((1 + n // bs, 1, 1, bs, r.shape[-1]), jnp.float32)
             for r in records]
    for pool, r, i in zip(pools, records, (0, 1)):
        pools[i] = pool.at[1:, 0, 0].set(
            r[0, :, 0].reshape(n // bs, bs, -1)).at[-1, 0, 0, -1].set(0.0)
    tables = jnp.arange(1, 1 + n // bs)[None]
    got, _, (idx, valid) = layer.decode_paged(
        p, x[:, -1:], tuple(pools), (0, 0), tables, jnp.asarray([n - 1]),
        None, x[:, -1:])
    assert int(valid.sum()) == min(topk, n)
    np.testing.assert_allclose(np.asarray(got[0, 0]), np.asarray(out[0, -1]),
                               atol=1e-5)


@pytest.mark.parametrize("dtype,limit", [(jnp.float32, 1e-4),
                                         (jnp.bfloat16, 0.04)])
def test_prefill_logits_against_the_reference(dtype, limit):
    """At 5 x ``index_topk`` positions.  float32: every position.  bf16:
    nine in ten of the positions with no near tie in the router (a router
    of 8 flips often, and a selection of 8 that swaps one position moves an
    eighth of the softmax's weight, where one of 2 048 moves nothing)."""
    cfg, model, params = build(dtype)
    toks = tokens(5 * TOPK)
    logits, _ = jax.jit(model.prefill)(params, toks)
    want = np.asarray(ref.glm_dsa_reference(params, toks, cfg))[0]
    err = np.abs(np.asarray(logits[0], np.float32) - want).max(-1) \
        / np.abs(want).max()
    if dtype == jnp.float32:
        assert err.max() < limit
        return
    keep = ~np.asarray(ref.glm_dsa_near_ties(params, toks, cfg, 1e-2))[0]
    assert keep.sum() >= TOPK
    assert np.percentile(err[keep], 90) < limit and np.median(err) < 0.01


def test_the_programs_reference_and_the_benchmarks_are_one():
    cfg, _, params = build()
    toks = tokens(24, seed=4)
    np.testing.assert_array_equal(
        np.asarray(ref.glm_dsa_reference(params, toks, cfg)),
        np.asarray(bench_ref.gpt_reference_logits(params, toks, cfg)))
    np.testing.assert_array_equal(
        np.asarray(ref.glm_dsa_near_ties(params, toks, cfg, 5e-3)),
        np.asarray(bench_ref.near_ties(params, toks, cfg, 5e-3)))


def test_index_topk_at_least_the_length_is_dense_latent_attention():
    """With ``index_topk`` >= the length the model is dense latent
    attention; with less it is not, and equals the masked reference."""
    toks = tokens(40, seed=5)
    cfg, model, params = build()
    dense_cfg = dataclasses.replace(cfg, index_topk=64)
    sparse = jax.jit(model.prefill)(params, toks)[0]
    dense = jax.jit(GPTModel(dense_cfg).prefill)(params, toks)[0]
    assert of_range(dense, ref.glm_dsa_reference(params, toks, dense_cfg)) \
        < 1e-4
    assert of_range(sparse, ref.glm_dsa_reference(params, toks, cfg)) < 1e-4
    np.testing.assert_allclose(np.asarray(sparse[0, :TOPK]),
                               np.asarray(dense[0, :TOPK]), atol=1e-5)
    assert of_range(sparse[:, TOPK + 4:], dense[:, TOPK + 4:]) > 1e-3


def test_a_shared_layer_has_no_indexer_and_no_index_key():
    cfg, model, params = build()
    mixers = [(l.mix, p["mixer"]) for l, p in zip(model.layers,
                                                  params["layers"])
              if l.mixer == "*"]
    assert [m.indexer for m, _ in mixers] == [True, False, True]
    for m, p in mixers:
        assert ("index_q" in p) == ("index_k_norm" in p) == m.indexer
    assert model.cache_record() == ((3, 1, 128), (2, 1, 16, jnp.float32))
    _, records = jax.jit(model.prefill)(params, tokens(16))
    assert [r.shape for r in records] == [(3, 1, 1, 16, 1, 128),
                                          (2, 1, 1, 16, 1, 16)]
    # the shared layer attends under the first layer's selection: with the
    # first indexer's weights changed and nothing else, only what the
    # selection decides can move the shared layer's output
    toks = tokens(40, seed=6)
    other = jax.tree.map(lambda a: a, params)
    other["layers"][0]["mixer"]["index_w"] = {
        "weight": -params["layers"][0]["mixer"]["index_w"]["weight"]}
    a = jax.jit(model.prefill)(params, toks)[0]
    b = jax.jit(model.prefill)(other, toks)[0]
    np.testing.assert_allclose(np.asarray(a[0, :TOPK]),
                               np.asarray(b[0, :TOPK]), atol=1e-6)
    assert of_range(a[:, TOPK + 4:], b[:, TOPK + 4:]) > 1e-3
    assert of_range(b, ref.glm_dsa_reference(other, toks, cfg)) < 1e-4


# -- through the engine -----------------------------------------------------------

def serve(model, params, prompts, new=8, **kw):
    """Tokens and every tick's logits of ``prompts`` served together."""
    kw = {"max_slots": 4, "block_size": 8, "cache_dtype": model.cfg.dtype,
          **kw}
    eng = PagedInferenceEngine(model, params, **kw)
    ticks, inner = [], eng._decode_paged

    def decode(p, toks, pool, tables, positions):
        logits, ids, pool = inner(p, toks, pool, tables, positions)
        ticks.append((np.asarray(positions), np.asarray(logits)))
        return logits, ids, pool
    eng._decode_paged = decode
    for i, p in enumerate(prompts):
        eng.submit(Request(request_id=i, prompt=p, max_new_tokens=new,
                           eos_id=None))
    done = {r.request_id: r for r in eng.run()}
    return eng, done, ticks


@pytest.mark.parametrize("dtype,limit", [(jnp.float32, 1e-4),
                                         (jnp.bfloat16, 0.08)])
def test_prefill_then_ticks_against_the_references_full_forward(dtype, limit):
    """Two sequences of different lengths in the same ticks: every decoded
    position's logits (float32), every decoded token's gap (bf16), against
    the reference's forward over prompt and reply."""
    cfg, model, params = build(dtype)
    prompts = [tokens(30, seed=7)[0].tolist(), tokens(17, seed=8)[0].tolist()]
    eng, done, ticks = serve(model, params, prompts)
    assert len(ticks) == 7 and eng.pool.token_bytes == (
        3 * 128 * jnp.dtype(dtype).itemsize + 2 * 16 * 4)
    # a tick's indexers score every cached position and its attention
    # reads index_topk of them: the counters say so
    contexts = [len(p) + 1 + j for p in prompts for j in range(7)]
    assert eng._c_scored.value() == sum(contexts)
    assert eng._c_selected.value() == sum(min(c, TOPK) for c in contexts)
    for i, prompt in enumerate(prompts):
        reply = list(done[i].tokens)
        assert done[i].finish_reason == "length" and len(reply) == 8
        want = np.asarray(ref.glm_dsa_reference(
            params, jnp.asarray([prompt + reply], jnp.int32), cfg))[0]
        scale = np.abs(want).max()
        for j, t in enumerate(reply):
            row = want[len(prompt) - 1 + j]
            assert (row.max() - row[t]) / scale <= limit
        if dtype == jnp.float32:
            for positions, logits in ticks:
                assert np.abs(logits[i] - want[positions[i]]).max() / scale \
                    < limit


@pytest.mark.parametrize("program", ["prefill", "decode_step_paged"])
def test_a_patterns_cache_paths_name_embeddings_and_the_head(program):
    """Both programs the cell runs carry ``embeddings`` and ``lm_head``
    beside the layers' ``attention`` and ``mlp`` (PR 37: ``embeddings``
    moved the gather out of the unscoped rest), with the latent
    attention's parts nested under ``attention``."""
    import re
    from benchmarks.harness import span_readers as sr
    cfg, model, params = build()
    eng = PagedInferenceEngine(model, params, max_slots=2, block_size=8,
                               cache_dtype=jnp.float32)
    row = jax.ShapeDtypeStruct((2,), jnp.int32)
    args = {"prefill": (tokens(16),),
            "decode_step_paged": (row, eng.pool.data,
                                  jnp.asarray(eng._tables), row)}[program]
    text = jax.jit(getattr(model, program)).lower(
        params, *args).as_text(debug_info=True)
    paths = {p for p in re.findall(r'loc\("([^"]*)"', text)
             if p.startswith(f"jit({program})/")}
    by = {}
    for p in paths:
        by.setdefault(sr.innermost_scope(
            p, sr.SCOPES + ("lm_head",)), []).append(p)
    assert {"embeddings", "attention", "mlp", "lm_head"} <= set(by)
    assert any("/attention/mla.q/" in p for p in by["attention"])
    assert any("/embeddings/jit(_take)" in p for p in by["embeddings"])


def test_preempt_and_resume_give_the_same_tokens():
    cfg, model, params = build()
    prompts = [tokens(20, seed=9)[0].tolist(), tokens(12, seed=10)[0].tolist()]
    _, want, _ = serve(model, params, prompts)
    eng = PagedInferenceEngine(model, params, max_slots=4, block_size=8,
                               cache_dtype=jnp.float32)
    for i, p in enumerate(prompts):
        eng.submit(Request(request_id=i, prompt=p, max_new_tokens=8,
                           eos_id=None))
    eng.step(), eng.step(), eng.step()
    assert eng.preempt() == 2 and eng.active_requests == 0
    got = {r.request_id: r.tokens for r in eng.run()}
    assert got == {i: r.tokens for i, r in want.items()}


def test_a_shared_prefix_is_served_from_the_trie():
    """The prefix trie, copy-on-write and fork treat the pool's two arrays
    alike: a second request with the first's prompt reads its blocks."""
    cfg, model, params = build()
    prompt = tokens(33, seed=11)[0].tolist()
    eng, done, _ = serve(model, params, [prompt])
    eng.submit(Request(request_id="again", prompt=prompt, max_new_tokens=8,
                       eos_id=None))
    again = {r.request_id: r for r in eng.run()}["again"]
    assert eng.pool.prefix_hit_tokens == 32
    assert again.tokens == done[0].tokens
    seq = eng.pool.acquire(prompt)
    twin = eng.pool.fork(seq)
    before = [np.asarray(a[twin.block_ids[-1]]) for a in eng.pool.data]
    new = eng.pool.ensure_writable(twin, len(twin.block_ids) - 1)
    assert new != seq.block_ids[-1] and eng.pool.cow_copies == 1
    for a, b in zip(eng.pool.data, before):
        np.testing.assert_array_equal(np.asarray(a[new]), b)


def test_export_kv_and_adopt_kv_move_both_arrays():
    cfg, model, params = build()
    prompt = tokens(21, seed=12)[0].tolist()
    _, want, _ = serve(model, params, [prompt])
    src = PagedInferenceEngine(model, params, max_slots=2, block_size=8,
                               cache_dtype=jnp.float32)
    dst = PagedInferenceEngine(model, params, max_slots=2, block_size=8,
                               cache_dtype=jnp.float32)
    src.submit(Request(request_id=0, prompt=prompt, max_new_tokens=8,
                       eos_id=None))
    src.step(), src.step()
    handoff = src.export_kv(0)
    assert [a.shape[1:] for a in handoff.payload["data"]] == [
        (3, 1, 8, 128), (2, 1, 8, 16)]
    assert handoff.nbytes() == 3 * (3 * 128 + 2 * 16) * 8 * 4
    dst.adopt_kv(handoff)
    (r,) = dst.run()
    assert r.tokens == want[0].tokens


@pytest.mark.parametrize("entry,names", [
    ("chunked_prefill", "decode_chunk"), ("speculative", "decode_chunk"),
    ("contiguous", "decode_step"), ("int8_pool", "int8"),
    ("decode_chunk_quant", "decode_chunk_quant"),
    ("training_forward", "training forward")])
def test_every_refused_entry_says_what_it_lacks(entry, names):
    cfg, model, params = build()
    with pytest.raises(NotImplementedError, match=names):
        if entry == "chunked_prefill":
            PagedInferenceEngine(model, params, chunked_prefill=True)
        elif entry == "speculative":
            PagedInferenceEngine(model, params, speculative=SpeculativeConfig(
                model=model, params=params, num_tokens=2))
        elif entry == "contiguous":
            InferenceEngine(model, params)
        elif entry == "int8_pool":
            QuantizedPagedKVCache(9, 8, 3, 1, 128,
                                  record=model.cache_record())
        elif entry == "decode_chunk_quant":
            model.decode_chunk_quant(params, None, None, None, None, None,
                                     None, None)
        else:
            model(params, tokens(8))


def test_a_plain_models_pool_is_one_array_as_it_was():
    pool = PagedKVCache(5, 8, 2, 4, 16)
    assert pool.data.shape == (5, 2, 2, 8, 64)
    assert pool.token_bytes == 2 * 2 * 64 * 2
    plain = GPTModel(GPTConfig(vocab_size=64, hidden_size=32, num_layers=2,
                               num_attention_heads=2, max_seq_len=32))
    assert plain.cache_record() == ((2, 2, 32),)


def test_a_pattern_of_plain_attention_is_served_too():
    """``*``, ``D`` and ``E`` layers without latent attention: K and V of
    every head in one array, through the same two programs."""
    over = dict(kv_lora_rank=0, q_lora_rank=0, qk_nope_head_dim=0,
                qk_rope_head_dim=0, v_head_dim=0, index_topk=0,
                index_n_heads=0, index_head_dim=0, indexer_types=None,
                rope_base=1e4)
    cfg, model, params = build(**over)
    assert model.cache_record() == ((3, 2, 64),)
    prompt = tokens(13, seed=13)[0].tolist()
    eng, done, ticks = serve(model, params, [prompt], new=4)
    logits, _ = jax.jit(model.prefill)(
        params, jnp.asarray([prompt + list(done[0].tokens)], jnp.int32))
    for positions, got in ticks:
        np.testing.assert_allclose(got[0], np.asarray(logits[0, positions[0]]),
                                   atol=2e-5)


# -- the share ---------------------------------------------------------------------

def test_the_shares_add_up_to_the_uncut_layer():
    """4 shares of 2 of 8 experts: attention, which every share computes
    alike, and the shared expert counted once, the routed parts added,
    equal the uncut reference's published layer (attention, then
    experts)."""
    cfg, model, params = build(layer_pattern="*E", indexer_types=("full",),
                               moe_held=(0, 8))
    toks = tokens(24, seed=14)
    whole = GPTConfig(**{**TINY, "layer_pattern": "*E",
                         "indexer_types": ("full",), "moe_held": None})
    x = params["embedding"]["weight"][toks]
    attn, ffn = params["layers"]
    with jax.default_matmul_precision("highest"):
        u = ref._glm_rms_norm(x, attn["norm"])
        x1 = x + ref.glm_dsa_attention(attn["mixer"], u, whole, None)[0]
        u = ref._glm_rms_norm(x1, ffn["norm"])
        want = x1 + ref.glm_dsa_experts(ffn["mixer"], u, whole)[0]
        shared = ref.glm_dsa_dense(ffn["mixer"]["shared"], u, whole)
    routed, firsts = 0.0, []
    for k in range(4):
        share = GPTModel(dataclasses.replace(cfg, moe_held=(2 * k, 2)))
        held = {**ffn["mixer"], "w1": ffn["mixer"]["w1"][2 * k:2 * k + 2],
                "w2": ffn["mixer"]["w2"][2 * k:2 * k + 2]}
        y1, _, _ = share.layers[0].prefill(attn, x)
        y2, _, _ = share.layers[1].prefill({**ffn, "mixer": held}, y1)
        firsts.append(y1)
        routed = routed + (y2 - y1 - shared)
    for y1 in firsts[1:]:
        np.testing.assert_array_equal(np.asarray(y1), np.asarray(firsts[0]))
    np.testing.assert_allclose(np.asarray(firsts[0] + shared + routed),
                               np.asarray(want), atol=2e-5)


def test_near_ties_names_a_position_only_where_a_held_expert_is_at_the_cut():
    cfg, _, _ = build()                     # top 2 of 8, experts 0-3 held
    base = np.array([.9, .1, .15, .2, .5, .7, .3, .25], np.float32)
    rows = np.stack([base] * 5)
    rows[0, [0, 5, 4]] = [.9, .7, .6999]    # cut between 5 and 4: not held
    rows[1, [0, 5, 1]] = [.9, .7, .6999]    # 1 is held and first unchosen
    rows[2, [0, 1, 5, 4]] = [.1, .7, .9, .6999]     # 1 held, last chosen
    rows[3, [0, 5, 4]] = [.7001, .7, .3]    # held at the cut, but far apart
    rows[4, [0, 5, 4]] = [.7001, .7, .6999]  # held FIRST, cut not held
    gaps = np.asarray(ref.glm_dsa_tie_gaps([jnp.asarray(rows[None])], cfg))[0]
    assert (gaps < 5e-3).tolist() == [False, True, True, False, False]
    np.testing.assert_array_equal(gaps, np.asarray(bench_ref.tie_gaps(
        [jnp.asarray(rows[None])], cfg))[0])


def test_the_examples_share_is_the_benchmarks_model_group(config):
    """``examples/glm_dsa/serve_glm_dsa.py --config share`` builds the
    ``GPTConfig`` the benchmark configuration's ``model`` group builds, and
    its tiny size serves."""
    recipe = _load("examples/glm_dsa/serve_glm_dsa.py", "serve_glm_dsa")
    kw = {k: getattr(jnp, v) if v in ("bfloat16", "float32") else v
          for k, v in config["model"].items()}
    assert recipe.model_config("share") == GPTConfig(**kw)
    assert recipe._ENGINES["share"] == {
        k: v for k, v in config["engine"].items() if k != "cache_dtype"}
    done = recipe.main(["--config", "tiny", "--requests", "2",
                        "--prompt-len", "20", "--new-tokens", "3",
                        "--dtype", "float32"])
    assert [r.finish_reason for r in done] == ["length"] * 2
