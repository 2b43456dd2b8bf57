"""Command A+'s mechanisms at a tiny size on the CPU (seeded random weights):
the parallel block (one weight-only LayerNorm feeding grouped attention and
the experts, one residual sum, the shared experts averaged with the routed
sum), window layers whose storage is bounded by the window, full layers with
no position code, the two kernels that read a KV head for a group of query
heads and visit only a row's band, held to the plain float32 reference
(``apex_tpu/models/reference.py::command_a_reference``, whose copy
``benchmarks/configs/command-a-plus-05-2026.reference.py`` decides the cell's
``correct``).  The configuration's own cases are collected from
``benchmarks/tests/``."""

import dataclasses
import importlib.util
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.inference import InferenceEngine, Request
from apex_tpu.models import reference as ref
from apex_tpu.models.gpt import GPTConfig, GPTModel
from apex_tpu.ops.flash_attention import (grouped_attention_reference,
                                          grouped_decode,
                                          grouped_decode_reference,
                                          window_flash)
from apex_tpu.serving import PagedInferenceEngine, PagedKVCache
from apex_tpu.serving.speculative import SpeculativeConfig

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load(relpath, name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(ROOT, relpath))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


_own = _load("benchmarks/tests/test_command_a_config.py",
             "command_a_config_tests")
globals().update({k: v for k, v in vars(_own).items()
                  if k.startswith("test_") or k == "config"})
bench_ref = _load("benchmarks/configs/command-a-plus-05-2026.reference.py",
                  "command_a_bench_ref")


def test_the_cell_resolves_by_name_with_its_metrics():
    """The configuration's own case of this name holds its two kernels'
    metrics to be the manifest's LAST, which a cell appended after it ended; a
    file under ``benchmarks/`` is a ``benchmark`` PR's to edit, so tier-1
    runs the same checks here with the two in their place, not last."""
    man = _own.manifest.Manifest(ROOT)
    c = man.cell(_own.CELL)
    assert c.chips == 1 and c.traffic["job"] == "serve_open"
    assert c.config["name"] == _own.NAME and c.config["job"] == "serve"
    assert {m["name"] for m in c.end_to_end} == {"ttft_p90_s", "tpot_p90_s",
                                                 "setup_s"}
    assert {m["name"] for m in c.per_layer} == _own.NEW | _own.JOINED | {
        "tick_ahead_share.tpot"}
    assert not {m["name"] for m in c.per_layer} & _own.NOT_JOINED
    assert all(callable(getattr(_own.readers, m["reader"]))
               for m in c.per_layer)
    names = [m["name"] for m in man.data["per_layer"]]
    at = names.index("window_flash_time_share.ttft")
    assert names[at:at + 2] == ["window_flash_time_share.ttft",
                                "grouped_decode_time_share.tpot"]
    for m in (m for m in man.data["per_layer"] if m["name"] in _own.NEW):
        spec = man.metric(m)
        assert m["workloads"] == [_own.CELL] and m["unit"] == "%"
        assert m["layer"] == "kernels" and m["source"] == "device_trace"
        assert spec["reader"] == "op_time_share" \
            and spec["args"]["over"] == "busy"
        assert m["moves"] == {"ttft": "ttft_p90_s", "tpot": "tpot_p90_s"}[
            m["name"].rsplit(".", 1)[1]]
    cell = next(w for w in man.data["workloads"] if w["name"] == _own.CELL)
    config = next(e for e in man.data["configs"] if e["name"] == _own.NAME)
    assert len(cell["why"]) <= 200 and len(config["why"]) <= 200
    assert set(c.config["check"]) == {"near_tie_margin", "min_compared"}

WINDOW, BLOCK = 16, 8
TINY = dict(
    vocab_size=128, hidden_size=64, num_attention_heads=16, num_kv_heads=2,
    head_dim=16, max_seq_len=128, layer_pattern="PPPP",
    attention_types=("sliding",) * 3 + ("full",), sliding_window=WINDOW,
    norm="layernorm", norm_bias=False, bias=False, tie_head=True,
    ffn_activation="swiglu", ffn_hidden_size=32, n_experts=8, moe_top_k=2,
    moe_router="sigmoid", moe_router_bias=False, moe_shared_ffn=64,
    moe_shared_combine="average", moe_held=(0, 4), rope_base=5e4)


def build(dtype=jnp.float32, seed=0, **over):
    cfg = GPTConfig(**{**TINY, **over}, dtype=dtype, param_dtype=dtype)
    model = GPTModel(cfg)
    return cfg, model, jax.jit(model.init_params)(jax.random.PRNGKey(seed))


def tokens(n, seed=0):
    return np.random.default_rng(seed).integers(
        0, TINY["vocab_size"], n).tolist()


def serve(model, params, prompts, new=12, **kw):
    """The engine's tokens and every tick's ``(positions, logits)``."""
    kw = {"max_slots": 4, "block_size": BLOCK,
          "cache_dtype": model.cfg.dtype, **kw}
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
    return eng, {r.request_id: r for r in eng.run()}, ticks


def worst_tick(prompts, done, ticks, params, cfg):
    """Of the range: the worst distance of a tick's logits from the
    reference's full forward over prompt and reply, and of the prefill's
    last row (every decoded position of every request)."""
    worst = 0.0
    for i, prompt in enumerate(prompts):
        full = prompt + list(done[i].tokens)
        want = np.asarray(ref.command_a_reference(
            params, jnp.asarray([full], jnp.int32), cfg))[0]
        scale = np.abs(want).max()
        for positions, logits in ticks:
            if positions[i] < len(full) - 1:
                worst = max(worst, np.abs(logits[i] - want[positions[i]])
                            .max() / scale)
    return worst


# -- the kernels, interpreted --------------------------------------------------

@pytest.mark.parametrize("dtype,limit", [(jnp.float32, 1e-5),
                                         (jnp.bfloat16, 2e-2)])
@pytest.mark.parametrize("window,block", [(None, 128), (200, 128),
                                          (128, 128), (300, 256)])
def test_window_flash_against_the_reference(window, block, dtype, limit):
    """The prefill kernel in interpret mode, 4 query heads over 2 KV heads
    of 128 at 512 positions: no window, windows inside a block, on a block's
    edge and wider than one; the KV head is read through the index map.
    float32: the kernel's sums in another order (1e-5 of the range); bf16:
    ``p`` rounded to bf16 before ``p v``, as the kernel does (2e-2)."""
    s, h, kv, d = 512, 4, 2, 128
    keys = jax.random.split(jax.random.PRNGKey(block), 3)
    q, k, v = (jax.random.normal(key, (1, s, n * d), dtype)
               for key, n in zip(keys, (h, kv, kv)))
    want = grouped_attention_reference(q, k, v, h, window).astype(
        jnp.float32)
    got = window_flash(q, k, v, heads=h, window=window, scale=d ** -0.5,
                       block_q=block, block_k=block, interpret=True)
    assert got.shape == q.shape and got.dtype == dtype
    err = float(jnp.abs(got.astype(jnp.float32) - want).max()
                / jnp.abs(want).max())
    assert err < limit


def test_window_flash_visits_the_band_alone():
    """The grid's innermost axis is the band's width in key blocks, not the
    sequence's: at 4 096 positions, blocks of 1 024 and a window of 1 024, a
    row block sees its own block and the one before."""
    from apex_tpu.ops.flash_attention import _band_steps
    assert _band_steps(4096, 1024, 1024, 1024) == 2
    assert _band_steps(4096, 1024, 1024, None) == 4
    assert _band_steps(16384, 1024, 1024, 4096) == 5


@pytest.mark.parametrize("window", [None, 100, 16])
def test_grouped_decode_against_the_reference(window):
    """The tick's kernel in interpret mode, 4 query heads over 2 KV heads of
    128 in blocks of 8: three rows, one of whose tables has returned its
    first three blocks behind a window of 100 (blocks it would not read), one
    of which a window of 16 cuts mid-block; float32, sums in another order
    (1e-5 of the range)."""
    h, kv, d, bs, nb, slots = 4, 2, 128, 8, 20, 3
    keys = jax.random.split(jax.random.PRNGKey(0), 2)
    pool = jax.random.normal(keys[0], (1 + slots * nb, 2, 2, bs, kv * d))
    tables = (1 + np.arange(slots * nb)).reshape(slots, nb).astype(np.int32)
    if window is not None:
        tables[2, :3] = 0
    lens = jnp.asarray([5, 77, 160], jnp.int32)
    q = jax.random.normal(keys[1], (slots, h, d))
    want = grouped_decode_reference(q, pool, 1, jnp.asarray(tables), lens,
                                    window)
    got = grouped_decode(lens, jnp.asarray(tables), jnp.full((1,), 1,
                                                             jnp.int32),
                         q, pool, window=window, scale=d ** -0.5,
                         interpret=True)
    assert float(jnp.abs(got - want).max() / jnp.abs(want).max()) < 1e-5


# -- the model against its reference ----------------------------------------------

@pytest.mark.parametrize("dtype,limit", [(jnp.float32, 1e-4),
                                         (jnp.bfloat16, 0.08)])
def test_prefill_then_ticks_across_the_window_against_the_reference(dtype,
                                                                    limit):
    """Two requests of 30 and 9 tokens, 12 decoded tokens each, window 16:
    every context crosses the window.  float32: every tick's logits and
    every prefill's within 1e-4 of the range (the same sums in other
    orders).  bf16: every decoded token within 0.08 of the range of the
    reference's best (the harness's limit for a decoded token, twice
    ``LOGIT_TOL``), since bf16 rounding moves a near tie of the router."""
    cfg, model, params = build(dtype)
    prompts = [tokens(30, 1), tokens(9, 2)]
    eng, done, ticks = serve(model, params, prompts)
    assert len(ticks) == 11
    # the ticks after the admissions were launched on the device's ids
    assert eng.metrics.registry.get("serving_ticks_ahead_total").value() > 0
    for i, prompt in enumerate(prompts):
        reply = list(done[i].tokens)
        assert done[i].finish_reason == "length" and len(reply) == 12
        want = np.asarray(ref.command_a_reference(
            params, jnp.asarray([prompt + reply], jnp.int32), cfg))[0]
        scale = np.abs(want).max()
        for j, t in enumerate(reply):
            row = want[len(prompt) - 1 + j]
            assert (row.max() - row[t]) / scale <= limit
        if dtype == jnp.float32:
            logits, _ = jax.jit(model.prefill)(
                params, jnp.asarray([prompt], jnp.int32))
            assert np.abs(np.asarray(logits[0]) - want[:len(prompt)]).max() \
                / scale < limit
    if dtype == jnp.float32:
        assert worst_tick(prompts, done, ticks, params, cfg) < limit


def test_the_window_off_fails_the_same_tolerances():
    """The control that lets the test above fail: the same weights served
    with the window wider than any context (every layer attends to all of
    it) lie far outside 1e-4 of the reference with the window, once a
    context passes 16 positions."""
    cfg, _, params = build()
    wide = GPTModel(dataclasses.replace(cfg, sliding_window=1024))
    prompts = [tokens(30, 1), tokens(9, 2)]
    _, done, ticks = serve(wide, params, prompts)
    assert worst_tick(prompts, done, ticks, params, cfg) > 1e-2


def test_the_programs_reference_and_the_benchmarks_are_one():
    cfg, _, params = build()
    toks = jnp.asarray([tokens(40, 4)], jnp.int32)
    np.testing.assert_array_equal(
        np.asarray(ref.command_a_reference(params, toks, cfg)),
        np.asarray(bench_ref.gpt_reference_logits(params, toks, cfg)))
    np.testing.assert_array_equal(
        np.asarray(ref.command_a_near_ties(params, toks, cfg, 5e-3)),
        np.asarray(bench_ref.near_ties(params, toks, cfg, 5e-3)))


def test_only_the_window_layers_rotate():
    """A full layer has no position code: its output does not move when the
    rotary base does; a window layer's does."""
    cfg, model, params = build()
    toks = jnp.asarray([tokens(24, 5)], jnp.int32)
    other = GPTModel(dataclasses.replace(cfg, rope_base=7.0))
    x = model.embed(params, toks)
    for i, layer in enumerate(model.layers):
        a = layer.prefill(params["layers"][i], x)[0]
        b = other.layers[i].prefill(params["layers"][i], x)[0]
        assert bool(jnp.any(a != b)) == (layer.attention.window is not None)


def test_the_shares_add_up_to_the_uncut_layer():
    """4 shares of 2 of 8 experts, one parallel layer: attention and half
    the shared experts, which every share computes alike, counted once, and
    each share's half of its routed sum added, equal the uncut reference's
    layer ``x + A + (R + Sh) / 2``."""
    cfg, _, params = build(layer_pattern="P", attention_types=("sliding",),
                           moe_held=(0, 8))
    lp = params["layers"][0]
    whole = dataclasses.replace(cfg, moe_held=None)
    x = params["embedding"]["weight"][jnp.asarray([tokens(24, 6)])]
    with jax.default_matmul_precision("highest"):
        u = ref._ca_layer_norm(x, lp["norm"])
        attn = ref.command_a_attention(lp["attention"], u, whole, WINDOW)
        want = x + attn + ref.command_a_experts(lp["mlp"], u, whole)[0]
        half_shared = ref.command_a_shared(lp["mlp"]["shared"], u) / 2
    routed = 0.0
    for k in range(4):
        share = GPTModel(dataclasses.replace(cfg, moe_held=(2 * k, 2)))
        held = {**lp["mlp"], "w1": lp["mlp"]["w1"][2 * k:2 * k + 2],
                "w2": lp["mlp"]["w2"][2 * k:2 * k + 2]}
        y, _, _ = share.layers[0].prefill({**lp, "mlp": held}, x)
        routed = routed + (y - x - attn - half_shared)
    np.testing.assert_allclose(np.asarray(x + attn + half_shared + routed),
                               np.asarray(want), atol=2e-5)


# -- the cache --------------------------------------------------------------------

def test_the_window_storage_never_holds_more_than_its_band():
    """A sequence of 70 prompt and 40 decoded positions at a window of 16 in
    blocks of 8 holds at most ceil(16 / 8) + 1 = 3 window blocks before and
    after every tick, leaves the ones behind the window (the counter and the
    gauge say so), and reads its band from its slot's ring of 3 blocks, in
    which the band's blocks never meet."""
    cfg, model, params = build()
    eng = PagedInferenceEngine(model, params, max_slots=2, block_size=BLOCK,
                               cache_dtype=jnp.float32)
    pool, held = eng.pool, []
    advance = pool.advance_window

    def watched(seq, position):
        released = advance(seq, position)
        lo, hi = seq.window_span
        held.append(hi - lo + 1)
        assert hi == position // BLOCK
        slot = next(s for s, q in eng._seqs.items() if q is seq)
        ring = eng._wtables[slot, lo:hi + 1]
        assert len(set(ring)) == hi - lo + 1
        assert set(ring) <= set(range(1 + 3 * slot, 4 + 3 * slot))
        return released
    pool.advance_window = watched
    eng.submit(Request(request_id=0, prompt=tokens(70, 7), max_new_tokens=40,
                       eos_id=None))
    eng.run()
    assert pool.window_band == 3 and len(held) == 39 and max(held) == 3
    # the admission holds blocks 6-8 (positions 54-69); the last tick writes
    # position 108 and holds 11-13: 6-10 were left behind
    assert pool.window_released == 5 and pool.window_blocks_held == 0
    assert pool.window_data.shape == (1 + 2 * 3, 3, 2, BLOCK, 32)
    registry = eng.metrics.registry
    assert registry.get("serving_window_blocks_released_total").value(
        cache="pool0") == 5
    assert registry.get("serving_window_blocks_held").value(
        cache="pool0") == 0


def test_an_admission_writes_the_prompts_last_positions_alone():
    """A prompt of 70 positions at a window of 16, in slot 1: its window
    layers' KV goes to the blocks of positions 54-69, logical blocks 6-8 at
    their places in the slot's ring (blocks 4-6 of the storage), and no
    other block of the window storage is touched; the full layer gets all
    70."""
    cfg, model, params = build()
    eng = PagedInferenceEngine(model, params, max_slots=2, block_size=BLOCK,
                               cache_dtype=jnp.float32)
    before = np.asarray(eng.pool.window_data)
    seq = eng.pool.acquire(tokens(70, 8))
    assert seq.window_span == (6, 8)
    _, (kv, window_kv) = jax.jit(model.prefill)(
        params, jnp.asarray([tokens(70, 8) + [0] * 58], jnp.int32))
    assert eng.pool.write_window_kv(1, window_kv, 70) == 3
    after = np.asarray(eng.pool.window_data)
    touched = {b for b in range(after.shape[0])
               if not np.array_equal(after[b], before[b])}
    ring = eng.pool.window_table_row(1, 16)
    assert touched <= {4, 5, 6} and list(ring[6:9]) == [4, 5, 6]
    for j in range(6, 9):
        bid = ring[j]
        rows = slice(j * BLOCK, min((j + 1) * BLOCK, 70))
        n = rows.stop - rows.start
        np.testing.assert_array_equal(
            after[bid, :, :, :n],
            np.asarray(window_kv[:, :, 0, rows]).reshape(3, 2, n, -1))


def test_preempt_and_resume_give_the_same_tokens():
    cfg, model, params = build()
    prompts = [tokens(20, 9), tokens(12, 10)]
    _, want, _ = serve(model, params, prompts)
    eng = PagedInferenceEngine(model, params, max_slots=4, block_size=BLOCK,
                               cache_dtype=jnp.float32)
    for i, p in enumerate(prompts):
        eng.submit(Request(request_id=i, prompt=p, max_new_tokens=12,
                           eos_id=None))
    eng.step(), eng.step(), eng.step()
    assert eng.preempt() == 2 and eng.pool.window_blocks_held == 0
    got = {r.request_id: r.tokens for r in eng.run()}
    assert got == {i: r.tokens for i, r in want.items()}


def test_no_prefix_is_published_or_reused():
    """The same prompt twice: the second request recomputes (the trie would
    have to carry window blocks the window has returned) and gets the same
    tokens."""
    cfg, model, params = build()
    prompt = tokens(33, 11)
    eng, done, _ = serve(model, params, [prompt])
    eng.submit(Request(request_id="again", prompt=prompt, max_new_tokens=12,
                       eos_id=None))
    again = {r.request_id: r for r in eng.run()}["again"]
    assert not eng.pool.share_prefixes and eng.pool.prefix_hit_tokens == 0
    assert again.tokens == done[0].tokens


@pytest.mark.parametrize("entry,names", [
    ("chunked_prefill", "decode_chunk"), ("speculative", "decode_chunk"),
    ("contiguous", "decode_step"), ("int8_pool", "int8"),
    ("decode_chunk_quant", "decode_chunk_quant"),
    ("training_forward", "sliding window"), ("export_kv", "window"),
    ("adopt_kv", "window"), ("fork", "window blocks"),
    ("sequence_parallel", "sequence_parallel")])
def test_every_refused_entry_says_what_it_lacks(entry, names):
    cfg, model, params = build()
    with pytest.raises((NotImplementedError, ValueError), match=names):
        if entry == "chunked_prefill":
            PagedInferenceEngine(model, params, chunked_prefill=True)
        elif entry == "speculative":
            PagedInferenceEngine(model, params, speculative=SpeculativeConfig(
                model=model, params=params, num_tokens=2))
        elif entry == "contiguous":
            InferenceEngine(model, params)
        elif entry == "int8_pool":
            model._check_decode_supported("decode_step_paged_quant")
        elif entry == "decode_chunk_quant":
            model.decode_chunk_quant(params, None, None, None, None, None,
                                     None, None)
        elif entry == "training_forward":
            model(params, jnp.asarray([tokens(8)], jnp.int32))
        elif entry in ("export_kv", "adopt_kv"):
            eng = PagedInferenceEngine(model, params, max_slots=2,
                                       block_size=BLOCK,
                                       cache_dtype=jnp.float32)
            eng.submit(Request(request_id=0, prompt=tokens(20),
                               max_new_tokens=4, eos_id=None))
            eng.step()
            if entry == "export_kv":
                eng.export_kv(0)
            eng.adopt_kv(None)
        elif entry == "fork":
            eng = PagedInferenceEngine(model, params, max_slots=2,
                                       block_size=BLOCK,
                                       cache_dtype=jnp.float32)
            eng.pool.fork(eng.pool.acquire(tokens(20)))
        else:
            GPTConfig(**{**TINY, "sequence_parallel": True})


def test_a_plain_models_pool_and_tick_are_as_they_were():
    """No window record, no window storage: the pool is one bare array and
    the engine hands the tick that array and one table."""
    pool = PagedKVCache(5, 8, 2, 4, 16)
    assert pool.window_data is None and pool.data.shape == (5, 2, 2, 8, 64)
    plain = GPTModel(GPTConfig(vocab_size=64, hidden_size=32, num_layers=2,
                               num_attention_heads=2, max_seq_len=32))
    assert plain.window_record() is None


@pytest.mark.parametrize("program", ["prefill", "decode_step_paged"])
def test_the_parallel_block_names_attention_and_mlp(program):
    """Both programs the cell runs bill the shared norm and attention to
    ``attention``, with ``attention.window`` or ``attention.full`` nested
    in it, the experts to ``mlp``, and keep ``embeddings`` and ``lm_head``
    beside them."""
    from benchmarks.harness import span_readers as sr
    cfg, model, params = build()
    eng = PagedInferenceEngine(model, params, max_slots=2, block_size=BLOCK,
                               cache_dtype=jnp.float32)
    row = jax.ShapeDtypeStruct((2,), jnp.int32)
    tables = jnp.asarray(eng._tables)
    args = {"prefill": (jnp.asarray([tokens(16)], jnp.int32),),
            "decode_step_paged": (row, (eng.pool.data, eng.pool.window_data),
                                  (tables, tables), row)}[program]
    text = jax.jit(getattr(model, program)).lower(
        params, *args).as_text(debug_info=True)
    paths = {p for p in re.findall(r'loc\("([^"]*)"', text)
             if p.startswith(f"jit({program})/")}
    by = {}
    for p in paths:
        by.setdefault(sr.innermost_scope(
            p, sr.SCOPES + ("lm_head",)), []).append(p)
    assert {"embeddings", "attention", "mlp", "lm_head"} <= set(by)
    for part in ("window", "full"):
        assert any(f"/attention/attention.{part}/" in p
                   for p in by["attention"])
    assert any("/mlp/moe.shared/" in p for p in by["mlp"])
