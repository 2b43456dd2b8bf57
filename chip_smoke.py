#!/usr/bin/env python
"""chip_smoke.py — the quickest proof that apex-tpu still starts on the chip.

Drives the main path once at the full width of the two models the repo
benchmarks, depth uncut, weights random from a seed:

* ``kernels`` — every Pallas kernel against its jnp reference at those
  models' shapes, and proof that Mosaic (not the reference) ran;
* ``bert_train`` — BERT-large, per-leaf FusedLAMB + amp O2, micro-batch 16,
  a few steps through ``examples/bert/pretrain_bert.py``'s own ``build``;
* ``gpt_serve`` — GPT-350M behind ``InferenceEngine`` and
  ``PagedInferenceEngine``: greedy requests via ``submit``/``run``, logits
  held against the float32 reference forward;
* ``hybrid_reference[=<configuration>]`` — one chip's share of a
  ``layer_pattern`` model at the published widths and its benchmark cell's
  shapes, by the name of its file under ``benchmarks/configs/``
  (``nemotron-3-nano-30b-a3b``, the default: 9 layers, 8 192 tokens;
  ``lfm2-24b-a2b``: 14 one-mixer layers, 2 x 8 192 tokens): the O2
  program's logits, loss and gradients against the configuration's plain
  float32 reference computed layer by layer;
* ``paged_decode_timing`` / ``flash_timing`` — the serving tick's paged
  decode kernels, and one attention call's forward and backward from and
  to ``(b, s, h*d)`` rows, timed from the profiler's trace beside the
  checkout under ``scratch_chip/parent`` if there is one;
* ``four_chip_bert`` / ``four_chip_gpt`` — only where JAX reports four or
  more devices: BERT-large dp4 under ``shard_map`` and GPT-350M dp2 x tp2
  with sequence parallelism against the one-chip serial loss.

    python chip_smoke.py [phase ...]        # default: every phase that fits

One process, one chip: nothing here starts a child.  Any failed check
raises; the last stdout line of a passing run is one JSON object naming
the device as JAX reports it.  A CPU run says nothing about the chip, so
without a TPU the script exits non-zero before any phase.
"""

from __future__ import annotations

import importlib.util
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from apex_tpu.utils.platform import set_force_pallas, setup_compile_cache

_ROOT = os.path.dirname(os.path.abspath(__file__))
_MOSAIC = "tpu_custom_call"
_bf16 = jnp.bfloat16
_f32 = jnp.float32

# the two models the repo benchmarks (ROADMAP Speed cells)
BERT = dict(hidden=1024, layers=24, heads=16, seq=512, vocab=30528,
            micro_batch=16)
GPT = dict(hidden_size=1024, num_layers=24, num_attention_heads=16,
           vocab_size=50304, max_seq_len=1024)
HEAD_DIM = 64
TOKENS = 8192                      # 16 x 512 (BERT) == 8 x 1024 (GPT)
SLOTS = 8                          # serving batch: 8 x 1024 bf16 cache


# ---------------------------------------------------------------------------
# kernel parity (tests/test_on_chip.py imports KERNEL_CHECKS)
# ---------------------------------------------------------------------------

def _rel_err(got, ref):
    """Largest absolute difference over the reference's largest
    magnitude, across a pytree."""
    worst = 0.0
    for g, r in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(ref), strict=True):
        g, r = np.asarray(g, np.float32), np.asarray(r, np.float32)
        assert g.shape == r.shape, (g.shape, r.shape)
        assert np.all(np.isfinite(g)), "kernel produced non-finite values"
        worst = max(worst, float(np.abs(g - r).max()
                                 / max(np.abs(r).max(), 1e-30)))
    return worst


def _kernel_vs_reference(fn, args, tol, reorders=True, zero_rows=None):
    """Run ``fn`` on its Pallas path and on the repo's own jnp path (what
    every op dispatches to off-TPU: its ``*_reference``) and return the
    relative error.  The Pallas lowering must hold a Mosaic custom call
    and the jnp one none; where the kernel reorders a reduction the
    error must also be non-zero — 0.0 would mean the reference ran
    twice.  ``zero_rows`` (a boolean mask over the leading axis) names
    rows the kernel must leave exactly zero and the reference is free to
    fill: they are zeroed on its side before the comparison."""
    kernel = jax.jit(lambda *a: fn(*a))
    assert _MOSAIC in kernel.lower(*args).as_text(), \
        "no Mosaic custom call in the kernel path"
    got = kernel(*args)
    set_force_pallas(False)
    try:
        reference = jax.jit(lambda *a: fn(*a))
        assert _MOSAIC not in reference.lower(*args).as_text()
        ref = reference(*args)
    finally:
        set_force_pallas(None)
    if zero_rows is not None:
        assert not np.asarray(got, np.float32)[zero_rows].any(), \
            "the kernel wrote to a row it should have left zero"
        ref = jnp.where(jnp.asarray(zero_rows).reshape(
            (-1,) + (1,) * (ref.ndim - 1)), 0, ref)
    err = _rel_err(got, ref)
    assert err <= tol, f"kernel differs from its reference: {err} > {tol}"
    if reorders:
        assert err > 0.0, "bitwise equal to the reference: which path ran?"
    return err


def _randn(seed, shape, dtype, scale=1.0):
    return jnp.asarray(
        np.random.RandomState(seed).randn(*shape) * scale, dtype)


def _fwd_bwd(f):
    """``f``'s output and, for a fixed cotangent, its gradients w.r.t.
    every argument — fwd+bwd parity in one comparison."""
    def run(*a):
        out, vjp = jax.vjp(f, *a)
        ct = jax.random.normal(jax.random.PRNGKey(7), out.shape, _f32)
        return out, vjp(ct.astype(out.dtype))
    return run


def check_flash_attention(causal, b, s, heads=16, head_dim=HEAD_DIM):
    """Through the entry the models call, on ``(b, s, heads, head_dim)``:
    heads of 64 two to a 128-lane tile of the ``(b, s, h*d)`` rows, heads
    of 128 transposed to one padded head a tile."""
    from apex_tpu.ops.flash_attention import flash_attention_bshd
    q, k, v = (_randn(i, (b, s, heads, head_dim), _bf16) for i in range(3))
    return _kernel_vs_reference(
        _fwd_bwd(lambda q, k, v: flash_attention_bshd(q, k, v,
                                                      causal=causal)),
        (q, k, v), tol=4e-2)


def _cache_lens(S):
    """Eight slots: one token, mid-block, block boundaries, a full cache."""
    return jnp.asarray([1, 97, 128, 129, 511, 512, 1000, S], jnp.int32)


def check_decode():
    from apex_tpu.ops.flash_attention import flash_attention_decode
    b, S, h = SLOTS, GPT["max_seq_len"], 16
    q = _randn(0, (b, h, HEAD_DIM), _bf16)
    k = _randn(1, (b, S, h, HEAD_DIM), _bf16)
    v = _randn(2, (b, S, h, HEAD_DIM), _bf16)
    return _kernel_vs_reference(flash_attention_decode,
                                (q, k, v, _cache_lens(S)), tol=2e-2)


def check_decode_paged(block_size):
    """Sixteen slots of a 1 024-position table, three layers deep (the
    kernel finds layer 1 itself): ragged rows around the edges of a
    block and of the group of blocks one step of the kernel's loop
    brings in, a full row among rows of one block, slots that hold no
    request (an all-zero table, a stale length) between live ones, a
    row whose blocks fall through the pool and two that share its
    prefix.  Then every slot empty.  An empty row reads as zeros from
    the kernel and as garbage from the gather path."""
    from apex_tpu.ops.flash_attention import (
        _PAGED_GROUP, flash_attention_decode_paged)
    S, h = GPT["max_seq_len"], 16
    nb, group = S // block_size, _PAGED_GROUP * block_size
    lens = [1, S, 1, group - 1, group, group + 1, 777, 2 * group + 1,
            97, block_size, S - 1, 3 * group, 511, 1, 300, 2 * group]
    b = len(lens)
    rng = np.random.RandomState(3)
    q = _randn(0, (b, h, HEAD_DIM), _bf16)
    pool = _randn(1, (1 + b * nb, 3, 2, block_size, h * HEAD_DIM), _bf16)
    # every sequence's blocks scattered over the pool; block 0 is garbage
    tables = 1 + rng.permutation(b * nb).reshape(b, nb)
    tables[10] = np.sort(tables[10])[::-1]
    tables[11] = tables[10]
    tables[12, :40] = tables[10, :40]
    tables[[6, 8, 9, 13]] = 0
    lens = jnp.asarray(lens, jnp.int32)

    def check(tables, **kw):
        return _kernel_vs_reference(
            lambda q, pool, tables, lens: flash_attention_decode_paged(
                q, pool, 1, tables, lens),
            (q, pool, jnp.asarray(tables, jnp.int32), lens),
            zero_rows=tables[:, 0] == 0, **kw)

    check(np.zeros_like(tables), tol=0.0, reorders=False)
    return check(tables, tol=2e-2)


def check_layer_norm():
    from apex_tpu.ops.layer_norm import fused_layer_norm_affine
    x = _randn(0, (TOKENS, 1024), _bf16)
    w = 1.0 + _randn(1, (1024,), _f32, 0.1)
    b = _randn(2, (1024,), _f32, 0.1)
    # kernel and fallback share one row-wise math function: bit equality
    # is legitimate, the Mosaic custom call alone proves the kernel ran
    return _kernel_vs_reference(_fwd_bwd(fused_layer_norm_affine),
                                (x, w, b), tol=2e-2, reorders=False)


def check_lm_head(vocab, hidden=1024):
    from apex_tpu.ops.lm_head import fused_linear_cross_entropy
    x = _randn(0, (TOKENS, hidden), _bf16)
    w = _randn(1, (vocab, hidden), _bf16, 0.02)
    tgt = jnp.asarray(np.random.RandomState(2).randint(0, vocab, TOKENS))
    return _kernel_vs_reference(
        _fwd_bwd(lambda x, w: fused_linear_cross_entropy(x, w, tgt)),
        (x, w), tol=2e-2)


def check_fused_ffn():
    from apex_tpu.ops.fused_ffn import fused_ffn
    x = _randn(0, (TOKENS, 1024), _bf16)
    w1 = _randn(1, (4096, 1024), _bf16, 0.02)
    b1 = _randn(2, (4096,), _bf16, 0.02)
    w2 = _randn(3, (1024, 4096), _bf16, 0.02)
    b2 = _randn(4, (1024,), _bf16, 0.02)
    return _kernel_vs_reference(_fwd_bwd(fused_ffn), (x, w1, b1, w2, b2),
                                tol=3e-2)


def check_quant_gemm(out_features):
    from apex_tpu.ops.quant_gemm import quant_gemm, quantize_weight
    x = _randn(0, (SLOTS, 1024), _bf16)         # one decode row per slot
    w8, scale = quantize_weight(_randn(1, (out_features, 1024), _f32, 0.02))
    return _kernel_vs_reference(quant_gemm, (x, w8, scale), tol=2e-2)


def check_packed_adam():
    from apex_tpu.ops.multi_tensor import adam_packed
    g, p, m = (_randn(i, (16384, 128), _f32, 0.1) for i in range(3))
    v = jnp.abs(_randn(3, (16384, 128), _f32, 0.1))
    step = lambda g, p, m, v: adam_packed(      # noqa: E731
        g, p, m, v, lr=1e-3, beta1=0.9, beta2=0.999, eps=1e-8,
        weight_decay=0.01, bias_correction1=0.1, bias_correction2=0.001,
        block_rows=512)
    return _kernel_vs_reference(step, (g, p, m, v), tol=1e-4,
                                reorders=False)   # elementwise, as LN


def check_packed_lamb():
    from apex_tpu.ops.multi_tensor import (lamb_stage1_packed,
                                           lamb_stage2_packed)
    g, p, m = (_randn(i, (16384, 128), _f32, 0.1) for i in range(3))
    v = jnp.abs(_randn(3, (16384, 128), _f32, 0.1))

    def step(g, p, m, v):
        u, m2, v2, usq, psq = lamb_stage1_packed(
            g, p, m, v, beta1=0.9, beta2=0.999, eps=1e-6,
            weight_decay=0.01, bias_correction1=0.1,
            bias_correction2=0.001, block_rows=512)
        ratio = jnp.sqrt(psq) / jnp.maximum(jnp.sqrt(usq), 1e-12)
        return lamb_stage2_packed(u, p, ratio, lr=1e-3,
                                  block_rows=512), m2, v2, usq, psq
    return _kernel_vs_reference(step, (g, p, m, v), tol=1e-4,
                                reorders=False)


# name -> zero-argument check returning the relative error
KERNEL_CHECKS = {
    "flash_attention fwd+bwd d64 (BERT b16 s512)":
        lambda: check_flash_attention(False, 16, 512),
    "flash_attention fwd+bwd d64 causal (GPT b8 s1024)":
        lambda: check_flash_attention(True, 8, 1024),
    # 8 of the hybrid's 32 query heads: the reference keeps float32
    # scores of (heads, 8192, 8192) twice, 17 GB at 32 (heads are the
    # kernel's batch axis: one is like another)
    "flash_attention fwd+bwd d128 causal (hybrid b1 h8 s8192)":
        lambda: check_flash_attention(True, 1, 8192, heads=8, head_dim=128),
    "flash_attention_decode (8 slots x 1024)": check_decode,
    "flash_attention_decode_paged block 8":
        lambda: check_decode_paged(8),
    "flash_attention_decode_paged block 16":
        lambda: check_decode_paged(16),
    "fused_layer_norm_affine fwd+bwd": check_layer_norm,
    "fused_linear_cross_entropy fwd+bwd v30528":
        lambda: check_lm_head(30528),
    "fused_linear_cross_entropy fwd+bwd v50304":
        lambda: check_lm_head(50304),
    "fused_linear_cross_entropy fwd+bwd v16384 h2688 (hybrid)":
        lambda: check_lm_head(16384, hidden=2688),
    "fused_ffn fwd+bwd 8192x1024->4096": check_fused_ffn,
    "quant_gemm int8 1024->4096": lambda: check_quant_gemm(4096),
    "quant_gemm int8 1024->50304 (head)": lambda: check_quant_gemm(50304),
    "adam_packed": check_packed_adam,
    "lamb_stage1+2_packed": check_packed_lamb,
}


def phase_kernels():
    for name, check in KERNEL_CHECKS.items():
        print(f"  kernel {name}: rel_err={check():.3e}", flush=True)
    return f"{len(KERNEL_CHECKS)} kernels match their references"


# ---------------------------------------------------------------------------
# trainer
# ---------------------------------------------------------------------------

def _load(name, *relpath):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(_ROOT, *relpath))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _bert_recipe():
    return _load("pretrain_bert", "examples", "bert", "pretrain_bert.py")


def _abs_sum(tree):
    return float(jax.jit(lambda t: sum(
        jnp.sum(jnp.abs(l.astype(_f32)))
        for l in jax.tree_util.tree_leaves(t)))(tree))


def _spans(tree, n_devices):
    """Every leaf lives on ``n_devices`` devices — not all on the first."""
    for leaf in jax.tree_util.tree_leaves(tree):
        assert len(leaf.sharding.device_set) == n_devices, leaf.sharding


def _train_bert(devices, steps=3):
    recipe = _bert_recipe()
    n_dev = len(devices)
    args = recipe.parse_args([
        "--config", "large", "--opt-level", "O2",
        "--optimizer-layout", "per_leaf",
        "--batch-size", str(BERT["micro_batch"] * n_dev),
        "--seq-len", str(BERT["seq"]), "--vocab-size", str(BERT["vocab"])])
    train_step, (params, opt_state, scaler_state), make_batch, n_params = \
        recipe.build(args, devices=devices)
    _spans((params, opt_state), n_dev)
    batch = make_batch()
    lowered = train_step.lower(params, opt_state, scaler_state, *batch)
    n_mosaic = lowered.as_text().count(_MOSAIC)
    assert n_mosaic > 0, "train step lowered without a Mosaic custom call"
    train_step = lowered.compile()          # one trace for check and run
    before = _abs_sum(params)
    step0 = int(opt_state["step"])
    losses = []
    for _ in range(steps):
        params, opt_state, scaler_state, loss = train_step(
            params, opt_state, scaler_state, *batch)
        losses.append(float(loss))
        batch = make_batch()
    assert all(np.isfinite(losses)), losses
    assert len(set(losses)) == steps, f"loss did not move: {losses}"
    assert _abs_sum(params) != before, "parameters did not change"
    assert int(opt_state["step"]) == step0 + steps, \
        (step0, int(opt_state["step"]))
    _spans((params, opt_state), n_dev)
    return (f"BERT-large {n_params / 1e6:.0f}M dp={n_dev} x micro-batch "
            f"{BERT['micro_batch']} LAMB O2: {steps} steps, losses="
            f"{[round(l, 4) for l in losses]}, {n_mosaic} Mosaic calls, "
            f"state on {n_dev} device(s)")


def phase_bert_train():
    return _train_bert(jax.devices()[:1])


# ---------------------------------------------------------------------------
# the hybrid against its reference
# ---------------------------------------------------------------------------

# A position is left out of the logit comparison where, in any expert layer
# of the REFERENCE, the last chosen and the first unchosen of score + bias
# (the 6th and 7th of the hybrid's top 6, the 4th and 5th of lfm2's top 4)
# lie closer than this and one of the two is an expert held here: bf16
# activations move a score by about 1e-3, so such a position may take another
# held expert than the float32 reference did, which is a different (and
# equally valid) function.
HYBRID_SCORE_MARGIN = 5e-3
# The configurations the phase takes (``hybrid_reference=<name>``; the first
# is the default), each with its limits.  A limit lies between two readings
# on the chip where there are two (PERF.md section 6, PR 27 and PR 31): what
# the O2 program gives, and what it gives with one module's float32 taken
# away (``coarse``: the Mamba scan's decays, sums and states; the expert
# layer's router scores and accumulations).  ``probe`` is the first layer of
# that kind alone, on the reference's input: the comparison that tells the
# two apart.  For the hybrid it is a Mamba mixer at a dt near 3, where decays
# are far from 1 (0.0091 against 0.339); in the logits of the seeded model,
# whose dt is 1e-3..1e-1, they read 0.033 and 0.045 at the 99th percentile.
HYBRIDS = {
    "nemotron-3-nano-30b-a3b": dict(
        family="nemotron_h", coarse="apex_tpu.models.mamba2", probe="M",
        grads_from="*",
        logit_tol=4e-2,     # p99 over kept positions, of the logit range
        probe_tol=5e-2,     # of the probed layer's largest output
        loss_tol=1e-3,
        grad_tol=0.2,       # of a leaf's largest gradient entry
        stack_tol=0.2,      # the same, of an expert stack (w1, w2)
        grad_leaves=(
            "['layers'][5]['mixer']['qkv']['weight']",
            "['layers'][5]['mixer']['proj']['weight']",
            "['layers'][7]['mixer']['in_proj']['weight']",
            "['layers'][7]['mixer']['conv']['weight']",
            "['layers'][7]['mixer']['A_log']",
            "['layers'][7]['mixer']['dt_bias']",
            "['layers'][7]['mixer']['out_proj']['weight']",
            "['layers'][7]['norm']['weight']",
            "['layers'][8]['mixer']['router']['weight']",
            "['layers'][8]['mixer']['w1']",
            "['layers'][8]['mixer']['w2']",
            "['layers'][8]['mixer']['shared']['fc1']['weight']",
            "['lm_head']['weight']",
        )),
    # bf16 weights and activations through 14 one-mixer layers against the
    # float32 reference (readings, PR 31: logits p99 0.0176, with the expert
    # layer in bf16 0.0259; the first expert layer alone 0.0040 against
    # 0.4582; loss 2.5e-6; gradients 0.013-0.033, the router's 0.093).  The
    # tied embedding's gradient is left out (the reference's tail has the
    # head's term of it, not the lookup's).  An expert stack's gradient is a
    # sum of whole tokens' terms, about 1 000 an expert: by the sixth expert
    # layer the residual stream carries 13 layers of bf16 rounding, scores
    # move by more than the margin above at some kept positions, and each
    # token that takes another expert moves its whole term from one expert's
    # rows to another's: 0.18-0.30 of the largest entry where the hybrid's
    # four expert layers read 0.11, hence a limit of their own
    "lfm2-24b-a2b": dict(
        family="lfm2", coarse="apex_tpu.transformer.expert_parallel",
        probe="E", grads_from="D",
        logit_tol=4e-2, probe_tol=5e-2, loss_tol=1e-3, grad_tol=0.2,
        stack_tol=0.5,
        grad_leaves=(
            "['layers'][1]['mixer']['fc1']['weight']",
            "['layers'][1]['mixer']['fc2']['weight']",
            "['layers'][2]['mixer']['qkv']['weight']",
            "['layers'][2]['mixer']['q_norm']['weight']",
            "['layers'][2]['mixer']['k_norm']['weight']",
            "['layers'][2]['mixer']['proj']['weight']",
            "['layers'][3]['mixer']['router']['weight']",
            "['layers'][3]['mixer']['w1']",
            "['layers'][3]['mixer']['w2']",
            "['layers'][4]['mixer']['in_proj']['weight']",
            "['layers'][4]['mixer']['conv']['weight']",
            "['layers'][4]['mixer']['out_proj']['weight']",
            "['layers'][4]['norm']['weight']",
            "['layers'][13]['mixer']['w1']",
            "['final_layernorm']['weight']",
        )),
    # bf16 weights and activations through 8 one-mixer layers (a full layer,
    # three window layers, each with its experts) against the float32
    # reference at 16 384 positions.  The router reads the residual stream
    # before the attention layer's norm, whose rows are the embedding's
    # size (0.02), so its 64 logits spread by some 0.02 and the 6th and 7th
    # lie about 0.002 apart: ties are left out at 1e-3 of a logit, not at
    # the sigmoid routers' 5e-3 of a score.  The logits are compared on
    # every 8th position (all 37 984 of a row): whole, each side's would be
    # 2.5 GB beside the two backward passes.  The gradients are the whole
    # model's from the first layer on
    # The control is the experts' products at float8 (``coarse=None``: every
    # expert stack rounded to e4m3, 3.6 % RMS a weight): the first expert
    # layer alone reads some 5 % of its largest output with it, where bf16
    # weights and activations give lfm2's 0.4 %, hence the probe's
    # limit of 2 %; its router's logits are float32 either way, so the
    # expert module's float32 taken away moves no choice that is not a near
    # tie and would not tell the two apart
    "smallthinker-21ba3b-instruct": dict(
        family="smallthinker", coarse=None,
        probe="E", grads_from="*", score_margin=1e-3, logit_stride=8,
        logit_tol=4e-2, probe_tol=2e-2, loss_tol=1e-3, grad_tol=0.2,
        stack_tol=0.5,
        grad_leaves=(
            "['layers'][0]['mixer']['qkv']['weight']",
            "['layers'][0]['mixer']['proj']['weight']",
            "['layers'][0]['norm']['weight']",
            "['layers'][1]['mixer']['router']['weight']",
            "['layers'][1]['mixer']['w1']",
            "['layers'][1]['mixer']['w2']",
            "['layers'][1]['norm']['weight']",
            "['layers'][2]['mixer']['qkv']['weight']",
            "['layers'][2]['mixer']['proj']['weight']",
            "['layers'][6]['mixer']['qkv']['weight']",
            "['layers'][7]['mixer']['router']['weight']",
            "['layers'][7]['mixer']['w1']",
            "['layers'][7]['mixer']['w2']",
            "['final_layernorm']['weight']",
            "['lm_head']['weight']",
        )),
}


def phase_hybrid_reference(name=next(iter(HYBRIDS)), seed=0):
    """One chip's share of ``benchmarks/configs/<name>.json`` at its
    published widths and its cell's shapes: the O2 program's logits, loss
    and gradients against the configuration's float32 reference computed
    layer by layer."""
    import functools
    import importlib

    from apex_tpu import amp
    from apex_tpu.models.gpt import GPTModel

    spec = HYBRIDS[name]
    with open(os.path.join(_ROOT, "benchmarks", "configs",
                           name + ".json")) as f:
        config = json.load(f)
    recipe = _load("hybrid_recipe", config["builder"]["file"])
    ref = _load("hybrid_reference", config["reference"])
    ref_layer_fn, ref_route, ref_head = (
        getattr(ref, f"{spec['family']}_{part}")
        for part in ("layer", "route", "head"))
    margin = spec.get("score_margin", HYBRID_SCORE_MARGIN)
    stride = spec.get("logit_stride", 1)
    batch = config["micro_batch"]
    args = recipe.parse_args([a.format(global_batch=batch, seed=seed)
                              for a in config["builder"]["argv"]])
    cfg = recipe.model_config(args)
    model = GPTModel(cfg)
    # a layer's static kind (``smallthinker_kinds``: its window and rotary
    # beside its symbol); ``ahead``: an expert layer routes on the input of
    # the attention layer below it, which the loops carry as ``below``
    kinds = getattr(ref, f"{spec['family']}_kinds",
                    lambda cfg: tuple(cfg.layer_pattern))(cfg)
    ahead = cfg.moe_route_before_attention
    dev = jax.devices()[0]
    params, n_params = recipe.init_params(
        args, model, amp.initialize(model.apply, None, opt_level="O2"), dev)
    ids = np.random.RandomState(seed).randint(
        0, cfg.vocab_size, (batch, args.seq_len + 1))
    tokens, targets = jnp.asarray(ids[:, :-1]), jnp.asarray(ids[:, 1:])
    first = cfg.layer_pattern.index(spec["grads_from"])  # gradients from here
    f32 = lambda t: jax.tree_util.tree_map(        # noqa: E731
        lambda a: a.astype(_f32), t)

    # -- the reference, one layer at a time
    def layer_fn(kind, lp, x, below):
        return ref_layer_fn(kind, lp, x, cfg,
                            **({"below": below} if ahead else {}))

    @functools.partial(jax.jit, static_argnums=0)
    def ref_layer(kind, lp, x, below=None):
        with jax.default_matmul_precision("highest"):
            return layer_fn(kind, f32(lp), x, below)

    @jax.jit
    def near_tie(lp, x, below=None):
        """Positions whose last chosen and first unchosen biased scores
        are within the margin with a held expert among the two."""
        lp = f32(lp)
        lo, count = cfg.moe_held
        with jax.default_matmul_precision("highest"):
            u = below if ahead else ref._rms_norm(
                x, lp["norm"]["weight"], ref._NORM_EPS)
            biased, _, _ = ref_route(
                lp["mixer"], u.reshape(-1, u.shape[-1]), cfg)
        top, idx = jax.lax.top_k(biased, cfg.moe_top_k + 1)
        held = (idx[:, -2:] >= lo) & (idx[:, -2:] < lo + count)
        return ((top[:, -2] - top[:, -1] < margin)
                & held.any(-1)).reshape(x.shape[:2])

    # the reference's input to the layers that are looked at again (the
    # probe's, the first of the gradients' tail) and its last output: the
    # others would hold 2 GB of the chip beside both backward passes
    at = cfg.layer_pattern.index(spec["probe"])
    x = params["embedding"]["weight"].astype(_f32)[tokens]
    xs, below = {}, None
    near = jnp.zeros(tokens.shape, bool)
    near_at = {}                # per expert layer, for the probe
    for li, (kind, lp) in enumerate(zip(kinds, params["layers"])):
        if li in (at, first) or (ahead and li == at - 1):
            xs[li] = x
        if kind[0] == "E":
            near_at[li] = near_tie(lp, x, below)
            near = near | near_at[li]
        x, below = ref_layer(kind, lp, x, below), x
        assert bool(jnp.isfinite(x).all()), \
            f"the reference overflowed in layer {li}"
    xs[-1] = x

    # -- the program: the O2 forward, the step's own loss, and the gradients
    # of the loss over the positions kept
    keep = (~near).astype(_f32)
    print(f"  reference forward done: {float(near.mean()):.3f} of positions "
          "are near ties", flush=True)
    assert float(keep.sum()) > 0
    forward = jax.jit(lambda p: model(p, tokens)[:, ::stride])
    assert _MOSAIC in forward.lower(params).as_text()
    logits = forward(params)
    loss = jax.jit(lambda p: model.loss(p, tokens, targets))(params)

    def kept_loss(p):
        x, _ = model.backbone(p, model.embed(p, tokens))
        return jnp.sum(model.head_loss(p, x, targets) * keep) / keep.sum()

    grads = jax.jit(jax.grad(kept_loss))(params)

    # the first layer of the probed kind alone, on the reference's input.
    # A Mamba mixer with dt raised from the initialiser's 1e-3..1e-1 to
    # about 3: decays far from 1, as a trained layer has them and as the
    # seeded one has not.  An expert layer over the positions that are no
    # near ties in it
    probed = dict(params["layers"][at])
    probe_keep = ~near_at.get(at, jnp.zeros(tokens.shape, bool))
    if spec["probe"] == "M":
        probed["mixer"] = dict(probed["mixer"],
                               dt_bias=probed["mixer"]["dt_bias"] + 3.0)
    probe_below = xs.get(at - 1) if ahead else None
    probe_ref = ref_layer(kinds[at], probed, xs[at], probe_below)

    def probe_err(probed=probed):
        """What the layer adds (its mixer on its norm's output, before the
        residual sum rounds it to the stream's bf16), against what the
        reference's adds."""
        layer = model.layers[at]

        def mixed(lp, x, below):
            h = layer.norm(lp["norm"], x)
            if not ahead:
                return layer.kind(lp["mixer"], h)
            return layer.kind(lp["mixer"], h, layer.kind.route(
                lp["mixer"], below.astype(cfg.dtype)))
        # a new function every call: jit would hand back the first trace
        got = jax.jit(lambda lp, x, below: mixed(lp, x, below))(
            probed, xs[at].astype(cfg.dtype), probe_below)
        got = got[0] if isinstance(got, tuple) else got
        mask = probe_keep[..., None]
        return _rel_err(jnp.where(mask, got.astype(_f32), 0),
                        jnp.where(mask, probe_ref - xs[at], 0))

    tail = {"layers": params["layers"][first:],
            "final_layernorm": params["final_layernorm"],
            model.head: params[model.head]}

    def tail_loss(tail, x):
        tail = f32(tail)
        below = None
        with jax.default_matmul_precision("highest"):
            for kind, lp in zip(kinds[first:], tail["layers"]):
                x, below = jax.checkpoint(functools.partial(
                    layer_fn, kind))(lp, x, below), x
            # the head's rows a block at a time, each recomputed in the
            # backward: whole, the float32 logits and their softmax are
            # 2.5 GB each at 16 384 x 37 984
            n = x.shape[0] * x.shape[1]
            rows = max(r for r in range(1, min(n, 2048) + 1) if n % r == 0)

            @jax.checkpoint
            def kept_nll(args):
                xb, tb, kb = args
                logp = jax.nn.log_softmax(ref_head(tail, xb, cfg)[0], -1)
                return -jnp.sum(jnp.take_along_axis(
                    logp, tb[:, None], -1)[:, 0] * kb)
            per = jax.lax.map(kept_nll, (
                x.reshape(n // rows, rows, -1), targets.reshape(-1, rows),
                keep.reshape(-1, rows)))
            return jnp.sum(per) / keep.sum()

    with jax.default_matmul_precision("highest"):
        ref_loss = jax.jit(lambda t, x: ref_head(
            f32(t), x, cfg, targets)[1])(tail, xs[-1])
        ref_logits = jax.jit(lambda t, x: ref_head(
            f32(t), x[:, ::stride], cfg)[0])(tail, xs[-1])
    ref_grads = jax.jit(jax.grad(tail_loss))(tail, xs[first])

    def logit_err(got):
        """Per kept position the largest difference over the reference's
        logit range; its 99th percentile over positions and its maximum
        (the few positions where bf16 moved a score past the margin take
        another expert and sit in the last percent)."""
        keep_np = ~np.asarray(near)[:, ::stride]
        want = np.asarray(ref_logits, np.float32)[keep_np]
        per = np.abs(np.asarray(got, np.float32)[keep_np] - want).max(-1) \
            / (want.max() - want.min())
        return float(np.percentile(per, 99)), float(per.max()), \
            float(np.median(per))

    err, err_max, err_p50 = logit_err(logits)
    loss_err = abs(float(loss) / float(ref_loss) - 1)
    got = {jax.tree_util.keystr(k): v for k, v in
           jax.tree_util.tree_leaves_with_path(grads)}
    want = {jax.tree_util.keystr(k): v for k, v in
            jax.tree_util.tree_leaves_with_path(ref_grads)}
    print(f"  program loss {float(loss):.5f}, reference {float(ref_loss):.5f}",
          flush=True)
    for side, tree in (("program", got), ("reference", want)):
        bad = [k for k, v in tree.items() if not bool(jnp.isfinite(v).all())]
        assert not bad, f"non-finite {side} gradients: {bad}"
    grad_errs = {}
    for leaf in spec["grad_leaves"]:
        layer = leaf.split("]")[1].lstrip("[")
        tail_name = leaf.replace(f"[{layer}]", f"[{int(layer) - first}]", 1) \
            if leaf.startswith("['layers']") else leaf
        grad_errs[leaf] = _rel_err(got[leaf], want[tail_name])

    # -- the same program with one module's float32 taken away (or, with no
    # such module named, its experts' weights at float8), for the record and
    # for the probe's second reading
    fine_probe = probe_err()
    if spec["coarse"] is None:
        def float8(p):
            return jax.tree_util.tree_map_with_path(
                lambda k, a: _float8_bits(a) if jax.tree_util.keystr(k)
                .endswith(("['w1']", "['w2']")) else a, p)
        coarse, coarse_max, coarse_p50 = logit_err(
            jax.jit(lambda p: model(float8(p), tokens)[:, ::stride])(params))
        coarse_probe = probe_err(jax.jit(float8)(probed))
        what = "the experts' weights"
    else:
        module = importlib.import_module(spec["coarse"])
        module._f32 = _bf16
        try:
            coarse, coarse_max, coarse_p50 = logit_err(
                jax.jit(lambda p: model(p, tokens)[:, ::stride])(params))
            coarse_probe = probe_err()
        finally:
            module._f32 = _f32
        what = spec["coarse"].rsplit(".", 1)[-1]
    lowered = "float8" if spec["coarse"] is None else "bf16"
    print(f"  logits, of the range: p99 over positions {err:.4f} (median "
          f"{err_p50:.4f}, max {err_max:.4f}), {float(near.mean()):.3f} of "
          f"positions left out as near ties; with {what} in {lowered} p99 "
          f"{coarse:.4f} (median {coarse_p50:.4f}, max {coarse_max:.4f})",
          flush=True)
    print(f"  first {spec['probe']} layer alone: {fine_probe:.4f} of its "
          f"largest output; with {what} in {lowered} {coarse_probe:.4f}",
          flush=True)
    print(f"  loss: program {float(loss):.5f} reference "
          f"{float(ref_loss):.5f} ({loss_err:.2e})", flush=True)
    for leaf, e in grad_errs.items():
        print(f"  grad {leaf}: {e:.4f}", flush=True)
    assert err <= spec["logit_tol"], (err, spec["logit_tol"])
    assert fine_probe <= spec["probe_tol"], (fine_probe, spec["probe_tol"])
    assert coarse_probe > spec["probe_tol"], \
        f"{what} in {lowered} passes the probe's tolerance: {coarse_probe}"
    assert loss_err <= spec["loss_tol"], (loss_err, spec["loss_tol"])
    worst = max(grad_errs, key=grad_errs.get)
    for leaf, e in grad_errs.items():
        stack = leaf.endswith(("['w1']", "['w2']"))
        assert e <= spec["stack_tol" if stack else "grad_tol"], (leaf, e)
    return (f"{name} share {n_params / 1e6:.0f}M, {cfg.num_layers} layers x "
            f"{batch} x {args.seq_len} tokens: logits within {err:.4f} of "
            f"the range ({float(near.mean()):.3f} left out; {what} in "
            f"{lowered} "
            f"{coarse:.4f}), first {spec['probe']} layer {fine_probe:.4f} "
            f"({coarse_probe:.4f}), loss {loss_err:.1e}, worst gradient "
            f"{grad_errs[worst]:.4f} ({worst})")


# ---------------------------------------------------------------------------
# server
# ---------------------------------------------------------------------------

# bf16 activations through 24 layers against a float32 reference: the
# logits agree to a few percent of their range.  Computing in anything
# coarser than bf16 (or dropping a term) lands well outside it.
LOGIT_TOL = 4e-2


def _record_prefill_logits(engine, sink):
    """Keep the first-step logits the engine itself samples from."""
    inner = engine._prefill

    def prefill(params, tokens):
        logits, kv = inner(params, tokens)
        sink.append(np.asarray(logits[0], np.float32))
        return logits, kv
    engine._prefill = prefill


def _check_tick_picks(engine, name):
    """Hold every tick's ids to ``np.argmax`` of the logits it returns: the
    engine takes the device's pick for a greedy row and never fetches the
    row, so nothing else would see the two part."""
    attr = "_decode_paged" if name == "paged" else "_decode"
    inner = getattr(engine, attr)

    def decode(*args):
        logits, ids, *cache = inner(*args)
        want = np.argmax(np.asarray(logits), axis=-1)
        assert np.array_equal(np.asarray(ids), want), \
            f"{name}: the tick picked {np.asarray(ids)}, np.argmax {want}"
        return (logits, ids, *cache)
    setattr(engine, attr, decode)


def _serve(engine, prompts, new_tokens, ref_logits, name):
    from apex_tpu.inference import Request
    first_logits = []
    _record_prefill_logits(engine, first_logits)
    _check_tick_picks(engine, name)
    for i, (prompt, n) in enumerate(zip(prompts, new_tokens)):
        engine.submit(Request(request_id=f"{name}-{i}", prompt=prompt,
                              max_new_tokens=n))
    responses = {r.request_id: r for r in engine.run()}
    assert len(responses) == len(prompts), sorted(responses)
    worst_first = worst_gap = 0.0
    for i, (prompt, n) in enumerate(zip(prompts, new_tokens)):
        r = responses[f"{name}-{i}"]
        assert r.finish_reason != "error", (r.request_id, r.error)
        assert r.finish_reason == "length" and len(r.tokens) == n, \
            (r.request_id, r.finish_reason, len(r.tokens))
        ref = ref_logits(prompt + list(r.tokens))        # (len, vocab)
        scale = float(np.abs(ref).max())
        # first step: the logits the engine sampled from (FIFO admission,
        # so the i-th prefill is the i-th request)
        got = first_logits[i][len(prompt) - 1]
        err = float(np.abs(got - ref[len(prompt) - 1]).max()) / scale
        assert err <= LOGIT_TOL, f"{r.request_id}: first-step logits " \
            f"off the float32 reference by {err}"
        worst_first = max(worst_first, err)
        # every decoded token must be the reference's argmax up to the
        # same tolerance on both logits (tokens themselves flip on
        # rounding with random weights)
        for j, tok in enumerate(r.tokens):
            row = ref[len(prompt) - 1 + j]
            gap = float(row.max() - row[tok]) / scale
            assert gap <= 2 * LOGIT_TOL, \
                f"{r.request_id} token {j}: {gap} below the reference max"
            worst_gap = max(worst_gap, gap)
    # greedy requests: every token was the device's pick (the first the
    # arg-max of the row recorded above), no row of logits came home
    registry = engine.metrics.registry
    picked = registry.get("serving_tokens_picked_on_device_total").value()
    fetched = registry.get("serving_logit_rows_fetched_total").value()
    assert (picked, fetched) == (sum(new_tokens), 0), (picked, fetched)
    for i, prompt in enumerate(prompts):
        first = responses[f"{name}-{i}"].tokens[0]
        assert first == int(np.argmax(first_logits[i][len(prompt) - 1])), \
            f"{name}-{i}: first token {first} is not its row's arg-max"
    return worst_first, worst_gap


def phase_gpt_serve():
    from apex_tpu.inference import InferenceEngine
    from apex_tpu.models.gpt import GPTConfig, GPTModel
    from apex_tpu.models.reference import gpt_reference_logits
    from apex_tpu.serving import PagedInferenceEngine

    cfg = GPTConfig(dtype=_bf16, **GPT)
    model = GPTModel(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    rng = np.random.RandomState(0)
    # two prefill buckets (8 and 16), 16-32 new tokens each
    lengths, new_tokens = [5, 8, 11, 14, 16], [16, 20, 24, 28, 32]
    prompts = [[int(t) for t in rng.randint(0, cfg.vocab_size, n)]
               for n in lengths]

    reference = jax.jit(lambda toks: gpt_reference_logits(
        params, toks, cfg)[0])
    pad = max(lengths) + max(new_tokens)

    def ref_logits(tokens):
        toks = np.zeros((1, pad), np.int32)     # causal: padding is inert
        toks[0, :len(tokens)] = tokens
        return np.asarray(reference(jnp.asarray(toks)))[:len(tokens)]

    out = []
    for name, engine in (
            ("contiguous", InferenceEngine(
                model, params, max_slots=SLOTS, cache_dtype=_bf16)),
            ("paged", PagedInferenceEngine(
                model, params, max_slots=SLOTS, cache_dtype=_bf16))):
        store = engine.pool if name == "paged" else engine.cache
        decode = engine._decode_paged if name == "paged" else engine._decode
        tables = (jnp.asarray(engine._tables),) if name == "paged" else ()
        slots = jnp.zeros((SLOTS,), jnp.int32)
        assert _MOSAIC in decode.lower(
            params, slots, store.data, *tables, slots).as_text(), \
            f"{name} decode step lowered without a Mosaic custom call"
        first, gap = _serve(engine, prompts, new_tokens, ref_logits, name)
        out.append(f"{name}: {len(prompts)} requests, 0 errors, "
                   f"first-step logits err={first:.3e}, "
                   f"decode max-gap={gap:.3e}")
    return "GPT-350M " + "; ".join(out) + f" (tol {LOGIT_TOL})"


# ---------------------------------------------------------------------------
# the paged decode kernel, timed
# ---------------------------------------------------------------------------

def paged_fills(slots=32, max_blocks=128, block_size=8):
    """(name, tables, lengths) of the four fills the paged kernel has
    been timed at since PR 26: full tables, a busy batch, what the
    serving cell's tick holds (two requests, the other slots' tables
    zero as the engine leaves them) and one token a row."""
    rng = np.random.RandomState(0)
    busy = rng.randint(100, 501, slots).astype(np.int32)
    ones = np.ones(slots, np.int32)
    pair = ones.copy()
    pair[:2] = rng.randint(100, 301, 2)
    tables = 1 + rng.permutation(slots * max_blocks).reshape(
        slots, max_blocks)
    cell = tables.copy()
    cell[2:] = 0
    return [("every row 1024", tables, ones * max_blocks * block_size),
            ("32 rows of 100-500", tables, busy),
            ("the cell's: 2 rows of 100-300, 30 slots empty", cell, pair),
            ("every row 1", tables, ones)]


def _traced(program, args, tag, reps=5):
    """Warm ``program`` (jitted) up, run it ``reps`` times under the
    profiler and return the first chip's side of the trace and the last
    result.  Only the traced runs are in the trace."""
    from benchmarks.harness import trace
    jax.block_until_ready(program(*args))
    where = os.path.join(_ROOT, ".bench_trace", tag)
    trace.start(where)
    for _ in range(reps):
        out = program(*args)
    jax.block_until_ready(out)
    trace.stop()
    return trace.load(where, ()).devices[0], out


def paged_tick_ms(decode_paged, q, pool, tables, lens, tag, calls=24,
                  reps=5):
    """ms that ``calls`` chained calls of ``decode_paged`` (a decode
    tick's worth: one a layer) spend in their Mosaic kernels, from the
    profiler's trace, and the first call's result."""
    layers = pool.shape[1]

    @jax.jit
    def tick(q, pool, tables, lens):
        first = None
        for i in range(calls):
            o = decode_paged(q, pool, i % layers, tables, lens)
            first = o if first is None else first
            q = (q + o * 1e-3).astype(q.dtype)      # a chain: no CSE
        return first, q

    dev, out = _traced(
        tick, (q, pool, jnp.asarray(tables, jnp.int32), jnp.asarray(lens)),
        os.path.join("paged_timing", tag), reps)
    # the mean is over the events found, should the profiler drop one
    start, end = dev.ops.matching(_MOSAIC)
    assert 0.9 * reps * calls <= len(start) <= reps * calls, len(start)
    return float(np.mean(end - start)) * calls / 1e6, out[0]


def phase_paged_decode_timing():
    """The serving cell's tick (32 slots, 16 heads of 64, blocks of 8, a
    table of 128 entries, 24 calls) at :func:`paged_fills`.  Where
    ``scratch_chip/parent`` (gitignored) holds another checkout
    (``git archive <commit> | tar -x -C scratch_chip/parent``) its kernel
    runs beside this one's on the same arrays, and this one may be no
    slower at any fill.  Writes ``chiprun_out/paged_decode_timing.json``."""
    from apex_tpu.ops.flash_attention import flash_attention_decode_paged
    kernels = {"this": flash_attention_decode_paged}
    other = ("scratch_chip", "parent", "apex_tpu", "ops",
             "flash_attention.py")
    if os.path.exists(os.path.join(_ROOT, *other)):
        kernels = {"other": _load("other_flash_attention", *other)
                   .flash_attention_decode_paged, **kernels}
    pool = jax.random.normal(jax.random.PRNGKey(0),
                             (4097, 4, 2, 8, 16 * HEAD_DIM), _bf16)
    q = jax.random.normal(jax.random.PRNGKey(1), (32, 16, HEAD_DIM), _bf16)
    rows = []
    for i, (name, tables, lens) in enumerate(paged_fills()):
        row = {"fill": name, "tokens": int(lens[tables[:, 0] != 0].sum())}
        outs = {}
        for which, fn in kernels.items():
            row[which + "_ms_24_calls"], outs[which] = paged_tick_ms(
                fn, q, pool, tables, lens, f"{which}_{i}")
        if "other" in outs:
            live = tables[:, 0] != 0
            row["max_abs_diff"] = float(jnp.max(jnp.abs(
                outs["this"].astype(_f32) - outs["other"].astype(_f32))[live]))
            assert row["max_abs_diff"] < 2e-2, row
            assert row["this_ms_24_calls"] <= row["other_ms_24_calls"], row
        rows.append(row)
        print(f"  {json.dumps(row)}", flush=True)
    os.makedirs(os.path.join(_ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(_ROOT, "chiprun_out",
                           "paged_decode_timing.json"), "w") as f:
        json.dump(rows, f, indent=1)
    return "; ".join(f"{r['fill']}: {r['this_ms_24_calls']:.2f} ms"
                     for r in rows)


# ---------------------------------------------------------------------------
# flash attention forward and backward, timed where a model calls it
# ---------------------------------------------------------------------------

# (name, batch, seq, heads, head_dim, causal): the BERT step's call, a
# 512-token GPT-2 prompt's, and 8 of the hybrid's 32 heads of 128
FLASH_SHAPES = [("BERT b16 s512 h16 d64", 16, 512, 16, 64, False),
                ("GPT-2 b1 s512 h16 d64 causal", 1, 512, 16, 64, True),
                ("hybrid b1 s8192 h8 d128 causal", 1, 8192, 8, 128, True)]


def flash_rows_ms(flash, heads_major, b, s, h, d, causal, tag, reps=5):
    """ms a forward and a backward of ``flash`` take on the chip, FROM and
    TO the ``(b, s, h*d)`` rows that the QKV matmul writes and the output
    projection reads: the whole program (kernels and whatever relayouts
    the entry needs) and its Mosaic kernels alone, per call, from the
    profiler's trace; and the results.  ``heads_major``: ``flash`` takes
    ``(b, h, s, d)``, else ``(b, s, h, d)``."""
    def rows_in(t):
        t = t.reshape(b, s, h, d)
        return t.transpose(0, 2, 1, 3) if heads_major else t

    def attend(q, k, v):
        o = flash(rows_in(q), rows_in(k), rows_in(v), causal=causal)
        return (o.transpose(0, 2, 1, 3) if heads_major else o).reshape(
            b, s, h * d)

    @jax.jit
    def program(q, k, v, ct):
        out, vjp = jax.vjp(attend, q, k, v)
        return out, vjp(ct)

    args = tuple(_randn(i, (b, s, h * d), _bf16) for i in range(4))
    dev, out = _traced(program, args, os.path.join("flash_timing", tag),
                       reps)
    whole = dev.modules.matching("jit_program")
    kernels = dev.ops.matching(_MOSAIC)
    assert len(whole[0]) == reps and len(kernels[0]) == 3 * reps, \
        (len(whole[0]), len(kernels[0]))
    return (float(np.sum(whole[1] - whole[0])) / reps / 1e6,
            float(np.sum(kernels[1] - kernels[0])) / reps / 1e6, out)


def phase_flash_timing():
    """One attention call's forward and backward at :data:`FLASH_SHAPES`,
    from and to ``(b, s, h*d)`` rows.  Where ``scratch_chip/parent``
    (gitignored) holds another checkout (``git archive <commit> | tar -x
    -C scratch_chip/parent``) its entry runs beside this one's on the same
    arrays (the one over ``(b, s, h, d)`` if it has one, else
    ``flash_attention`` between the transposes its models made), and this
    tree's program may take no more than 1.02 of its time at any shape
    (at heads of 128 both run the same kernels: two runs of one program
    differ by that little).  Writes ``chiprun_out/flash_timing.json``."""
    from apex_tpu.ops.flash_attention import flash_attention_bshd
    entries = {"this": (flash_attention_bshd, False)}
    other = ("scratch_chip", "parent", "apex_tpu", "ops",
             "flash_attention.py")
    if os.path.exists(os.path.join(_ROOT, *other)):
        module = _load("other_flash_attention", *other)
        rows = getattr(module, "flash_attention_bshd", None)
        entries = {"other": (rows or module.flash_attention, rows is None),
                   **entries}
    table = []
    for i, (name, *shape) in enumerate(FLASH_SHAPES):
        row, outs = {"shape": name}, {}
        for which, (flash, heads_major) in entries.items():
            row[which + "_ms"], row[which + "_kernels_ms"], outs[which] = \
                flash_rows_ms(flash, heads_major, *shape, f"{which}_{i}")
        if "other" in outs:
            row["rel_diff"] = _rel_err(outs["this"], outs["other"])
            assert row["rel_diff"] < 2e-2, row
            assert row["this_ms"] <= 1.02 * row["other_ms"], row
        table.append(row)
        print(f"  {json.dumps(row)}", flush=True)
    os.makedirs(os.path.join(_ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(_ROOT, "chiprun_out", "flash_timing.json"),
              "w") as f:
        json.dump(table, f, indent=1)
    return "; ".join(f"{r['shape']}: {r['this_ms']:.3f} ms"
                     f" ({r['this_kernels_ms']:.3f} in kernels)"
                     for r in table)


# ---------------------------------------------------------------------------
# four chips
# ---------------------------------------------------------------------------


# -- a served layer_pattern model held to its float32 reference ----------------

def _served(name):
    """``(config, cell's mix, cfg, model)`` of ``benchmarks/configs/<name>
    .json`` as the benchmark builds them."""
    from apex_tpu.models.gpt import GPTConfig, GPTModel
    with open(os.path.join(_ROOT, "BENCHMARK.json")) as f:
        cell = next(w for w in json.load(f)["workloads"]
                    if w["config"] == name)
    with open(os.path.join(_ROOT, "benchmarks", "configs",
                           name + ".json")) as f:
        config = json.load(f)
    with open(os.path.join(_ROOT, "benchmarks", "traffic",
                           cell["traffic"] + ".json")) as f:
        mix = json.load(f)
    kw = {k: getattr(jnp, v) if v in ("bfloat16", "float32") else v
          for k, v in config["model"].items()}
    cfg = GPTConfig(**kw)
    return config, mix, cfg, GPTModel(cfg)


def _float8_bits(a):
    """A bf16 matrix rounded to float8's three bits of mantissa (e4m3; to
    nearest, on the bits: a ``convert`` to ``float8_e4m3fn`` and back left
    the weights as they were on the chip); anything else as it is."""
    if a.dtype != jnp.bfloat16 or a.ndim < 2:
        return a
    bits = jax.lax.bitcast_convert_type(a.astype(jnp.float32), jnp.uint32)
    bits = (bits + jnp.uint32(1 << 19)) & jnp.uint32(0xFFF00000)
    return jax.lax.bitcast_convert_type(bits, jnp.float32).astype(a.dtype)


def _float8_weights(params):
    """Every bf16 matrix at float8 (:func:`_float8_bits`), the next
    precision under the one the configuration states."""
    # donated: the engine's copy takes the place of the one it came from
    return jax.jit(lambda p: jax.tree.map(_float8_bits, p),
                   donate_argnums=0)(params)


def _selection_differs(model, cfg, ref_mod, params, toks):
    """Selected positions a row that the program's first indexer and the
    reference's do not share, from the same layer input: ``(rows,)``."""
    from apex_tpu.models.latent import _rms_norm
    layer = model.layers[0]

    def both(params, toks):
        lp = params["layers"][0]
        x = model.embed(params, toks)
        h32 = _rms_norm(x.astype(jnp.float32), lp["norm"]["weight"],
                        model.cfg.norm_eps)
        got = layer.kind._selection_mask(*layer.kind._index(
            lp["mixer"], h32, jnp.arange(toks.shape[1]))[1:])
        with jax.default_matmul_precision("highest"):
            x32 = params["embedding"]["weight"][toks].astype(jnp.float32)
            u = ref_mod._rms_norm(x32, lp["norm"])
            c32 = ref_mod._rms_norm(u @ ref_mod._w(lp["mixer"]["q_a"]).T,
                                    lp["mixer"]["q_norm"])
            want = ref_mod.selection(lp["mixer"], u, c32, cfg)
        return jnp.sum(got ^ want, -1)[0] // 2
    return np.asarray(jax.jit(both)(params, toks))


def phase_serve_reference(name="glm-5.2", seeds="0", control="",
                          new_tokens=""):
    """The benchmark's own check of a served configuration, alone and for
    one seed after another in one process: the cell's ``check_prompts``
    through the engine (prefill, then ``check_new_tokens`` paged ticks, or
    ``new_tokens``) against the configuration's float32 reference, by
    ``harness/serve.py``'s ``compare`` and ``counts_notes``; every number
    beside its limit.  ``seeds``: ``a-b`` or a comma list.  ``control``:
    ``fp8`` gives the engine its weights through float8 (the reference keeps
    them: must read NOT correct); ``sel`` also counts the selected positions
    the program's first indexer and the reference's do not share at the
    longest check prompt.  A model with window layers also reports, over
    every tick, the largest band a sequence held and the largest reading of
    the gauge ``serving_window_blocks_held``, and fails if a band passes
    ``ceil(window / block_size) + 1`` blocks."""
    sys.path.insert(0, _ROOT)
    from apex_tpu.inference import Request
    from apex_tpu.serving import PagedInferenceEngine
    from benchmarks.harness import serve, traffic
    from benchmarks.harness.job import load_module

    config, mix, cfg, model = _served(name)
    ref_mod = load_module(_ROOT, config["reference"], "bench_ref")
    margin, min_compared = serve.check_limits(config)
    reference, near_ties = serve.reference_of(ref_mod, cfg, margin)
    init = jax.jit(model.init_params)
    ekw = dict(config["engine"])
    ekw["cache_dtype"] = getattr(jnp, ekw["cache_dtype"])
    if "-" in seeds:
        lo, hi = (int(s) for s in seeds.split("-"))
        seeds = list(range(lo, hi + 1))
    else:
        seeds = [int(s) for s in seeds.split(",")]
    engine, rows = None, []
    new = int(new_tokens) if new_tokens else mix["check_new_tokens"]
    for seed in seeds:
        t0 = time.perf_counter()
        # the engine first, on what it is served; then the reference on the
        # seed's own weights, made again (both copies do not fit the chip)
        served = init(jax.random.PRNGKey(seed))
        if "fp8" in control:
            served = _float8_weights(served)
        if engine is None:
            engine = PagedInferenceEngine(model, served, **ekw)
        engine.params = served
        pad = engine._bucket(max(mix["check_prompts"]) + new)
        prompts = {f"check-{n}": traffic.tokens(seed + 1, i, n,
                                                cfg.vocab_size)
                   for i, n in enumerate(mix["check_prompts"])}
        for rid, prompt in prompts.items():
            engine.submit(Request(request_id=rid, prompt=prompt,
                                  max_new_tokens=new, eos_id=None))
        band = gauge = 0
        while engine._queue or engine._active:
            engine.step()
            if engine.pool.window is not None:
                band = max([band] + [s.window_span[1] - s.window_span[0] + 1
                                     for s in engine._seqs.values()])
                gauge = max(gauge, engine.metrics.registry.get(
                    "serving_window_blocks_held").value(cache="pool0"))
        done = {r.request_id: r for r in engine.completed}
        engine.pool.flush_prefixes()
        worst = first = compared = left_out = 0
        notes, differs, firsts = [], None, {}
        for rid, prompt in prompts.items():
            r = done[rid]
            assert r.finish_reason == "length" and len(r.tokens) == new, \
                (rid, r.finish_reason, r.error)
            ptoks = np.zeros((1, engine._bucket(len(prompt))), np.int32)
            ptoks[0, :len(prompt)] = prompt
            logits, _ = engine._prefill(served, jnp.asarray(ptoks))
            firsts[rid] = np.asarray(logits[0, len(prompt) - 1], np.float32)
        if "fp8" in control:
            engine.params = None
            del served
            served = init(jax.random.PRNGKey(seed))
        params = served
        for rid, prompt in prompts.items():
            r, got = done[rid], firsts[rid]
            toks = np.zeros((1, pad), np.int32)
            full = prompt + list(r.tokens)
            toks[0, :len(full)] = full
            ref = np.asarray(reference(params, jnp.asarray(toks)))
            mask = np.zeros(pad, bool) if near_ties is None else np.asarray(
                near_ties(params, jnp.asarray(toks)))
            c = serve.compare(ref, got, len(prompt), r.tokens, mask,
                              serve.LOGIT_TOL)
            worst = max(worst, c.err or 0.0, c.gap / 2)
            first += c.err is not None
            compared += c.compared
            left_out += c.left_out
            print(f"  seed {seed} {rid}: first-step err "
                  f"{'left out' if c.err is None else f'{c.err:.4f}'} "
                  f"(limit {serve.LOGIT_TOL}), worst decoded gap {c.gap:.4f} "
                  f"(limit {2 * serve.LOGIT_TOL}), compared {c.compared} "
                  f"left out {c.left_out}, ok {c.ok}", flush=True)
            if not c.ok:
                notes.append(rid)
            if "sel" in control and len(prompt) == max(mix["check_prompts"]):
                ptoks = np.zeros((1, engine._bucket(len(prompt))), np.int32)
                ptoks[0, :len(prompt)] = prompt
                d = _selection_differs(model, cfg, ref_mod, params,
                                       jnp.asarray(ptoks))[:len(prompt)]
                differs = {"last_row": int(d[-1]), "mean": float(d.mean()),
                           "max": int(d.max())}
        checked = new * len(prompts)
        notes += serve.counts_notes(compared, left_out, checked,
                                    checked if min_compared is None
                                    else min_compared)
        rows.append({"seed": seed, "correct": not notes,
                     "worst_logit_err": round(worst, 4),
                     "compared": f"{first}+{compared}",
                     "left_out": left_out, "selection_differs": differs,
                     "seconds": round(time.perf_counter() - t0, 1)})
        if engine.pool.window is not None:
            rows[-1].update(band_max=band, band_limit=engine.pool.window_band,
                            window_blocks_held_max=gauge,
                            window_blocks_released=engine.pool.window_released)
            assert band <= engine.pool.window_band, rows[-1]
        print(f"  {json.dumps(rows[-1])}", flush=True)
        engine.params = None
        del params, served
    stats = jax.devices()[0].memory_stats() or {}
    out = {"configuration": name, "control": control, "margin": margin,
           "min_compared": min_compared, "token_bytes": engine.pool.token_bytes,
           "rows": rows, "peak_bytes": stats.get("peak_bytes_in_use", 0)}
    os.makedirs(os.path.join(_ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(_ROOT, "chiprun_out",
                           f"serve_reference_{name}_{control or 'plain'}_"
                           f"{seeds[0]}-{seeds[-1]}.json"), "w") as f:
        json.dump(out, f, indent=1)
    bad = [r["seed"] for r in rows if not r["correct"]]
    if "fp8" in control:
        assert len(bad) == len(rows), \
            f"the float8 control read correct on seeds {sorted(set(seeds) - set(bad))}"
    else:
        assert not bad, f"not correct on seeds {bad}"
    return (f"{len(rows)} seeds, worst_logit_err "
            f"{max(r['worst_logit_err'] for r in rows)}, left_out at most "
            f"{max(r['left_out'] for r in rows)} of {new * len(prompts)}"
            + (" (all NOT correct, as a control must)" if bad else ""))


def _roofline(name, args, seconds):
    """Percent of its roofline (``benchmarks/configs/glm-5.2.flops.py``,
    ``harness/peaks.py``) that ``seconds`` is."""
    sys.path.insert(0, _ROOT)
    from benchmarks.harness import flops, peaks
    from benchmarks.harness.job import load_module
    counts = load_module(_ROOT, "benchmarks/configs/glm-5.2.flops.py",
                         "glm_flops")
    ops, nbytes = getattr(counts, name)(**args)
    peak = peaks.peaks(jax.devices()[0].device_kind)
    return flops.roofline_share(ops, nbytes, seconds, peak), ops, nbytes


def _timed(program, args, reps=20):
    jax.block_until_ready(program(*args))
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(program(*args))
        times.append(time.perf_counter() - t0)
    return float(np.median(times))


def _tick_shapes(name, context):
    """The cell's pools, tables and positions with every slot at
    ``context`` cached positions."""
    config, _, cfg, model = _served(name)
    slots, bs = config["engine"]["max_slots"], config["engine"]["block_size"]
    blocks = cfg.max_seq_len // bs
    pools = [_randn(7 + i, (1 + slots * blocks, *spec[:2], bs, spec[2]),
                    *spec[3:] or (jnp.bfloat16,))
             for i, spec in enumerate(model.cache_record())]
    tables = jnp.asarray(1 + np.arange(slots * blocks).reshape(slots, blocks),
                         jnp.int32)
    return cfg, pools, tables, jnp.full((slots,), context, jnp.int32)


def phase_sparse_attention_timing(name="glm-5.2", calls=10):
    """The tick's sparse attention alone at the cell's shapes (8 rows,
    ``index_topk`` records each of 64 heads): ``calls`` calls chained in one
    program, each on the last's output, as a share of its roofline."""
    from apex_tpu.ops.latent_attention import sparse_decode_attention
    context = 13777
    cfg, pools, tables, lens = _tick_shapes(name, context)
    slots, k = tables.shape[0], cfg.index_topk
    q = _randn(1, (slots, cfg.num_attention_heads, pools[0].shape[-1]),
               jnp.bfloat16)
    idx = jnp.asarray(np.stack([np.random.RandomState(s).permutation(
        context)[:k] for s in range(slots)]), jnp.int32)
    valid = jnp.ones((slots, k), bool)

    @jax.jit
    def program(q, pool):
        def body(i, q):
            o = sparse_decode_attention(q, pool, i % pool.shape[1], tables,
                                        idx, valid, scale=1 / 16.0,
                                        v_width=cfg.kv_lora_rank)
            return q.at[..., :cfg.kv_lora_rank].add(1e-3 * o)
        return jax.lax.fori_loop(0, calls, body, q)
    seconds = _timed(program, (q, pools[0])) / calls
    share, ops, nbytes = _roofline("sparse_attention", dict(
        heads=cfg.num_attention_heads, kv_lora_rank=cfg.kv_lora_rank,
        qk_rope_head_dim=cfg.qk_rope_head_dim, selected_tokens=slots * k),
        seconds)
    return (f"{seconds * 1e3:.3f} ms a call of {slots} x {k} records, "
            f"{ops / 1e9:.2f} GFLOP {nbytes / 1e6:.1f} MB: "
            f"sparse_attention_roofline {share:.1f} %")


def phase_indexer_timing(name="glm-5.2", calls=10):
    """The tick's indexer alone at the cell's shapes (8 rows of 13 777
    cached positions, tables of 16 384): the scoring of every cached key,
    and the exact top-k, each as a share of its roofline."""
    from apex_tpu.ops.latent_attention import (gather_index_keys,
                                               index_scores, topk_positions)
    context = 13777
    cfg, pools, tables, lens = _tick_shapes(name, context)
    slots = tables.shape[0]
    q = _randn(2, (slots, 1, cfg.index_n_heads, cfg.index_head_dim),
               jnp.float32)
    w = _randn(3, (slots, 1, cfg.index_n_heads), jnp.float32)

    @jax.jit
    def score(q, pool):
        def body(i, q):
            s = index_scores(q, w, gather_index_keys(
                pool, i % pool.shape[1], tables))
            return q + (1e-6 * jnp.mean(s)).astype(q.dtype)
        return jax.lax.fori_loop(0, calls, body, q)

    @jax.jit
    def select(scores):
        def body(i, carry):
            scores, acc = carry
            idx, _ = topk_positions(scores, lens, cfg.index_topk)
            return scores + 1e-6 * idx[:, :1], acc + idx[:, 0]
        return jax.lax.fori_loop(0, calls, body,
                                 (scores, jnp.zeros((slots,), jnp.int32)))
    t_score = _timed(score, (q, pools[1])) / calls
    scores = _randn(4, (slots, tables.shape[1] * pools[1].shape[3]),
                    jnp.float32)
    t_top = _timed(select, (scores,)) / calls
    s_share, _, _ = _roofline("indexer_scores", dict(
        index_n_heads=cfg.index_n_heads, index_head_dim=cfg.index_head_dim,
        context_tokens=slots * context), t_score)
    t_share, _, _ = _roofline("topk_select", dict(
        context_tokens=slots * context), t_top)
    return (f"scoring {t_score * 1e3:.3f} ms a call of {slots} x {context} "
            f"keys: indexer_scores_roofline {s_share:.1f} %; exact top-"
            f"{cfg.index_topk} {t_top * 1e3:.3f} ms: topk_select_roofline "
            f"{t_share:.2f} %")


def masked_flash_counts(heads, positions, qk_head_dim, v_head_dim,
                        itemsize=2):
    """(operations, bytes) of a prefill's attention under a causal selection
    for ``heads`` heads of one sequence: the causal half of ``2 s s (d +
    dv)`` a head, a multiply and an add each; q, k, v and the output once,
    the mask's ``s x s`` bytes once.  Recomputed operations do not count.
    (For ``benchmarks/configs/glm-5.2.flops.py``, with the reader that takes
    a prefill's counts from the traced admissions: the next ``benchmark``
    PR's, ``ROADMAP.md`` W12.)"""
    s = positions
    return (heads * s * s * (qk_head_dim + v_head_dim),
            heads * s * 2 * (qk_head_dim + v_head_dim) * itemsize + s * s)


def phase_masked_flash_timing(name="glm-5.2", calls=4):
    """A prefill's masked attention alone, one layer's group of heads at
    buckets 8 192 and 16 384 under a random causal top-``index_topk``
    selection: ``calls`` calls chained in one program, each on the last's
    output; the kernel against the ``jax.numpy`` loop it replaced at the
    same shapes, as milliseconds a call and as a share of the roofline."""
    from apex_tpu.models.latent import _PREFILL_HEADS
    from apex_tpu.ops.latent_attention import masked_attention, topk_mask
    from apex_tpu.utils.platform import set_force_pallas
    sys.path.insert(0, _ROOT)
    from benchmarks.harness import flops, peaks
    _, _, cfg, _ = _served(name)
    h, d, dv = (_PREFILL_HEADS, cfg.qk_nope_head_dim + cfg.qk_rope_head_dim,
                cfg.v_head_dim)
    peak = peaks.peaks(jax.devices()[0].device_kind)

    @jax.jit
    def selection(scores):
        s = scores.shape[-1]

        def block(r0):
            causal = (r0 + jnp.arange(128))[:, None] >= jnp.arange(s)
            return topk_mask(jax.lax.dynamic_slice_in_dim(scores, r0, 128, 1),
                             causal, cfg.index_topk)
        mask = jax.lax.map(block, jnp.arange(0, s, 128))
        return mask.transpose(1, 0, 2, 3).reshape(1, s, s)

    def program(q, k, v, mask):
        def body(_, q):
            o = masked_attention(q, k, v, mask, 1 / 16.0)
            return q + 1e-3 * o.reshape(1, -1, h, dv).transpose(0, 2, 1, 3)
        return jax.lax.fori_loop(0, calls, body, q)

    out = []
    for s in (8192, 16384):
        keys = jax.random.split(jax.random.PRNGKey(s), 4)
        q, k, v = (jax.random.normal(key, (1, h, s, w), jnp.bfloat16)
                   for key, w in zip(keys, (d, d, dv)))
        mask = selection(jax.random.normal(keys[3], (1, s, s)))
        ops, nbytes = masked_flash_counts(h, s, d, dv)
        ms, got = {}, {}
        for which, force in (("kernel", None), ("loop", False)):
            # the loop: what ``masked_attention`` is off the TPU.  A fresh
            # function each time: ``jax.jit`` remembers a function's trace,
            # and what ``masked_attention`` picks is not among its arguments
            set_force_pallas(force)
            try:
                run = jax.jit(lambda *a: program(*a))
                got[which] = np.asarray(run(q, k, v, mask), np.float32)
                ms[which] = _timed(run, (q, k, v, mask), reps=5) / calls
            finally:
                set_force_pallas(None)
        err = np.abs(got["kernel"] - got["loop"]).max() \
            / np.abs(got["loop"]).max()
        assert err < 2e-2, f"kernel and loop {err:.4f} apart at {s}"
        out.append(
            f"{h} heads x {s}: {ops / 1e12:.2f} TFLOP {nbytes / 1e6:.0f} MB, "
            + ", ".join(f"{which} {t * 1e3:.2f} ms masked_flash_roofline "
                        f"{flops.roofline_share(ops, nbytes, t, peak):.1f} %"
                        for which, t in ms.items())
            + f", {err:.1e} apart")
    return "; ".join(out)


def _counts(name):
    sys.path.insert(0, _ROOT)
    from benchmarks.harness.job import load_module
    return load_module(_ROOT, f"benchmarks/configs/{name}.flops.py",
                       name.replace("-", "_") + "_flops")


def phase_window_flash_timing(name="command-a-plus-05-2026", calls=4):
    """A prefill's grouped attention alone at the cell's widths (one layer,
    rows of 128 query heads and 8 KV heads of 128), window and full, at
    buckets 8 192 and 16 384: ``calls`` calls of the Pallas kernel
    ``window_flash`` chained in one program, each on the last's output, as
    milliseconds a call and as a share of the roofline
    (``benchmarks/configs/<name>.flops.py``); first, on 16 heads of one KV
    head at 4 096 positions, the kernel against the materialised
    reference."""
    from apex_tpu.ops.flash_attention import (grouped_attention,
                                              grouped_attention_reference)
    sys.path.insert(0, _ROOT)
    from benchmarks.harness import flops, peaks
    _, _, cfg, _ = _served(name)
    h, kv, d, w = (cfg.num_attention_heads, cfg.num_kv_heads, cfg.head_dim,
                   cfg.sliding_window)
    counts, peak = _counts(name), peaks.peaks(jax.devices()[0].device_kind)
    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    q, k, v = (jax.random.normal(key, (1, 4096, n * d), _bf16)
               for key, n in zip(keys, (16, 1, 1)))
    for window in (1024, None):
        got = jax.jit(lambda q, k, v: grouped_attention(q, k, v, 16, window))(
            q, k, v)
        want = grouped_attention_reference(q, k, v, 16, window)
        err = float(jnp.abs(got.astype(_f32) - want.astype(_f32)).max()
                    / jnp.abs(want.astype(_f32)).max())
        assert err < 2e-2, f"kernel {err:.4f} off the reference, window " \
            f"{window}"
    out = [f"16 heads x 4096 within {err:.1e} of the reference"]
    for s in (8192, 16384):
        q, k, v = (jax.random.normal(key, (1, s, n * d), _bf16)
                   for key, n in zip(keys, (h, kv, kv)))
        for window in (w, None):
            def program(q, k, v, window=window):
                def body(_, q):
                    return q + 1e-3 * grouped_attention(q, k, v, h, window)
                return jax.lax.fori_loop(0, calls, body, q)
            t = _timed(jax.jit(program), (q, k, v), reps=5) / calls
            ops, nbytes = counts.window_flash(h, kv, d, s, window)
            out.append(f"{'window' if window else 'full'} {s}: {t * 1e3:.2f} "
                       f"ms, {ops / 1e12:.2f} TFLOP, window_flash_roofline "
                       f"{flops.roofline_share(ops, nbytes, t, peak):.1f} %")
    return "; ".join(out)


def phase_grouped_decode_timing(name="command-a-plus-05-2026", calls=8):
    """The tick's grouped attention alone at the cell's shapes (16 slots,
    128 query heads over 8 KV heads of 128, blocks of 64): a window layer's
    kernel over the window storage and the full layer's over the pool, with
    every slot at ``context`` cached positions, ``calls`` calls chained in
    one program; milliseconds a call and the share of the roofline (K and
    V bytes read: ``min(context, window)`` positions a row on a window
    layer, ``context`` on the full layer), after a parity check against the
    gathered reference at the same shapes."""
    from apex_tpu.ops.flash_attention import (flash_attention_decode_paged,
                                              grouped_decode_reference)
    sys.path.insert(0, _ROOT)
    from benchmarks.harness import flops, peaks
    config, _, cfg, model = _served(name)
    slots, bs = config["engine"]["max_slots"], config["engine"]["block_size"]
    h, kv, d, w = (cfg.num_attention_heads, cfg.num_kv_heads, cfg.head_dim,
                   cfg.sliding_window)
    blocks = cfg.max_seq_len // bs
    band = -(-w // bs) + 1
    counts, peak = _counts(name), peaks.peaks(jax.devices()[0].device_kind)
    (full_layers, _, width), = model.cache_record()
    window_layers = model.window_record()[0]
    pool = _randn(1, (1 + slots * blocks, full_layers, 2, bs, width), _bf16)
    wpool = _randn(2, (1 + slots * band, window_layers, 2, bs, width), _bf16)
    q = _randn(3, (slots, h, d), _bf16)
    out = []
    for context in (2381, 8144, 15060):
        lens = jnp.full((slots,), context, jnp.int32)
        tables = 1 + np.arange(slots * blocks).reshape(slots, blocks)
        wtables = np.zeros((slots, blocks), np.int32)
        lo, hi = max(context - w, 0) // bs, (context - 1) // bs
        for i in range(slots):
            wtables[i, lo:hi + 1] = 1 + i * band + np.arange(hi - lo + 1)
        ms = {}
        for kind, p, t, win in (("window", wpool, wtables, w),
                                ("full", pool, tables, None)):
            t = jnp.asarray(t, jnp.int32)
            got = jax.jit(lambda q, p, win=win, t=t: flash_attention_decode_paged(
                q, p, 0, t, lens, window=win))(q, p)
            want = grouped_decode_reference(q, p, 0, t, lens, win)
            err = float(jnp.abs(got.astype(_f32) - want.astype(_f32)).max()
                        / jnp.abs(want.astype(_f32)).max())
            assert err < 2e-2, f"{kind} {context}: {err:.4f} off"

            def program(q, p, win=win, t=t):
                def body(i, q):
                    return q + 1e-3 * flash_attention_decode_paged(
                        q, p, i % p.shape[1], t, lens, window=win)
                return jax.lax.fori_loop(0, calls, body, q)
            secs = _timed(jax.jit(program), (q, p), reps=10) / calls
            read = min(context, win or context)
            ops, nbytes = counts.grouped_decode(h, kv, d, slots * read)
            ms[kind] = (f"{kind} {secs * 1e3:.3f} ms grouped_decode_roofline "
                        f"{flops.roofline_share(ops, nbytes, secs, peak):.1f}"
                        " %")
        out.append(f"{slots} x {context}: " + ", ".join(ms.values()))
    return "; ".join(out)


def phase_band_flash_timing(name="smallthinker-21ba3b-instruct", calls=4):
    """The training step's grouped attention alone at the cell's widths (one
    layer, rows of 28 query heads over 4 KV heads of 128, one sequence of
    16 384), with the window and without: first, at 4 096 positions, the
    forward and its three gradients against the materialised reference's
    (autodiff); then ``calls`` calls of the forward (``band_flash_fwd`` or
    ``grouped_flash_fwd``) and of the backward's two (``*_dq``, ``*_dkv``)
    chained in one program, as milliseconds a call and as shares of the
    roofline (``benchmarks/configs/<name>.flops.py``), and the window's
    time over the full layer's."""
    from apex_tpu.ops import flash_attention as fa
    sys.path.insert(0, _ROOT)
    from benchmarks.harness import flops, peaks
    with open(os.path.join(_ROOT, "benchmarks", "configs",
                           name + ".json")) as f:
        config = json.load(f)
    h, kv, d = (config["num_attention_heads"], config["num_key_value_heads"],
                config["head_dim"])
    w, s = config["sliding_window_size"], config["seq_len"]
    counts, peak = _counts(name), peaks.peaks(jax.devices()[0].device_kind)
    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    out = []
    for window in (1024, None):
        q, k, v = (jax.random.normal(key, (1, 4096, n * d), _bf16)
                   for key, n in zip(keys, (h, kv, kv)))
        ct = jax.random.normal(keys[3], q.shape, _bf16)

        def grads(f, *args):
            o, vjp = jax.vjp(f, *args)
            return (o,) + vjp(ct.astype(o.dtype))
        got = jax.jit(lambda: grads(
            lambda q, k, v: fa.grouped_flash(q, k, v, h, window), q, k, v))()
        want = jax.jit(lambda: grads(
            lambda q, k, v: fa.grouped_attention_reference(q, k, v, h, window),
            *(a.astype(_f32) for a in (q, k, v))))()
        errs = [_rel_err(a.astype(_f32), b) for a, b in zip(got, want)]
        assert max(errs) < 2e-2, f"window {window}: {errs}"
        out.append(f"window {window} at 4096: o, dq, dk, dv within "
                   + ", ".join(f"{e:.1e}" for e in errs))
    q, k, v, do = (jax.random.normal(key, (1, s, n * d), _bf16)
                   for key, n in zip(keys, (h, kv, kv, h)))
    ms = {}
    for window in (w, None):
        static = dict(heads=h, window=window, scale=d ** -0.5,
                      block_q=fa._BAND_BLOCK, block_k=fa._BAND_BLOCK,
                      interpret=False)
        windowed = window is not None
        fwd = fa._BAND_CALLS[windowed, "fwd"]
        dq_call, dkv_call = (fa._BAND_CALLS[windowed, part]
                             for part in ("dq", "dkv"))
        o, lse = jax.jit(lambda q, k, v: fwd(q, k, v, **static))(q, k, v)
        delta = jnp.sum((do.astype(_f32) * o.astype(_f32)).reshape(
            1, s, h, d), -1).transpose(0, 2, 1)[..., None]

        def forward(q, k, v):
            def body(_, q):
                return q + 1e-3 * fwd(q, k, v, **static)[0]
            return jax.lax.fori_loop(0, calls, body, q)

        def backward(q, k, v, do, lse, delta):
            def body(_, q):
                dq = dq_call(q, k, v, do, lse, delta, **static)
                dk, dv = dkv_call(q, k, v, do, lse, delta, **static)
                return q + 1e-3 * dq + (1e-6 * (jnp.sum(dk) + jnp.sum(dv))
                                        ).astype(q.dtype)
            return jax.lax.fori_loop(0, calls, body, q)
        t_f = _timed(jax.jit(forward), (q, k, v), reps=5) / calls
        t_b = _timed(jax.jit(backward), (q, k, v, do, lse, delta),
                     reps=5) / calls
        kind = "window" if windowed else "full"
        f_ops, f_bytes = counts.band_flash_fwd(h, kv, d, s, window)
        b_ops, b_bytes = counts.band_flash_bwd(h, kv, d, s, window)
        ms[kind] = t_f + t_b
        out.append(
            f"{kind} {s}: forward {t_f * 1e3:.2f} ms {f_ops / 1e12:.2f} "
            f"TFLOP band_flash_roofline "
            f"{flops.roofline_share(f_ops, f_bytes, t_f, peak):.1f} %, "
            f"backward {t_b * 1e3:.2f} ms {b_ops / 1e12:.2f} TFLOP "
            f"{flops.roofline_share(b_ops, b_bytes, t_b, peak):.1f} %")
    out.append(f"window over full {ms['window'] / ms['full']:.3f} (pairs "
               f"{counts.visible_pairs(s, w) / counts.visible_pairs(s):.3f})")
    return "; ".join(out)


def phase_four_chip_bert():
    return _train_bert(jax.devices()[:4])


def phase_four_chip_gpt(steps=3):
    """GPT-350M at dp2 x tp2 with sequence parallelism through
    ``ParallelPlan`` -> ``ElasticPlan.build`` -> ``pack_for_shard_map`` ->
    ``pipeline_step`` (``tools/autotune.build_train_step``): first loss
    equal to the one-chip serial loss on the same batch."""
    from apex_tpu.models.gpt import GPTConfig, GPTModel
    from apex_tpu.parallel.plan import ParallelPlan
    from tools.autotune import build_train_step

    devices = jax.devices()[:4]
    plan = ParallelPlan(dp=2, tp=2, sequence_parallel=True)
    cfg_kw = dict(dtype=_bf16, **GPT)
    batch, seq = 8, GPT["max_seq_len"]
    train_step, (packed, opt_state, tokens, targets), n_params = \
        build_train_step(plan, cfg_kw, batch, seq, devices)

    # serial one-chip loss on the same batch and the same seed-0 weights
    serial = GPTModel(GPTConfig(**cfg_kw))
    ref = float(jax.jit(serial.loss)(
        serial.init_params(jax.random.PRNGKey(0)), tokens, targets))

    # build_train_step leaves the state where init made it, on the first
    # device: compile once and place every argument where the program
    # wants it (the shard_map's in_specs, propagated)
    step = jax.jit(train_step, donate_argnums=(0, 1)).lower(
        packed, opt_state, tokens, targets).compile()
    packed, opt_state, tokens, targets = jax.device_put(
        (packed, opt_state, tokens, targets), step.input_shardings[0])
    _spans((packed, opt_state), 4)

    losses = []
    for _ in range(steps):
        loss, packed, opt_state = step(packed, opt_state, tokens, targets)
        losses.append(float(loss))
    assert all(np.isfinite(losses)), losses
    # bf16 activations, two reduction orders: 1% of a ~10.8 loss
    assert abs(losses[0] - ref) <= 1e-2 * abs(ref), (losses[0], ref)
    assert losses[-1] < losses[0], f"loss did not fall: {losses}"
    _spans((packed, opt_state), 4)
    return (f"GPT-350M {n_params / 1e6:.0f}M {plan.describe()}: loss "
            f"{losses[0]:.4f} vs one-chip serial {ref:.4f}, {steps} steps "
            f"-> {losses[-1]:.4f}, state on 4 devices")


PHASES = {
    "kernels": phase_kernels,
    "bert_train": phase_bert_train,
    "gpt_serve": phase_gpt_serve,
    "hybrid_reference": phase_hybrid_reference,
    "paged_decode_timing": phase_paged_decode_timing,
    "flash_timing": phase_flash_timing,
    "serve_reference": phase_serve_reference,
    "sparse_attention_timing": phase_sparse_attention_timing,
    "indexer_timing": phase_indexer_timing,
    "masked_flash_timing": phase_masked_flash_timing,
    "window_flash_timing": phase_window_flash_timing,
    "grouped_decode_timing": phase_grouped_decode_timing,
    "band_flash_timing": phase_band_flash_timing,
    "four_chip_bert": phase_four_chip_bert,
    "four_chip_gpt": phase_four_chip_gpt,
}


# the served share's legs hold 8 GB of weights and take minutes: by name
BY_NAME_ONLY = ("serve_reference", "sparse_attention_timing",
                "indexer_timing", "masked_flash_timing",
                "window_flash_timing", "grouped_decode_timing",
                "band_flash_timing")


def main(argv):
    cache_dir = setup_compile_cache()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(
            f"chip_smoke.py needs a TPU; JAX found platform "
            f"{dev.platform!r} ({dev.device_kind} x {len(jax.devices())})")
    n_dev = len(jax.devices())
    print(f"device_kind={dev.device_kind} count={n_dev} jax={jax.__version__}"
          f" compile_cache={cache_dir}", flush=True)
    unknown = [a for a in argv if a.split("=")[0] not in PHASES]
    if unknown:
        raise SystemExit(f"unknown phase {unknown}; choose from "
                         f"{sorted(PHASES)}")
    names = argv or [n for n in PHASES
                     if (n_dev >= 4 or not n.startswith("four_chip"))
                     and n not in BY_NAME_ONLY]
    t_all = time.perf_counter()
    for name in names:
        t0 = time.perf_counter()
        # ``phase=argument``: hybrid_reference takes a configuration's name
        summary = PHASES[name.split("=")[0]](*name.split("=")[1:])
        print(f"PASS {name} ({time.perf_counter() - t0:.1f}s): {summary}",
              flush=True)
    print(f"all {len(names)} phases passed in "
          f"{time.perf_counter() - t_all:.1f}s", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": n_dev}}))


if __name__ == "__main__":
    main(sys.argv[1:])
