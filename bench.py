"""apex_tpu benchmark — run on the real TPU chip, print ONE JSON line.

Measures the binding BASELINE.md metrics that are measurable on a single
chip:

* BERT-large (340M) MLM pretrain step with FusedLAMB + amp O2 — the
  BASELINE.md row-1 north-star workload — -> tokens/s and MFU (>=50%
  MFU target at pod scale).  This is the headline metric.  Round-5
  config (measured sweep, tools/profile_bert.py): micro-batch 16 x 2
  gradient accumulation (global batch 32), NO remat, per-leaf
  (bucketed=False) FusedLAMB.
* GPT (350M-class) fwd+bwd+FusedAdam step -> tokens/s and MFU
  (batch 8, no remat, per-leaf FusedAdam).
* A per-component breakdown of the BERT step (attention / GEMMs / FFN /
  LN / LM head / optimizer), each isolated with in-jit chaining so
  per-dispatch host cost cannot pollute small components.
* The optimizer question, settled two ways: (a) standalone packed
  FusedAdam vs per-leaf FusedAdam vs unfused optax on the same param
  census; (b) IN-STEP: the same BERT train step with packed vs
  per-leaf FusedLAMB vs an optax LAMB + f32 masters.

Timing: host clock around work that ends in ``jax.block_until_ready``,
amortized over >=8 timed iterations.  The run refuses any platform but
a TPU, an unknown ``device_kind`` has no peak (it raises), and a leg
that fails fails the run.

Peak accounting: ``mfu`` is achieved/spec-sheet peak;
``mfu_vs_calibrated`` divides by a same-window chained-matmul probe,
reported RAW (never clamped up to spec).

One process per chip: this process owns the device, so the multi-device
legs run in-process over ``jax.devices()`` and skip on a one-chip host.
The only child is the lint gate, a compile-only check pinned to the
host CPU platform that never asks for a chip.
"""

from __future__ import annotations

import functools
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from apex_tpu.utils.platform import setup_compile_cache

# peak dense bf16 FLOPs/s per chip by device kind (public spec sheets)
_PEAK_BF16 = {
    "TPU v5 lite": 197e12,       # v5e
    "TPU v5e": 197e12,
    "TPU v5p": 459e12,
    "TPU v5": 459e12,
    "TPU v4": 275e12,
    "TPU v6 lite": 918e12,       # v6e / Trillium
    "TPU v6e": 918e12,
}


def _spec_peak() -> float:
    kind = jax.devices()[0].device_kind
    # longest matching prefix wins ("TPU v5 lite" before "TPU v5")
    best = 0.0
    best_len = -1
    for k, v in _PEAK_BF16.items():
        if kind.startswith(k) and len(k) > best_len:
            best, best_len = v, len(k)
    if best_len < 0:
        raise RuntimeError(
            f"no published bf16 peak for device_kind {kind!r}; add it to "
            "_PEAK_BF16 with its source")
    return best


_CAL_STATE = None


_CAL_CHAIN = 96      # 4096^3 matmuls: ~100 ms device work per dispatch


def _calibrated_peak(rounds: int = 1) -> float:
    """Sustained bf16 matmul FLOP/s on this device — ONE timing window
    per call so callers can pair it tightly with another measurement.

    The probe is a chain of ``_CAL_CHAIN`` DEPENDENT matmuls
    inside one jitted program (~100 ms of device work per dispatch):
    per-dispatch latency must be amortized the way a real train
    step amortizes it.  The chain CARRIES its operand between calls
    (donated) so every timed execution is a distinct computation.
    Returned RAW — do NOT clamp it up to the spec sheet."""
    global _CAL_STATE
    # 4096^2 operands: big enough for full MXU utilization, small enough
    # (3 x 32 MB) to coexist with a batch-32 model's HBM footprint
    n = 4096
    if _CAL_STATE is None:
        key = jax.random.PRNGKey(0)
        a = jax.random.normal(key, (n, n), jnp.bfloat16)
        b = jax.random.normal(key, (n, n), jnp.bfloat16)

        @functools.partial(jax.jit, donate_argnums=(0,))
        def chain(a, b):
            def body(c, _):
                # dependent chain: no dead-code elimination, no overlap;
                # the rescale keeps values finite across calls
                c = jnp.dot(c, b, preferred_element_type=jnp.bfloat16)
                c = c * (1.0 / jnp.maximum(
                    jnp.max(jnp.abs(c)), 1.0)).astype(jnp.bfloat16)
                return c, None
            c, _ = jax.lax.scan(body, a, None, length=_CAL_CHAIN)
            return c

        a = jax.block_until_ready(chain(a, b))                   # compile outside timing
        _CAL_STATE = {"a": a, "b": b, "chain": chain}
    st = _CAL_STATE
    best = 0.0
    for _ in range(rounds):
        iters = 2
        t0 = time.perf_counter()
        for _ in range(iters):
            st["a"] = st["chain"](st["a"], st["b"])
        jax.block_until_ready(st["a"])
        dt = (time.perf_counter() - t0) / (iters * _CAL_CHAIN)
        best = max(best, 2.0 * n ** 3 / dt)
    return best


def _free_calibration():
    global _CAL_STATE
    _CAL_STATE = None


def _time_steps(fn, args, warmup=2, iters=8, rounds=3):
    """Median over ``rounds`` timing rounds, each ended by
    ``block_until_ready``."""
    for _ in range(warmup):
        out = fn(*args)
    jax.block_until_ready(out)
    times = []
    for _ in range(rounds):
        t0 = time.perf_counter()
        for _ in range(iters):
            out = fn(*args)
        jax.block_until_ready(out)
        times.append((time.perf_counter() - t0) / iters)
    times.sort()
    return times[len(times) // 2]


def _paired_mfu_passes(run, args, tokens_per_step, flops_per_token,
                       n_passes=5):
    """The paired-calibration MFU protocol shared by the model legs:
    each pass times a bf16 calibration matmul and the train step
    back-to-back in one window; the headline is the median pass.

    ``mfu`` (headline) is achieved/spec for BASELINE comparability;
    ``mfu_vs_calibrated`` divides by the RAW same-window calibration
    (clamped only by achieved itself: a step genuinely cannot beat a
    peak, so achieved > cal means the calibration undershot)."""
    spec = _spec_peak()
    passes = []
    for _ in range(n_passes):
        cal = _calibrated_peak(rounds=1)
        # a broken calibration (freed state, early return) lands
        # far below any plausible silicon; without this floor it would
        # silently clamp mfu_vs_calibrated to a fabricated 1.0
        assert cal > 0.2 * spec, (
            f"calibration probe measured {cal / 1e12:.1f} TF/s "
            f"(< 20% of the {spec / 1e12:.0f} TF/s spec) — the "
            "calibration matmul is not measuring peak")
        dt = _time_steps(run, args, warmup=1, rounds=1)
        achieved = tokens_per_step / dt * flops_per_token
        passes.append({"dt": dt, "achieved": achieved, "cal": cal,
                       "mfu_spec": achieved / spec,
                       "mfu_cal": achieved / max(cal, achieved)})
    passes.sort(key=lambda p: p["mfu_spec"])
    mid = passes[len(passes) // 2]
    assert mid["mfu_spec"] > 0.0
    return {
        "clamped_passes": sum(p["achieved"] > p["cal"] for p in passes),
        "mfu_pass_spread": [round(p["mfu_spec"], 4) for p in passes],
        "step_time_s": mid["dt"],
        "tokens_per_s": tokens_per_step / mid["dt"],
        "achieved_flops": mid["achieved"],
        "peak_spec": spec,
        "peak_calibrated_raw": mid["cal"],
        "silicon_fraction_of_spec": mid["cal"] / spec,
        "mfu_spec": mid["mfu_spec"],
        "mfu_vs_calibrated": mid["mfu_cal"],
        "mfu": mid["mfu_spec"],
    }


# ---------------------------------------------------------------------------
# model legs
# ---------------------------------------------------------------------------

def _accumulated_grads(loss_fn, params, tokens, labels, accum,
                       grad_dtype=None):
    """Mean loss + mean grads over ``accum`` leading-axis microbatches
    via lax.scan, accumulating in f32; ``grad_dtype`` casts the final
    grads (bf16 under O2 — the cotangent dtype the optimizer expects).
    Single source for the BERT and GPT accumulation legs (and imported
    by tools/sweep_gpt.py) so the accumulation numerics cannot drift
    between them."""
    if accum == 1:
        loss, grads = jax.value_and_grad(loss_fn)(params, tokens[0],
                                                  labels[0])
        # same cotangent dtype contract as the accumulated branch: the
        # optimizer must see identical grad dtypes whatever accum is
        if grad_dtype is not None:
            grads = jax.tree_util.tree_map(
                lambda g: g.astype(grad_dtype), grads)
        return loss, grads

    def mb(carry, tl):
        tk, lb = tl
        l, g = jax.value_and_grad(loss_fn)(params, tk, lb)
        acc_l, acc_g = carry
        g = jax.tree_util.tree_map(
            lambda a, b: a + b.astype(jnp.float32), acc_g, g)
        return (acc_l + l, g), None

    zero = (jnp.zeros(()),
            jax.tree_util.tree_map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params))
    (loss, grads), _ = jax.lax.scan(mb, zero, (tokens, labels))
    inv = 1.0 / accum
    cast = (lambda g: g * inv) if grad_dtype is None else (
        lambda g: (g * inv).astype(grad_dtype))
    return loss * inv, jax.tree_util.tree_map(cast, grads)

def _packed_opt(cls, **kw):
    """Packed-engine instance for the comparison arms.  The ctor opt-in
    was removed after the packed layout lost two bench rounds
    (packed_vs_optax_speedup 0.49-0.53); these arms keep measuring the
    engine — it survives as the ZeRO sharding unit — by flipping the
    attribute the way the distributed mixin selects it."""
    opt = cls(**kw)
    opt.bucketed = True
    return opt


def _make_bert_lamb_step(batch, accum, *, remat, bucketed, optimizer="lamb"):
    """The BASELINE row-1 workload: BERT-large MLM + FusedLAMB + amp O2
    (bf16 model params, fp32 masters, keep-norm-fp32), global batch
    ``batch * accum`` via in-step gradient accumulation."""
    from apex_tpu import amp
    from apex_tpu.models.bert import BertConfig, BertModel
    from apex_tpu.optimizers import FusedLAMB

    cfg = BertConfig(hidden_size=1024, num_layers=24,
                     num_attention_heads=16, max_seq_len=512, remat=remat,
                     remat_policy="dots" if remat else "full",
                     dtype=jnp.bfloat16)
    seq = 512
    model = BertModel(cfg)
    if optimizer == "lamb":
        opt = (_packed_opt(FusedLAMB, lr=1e-3) if bucketed
               else FusedLAMB(lr=1e-3, bucketed=False))
        # amp.initialize implements O2's fp32-master contract by setting
        # master_weights on THIS instance — it must be the optimizer
        # actually stepped, or the workload silently loses its masters
        state = amp.initialize(model.loss, opt, opt_level="O2")
    else:                                        # optax comparison arm
        import optax
        opt = optax.lamb(1e-3, b1=0.9, b2=0.999, eps=1e-6,
                         weight_decay=0.01)
        # the optax arm implements the same master contract explicitly
        # below; initialize only supplies apply_fn/cast_params here
        state = amp.initialize(model.loss, None, opt_level="O2")
    params = state.cast_params(model.init_params(jax.random.PRNGKey(0)))
    n_params = sum(int(np.prod(p.shape))
                   for p in jax.tree_util.tree_leaves(params))
    if optimizer == "lamb":
        opt_state = opt.init(params)
    else:
        # optax arm: the ONLY persistent state is (f32 masters, optax
        # state) — model-dtype params are derived inside the step.
        # Holding a separate params tree would alias its f32 norm
        # leaves with the masters (astype is an identity there) and a
        # donated call would then donate one buffer twice.
        masters = jax.tree_util.tree_map(
            lambda p: jnp.array(p, dtype=jnp.float32, copy=True), params)
        opt_state = (masters, opt.init(masters))
        dtype_template = jax.tree_util.tree_map(lambda p: p.dtype, params)

    rng = np.random.RandomState(0)
    tokens = jnp.asarray(rng.randint(0, cfg.vocab_size,
                                     (accum, batch, seq)))
    # MLM convention: label = original id at ~15% masked positions, -1 off
    labels = np.where(rng.rand(accum, batch, seq) < 0.15,
                      rng.randint(0, cfg.vocab_size, (accum, batch, seq)),
                      -1)
    labels = jnp.asarray(labels)

    def grads_of(params, tokens, labels):
        return _accumulated_grads(state.apply_fn, params, tokens, labels,
                                  accum, grad_dtype=jnp.bfloat16)

    if optimizer == "lamb":
        @functools.partial(jax.jit, donate_argnums=(0, 1))
        def train_step(params, opt_state, tokens, labels):
            loss, grads = grads_of(params, tokens, labels)
            new_params, new_opt = opt.step(grads, params, opt_state)
            return loss, new_params, new_opt
    else:
        import optax

        @functools.partial(jax.jit, donate_argnums=(0,))
        def train_step(opt_state, tokens, labels):
            masters, ostate = opt_state
            model_params = jax.tree_util.tree_map(
                lambda m, dt: m.astype(dt), masters, dtype_template)
            loss, grads = grads_of(model_params, tokens, labels)
            grads = jax.tree_util.tree_map(
                lambda g: g.astype(jnp.float32), grads)
            updates, ostate = opt.update(grads, ostate, masters)
            masters = optax.apply_updates(masters, updates)
            return loss, (masters, ostate)

    if optimizer == "lamb":
        holder = {"p": params, "o": opt_state}

        def run(tokens, labels):
            loss, holder["p"], holder["o"] = train_step(
                holder["p"], holder["o"], tokens, labels)
            return loss
    else:
        holder = {"o": opt_state}

        def run(tokens, labels):
            loss, holder["o"] = train_step(holder["o"], tokens, labels)
            return loss

    flops_per_token = 6 * n_params + 12 * cfg.num_layers * cfg.hidden_size \
        * seq
    return run, (tokens, labels), batch * accum * seq, flops_per_token, \
        n_params


def bench_bert_lamb_train_step():
    """Headline: measured-best config from the round-5 sweep — micro 16
    x 2 accumulation (global batch 32, same as rounds 1-4), NO remat
    (the per-leaf optimizer freed the packed-engine HBM that forced
    remat), per-leaf FusedLAMB."""
    run, args, tokens_per_step, flops_per_token, n_params = \
        _make_bert_lamb_step(16, 2, remat=False, bucketed=False)
    out = _paired_mfu_passes(run, args, tokens_per_step, flops_per_token)
    return {"n_params": n_params, "batch": 16, "accum": 2, "seq": 512,
            "remat": "none", "optimizer_layout": "per_leaf", **out}


def bench_lamb_in_step():
    """VERDICT r4 item 3: the SAME BERT train step with (a) packed
    FusedLAMB, (b) per-leaf FusedLAMB, (c) unfused optax LAMB + f32
    masters — the in-graph optimizer cost, where XLA may fuse packing
    into producers.  Small arm (batch 8, no remat, accum 1) keeps three
    full-model compiles affordable; the optimizer cost is constant per
    step so the DELTAS transfer to any batch."""
    arms = {}
    for name, kw in (("packed", dict(bucketed=True)),
                     ("per_leaf", dict(bucketed=False)),
                     ("optax_lamb", dict(bucketed=False,
                                         optimizer="optax"))):
        def arm():
            run, args, _, _, _ = _make_bert_lamb_step(8, 1, remat=False,
                                                      **kw)
            return _time_steps(run, args, warmup=1, iters=4, rounds=3)
        arms[name] = arm()
        jax.clear_caches()
    out = {"step_time_s": {k: (round(v, 5) if v else None)
                           for k, v in arms.items()}}
    if arms["packed"] and arms["per_leaf"]:
        out["per_leaf_vs_packed_speedup"] = round(
            arms["packed"] / arms["per_leaf"], 3)
    if arms["optax_lamb"] and arms["per_leaf"]:
        out["per_leaf_vs_optax_speedup"] = round(
            arms["optax_lamb"] / arms["per_leaf"], 3)
    return out


def bench_gpt_train_step():
    from apex_tpu.models.gpt import GPTConfig, GPTModel
    from apex_tpu.optimizers import FusedAdam

    # measured best (tools/sweep_gpt.py): micro-batch 8 x 2 gradient
    # accumulation (global batch 16, the same 16 Ktok/step as rounds
    # 1-4), NO remat, per-leaf FusedAdam; the fused logit-free LM head
    # keeps the (b*s, vocab) logits out of HBM, which is what lets
    # no-remat fit at all
    cfg = GPTConfig(vocab_size=50304, hidden_size=1024, num_layers=24,
                    num_attention_heads=16, max_seq_len=1024, remat=False,
                    dtype=jnp.bfloat16)
    batch, seq, accum = 8, 1024, 2
    model = GPTModel(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    n_params = sum(int(np.prod(p.shape))
                   for p in jax.tree_util.tree_leaves(params))
    adam = FusedAdam(lr=1e-4, bucketed=False)
    opt_state = adam.init(params)
    rng = np.random.RandomState(0)
    tokens = jnp.asarray(rng.randint(0, cfg.vocab_size,
                                     (accum, batch, seq)))
    targets = jnp.asarray(rng.randint(0, cfg.vocab_size,
                                      (accum, batch, seq)))

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def train_step(params, opt_state, tokens, targets):
        loss, grads = _accumulated_grads(model.loss, params, tokens,
                                         targets, accum)
        new_params, new_opt = adam.step(grads, params, opt_state)
        return loss, new_params, new_opt

    holder = {"p": params, "o": opt_state}

    def run(tokens, targets):
        loss, holder["p"], holder["o"] = train_step(holder["p"],
                                                    holder["o"], tokens,
                                                    targets)
        return loss

    # PaLM-style accounting: 6*N per token (fwd+bwd) + attention term
    flops_per_token = 6 * n_params + 12 * cfg.num_layers * cfg.hidden_size \
        * seq
    out = _paired_mfu_passes(run, (tokens, targets),
                             accum * batch * seq, flops_per_token)
    return {"n_params": n_params, "batch": batch, "accum": accum,
            "seq": seq, "remat": "none", "optimizer_layout": "per_leaf",
            **out}


def bench_gpt_decode():
    """Serving leg: prefill latency + steady-state batched decode
    throughput on the GPT-350M config with a bf16 KV cache.

    Decode is measured over the FULL slot table at mid-sequence depth —
    the continuous-batching engine's steady state, where every step is
    one `decode_step` whose batch dimension is the slot ring.  BASELINE
    has no inference row, so this rides in `extra` (the serving targets
    live in README "Inference & serving")."""
    from apex_tpu.models.gpt import GPTConfig, GPTModel
    from apex_tpu.utils.platform import is_tpu_backend

    cfg = GPTConfig(vocab_size=50304, hidden_size=1024, num_layers=24,
                    num_attention_heads=16, max_seq_len=1024,
                    dtype=jnp.bfloat16)
    slots, prompt_len = 8, 512
    model = GPTModel(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    rng = np.random.RandomState(0)

    prefill = jax.jit(model.prefill)
    prompt = jnp.asarray(rng.randint(0, cfg.vocab_size, (1, prompt_len)))
    t_prefill = _time_steps(lambda t: prefill(params, t)[0], (prompt,),
                            warmup=2, iters=4, rounds=3)

    cache = jnp.zeros((slots, cfg.num_layers, 2, cfg.max_seq_len,
                       cfg.num_attention_heads, cfg.head_dim),
                      jnp.bfloat16)
    positions = jnp.full((slots,), prompt_len, jnp.int32)
    tokens = jnp.asarray(rng.randint(0, cfg.vocab_size, (slots,)))
    # the cache threads step-to-step; donate it on TPU so XLA writes in
    # place (donating on CPU only emits warnings)
    step = jax.jit(model.decode_step,
                   donate_argnums=(2,) if is_tpu_backend() else ())
    holder = {"c": cache}

    def run(tokens, positions):
        logits, holder["c"] = step(params, tokens, holder["c"], positions)
        return logits

    dt = _time_steps(run, (tokens, positions), warmup=2, iters=16,
                     rounds=3)
    return {"slots": slots, "prompt_len": prompt_len,
            "max_seq": cfg.max_seq_len, "cache_dtype": "bfloat16",
            "prefill_s": t_prefill,
            "prefill_tokens_per_s": prompt_len / t_prefill,
            "decode_step_s": dt,
            "decode_tokens_per_s": slots / dt,
            "decode_token_latency_ms": dt * 1e3}


# ---------------------------------------------------------------------------
# breakdown leg (VERDICT r4 item 1)
# ---------------------------------------------------------------------------

def bench_bert_breakdown():
    """Per-component times at the HEADLINE step's shapes — batch 16 x
    seq 512, x2 accumulation microbatches per step (the optimizer runs
    once per step, after accumulation, so it is NOT doubled) — each
    isolated and repeated inside ONE jitted scan so per-dispatch host
    cost cannot dominate a small op.  Sum of
    components ~= the un-rematted headline step; this names where the
    step's time goes (bench extra ``breakdown``)."""
    from apex_tpu.normalization import MixedFusedLayerNorm
    from apex_tpu.ops.flash_attention import flash_attention
    from apex_tpu.ops.lm_head import fused_linear_cross_entropy
    from apex_tpu.optimizers import FusedLAMB

    b, s, h, nh, L, V = 16, 512, 1024, 16, 24, 30528
    accum = 2                     # headline: batch 16 x 2 accum
    hd = h // nh
    f = 4 * h
    rng = np.random.RandomState(0)
    bf = jnp.bfloat16
    out = {}

    def t_chain(fn_one, x0, *consts, reps=24):
        def loss(x, *cs):
            def body(c, _):
                return fn_one(c, *cs), None
            y, _ = jax.lax.scan(body, x, None, length=reps)
            return jnp.mean(y.astype(jnp.float32))
        g = jax.jit(jax.grad(loss, argnums=tuple(range(1 + len(consts)))))
        return _time_steps(g, (x0,) + consts, warmup=1, iters=4,
                           rounds=3) / reps

    q = jnp.asarray(rng.randn(b, nh, s, hd), bf)
    k = jnp.asarray(rng.randn(b, nh, s, hd), bf)
    v = jnp.asarray(rng.randn(b, nh, s, hd), bf)
    out["attention"] = accum * L * t_chain(
        lambda q, k, v: flash_attention(q, k, v, causal=False), q, k, v)
    del q, k, v
    jax.clear_caches()

    x = jnp.asarray(rng.randn(b * s, h), bf)
    wqkv = jnp.asarray(rng.randn(h, 3 * h) * 0.02, bf)
    wproj = jnp.asarray(rng.randn(h, h) * 0.02, bf)
    out["qkv_proj_gemms"] = accum * L * t_chain(
        lambda x, a, c: ((x @ a)[:, :h] @ c), x, wqkv, wproj)
    del wqkv, wproj
    jax.clear_caches()

    w1 = jnp.asarray(rng.randn(h, f) * 0.02, bf)
    w2 = jnp.asarray(rng.randn(f, h) * 0.02, bf)
    out["ffn"] = accum * L * t_chain(
        lambda x, w1, w2: jax.nn.gelu(x @ w1, approximate=True) @ w2,
        x, w1, w2, reps=8)
    del w1, w2
    jax.clear_caches()

    ln = MixedFusedLayerNorm(h)
    lp = ln.init_params()
    xf = jnp.asarray(rng.randn(b, s, h), bf)
    out["layernorm"] = accum * 2 * L * t_chain(
        lambda x, p: ln(p, x), xf, lp, reps=48)
    del xf, lp
    jax.clear_caches()

    emb = jnp.asarray(rng.randn(V, h) * 0.02, bf)
    tgt = jnp.asarray(rng.randint(0, V, (b * s,)))
    g = jax.jit(jax.grad(lambda hd_, w: jnp.mean(
        fused_linear_cross_entropy(hd_, w, tgt)), argnums=(0, 1)))
    out["lm_head_ce"] = accum * _time_steps(g, (x, emb), warmup=1,
                                            iters=4, rounds=3)
    del x, emb, tgt, g
    jax.clear_caches()

    shapes = []
    for _ in range(L):
        shapes += [(3 * h, h), (3 * h,), (h, h), (h,), (f, h), (f,),
                   (h, f), (h,), (h,), (h,), (h,), (h,)]
    shapes += [(V, h), (512, h), (2, h), (h, h), (h,), (h,), (h,)]
    params = [jnp.asarray(rng.randn(*sh).astype(np.float32) * 0.02)
              for sh in shapes]
    grads = [jnp.asarray(rng.randn(*sh).astype(np.float32) * 1e-3)
             for sh in shapes]
    lamb = FusedLAMB(lr=1e-3, bucketed=False)
    lstate = lamb.init(params)
    reps = 4

    @functools.partial(jax.jit, donate_argnums=(1, 2))
    def lamb_steps(grads, params, state):
        def body(c, _):
            p, s_ = c
            return lamb.step(grads, p, s_), None
        (p, s_), _ = jax.lax.scan(body, (params, state), None, length=reps)
        return p, s_

    holder = {"p": params, "s": lstate}

    def run(grads):
        holder["p"], holder["s"] = lamb_steps(grads, holder["p"],
                                              holder["s"])
        return holder["p"]

    out["optimizer_lamb_per_leaf"] = _time_steps(
        run, (grads,), warmup=1, iters=2, rounds=3) / reps
    del holder, params, grads, lstate
    jax.clear_caches()

    total = sum(out.values())
    return {
        **{k: round(v, 5) for k, v in out.items()},
        "sum_s": round(total, 5),
        "top_consumer": max(out, key=out.get),
        "note": "isolated fwd+bwd per component x layer count x 2 "
                "accum microbatches at the headline batch-16 shapes; "
                "optimizer once per step (after accumulation)",
    }


# ---------------------------------------------------------------------------
# standalone optimizer leg
# ---------------------------------------------------------------------------

def bench_fused_adam_vs_optax():
    import optax

    from apex_tpu.optimizers import FusedAdam

    # this leg is a self-relative ratio — the calibration buffers from
    # the model legs are dead weight; free them before allocating ~9 GB
    # of optimizer state
    _free_calibration()

    rng = np.random.RandomState(1)
    shapes = []
    # BERT-like param census at HALF depth (12 layers): three optimizer
    # states (packed + per-leaf + optax) must coexist for the
    # same-window ratios, and the full-depth census OOMs 16 GB HBM
    # with all three alive; the ratios are depth-independent
    for _ in range(12):
        shapes += [(1024, 1024), (4096, 1024), (1024, 4096),
                   (1024,), (4096,), (1024,), (1024,)]
    shapes += [(30522, 1024), (512, 1024)]
    params = [jnp.asarray(rng.randn(*s).astype(np.float32) * 0.02)
              for s in shapes]
    grads = [jnp.asarray(rng.randn(*s).astype(np.float32) * 1e-3)
             for s in shapes]

    packed = _packed_opt(FusedAdam, lr=1e-3)
    pstate = packed.init(params)

    @jax.jit
    def packed_step(grads, params, state):
        return packed.step(grads, params, state)

    leaf = FusedAdam(lr=1e-3, bucketed=False)
    lstate = leaf.init(params)

    @jax.jit
    def leaf_step(grads, params, state):
        return leaf.step(grads, params, state)

    opt = optax.adam(1e-3)
    ostate = opt.init(params)

    @jax.jit
    def optax_step(grads, params, state):
        updates, new_state = opt.update(grads, state, params)
        return optax.apply_updates(params, updates), new_state

    # Absolute timing drifts between windows, so each pass
    # times all three arms back-to-back in one window; the headline is
    # the median per-pass ratio with the spread shipped.
    #
    # Caveat on the PACKED ratio's meaning: this microbenchmark hands
    # the step PRE-MATERIALIZED grads, so the bucket packing is a pure
    # extra HBM round trip here — AND a pallas_call's operands must be
    # materialized buffers, so unlike the per-leaf path the packing can
    # never fuse into the in-graph gradient producers either
    # (bench_lamb_in_step measures exactly that in-step).  The packed
    # engine's remaining wins are the ZeRO collective/state layout and
    # the on-device noop-skip; per-leaf is the single-chip speed path.
    passes = []
    for _ in range(5):
        t_packed = _time_steps(packed_step, (grads, params, pstate),
                               warmup=1, rounds=1)
        t_leaf = _time_steps(leaf_step, (grads, params, lstate),
                             warmup=1, rounds=1)
        t_optax = _time_steps(optax_step, (grads, params, ostate),
                              warmup=1, rounds=1)
        passes.append({"packed": t_packed, "leaf": t_leaf,
                       "optax": t_optax})
    passes.sort(key=lambda p: p["optax"] / p["leaf"])
    mid = passes[len(passes) // 2]

    # fp16 leg: Mosaic has no f16, so fp16 buckets take the documented
    # jnp fallback (ops/multi_tensor.py::_use_kernel) — quantify what
    # that path costs relative to the f32 Pallas path on the same
    # element count.  Same optimizer configuration on both sides.
    del ostate, lstate
    params16 = [p.astype(jnp.float16) for p in params]
    grads16 = [g.astype(jnp.float16) for g in grads]
    fused16 = _packed_opt(FusedAdam, lr=1e-3)
    fstate16 = fused16.init(params16)

    @jax.jit
    def fused16_step(grads, params, state):
        return fused16.step(grads, params, state)

    fp16_passes = []
    for _ in range(3):
        t16 = _time_steps(fused16_step, (grads16, params16, fstate16),
                          warmup=1, rounds=1)
        t32 = _time_steps(packed_step, (grads, params, pstate),
                          warmup=1, rounds=1)
        fp16_passes.append(t16 / t32)
    fp16_passes.sort()

    return {
        "n_tensors": len(shapes),
        "n_elements": int(sum(int(np.prod(s)) for s in shapes)),
        "packed_step_s": mid["packed"],
        "per_leaf_step_s": mid["leaf"],
        "optax_step_s": mid["optax"],
        "per_leaf_vs_optax_speedup": round(mid["optax"] / mid["leaf"], 3),
        "packed_vs_optax_speedup": round(mid["optax"] / mid["packed"], 3),
        "spread_leaf_vs_optax": [round(p["optax"] / p["leaf"], 3)
                                 for p in passes],
        "fp16_fallback_vs_f32_kernel": round(
            fp16_passes[len(fp16_passes) // 2], 3),
        "fp16_fallback_spread": [round(r, 3) for r in fp16_passes],
    }


def bench_dp_comm():
    """Data-parallel comms leg (PR 2): the same Adam update at dp>=2 as
    (a) replicated — psum all grads, every device runs the full per-leaf
    update (the pre-PR-2 DP path); (b) sharded-update —
    DistributedFusedAdam's reduce-scatter / 1-of-dp shard update /
    all-gather (arXiv:2004.13336); (c) sharded + int8 block-quantized
    grad transport (EQuARX, arXiv:2506.17615).  Reports step time per
    arm; the acceptance bar is sharded <= replicated at dp>=2 (on a
    single chip there is no dp to measure, so the leg degrades to a
    skip marker)."""
    from jax.sharding import PartitionSpec as P

    from apex_tpu.optimizers import FusedAdam
    from apex_tpu.parallel import DistributedFusedAdam

    dp = len(jax.devices())
    if dp < 2:
        return {"skipped": f"needs dp>=2, have {dp} device(s)"}
    _free_calibration()
    mesh = jax.make_mesh((dp,), ("data",))
    rng = np.random.RandomState(2)
    shapes = []
    for _ in range(4):
        shapes += [(512, 512), (2048, 512), (512, 2048), (512,), (2048,)]
    shapes += [(8192, 512)]
    params = {f"p{i}": jnp.asarray(rng.randn(*s).astype(np.float32) * 0.02)
              for i, s in enumerate(shapes)}
    # stacked per-device microbatch grads, sharded over the data axis —
    # the same input every arm consumes (its reduction is what differs)
    grads = {k: jnp.asarray(rng.randn(dp, *v.shape).astype(np.float32)
                            * 1e-3) for k, v in params.items()}
    g_specs = jax.tree_util.tree_map(lambda _: P("data"), params)

    leaf = FusedAdam(lr=1e-3, bucketed=False)
    lstate = leaf.init(params)

    @jax.jit
    def replicated_step(g, p, s):
        def local(g, p, s):
            g = jax.tree_util.tree_map(lambda x: x[0], g)
            g = jax.tree_util.tree_map(
                lambda x: jax.lax.psum(x, "data") / dp, g)
            return leaf.step(g, p, s)
        return jax.shard_map(local, mesh=mesh,
                             in_specs=(g_specs, P(), P()),
                             out_specs=(P(), P()), check_vma=False)(g, p, s)

    arms = {}

    def rep_arm():
        return _time_steps(replicated_step, (grads, params, lstate),
                           warmup=2, iters=4, rounds=3)
    arms["replicated"] = rep_arm()

    for name, mode in (("sharded", None), ("sharded_int8", "int8")):
        opt = DistributedFusedAdam(lr=1e-3, world_size=dp,
                                   allreduce_dtype=mode)
        state = opt.make_init(mesh)(params)
        step = opt.make_step(mesh)

        def dist_arm():
            return _time_steps(step, (grads, params, state),
                               warmup=2, iters=4, rounds=3)
        arms[name] = dist_arm()
        jax.clear_caches()

    out = {"dp": dp,
           "n_elements": int(sum(int(np.prod(s)) for s in shapes)),
           "step_time_s": {k: (round(v, 6) if v else None)
                           for k, v in arms.items()}}
    if arms["replicated"] and arms["sharded"]:
        out["sharded_vs_replicated_speedup"] = round(
            arms["replicated"] / arms["sharded"], 3)
    if arms["replicated"] and arms["sharded_int8"]:
        out["int8_vs_replicated_speedup"] = round(
            arms["replicated"] / arms["sharded_int8"], 3)
    return out


def bench_tp_overlap():
    """Tensor-parallel latency-hiding leg (ISSUE 3): the same GPT
    fwd+bwd step at tp=2/4/8 as (a) replicated — the all-gather/psum TP
    edges with sequence-replicated activations (the pre-SP path); (b)
    sequence-parallel — gather(tiled)/psum_scatter edges, LN/residual on
    ``(b, s/t, h)``; (c) sequence-parallel + chunked overlap — the TP-edge
    collective+GEMM pairs fused into ``ppermute`` rings
    (``overlap_chunks=4``).  Reports step time per arm and
    ``tp_overlap_speedup`` (replicated / best latency-hiding arm at the
    widest tp).  Degrades to a skip marker on a single chip, like
    :func:`bench_dp_comm`."""
    from jax.sharding import PartitionSpec as P

    from apex_tpu.models.gpt import GPTConfig, GPTModel, pack_for_shard_map

    n_dev = len(jax.devices())
    if n_dev < 2:
        return {"skipped": f"needs tp>=2, have {n_dev} device(s)"}
    _free_calibration()
    rng = np.random.RandomState(3)
    batch, seq = 2, 256

    def cfg(**kw):
        return GPTConfig(vocab_size=1024, hidden_size=256, num_layers=2,
                         num_attention_heads=8, max_seq_len=seq,
                         rotary=True, **kw)

    params = GPTModel(cfg()).init_params(jax.random.PRNGKey(0))
    tokens = jnp.asarray(rng.randint(0, 1024, (batch, seq)))
    targets = jnp.asarray(rng.randint(0, 1024, (batch, seq)))

    def arm_time(model):
        mesh = jax.make_mesh((model.cfg.tensor_parallel_size,), ("model",))
        packed, in_specs, local_fn, repack_fn = pack_for_shard_map(
            model, params)

        def step(sp, tokens, targets):
            loss, g = jax.value_and_grad(model.loss)(local_fn(sp), tokens,
                                                     targets)
            return loss, repack_fn(g)

        run = jax.jit(jax.shard_map(
            step, mesh=mesh, in_specs=(in_specs, P(), P()),
            out_specs=(P(), in_specs), check_vma=False))

        def timed():
            return _time_steps(run, (packed, tokens, targets),
                               warmup=2, iters=4, rounds=3)
        t = timed()
        jax.clear_caches()
        return t

    out = {"batch": batch, "seq_len": seq, "per_tp": {}}
    speedup = None
    for tp in (2, 4, 8):
        if tp > n_dev:
            break
        arms = {
            "replicated": arm_time(GPTModel(cfg(
                tensor_parallel_size=tp, axis_name="model"))),
            "sequence_parallel": arm_time(GPTModel(cfg(
                tensor_parallel_size=tp, axis_name="model",
                sequence_parallel=True))),
            "sp_chunked": arm_time(GPTModel(cfg(
                tensor_parallel_size=tp, axis_name="model",
                sequence_parallel=True, overlap_chunks=4))),
        }
        row = {"step_time_s": {k: (round(v, 6) if v else None)
                               for k, v in arms.items()}}
        best = min((v for k, v in arms.items()
                    if k != "replicated" and v), default=None)
        if arms["replicated"] and best:
            speedup = round(arms["replicated"] / best, 3)
            row["tp_overlap_speedup"] = speedup
        out["per_tp"][f"tp{tp}"] = row
    # headline: the widest mesh measured (speedup carries tp by tp above)
    out["tp_overlap_speedup"] = speedup
    return out


def bench_pp_schedules():
    """Pipeline-parallel leg (ISSUE 6): the same GPT fwd+bwd step as
    (a) single-stage — one device, plain ``value_and_grad`` over the
    full microbatch set; (b) 1F1B ``pipeline_step`` at pp=2 and pp=4;
    (c) interleaved virtual stages (``n_virtual=2``) at the same
    widths.  Each pipelined arm reports its analytic bubble fraction
    next to the measured step time: 1F1B idles (S-1)/(M+S-1) of the
    schedule, interleaving cuts that to (S-1)/(Mv+(v+1)S-2) ticks'
    worth at the cost of v x more ppermute hops — the measurement
    shows whether the wire cost eats the bubble win at each width.
    ``vs_single_stage`` is wall-clock speedup over the one-device arm
    (upper bound S, bubble + p2p overhead eat the rest)."""
    from jax.sharding import PartitionSpec as P

    from apex_tpu.models.gpt import GPTConfig, GPTModel, pack_for_shard_map
    from apex_tpu.models.gpt import pipeline_step
    from apex_tpu.transformer.pipeline_parallel import bubble_fraction

    n_dev = len(jax.devices())
    if n_dev < 2:
        return {"skipped": f"needs pp>=2, have {n_dev} device(s)"}
    _free_calibration()
    rng = np.random.RandomState(5)
    # 8 layers: divisible into S*v chunks for every (S, v) below;
    # M=8 microbatches satisfies the interleaved M % S == 0 constraint
    M, mb, seq = 8, 1, 256
    cfg = GPTConfig(vocab_size=1024, hidden_size=256, num_layers=8,
                    num_attention_heads=8, max_seq_len=seq, rotary=True)
    model = GPTModel(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    tokens = jnp.asarray(rng.randint(0, 1024, (M * mb, seq)))
    targets = jnp.asarray(rng.randint(0, 1024, (M * mb, seq)))

    def single_stage_arm():
        run = jax.jit(jax.value_and_grad(model.loss))

        def timed():
            return _time_steps(run, (params, tokens, targets),
                               warmup=2, iters=4, rounds=3)
        t = timed()
        jax.clear_caches()
        return t

    def pp_arm(S, v):
        mesh = jax.make_mesh((S,), ("pipe",), devices=jax.devices()[:S])
        packed, in_specs, local_fn, repack_fn = pack_for_shard_map(
            model, params, n_stages=S, tensor_axis=None, n_virtual=v)

        def step(sp, tk, tg):
            loss, g = pipeline_step(model, local_fn(sp),
                                    tk.reshape(M, mb, seq),
                                    tg.reshape(M, mb, seq),
                                    pipe_axis="pipe", n_virtual=v)
            return loss, repack_fn(g)

        run = jax.jit(jax.shard_map(
            step, mesh=mesh, in_specs=(in_specs, P(), P()),
            out_specs=(P(), in_specs), check_vma=False))

        def timed():
            return _time_steps(run, (packed, tokens, targets),
                               warmup=2, iters=4, rounds=3)
        t = timed()
        jax.clear_caches()
        return t

    out = {"microbatches": M, "micro_batch_size": mb, "seq_len": seq,
           "n_layers": cfg.num_layers, "per_pp": {}}
    t_single = single_stage_arm()
    out["single_stage_step_s"] = round(t_single, 6) if t_single else None
    for S in (2, 4):
        if S > n_dev:
            break
        row = {}
        for name, v in (("1f1b", 1), ("interleaved", 2)):
            t = pp_arm(S, v)
            cell = {"step_time_s": round(t, 6) if t else None,
                    "bubble_fraction": round(bubble_fraction(M, S, v), 4)}
            if t and t_single:
                cell["vs_single_stage"] = round(t_single / t, 3)
            row[name] = cell
        a, b = (row["1f1b"]["step_time_s"],
                row["interleaved"]["step_time_s"])
        if a and b:
            row["interleaved_vs_1f1b_speedup"] = round(a / b, 3)
        out["per_pp"][f"pp{S}"] = row
    return out


def bench_resilience():
    """Resilience leg (ISSUE 4): what fault tolerance costs.

    (a) Checkpoint save / restore wall seconds for a full train state
    (params + both FusedAdam slots + step counter) through
    CheckpointManager's atomic commit protocol (payload + sha256
    manifest + latest-symlink flip), plus the async enqueue latency —
    the time the train loop actually stalls when double-buffered
    writes are used.  (b) Guarded vs raw train-step overhead: the SAME
    loss + FusedAdam update run bare vs through GuardedTrainStep
    (in-graph grad-norm/finiteness checks + the per-step host readback
    of the 3-element flags vector).  Acceptance target: overhead < 2%.
    """
    import shutil
    import tempfile

    from apex_tpu.optimizers import FusedAdam
    from apex_tpu.resilience import CheckpointManager, GuardedTrainStep

    _free_calibration()
    rng = np.random.RandomState(4)
    shapes = []
    for _ in range(4):
        shapes += [(512, 512), (2048, 512), (512, 2048), (512,), (2048,)]
    shapes += [(8192, 512)]
    params = {f"p{i}": jnp.asarray(rng.randn(*s).astype(np.float32) * 0.02)
              for i, s in enumerate(shapes)}
    n_elements = int(sum(int(np.prod(s)) for s in shapes))
    adam = FusedAdam(lr=1e-3, bucketed=False)
    opt_state = adam.init(params)

    # -- checkpoint save / restore -------------------------------------
    state = {"params": params, "opt": opt_state,
             "step": jnp.zeros((), jnp.int32)}
    ckdir = tempfile.mkdtemp(prefix="apex_tpu_bench_ck_")
    try:
        mgr = CheckpointManager(ckdir, keep=2)
        saves, restores, enqueues = [], [], []
        for i in range(3):
            t0 = time.perf_counter()
            mgr.save(i, state)
            saves.append(time.perf_counter() - t0)
            t0 = time.perf_counter()
            mgr.restore(state)
            restores.append(time.perf_counter() - t0)
            t0 = time.perf_counter()
            mgr.save_async(100 + i, state)   # train-loop stall only
            enqueues.append(time.perf_counter() - t0)
        mgr.wait()
        saves.sort(); restores.sort(); enqueues.sort()
        ck = {"state_bytes": 3 * 4 * n_elements,
              "save_s": round(saves[1], 4),
              "restore_s": round(restores[1], 4),
              "async_enqueue_s": round(enqueues[1], 4)}
    finally:
        shutil.rmtree(ckdir, ignore_errors=True)

    # -- guard overhead ------------------------------------------------
    # measured on a real (small) GPT fwd+bwd+Adam step so the guard's
    # extra work — the in-graph grad-norm pass, the injection-flag
    # folding, and the per-step host readback of the 3-float flags
    # vector — is weighed against realistic step compute, the way a
    # production train loop would pay it
    from apex_tpu.models.gpt import GPTConfig, GPTModel

    cfg = GPTConfig(vocab_size=1024, hidden_size=256, num_layers=2,
                    num_attention_heads=8, max_seq_len=256)
    model = GPTModel(cfg)
    gparams = model.init_params(jax.random.PRNGKey(0))
    gadam = FusedAdam(lr=1e-4, bucketed=False)
    tokens = jnp.asarray(rng.randint(0, cfg.vocab_size, (4, 256)))
    targets = jnp.asarray(rng.randint(0, cfg.vocab_size, (4, 256)))

    @jax.jit
    def raw_step(params, opt_state, tokens, targets):
        loss, grads = jax.value_and_grad(model.loss)(params, tokens,
                                                     targets)
        new_p, new_o = gadam.step(grads, params, opt_state)
        return loss, new_p, new_o

    hr = {"p": gparams, "o": gadam.init(gparams)}

    def run_raw(tokens, targets):
        loss, hr["p"], hr["o"] = raw_step(hr["p"], hr["o"], tokens,
                                          targets)
        return loss

    guard = GuardedTrainStep(model.loss, gadam)
    hg = {"p": gparams, "o": gadam.init(gparams),
          "g": guard.init_state()}

    def run_guard(tokens, targets):
        r = guard(hg["p"], hg["o"], hg["g"], tokens, targets)
        hg["p"], hg["o"], hg["g"] = r.params, r.opt_state, r.guard_state
        return r.loss

    t_raw = _time_steps(run_raw, (tokens, targets), warmup=2, iters=4,
                        rounds=3)
    t_guard = _time_steps(run_guard, (tokens, targets), warmup=2,
                          iters=4, rounds=3)
    overhead = t_guard / t_raw - 1.0
    return {"n_elements": n_elements, "checkpoint": ck,
            "raw_step_s": round(t_raw, 6),
            "guarded_step_s": round(t_guard, 6),
            "guard_overhead_frac": round(overhead, 4),
            "guard_overhead_target": 0.02,
            "guard_overhead_ok": bool(overhead < 0.02)}


def bench_elastic():
    """Elastic leg (ISSUE 9): what a topology re-plan costs.

    An :class:`ElasticTrainer` runs a guarded FusedAdam loop and is
    asked — through the :class:`HostSignals` mailbox, the SIGTERM
    route — to shrink dp to half and later grow back.  Each re-plan
    decomposes into the trainer's own phase stats (``checkpoint_s``:
    drain + boundary save, ``reshard_s``: rebuild + re-partition +
    post-reshard save) plus a measured ``recompile_s``: the first step
    under the new topology minus the steady-state median step (XLA
    retraces because the mesh changed).  ``total_recovery_s`` is the
    sum — the wall time a preempted pod spends not training.  Runs on
    any device count (dp=1 -> dp=1 still exercises the full
    drain/checkpoint/reshard/recompile cycle)."""
    import shutil
    import tempfile

    from apex_tpu.optimizers import FusedAdam
    from apex_tpu.resilience import (ElasticComponents, ElasticPlan,
                                     ElasticTrainer, GuardedTrainStep,
                                     HostSignals, TopologySpec)

    _free_calibration()
    n = len(jax.devices())
    dp = 4 if n >= 4 else (2 if n >= 2 else 1)
    base = TopologySpec(dp=dp)
    shrink = TopologySpec(dp=max(1, dp // 2))

    def loss_fn(p, x, y):
        return jnp.mean(jnp.square(x @ p["w"] + p["b"] - y))

    def factory(plan, ckpt, inj):
        opt = FusedAdam(lr=1e-3, bucketed=False)
        guard = GuardedTrainStep(loss_fn, opt, warmup_steps=1,
                                 checkpoint=ckpt, fault_injector=inj)
        r = np.random.RandomState(7)
        params = plan.put(
            {"w": jnp.asarray((r.randn(512, 256) * 0.02).astype(np.float32)),
             "b": jnp.zeros((256,), jnp.float32)})
        return ElasticComponents(guard, params, opt.init(params),
                                 guard.init_state())

    n_steps = 10
    signals = HostSignals()
    stamps = {}

    def batch_fn(step, plan):
        # one timestamp per executed step: gap s -> s+1 is the cost of
        # executing step s (+ the re-plan when one precedes step s+1)
        stamps.setdefault(step, time.perf_counter())
        if step == 3:
            signals.request_replan(shrink)
        elif step == 6:
            signals.request_replan(base)
        r = np.random.RandomState(9_000 + step)
        return (jnp.asarray(r.randn(64, 512).astype(np.float32)),
                jnp.asarray(r.randn(64, 256).astype(np.float32)))

    root = tempfile.mkdtemp(prefix="apex_tpu_bench_elastic_")
    try:
        trainer = ElasticTrainer(
            factory, ElasticPlan.build(base), directory=root,
            signals=signals)
        out = trainer.train(batch_fn, n_steps)
        stamps[n_steps] = time.perf_counter()
        assert out["replans"] == 2, out
        gap = {s: stamps[s + 1] - stamps[s] for s in range(n_steps)}
        # signals requested at steps 3/6 land at the NEXT poll, so the
        # re-plans precede steps 4 and 7: gaps 3 and 6 absorb the
        # re-plan, gaps 4 and 7 absorb the recompile
        steady = float(np.median([gap[s] for s in (1, 2, 5, 8)]))
        recompile = max(0.0,
                        float(np.median([gap[4], gap[7]])) - steady)
        ck = trainer.stats["last_checkpoint_s"]
        rs = trainer.stats["last_reshard_s"]
        return {"dp": dp, "shrink_dp": shrink.dp,
                "replans": out["replans"],
                "steady_step_s": round(steady, 5),
                "checkpoint_s": round(ck, 5),
                "reshard_s": round(rs, 5),
                "recompile_s": round(recompile, 5),
                "total_recovery_s": round(ck + rs + recompile, 5)}
    finally:
        shutil.rmtree(root, ignore_errors=True)


def bench_capacity():
    """Capacity-shifting leg (ROADMAP item 4): what moving chips
    between training and serving costs.

    A :class:`CapacityController` over a FusedAdam elastic trainer and
    a two-replica paged fleet runs one full lease cycle — shift
    **to_serving** (boundary-checkpoint drain + shrink re-shard +
    replica start) then **to_training** (replica migration drain +
    remove + grow re-shard) — and reports each shift's phase
    decomposition from the controller's own stats: ``drain_s``,
    ``reshard_s``, ``commit_s``, ``total_s`` (wall; the controller is
    given a wall clock while the fleet stays on its virtual one), plus
    the fleet ticks the serving drain took.  These are the latency
    numbers an operator trades against the SLO burn a shift relieves."""
    import shutil
    import tempfile

    from apex_tpu.models.gpt import GPTConfig, GPTModel
    from apex_tpu.observability.slo import SLOMonitor, SLOTarget
    from apex_tpu.optimizers import FusedAdam
    from apex_tpu.resilience import (CapacityController, ElasticComponents,
                                     ElasticPlan, ElasticTrainer,
                                     GuardedTrainStep, TopologySpec)
    from apex_tpu.serving import (FleetRouter, PagedInferenceEngine,
                                  TickScheduler, VirtualClock)
    from apex_tpu.utils.profiling import ServingMetrics

    _free_calibration()
    n = len(jax.devices())
    if n < 2:
        return {"skipped": "needs >= 2 devices"}
    dp = 4 if n >= 4 else 2

    def loss_fn(p, x, y):
        return jnp.mean(jnp.square(x @ p["w"] + p["b"] - y))

    def factory(plan, ckpt, inj):
        opt = FusedAdam(lr=1e-3, bucketed=False)
        guard = GuardedTrainStep(loss_fn, opt, warmup_steps=1,
                                 checkpoint=ckpt, fault_injector=inj)
        r = np.random.RandomState(7)
        params = plan.put(
            {"w": jnp.asarray((r.randn(512, 256) * 0.02).astype(np.float32)),
             "b": jnp.zeros((256,), jnp.float32)})
        return ElasticComponents(guard, params, opt.init(params),
                                 guard.init_state())

    def batch_fn(step, plan):
        r = np.random.RandomState(9_000 + step)
        return (jnp.asarray(r.randn(64, 512).astype(np.float32)),
                jnp.asarray(r.randn(64, 256).astype(np.float32)))

    clock = VirtualClock()
    cfg = GPTConfig(vocab_size=128, hidden_size=32, num_layers=2,
                    num_attention_heads=2, max_seq_len=64)
    model = GPTModel(cfg)
    mparams = model.init_params(jax.random.PRNGKey(0))

    def make_replica():
        slo = SLOMonitor([SLOTarget("ttft", 0.1, objective=0.9)],
                         clock=clock)
        return PagedInferenceEngine(
            model, mparams, max_slots=4, block_size=8,
            scheduler=TickScheduler(token_budget=64),
            metrics=ServingMetrics(clock, slo=slo), max_queue=32,
            clock=clock)

    fleet = FleetRouter([make_replica(), make_replica()], clock=clock)
    root = tempfile.mkdtemp(prefix="apex_tpu_bench_capacity_")
    try:
        trainer = ElasticTrainer(
            factory, ElasticPlan.build(TopologySpec(dp=dp)),
            directory=root, save_every=1)
        ctl = CapacityController(
            trainer, fleet, make_replica, min_train_dp=max(1, dp // 2),
            cooldown_s=0.0, clock=time.perf_counter)
        for _ in range(3):            # compile + steady state
            trainer.step_once(batch_fn)

        ctl.request_shift("to_serving")
        fleet.step()
        ctl.tick()
        clock.advance(0.01)
        assert ctl.stats["shifts"] == 1, ctl.shift_log
        to_serving = dict(ctl.stats["last_shift"])

        trainer.step_once(batch_fn)   # absorb the shrunk-plan recompile

        ctl.request_shift("to_training")
        ticks = 0
        while ctl.outstanding_leases or ctl.shifting:
            fleet.step()
            ctl.tick()
            clock.advance(0.01)
            ticks += 1
            assert ticks < 200, "capacity drain did not converge"
        assert ctl.stats["shifts"] == 2, ctl.shift_log
        to_training = dict(ctl.stats["last_shift"])

        rnd = lambda d: {k: (round(v, 5) if isinstance(v, float) else v)
                         for k, v in d.items()}
        return {"dp": dp, "shrink_dp": max(1, dp // 2),
                "replicas_leased": (dp - max(1, dp // 2)),
                "to_serving": rnd(to_serving),
                "to_training": rnd(to_training),
                "serving_drain_ticks": ticks}
    finally:
        shutil.rmtree(root, ignore_errors=True)


def bench_autopilot():
    """Self-driving-parallelism leg (ROADMAP item 3): what the closed
    drift -> refit -> re-rank -> gated-adoption loop costs.

    A :class:`ParallelismAutopilot` over a FusedAdam elastic trainer
    runs one full cycle against an injected interconnect drift: links
    go 16x slower (``cost_drift``), the refit window confirms it and
    the re-ranked plan commits through the measured baseline -> drain
    -> gate protocol; the links then recover with a
    ``plan_regression`` poisoning the re-adoption's gate, forcing the
    measured rollback.  Reported per phase, from the autopilot's own
    stats: ``refit_s`` (incremental cost-model refit), ``rank_s``
    (plan-space re-rank), ``drain_s`` + ``reshard_s`` (the adoption's
    boundary checkpoint and re-shard — the only training-visible
    cost), and ``rollback_s`` (replan back to the stamped old plan).
    Step times are driver-synthesized from the drifted alpha-beta
    curve (the controller is under test, not the toy model); the
    checkpoint/re-shard/rollback numbers are real wall time over the
    512x256 elastic trainer."""
    import shutil
    import tempfile

    from apex_tpu.observability import MetricsRegistry
    from apex_tpu.observability.costmodel import (
        CostFit, fit_cost_model, simulate_link_measurements)
    from apex_tpu.optimizers import FusedAdam
    from apex_tpu.resilience import (ElasticComponents, ElasticPlan,
                                     ElasticTrainer, Fault, FaultInjector,
                                     GuardedTrainStep,
                                     ParallelismAutopilot, TopologySpec)

    _free_calibration()
    n = len(jax.devices())
    if n < 2:
        return {"skipped": "needs >= 2 devices"}
    dp = 4 if n >= 4 else 2

    def loss_fn(p, x, y):
        return jnp.mean(jnp.square(x @ p["w"] + p["b"] - y))

    def factory(plan, ckpt, inj):
        opt = FusedAdam(lr=1e-3, bucketed=False)
        guard = GuardedTrainStep(loss_fn, opt, warmup_steps=1,
                                 checkpoint=ckpt, fault_injector=inj)
        r = np.random.RandomState(7)
        params = plan.put(
            {"w": jnp.asarray((r.randn(512, 256) * 0.02).astype(np.float32)),
             "b": jnp.zeros((256,), jnp.float32)})
        return ElasticComponents(guard, params, opt.init(params),
                                 guard.init_state())

    def batch_fn(step, plan):
        r = np.random.RandomState(9_000 + step)
        return (jnp.asarray(r.randn(64, 512).astype(np.float32)),
                jnp.asarray(r.randn(64, 256).astype(np.float32)))

    alpha0, beta0 = 2e-3, 1e-9
    grad_bytes = 512 * 256 * 4 + 256 * 4
    serial_s = 0.12

    def step_dt(step, cur_dp):
        scale = 1.0
        if step >= 2:
            scale *= 16.0
        if step >= 8:
            scale /= 16.0
        fit = CostFit(alpha0 * scale, beta0 * scale)
        comm = fit.predict("psum", grad_bytes, cur_dp) if cur_dp > 1 \
            else 0.0
        return serial_s / cur_dp + comm

    profile = fit_cost_model(
        simulate_link_measurements(alpha0, beta0, link_class="dcn",
                                   ops=("psum",)),
        meta={"source": "bench_autopilot"})
    inj = FaultInjector([Fault(2, "cost_drift", magnitude=16.0),
                         Fault(8, "cost_drift", magnitude=1.0 / 16.0),
                         Fault(8, "plan_regression", magnitude=4.0)])
    root = tempfile.mkdtemp(prefix="apex_tpu_bench_autopilot_")
    try:
        reg = MetricsRegistry()
        trainer = ElasticTrainer(
            factory, ElasticPlan.build(TopologySpec(dp=dp)),
            directory=root, save_every=1, fault_injector=inj)
        ap = ParallelismAutopilot(
            trainer, profile, min_dp=max(1, dp // 2),
            link_class="dcn", confirm_windows=2, min_measurements=8,
            cooldown_s=0.0, gate_steps=2, gate_tolerance=1.2,
            grad_bytes=grad_bytes, injector=inj, registry=reg)
        commit = None
        for step in range(16):
            trainer.step_once(batch_fn)
            ap.record_step(step_dt(step, trainer.plan.spec.dp))
            ap.tick()
            ap.tick()
            if commit is None and ap.stats["adoptions"] == 1:
                commit = dict(ap.stats["last_adoption"])
        assert ap.stats["adoptions"] == 1, ap.adoption_log
        assert ap.stats["rollbacks"] == 1, ap.adoption_log
        assert ap.audit() == [], ap.audit()
        rollback = dict(ap.stats["last_adoption"])

        rnd = lambda d: {k: (round(v, 5) if isinstance(v, float) else v)
                         for k, v in d.items()}
        return {"dp": dp, "shrink_dp": max(1, dp // 2),
                "grad_bytes": grad_bytes,
                "refit_windows": ap.stats["refits"],
                "refit_s": round(ap.stats["last_refit_s"], 5),
                "drift_confirmations": ap.stats["drift_confirmed"],
                "commit": rnd(commit),
                "rollback": rnd(rollback)}
    finally:
        shutil.rmtree(root, ignore_errors=True)


def bench_observability():
    """Observability leg (ISSUE 5): what monitoring costs.

    The SAME GuardedTrainStep GPT step run bare vs wrapped in
    ``TrainingMonitor`` (per-step wall timing, registry mutations for
    the step-time/tokens-s/grad-norm/loss/loss-scale series, one JSONL
    ``train_step`` record per step).  The monitor reads everything from
    the telemetry vector the guard's host readback already materializes
    — no extra device→host syncs — so the acceptance target is < 2%
    overhead.  Also round-trips the emitted stream through
    ``replay_jsonl`` so a broken exporter fails the leg, not a later
    consumer."""
    import io

    from apex_tpu.models.gpt import GPTConfig, GPTModel
    from apex_tpu.observability import (MetricsRegistry, TrainingMonitor,
                                        replay_jsonl)
    from apex_tpu.optimizers import FusedAdam
    from apex_tpu.resilience import GuardedTrainStep

    _free_calibration()
    rng = np.random.RandomState(5)
    cfg = GPTConfig(vocab_size=1024, hidden_size=256, num_layers=2,
                    num_attention_heads=8, max_seq_len=256)
    model = GPTModel(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    adam = FusedAdam(lr=1e-4, bucketed=False)
    tokens = jnp.asarray(rng.randint(0, cfg.vocab_size, (4, 256)))
    targets = jnp.asarray(rng.randint(0, cfg.vocab_size, (4, 256)))
    guard = GuardedTrainStep(model.loss, adam)

    hb = {"p": params, "o": adam.init(params), "g": guard.init_state()}

    def run_bare(tokens, targets):
        r = guard(hb["p"], hb["o"], hb["g"], tokens, targets)
        hb["p"], hb["o"], hb["g"] = r.params, r.opt_state, r.guard_state
        return r.loss

    buf = io.StringIO()
    reg = MetricsRegistry()
    reg.attach_stream(buf)
    mon = TrainingMonitor(reg, tokens_per_step=4 * 256)
    hm = {"p": params, "o": adam.init(params), "g": guard.init_state()}

    def step_mon(tokens, targets):
        r = guard(hm["p"], hm["o"], hm["g"], tokens, targets)
        hm["p"], hm["o"], hm["g"] = r.params, r.opt_state, r.guard_state
        return r

    monitored = mon.wrap(step_mon)

    def run_mon(tokens, targets):
        return monitored(tokens, targets).loss

    # paired windows: absolute timing drifts between windows (busy
    # host), so each pass times bare and monitored back-to-back
    # and the headline overhead is the median per-pass ratio
    passes = []
    for _ in range(5):
        t_b = _time_steps(run_bare, (tokens, targets), warmup=1,
                          iters=8, rounds=1)
        t_m = _time_steps(run_mon, (tokens, targets), warmup=1,
                          iters=8, rounds=1)
        passes.append((t_b, t_m))
    passes.sort(key=lambda p: p[1] / p[0])
    t_bare, t_mon = passes[len(passes) // 2]
    overhead = t_mon / t_bare - 1.0

    # the stream the monitored arm produced must replay and carry the
    # per-step keys an alerting pipeline needs
    replayed, records = replay_jsonl(buf.getvalue().splitlines())
    steps = [r for r in records if r.get("event") == "train_step"]
    stream_ok = (bool(steps)
                 and all({"step", "step_time_s", "tokens_per_s",
                          "grad_norm"} <= set(r) for r in steps)
                 and replayed.get("train_steps_total").value()
                 == mon.steps)
    return {"bare_step_s": round(t_bare, 6),
            "monitored_step_s": round(t_mon, 6),
            "monitor_overhead_frac": round(overhead, 4),
            "monitor_overhead_target": 0.02,
            "monitor_overhead_ok": bool(overhead < 0.02),
            "stream_records": len(records),
            "stream_ok": bool(stream_ok)}


def bench_serving_observability():
    """Serving-observability leg (ISSUE 7): what per-request tracing +
    SLO monitoring cost on the decode loop.

    The SAME continuous-batching engine workload (submit a batch of
    requests, drive ``step()`` to completion) run with default metrics
    vs fully instrumented — a ``Tracer`` attached (per-request async
    spans materialized at completion), an ``SLOMonitor`` classifying
    TTFT/token-latency/queue-wait, and the queue-wait/decode-ticks
    series live.  The hot-path additions are dict writes and int
    increments; span events materialize once per request, so the
    acceptance target is < 2% (paired windows, median per-pass ratio,
    same protocol as the training-observability leg)."""
    from apex_tpu.inference import InferenceEngine, Request
    from apex_tpu.models.gpt import GPTConfig, GPTModel
    from apex_tpu.observability import (MetricsRegistry, SLOMonitor,
                                        SLOTarget, Tracer)
    from apex_tpu.utils.profiling import ServingMetrics

    _free_calibration()
    cfg = GPTConfig(vocab_size=1024, hidden_size=256, num_layers=2,
                    num_attention_heads=8, max_seq_len=128)
    model = GPTModel(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    rng = np.random.RandomState(7)
    prompts = [list(rng.randint(1, cfg.vocab_size, 12)) for _ in range(8)]

    eng_bare = InferenceEngine(model, params, max_slots=4)
    tracer = Tracer(clock=time.monotonic)     # engine's clock domain
    slo = SLOMonitor([SLOTarget("ttft", 0.5, objective=0.95),
                      SLOTarget("token_latency", 0.1, objective=0.99)],
                     clock=time.monotonic)
    metrics = ServingMetrics(time.monotonic,
                             registry=MetricsRegistry(), slo=slo)
    eng_traced = InferenceEngine(model, params, max_slots=4,
                                 metrics=metrics, tracer=tracer)

    ids = {"n": 0}

    def run(eng):
        for p in prompts:
            ids["n"] += 1
            eng.submit(Request(request_id=ids["n"], prompt=p,
                               max_new_tokens=16))
        while eng.step():
            pass

    run(eng_bare)                             # compile outside timing
    run(eng_traced)
    passes = []
    for _ in range(5):
        t0 = time.perf_counter()
        run(eng_bare)
        t_b = time.perf_counter() - t0
        t0 = time.perf_counter()
        run(eng_traced)
        t_t = time.perf_counter() - t0
        passes.append((t_b, t_t))
        tracer.clear()                        # bound trace growth
    passes.sort(key=lambda p: p[1] / p[0])
    t_bare, t_traced = passes[len(passes) // 2]
    overhead = t_traced / t_bare - 1.0

    # the instrumented arm must actually have produced its artifacts
    n_done = ids["n"] - len(prompts)          # warmup pass excluded
    trace_ok = (eng_traced.trace.pending == 0
                and len(metrics.decode_ticks) > 0
                and metrics._h_queue_wait.count() == ids["n"] // 2
                and slo.snapshot()["percentiles"]["ttft"]["n"] > 0)
    return {"bare_window_s": round(t_bare, 6),
            "traced_window_s": round(t_traced, 6),
            "trace_overhead_frac": round(overhead, 4),
            "trace_overhead_target": 0.02,
            "trace_overhead_ok": bool(overhead < 0.02),
            "requests_per_window": len(prompts),
            "trace_ok": bool(trace_ok)}


def bench_serving_paged():
    """Paged-serving leg (ISSUE 10): the paged engine against the
    contiguous engine on the same shared-prefix workload.

    Three timed arms over an identical request set (8 requests, half
    sharing one 32-token system prompt, 24 new tokens each): the
    contiguous engine, the paged engine (prefix sharing on), and the
    paged engine with chunked prefill.  Reported: decode throughput and
    token agreement vs contiguous per arm, the paged pool's block
    savings and prefix hit rate, and — untimed — the speculative accept
    rate with a self-draft.  The PAGED arm's parity is asserted exact
    (it is the same attention reference over a gathered pool — bitwise
    by construction); chunked prefill and the speculative verify chunk
    are a different XLA compute schedule, so their agreement is
    MEASURED, not assumed — on a random-init model with near-flat
    logits even last-ulp rounding flips argmax, which trained-model
    margins absorb (the tier-1 tests pin exact agreement at their
    configs)."""
    from apex_tpu.inference import InferenceEngine, Request
    from apex_tpu.models.gpt import GPTConfig, GPTModel
    from apex_tpu.serving import (PagedInferenceEngine, SpeculativeConfig,
                                  TickScheduler)

    _free_calibration()
    cfg = GPTConfig(vocab_size=1024, hidden_size=256, num_layers=2,
                    num_attention_heads=8, max_seq_len=128)
    model = GPTModel(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    rng = np.random.RandomState(11)
    sysp = list(rng.randint(1, cfg.vocab_size, 32))
    prompts = [(sysp if i % 2 == 0 else []) +
               list(rng.randint(1, cfg.vocab_size, 12))
               for i in range(8)]

    def workload():
        return [Request(request_id=i, prompt=p, max_new_tokens=24)
                for i, p in enumerate(prompts)]

    def drive(eng):
        for r in workload():
            eng.submit(r)
        out = eng.run()
        return ({r.request_id: r.tokens for r in out},
                sum(len(r.tokens) for r in out))

    arms = {}
    tokens_ref = None
    mk = {
        "contiguous": lambda: InferenceEngine(model, params, max_slots=4),
        "paged": lambda: PagedInferenceEngine(model, params, max_slots=4,
                                              block_size=16),
        "paged_chunked": lambda: PagedInferenceEngine(
            model, params, max_slots=4, block_size=16,
            chunked_prefill=True,
            scheduler=TickScheduler(token_budget=64, min_chunk=16,
                                    max_chunk=32)),
    }
    pool_stats = {}

    def agreement(toks):
        return sum(toks[i] == tokens_ref[i] for i in tokens_ref) \
            / len(tokens_ref)

    for name, make in mk.items():
        drive(make())                          # compile outside timing

        def timed(make=make, name=name):
            eng = make()
            t0 = time.perf_counter()
            toks, n = drive(eng)
            dt = time.perf_counter() - t0
            if hasattr(eng, "pool"):
                pool_stats[name] = eng.pool.stats()
            return toks, n, dt
        toks, n, dt = timed()
        if tokens_ref is None:
            tokens_ref = toks
        agree = agreement(toks)
        if name == "paged":                    # bitwise by construction
            assert agree == 1.0, "paged arm diverged from contiguous"
        arms[name] = {"tokens": n, "window_s": round(dt, 6),
                      "tokens_per_s": round(n / dt, 2),
                      "token_agreement": round(agree, 4)}

    # speculative arm (untimed): accept rate + stream agreement
    spec = PagedInferenceEngine(
        model, params, max_slots=4, block_size=16,
        speculative=SpeculativeConfig(model, params, num_tokens=3))
    toks, _ = drive(spec)
    ps = pool_stats.get("paged", {})
    lookup = ps.get("prefix_lookup_tokens", 0)
    return {
        "arms": arms,
        "prefix_hit_rate": round(ps.get("prefix_hit_tokens", 0) / lookup,
                                 4) if lookup else 0.0,
        "paged_pool": ps,
        "spec_accept_rate": round(spec.spec_accept_rate, 4),
        "spec_token_agreement": round(agreement(toks), 4),
        "paged_parity_ok": True,
    }


def bench_serving_chaos():
    """Serving-chaos leg (ISSUE 12): recovery time under replica loss.

    Two chaos scenarios from ``tools/loadgen.py`` on a 3-replica CPU
    fleet over a virtual clock (deterministic, sleep-free):

    * ``replica_kill`` — a replica crashes mid-run; the metric is the
      detection -> migration -> first-resumed-token chain from the
      fleet's recovery report, in ticks and virtual seconds.
    * ``bursty`` — synchronized arrival bursts stress admission,
      retry/backoff, and the degradation ladder.

    Both scenarios are HARD-GATED on the exactly-once ledger (zero lost,
    zero client-visible duplicates) and on SLO attainment: losing a
    replica may cost tail latency, but never correctness."""
    import argparse
    import sys

    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "tools"))
    try:
        import loadgen
    finally:
        sys.path.pop(0)

    def ns(**kw):
        base = dict(
            scenario="replica_kill", requests=16, rate=1e9, replicas=3,
            max_slots=2, max_queue=64, max_queue_depth=4,
            burn_threshold=14.4, burn_window_s=60.0, ttft_slo_s=0.5,
            block_size=4, chunked=False, token_budget=32,
            client_retries=3, tick_s=0.02, e2e_slo_s=3.0, max_ticks=2000,
            retry_budget=4, hedge_after_s=None, ladder_step_down_s=0.5,
            kill_tick=4, kill_replica=1, kill_duration=10 ** 6,
            slow_tick=4, slow_s=0.1, slow_duration=40, burst_n=6,
            burst_gap_s=0.3, period_s=2.0, seed=0, min_prompt=4,
            pareto_shape=2.5, max_new=6, shared_prefix_prob=0.5,
            shared_prefix_len=8, num_prefixes=2, vocab=64, hidden=32,
            layers=2, heads=2, max_seq=48)
        base.update(kw)
        return argparse.Namespace(**base)

    out = {}
    for scenario in ("replica_kill", "bursty"):
        rep = loadgen.run_scenario(ns(scenario=scenario))
        # correctness gates: exactly-once, nothing stranded
        assert rep["lost"] == [], (scenario, rep["lost"])
        assert rep["duplicated"] == 0, scenario
        assert rep["fleet_pending"] == 0, scenario
        assert rep["slo_attainment"] >= 0.9, (scenario,
                                              rep["slo_attainment"])
        leg = {"responses": rep["responses"],
               "served": rep["e2e_served"],
               "slo_attainment": rep["slo_attainment"],
               "e2e_p50_s": rep["e2e_p50_s"],
               "e2e_p99_s": rep["e2e_p99_s"],
               "retries": rep["retries"],
               "migrations": rep["migrations"],
               "degraded_max_level": rep["degraded_max_level"],
               "ticks": rep["ticks"]}
        if scenario == "replica_kill":
            rec = rep["recovery"]
            assert rec["first_dead"] is not None, "kill never detected"
            assert rec["first_resumed_token"] is not None, \
                "migrated work never resumed"
            dead, resumed = rec["first_dead"], rec["first_resumed_token"]
            kill_t = ns().kill_tick * ns().tick_s
            leg["recovery"] = {
                "detect_ticks": dead["tick"] - ns().kill_tick,
                "detect_s": round(dead["t"] - kill_t, 4),
                "resume_ticks": resumed["tick"] - ns().kill_tick,
                "kill_to_first_resumed_token_s": round(
                    resumed["t"] - kill_t, 4)}
            assert leg["recovery"]["kill_to_first_resumed_token_s"] \
                >= 0.0
        out[scenario] = leg
    out["exactly_once_ok"] = True
    return out


def bench_serving_disagg():
    """Disaggregated-serving leg (ISSUE 16): the two-pool fleet and the
    quantized KV cache against the single-engine arms.

    Five arms over an identical request set (8 requests, half sharing
    one 32-token system prompt, 24 new tokens each):

    * ``contiguous`` — the slot-ring engine (KV bytes/user is the full
      preallocated ``max_seq`` stripe);
    * ``paged`` — the paged engine with chunked prefill (the mode
      every disagg engine runs, and the arm agreement is measured
      against);
    * ``disagg`` — a 1-prefill + 1-decode :class:`DisaggregatedFleet`
      on a virtual clock, f32 KV blocks over the handoff channel;
    * ``disagg_int8`` — the same fleet on the int8 scale-per-block
      :class:`QuantizedPagedKVCache`;
    * ``disagg_int8_weights`` — int8 KV *and* int8 decode weights
      (``GPTConfig(weight_quant="int8")``): every replica quantizes
      its param tree once at init and decodes through the fused
      dequant-GEMM, reported with weight HBM bytes per replica and
      the kv+weight bytes each concurrent user pays.

    Reported per arm: wall tokens/s, KV bytes per user (measured from
    the live cache buffers, not the spec), token agreement vs the paged
    arm; the disagg arms add handoff count/bytes and simulated seconds
    on the virtual clock.  Agreement is MEASURED, not asserted: with 8
    requests over 4 slots the single engine plans prefill chunks while
    decodes are in flight, a different chunk partitioning (= XLA
    schedule) than the prefill-only pool's, and on a random-init
    near-flat-logits model last-ulp rounding flips argmax — the tier-1
    tests and the CI dryrun pin exact parity at the configs where the
    schedules match.  The headline extra is the int8/f32 handoff byte
    ratio — the series the CI leg gates at < 0.30."""
    import dataclasses

    from apex_tpu.inference import InferenceEngine, Request
    from apex_tpu.models.gpt import GPTConfig, GPTModel
    from apex_tpu.serving import (DisaggregatedFleet, PagedInferenceEngine,
                                  TickScheduler, VirtualClock)
    from apex_tpu.utils.profiling import ServingMetrics

    _free_calibration()
    cfg = GPTConfig(vocab_size=1024, hidden_size=256, num_layers=2,
                    num_attention_heads=8, max_seq_len=128)
    model = GPTModel(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    rng = np.random.RandomState(11)
    sysp = list(rng.randint(1, cfg.vocab_size, 32))
    prompts = [(sysp if i % 2 == 0 else []) +
               list(rng.randint(1, cfg.vocab_size, 12))
               for i in range(8)]
    reqs = [Request(request_id=i, prompt=p, max_new_tokens=24)
            for i, p in enumerate(prompts)]

    def sched():
        return TickScheduler(token_budget=64, min_chunk=16, max_chunk=32)

    # int8-weight fleet arm: same f32 params in, the engine quantizes
    # once at init off the config knob
    qmodel = GPTModel(dataclasses.replace(cfg, weight_quant="int8"))

    def paged_engine(clock, quant=None, prefill_only=False, m=None):
        return PagedInferenceEngine(
            m or model, params, max_slots=4, block_size=16,
            chunked_prefill=True, scheduler=sched(), kv_quant=quant,
            prefill_only=prefill_only,
            metrics=ServingMetrics(clock), clock=clock)

    def fleet_arm(quant, m=None):
        clock = VirtualClock()
        # a 4-slot decode pool stays full for a whole 24-token decode:
        # let buffered handoffs wait for capacity instead of falling
        # back to re-prefill, so every request ships over the channel
        fleet = DisaggregatedFleet(
            [paged_engine(clock, quant, prefill_only=True, m=m)],
            [paged_engine(clock, quant, m=m)], clock=clock,
            handoff_retry_ticks=64)
        return fleet, clock

    def drive_engine(eng):
        for r in reqs:
            eng.submit(dataclasses.replace(r))
        out = eng.run()
        return ({r.request_id: r.tokens for r in out},
                sum(len(r.tokens) for r in out))

    def drive_fleet(fleet, clock):
        for r in reqs:
            fleet.submit(dataclasses.replace(r))
        for _ in range(2000):
            busy = fleet.step()
            clock.advance(0.01)
            if not busy and fleet.pending == 0:
                break
        out = fleet.completed
        return ({r.request_id: r.tokens for r in out},
                sum(len(r.tokens) for r in out))

    def paged_bytes_per_user(pool):
        # blocks a request's full sequence pins, ignoring prefix
        # sharing (the per-user worst case the capacity planner sizes)
        return pool.block_bytes * sum(
            pool.blocks_for(len(r.prompt) + r.max_new_tokens)
            for r in reqs) / len(reqs)

    arms = {}
    tokens_ref = None

    def agreement(toks):
        return sum(toks[i] == tokens_ref[i] for i in tokens_ref) \
            / len(tokens_ref)

    # -- single-engine arms ----------------------------------------------
    single = {
        "paged": lambda c: paged_engine(c),
        "contiguous": lambda c: InferenceEngine(
            model, params, max_slots=4, metrics=ServingMetrics(c),
            clock=c),
    }
    for name in ("paged", "contiguous"):       # paged first: the ref
        drive_engine(single[name](VirtualClock()))    # compile untimed

        def timed(name=name):
            clock = VirtualClock()
            eng = single[name](clock)
            t0 = time.perf_counter()
            toks, n = drive_engine(eng)
            dt = time.perf_counter() - t0
            if hasattr(eng, "pool"):
                per_user = paged_bytes_per_user(eng.pool)
            else:
                per_user = eng.cache.data.nbytes / eng.cache.data.shape[0]
            return toks, n, dt, per_user
        toks, n, dt, per_user = timed()
        if tokens_ref is None:
            tokens_ref = toks
        arms[name] = {"tokens": n, "window_s": round(dt, 6),
                      "tokens_per_s": round(n / dt, 2),
                      "kv_bytes_per_user": round(per_user, 1),
                      "token_agreement": round(agreement(toks), 4)}

    # -- disaggregated arms ----------------------------------------------
    handoff_bytes = {}
    weight_bytes = {}
    for name, quant, m in (("disagg", None, None),
                           ("disagg_int8", "int8", None),
                           ("disagg_int8_weights", "int8", qmodel)):
        f0, c0 = fleet_arm(quant, m)
        drive_fleet(f0, c0)                    # compile untimed

        def timed(quant=quant, m=m):
            fleet, clock = fleet_arm(quant, m)
            t0 = time.perf_counter()
            toks, n = drive_fleet(fleet, clock)
            dt = time.perf_counter() - t0
            return toks, n, dt, fleet, clock
        toks, n, dt, fleet, clock = timed()
        eng = fleet.decode.replicas[0]
        pool = eng.pool
        handoff_bytes[name] = fleet.channel.handoff_bytes
        weight_bytes[name] = eng.weight_bytes
        kv_per_user = paged_bytes_per_user(pool)
        arms[name] = {
            "tokens": n, "window_s": round(dt, 6),
            "tokens_per_s": round(n / dt, 2),
            "kv_bytes_per_user": round(kv_per_user, 1),
            "weight_bytes_per_replica": eng.weight_bytes,
            # weights amortize over the replica's concurrent users
            # (max_slots); KV is per user outright
            "kv_plus_weight_bytes_per_user": round(
                kv_per_user + eng.weight_bytes / 4, 1),
            "token_agreement": round(agreement(toks), 4),
            "handoffs": fleet.handoffs,
            "fallbacks": fleet.fallbacks,
            "handoff_bytes": fleet.channel.handoff_bytes,
            "sim_seconds": round(clock(), 4)}

    ratio = None
    if handoff_bytes.get("disagg") and handoff_bytes.get("disagg_int8"):
        ratio = round(handoff_bytes["disagg_int8"]
                      / handoff_bytes["disagg"], 4)
        assert ratio < 0.30, f"int8 handoff ratio {ratio} >= 0.30"
    wratio = None
    if weight_bytes.get("disagg") and weight_bytes.get("disagg_int8_weights"):
        wratio = round(weight_bytes["disagg_int8_weights"]
                       / weight_bytes["disagg"], 4)
        assert wratio < 0.30, \
            f"int8 weight byte ratio {wratio} >= 0.30"
    return {"arms": arms, "int8_handoff_byte_ratio": ratio,
            "int8_weight_byte_ratio": wratio}


def bench_lint():
    """Static-analysis leg (ISSUE 8): time the lint gate itself.

    Linting is compile-only and the gate is meant to ride in CI, so the
    metric is wall time per canonical program (<10 s each) plus the
    baseline diff.  The linter needs an 8-device mesh for the canonical
    dp/tp/pp programs and only compiles, so ``tools/lint_graph.py`` runs
    as a child pinned to the host CPU platform: it never asks for the
    chip this process holds."""
    import subprocess
    import sys

    script = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "tools", "lint_graph.py")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)       # lint_graph sets its own device count
    t0 = time.perf_counter()
    out = subprocess.run([sys.executable, script, "--json"],
                         capture_output=True, text=True, env=env,
                         timeout=600)
    wall = time.perf_counter() - t0
    if out.returncode != 0:
        raise RuntimeError(
            f"lint gate failed (exit {out.returncode}): "
            f"{out.stderr[-1500:]}")
    doc = json.loads(out.stdout)
    per_program = {p["program"]: p["elapsed_s"] for p in doc["programs"]}
    slowest = max(per_program.values()) if per_program else 0.0
    return {"programs": len(per_program),
            "findings": sum(len(p["findings"]) for p in doc["programs"]),
            "new_findings": sum(len(v) for v in
                                doc.get("new_findings", {}).values()),
            "per_program_s": {k: round(v, 3)
                              for k, v in per_program.items()},
            "slowest_program_s": round(slowest, 3),
            "per_program_target_s": 10.0,
            "per_program_ok": bool(slowest < 10.0),
            "total_wall_s": round(wall, 3)}


def _tool(name):
    """Import ``tools/<name>.py`` (the tools import their siblings by
    bare name, so the directory itself goes on the path)."""
    import importlib
    import sys

    tools = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                         "tools")
    if tools not in sys.path:
        sys.path.insert(0, tools)
    return importlib.import_module(name)


def bench_autotune():
    """Auto-parallel planner leg (ISSUE 11): predicted-vs-measured gap.

    Runs ``tools/autotune.autotune`` end-to-end on a small GPT over this
    process's own devices — enumerate, memory-prune, cost-model rank,
    measure top-3 — and reports how far the cost model's predictions
    sit from the wall clock it then measured.  In-process: a child
    could not reach the chips this process already holds."""
    n_dev = len(jax.devices())
    if n_dev < 2:
        return {"skipped": "needs >= 2 devices"}
    t0 = time.perf_counter()
    report = _tool("autotune").autotune(
        n_dev, max_tp=2, max_pp=2, zero=False, remat_options=(False,),
        verbose=False)
    wall = time.perf_counter() - t0
    measured = [c for c in report["candidates"]
                if c.get("measured_s") is not None]
    # the gap the cost model owes the user: per measured candidate,
    # |predicted - measured| / measured
    gaps = [abs(c["predicted_s"] - c["measured_s"]) / c["measured_s"]
            for c in measured]
    ranked = sorted((c for c in report["candidates"]
                     if c.get("predicted_s") is not None),
                    key=lambda c: c["predicted_s"])
    pred_best = ranked[0]["plan"] if ranked else None
    meas_best = min(measured, key=lambda c: c["measured_s"]) if measured \
        else None
    return {"candidates": len(report["candidates"]),
            "measured": len(measured),
            "winner": report["plan"],
            "predicted_s": report.get("predicted_s"),
            "measured_s": report.get("measured_s"),
            "gap_mean": round(sum(gaps) / len(gaps), 4) if gaps else None,
            "gap_max": round(max(gaps), 4) if gaps else None,
            "predicted_best_is_measured_best": bool(
                pred_best is not None and meas_best is not None
                and pred_best == meas_best["plan"]),
            "total_wall_s": round(wall, 3)}


def bench_mpmd():
    """Cross-pod MPMD schedule leg (ISSUE 14): how much of a slow DCN
    hop each schedule hides.

    Prices classic 1F1B under blocking sends (the lockstep/SPMD model:
    every inter-pod hop sits on the critical path) against the
    ``dcn_hiding`` schedule under asynchronous sends (the MPMD host
    model: extra in-flight microbatches buffer the hop) with the
    ``apex_tpu.mpmd.schedule.simulate`` event model — 4 stages split
    across 2 pods, the DCN edge costing ~half a forward.  Pure host
    arithmetic (no devices), so the recorded bubble fractions are
    deterministic across rounds and ``bench_diff``-able; the MPMD
    engine's numerics ride the tier-1 gate
    (``__graft_entry__._dryrun_mpmd``), not this leg."""
    from apex_tpu.mpmd.schedule import (SCHEDULES, edge_link_classes,
                                        simulate)

    S, M, pods = 4, 8, 2
    t_fwd, t_bwd = 1.0, 2.0
    classes = edge_link_classes(S, pods)
    rows = {}
    for dcn_s in (0.0, 1.5):
        link = {e: (dcn_s if lc == "dcn" else 0.05)
                for e, lc in classes.items()}
        for name in ("1f1b", "dcn_hiding"):
            sim = simulate(SCHEDULES[name](S, M), S, M, t_fwd=t_fwd,
                           t_bwd=t_bwd, link_seconds=link,
                           link_classes=classes,
                           blocking_sends=(name == "1f1b"))
            rows[f"{name}_dcn{dcn_s:g}"] = {
                "makespan": round(sim["makespan"], 3),
                "bubble_fraction": round(sim["bubble_fraction"], 4),
                "dcn_hidden_fraction": round(
                    sim["hidden_fraction"]["dcn"], 4),
            }
    slow_base = rows["1f1b_dcn1.5"]
    slow_tuned = rows["dcn_hiding_dcn1.5"]
    return {
        "stages": S, "microbatches": M, "pods": pods,
        "t_fwd": t_fwd, "t_bwd": t_bwd, "dcn_link_s": 1.5,
        "schedules": rows,
        "bubble_reduction_vs_1f1b": round(
            slow_base["bubble_fraction"] - slow_tuned["bubble_fraction"],
            4),
        "speedup_vs_1f1b": round(
            slow_base["makespan"] / slow_tuned["makespan"], 4),
        "dcn_tuned_wins": bool(
            slow_tuned["bubble_fraction"] < slow_base["bubble_fraction"]),
    }


def bench_fused_ffn():
    """Fused-FFN leg (ISSUE 17): the Pallas fused bias-GELU FFN pair vs
    the unfused XLA chain, fwd+bwd at the BERT-large headline FFN shape
    (16x512 tokens, 1024 -> 4096 -> 1024, bf16).

    The fused arm runs the kernel and the speedup prices the
    HBM round-trip of the ``(tokens, ffn_hidden)`` activation the
    unfused chain pays between its two GEMMs; the recorded ``path``
    says which arm ran.  Tiling sweeps live in ``tools/sweep_ffn.py``."""
    from apex_tpu.ops.fused_ffn import fused_ffn, fused_ffn_reference
    from apex_tpu.utils import use_pallas

    m, h, f = 16 * 512, 1024, 4096
    rng = np.random.RandomState(0)
    bf = jnp.bfloat16
    x = jnp.asarray(rng.randn(m, h), bf)
    w1 = jnp.asarray(rng.randn(f, h) * 0.02, bf)
    b1 = jnp.asarray(rng.randn(f) * 0.02, bf)
    w2 = jnp.asarray(rng.randn(h, f) * 0.02, bf)
    b2 = jnp.asarray(rng.randn(h) * 0.02, bf)
    args = (x, w1, b1, w2, b2)

    def grad_of(ffn):
        def loss(*a):
            return jnp.sum(ffn(*a).astype(jnp.float32))
        return jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4)))

    t_unfused = _time_steps(grad_of(fused_ffn_reference), args,
                            warmup=2, iters=8, rounds=3)
    jax.clear_caches()
    t_fused = _time_steps(grad_of(fused_ffn), args,
                          warmup=2, iters=8, rounds=3)
    jax.clear_caches()
    out = {"tokens": m, "hidden": h, "ffn_hidden": f,
           "dtype": "bfloat16",
           "path": "pallas" if use_pallas() else "reference",
           "unfused_s": round(t_unfused, 6),
           "fused_s": round(t_fused, 6)}
    out["speedup"] = round(t_unfused / t_fused, 4)
    return out


def bench_mfu_multichip():
    """Multi-chip MFU leg (ISSUE 17): per-chip achieved FLOPs and MFU
    for dp x tp train steps with the fused-FFN knob on, plus the
    autotune planner's predicted-vs-measured gap at those plans.

    Runs ``tools/mfu_multichip.measure`` over this process's own devices
    (in-process: a child could not reach chips this process holds).  The
    MFU denominator is the same calibrated matmul roofline the planner
    ranks with."""
    n_dev = len(jax.devices())
    if n_dev < 2:
        return {"skipped": "needs >= 2 devices"}
    t0 = time.perf_counter()
    report = _tool("mfu_multichip").measure(n_dev, batch=8, quiet=True)
    report["total_wall_s"] = round(time.perf_counter() - t0, 3)
    return report


def bench_anatomy():
    """Step-anatomy leg (ISSUE 20): what the measured critical-path
    profiler costs and whether its attribution stays exact.

    Three parts.  (1) A deterministic synthetic core: simulate a
    4-stage/8-microbatch 1F1B schedule with a slow DCN edge,
    synthesize its trace events, reconstruct + attribute, and
    self-diff against the generating simulation — the attribution
    must sum to the makespan exactly, per-op ratios must cover every
    op, and the self-diff drift must be ~0 (pure host arithmetic, so
    the recorded fractions are bench_diff-able across rounds).
    (2) The paired-window trace-overhead gate: the SAME dp2 x pp2
    ``MpmdPipeline`` step run bare vs ``trace=True`` back-to-back,
    median per-pass ratio, < 2% target — the established
    observability-leg protocol.  (3) One ``measure_ops=True`` step
    reconstructed and attributed for real (wall numbers advisory:
    host-serial dispatch on a shared CPU is honest but noisy)."""
    from apex_tpu.mpmd.schedule import (SCHEDULES, edge_link_classes,
                                        simulate)
    from apex_tpu.observability.anatomy import (
        CATEGORIES, attribute, diff_timelines, reconstruct,
        synthesize_events)

    S, M, pods = 4, 8, 2
    classes = edge_link_classes(S, pods)
    link = {e: (1.5 if lc == "dcn" else 0.05)
            for e, lc in classes.items()}
    order = SCHEDULES["1f1b"](S, M)
    sim = simulate(order, S, M, t_fwd=1.0, t_bwd=2.0,
                   link_seconds=link, link_classes=classes,
                   blocking_sends=False)
    evs = synthesize_events(sim, n_stages=S, n_microbatches=M)
    reps = 20
    t0 = time.perf_counter()
    for _ in range(reps):
        tl = reconstruct(evs)
        attr = attribute(tl)
    anat_s = (time.perf_counter() - t0) / reps
    err = max(abs(st["total"] - attr["makespan"])
              for st in attr["per_stage"]) / attr["makespan"]
    self_diff = diff_timelines(tl, sim)
    out = {
        "stages": S, "microbatches": M, "events": len(evs),
        "reconstruct_attribute_s_advisory": round(anat_s, 6),
        "attribution_rel_err": float(err),
        "attribution_exact": bool(err < 1e-9),
        "fractions": {c: round(attr["fractions"][c], 4)
                      for c in CATEGORIES},
        "self_drift_score": round(self_diff["drift_score"], 6),
        "ratios_cover_all_ops": bool(
            len(self_diff["ratios"]) == 2 * S * M),
    }

    n = len(jax.devices())
    if n < 4:
        out["engine"] = {"skipped": "needs >= 4 devices"}
        return out
    from apex_tpu.models.gpt import GPTConfig, GPTModel
    from apex_tpu.mpmd import MpmdPipeline
    from apex_tpu.parallel.plan import ParallelPlan

    _free_calibration()
    kw = dict(vocab_size=256, hidden_size=64, num_layers=4,
              num_attention_heads=4, max_seq_len=32)
    model = GPTModel(GPTConfig(**kw))
    params = model.init_params(jax.random.PRNGKey(0))
    plan = ParallelPlan(dp=2, pp=2, n_pods=2, n_microbatches=4)
    devs = jax.devices()[:4]
    rng = np.random.RandomState(0)
    tokens = jnp.asarray(rng.randint(0, 256, (2 * 4 * 2, 32)))
    targets = jnp.asarray(rng.randint(0, 256, (2 * 4 * 2, 32)))
    bare = MpmdPipeline(kw, params, plan, devices=devs)
    traced = MpmdPipeline(kw, params, plan, devices=devs, trace=True)

    def run_bare(tk, tg):
        return bare.loss_and_grads(tk, tg, step=0)[0]

    def run_traced(tk, tg):
        for tr in traced.tracers:   # bound the event buffers
            tr.clear()
        return traced.loss_and_grads(tk, tg, step=0)[0]

    # paired windows: time bare and traced back-to-back each pass,
    # headline is the median per-pass ratio (the < 2% protocol of
    # bench_observability); a ~60ms host-serial step needs wide
    # windows and several passes for the median to beat shared-host
    # scheduler noise down to the gate's resolution
    passes = []
    for _ in range(9):
        t_b = _time_steps(run_bare, (tokens, targets), warmup=1,
                          iters=10, rounds=1)
        t_t = _time_steps(run_traced, (tokens, targets), warmup=1,
                          iters=10, rounds=1)
        passes.append((t_b, t_t))
    passes.sort(key=lambda p: p[1] / p[0])
    t_b, t_t = passes[len(passes) // 2]
    overhead = t_t / t_b - 1.0
    out["engine"] = {
        "bare_step_s_advisory": round(t_b, 6),
        "traced_step_s_advisory": round(t_t, 6),
        "trace_overhead_frac": round(overhead, 4),
        "trace_overhead_target": 0.02,
        "trace_overhead_ok": bool(overhead < 0.02),
    }

    # one honest measured step: block on every op, reconstruct,
    # attribute, diff against the schedule priced at measured medians
    anat = MpmdPipeline(kw, params, plan, devices=devs,
                        measure_ops=True)
    anat.loss_and_grads(tokens, targets, step=0)     # compile warmup
    for tr in anat.tracers:
        tr.clear()
    anat.loss_and_grads(tokens, targets, step=1)
    tl_r = reconstruct(anat.anatomy_events())
    attr_r = attribute(tl_r)
    err_r = max(abs(st["total"] - attr_r["makespan"])
                for st in attr_r["per_stage"]) / attr_r["makespan"]

    med = lambda xs: sorted(xs)[len(xs) // 2] if xs else 1e-6
    durs = {"fwd": [], "bwd": []}
    for o in tl_r.ops:
        durs[o["kind"]].append(o["end"] - o["start"])
    by_edge = {}
    for x in tl_r.xfers:
        if x["mb"] >= 0:
            by_edge.setdefault(min(x["src"], x["dst"]), []).append(
                x["end"] - x["start"])
    sim_r = simulate(anat.order, 2, 4,
                     t_fwd=med(durs["fwd"]) or med(durs["bwd"]),
                     t_bwd=med(durs["bwd"]),
                     link_seconds={e: med(ts)
                                   for e, ts in by_edge.items()},
                     link_classes=edge_link_classes(2, 2),
                     blocking_sends=False)
    d_r = diff_timelines(tl_r, sim_r, fold_last_fwd=True)
    out["measured"] = {
        "makespan_s_advisory": round(tl_r.makespan, 6),
        "n_ops": len(tl_r.ops),
        "attribution_rel_err": float(err_r),
        "attribution_exact": bool(err_r < 1e-9),
        "ratios_cover_all_ops": bool(
            d_r["matched"] == d_r["n_ops"] == len(tl_r.ops)),
        # real wall seconds on a shared host: advisory per key so a
        # noisy round never flags a phantom component regression —
        # the *.anatomy.json sidecar carries these for bench_diff's
        # attribution-delta printing instead
        **{f"{c}_s_advisory": round(attr_r["totals"][c], 6)
           for c in CATEGORIES},
        "drift_score_advisory": round(d_r["drift_score"], 4),
        **{f"{c}_frac_advisory": round(attr_r["fractions"][c], 4)
           for c in CATEGORIES},
    }
    return out


def _extra_legs():
    """Leg name (as it appears under the result's ``extra``) -> bench
    function, for ``--legs`` subset runs."""
    return {
        "bert_large_lamb": bench_bert_lamb_train_step,
        "breakdown": bench_bert_breakdown,
        "lamb_in_step": bench_lamb_in_step,
        "gpt": bench_gpt_train_step,
        "gpt_decode": bench_gpt_decode,
        "fused_adam_vs_optax": bench_fused_adam_vs_optax,
        "dp_comm": bench_dp_comm,
        "tp_overlap": bench_tp_overlap,
        "pp_schedules": bench_pp_schedules,
        "resilience": bench_resilience,
        "elastic": bench_elastic,
        "capacity": bench_capacity,
        "autopilot": bench_autopilot,
        "observability": bench_observability,
        "serving_observability": bench_serving_observability,
        "serving_paged": bench_serving_paged,
        "serving_chaos": bench_serving_chaos,
        "serving_disagg": bench_serving_disagg,
        "lint": bench_lint,
        "autotune": bench_autotune,
        "mpmd": bench_mpmd,
        "anatomy": bench_anatomy,
        "fused_ffn": bench_fused_ffn,
        "mfu_multichip": bench_mfu_multichip,
    }


def _headline_of(leg_name: str, leg: dict):
    """A representative (metric, value) for a subset run's headline:
    the first ``tokens_per_s`` / ``mfu`` / ``speedup`` leaf, else the
    first numeric leaf."""
    def flat(d, pre=""):
        for k, v in d.items():
            if isinstance(v, dict):
                yield from flat(v, f"{pre}{k}.")
            elif isinstance(v, (int, float)) and not isinstance(v, bool):
                yield f"{pre}{k}", float(v)
    pairs = list(flat(leg))
    for pat in ("tokens_per_s", "mfu", "speedup"):
        for k, v in pairs:
            if pat in k:
                return f"{leg_name}.{k}", v
    if pairs:
        return f"{leg_name}.{pairs[0][0]}", pairs[0][1]
    return leg_name, 0.0


def _main_subset(names):
    """Run only the named extra legs (no headline BERT leg) and print
    the same one-line JSON shape ``main()`` does, headlined by the
    first leg's primary metric."""
    table = _extra_legs()
    unknown = [n for n in names if n not in table]
    if unknown:
        raise SystemExit(f"unknown legs: {unknown}; "
                         f"choose from {sorted(table)}")
    extra = {"backend": jax.default_backend(),
             "device_kind": jax.devices()[0].device_kind}
    for n in names:
        extra[n] = table[n]()
    metric, value = _headline_of(names[0], extra[names[0]])
    print(json.dumps({"metric": metric, "value": round(value, 4),
                      "unit": "per_leg", "legs": names, "extra": extra}))


def main(argv=None):
    import argparse
    ap = argparse.ArgumentParser(
        description="apex_tpu bench: one JSON line on stdout")
    ap.add_argument("--legs", default=None,
                    help="comma-separated subset of extra legs to run "
                         "(e.g. serving_disagg,serving_paged); the "
                         "headline BERT leg and every unlisted leg are "
                         "skipped, and the first listed leg's primary "
                         "metric becomes the headline")
    args = ap.parse_args(argv)
    setup_compile_cache()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(
            f"bench.py measures a TPU; JAX found platform "
            f"{dev.platform!r} ({dev.device_kind}). A CPU run has no "
            "device timings to report.")
    if args.legs is not None:
        return _main_subset([s for s in args.legs.split(",") if s])
    backend = jax.default_backend()
    # every leg's result also lands on the metrics registry as one
    # `bench_leg` JSONL record (ISSUE 5) — BENCH output carries a
    # `metrics_stream` pointer to the stream file
    from apex_tpu.observability import MetricsRegistry

    stream_path = os.environ.get("APEX_TPU_METRICS_STREAM",
                                 "bench_metrics.jsonl")
    registry = MetricsRegistry()
    try:
        registry.open_stream(stream_path)
    except OSError:
        stream_path = None
    bert = bench_bert_lamb_train_step()
    gpt = bench_gpt_train_step()
    decode = bench_gpt_decode()
    breakdown = bench_bert_breakdown()
    in_step = bench_lamb_in_step()
    adam = bench_fused_adam_vs_optax()
    dp_comm = bench_dp_comm()
    tp_overlap = bench_tp_overlap()
    pp_schedules = bench_pp_schedules()
    resilience = bench_resilience()
    elastic = bench_elastic()
    capacity = bench_capacity()
    autopilot = bench_autopilot()
    observability = bench_observability()
    serving_obs = bench_serving_observability()
    serving_paged = bench_serving_paged()
    serving_chaos = bench_serving_chaos()
    serving_disagg = bench_serving_disagg()
    lint_gate = bench_lint()
    autotune_leg = bench_autotune()
    mpmd = bench_mpmd()
    anatomy = bench_anatomy()
    fused_ffn_leg = bench_fused_ffn()
    mfu_multichip = bench_mfu_multichip()
    rounded = lambda d: {k: (round(v, 6) if isinstance(v, float) else v)
                         for k, v in d.items()}
    # headline = the binding BASELINE.md row-1 workload (BERT-large +
    # FusedLAMB + amp O2); the GPT and optimizer legs ride in `extra`
    result = {
        "metric": "bert_large_lamb_mfu",
        "value": round(bert["mfu"], 4),
        "unit": "fraction_of_peak_bf16",
        "vs_baseline": round(bert["mfu"] / 0.5, 4),  # >=50% MFU target
        "extra": {
            "backend": backend,
            "device_kind": jax.devices()[0].device_kind,
            "bert_large_lamb": rounded(bert),
            "breakdown": breakdown,
            "lamb_in_step": in_step,
            "gpt_350m_train_mfu": round(gpt["mfu"], 4),
            "gpt": rounded(gpt),
            "gpt_decode": rounded(decode),
            "fused_adam_vs_optax": rounded(adam),
            "dp_comm": dp_comm,
            "tp_overlap": tp_overlap,
            "pp_schedules": pp_schedules,
            "resilience": resilience,
            "elastic": elastic,
            "capacity": capacity,
            "autopilot": autopilot,
            "observability": rounded(observability),
            "serving_observability": rounded(serving_obs),
            "serving_paged": serving_paged,
            "serving_chaos": serving_chaos,
            "serving_disagg": serving_disagg,
            "lint": lint_gate,
            "autotune": autotune_leg,
            "mpmd": mpmd,
            "anatomy": anatomy,
            "fused_ffn": fused_ffn_leg,
            "mfu_multichip": mfu_multichip,
        },
    }
    result["metrics_stream"] = stream_path
    if stream_path is not None:
        g_mfu = registry.gauge("bench_bert_mfu",
                               "headline BERT-large MFU (spec)")
        g_mfu.set(bert["mfu"])
        for leg, res in result["extra"].items():
            if isinstance(res, dict):
                registry.event("bench_leg", leg=leg, result=res)
        registry.close()
    print(json.dumps(result))


if __name__ == "__main__":
    main()
