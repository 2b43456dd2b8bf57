#!/usr/bin/env python
"""On-chip BERT-large profiling: remat/batch sweep + per-component
breakdown (VERDICT r4 items 1+2).

Runs each candidate train-step config with the bench.py timing
protocol and prints tokens/s; then times isolated sub-components at the
headline step's shapes (batch 16 x seq 512, x2 accumulation
microbatches; the optimizer runs once per step) so the bench can ship a
`breakdown` dict whose component seconds sum comparably to the headline
step.

Usage:
    python tools/profile_bert.py sweep      # remat/batch sweep
    python tools/profile_bert.py breakdown  # per-component attribution
"""

from __future__ import annotations

import functools
import sys

import jax
import jax.numpy as jnp
import numpy as np

from _timing import time_steps as _time  # noqa: E402 (sets sys.path)


def make_step(batch, remat, policy, accum=1, leaf=False):
    from apex_tpu import amp
    from apex_tpu.models.bert import BertConfig, BertModel
    from apex_tpu.optimizers import FusedLAMB

    cfg = BertConfig(hidden_size=1024, num_layers=24,
                     num_attention_heads=16, max_seq_len=512,
                     remat=remat, remat_policy=policy,
                     dtype=jnp.bfloat16)
    seq = 512
    model = BertModel(cfg)
    lamb = FusedLAMB(lr=1e-3, bucketed=not leaf)
    state = amp.initialize(model.loss, lamb, opt_level="O2")
    params = state.cast_params(model.init_params(jax.random.PRNGKey(0)))
    opt_state = lamb.init(params)

    rng = np.random.RandomState(0)
    tokens = jnp.asarray(rng.randint(0, cfg.vocab_size,
                                     (accum, batch, seq)))
    labels = np.where(rng.rand(accum, batch, seq) < 0.15,
                      rng.randint(0, cfg.vocab_size, (accum, batch, seq)),
                      -1)
    labels = jnp.asarray(labels)

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def train_step(params, opt_state, tokens, labels):
        if accum == 1:
            loss, grads = jax.value_and_grad(state.apply_fn)(
                params, tokens[0], labels[0])
        else:
            def mb(carry, tl):
                tk, lb = tl
                l, g = jax.value_and_grad(state.apply_fn)(params, tk, lb)
                acc_l, acc_g = carry
                return (acc_l + l,
                        jax.tree_util.tree_map(jnp.add, acc_g, g)), None
            zero = (jnp.zeros(()),
                    jax.tree_util.tree_map(jnp.zeros_like, params))
            (loss, grads), _ = jax.lax.scan(mb, zero, (tokens, labels))
            inv = 1.0 / accum
            loss = loss * inv
            grads = jax.tree_util.tree_map(lambda g: g * inv, grads)
        new_params, new_opt = lamb.step(grads, params, opt_state)
        return loss, new_params, new_opt

    holder = {"params": params, "opt": opt_state}

    def run(tokens, labels):
        loss, holder["params"], holder["opt"] = train_step(
            holder["params"], holder["opt"], tokens, labels)
        return loss

    return run, (tokens, labels), batch * accum * seq


def sweep():
    configs = [
        ("b32_full", dict(batch=32, remat=True, policy="full")),
        ("b16_dots", dict(batch=16, remat=True, policy="dots")),
        ("b24_dots", dict(batch=24, remat=True, policy="dots")),
        ("b32_dots", dict(batch=32, remat=True, policy="dots")),
        ("b16x2_dots", dict(batch=16, remat=True, policy="dots",
                            accum=2)),
        ("b8_none", dict(batch=8, remat=False, policy="full")),
        ("b16_none", dict(batch=16, remat=False, policy="full")),
        ("b32_dots_leaf", dict(batch=32, remat=True, policy="dots",
                               leaf=True)),
        ("b16x2_dots_leaf", dict(batch=16, remat=True, policy="dots",
                                 accum=2, leaf=True)),
        ("b24_dots_leaf", dict(batch=24, remat=True, policy="dots",
                               leaf=True)),
        ("b16_none_leaf", dict(batch=16, remat=False, policy="full",
                               leaf=True)),
        ("b24_none_leaf", dict(batch=24, remat=False, policy="full",
                               leaf=True)),
        ("b32_none_leaf", dict(batch=32, remat=False, policy="full",
                               leaf=True)),
        ("b16x2_none_leaf", dict(batch=16, remat=False, policy="full",
                                 accum=2, leaf=True)),
    ]
    if len(sys.argv) > 2:                  # run a subset by name
        names = set(sys.argv[2].split(","))
        configs = [c for c in configs if c[0] in names]
    for name, kw in configs:
        try:
            run, args, tokens_per_step = make_step(**kw)
            dt = _time(run, args)
            print(f"{name}: {tokens_per_step / dt:,.0f} tok/s "
                  f"(step {dt * 1e3:.1f} ms)", flush=True)
        except Exception as e:  # OOM etc.
            msg = str(e).split("\n")[0][:160]
            print(f"{name}: FAILED {type(e).__name__}: {msg}", flush=True)
        # free everything between configs
        jax.clear_caches()


def breakdown():
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

    def t_grad(fn, *args, iters=8):
        """fwd+bwd time of mean(fn) w.r.t. all args."""
        g = jax.jit(jax.grad(lambda *a: jnp.mean(fn(*a).astype(
            jnp.float32)), argnums=tuple(range(len(args)))))
        return _time(g, args, iters=iters)

    def t_chain(fn_one, x0, *consts, reps=24):
        """fwd+bwd of ``reps`` chained applications inside ONE jitted
        program (per-dispatch host overhead would otherwise dominate a
        single-op program); returns seconds PER application."""
        def loss(x, *cs):
            def body(c, _):
                return fn_one(c, *cs), None
            y, _ = jax.lax.scan(body, x, None, length=reps)
            return jnp.mean(y.astype(jnp.float32))
        g = jax.jit(jax.grad(loss, argnums=tuple(range(1 + len(consts)))))
        return _time(g, (x0,) + consts) / reps

    out = {}

    def done(name, sec):
        out[name] = sec
        print(f"  {name:>16}: {sec * 1e3:7.1f} ms", flush=True)
        jax.clear_caches()

    # attention: chained flash fwd+bwd (q carries), per-layer x L
    q = jnp.asarray(rng.randn(b, nh, s, hd), bf)
    k = jnp.asarray(rng.randn(b, nh, s, hd), bf)
    v = jnp.asarray(rng.randn(b, nh, s, hd), bf)
    done("attention", accum * L * t_chain(
        lambda q, k, v: flash_attention(q, k, v, causal=False), q, k, v))
    del q, k, v

    # qkv + proj GEMMs: (b*s, h) x (h, 3h) and (b*s, h) x (h, h)
    x = jnp.asarray(rng.randn(b * s, h), bf)
    wqkv = jnp.asarray(rng.randn(h, 3 * h) * 0.02, bf)
    wproj = jnp.asarray(rng.randn(h, h) * 0.02, bf)
    done("qkv_proj_gemms", accum * L * t_chain(
        lambda x, a, c: ((x @ a)[:, :h] @ c), x, wqkv, wproj))
    del wqkv, wproj

    # FFN: (b*s, h) -> 4h -> gelu -> h (reps capped: the scan saves the
    # (b*s, 4h) gelu inputs per rep, ~300 MB each)
    w1 = jnp.asarray(rng.randn(h, f) * 0.02, bf)
    w2 = jnp.asarray(rng.randn(f, h) * 0.02, bf)
    done("ffn", accum * L * t_chain(
        lambda x, w1, w2: jax.nn.gelu(x @ w1, approximate=True) @ w2,
        x, w1, w2, reps=8))
    del w1, w2

    # layer norm: 2 per layer + embedding/mlm LNs ~ 2L
    ln = MixedFusedLayerNorm(h)
    lp = ln.init_params()
    xf = jnp.asarray(rng.randn(b, s, h), bf)
    done("layernorm", accum * 2 * L * t_chain(
        lambda x, p: ln(p, x), xf, lp, reps=48))
    del xf, lp

    # LM head: fused linear CE over the full vocab (device work per
    # dispatch ~50 ms, overhead negligible — no chaining needed)
    emb = jnp.asarray(rng.randn(V, h) * 0.02, bf)
    tgt = jnp.asarray(rng.randint(0, V, (b * s,)))
    done("lm_head_ce", accum * t_grad(
        lambda hd_, w: fused_linear_cross_entropy(hd_, w, tgt),
        x, emb, iters=4))
    del x, emb, tgt

    # optimizer: FusedLAMB step on the BERT census
    shapes = []
    for _ in range(L):
        shapes += [(3 * h, h), (3 * h,), (h, h), (h,), (f, h), (f,),
                   (h, f), (h,), (h,), (h,), (h,), (h,)]
    shapes += [(V, h), (512, h), (2, h), (h, h), (h,), (h,), (h,)]
    params = [jnp.asarray(rng.randn(*sh).astype(np.float32) * 0.02)
              for sh in shapes]
    grads = [jnp.asarray(rng.randn(*sh).astype(np.float32) * 1e-3)
             for sh in shapes]
    lamb = FusedLAMB(lr=1e-3)
    lstate = lamb.init(params)

    reps = 4

    @functools.partial(jax.jit, donate_argnums=(1, 2))
    def lamb_steps(grads, params, state):
        def body(c, _):
            p, s = c
            return lamb.step(grads, p, s), None
        (p, s), _ = jax.lax.scan(body, (params, state), None, length=reps)
        return p, s

    def run(grads):
        nonlocal params, lstate
        params, lstate = lamb_steps(grads, params, lstate)
        return params

    done("optimizer_lamb", _time(run, (grads,), iters=4) / reps)

    total = sum(out.values())
    print("component breakdown (fwd+bwd isolated, x layer count x 2 "
          "accum; optimizer once per step):")
    for k_, v_ in sorted(out.items(), key=lambda kv: -kv[1]):
        print(f"  {k_:>16}: {v_ * 1e3:7.1f} ms  ({v_ / total:5.1%})")
    print(f"  {'sum':>16}: {total * 1e3:7.1f} ms")


if __name__ == "__main__":
    from apex_tpu.utils.platform import setup_compile_cache
    setup_compile_cache()
    {"sweep": sweep, "breakdown": breakdown}[sys.argv[1]]()
