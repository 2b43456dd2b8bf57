#!/usr/bin/env python
"""Measure one 7B pipeline stage on the real chip and project
tokens/sec/chip for the BASELINE.md row-2 workload (GPT ~7B via TP x PP
on a v5e-64 pod) from measured stage time + modeled ICI boundary cost.

Method (written into BASELINE.md):

* The 7B recipe (examples/gpt7b: hidden 4096, 32 layers, seq 2048,
  tp=4 x pp=4 x dp=4 on 64 chips) gives each pipeline stage 8 layers,
  each layer's GEMMs sharded 4-way over TP.  A single chip therefore
  executes per microbatch tick: 8 layers at hidden 4096 with 1/4 of
  every GEMM's output features (qkv 4096->3072, proj 1024->4096,
  fc1 4096->4096, fc2 4096->4096 per-rank shards).
* This script times exactly that stage (fwd+bwd, bf16, remat off) on
  one chip at micro-batch 1 x seq 2048.
* The pipeline bubble is (pp-1)/(M+pp-1) with M microbatches per rank;
  the stage-boundary ppermute moves (mb, s, h) bf16 = 16 MB per tick
  over ICI (~45 GB/s effective per link on v5e) ~ 0.4 ms, overlapped
  with the next tick's compute by XLA's latency-hiding scheduler — it
  is carried as an error term, not a serial cost.
* tokens/sec/chip = mb*s*M / (T_stage*(M+pp-1) + eps) / 1 chip-of-64,
  where each of the 64 chips holds one (tp, pp) shard and dp=4 scales
  tokens and chips together (cancels).

Known error term this script CANNOT measure on one chip: the TP
all-reduces inside each layer (2 psums fwd + 2 bwd of the (mb, s, h)
activation over the 4-chip ring, ~26 ms/tick serial worst case vs the
~60 ms measured compute).  BASELINE.md carries the projection as a
range whose lower bound charges them fully serial and whose upper
bound assumes full overlap.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from _timing import time_steps as _time  # noqa: E402

H, L_STAGE, SEQ, TP, PP, M = 4096, 8, 2048, 4, 4, 8
FFN = 4 * H
HEADS_LOCAL = 32 // TP


def stage_fwd(params, x):
    """8 TP-sharded GPT layers, one microbatch (1, s, h/1) local math.

    The TP collectives themselves ride ICI and are not measurable on
    one chip; their FLOPs/bytes are the sharded GEMMs below, which ARE
    measured.  (Collective cost rides the error bar.)"""
    from apex_tpu.ops.flash_attention import flash_attention

    def layer(x, lp):
        h_ = x
        qkv = h_ @ lp["wqkv"]                       # (1, s, 3h/tp)
        b, s, _ = qkv.shape
        q, k, v = jnp.split(qkv.reshape(b, s, HEADS_LOCAL, 3 * 128), 3,
                            axis=-1)
        ctx = flash_attention(q.transpose(0, 2, 1, 3),
                              k.transpose(0, 2, 1, 3),
                              v.transpose(0, 2, 1, 3), causal=True)
        ctx = ctx.transpose(0, 2, 1, 3).reshape(b, s, -1)
        x = x + ctx @ lp["wproj"]                   # row-parallel local
        h2 = x @ lp["w1"]
        h2 = jax.nn.gelu(h2, approximate=True)
        return x + h2 @ lp["w2"], None

    x, _ = jax.lax.scan(layer, x, params)
    return x


def main():
    rng = np.random.RandomState(0)
    bf = jnp.bfloat16
    params = {
        "wqkv": jnp.asarray(rng.randn(L_STAGE, H, 3 * H // TP) * 0.02, bf),
        "wproj": jnp.asarray(rng.randn(L_STAGE, H // TP, H) * 0.02, bf),
        "w1": jnp.asarray(rng.randn(L_STAGE, H, FFN // TP) * 0.02, bf),
        "w2": jnp.asarray(rng.randn(L_STAGE, FFN // TP, H) * 0.02, bf),
    }
    x = jnp.asarray(rng.randn(1, SEQ, H), bf)

    grad = jax.jit(jax.grad(
        lambda p, x: jnp.sum(stage_fwd(p, x).astype(jnp.float32)),
        argnums=(0, 1)))
    t_stage = _time(grad, (params, x), warmup=2, iters=4, rounds=3)
    print(f"stage fwd+bwd (8 layers, h={H}, tp={TP} shard, mb=1 x "
          f"s={SEQ}): {t_stage * 1e3:.1f} ms", flush=True)

    # per-stage FLOPs for an MFU cross-check: GEMMs (fwd 2x + bwd 4x =
    # 6x weight size per token) + flash attention (12*s*h per token per
    # layer, fwd; x3 for fwd+bwd, local heads = 1/tp share)
    w_els = sum(int(np.prod(p.shape[1:])) for p in params.values()) * L_STAGE
    flops = 6 * w_els * SEQ + 3 * 12 * L_STAGE * (H // TP) * SEQ * SEQ
    print(f"stage FLOPs ~{flops / 1e12:.2f} T -> "
          f"{flops / t_stage / 1e12:.1f} TF/s sustained")

    # projection: 1F1B with M microbatches; boundary ppermute 16 MB
    # per tick over ICI (overlappable; carried as +/- term)
    ticks = M + PP - 1
    t_step = t_stage * ticks
    boundary = 16e6 / 45e9                        # s per tick, if serial
    tokens = M * 1 * SEQ                          # per pipeline replica
    # each replica spans tp*pp = 16 chips; tokens/sec/chip divides by 16
    chips = TP * PP
    lo = tokens / ((t_step + ticks * boundary) * chips)
    hi = tokens / (t_step * chips)
    print(f"1F1B ticks={ticks} bubble={(PP - 1) / ticks:.2%}")
    print(f"projected tokens/sec/chip (7B, tp4 x pp4, M={M}, mb=1): "
          f"{lo:,.0f} - {hi:,.0f}")


if __name__ == "__main__":
    from apex_tpu.utils.platform import setup_compile_cache
    setup_compile_cache()
    main()
