#!/usr/bin/env python
"""On-chip GPT-350M decode sweep: slot-batch x cache-depth steady-state
decode throughput + prefill latency (companion to tools/sweep_gpt.py;
same timing protocol).  Informs the engine's max_slots/max_seq
choices: decode is cache-bandwidth bound, so tokens/s should scale with
slots until the KV reads saturate HBM."""

from __future__ import annotations

import sys

import jax
import jax.numpy as jnp
import numpy as np

from _timing import time_steps as _time  # noqa: E402


def make_decode(slots, depth, cache_dtype=jnp.bfloat16, max_seq=1024):
    from apex_tpu.models.gpt import GPTConfig, GPTModel
    from apex_tpu.utils.platform import is_tpu_backend

    cfg = GPTConfig(vocab_size=50304, hidden_size=1024, num_layers=24,
                    num_attention_heads=16, max_seq_len=max_seq,
                    dtype=jnp.bfloat16)
    model = GPTModel(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    rng = np.random.RandomState(0)
    cache = jnp.zeros((slots, cfg.num_layers, 2, max_seq,
                       cfg.num_attention_heads, cfg.head_dim), cache_dtype)
    tokens = jnp.asarray(rng.randint(0, cfg.vocab_size, (slots,)))
    positions = jnp.full((slots,), depth, jnp.int32)
    step = jax.jit(model.decode_step,
                   donate_argnums=(2,) if is_tpu_backend() else ())
    holder = {"c": cache}

    def run(tokens, positions):
        logits, holder["c"] = step(params, tokens, holder["c"],
                                   positions)
        return logits

    return run, (tokens, positions), slots


def make_prefill(prompt_len):
    from apex_tpu.models.gpt import GPTConfig, GPTModel

    cfg = GPTConfig(vocab_size=50304, hidden_size=1024, num_layers=24,
                    num_attention_heads=16, max_seq_len=1024,
                    dtype=jnp.bfloat16)
    model = GPTModel(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    rng = np.random.RandomState(0)
    toks = jnp.asarray(rng.randint(0, cfg.vocab_size, (1, prompt_len)))
    prefill = jax.jit(model.prefill)

    def run(toks):
        return prefill(params, toks)[0]

    return run, (toks,), prompt_len


def main():
    configs = [
        ("decode_s1_d512", lambda: make_decode(1, 512)),
        ("decode_s4_d512", lambda: make_decode(4, 512)),
        ("decode_s8_d512", lambda: make_decode(8, 512)),
        ("decode_s16_d512", lambda: make_decode(16, 512)),
        ("decode_s8_d128", lambda: make_decode(8, 128)),
        ("decode_s8_d1016", lambda: make_decode(8, 1016)),
        ("decode_s8_d512_f32", lambda: make_decode(8, 512, jnp.float32)),
        ("prefill_p128", lambda: make_prefill(128)),
        ("prefill_p512", lambda: make_prefill(512)),
    ]
    if len(sys.argv) > 1:
        names = set(sys.argv[1].split(","))
        configs = [c for c in configs if c[0] in names]
    for name, make in configs:
        try:
            run, args, tok = make()
            dt = _time(run, args)
            print(f"{name}: {tok / dt:,.0f} tok/s (step {dt * 1e3:.1f} ms)",
                  flush=True)
        except Exception as e:
            print(f"{name}: FAILED {type(e).__name__}: "
                  f"{str(e).splitlines()[0][:120]}", flush=True)
        jax.clear_caches()


if __name__ == "__main__":
    from apex_tpu.utils.platform import setup_compile_cache
    setup_compile_cache()
    main()
