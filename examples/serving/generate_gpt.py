#!/usr/bin/env python
"""Continuous-batching GPT serving demo (``apex_tpu.inference``).

Builds a small randomly-initialized GPT, submits a mixed batch of
requests (different prompt lengths, budgets, sampling modes) to the
:class:`~apex_tpu.inference.InferenceEngine`, and streams them through
the KV-cache decode path: each request gets one prefill when a cache
slot frees up, then rides the single batched ``decode_step`` until it
finishes — no batch drain between requests.

Runs anywhere (CPU demo sizes by default; the decode attention lowers to
the Pallas single-query kernel on TPU):

    python examples/serving/generate_gpt.py --requests 6 --max-slots 2

The greedy responses printed are token-identical to decoding each
request alone — the engine invariant the test suite asserts.
"""

from __future__ import annotations

import argparse


def parse_args():
    p = argparse.ArgumentParser(description="apex_tpu serving demo")
    p.add_argument("--hidden", type=int, default=64)
    p.add_argument("--layers", type=int, default=2)
    p.add_argument("--heads", type=int, default=4)
    p.add_argument("--vocab", type=int, default=256)
    p.add_argument("--max-seq", type=int, default=64)
    p.add_argument("--max-slots", type=int, default=2,
                   help="cache slots == max concurrent sequences")
    p.add_argument("--requests", type=int, default=6)
    p.add_argument("--max-new-tokens", type=int, default=12)
    p.add_argument("--cache-dtype", choices=["bf16", "f32"],
                   default="bf16")
    p.add_argument("--temperature", type=float, default=0.0,
                   help="0 = greedy; the last request additionally "
                        "samples top-k when > 0")
    p.add_argument("--seed", type=int, default=0)
    return p.parse_args()


def main():
    args = parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from apex_tpu.inference import (InferenceEngine, Request,
                                    SamplingParams)
    from apex_tpu.models.gpt import GPTConfig, GPTModel

    cfg = GPTConfig(vocab_size=args.vocab, hidden_size=args.hidden,
                    num_layers=args.layers,
                    num_attention_heads=args.heads,
                    max_seq_len=args.max_seq)
    model = GPTModel(cfg)
    params = model.init_params(jax.random.PRNGKey(args.seed))
    cache_dtype = (jnp.bfloat16 if args.cache_dtype == "bf16"
                   else jnp.float32)
    engine = InferenceEngine(model, params, max_slots=args.max_slots,
                             cache_dtype=cache_dtype)
    print(f"devices={len(jax.devices())} slots={args.max_slots} "
          f"cache_dtype={args.cache_dtype}")

    rng = np.random.RandomState(args.seed)
    sampling = (SamplingParams() if args.temperature == 0.0 else
                SamplingParams(temperature=args.temperature, top_k=16))
    for i in range(args.requests):
        prompt = [int(t) for t in
                  rng.randint(1, args.vocab, rng.randint(3, 17))]
        engine.submit(Request(
            request_id=i, prompt=prompt,
            max_new_tokens=args.max_new_tokens,
            sampling=sampling if i == args.requests - 1
            else SamplingParams(),
            seed=args.seed + i))

    for r in engine.run():
        print(f"request {r.request_id}: prompt[{len(r.prompt)}] -> "
              f"{r.tokens} ({r.finish_reason})")

    s = engine.metrics.summary()
    print(f"served {s['requests']} requests, {s['tokens']} tokens at "
          f"{s['tokens_per_s']:.1f} tok/s | ttft p50 "
          f"{s['ttft_p50_s'] * 1e3:.1f} ms | token latency p50 "
          f"{s['token_latency_p50_s'] * 1e3:.2f} ms | occupancy "
          f"{s['slot_occupancy_mean']:.2f}")
    print("DONE")


if __name__ == "__main__":
    from apex_tpu.utils.platform import setup_compile_cache
    setup_compile_cache()
    main()
