#!/usr/bin/env python
"""Long-context GPT training via context parallelism (beyond-reference:
the apex reference has no long-context mechanism; this recipe uses
``apex_tpu.transformer.context_parallel`` — ring attention or Ulysses
all-to-all — to train on sequences that do not fit one device's
attention memory).

The GLOBAL sequence is sharded contiguously over the ``context`` mesh
axis; each device holds ``seq/n`` tokens and attention runs over the
full global sequence (ring: KV rotates over ICI; ulysses: all-to-all
head resharding into the Pallas flash kernel).  Loss and grads are
exactly the serial model's (see tests/test_context_parallel.py).

Run:  python examples/long_context/train_long_gpt.py \\
          --seq-len 8192 --mechanism ring --steps 10
"""

from __future__ import annotations

import argparse
import time

import numpy as np


def parse_args():
    p = argparse.ArgumentParser(description="apex_tpu long-context GPT")
    p.add_argument("--seq-len", type=int, default=8192,
                   help="GLOBAL sequence length (split over devices)")
    p.add_argument("--batch-size", type=int, default=1)
    p.add_argument("--hidden", type=int, default=512)
    p.add_argument("--layers", type=int, default=8)
    p.add_argument("--heads", type=int, default=8)
    p.add_argument("--vocab", type=int, default=32768)
    p.add_argument("--mechanism", default="ring",
                   choices=["ring", "ulysses"])
    p.add_argument("--steps", type=int, default=10)
    p.add_argument("--lr", type=float, default=3e-4)
    p.add_argument("--print-freq", type=int, default=2)
    p.add_argument("--seed", type=int, default=0)
    return p.parse_args()


def main():
    args = parse_args()

    import jax
    import jax.numpy as jnp
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from apex_tpu.models.gpt import GPTConfig, GPTModel
    from apex_tpu.optimizers import FusedAdam
    from apex_tpu.utils.collectives import psum_if_varying

    n = len(jax.devices())
    if args.seq_len % n:
        raise SystemExit(
            f"--seq-len must be divisible by the device count ({n})")
    mesh = jax.make_mesh((n,), ("context",))

    cfg = GPTConfig(vocab_size=args.vocab, hidden_size=args.hidden,
                    num_layers=args.layers,
                    num_attention_heads=args.heads,
                    max_seq_len=args.seq_len, remat=True,
                    dtype=jnp.bfloat16,
                    context_axis="context" if n > 1 else None,
                    context_mechanism=args.mechanism)
    model = GPTModel(cfg)
    params = model.init_params(jax.random.PRNGKey(args.seed))
    adam = FusedAdam(lr=args.lr)
    opt_state = adam.init(params)

    seq_spec = P(None, "context")

    def local_step(params, tokens, targets):
        loss, grads = jax.value_and_grad(model.loss)(params, tokens,
                                                     targets)
        # varying leaves hold ring-partial sums; invariant ones were
        # auto-reduced — same staging as the DP layer
        return loss, psum_if_varying(grads, "context")

    @jax.jit
    def train_step(params, opt_state, tokens, targets):
        if n > 1:
            loss, grads = shard_map(
                local_step, mesh=mesh,
                in_specs=(P(), seq_spec, seq_spec),
                out_specs=(P(), P()))(params, tokens, targets)
        else:
            loss, grads = jax.value_and_grad(model.loss)(params, tokens,
                                                         targets)
        params, opt_state = adam.step(grads, params, opt_state)
        return params, opt_state, loss

    rng = np.random.RandomState(args.seed)

    def make_batch():
        t = rng.randint(0, args.vocab, (args.batch_size, args.seq_len))
        return jnp.asarray(t), jnp.asarray(
            rng.randint(0, args.vocab, (args.batch_size, args.seq_len)))

    tokens, targets = make_batch()
    params, opt_state, loss = train_step(params, opt_state, tokens,
                                         targets)
    jax.block_until_ready(loss)
    t0 = time.perf_counter()
    for step in range(1, args.steps + 1):
        tokens, targets = make_batch()
        params, opt_state, loss = train_step(params, opt_state, tokens,
                                             targets)
        if step % args.print_freq == 0 or step == args.steps:
            tok_s = step * args.batch_size * args.seq_len \
                / (time.perf_counter() - t0)
            print(f"step {step:4d}  loss {float(loss):8.4f}  "
                  f"{tok_s:10.0f} tok/s", flush=True)
    jax.block_until_ready(loss)
    dt = time.perf_counter() - t0
    print(f"DONE mechanism={args.mechanism} devices={n} "
          f"global_seq={args.seq_len} "
          f"throughput={args.steps * args.batch_size * args.seq_len / dt:.0f}"
          " tok/s")


if __name__ == "__main__":
    from apex_tpu.utils.platform import setup_compile_cache
    setup_compile_cache()
    main()
