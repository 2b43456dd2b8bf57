#!/usr/bin/env python
"""Switch-GPT training via expert parallelism (beyond-reference: MoE is
not in apex; this recipe exercises
``apex_tpu.transformer.expert_parallel`` through the GPT flagship).

Experts are sharded over the ``expert`` mesh axis, which doubles as the
data axis (each device trains on its own token shard — the standard
Switch/GShard deployment).  Dense params stay replicated and their
grads pmean; expert-stack grads are per-shard by construction.

Run:  python examples/moe/train_switch_gpt.py --n-experts 8 \\
          --top-k 1 --steps 20
"""

from __future__ import annotations

import argparse
import time

import numpy as np


def parse_args():
    p = argparse.ArgumentParser(description="apex_tpu Switch-GPT")
    p.add_argument("--n-experts", type=int, default=8)
    p.add_argument("--top-k", type=int, default=1,
                   help="1 = Switch, 2 = GShard")
    p.add_argument("--capacity-factor", type=float, default=1.25)
    p.add_argument("--batch-per-device", type=int, default=4)
    p.add_argument("--seq-len", type=int, default=512)
    p.add_argument("--hidden", type=int, default=256)
    p.add_argument("--layers", type=int, default=4)
    p.add_argument("--heads", type=int, default=8)
    p.add_argument("--vocab", type=int, default=32768)
    p.add_argument("--steps", type=int, default=20)
    p.add_argument("--lr", type=float, default=3e-4)
    p.add_argument("--print-freq", type=int, default=5)
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
    from apex_tpu.transformer.expert_parallel import (
        is_gpt_expert_leaf, localize_expert_params, reduce_moe_grads)

    ep = len(jax.devices())
    if args.n_experts % ep:
        raise SystemExit(
            f"--n-experts must be divisible by the device count ({ep})")

    serial_cfg = GPTConfig(
        vocab_size=args.vocab, hidden_size=args.hidden,
        num_layers=args.layers, num_attention_heads=args.heads,
        max_seq_len=args.seq_len, dtype=jnp.bfloat16,
        n_experts=args.n_experts, moe_top_k=args.top_k,
        moe_capacity_factor=args.capacity_factor)
    init_model = GPTModel(serial_cfg)
    params = init_model.init_params(jax.random.PRNGKey(args.seed))

    if ep > 1:
        import dataclasses
        cfg = dataclasses.replace(serial_cfg, expert_axis="expert",
                                  expert_parallel_size=ep)
    else:
        cfg = serial_cfg
    model = GPTModel(cfg)
    nl = args.n_experts // ep

    is_expert = is_gpt_expert_leaf

    # shard the expert stacks (leading (ep, nl, ...) axis); replicate
    # rest.  ep=1 trains the plain serial form (no extra axis).
    sharded = jax.tree_util.tree_map_with_path(
        lambda p, x: x.reshape(ep, nl, *x.shape[1:])
        if ep > 1 and is_expert(p) else x, params)
    specs = jax.tree_util.tree_map_with_path(
        lambda p, x: P("expert") if is_expert(p) else P(), params)
    mesh = jax.make_mesh((ep,), ("expert",),
                         axis_types=(jax.sharding.AxisType.Auto,))

    adam = FusedAdam(lr=args.lr)
    # optimizer runs OUTSIDE shard_map on the stacked (ep, nl, ...)
    # pytree: the packed buckets are ordinary arrays whose sharding GSPMD
    # propagates from the param shardings
    opt_state = adam.init(sharded)

    if ep > 1:
        def grad_fn(p, tokens, targets):
            # differentiate the LOCAL per-device loss, then apply the
            # shared EP reduction recipe (reduce_moe_grads)
            local = localize_expert_params(p)
            loss, grads = jax.value_and_grad(model.loss)(local, tokens,
                                                         targets)
            grads = reduce_moe_grads(grads, "expert")
            return jax.lax.pmean(loss, "expert"), grads

        @jax.jit
        def train_step(p, opt_state, tokens, targets):
            loss, grads = shard_map(
                grad_fn, mesh=mesh,
                in_specs=(specs, P("expert"), P("expert")),
                out_specs=(P(), specs), check_vma=False)(p, tokens,
                                                         targets)
            new_p, new_opt = adam.step(grads, p, opt_state)
            return loss, new_p, new_opt
    else:
        @jax.jit
        def train_step(p, opt_state, tokens, targets):
            loss, grads = jax.value_and_grad(model.loss)(p, tokens,
                                                         targets)
            new_p, new_opt = adam.step(grads, p, opt_state)
            return loss, new_p, new_opt

    rng = np.random.RandomState(args.seed)
    B = ep * args.batch_per_device

    def make_batch():
        return (jnp.asarray(rng.randint(0, args.vocab,
                                        (B, args.seq_len))),
                jnp.asarray(rng.randint(0, args.vocab,
                                        (B, args.seq_len))))

    tokens, targets = make_batch()
    loss, sharded, opt_state = train_step(sharded, opt_state, tokens,
                                          targets)
    jax.block_until_ready(loss)
    t0 = time.perf_counter()
    for step in range(1, args.steps + 1):
        tokens, targets = make_batch()
        loss, sharded, opt_state = train_step(sharded, opt_state,
                                              tokens, targets)
        if step % args.print_freq == 0 or step == args.steps:
            tok_s = step * B * args.seq_len / (time.perf_counter() - t0)
            print(f"step {step:4d}  loss {float(loss):8.4f}  "
                  f"{tok_s:10.0f} tok/s", flush=True)
    jax.block_until_ready(loss)
    dt = time.perf_counter() - t0
    print(f"DONE experts={args.n_experts} top_k={args.top_k} devices={ep}"
          f" throughput={args.steps * B * args.seq_len / dt:.0f} tok/s")


if __name__ == "__main__":
    from apex_tpu.utils.platform import setup_compile_cache
    setup_compile_cache()
    main()
