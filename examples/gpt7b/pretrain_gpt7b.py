#!/usr/bin/env python
"""7B-class GPT pretraining via TP x PP x DP (BASELINE.md row 2: "GPT
7B-class, tokens/sec/chip via tensor+pipeline parallel").

The model is the flagship :class:`apex_tpu.models.gpt.GPTModel` at
hidden=4096 / layers=32 / heads=32 / seq=2048 (~6.9B params with the
tied 50304 vocab); parallelism is the explicit shard_map form —
``pack_for_shard_map`` + the ring pipeline (``pipeline_step``, 1F1B on
a compiled scan) over a ``(data, pipe, model)`` mesh with sequence
parallelism on the TP axis — with per-layer remat and a FusedAdam
step, bf16 activations and fp32 params.

Pod launch (v5e-64 example; the same script, no code changes):

    # 16 hosts x 4 chips, multi-controller JAX: run on EVERY host
    python examples/gpt7b/pretrain_gpt7b.py --tp 4 --pp 4 --steps 100

    TP rides the intra-host ICI (tp=4 matches the v5e host's 2x2
    block); PP spans hosts (stage boundaries are the only inter-host
    hops, one (mb, s, h) ppermute per tick); the leftover mesh extent
    is DP.  Multi-controller init (jax.distributed.initialize) is
    automatic under TPU pod runtimes.

Hardware-free validation (what CI runs — same code path, scaled shapes):

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
    python examples/gpt7b/pretrain_gpt7b.py --smoke --steps 2

``--smoke`` keeps the FULL topology (tp=2 x pp=2 x dp=2) and every
collective family, shrinking only the shape hyperparameters; the real
config stays the default so the recipe is the runnable artifact for the
7B row.
"""

from __future__ import annotations

import argparse
import time

import numpy as np


def parse_args():
    p = argparse.ArgumentParser(description="apex_tpu 7B GPT TP x PP")
    p.add_argument("--tp", type=int, default=4,
                   help="tensor-parallel ways (intra-host ICI)")
    p.add_argument("--pp", type=int, default=4,
                   help="pipeline stages (inter-host axis on pods)")
    p.add_argument("--hidden", type=int, default=4096)
    p.add_argument("--layers", type=int, default=32)
    p.add_argument("--heads", type=int, default=32)
    p.add_argument("--seq-len", type=int, default=2048)
    p.add_argument("--vocab", type=int, default=50304)
    p.add_argument("--microbatches", type=int, default=4,
                   help="pipeline microbatches per step (per dp rank)")
    p.add_argument("--micro-batch-size", type=int, default=1)
    p.add_argument("--steps", type=int, default=10)
    p.add_argument("--lr", type=float, default=1.5e-4)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--smoke", action="store_true",
                   help="scale shapes down for the 8-virtual-device CPU "
                        "mesh; topology (tp x pp x dp) is unchanged")
    return p.parse_args()


def main():
    args = parse_args()
    if args.smoke:
        args.tp, args.pp = 2, 2
        args.hidden, args.layers, args.heads = 64, 4, 4
        args.seq_len, args.vocab = 32, 128
        args.microbatches, args.micro_batch_size = 2, 2

    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from apex_tpu.models.gpt import (GPTConfig, GPTModel,
                                     pack_for_shard_map, pipeline_step)
    from jax import shard_map
    from apex_tpu.optimizers import FusedAdam
    from apex_tpu.transformer import parallel_state

    n = len(jax.devices())
    tp, pp = args.tp, args.pp
    if n % (tp * pp):
        raise SystemExit(f"device count {n} not divisible by tp*pp="
                         f"{tp * pp}")
    mesh = parallel_state.initialize_model_parallel(tp, pp)
    dp = parallel_state.get_data_parallel_world_size()

    cfg_kw = dict(vocab_size=args.vocab, hidden_size=args.hidden,
                  num_layers=args.layers, num_attention_heads=args.heads,
                  max_seq_len=args.seq_len, dtype=jnp.bfloat16,
                  remat=True)
    serial = GPTModel(GPTConfig(**cfg_kw))
    params = serial.init_params(jax.random.PRNGKey(args.seed))
    n_params = sum(int(np.prod(p.shape))
                   for p in jax.tree_util.tree_leaves(params))

    # the ring pipeline's TP composition requires sequence parallelism
    par = GPTModel(GPTConfig(tensor_parallel_size=tp,
                             axis_name="model" if tp > 1 else None,
                             sequence_parallel=tp > 1,
                             **cfg_kw))
    tensor_axis = "model" if tp > 1 else None
    packed, in_specs, local_fn, repack_fn = pack_for_shard_map(
        par, params, n_stages=pp, tensor_axis=tensor_axis)
    del params                                   # packed owns the memory
    adam = FusedAdam(lr=args.lr)
    opt_state = adam.init(packed)

    M, mb, seq = args.microbatches, args.micro_batch_size, args.seq_len
    tokens_per_step = dp * M * mb * seq

    def grad_step(sp, tokens, targets):
        tk = tokens.reshape(M, mb, seq)
        tg = targets.reshape(M, mb, seq)
        # remat follows cfg.remat=True (per-layer stage checkpoint)
        loss, g = pipeline_step(par, local_fn(sp), tk, tg,
                                pipe_axis="pipe", data_axis="data")
        return loss, repack_fn(g)

    @jax.jit
    def train_step(packed, opt_state, tokens, targets):
        loss, grads = shard_map(
            grad_step, mesh=mesh,
            in_specs=(in_specs, P("data"), P("data")),
            out_specs=(P(), in_specs),
            check_vma=False)(packed, tokens, targets)
        new_packed, new_opt = adam.step(grads, packed, opt_state)
        return loss, new_packed, new_opt

    rng = np.random.RandomState(args.seed)
    print(f"gpt7b: params={n_params / 1e9:.2f}B mesh=(dp={dp}, pp={pp}, "
          f"tp={tp}) devices={n} tokens/step={tokens_per_step}")

    losses, t0 = [], None
    for step in range(args.steps):
        tokens = jnp.asarray(
            rng.randint(0, args.vocab, (dp * M * mb, seq)))
        targets = jnp.asarray(
            rng.randint(0, args.vocab, (dp * M * mb, seq)))
        loss, packed, opt_state = train_step(packed, opt_state, tokens,
                                             targets)
        losses.append(float(loss))
        if step == 0:
            jax.block_until_ready(packed)
            t0 = time.perf_counter()          # exclude compile
        print(f"step {step}: loss={losses[-1]:.4f}")
    jax.block_until_ready(packed)
    if args.steps > 1 and t0 is not None:
        dt = (time.perf_counter() - t0) / (args.steps - 1)
        per_chip = tokens_per_step / dt / n
        print(f"throughput: {tokens_per_step / dt:.1f} tokens/s "
              f"({per_chip:.1f} tokens/s/chip, step {dt * 1e3:.0f} ms)")
    assert all(np.isfinite(losses)), losses
    print("DONE")


if __name__ == "__main__":
    from apex_tpu.utils.platform import setup_compile_cache
    setup_compile_cache()
    main()
