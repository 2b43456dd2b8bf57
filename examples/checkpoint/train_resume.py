#!/usr/bin/env python
"""Checkpoint / resume recipe (SURVEY §5 "checkpoint/resume"): save the
COMPLETE training state mid-run — params, fused-optimizer state (packed
moment buckets + step counter), dynamic loss-scaler state, and the data
seed — restore it in a fresh process, and continue bit-for-bit.

The reference's apex-owned checkpoint surface is the amp loss-scaler
state_dict round-trip (apex ``tests/L0/run_amp/test_checkpointing.py``);
model/optimizer persistence is user-side ``torch.save``.  Here the whole
state is one pytree saved through the framework's own parallel-IO
runtime (:mod:`apex_tpu.contrib.gpu_direct_storage`, the cuFile-GDS
equivalent), so the recipe doubles as the failure-recovery story: kill
the process at any step, relaunch with ``--resume``, the trajectory is
identical to the uninterrupted run (the test asserts exactly that).

Run:  python examples/checkpoint/train_resume.py --steps 6 \\
          --save-at 3 --ckpt /tmp/ck.bin
      python examples/checkpoint/train_resume.py --steps 6 \\
          --resume --ckpt /tmp/ck.bin
"""

from __future__ import annotations

import argparse

import numpy as np


def parse_args():
    p = argparse.ArgumentParser(description="apex_tpu checkpoint/resume")
    p.add_argument("--steps", type=int, default=6,
                   help="total steps of the full trajectory")
    p.add_argument("--save-at", type=int, default=3,
                   help="step AFTER which the checkpoint is written")
    p.add_argument("--ckpt", type=str, default="/tmp/apex_tpu_ck.bin")
    p.add_argument("--resume", action="store_true",
                   help="restore --ckpt and run the remaining steps")
    p.add_argument("--hidden", type=int, default=64)
    p.add_argument("--layers", type=int, default=2)
    p.add_argument("--vocab", type=int, default=256)
    p.add_argument("--seq-len", type=int, default=32)
    p.add_argument("--lr", type=float, default=1e-3)
    p.add_argument("--seed", type=int, default=0)
    return p.parse_args()


def main():
    args = parse_args()

    import jax
    import jax.numpy as jnp

    from apex_tpu import amp
    from apex_tpu.contrib import gpu_direct_storage as gds
    from apex_tpu.models.gpt import GPTConfig, GPTModel
    from apex_tpu.optimizers import FusedAdam

    cfg = GPTConfig(vocab_size=args.vocab, hidden_size=args.hidden,
                    num_layers=args.layers, num_attention_heads=4,
                    max_seq_len=args.seq_len)
    model = GPTModel(cfg)
    adam = FusedAdam(lr=args.lr)
    # fp16-style dynamic scaler: its state (scale + growth counter) is
    # part of the checkpoint contract, like apex amp.state_dict()
    scaler = amp.LossScaler(loss_scale="dynamic", init_scale=2.0 ** 12)

    def batch_for(step):
        """Deterministic per-step synthetic batch (seeded off the step,
        so a resumed run sees the same data stream)."""
        r = np.random.RandomState(args.seed * 100003 + step)
        t = jnp.asarray(r.randint(0, args.vocab,
                                  (4, args.seq_len)))
        return t, jnp.asarray(
            r.randint(0, args.vocab, (4, args.seq_len)))

    @jax.jit
    def train_step(params, opt_state, sstate, tokens, targets):
        def loss_fn(p):
            return amp.scale_loss(model.loss(p, tokens, targets), sstate)
        loss, grads = jax.value_and_grad(loss_fn)(params)
        params, opt_state, sstate, _ = amp.unscale_step(
            adam, grads, params, opt_state, scaler, sstate)
        return loss / sstate.loss_scale, params, opt_state, sstate

    if args.resume:
        # the loader restores INTO a structure template (the pytree is
        # stored flat); building it from init is cheap and guarantees
        # the treedef matches what training would have produced
        params_t = model.init_params(jax.random.PRNGKey(args.seed))
        template = {"params": params_t, "opt": adam.init(params_t),
                    "scaler": tuple(scaler.init()),
                    "step": jnp.int32(0)}
        state = gds.load(args.ckpt, tree_like=template)
        params, opt_state = state["params"], state["opt"]
        sstate = amp.LossScaleState(*(jnp.asarray(v)
                                      for v in state["scaler"]))
        start = int(state["step"])
        print(f"resumed from {args.ckpt} at step {start}")
    else:
        params = model.init_params(jax.random.PRNGKey(args.seed))
        opt_state = adam.init(params)
        sstate = scaler.init()
        start = 0

    for step in range(start, args.steps):
        tokens, targets = batch_for(step)
        loss, params, opt_state, sstate = train_step(
            params, opt_state, sstate, tokens, targets)
        print(f"step {step}: loss={float(loss):.6f} "
              f"scale={float(sstate.loss_scale):.0f}")
        if not args.resume and step + 1 == args.save_at:
            gds.save(args.ckpt, {
                "params": params,
                "opt": opt_state,
                "scaler": tuple(sstate),
                "step": jnp.int32(step + 1),
            })
            print(f"checkpoint written to {args.ckpt} after step {step}")
    print("DONE")


if __name__ == "__main__":
    from apex_tpu.utils.platform import setup_compile_cache
    setup_compile_cache()
    main()
