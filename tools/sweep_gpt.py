#!/usr/bin/env python
"""On-chip GPT-350M train-step sweep: remat policy x batch x optimizer
layout (companion to tools/profile_bert.py; same timing protocol)."""

from __future__ import annotations

import functools
import sys

import jax
import jax.numpy as jnp
import numpy as np

from _timing import time_steps as _time  # noqa: E402


def make_step(batch, remat, policy, leaf, accum=1):
    from apex_tpu.models.gpt import GPTConfig, GPTModel
    from apex_tpu.optimizers import FusedAdam

    cfg = GPTConfig(vocab_size=50304, hidden_size=1024, num_layers=24,
                    num_attention_heads=16, max_seq_len=1024, remat=remat,
                    remat_policy=policy, dtype=jnp.bfloat16)
    seq = 1024
    model = GPTModel(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    adam = FusedAdam(lr=1e-4, bucketed=not leaf)
    opt_state = adam.init(params)
    rng = np.random.RandomState(0)
    tokens = jnp.asarray(rng.randint(0, cfg.vocab_size,
                                     (accum, batch, seq)))
    targets = jnp.asarray(rng.randint(0, cfg.vocab_size,
                                      (accum, batch, seq)))

    from bench import _accumulated_grads  # shared accumulation numerics

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

    return run, (tokens, targets), accum * batch * seq


def main():
    configs = [
        ("b16_dots_leaf", dict(batch=16, remat=True, policy="dots",
                               leaf=True)),
        ("b8_none_leaf", dict(batch=8, remat=False, policy="full",
                              leaf=True)),
        ("b12_none_leaf", dict(batch=12, remat=False, policy="full",
                               leaf=True)),
        ("b16_none_leaf", dict(batch=16, remat=False, policy="full",
                               leaf=True)),
        ("b16_dots", dict(batch=16, remat=True, policy="dots",
                          leaf=False)),
        ("b8x2_none_leaf", dict(batch=8, remat=False, policy="full",
                                leaf=True, accum=2)),
        ("b8x4_none_leaf", dict(batch=8, remat=False, policy="full",
                                leaf=True, accum=4)),
    ]
    if len(sys.argv) > 1:
        names = set(sys.argv[1].split(","))
        configs = [c for c in configs if c[0] in names]
    for name, kw in configs:
        try:
            run, args, tok = make_step(**kw)
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
