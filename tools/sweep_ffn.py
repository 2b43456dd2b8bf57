#!/usr/bin/env python
"""On-chip fused-FFN tuning sweep (ISSUE 17).

Times the Pallas fused bias-GELU FFN kernel fwd+bwd across
``(block_m, block_f)`` tilings at the model FFN shapes, and races the
unfused XLA chain (GEMM + epilogue-fused bias/GELU + GEMM) at each —
the fused win is the HBM round-trip of the ``(tokens, ffn_hidden)``
activation between the two GEMMs, so the crossover and the best tiling
are measured facts, not guesses.  Measured rows feed the autotune
CostModel's FFN term and the kernel's ``block_m``/``block_f`` defaults.

Usage: python tools/sweep_ffn.py
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from _timing import time_steps as _time  # noqa: E402 (sets sys.path)

from apex_tpu.ops.fused_ffn import (fused_ffn,                # noqa: E402
                                    fused_ffn_reference)


def grad_fn(ffn):
    def f(x, w1, b1, w2, b2):
        return jnp.sum(ffn(x, w1, b1, w2, b2).astype(jnp.float32))
    return jax.jit(jax.grad(f, argnums=(0, 1, 2, 3, 4)))


def main():
    rng = np.random.RandomState(0)
    # (label, tokens, hidden, ffn_hidden) — BERT-large headline step
    # (16x512 tokens), GPT-350M (8x1024), and a 2x-width arm
    shapes = [("bert", 16 * 512, 1024, 4096),
              ("gpt", 8 * 1024, 1024, 4096),
              ("wide", 4 * 1024, 2048, 8192)]
    blocks = [(128, 512), (256, 256), (256, 512), (512, 512),
              (256, 1024), (512, 1024)]
    for label, m, h, f in shapes:
        x = jnp.asarray(rng.randn(m, h), jnp.bfloat16)
        w1 = jnp.asarray(rng.randn(f, h) * 0.02, jnp.bfloat16)
        b1 = jnp.asarray(rng.randn(f) * 0.02, jnp.bfloat16)
        w2 = jnp.asarray(rng.randn(h, f) * 0.02, jnp.bfloat16)
        b2 = jnp.asarray(rng.randn(h) * 0.02, jnp.bfloat16)
        args = (x, w1, b1, w2, b2)

        unfused = grad_fn(fused_ffn_reference)
        try:
            dt = _time(unfused, args)
            print(f"{label} m={m} f={f} unfused(XLA): {dt * 1e3:8.2f} ms",
                  flush=True)
        except Exception as e:
            print(f"{label} m={m} f={f} unfused(XLA): FAILED "
                  f"{str(e).splitlines()[0][:100]}", flush=True)

        for bm, bf in blocks:
            if bm > m or bf > f:
                continue
            fl = grad_fn(lambda x, w1, b1, w2, b2, _bm=bm, _bf=bf:
                         fused_ffn(x, w1, b1, w2, b2, block_m=_bm,
                                   block_f=_bf))
            try:
                dt = _time(fl, args)
                print(f"{label} m={m} f={f} fused({bm},{bf}): "
                      f"{dt * 1e3:8.2f} ms", flush=True)
            except Exception as e:
                print(f"{label} m={m} f={f} fused({bm},{bf}): FAILED "
                      f"{str(e).splitlines()[0][:100]}", flush=True)
        jax.clear_caches()


if __name__ == "__main__":
    from apex_tpu.utils.platform import setup_compile_cache
    setup_compile_cache()
    main()
