#!/usr/bin/env python
"""On-chip flash-attention tuning sweep (VERDICT r4 item 4).

Times the Pallas flash kernel fwd+bwd across block sizes and sequence
lengths at BERT/GPT-like shapes, and races XLA's dense (materialized)
attention at short sequence — if dense wins at seq <= 512, the public
wrapper should dispatch on length.

Usage: python tools/sweep_flash.py
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from _timing import time_steps as _time  # noqa: E402 (sets sys.path)

from apex_tpu.ops.flash_attention import (flash_attention,          # noqa: E402
                                          flash_attention_reference)


def grad_fn(attn, causal):
    def f(q, k, v):
        return jnp.sum(attn(q, k, v, causal).astype(jnp.float32))
    return jax.jit(jax.grad(f, argnums=(0, 1, 2)))


def main():
    rng = np.random.RandomState(0)
    # (label, b, h, s, d, causal) — BERT-large (s 512, non-causal),
    # GPT-350M (s 1024, causal), long-seq (s 2048, causal)
    shapes = [("bert", 32, 16, 512, 64, False),
              ("gpt", 16, 16, 1024, 64, True),
              ("long", 4, 16, 2048, 64, True)]
    blocks = [(256, 256), (512, 512), (1024, 1024), (256, 512),
              (512, 256), (512, 1024)]
    for label, b, h, s, d, causal in shapes:
        q = jnp.asarray(rng.randn(b, h, s, d), jnp.bfloat16)
        k = jnp.asarray(rng.randn(b, h, s, d), jnp.bfloat16)
        v = jnp.asarray(rng.randn(b, h, s, d), jnp.bfloat16)

        dense = grad_fn(lambda q, k, v, c: flash_attention_reference(
            q, k, v, causal=c), causal)
        try:
            dt = _time(dense, (q, k, v))
            print(f"{label} s={s} dense(XLA): {dt * 1e3:8.2f} ms",
                  flush=True)
        except Exception as e:
            print(f"{label} s={s} dense(XLA): FAILED "
                  f"{str(e).splitlines()[0][:100]}", flush=True)

        for bq, bk in blocks:
            if bq > s or bk > s:
                continue
            fl = grad_fn(lambda q, k, v, c, _bq=bq, _bk=bk:
                         flash_attention(q, k, v, causal=c, block_q=_bq,
                                         block_k=_bk), causal)
            try:
                dt = _time(fl, (q, k, v))
                print(f"{label} s={s} flash({bq},{bk}): {dt * 1e3:8.2f} ms",
                      flush=True)
            except Exception as e:
                print(f"{label} s={s} flash({bq},{bk}): FAILED "
                      f"{str(e).splitlines()[0][:100]}", flush=True)
        jax.clear_caches()


if __name__ == "__main__":
    from apex_tpu.utils.platform import setup_compile_cache
    setup_compile_cache()
    main()
