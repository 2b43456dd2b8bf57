#!/usr/bin/env python
"""Device capability probe: sustained HBM bandwidth (chained 1 GB axpy)
and bf16/f32 matmul rates (chained DEPENDENT 4096^3 matmuls in one
jitted program), to hold against the published
peaks of the device it runs on."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from _timing import time_steps as _time  # noqa: E402


def hbm_bandwidth():
    n = 256 * 1024 * 1024  # 1 GB f32
    x = jnp.ones((n // 128, 128), jnp.float32)
    reps = 8

    @functools.partial(jax.jit, donate_argnums=(0,))
    def axpy_chain(x):
        def body(c, _):
            return c * 1.000001 + 1e-7, None
        y, _ = jax.lax.scan(body, x, None, length=reps)
        return y

    holder = [x]

    def run():
        holder[0] = axpy_chain(holder[0])
        return holder[0]

    dt = _time(lambda _=None: run(), (None,), warmup=1, iters=4,
               rounds=3) / reps
    gb = 2 * x.size * 4 / 1e9  # read + write per rep
    print(f"HBM axpy: {gb / dt:.0f} GB/s ({dt * 1e3:.2f} ms per "
          f"1GB-rw pass)", flush=True)


def matmul_rate(dtype):
    n = 4096
    key = jax.random.PRNGKey(0)
    a = jax.random.normal(key, (n, n), dtype)
    b = jax.random.normal(key, (n, n), dtype)
    chain_len = 48

    @functools.partial(jax.jit, donate_argnums=(0,))
    def chain(a, b):
        def body(c, _):
            c = jnp.dot(c, b, preferred_element_type=dtype)
            c = c * (1.0 / jnp.maximum(jnp.max(jnp.abs(c)),
                                       1.0)).astype(dtype)
            return c, None
        c, _ = jax.lax.scan(body, a, None, length=chain_len)
        return c

    holder = [a]

    def run():
        holder[0] = chain(holder[0], b)
        return holder[0]

    dt = _time(lambda _=None: run(), (None,), warmup=1, iters=2,
               rounds=3) / chain_len
    print(f"matmul {jnp.dtype(dtype).name} {n}^3: "
          f"{2 * n ** 3 / dt / 1e12:.1f} TF/s", flush=True)


if __name__ == "__main__":
    from apex_tpu.utils.platform import setup_compile_cache
    setup_compile_cache()
    hbm_bandwidth()
    jax.clear_caches()
    matmul_rate(jnp.bfloat16)
    matmul_rate(jnp.float32)
