#!/usr/bin/env python
"""On-chip dequant-GEMM tuning sweep (ISSUE 18).

Times the Pallas int8 dequantize-then-matmul kernel across
``(block_n, block_k)`` tilings at the decode GEMM shapes (small token
batch against each dense weight of the serving configs), and reports
the achieved HBM bytes/s against a calibrated streaming roofline — at
decode batch sizes the GEMM is weight-bandwidth-bound, so bytes/s vs
the measured copy ceiling says how close each tiling gets to the win
the int8 weights bought.  Measured rows feed the kernel's
``block_n``/``block_k`` defaults (mirror of ``tools/sweep_ffn.py``).

Usage: python tools/sweep_quant.py
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from _timing import time_steps as _time  # noqa: E402 (sets sys.path)

from apex_tpu.ops.quant_gemm import (quant_gemm,              # noqa: E402
                                     quantize_weight)


def calibrate_copy_bytes(nbytes: int = 64 * 1024 * 1024) -> float:
    """Measured streaming bytes/s: a device-wide f32 copy (read +
    write), the same ceiling the dequant-GEMM's weight stream is
    bounded by.  A measured constant, not a spec-sheet number."""
    x = jnp.zeros(nbytes // 4, jnp.float32)
    f = jax.jit(lambda a: a + 1.0)
    dt = _time(f, (x,))
    return 2 * x.nbytes / dt


def gemm_bytes(m: int, n: int, k: int, act_itemsize: int) -> int:
    """HBM traffic of one dequant-GEMM call: int8 weight + f32 scale
    stream, activation read, f32 output write."""
    return n * k + n * 4 + m * k * act_itemsize + m * n * 4


def main():
    rng = np.random.RandomState(0)
    ceiling = calibrate_copy_bytes()
    print(f"calibrated copy roofline: {ceiling / 1e9:8.2f} GB/s",
          flush=True)
    # (label, m, n, k) — decode-batch GEMMs of the serving configs:
    # qkv/fc1 (3h x h / 4h x h), fc2 (h x 4h), lm head (vocab x h)
    shapes = [("qkv_1k", 8, 3 * 1024, 1024),
              ("fc1_1k", 8, 4 * 1024, 1024),
              ("fc2_1k", 8, 1024, 4 * 1024),
              ("head_32k", 8, 32768, 1024),
              ("fc1_2k_b32", 32, 8192, 2048)]
    blocks = [(256, 256), (256, 512), (512, 512), (512, 1024),
              (1024, 512), (1024, 1024)]
    for label, m, n, k in shapes:
        x = jnp.asarray(rng.randn(m, k), jnp.bfloat16)
        w8, scale = quantize_weight(
            jnp.asarray(rng.randn(n, k) * 0.02, jnp.float32))
        nbytes = gemm_bytes(m, n, k, x.dtype.itemsize)
        for bn, bk in blocks:
            if bn > n or bk > k:
                continue
            f = jax.jit(lambda x, w8, s, _bn=bn, _bk=bk:
                        quant_gemm(x, w8, s, block_n=_bn, block_k=_bk))
            try:
                dt = _time(f, (x, w8, scale))
                bps = nbytes / dt
                print(f"{label} m={m} n={n} k={k} blocks=({bn},{bk}): "
                      f"{dt * 1e6:8.1f} us  {bps / 1e9:7.2f} GB/s "
                      f"({bps / ceiling:5.1%} of roofline)", flush=True)
            except Exception as e:
                print(f"{label} m={m} n={n} k={k} blocks=({bn},{bk}): "
                      f"FAILED {str(e).splitlines()[0][:100]}",
                      flush=True)
        jax.clear_caches()


if __name__ == "__main__":
    from apex_tpu.utils.platform import setup_compile_cache
    setup_compile_cache()
    main()
