"""Shared timing loop for the on-chip tools: median seconds per call,
each window ended by ``jax.block_until_ready``.  Importing it also puts
the checkout on ``sys.path`` so ``import apex_tpu`` works from any CWD.
"""

from __future__ import annotations

import os
import sys
import time

# make `import apex_tpu` work regardless of the caller's CWD
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax                                                   # noqa: E402


def time_steps(fn, args, warmup=2, iters=8, rounds=3):
    """Median seconds per call over ``rounds`` windows."""
    for _ in range(warmup):
        out = fn(*args)
    jax.block_until_ready(out)
    times = []
    for _ in range(rounds):
        t0 = time.perf_counter()
        for _ in range(iters):
            out = fn(*args)
        jax.block_until_ready(out)
        times.append((time.perf_counter() - t0) / iters)
    times.sort()
    return times[len(times) // 2]
