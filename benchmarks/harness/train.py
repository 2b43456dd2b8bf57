"""The ``train`` job: the program's own step, enqueued back to back, the
loss read every ``read_every``-th step as a trainer that logs does.  The
window ends on such a read, so every step counted has completed."""

from __future__ import annotations

import math
import time

from . import flops, trace as tracing
from .job import CompileCounter, Run, load_module

SPANS = ("make_batch", "train_step", "wait_loss")


def run(ctx):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.profiler import TraceAnnotation

    cfg, mix = ctx.config, ctx.traffic
    b = cfg["builder"]
    global_batch = cfg["micro_batch"] * ctx.chips
    recipe = load_module(ctx.root, b["file"], "bench_recipe")
    argv = [str(a).format(global_batch=global_batch, seed=ctx.seed)
            for a in b["argv"]]
    compiles = CompileCounter()
    train_step, state, make_batch, n_params = recipe.build(
        recipe.parse_args(argv), devices=ctx.devices)
    jax.block_until_ready(state)
    ctx.lap("weights")

    abs_sum = jax.jit(lambda t: sum(
        jnp.sum(jnp.abs(leaf.astype(jnp.float32)))
        for leaf in jax.tree_util.tree_leaves(t)))
    before = float(abs_sum(state[0]))
    step0 = int(state[1]["step"])
    losses, ran = [], 0

    def steps(n):
        nonlocal state, ran
        ran += n
        for _ in range(n):
            with TraceAnnotation("make_batch"):
                batch = make_batch()
            with TraceAnnotation("train_step"):
                *state, loss = train_step(*state, *batch)
        with TraceAnnotation("wait_loss"):
            losses.append(float(loss))

    every = mix["read_every"]
    steps(1)                    # compiles, or loads the program
    steps(every)                # the window's own rhythm, once
    ctx.lap("compile_or_cache_and_warmup")
    first_loss = losses[0]

    tokens_per_step = global_batch * cfg["seq_len"]
    compiles.arm()
    t0 = time.perf_counter()
    done, traced = 0, None
    while (now := time.perf_counter()) - t0 < ctx.seconds:
        if ctx.trace and traced is None and now - t0 >= ctx.seconds / 4:
            tracing.start(ctx.trace_dir)
            steps(mix["trace_steps"] // every * every)
            tracing.stop()
            traced = tracing.load(ctx.trace_dir, SPANS)
            done += mix["trace_steps"] // every * every
            continue
        steps(every)
        done += every
    window = time.perf_counter() - t0

    n_dev = len(ctx.devices)
    checks = {
        "losses finite": all(math.isfinite(x) for x in losses),
        "first loss within 5% of ln(vocab)":
            abs(first_loss / math.log(cfg["vocab_size_run"]) - 1) < 0.05,
        "parameters changed": float(abs_sum(state[0])) != before,
        "optimizer counted every step":
            int(state[1]["step"]) == step0 + ran,
        "state on every chip": all(
            len(leaf.sharding.device_set) == n_dev
            for leaf in jax.tree_util.tree_leaves(state[:2])),
        "no compile inside the window": compiles.count == 0,
    }
    notes = [f"check failed: {k}" for k, ok in checks.items() if not ok]
    return Run(
        correct=not notes, attempted=done, failed=0,
        end_to_end={"train_tokens_per_s": done * tokens_per_step / window},
        samples={}, trace=traced, notes=notes + [
            f"steps={done} window_s={window:.3f} first_loss={first_loss:.4f}"
            f" last_loss={losses[-1]:.4f} n_params={n_params}"],
        facts={"tokens_per_step": tokens_per_step, "chips": ctx.chips,
               "flops_per_token": flops.train_flops_per_token(
                   n_params, **cfg["flops"]),
               "shapes": cfg.get("kernel_shapes", {})})
