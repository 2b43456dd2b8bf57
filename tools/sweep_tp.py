#!/usr/bin/env python
"""On-chip tensor-parallel overlap sweep: sequence-parallel GPT train
step across ``overlap_chunks`` (ring granularity) x tp width, against
the replicated-activation baseline (companion to tools/sweep_gpt.py;
same timing protocol).

``chunks=r`` is the replicated (pre-sequence-parallel) arm; ``chunks=0``
is sequence-parallel with monolithic gather/scatter collectives; higher
chunk counts split each TP-edge collective+GEMM pair into that many
ring sub-steps, trading launch overhead for collective/compute overlap.
The sweet spot is topology-dependent — on a CPU host mesh (no real ICI)
chunking only adds overhead; sweep on the target slice.

Usage: ``python tools/sweep_tp.py [name,name,...]`` where names look
like ``tp4_c2`` / ``tp4_repl`` (default: every arm that fits the
device count).
"""

from __future__ import annotations

import sys

import jax
import jax.numpy as jnp
import numpy as np

from _timing import time_steps as _time  # noqa: E402


def make_step(tp, chunks, replicated=False, batch=4, seq=512):
    from jax.sharding import PartitionSpec as P

    from apex_tpu.models.gpt import GPTConfig, GPTModel, pack_for_shard_map

    cfg = GPTConfig(vocab_size=8192, hidden_size=512, num_layers=4,
                    num_attention_heads=8, max_seq_len=seq, rotary=True,
                    tensor_parallel_size=tp, axis_name="model",
                    sequence_parallel=not replicated,
                    overlap_chunks=0 if replicated else chunks,
                    dtype=jnp.bfloat16)
    model = GPTModel(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    rng = np.random.RandomState(0)
    tokens = jnp.asarray(rng.randint(0, cfg.vocab_size, (batch, seq)))
    targets = jnp.asarray(rng.randint(0, cfg.vocab_size, (batch, seq)))

    mesh = jax.make_mesh((tp,), ("model",))
    packed, in_specs, local_fn, repack_fn = pack_for_shard_map(model, params)

    def step(sp, tokens, targets):
        loss, g = jax.value_and_grad(model.loss)(local_fn(sp), tokens,
                                                 targets)
        return loss, repack_fn(g)

    run = jax.jit(jax.shard_map(step, mesh=mesh,
                                in_specs=(in_specs, P(), P()),
                                out_specs=(P(), in_specs), check_vma=False))

    def timed(tokens, targets):
        loss, _ = run(packed, tokens, targets)
        return loss

    return timed, (tokens, targets), batch * seq


def main():
    n_dev = len(jax.devices())
    configs = []
    for tp in (2, 4, 8):
        if tp > n_dev:
            break
        configs.append((f"tp{tp}_repl", dict(tp=tp, chunks=0,
                                             replicated=True)))
        for chunks in (0, 1, 2, 4, 8):
            configs.append((f"tp{tp}_c{chunks}", dict(tp=tp,
                                                      chunks=chunks)))
    if not configs:
        print(f"needs >=2 devices for tensor parallelism, have {n_dev}",
              flush=True)
        return
    if len(sys.argv) > 1:
        names = set(sys.argv[1].split(","))
        configs = [c for c in configs if c[0] in names]
    base = {}  # tp -> replicated step time, for the speedup column
    for name, kw in configs:
        try:
            run, args, tok = make_step(**kw)
            dt = _time(run, args)
            extra = ""
            if kw.get("replicated"):
                base[kw["tp"]] = dt
            elif kw["tp"] in base:
                extra = f"  [{base[kw['tp']] / dt:.3f}x vs replicated]"
            print(f"{name}: {tok / dt:,.0f} tok/s (step {dt * 1e3:.1f} ms)"
                  f"{extra}", flush=True)
        except Exception as e:
            print(f"{name}: FAILED {type(e).__name__}: "
                  f"{str(e).splitlines()[0][:120]}", flush=True)
        jax.clear_caches()


if __name__ == "__main__":
    from apex_tpu.utils.platform import setup_compile_cache
    setup_compile_cache()
    main()
