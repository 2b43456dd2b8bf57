#!/usr/bin/env python
"""Serving recipe for the ``glm_moe_dsa`` family (latent attention over a
paged pool of latent records, a learned sparse-attention indexer whose
top-k selection the layers above a "full" layer share, a shared expert and
sigmoid top-8 gated experts, a dense gated FFN in the leading layers, an
untied head), as ONE expert-parallel rank sees it: ``--config share`` is one
chip's share of a 16-chip layer of GLM-5.2 at the published widths
(published layers 2-6, experts 0-15 of 256, rows 0-19 359 of the 154 880-row
vocabulary; ``benchmarks/configs/glm-5.2.README.md``), the ``GPTConfig`` keys
of the benchmark configuration's ``model`` group
(``tests/test_glm_dsa.py`` holds the two to each other).

A published layer is attention then a feed-forward, each ``x + f(norm(x))``:
two symbols of the ``layer_pattern`` (``*`` attention; ``D`` dense FFN, ``E``
experts), and ``indexer_types`` says, a ``*`` layer, whether it owns an
indexer (``full``) or attends under the selection of the nearest full layer
below it (``shared``).

* model  — ``apex_tpu.models.gpt.GPTModel`` under a ``layer_pattern``;
           bf16 weights from ``init_params``, float32 norms and router
* engine — ``apex_tpu.serving.PagedInferenceEngine``: the pool's record is
           the model's (``GPTModel.cache_record()``: 640 numbers a layer,
           128 more on the layers that own an indexer), the bucketed
           prefill, one in-place write of a prompt's records, the tick

More than one device is refused (the share's experts and vocabulary are one
rank's; the exchange is not built), as are chunked prefill, speculative
decoding and the int8 pool, each naming what it lacks.

Run:  python examples/glm_dsa/serve_glm_dsa.py --config tiny \\
          --prompt-len 48 --new-tokens 8 --requests 3
"""

from __future__ import annotations

import argparse
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from apex_tpu.inference import Request  # noqa: E402
from apex_tpu.models.gpt import GPTConfig, GPTModel  # noqa: E402
from apex_tpu.serving import PagedInferenceEngine  # noqa: E402

# published layer 2 (dense FFN, full indexer; the three leading dense layers
# count once), layers 3-5 (experts, shared) and layer 6 (experts, full)
_BLOCK = dict(norm="rmsnorm", ffn_activation="swiglu", bias=False,
              tie_head=False, rotary=True, rope_base=8e6,
              moe_router="sigmoid", moe_routed_scale=2.5)
_CONFIGS = {
    # the published widths; 16 of 256 experts, 1/8 of the vocabulary
    "share": dict(
        _BLOCK, vocab_size=19360, hidden_size=6144, num_attention_heads=64,
        max_seq_len=16384, layer_pattern="*D*E*E*E*E",
        indexer_types=("full", "shared", "shared", "shared", "full"),
        ffn_hidden_size=2048, dense_ffn_hidden_size=12288, n_experts=256,
        moe_top_k=8, moe_shared_ffn=2048, moe_held=(0, 16),
        kv_lora_rank=512, q_lora_rank=2048, qk_nope_head_dim=192,
        qk_rope_head_dim=64, v_head_dim=256, index_topk=2048,
        index_n_heads=32, index_head_dim=128),
    # the CPU's size: full, shared, full; 4 of 8 experts, top 2
    "tiny": dict(
        _BLOCK, vocab_size=512, hidden_size=64, num_attention_heads=2,
        max_seq_len=128, layer_pattern="*D*E*E",
        indexer_types=("full", "shared", "full"), ffn_hidden_size=32,
        dense_ffn_hidden_size=96, n_experts=8, moe_top_k=2,
        moe_shared_ffn=32, moe_held=(0, 4), kv_lora_rank=32, q_lora_rank=48,
        qk_nope_head_dim=24, qk_rope_head_dim=8, v_head_dim=32, index_topk=8,
        index_n_heads=2, index_head_dim=16),
}
_ENGINES = {"share": dict(max_slots=8, block_size=64),
            "tiny": dict(max_slots=4, block_size=8)}


def parse_args(argv=None):
    p = argparse.ArgumentParser(description="apex_tpu glm_dsa serving")
    p.add_argument("--config", choices=sorted(_CONFIGS), default="tiny")
    p.add_argument("--prompt-len", type=int, default=48)
    p.add_argument("--new-tokens", type=int, default=8)
    p.add_argument("--requests", type=int, default=3)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--dtype", choices=("bfloat16", "float32"),
                   default="bfloat16")
    return p.parse_args(argv)


def model_config(name, dtype=jnp.bfloat16):
    return GPTConfig(**_CONFIGS[name], dtype=dtype, param_dtype=dtype)


def build(args):
    """``(model, params, engine)``: the share's model with weights from the
    seed, behind the paged engine at the configuration's engine sizes."""
    if jax.device_count() > 1 and jax.default_backend() != "cpu":
        raise SystemExit("one expert-parallel rank's share: run on one "
                         "device (the experts' exchange is not built)")
    dtype = getattr(jnp, args.dtype)
    model = GPTModel(model_config(args.config, dtype))
    params = jax.jit(model.init_params)(jax.random.PRNGKey(args.seed))
    engine = PagedInferenceEngine(model, params, cache_dtype=dtype,
                                  **_ENGINES[args.config])
    return model, params, engine


def main(argv=None):
    args = parse_args(argv)
    model, params, engine = build(args)
    cfg = model.cfg
    rng = np.random.default_rng(args.seed)
    for i in range(args.requests):
        engine.submit(Request(
            request_id=i, max_new_tokens=args.new_tokens, eos_id=None,
            prompt=rng.integers(0, cfg.vocab_size, args.prompt_len).tolist()))
    t0 = time.perf_counter()
    done = engine.run()
    seconds = time.perf_counter() - t0
    n = sum(a.size for a in jax.tree.leaves(params))
    print(f"{n:,} parameters; record {engine.pool.token_bytes} bytes a "
          f"token; {len(done)} requests of {args.prompt_len} + "
          f"{args.new_tokens} tokens in {seconds:.2f} s on "
          f"{jax.devices()[0].platform}")
    for r in done:
        print(r.request_id, r.finish_reason, list(r.tokens))
    return done


if __name__ == "__main__":
    from apex_tpu.utils.platform import setup_compile_cache
    setup_compile_cache()
    main()
