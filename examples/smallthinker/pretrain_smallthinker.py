#!/usr/bin/env python
"""Pretraining recipe for the ``smallthinker`` family (window and full
attention layers 3:1 over 28 query / 4 KV heads of 128, no position code on
the full layer, a router that reads the layer's input before attention,
softmax top-6 ReGLU experts with none shared, an untied head), as ONE
expert-parallel rank sees it: ``--config share`` is one chip's share of a
4-chip layer of SmallThinker-21BA3B-Instruct at the published widths
(published layers 0-3, experts 0-15 of 64, rows 0-37 983 of the 151 936-row
vocabulary; ``benchmarks/configs/smallthinker-21ba3b-instruct.README.md``).

A published layer is attention then experts, each ``x + f(norm(x))``: two
symbols of the ``layer_pattern`` (``*`` attention, ``E`` experts), the
experts routed on the attention layer's input.  The body is the one
``lfm2/pretrain_lfm2.py`` runs (``examples/share_recipe.py``):

* model  — ``apex_tpu.models.gpt.GPTModel`` under a ``layer_pattern``; the
           window and full layers through ``grouped_flash``
* opt    — ``FusedAdam(master_weights=True)``, per leaf; the replicated
           router is not stepped on one rank's term of its gradient
* amp O2 — bf16 weights and activations; norms, the router and the masters
           float32
* remat  — every layer recomputed in the backward

Random tokens, next-token loss over the sliced vocabulary.  More than one
device is refused, and serving this model raises.

Run:  python examples/smallthinker/pretrain_smallthinker.py --config tiny \\
          --batch-size 1 --seq-len 256 --steps 20
"""

from __future__ import annotations

import functools
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import share_recipe  # noqa: E402  (examples/share_recipe.py: the one body)

# published layers 0-3: full, window, window, window (sliding_window_layout
# and rope_layout 0, 1, 1, 1), each attention then experts
_BLOCK = dict(norm="rmsnorm", norm_eps=1e-6, ffn_activation="reglu",
              bias=False, tie_head=False, rotary=True, rope_base=1.5e6,
              moe_router="softmax_sorted", moe_router_bias=False,
              moe_top_k=6, moe_routed_scale=1.0,
              moe_route_before_attention=True, layer_pattern="*E*E*E*E",
              attention_types=("full", "sliding", "sliding", "sliding"),
              rotary_layers=(0, 1, 1, 1))
_CONFIGS = {
    # the published widths; 16 of 64 experts, 37 984 of 151 936 rows
    "share": dict(
        _BLOCK, vocab_size=37984, hidden_size=2560, num_attention_heads=28,
        num_kv_heads=4, head_dim=128, ffn_hidden_size=768, n_experts=64,
        moe_held=(0, 16), sliding_window=4096),
    # the CPU's size: the same pattern, 2 of 8 experts, 7 heads over 1
    "tiny": dict(
        _BLOCK, vocab_size=512, hidden_size=64, num_attention_heads=7,
        num_kv_heads=1, head_dim=128, ffn_hidden_size=48, n_experts=8,
        moe_held=(0, 2), sliding_window=64),
}
# float32 under O2 beside the norms amp keeps: what decides an expert must
# not round to 8 bits
_KEEP_F32 = ("router",)
_ABOUT = "apex_tpu smallthinker pretrain"

parse_args = functools.partial(share_recipe.parse_args, _CONFIGS, _ABOUT)
model_config = functools.partial(share_recipe.model_config, _CONFIGS)
init_params = functools.partial(share_recipe.init_params, _KEEP_F32)
build = functools.partial(share_recipe.build, _CONFIGS, _KEEP_F32)
zero_counters, expert_load = share_recipe.zero_counters, \
    share_recipe.expert_load


if __name__ == "__main__":
    from apex_tpu.utils.platform import setup_compile_cache
    setup_compile_cache()
    share_recipe.main(_CONFIGS, _KEEP_F32, _ABOUT)
