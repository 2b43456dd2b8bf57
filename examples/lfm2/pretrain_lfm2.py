#!/usr/bin/env python
"""Pretraining recipe for the ``lfm2_moe`` family (gated short convolutions,
gated top-4 experts with no shared expert, 32/8 grouped attention at head 64
with QK-norm and rotary positions at base 1e6, a dense gated FFN in the
leading layers, a tied head), as ONE expert-parallel rank sees it:
``--config share`` is one chip's share of an 8-chip layer of LFM2-24B-A2B at
the published widths (published layer 1 and layers 2-7, experts 0-7 of 64,
rows 0-8 191 of the 65 536-row vocabulary;
``benchmarks/configs/README.md``, "lfm2-24b-a2b").

A published layer is an operator then a feed-forward, each ``x +
f(norm(x))``: two symbols of the ``layer_pattern`` (``C`` convolution, ``*``
attention; ``D`` dense FFN, ``E`` experts).  The body is the one
``nemotron_h/pretrain_nemotron_h.py`` runs (``examples/share_recipe.py``):

* model  — ``apex_tpu.models.gpt.GPTModel`` under a ``layer_pattern``
* opt    — ``FusedAdam(master_weights=True)``, per leaf; the replicated
           router is not stepped on one rank's term of its gradient
* amp O2 — bf16 weights and activations; norms (QK-norm's too), the router
           and the masters float32
* remat  — every layer recomputed in the backward

Random tokens, next-token loss over the sliced vocabulary.  More than one
device is refused, and serving this model raises.

Run:  python examples/lfm2/pretrain_lfm2.py --config tiny \\
          --batch-size 2 --seq-len 256 --steps 20
"""

from __future__ import annotations

import functools
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import share_recipe  # noqa: E402  (examples/share_recipe.py: the one body)

# published layer 1 (conv + dense FFN; the two leading dense layers count
# once), then layers 2-7: attention, conv, conv, conv, attention, conv, each
# with experts
_BLOCK = dict(norm="rmsnorm", ffn_activation="swiglu", bias=False,
              tie_head=True, rotary=True, rope_base=1e6, qk_norm=True,
              moe_router="sigmoid", moe_top_k=4, moe_routed_scale=1.0,
              short_conv_kernel=3, layer_pattern="CD*ECECECE*ECE")
_CONFIGS = {
    # the published widths; 8 of 64 experts, 1/8 of the vocabulary
    "share": dict(
        _BLOCK, vocab_size=8192, hidden_size=2048, num_attention_heads=32,
        num_kv_heads=8, head_dim=64, ffn_hidden_size=1536,
        dense_ffn_hidden_size=11776, n_experts=64, moe_held=(0, 8)),
    # the CPU's size: the same pattern, 4 of 16 experts
    "tiny": dict(
        _BLOCK, vocab_size=512, hidden_size=64, num_attention_heads=4,
        num_kv_heads=2, head_dim=16, ffn_hidden_size=48,
        dense_ffn_hidden_size=96, n_experts=16, moe_held=(0, 4)),
}
# float32 under O2 beside the norms amp keeps: what decides an expert must
# not round to 8 bits
_KEEP_F32 = ("router",)
_ABOUT = "apex_tpu lfm2 pretrain"

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
