#!/usr/bin/env python
"""Pretraining recipe for the ``nemotron_h`` hybrid (Mamba-2 layers,
sigmoid-routed experts with a shared expert, grouped attention), as ONE
expert-parallel rank sees it: ``--config share`` is one chip's share of a
16-chip layer of NVIDIA-Nemotron-3-Nano-30B-A3B at the published widths
(the first 9 layers ``MEMEM*EME``, experts 0-7 of 128, rows 0-16 383 of
the 131 072-row vocabulary; ``benchmarks/configs/README.md``).

The same wiring as ``examples/bert/pretrain_bert.py``, in the body this
recipe shares with ``lfm2/pretrain_lfm2.py`` (``examples/share_recipe.py``):

* model  — ``apex_tpu.models.gpt.GPTModel`` under a ``layer_pattern``
* opt    — ``FusedAdam(master_weights=True)``, per leaf
* amp O2 — bf16 weights and activations; norms, the router, the Mamba
           decays (``A_log``, ``dt_bias``, ``D``) and the masters float32
* remat  — every layer recomputed in the backward

Random tokens, next-token loss over the sliced vocabulary.  The share is
one rank's: more than one device is refused (expert parallelism over
chips is not here yet), and serving this model raises.

Run:  python examples/nemotron_h/pretrain_nemotron_h.py --config tiny \\
          --batch-size 2 --seq-len 256 --steps 20
"""

from __future__ import annotations

import functools
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import share_recipe  # noqa: E402  (examples/share_recipe.py: the one body)

_BLOCK = dict(norm="rmsnorm", ffn_activation="relu2", bias=False,
              tie_head=False, rotary=False, moe_router="sigmoid",
              moe_top_k=6, moe_routed_scale=2.5, mamba_conv_kernel=4,
              layer_pattern="MEMEM*EME")
_CONFIGS = {
    # the published widths; 8 of 128 experts, 1/8 of the vocabulary
    "share": dict(
        _BLOCK, vocab_size=16384, hidden_size=2688, num_attention_heads=32,
        num_kv_heads=2, head_dim=128, ffn_hidden_size=1856, n_experts=128,
        moe_held=(0, 8), moe_shared_ffn=3712, mamba_num_heads=64,
        mamba_head_dim=64, mamba_state_size=128, mamba_groups=8,
        mamba_chunk_size=128),
    # the CPU's size: the same pattern, 4 of 16 experts
    "tiny": dict(
        _BLOCK, vocab_size=512, hidden_size=64, num_attention_heads=4,
        num_kv_heads=2, head_dim=32, ffn_hidden_size=48, n_experts=16,
        moe_held=(0, 4), moe_shared_ffn=96, mamba_num_heads=8,
        mamba_head_dim=16, mamba_state_size=16, mamba_groups=2,
        mamba_chunk_size=32),
}
# float32 under O2 beside the norms amp keeps: what decides an expert or a
# decay must not round to 8 bits
_KEEP_F32 = ("router", "A_log", "dt_bias", "'D'")
_ABOUT = "apex_tpu nemotron_h pretrain"

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
