"""GPT model built from apex_tpu components — the flagship model family
(reference: ``apex/transformer/testing/standalone_gpt.py``, which wires
apex's TP layers/fused ops into a Megatron-style GPT for the L0 tests; the
same wiring here is the production model).

Every compute block is a framework component: VocabParallelEmbedding,
ColumnParallelLinear/RowParallelLinear (TP + sequence parallel),
MixedFusedLayerNorm (Pallas), fused RoPE, causal flash attention (Pallas),
vocab-parallel cross entropy.  One config serves three execution modes:

* serial  — ``tensor_parallel_size=1, axis_name=None`` (tests, single chip)
* GSPMD   — jit the serial form with ``partition_specs()``
* shard_map — ``axis_name="model"`` with sharded params; combine with the
  pipeline engine by stacking layer params per stage.

Activations are ``(batch, seq, hidden)``.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from apex_tpu.normalization import MixedFusedLayerNorm, MixedFusedRMSNorm
from apex_tpu.ops.flash_attention import (dequantize_kv_blocks,
                                          flash_attention_bshd,
                                          grouped_attention,
                                          grouped_flash,
                                          flash_attention_chunk_paged,
                                          flash_attention_decode,
                                          flash_attention_decode_paged,
                                          flash_attention_decode_paged_quant,
                                          quantize_kv_blocks,
                                          scatter_paged_kv)
from apex_tpu.ops.fused_ffn import fused_ffn_tp
from apex_tpu.ops.latent_attention import (gather_index_keys, index_scores,
                                           index_segments, latent_record,
                                           latent_record_width,
                                           masked_attention, rotary_pairs,
                                           scatter_record,
                                           sparse_decode_attention,
                                           topk_mask, topk_positions)
from apex_tpu.ops.rope import (fused_apply_rotary_pos_emb_at_positions,
                               fused_apply_rotary_pos_emb_cached, rope_freqs)
from apex_tpu.transformer import tensor_parallel as tp

_f32 = jnp.float32
INIT_STD = 0.02        # of every matrix (the linear layers' own default)

# Dropout-stream strides: layer i / microbatch m walk the seed space at
# large odd strides (bijective mod 2^32, int32 wraparound is fine) so a
# caller advancing the base seed by +1 per training step can never land
# on a neighboring layer's or microbatch's stream from another step —
# with stride 1 ("seed + i"), step t+1 layer i would replay step t
# layer i+1's mask exactly.
_SEED_LAYER_STRIDE = 0x3C6EF35F
_SEED_MB_STRIDE = 0x5BD1E995
_SEED_TP_RANK_STRIDE = 0x7F4A7C15  # per-TP-rank dropout stream offset


def _remat_policy(name: str):
    """jax.checkpoint policy for a GPTConfig.remat_policy name."""
    if name == "dots":
        return jax.checkpoint_policies.dots_with_no_batch_dims_saveable
    return None                                  # "full": save nothing


@dataclasses.dataclass
class GPTConfig:
    vocab_size: int = 50304
    hidden_size: int = 768
    num_layers: int = 12
    num_attention_heads: int = 12
    max_seq_len: int = 1024
    ffn_hidden_size: Optional[int] = None      # default 4*hidden
    tensor_parallel_size: int = 1
    axis_name: Optional[str] = None            # "model" inside shard_map
    sequence_parallel: bool = False
    overlap_chunks: int = 0                    # >0: ppermute-ring TP GEMMs
    rotary: bool = True
    context_axis: Optional[str] = None         # CP: sequence sharded here
    context_mechanism: str = "ring"            # "ring" | "ulysses"
    n_experts: int = 0                         # >0: Switch/GShard MoE FFN
    moe_top_k: int = 1
    moe_capacity_factor: float = 1.25
    moe_aux_weight: float = 1e-2
    expert_axis: Optional[str] = None          # EP: experts sharded here
    expert_parallel_size: int = 1
    attention_dropout: float = 0.0             # fused flash-kernel dropout
    fused_lm_head: bool = True                 # logit-free blockwise CE
    fused_ffn: bool = False                    # Pallas fused bias-GELU FFN
    weight_quant: Optional[str] = None         # "int8": decode-path weights
    remat: bool = False                        # jax.checkpoint each layer
    remat_policy: str = "full"                 # "full" | "dots" (selective)
    dtype: jnp.dtype = jnp.float32             # activation/compute dtype
    param_dtype: jnp.dtype = jnp.float32
    # -- the block's variants; every default is the GPT-2 block -----------
    norm: str = "layernorm"                    # | "rmsnorm"
    norm_eps: float = 1e-5                     # the block's norms' epsilon
    ffn_activation: str = "gelu"    # | "relu2" | "swiglu", "reglu" (gated)
    bias: bool = True                          # on every linear layer
    num_kv_heads: Optional[int] = None         # < heads: grouped attention
    head_dim: Optional[int] = None             # default hidden / heads
    tie_head: bool = True                      # False: its own head matrix
    rope_base: float = 10000.0                 # rotary: the frequencies' base
    qk_norm: bool = False       # RMSNorm over head_dim on q and k (pattern)
    # one mixer a layer, by symbol: "M" Mamba-2, "E" experts, "*" attention,
    # "C" gated short convolution, "D" dense FFN (each ``x + mixer(norm(x))``);
    # "P" the parallel block, ``x + attention(h) + experts(h)`` with ``h =
    # norm(x)`` once; None is num_layers attention+FFN blocks
    layer_pattern: Optional[str] = None
    dense_ffn_hidden_size: Optional[int] = None   # "D": default ffn_hidden
    short_conv_kernel: int = 3                    # "C": taps
    mamba_num_heads: int = 0
    mamba_head_dim: int = 64
    mamba_state_size: int = 128
    mamba_groups: int = 8
    mamba_conv_kernel: int = 4
    mamba_chunk_size: int = 128
    # | "sigmoid", "softmax_sorted": the sorted dispatch's routers, sigmoid
    # scores or the softmax over the chosen logits
    moe_router: str = "softmax"
    moe_routed_scale: float = 1.0
    moe_shared_ffn: int = 0                    # width of the shared expert
    moe_held: Optional[tuple] = None           # (offset, count) held here
    moe_shared_combine: str = "sum"     # | "average": (routed + shared) / 2
    moe_router_bias: bool = True        # the sigmoid router's balancing bias
    # an "E" layer routes on the input of the "*" layer below it (the
    # residual stream before that layer's norm), not on its own norm's output
    moe_route_before_attention: bool = False
    norm_bias: bool = True                     # LayerNorm with its bias
    # window and full attention mixed: one entry a "*" or "P" layer,
    # "sliding" (a layer that attends to the sliding_window positions up to
    # and including its own) or "full"; None is every layer full
    attention_types: Optional[tuple] = None
    sliding_window: int = 0
    # one entry a "*" layer: 1 where it turns q and k to their positions, 0
    # where it carries no position code; None is every "*" layer as
    # ``rotary`` says ("P" layers rotate where they slide)
    rotary_layers: Optional[tuple] = None
    # latent attention (MLA) in the "*" layers: kv_lora_rank > 0 turns it
    # on.  A cached position is (c, k_rope): kv_lora_rank + qk_rope_head_dim
    # numbers a layer, whatever the number of heads.  Its sparse-attention
    # indexer picks the index_topk positions a query attends to; a "full"
    # layer owns one, a "shared" layer uses the selection of the nearest
    # "full" layer below it (one entry a "*" layer)
    kv_lora_rank: int = 0
    q_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    index_topk: int = 0
    index_n_heads: int = 0
    index_head_dim: int = 0
    indexer_types: Optional[tuple] = None      # default: every layer "full"
    # one validated ParallelPlan instead of the per-knob kwargs above:
    # tp/SP/overlap/remat knobs are filled from it (plan wins on
    # conflict, with a DeprecationWarning); dp/pp/schedule fields are
    # consumed by the optimizer/pipeline layers, not the config
    plan: Optional[object] = None

    def __post_init__(self):
        if self.plan is not None:
            from apex_tpu.parallel.plan import apply_plan_to_config
            apply_plan_to_config(self)
        if self.ffn_hidden_size is None:
            self.ffn_hidden_size = 4 * self.hidden_size
        if self.head_dim is None and self.kv_lora_rank:
            self.head_dim = self.qk_nope_head_dim + self.qk_rope_head_dim
        if self.head_dim is None:
            if self.hidden_size % self.num_attention_heads:
                raise ValueError(
                    "hidden_size must be divisible by num_attention_heads")
            self.head_dim = self.hidden_size // self.num_attention_heads
        if self.num_kv_heads is None:
            self.num_kv_heads = self.num_attention_heads
        if self.moe_held is not None:
            self.moe_held = tuple(self.moe_held)    # a file gives a list
        self._check_block_variants()
        if self.num_attention_heads % self.tensor_parallel_size:
            raise ValueError(
                "num_attention_heads must be divisible by "
                "tensor_parallel_size")
        if self.context_mechanism not in ("ring", "ulysses"):
            raise ValueError(
                f"context_mechanism must be 'ring' or 'ulysses', got "
                f"{self.context_mechanism!r}")
        if self.n_experts > 0 and (
                self.ffn_hidden_size % self.tensor_parallel_size):
            raise ValueError(
                "MoE ffn_hidden_size must be divisible by "
                "tensor_parallel_size (each expert's FFN dim is "
                "Column/Row-sharded over the tensor axis)")
        if self.expert_axis is not None and self.n_experts <= 0:
            raise ValueError(
                "expert_axis requires n_experts > 0 (the axis shards "
                "the MoE expert stacks)")
        if not 0.0 <= self.attention_dropout < 1.0:
            raise ValueError(
                f"attention_dropout must be in [0, 1), got "
                f"{self.attention_dropout}")
        if self.attention_dropout > 0.0 and self.context_axis is not None:
            raise ValueError(
                "attention_dropout is not supported with context "
                "parallelism (the ring/ulysses kernels take no dropout)")
        if self.remat_policy not in ("full", "dots"):
            raise ValueError(
                f"remat_policy must be 'full' or 'dots', got "
                f"{self.remat_policy!r}")
        if self.overlap_chunks < 0:
            raise ValueError(
                f"overlap_chunks must be >= 0, got {self.overlap_chunks}")
        if self.overlap_chunks > 0 and not self.sequence_parallel:
            raise ValueError(
                "overlap_chunks rings the sequence-parallel collective/GEMM "
                "pairs; it requires sequence_parallel=True")
        if self.sequence_parallel and self.context_axis is not None:
            raise ValueError(
                "sequence_parallel and context parallelism both shard the "
                "sequence dimension; enable one or the other")
        if self.sequence_parallel and self.n_experts > 0:
            raise ValueError(
                "sequence_parallel does not compose with MoE FFNs: the "
                "router's TP-internal psum assumes every tensor rank sees "
                "the same (replicated) tokens, but SP shards them")
        if self.fused_ffn and self.n_experts > 0:
            raise ValueError(
                "fused_ffn fuses the dense ParallelMLP pair; with "
                "n_experts > 0 every FFN slot is a MoEFFN and the knob "
                "would be silently dead — enable one or the other")
        if self.weight_quant not in (None, "int8"):
            raise ValueError(
                f"weight_quant must be None or 'int8', got "
                f"{self.weight_quant!r}")
        if self.weight_quant is not None and self.n_experts > 0:
            raise ValueError(
                "weight_quant covers the dense qkv/proj/fc1/fc2/lm-head "
                "GEMMs; MoE expert stacks (n_experts > 0) keep their own "
                "3D weight layout that quantize_decode_params does not "
                "produce — disable one or the other")
        if self.weight_quant is not None and self.fused_ffn:
            raise ValueError(
                "weight_quant routes the FFN through the int8 "
                "dequant-GEMMs, which fused_ffn would bypass (the fused "
                "kernel consumes raw f32/bf16 fc1/fc2 leaves) — enable "
                "one or the other")

    def _check_block_variants(self):
        for field, allowed in (("norm", ("layernorm", "rmsnorm")),
                               ("ffn_activation",
                                ("gelu", "relu2", "swiglu", "reglu")),
                               ("moe_router", ("softmax", "sigmoid",
                                               "softmax_sorted")),
                               ("moe_shared_combine", ("sum", "average"))):
            if getattr(self, field) not in allowed:
                raise ValueError(f"{field} must be one of {allowed}, got "
                                 f"{getattr(self, field)!r}")
        grouped = self.num_kv_heads != self.num_attention_heads
        if grouped and (self.num_attention_heads % self.num_kv_heads
                        or self.tensor_parallel_size > 1):
            raise ValueError(
                "num_kv_heads must divide num_attention_heads, and grouped "
                "attention has no tensor-parallel split of its KV heads yet")
        if self.layer_pattern is None:
            if grouped or self.moe_router != "softmax" or not self.tie_head \
                    or self.qk_norm or self.gated \
                    or self.kv_lora_rank or self.attention_types \
                    or self.rotary_layers or self.moe_route_before_attention \
                    or self.head_dim * self.num_attention_heads \
                    != self.hidden_size:
                raise ValueError(
                    "grouped attention, a free head_dim, QK-norm, latent "
                    "attention, window layers, rotary by layer, a "
                    "gated FFN, the sorted dispatch's routers and an "
                    "untied head run under a layer_pattern only: the cache and "
                    "decode paths of the plain block assume hidden = heads x "
                    "head_dim, equal head counts, no norm on q and k and a "
                    "tied head, and its FFN (and ops/fused_ffn.py) is one "
                    "up-projection")
            return
        if set(self.layer_pattern) - set("ME*CDP") or not self.layer_pattern:
            raise ValueError(
                f"layer_pattern {self.layer_pattern!r}: one of 'M' (Mamba-2), "
                "'E' (experts), '*' (attention), 'C' (gated short "
                "convolution), 'D' (dense FFN), 'P' (attention and experts "
                "side by side) per layer")
        self.num_layers = len(self.layer_pattern)
        if self.dense_ffn_hidden_size is None:
            self.dense_ffn_hidden_size = self.ffn_hidden_size
        pp = getattr(self.plan, "pp", 1) if self.plan is not None else 1
        for name, on in (
                ("tensor parallelism", self.tensor_parallel_size > 1
                 or self.axis_name is not None),
                ("sequence_parallel", self.sequence_parallel),
                ("context parallelism", self.context_axis is not None),
                ("pipeline parallelism", pp > 1),
                ("fused_ffn", self.fused_ffn),
                ("weight_quant", self.weight_quant is not None),
                ("expert_axis", self.expert_axis is not None
                 or self.expert_parallel_size > 1)):
            if on:
                raise ValueError(
                    f"a layer_pattern does not compose with {name} yet: its "
                    "Mamba, convolution and expert mixers are written for "
                    "one device's share (say which experts live here with "
                    "moe_held)")
        if "M" in self.layer_pattern and (
                self.mamba_num_heads <= 0
                or self.mamba_num_heads % self.mamba_groups):
            raise ValueError("an 'M' layer needs mamba_num_heads, a multiple "
                             "of mamba_groups")
        if "E" in self.layer_pattern and self.n_experts <= 0:
            raise ValueError("an 'E' layer needs n_experts > 0")
        if "E" in self.layer_pattern and self.gated \
                and self.moe_router == "softmax":
            raise ValueError("gated ('swiglu', 'reglu') experts in an 'E' "
                             "layer need a sorted dispatch's router "
                             "('sigmoid' or 'softmax_sorted'): only the "
                             "sorted dispatch has the [gate | up] stack")
        if "C" in self.layer_pattern and self.short_conv_kernel < 1:
            raise ValueError("a 'C' layer needs short_conv_kernel >= 1")
        if "P" in self.layer_pattern and (
                set(self.layer_pattern) != {"P"} or self.n_experts <= 0
                or self.moe_router != "sigmoid" or self.kv_lora_rank
                or self.qk_norm):
            raise ValueError(
                "a pattern of 'P' layers has no other symbol, n_experts > 0 "
                "with moe_router='sigmoid' (its experts are the sorted "
                "dispatch's) and plain attention (no latent attention, no "
                "QK-norm); its layers turn adjacent pairs of lanes where "
                "they rotate")
        self._check_attention_types(grouped)
        self._check_rotary_layers()
        if self.moe_route_before_attention:
            self._check_route_before_attention()
        if self.kv_lora_rank:
            self._check_latent_attention(grouped)

    def _check_attention_types(self, grouped):
        attending = [m for m in self.layer_pattern if m in "*P"]
        if self.attention_types is None:
            self.attention_types = ("full",) * len(attending)
        self.attention_types = tuple(self.attention_types)
        # a "*" layer of a model with window layers attends through the
        # grouped training kernel, which reads [q | k | v] rows
        if (len(self.attention_types) != len(attending)
                or set(self.attention_types) - {"full", "sliding"}
                or ("sliding" in self.attention_types)
                != (self.sliding_window > 0)
                or (self.sliding_window > 0 and "*" in self.layer_pattern
                    and (not grouped or self.kv_lora_rank
                         or self.attention_dropout > 0.0))):
            raise ValueError(
                f"attention_types {self.attention_types!r}: 'full' or "
                f"'sliding' for each of the pattern's {len(attending)} '*' "
                "and 'P' layers, a sliding_window > 0 exactly when some "
                "layer slides, and '*' layers beside window layers grouped "
                "(num_kv_heads < num_attention_heads), not latent and "
                "without attention dropout")

    def _check_rotary_layers(self):
        n = self.layer_pattern.count("*")
        if self.rotary_layers is None:
            return
        self.rotary_layers = tuple(int(r) for r in self.rotary_layers)
        if (len(self.rotary_layers) != n or set(self.rotary_layers) - {0, 1}
                or not self.rotary or self.kv_lora_rank):
            raise ValueError(
                f"rotary_layers {self.rotary_layers!r}: 1 or 0 for each of "
                f"the pattern's {n} '*' layers, with rotary=True and no "
                "latent attention")

    def _check_route_before_attention(self):
        pattern = self.layer_pattern
        if (self.moe_router == "softmax" or self.kv_lora_rank
                or "E" not in pattern
                or any(m == "E" and (i == 0 or pattern[i - 1] != "*")
                       for i, m in enumerate(pattern))):
            raise ValueError(
                "moe_route_before_attention routes each 'E' layer on the "
                "input of the '*' layer right below it: every 'E' follows a "
                "'*' (no latent attention), and the router is a sorted "
                "dispatch's ('sigmoid' or 'softmax_sorted')")

    def _check_latent_attention(self, grouped):
        if (min(self.q_lora_rank, self.qk_nope_head_dim, self.v_head_dim,
                self.index_topk, self.index_n_heads) < 1
                or self.qk_rope_head_dim < 2 or self.qk_rope_head_dim % 2
                or self.index_head_dim < self.qk_rope_head_dim
                or not self.rotary or grouped or self.qk_norm
                or self.attention_dropout > 0.0):
            raise ValueError(
                "latent attention (kv_lora_rank > 0) needs q_lora_rank, "
                "qk_nope_head_dim, v_head_dim, an even qk_rope_head_dim, its "
                "indexer (index_topk, index_n_heads, index_head_dim >= "
                "qk_rope_head_dim: attention over every position is "
                "index_topk >= the context), rotary positions, and neither "
                "grouped heads, QK-norm nor attention dropout")
        n = self.layer_pattern.count("*")
        if self.indexer_types is None:
            self.indexer_types = ("full",) * n
        self.indexer_types = tuple(self.indexer_types)
        if (len(self.indexer_types) != n or n < 1
                or set(self.indexer_types) - {"full", "shared"}
                or self.indexer_types[0] != "full"):
            raise ValueError(
                f"indexer_types {self.indexer_types!r}: 'full' or 'shared' "
                f"for each of the pattern's {n} '*' layers, the first "
                "'full' (a shared layer uses the selection of the nearest "
                "full layer below it)")

    @property
    def gated(self):
        """The FFN is ``[gate | up]`` side by side (``swiglu``, ``reglu``)."""
        return self.ffn_activation in ("swiglu", "reglu")

    @property
    def learned_positions(self):
        """A position table is added to the embeddings: rotary off, and no
        layer pattern (whose Mamba or convolution layers carry the order)."""
        return not self.rotary and self.layer_pattern is None

    @property
    def local_heads(self):
        return self.num_attention_heads // self.tensor_parallel_size


class _WeightOnlyLayerNorm(MixedFusedLayerNorm):
    """LayerNorm with a weight and no bias (``cfg.norm_bias`` False): the
    fused kernel, given a bias of zeros that is no parameter."""

    def init_params(self):
        return {"weight": jnp.ones(self.normalized_shape, self.param_dtype)}

    def __call__(self, params, x):
        return super().__call__(
            {**params, "bias": jnp.zeros(self.normalized_shape,
                                         self.param_dtype)}, x)


def _norm(cfg):
    """The block's norm, by ``cfg.norm`` (and ``cfg.norm_bias``)."""
    if cfg.norm == "rmsnorm":
        return MixedFusedRMSNorm(cfg.hidden_size, eps=cfg.norm_eps)
    if not cfg.norm_bias:
        return _WeightOnlyLayerNorm(cfg.hidden_size, eps=cfg.norm_eps)
    return MixedFusedLayerNorm(cfg.hidden_size, eps=cfg.norm_eps)


_HEAD_NORM_EPS = 1e-5


def _lane_groups(width, head_dim):
    """``(width, width // head_dim)`` float32: 1 where lane ``i`` belongs to
    head ``j`` of rows of ``width`` lanes."""
    return (jnp.arange(width)[:, None] // head_dim
            == jnp.arange(width // head_dim)).astype(_f32)


def _rows_rms_norm(x, weight):
    """RMSNorm over each head's lanes of ``(b, s, heads * head_dim)`` rows
    in float32 (QK-norm: every head alike, one ``weight`` of ``head_dim``).
    The sums over a head's lanes and their way back to the lanes are two
    products with a 0/1 matrix, so no array is ever split into heads."""
    head_dim = weight.shape[0]
    groups = _lane_groups(x.shape[-1], head_dim)
    x32 = x.astype(_f32)
    mean_sq = jnp.einsum("bsw,wh->bsh", x32 * x32, groups,
                         precision=jax.lax.Precision.HIGHEST) / head_dim
    rstd = jnp.einsum("bsh,wh->bsw",
                      jax.lax.rsqrt(mean_sq + _HEAD_NORM_EPS), groups,
                      precision=jax.lax.Precision.HIGHEST)
    return (x32 * rstd * jnp.tile(weight.astype(_f32),
                                  x.shape[-1] // head_dim)).astype(x.dtype)


def _rows_rotary(x, cos, sin):
    """Rotary positions, rotate-half over each head's lanes, on ``(b, s,
    heads * head_dim)`` rows; ``cos``/``sin`` are :func:`rope_freqs`'s
    ``(s, 1, 1, head_dim)`` tables.  The other half of a head is 32 lanes
    away: a roll of the row, taken from the left or the right."""
    head_dim = cos.shape[-1]
    width = x.shape[-1]
    cos = jnp.tile(cos.reshape(-1, head_dim), (1, width // head_dim))
    sin = jnp.tile(sin.reshape(-1, head_dim), (1, width // head_dim))
    first_half = jnp.arange(width) % head_dim < head_dim // 2
    x32 = x.astype(_f32)
    rotated = jnp.where(first_half, -jnp.roll(x32, -(head_dim // 2), -1),
                        jnp.roll(x32, head_dim // 2, -1))
    return (x32 * cos + rotated * sin).astype(x.dtype)


_ROTARY_ROWS = 1024     # a prefill's rows turned at a time


def _rotary_rows(x, positions, head_dim, base):
    """:func:`rotary_pairs` over every lane of each head of rows ``x``
    ``(b, s, heads * head_dim)`` at ``positions`` (``(s,)``, or ``(b, 1)``
    for a tick), ``_ROTARY_ROWS`` rows at a time: turned whole, a prefill's
    16 384 rows of 128 query heads left a float32 copy of 1 GB for every
    step of the turn, three of them alive at once."""
    s = x.shape[1]
    if s <= _ROTARY_ROWS or s % _ROTARY_ROWS:
        return rotary_pairs(x, positions, head_dim, base, head_dim=head_dim)
    n = s // _ROTARY_ROWS
    chunks = x.reshape(x.shape[0], n, _ROTARY_ROWS, -1).swapaxes(0, 1)
    out = jax.lax.map(
        lambda a: rotary_pairs(a[0], a[1], head_dim, base,
                               head_dim=head_dim),
        (chunks, positions.reshape(n, _ROTARY_ROWS)))
    return out.swapaxes(0, 1).reshape(x.shape)


def _broadcast_heads(x, times):
    """Each KV head of ``(b, s, kv_heads, head_dim)`` to the ``times`` query
    heads it serves.  Heads that fill whole 128-lane tiles are repeated
    where they lie; narrower ones on the ``(b, s, kv_heads * head_dim)``
    rows, by a product with a 0/1 matrix (exact: one term a lane), because
    XLA repeats a ``(b, s, heads, 64)`` array in a layout with the sequence
    in the lanes, a transposing copy on each side.  Its transpose sums dK
    and dV over the group in float32."""
    b, s, kv, head_dim = x.shape
    if head_dim % 128 == 0:
        return jnp.repeat(x, times, axis=2)
    lane = jnp.arange(kv * times * head_dim)
    source = lane // (head_dim * times) * head_dim + lane % head_dim
    pick = (jnp.arange(kv * head_dim)[:, None] == source).astype(x.dtype)
    return jnp.einsum(
        "bsw,wv->bsv", x.reshape(b, s, kv * head_dim), pick,
        precision=jax.lax.Precision.HIGHEST).reshape(
            b, s, kv * times, head_dim)


def _head_init(key, shape, dtype=_f32):
    """A pattern's head matrix, its own or the embedding it is tied to:
    half the other matrices' deviation, so that unit-RMS rows through it
    give logits well under 1 and a first loss near ln(vocabulary)."""
    return 0.5 * INIT_STD * jax.random.normal(key, shape, dtype)


def _out_init(cfg):
    """Initialiser of a layer's output projection: under a layer pattern
    the residual writes are scaled down by sqrt(layers) (the source's
    ``rescale_prenorm_residual``); None keeps the linear layer's default."""
    if cfg.layer_pattern is None:
        return None
    std = INIT_STD / cfg.num_layers ** 0.5
    return lambda key, shape, dtype=_f32: std * jax.random.normal(
        key, shape, dtype)


class ParallelAttention:
    """Causal self-attention: TP-sharded QKV/proj, fused RoPE + softmax
    (apex ``transformer`` attention with FusedScaleMaskSoftmax.causal)."""

    def __init__(self, cfg: GPTConfig):
        self.cfg = cfg
        q_width = cfg.num_attention_heads * cfg.head_dim
        self.qkv = tp.ColumnParallelLinear(
            cfg.hidden_size, q_width + 2 * cfg.num_kv_heads * cfg.head_dim,
            bias=cfg.bias, gather_output=False,
            world_size=cfg.tensor_parallel_size, axis_name=cfg.axis_name,
            sequence_parallel_enabled=cfg.sequence_parallel,
            seq_dim=1, overlap_chunks=cfg.overlap_chunks,
            param_dtype=cfg.param_dtype)
        self.proj = tp.RowParallelLinear(
            q_width, cfg.hidden_size, bias=cfg.bias, input_is_parallel=True,
            init_method=_out_init(cfg),
            world_size=cfg.tensor_parallel_size, axis_name=cfg.axis_name,
            sequence_parallel_enabled=cfg.sequence_parallel,
            seq_dim=1, overlap_chunks=cfg.overlap_chunks,
            param_dtype=cfg.param_dtype)

    def init_params(self, key):
        k1, k2 = jax.random.split(key)
        params = {"qkv": self.qkv.init_params(k1),
                  "proj": self.proj.init_params(k2)}
        if self.cfg.qk_norm:
            # one weight of head_dim for every query head, one for every key
            # head; float32 under amp, as every norm
            for name in ("q_norm", "k_norm"):
                params[name] = {"weight": jnp.ones((self.cfg.head_dim,), _f32)}
        return params

    def _qkv(self, params, x, rope_cos=None, rope_sin=None):
        """Project ``x`` and split into ``(q, k, v)``, each
        ``(b, s, local_heads, head_dim)``.  Grouped heads also get their
        QK-norm and, given the tables, their rotary positions here, on the
        rows; the plain block's callers rotate what they get."""
        b = x.shape[0]
        cfg = self.cfg
        if cfg.num_kv_heads != cfg.num_attention_heads:
            q, k, v = self._rows(params, x, rope_cos, rope_sin)
            s = q.shape[1]
            return (q.reshape(b, s, -1, cfg.head_dim),
                    k.reshape(b, s, -1, cfg.head_dim),
                    v.reshape(b, s, -1, cfg.head_dim))
        qkv, _ = self.qkv(params["qkv"], x)      # (b, s, 3h/t)
        s = qkv.shape[1]
        nh = qkv.shape[-1] // (3 * self.cfg.head_dim)
        qkv = qkv.reshape(b, s, nh, 3 * self.cfg.head_dim)
        return jnp.split(qkv, 3, axis=-1)

    def _rows(self, params, x, rope_cos=None, rope_sin=None):
        """Grouped heads' ``(q, k, v)`` as rows ``(b, s, heads * head_dim)``
        and ``(b, s, kv_heads * head_dim)``: ``[q | k | v]`` side by side,
        k and v of num_kv_heads.  QK-norm and the rotary code work on the
        rows, all heads' lanes side by side: XLA lays a (b, s, heads, 64)
        array out with the sequence in the lanes and pays a transposing
        copy on each side of it."""
        cfg = self.cfg
        qkv, _ = self.qkv(params["qkv"], x)
        q, k, v = jnp.split(qkv, [
            cfg.num_attention_heads * cfg.head_dim,
            (cfg.num_attention_heads + cfg.num_kv_heads) * cfg.head_dim],
            axis=-1)
        if cfg.qk_norm:
            q = _rows_rms_norm(q, params["q_norm"]["weight"])
            k = _rows_rms_norm(k, params["k_norm"]["weight"])
        if rope_cos is not None:
            q = _rows_rotary(q, rope_cos, rope_sin)
            k = _rows_rotary(k, rope_cos, rope_sin)
        return q, k, v

    def train_rows(self, params, x, rope_cos, rope_sin, window):
        """The training forward of grouped heads beside window layers:
        rows in and out of :func:`grouped_flash`, every row over its last
        ``window`` positions (None: all of them), no KV head broadcast."""
        q, k, v = self._rows(params, x, rope_cos, rope_sin)
        out, _ = self.proj(params["proj"], grouped_flash(
            q, k, v, self.cfg.num_attention_heads, window))
        return out

    def __call__(self, params, x, rope_cos=None, rope_sin=None,
                 dropout_seed=None):
        cfg = self.cfg
        b = x.shape[0]
        grouped = cfg.num_kv_heads != cfg.num_attention_heads
        # (b, s, nh, hd); grouped heads come back with their positions
        q, k, v = self._qkv(params, x, rope_cos, rope_sin)
        s = q.shape[1]
        nh = q.shape[2]
        if rope_cos is not None and not grouped:
            # fused rope expects (seq, batch, heads, dim)
            q = fused_apply_rotary_pos_emb_cached(
                q.transpose(1, 0, 2, 3), rope_cos, rope_sin
            ).transpose(1, 0, 2, 3)
            k = fused_apply_rotary_pos_emb_cached(
                k.transpose(1, 0, 2, 3), rope_cos, rope_sin
            ).transpose(1, 0, 2, 3)
        if k.shape[2] != nh:
            # grouped attention: each KV head is broadcast to the query
            # heads it serves before the kernel, whose index maps stay as
            # they are; autodiff sums dK and dV over the group
            k = _broadcast_heads(k, nh // k.shape[2])
            v = _broadcast_heads(v, nh // v.shape[2])
        if cfg.context_axis is not None:
            # context parallelism: s here is the LOCAL shard; attention
            # runs over the global sequence (beyond-reference long-context)
            from apex_tpu.transformer.context_parallel import (
                ring_attention, ulysses_attention)
            attn = (ring_attention if cfg.context_mechanism == "ring"
                    else ulysses_attention)
            ctx = attn(q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
                       v.transpose(0, 2, 1, 3), cfg.context_axis,
                       causal=True).transpose(0, 2, 1, 3)
        else:
            # blockwise flash attention: O(s) memory, no materialized
            # (b*h, s, s) scores (the round-2 HBM ceiling)
            # train-time probability dropout stays on the fused O(s)
            # path (counter-hash mask, ops/flash_attention.py); no seed
            # (eval) means no dropout
            rate = cfg.attention_dropout if dropout_seed is not None \
                else 0.0
            seed = dropout_seed
            if seed is not None and cfg.axis_name is not None:
                # the counter hash keys on the LOCAL (batch, head) index,
                # so without an offset head j on every TP rank (different
                # global heads) would draw bit-identical masks; stride the
                # seed by rank like Megatron's per-TP-rank dropout RNG
                seed = seed + (jax.lax.axis_index(cfg.axis_name)
                               * _SEED_TP_RANK_STRIDE)
            ctx = flash_attention_bshd(q, k, v, causal=True, dropout=rate,
                                       dropout_seed=seed)
        ctx = ctx.reshape(b, s, nh * cfg.head_dim)
        out, _ = self.proj(params["proj"], ctx)
        return out

    def prefill(self, params, x, rope_cos=None, rope_sin=None):
        """Full-sequence causal attention that also returns the post-RoPE
        K/V in cache layout ``(b, s, local_heads, head_dim)`` — exactly
        what the decode path reads back, so prefill+decode reproduces the
        full forward token-for-token."""
        cfg = self.cfg
        b = x.shape[0]
        q, k, v = self._qkv(params, x)           # (b, s, nh, hd)
        s = q.shape[1]
        nh = q.shape[2]
        if rope_cos is not None:
            q = fused_apply_rotary_pos_emb_cached(
                q.transpose(1, 0, 2, 3), rope_cos, rope_sin
            ).transpose(1, 0, 2, 3)
            k = fused_apply_rotary_pos_emb_cached(
                k.transpose(1, 0, 2, 3), rope_cos, rope_sin
            ).transpose(1, 0, 2, 3)
        ctx = flash_attention_bshd(q, k, v, causal=True)
        ctx = ctx.reshape(b, s, nh * cfg.head_dim)
        out, _ = self.proj(params["proj"], ctx)
        return out, (k, v)

    def decode(self, params, x, cache, layer_index, positions):
        """One-token decode step against the KV cache.

        ``x``: ``(b, 1, hidden)`` — the incoming token's hidden state per
        cache slot; ``cache``: the full ring
        ``(slots, layers, 2, max_seq, local_heads, head_dim)``;
        ``positions``: ``(b,)`` absolute position of the incoming token
        (== valid cache entries before this step).  Writes the new K/V at
        ``positions`` (cast to the cache dtype), then attends over
        ``positions + 1`` entries.  Returns ``(out (b, 1, hidden), cache)``.
        """
        cfg = self.cfg
        b = x.shape[0]
        q, k, v = self._qkv(params, x)           # (b, 1, nh, hd)
        q, k, v = q[:, 0], k[:, 0], v[:, 0]      # (b, nh, hd)
        if cfg.rotary:
            # full-cache-depth tables; constant-folded under jit
            f = rope_freqs(cache.shape[3], cfg.head_dim, cfg.rope_base)
            q = fused_apply_rotary_pos_emb_at_positions(
                q, jnp.cos(f), jnp.sin(f), positions)
            k = fused_apply_rotary_pos_emb_at_positions(
                k, jnp.cos(f), jnp.sin(f), positions)
        rows = jnp.arange(b)
        with jax.named_scope("attention.kv_write"):
            cache = cache.at[rows, layer_index, 0, positions].set(
                k.astype(cache.dtype))
            cache = cache.at[rows, layer_index, 1, positions].set(
                v.astype(cache.dtype))
        ctx = flash_attention_decode(q, cache[:, layer_index, 0],
                                     cache[:, layer_index, 1],
                                     positions + 1)
        out, _ = self.proj(params["proj"],
                           ctx.reshape(b, 1, q.shape[1] * cfg.head_dim))
        return out, cache

    def decode_paged(self, params, x, pool, layer_index, block_tables,
                     positions):
        """One-token decode against a paged block pool — op-for-op the
        contiguous :meth:`decode` with the cache read/write indirected
        through ``block_tables`` (``(b, max_blocks)``; ``pool``:
        ``(num_blocks, layers, 2, block_size, kv_heads * head_dim)``).
        The token's K and V rows are written where they lie and the
        kernel reads the whole pool in place: no slice of the layer.
        RoPE tables are built at the pool's logical depth
        ``max_blocks * block_size``, whose rows are bitwise independent
        of the total length — paged and contiguous rows match exactly.
        """
        cfg = self.cfg
        b = x.shape[0]
        bs = pool.shape[3]
        q, k, v = self._qkv(params, x)           # (b, 1, nh, hd)
        q, k, v = q[:, 0], k[:, 0], v[:, 0]      # (b, nh, hd)
        if cfg.rotary:
            f = rope_freqs(block_tables.shape[1] * bs, cfg.head_dim,
                           cfg.rope_base)
            q = fused_apply_rotary_pos_emb_at_positions(
                q, jnp.cos(f), jnp.sin(f), positions)
            k = fused_apply_rotary_pos_emb_at_positions(
                k, jnp.cos(f), jnp.sin(f), positions)
        rows = jnp.arange(b)
        bids = block_tables[rows, positions // bs]
        offs = positions % bs
        with jax.named_scope("attention.kv_write"):
            pool = scatter_paged_kv(pool, layer_index, 0, bids, offs, k)
            pool = scatter_paged_kv(pool, layer_index, 1, bids, offs, v)
        ctx = flash_attention_decode_paged(
            q, pool, layer_index, block_tables, positions + 1)
        out, _ = self.proj(params["proj"],
                           ctx.reshape(b, 1, q.shape[1] * cfg.head_dim))
        return out, pool

    def decode_chunk(self, params, x, pool, layer_index, block_tables,
                     positions, write_blocks, write_offsets):
        """Multi-token decode against the pool (chunked prefill /
        speculative verify): ``x`` is ``(b, chunk, hidden)``,
        ``positions`` ``(b, chunk)`` absolute, and
        ``write_blocks``/``write_offsets`` ``(b, chunk)`` are the
        host-precomputed pool coordinates for each token's K/V (pad rows
        point at garbage block 0).  Attends causally over the whole
        cached context up to each query's position."""
        cfg = self.cfg
        b, c = x.shape[:2]
        q, k, v = self._qkv(params, x)           # (b, c, nh, hd)
        nh = q.shape[2]
        if cfg.rotary:
            f = rope_freqs(block_tables.shape[1] * pool.shape[3],
                           cfg.head_dim, cfg.rope_base)
            cos, sin = jnp.cos(f), jnp.sin(f)
            flat = positions.reshape(-1)
            q = fused_apply_rotary_pos_emb_at_positions(
                q.reshape(b * c, nh, cfg.head_dim), cos, sin, flat
            ).reshape(b, c, nh, cfg.head_dim)
            k = fused_apply_rotary_pos_emb_at_positions(
                k.reshape(b * c, nh, cfg.head_dim), cos, sin, flat
            ).reshape(b, c, nh, cfg.head_dim)
        with jax.named_scope("attention.kv_write"):
            pool = scatter_paged_kv(pool, layer_index, 0, write_blocks,
                                    write_offsets, k)
            pool = scatter_paged_kv(pool, layer_index, 1, write_blocks,
                                    write_offsets, v)
        ctx = flash_attention_chunk_paged(
            q.transpose(0, 2, 1, 3), pool, layer_index, block_tables,
            positions)
        ctx = ctx.transpose(0, 2, 1, 3).reshape(b, c, nh * cfg.head_dim)
        out, _ = self.proj(params["proj"], ctx)
        return out, pool

    def _grouped_rows(self, params, x, positions, rotates):
        """``(q, k, v)`` as the projection leaves them, rows of ``(b, s,
        heads * head_dim)`` and ``(b, s, kv_heads * head_dim)`` (a parallel
        block's grouped heads), q and k turned to ``positions`` on adjacent
        pairs of every head's lanes where the layer ``rotates``."""
        cfg = self.cfg
        qkv, _ = self.qkv(params["qkv"], x)
        q, k, v = jnp.split(qkv, [
            cfg.num_attention_heads * cfg.head_dim,
            (cfg.num_attention_heads + cfg.num_kv_heads) * cfg.head_dim],
            axis=-1)
        if rotates:
            q, k = (_rotary_rows(rows, positions, cfg.head_dim, cfg.rope_base)
                    for rows in (q, k))
        return q, k, v

    def prefill_rows(self, params, x, window, rotates):
        """A prefill of the grouped heads from position 0, every row causal
        over its last ``window`` positions (None: all of them), read in the
        rows by :func:`grouped_attention`; returns ``(out, (k, v))``, K and
        V ``(b, s, kv_heads, head_dim)`` as the cache keeps them."""
        cfg = self.cfg
        b, s = x.shape[:2]
        q, k, v = self._grouped_rows(params, x, jnp.arange(s), rotates)
        out, _ = self.proj(params["proj"], grouped_attention(
            q, k, v, cfg.num_attention_heads, window))
        return out, (k.reshape(b, s, cfg.num_kv_heads, cfg.head_dim),
                     v.reshape(b, s, cfg.num_kv_heads, cfg.head_dim))

    def decode_paged_rows(self, params, x, pool, layer_index, block_tables,
                          positions, window, rotates):
        """One token a row of the grouped heads against ``pool`` ``(blocks,
        layers, 2, block_size, kv_heads * head_dim)``: the token's K and V
        written where they lie, then every query head over its KV head's
        last ``window`` positions (None: all) through the table."""
        cfg = self.cfg
        b = x.shape[0]
        bs = pool.shape[3]
        q, k, v = self._grouped_rows(params, x, positions[:, None], rotates)
        bids = block_tables[jnp.arange(b), positions // bs]
        offs = positions % bs
        with jax.named_scope("attention.kv_write"):
            for part, rows in enumerate((k, v)):
                pool = scatter_paged_kv(
                    pool, layer_index, part, bids, offs,
                    rows.reshape(b, cfg.num_kv_heads, cfg.head_dim))
        ctx = flash_attention_decode_paged(
            q.reshape(b, cfg.num_attention_heads, cfg.head_dim), pool,
            layer_index, block_tables, positions + 1, window=window)
        out, _ = self.proj(params["proj"], ctx.reshape(
            b, 1, cfg.num_attention_heads * cfg.head_dim))
        return out, pool

    def _quant_insert(self, pool, scales, layer_index, bids, offs, k, v):
        """Write one token's K/V into an int8 pool: gather each row's
        target block, dequantize it, insert, and requantize the WHOLE
        block (safe and deterministic because quantized blocks are
        zeroed on allocation and shared blocks are never write targets
        — COW and the trie guarantee refcount 1 here).  Returns the
        updated ``(pool, scales)``."""
        rows = jnp.arange(bids.shape[0])
        blk = pool[bids, layer_index]            # (b, 2, bs, nh*hd) i8
        sc = scales[bids, layer_index]           # (b, 2, nh) f32
        # per-head scales: the lane-dense rows split into heads here
        deq = dequantize_kv_blocks(
            blk.reshape(*blk.shape[:-1], *k.shape[1:]), sc)
        deq = deq.at[rows, 0, offs].set(k.astype(jnp.float32))
        deq = deq.at[rows, 1, offs].set(v.astype(jnp.float32))
        q8, new_sc = quantize_kv_blocks(deq)
        pool = pool.at[bids, layer_index].set(q8.reshape(blk.shape))
        scales = scales.at[bids, layer_index].set(new_sc)
        return pool, scales

    def decode_paged_quant(self, params, x, pool, scales, layer_index,
                           block_tables, positions):
        """:meth:`decode_paged` against an int8 scale-per-block pool
        (``pool`` int8, ``scales`` ``(num_blocks, layers, 2, kv_heads)``
        f32).  The written block is dequantized, updated, and
        requantized; attention dequantizes per gathered block into the
        f32 score path.  Returns ``(out, pool, scales)``."""
        cfg = self.cfg
        b = x.shape[0]
        bs = pool.shape[3]
        q, k, v = self._qkv(params, x)           # (b, 1, nh, hd)
        q, k, v = q[:, 0], k[:, 0], v[:, 0]      # (b, nh, hd)
        if cfg.rotary:
            f = rope_freqs(block_tables.shape[1] * bs, cfg.head_dim,
                           cfg.rope_base)
            q = fused_apply_rotary_pos_emb_at_positions(
                q, jnp.cos(f), jnp.sin(f), positions)
            k = fused_apply_rotary_pos_emb_at_positions(
                k, jnp.cos(f), jnp.sin(f), positions)
        bids = block_tables[jnp.arange(b), positions // bs]
        with jax.named_scope("attention.kv_write"):
            pool, scales = self._quant_insert(pool, scales, layer_index,
                                              bids, positions % bs, k, v)
        ctx = flash_attention_decode_paged_quant(
            q, pool, scales, layer_index, block_tables, positions + 1)
        out, _ = self.proj(params["proj"],
                           ctx.reshape(b, 1, q.shape[1] * cfg.head_dim))
        return out, pool, scales

    def decode_chunk_quant(self, params, x, pool, scales, layer_index,
                           block_tables, positions, write_blocks,
                           write_offsets):
        """:meth:`decode_chunk` against an int8 pool.

        Tokens are inserted (and their block requantized) SEQUENTIALLY,
        each attending right after its own insertion — exactly the
        single-token :meth:`decode_paged_quant` block op applied
        ``chunk`` times under one shared QKV projection.  That
        serialization is what makes the quantized pool state (and every
        logits row) a fold over per-token ops, independent of how the
        scheduler sliced the prompt into chunks — the property the
        disaggregated handoff's bitwise guarantee rests on.  The cost is
        a ``fori_loop`` over the chunk instead of one wide attention;
        the quantized cache trades prefill throughput for capacity.
        """
        cfg = self.cfg
        b, c = x.shape[:2]
        q, k, v = self._qkv(params, x)           # (b, c, nh, hd)
        nh = q.shape[2]
        if cfg.rotary:
            f = rope_freqs(block_tables.shape[1] * pool.shape[3],
                           cfg.head_dim, cfg.rope_base)
            cos, sin = jnp.cos(f), jnp.sin(f)
            flat = positions.reshape(-1)
            q = fused_apply_rotary_pos_emb_at_positions(
                q.reshape(b * c, nh, cfg.head_dim), cos, sin, flat
            ).reshape(b, c, nh, cfg.head_dim)
            k = fused_apply_rotary_pos_emb_at_positions(
                k.reshape(b * c, nh, cfg.head_dim), cos, sin, flat
            ).reshape(b, c, nh, cfg.head_dim)

        def body(j, carry):
            pool, scales, ctx = carry
            bids = write_blocks[:, j]
            with jax.named_scope("attention.kv_write"):
                pool, scales = self._quant_insert(
                    pool, scales, layer_index, bids, write_offsets[:, j],
                    k[:, j], v[:, j])
            o = flash_attention_decode_paged_quant(
                q[:, j], pool, scales, layer_index, block_tables,
                positions[:, j] + 1)
            return pool, scales, ctx.at[:, j].set(o)

        ctx0 = jnp.zeros((b, c, nh, cfg.head_dim), q.dtype)
        pool, scales, ctx = jax.lax.fori_loop(0, c, body,
                                              (pool, scales, ctx0))
        out, _ = self.proj(params["proj"],
                           ctx.reshape(b, c, nh * cfg.head_dim))
        return out, pool, scales


def _rms_norm(x, weight, eps=_HEAD_NORM_EPS):
    """RMSNorm over the last axis in float32, back in ``x``'s dtype (the
    two norms inside latent attention; every norm keeps float32 weights)."""
    x32 = x.astype(_f32)
    rstd = jax.lax.rsqrt(jnp.mean(x32 * x32, -1, keepdims=True) + eps)
    return (x32 * rstd * weight.astype(_f32)).astype(x.dtype)


_INDEX_NORM_EPS = 1e-6      # the indexer's LayerNorm on its keys
_SELECT_ROWS = 128          # query rows a pass of a prefill's selection
_PREFILL_HEADS = 16         # heads a pass of a prefill's attention


class LatentAttention:
    """Multi-head latent attention (MLA) with a learned sparse-attention
    indexer (DeepSeek-V3.2's, as GLM-5.2 shares it between layers).

    For ``u`` the layer's normed input: ``c_q = RMSNorm(u W_qa)``,
    ``[q_nope_i | q_rope_i] = c_q W_qb``; ``[c_kv | k_r] = u W_kva``,
    ``c = RMSNorm(c_kv)``, ``k_rope = rope(k_r)``; expanded, ``[k_nope_i |
    v_i] = c W_kvb`` and head ``i`` attends with ``[q_nope_i |
    rope(q_rope_i)]`` to ``[k_nope_i | k_rope]`` at scale ``(nope + rope) **
    -0.5``, over the positions ``S_t`` its selection allows.  Rotary
    positions turn adjacent pairs (``rope_interleave``).  What is cached for
    a position is ``(c, k_rope)`` and nothing else.

    The selection.  A layer that owns an indexer (``indexer_types``
    ``"full"``) computes ``q_I = c_q W_Iq`` (heads of ``index_head_dim``),
    ``k_I = LayerNorm(u W_Ik)``, both with rotary positions on their first
    ``qk_rope_head_dim`` lanes, ``w = u W_Iw / sqrt(heads * index_head_dim)``
    and ``I[t, s] = sum_j w[t, j] relu(q_I[t, j] . k_I[s])``; ``S_t`` is the
    ``index_topk`` positions ``s <= t`` of largest ``I[t, s]``, the lower
    position winning a tie, and all of them while there are no more.  It
    caches ``k_I`` too.  A ``"shared"`` layer has no indexer parameters and
    attends over the ``S_t`` handed to it.  One code for every length:
    there is no dense path beside the sparse one.

    Two forms of the same numbers.  :meth:`prefill` (and ``__call__``)
    expands ``c`` into per-head keys and values and attends over all keys
    with ``S_t`` as a mask.  :meth:`decode_paged` absorbs ``W_uk`` into the
    query and applies ``W_uv`` after the sum, scores every cached ``k_I`` of
    the row, and gathers the ``index_topk`` selected records and no other
    (:func:`apex_tpu.ops.latent_attention.sparse_decode_attention`).

    Layout of the up-projections' output features (the same numbers, an
    order of ours): ``W_qb`` head by head ``[q_nope_i | q_rope_i]``, a whole
    number of 128-lane tiles a head; ``W_kvb`` all heads' ``k_nope`` then
    all heads' ``v``, whose rows ``W_uk`` and ``W_uv`` are views of."""

    def __init__(self, cfg: GPTConfig, indexer: bool):
        self.cfg = cfg
        self.indexer = indexer
        h = cfg.num_attention_heads
        self.nope, self.rope, self.vd = (cfg.qk_nope_head_dim,
                                         cfg.qk_rope_head_dim, cfg.v_head_dim)
        self.scale = float((self.nope + self.rope) ** -0.5)
        self.width = latent_record_width(cfg.kv_lora_rank, self.rope)

        def linear(n_in, n_out):
            return tp.ColumnParallelLinear(
                n_in, n_out, bias=False, gather_output=False, world_size=1,
                axis_name=None, param_dtype=cfg.param_dtype)

        self.linears = {
            "q_a": linear(cfg.hidden_size, cfg.q_lora_rank),
            "q_b": linear(cfg.q_lora_rank, h * (self.nope + self.rope)),
            "kv_a": linear(cfg.hidden_size, cfg.kv_lora_rank + self.rope),
            "kv_b": linear(cfg.kv_lora_rank, h * (self.nope + self.vd)),
            "proj": tp.RowParallelLinear(
                h * self.vd, cfg.hidden_size, bias=False,
                input_is_parallel=True, init_method=_out_init(cfg),
                world_size=1, axis_name=None, param_dtype=cfg.param_dtype)}
        if indexer:
            ih, idim = cfg.index_n_heads, cfg.index_head_dim
            self.linears.update(
                index_q=linear(cfg.q_lora_rank, ih * idim),
                index_k=linear(cfg.hidden_size, idim),
                index_w=linear(cfg.hidden_size, ih))

    def init_params(self, key):
        cfg = self.cfg
        keys = jax.random.split(key, len(self.linears))
        params = {name: lin.init_params(k)
                  for (name, lin), k in zip(self.linears.items(), keys)}
        params["q_norm"] = {"weight": jnp.ones((cfg.q_lora_rank,), _f32)}
        params["kv_norm"] = {"weight": jnp.ones((cfg.kv_lora_rank,), _f32)}
        if self.indexer:
            params["index_k_norm"] = {
                "weight": jnp.ones((cfg.index_head_dim,), _f32),
                "bias": jnp.zeros((cfg.index_head_dim,), _f32)}
        return params

    def _linear(self, params, name, x):
        return self.linears[name](params[name], x)[0]

    def _query_latent(self, params, x):
        """``c_q = RMSNorm(u W_qa)`` ``(b, s, q_lora_rank)``."""
        with jax.named_scope("mla.q"):
            return _rms_norm(self._linear(params, "q_a", x),
                             params["q_norm"]["weight"])

    def _queries(self, c_q, w_qb, positions):
        """``(b, s, heads * (nope + rope))``: the heads whose rows of
        ``W_qb`` are ``w_qb``, the rotary part of each turned to
        ``positions``."""
        with jax.named_scope("mla.q"):
            return rotary_pairs(c_q @ w_qb.astype(c_q.dtype).T, positions,
                                self.rope, self.cfg.rope_base,
                                head_dim=self.nope + self.rope)

    def _latent(self, params, x, positions):
        """``(c (b, s, kv_lora_rank), k_rope (b, s, rope))``: the normed
        latent and the one rotary key, turned."""
        with jax.named_scope("mla.kv"):
            kv = self._linear(params, "kv_a", x)
            r = self.cfg.kv_lora_rank
            return (_rms_norm(kv[..., :r], params["kv_norm"]["weight"]),
                    rotary_pairs(kv[..., r:], positions, self.rope,
                                 self.cfg.rope_base))

    def _index(self, params, h32, positions):
        """The indexer's ``(c_q (b, s, q_lora_rank), q_I (b, s, heads, d), w
        (b, s, heads), k_I (b, s, d))``, all float32 and every product at
        HIGHEST precision, from the layer's normed input in float32;
        ``k_I`` is what a position leaves in the cache, as it is.

        Why not bf16: a selection is a discrete choice no near tie excuses,
        attention's output is a sum of ``index_topk`` nearly equal terms of
        random sign, and swapping ``k`` of them moves it by ``sqrt(2 k /
        index_topk)``: the 5 of 2 048 positions a row that bf16 index
        products changed (my chip run, PR 35) moved the first layer's
        output by 7 %, where the embedding is no larger than attention's
        output, and every first-step row past 2 048 read 0.026-0.039 of the
        logits' range off the reference against 0.01 before it."""
        cfg = self.cfg
        b, s = h32.shape[:2]
        ih, idim = cfg.index_n_heads, cfg.index_head_dim

        def exact(x, name):
            return jnp.einsum("bsi,oi->bso", x,
                              params[name]["weight"].astype(_f32),
                              precision=jax.lax.Precision.HIGHEST)

        with jax.named_scope("indexer.score"):
            c_q = _rms_norm(exact(h32, "q_a"), params["q_norm"]["weight"])
            q_i = rotary_pairs(exact(c_q, "index_q"), positions, self.rope,
                               cfg.rope_base, head_dim=idim, first=True)
            k = exact(h32, "index_k")
            k = k - jnp.mean(k, -1, keepdims=True)
            k = k * jax.lax.rsqrt(jnp.mean(k * k, -1, keepdims=True)
                                  + _INDEX_NORM_EPS)
            k = k * params["index_k_norm"]["weight"] \
                + params["index_k_norm"]["bias"]
            k_i = rotary_pairs(k, positions, self.rope, cfg.rope_base,
                               first=True)
            w = exact(h32, "index_w") * float((ih * idim) ** -0.5)
        return c_q, q_i.reshape(b, s, ih, idim), w, k_i

    def _selection_mask(self, q_i, w, k_i):
        """``(b, s, s)`` bool, ``S_t`` of every row ``t`` of a prefill:
        :func:`topk_mask` of the index scores over the segments of
        :func:`index_segments`, ``_SELECT_ROWS`` queries at a time against
        the keys up to the segment's end (no later key is allowed to any
        of its rows); the rows before ``index_topk``, which select every
        key they may, are the causal mask and score nothing."""
        b, s = q_i.shape[:2]
        k = self.cfg.index_topk
        first = jnp.arange(min(k, s))[:, None] >= jnp.arange(s)
        parts = [jnp.broadcast_to(first, (b, *first.shape))]
        for r0, r1 in index_segments(s, k):
            rows = min(_SELECT_ROWS, r1 - r0)
            if (r1 - r0) % rows:
                raise ValueError(f"rows {r0}-{r1} of a prefill are not a "
                                 f"multiple of {rows}")
            keys = jnp.arange(r1)
            # the segment's own rows, sliced before the loop: sliced from
            # the whole prefill inside it, the TPU compiler fused the slice
            # into the product, and 3 rows of 16 384 took another 2 048th
            # key than the full-width selection (scores 1 ulp apart)
            q_seg, w_seg, k_pre = q_i[:, r0:r1], w[:, r0:r1], k_i[:, :r1]

            def block(t0):
                with jax.named_scope("indexer.score"):
                    scores = index_scores(
                        jax.lax.dynamic_slice_in_dim(q_seg, t0 - r0, rows, 1),
                        jax.lax.dynamic_slice_in_dim(w_seg, t0 - r0, rows, 1),
                        k_pre)
                with jax.named_scope("indexer.topk"):
                    causal = (t0 + jnp.arange(rows))[:, None] >= keys
                    return topk_mask(scores, causal, k)

            # (n, b, rows, r1), widened with False to s keys
            seg = jax.lax.map(block, jnp.arange(r0, r1, rows))
            parts.append(jnp.pad(seg.transpose(1, 0, 2, 3).reshape(b, -1, r1),
                                 ((0, 0), (0, 0), (0, s - r1))))
        return jnp.concatenate(parts, 1)

    def prefill(self, params, x, selection=None, h32=None):
        """The expanded form over a whole sequence from position 0 (``h32``:
        the same normed input in float32, which a layer that owns an indexer
        computes its selection from); returns
        ``(out, records, selection)``: ``records`` the parts a position
        leaves in the cache, ``(latent record (b, s, 1, width),)`` and for
        a layer with an indexer its ``(b, s, 1, index_head_dim)`` keys as
        well; ``selection`` the ``(b, s, s)`` mask this layer attended
        under, its own or the one it was handed."""
        cfg = self.cfg
        b, s = x.shape[:2]
        h, r = cfg.num_attention_heads, cfg.kv_lora_rank
        positions = jnp.arange(s)
        c, k_rope = self._latent(params, x, positions)
        records = (latent_record(c, k_rope)[:, :, None, :],)
        if self.indexer:
            c_q, q_i, w, k_i = self._index(params, h32, positions)
            c_q = c_q.astype(x.dtype)       # the query path's, rounded once
            selection = self._selection_mask(q_i, w, k_i)
            records += (k_i[:, :, None, :],)
        else:
            c_q = self._query_latent(params, x)
        # the heads go through the up-projections and the attention
        # _PREFILL_HEADS at a time, one group after another (lax.map over
        # the groups' rows of W_qb and W_kvb): the same products a head,
        # and the expanded q, k, v and scores alive at once are a group's
        # (all 64 heads' were 4.8 GB of temporaries at 16 384 positions)
        hb = max(d for d in range(1, min(h, _PREFILL_HEADS) + 1)
                 if h % d == 0)
        w_kvb = params["kv_b"]["weight"]
        groups = (params["q_b"]["weight"].reshape(h // hb, -1,
                                                  cfg.q_lora_rank),
                  w_kvb[:h * self.nope].reshape(h // hb, -1, r),
                  w_kvb[h * self.nope:].reshape(h // hb, -1, r))

        def heads_major(y):
            return y.reshape(b, s, hb, -1).transpose(0, 2, 1, 3)

        def group(ws):
            w_q, w_k, w_v = ws
            q = heads_major(self._queries(c_q, w_q, positions))
            with jax.named_scope("mla.kv"):
                k = jnp.concatenate([
                    heads_major(c @ w_k.astype(c.dtype).T),
                    jnp.broadcast_to(k_rope[:, None],
                                     (b, hb, s, self.rope))], -1)
                v = heads_major(c @ w_v.astype(c.dtype).T)
            with jax.named_scope("attention.sparse"):
                return masked_attention(q, k, v, selection, self.scale)

        ctx = jax.lax.map(group, groups)            # (h / hb, b, s, hb * vd)
        ctx = ctx.transpose(1, 2, 0, 3).reshape(b, s, h * self.vd)
        return self._linear(params, "proj", ctx), records, selection

    def decode_paged(self, params, x, pools, layers, block_tables,
                     positions, selection=None, h32=None):
        """The absorbed form, one token a row against the paged pools
        ``(latent records, index keys)``; ``layers`` is this layer's index
        in each.  The token's record (and index key) is written where it
        lies; a layer with an indexer scores every cached key of the row
        and selects, and every head then reads the selected records alone.
        Returns ``(out, pools, selection)`` with ``selection`` ``(idx,
        valid)``, each ``(b, index_topk)``."""
        cfg = self.cfg
        b = x.shape[0]
        h, r = cfg.num_attention_heads, cfg.kv_lora_rank
        latents, keys = pools
        bs = latents.shape[3]
        blocks = block_tables[jnp.arange(b), positions // bs]
        at = positions[:, None]
        if self.indexer:
            c_q, q_i, w, k_i = self._index(params, h32, at)
            c_q = c_q.astype(x.dtype)
        else:
            c_q = self._query_latent(params, x)
        q = self._queries(c_q, params["q_b"]["weight"], at)
        c, k_rope = self._latent(params, x, at)
        with jax.named_scope("mla.kv"):
            latents = scatter_record(latents, layers[0], blocks,
                                     positions % bs,
                                     latent_record(c[:, 0], k_rope[:, 0]))
        if self.indexer:
            with jax.named_scope("indexer.score"):
                keys = scatter_record(keys, layers[1], blocks,
                                      positions % bs, k_i[:, 0])
                scores = index_scores(
                    q_i, w, gather_index_keys(keys, layers[1],
                                              block_tables))[:, 0]
            with jax.named_scope("indexer.topk"):
                selection = topk_positions(scores, positions + 1,
                                           cfg.index_topk)
        w_kvb = params["kv_b"]["weight"].astype(x.dtype)
        q = q.reshape(b, h, self.nope + self.rope)
        with jax.named_scope("mla.absorb"):
            w_uk = w_kvb[:h * self.nope].reshape(h, self.nope, r)
            q_lat = jnp.einsum("bhd,hdc->bhc", q[..., :self.nope], w_uk)
            q_abs = jnp.concatenate([
                q_lat, q[..., self.nope:],
                jnp.zeros((b, h, self.width - r - self.rope), x.dtype)], -1)
        with jax.named_scope("attention.sparse"):
            o_lat = sparse_decode_attention(
                q_abs, latents, layers[0], block_tables, *selection,
                scale=self.scale, v_width=r)
        with jax.named_scope("mla.absorb"):
            w_uv = w_kvb[h * self.nope:].reshape(h, self.vd, r)
            ctx = jnp.einsum("bhc,hdc->bhd", o_lat, w_uv)
        out = self._linear(params, "proj", ctx.reshape(b, 1, h * self.vd))
        return out, (latents, keys), selection


class ParallelMLP:
    """Column→GELU→Row block (apex ParallelMLP).  Gated (``swiglu``,
    ``reglu``): ``fc1`` is ``[gate | up]`` side by side, one product of
    twice the width, and ``fc2`` takes ``silu(gate) * up`` or ``relu(gate)
    * up``."""

    def __init__(self, cfg: GPTConfig, ffn_hidden_size=None):
        self.cfg = cfg
        width = ffn_hidden_size or cfg.ffn_hidden_size
        gated = cfg.gated
        self.fc1 = tp.ColumnParallelLinear(
            cfg.hidden_size, 2 * width if gated else width, bias=cfg.bias,
            gather_output=False,
            world_size=cfg.tensor_parallel_size, axis_name=cfg.axis_name,
            sequence_parallel_enabled=cfg.sequence_parallel,
            seq_dim=1, overlap_chunks=cfg.overlap_chunks,
            param_dtype=cfg.param_dtype)
        self.fc2 = tp.RowParallelLinear(
            width, cfg.hidden_size, bias=cfg.bias, input_is_parallel=True,
            init_method=_out_init(cfg),
            world_size=cfg.tensor_parallel_size, axis_name=cfg.axis_name,
            sequence_parallel_enabled=cfg.sequence_parallel,
            seq_dim=1, overlap_chunks=cfg.overlap_chunks,
            param_dtype=cfg.param_dtype)

    def init_params(self, key):
        k1, k2 = jax.random.split(key)
        return {"fc1": self.fc1.init_params(k1),
                "fc2": self.fc2.init_params(k2)}

    def __call__(self, params, x):
        cfg = self.cfg
        if cfg.fused_ffn:
            # one Pallas op for GEMM+bias+GELU+GEMM, wrapped in the same
            # TP/SP edge collectives the unfused pair uses (bias2 after
            # the reduce) — bitwise vs unfused off-TPU at overlap 0
            return fused_ffn_tp(
                x, params["fc1"]["weight"], params["fc1"]["bias"],
                params["fc2"]["weight"], params["fc2"]["bias"],
                tensor_parallel_size=cfg.tensor_parallel_size,
                axis_name=cfg.axis_name,
                sequence_parallel=cfg.sequence_parallel, seq_dim=1)
        h, _ = self.fc1(params["fc1"], x)
        if cfg.ffn_activation == "relu2":
            h = jnp.square(jnp.maximum(h, 0))
        elif cfg.gated:
            gate, up = jnp.split(h, 2, axis=-1)
            h = (jax.nn.silu(gate) if cfg.ffn_activation == "swiglu"
                 else jnp.maximum(gate, 0)) * up
        else:
            h = jax.nn.gelu(h, approximate=True)
        y, _ = self.fc2(params["fc2"], h)
        return y


class MoEFFN:
    """Switch/GShard FFN in the layer slot (beyond-reference; Megatron's
    MoE lives outside apex).  Flattens ``(b, s, h)`` to tokens for
    :class:`apex_tpu.transformer.expert_parallel.MoEMLP` and returns
    ``(y, aux_loss)``."""

    def __init__(self, cfg: GPTConfig):
        from apex_tpu.transformer.expert_parallel import MoEConfig, MoEMLP
        sorted_ = cfg.moe_router != "softmax"
        self.moe = MoEMLP(MoEConfig(
            hidden_size=cfg.hidden_size,
            ffn_hidden_size=cfg.ffn_hidden_size,
            n_experts=cfg.n_experts,
            top_k=cfg.moe_top_k,
            capacity_factor=cfg.moe_capacity_factor,
            expert_parallel_size=cfg.expert_parallel_size,
            axis_name=cfg.expert_axis,
            tensor_parallel_size=cfg.tensor_parallel_size,
            tensor_axis=cfg.axis_name,
            param_dtype=cfg.param_dtype,
            compute_dtype=cfg.dtype,
            **(dict(router=cfg.moe_router, routed_scale=cfg.moe_routed_scale,
                    held=cfg.moe_held, init_std=INIT_STD,
                    out_init_std=INIT_STD / cfg.num_layers ** 0.5,
                    router_bias=cfg.moe_router_bias,
                    activation=cfg.ffn_activation
                    if cfg.ffn_activation in ("relu2", "swiglu", "reglu")
                    else "relu") if sorted_ else {})))
        # the shared expert: every token, every rank alike; with several
        # shared experts side by side, one of their summed width
        self.shared = (ParallelMLP(cfg, cfg.moe_shared_ffn)
                       if cfg.moe_shared_ffn else None)
        self.average = cfg.moe_shared_combine == "average"

    def init_params(self, key):
        if self.shared is None:
            return self.moe.init_params(key)
        k1, k2 = jax.random.split(key)
        return {**self.moe.init_params(k1),
                "shared": self.shared.init_params(k2)}

    def route(self, params, x):
        """The sorted dispatch's routing of ``x`` ``(b, s, hidden)``: what
        :meth:`__call__` takes as ``routing``."""
        return self.moe.route(params, x.reshape(-1, x.shape[-1]))

    def __call__(self, params, x, routing=None):
        b, s, h = x.shape
        y, aux = self.moe(params, x.reshape(b * s, h), routing)
        y = y.reshape(b, s, h)
        if self.shared is not None:
            with jax.named_scope("moe.shared"):
                y = y + self.shared(params["shared"], x)
                if self.average:
                    y = y / 2           # the mean of the routed and shared sums
        return y, aux


class ParallelTransformerLayer:
    """Pre-LN transformer block (apex ParallelTransformerLayer); the FFN
    slot is dense (ParallelMLP) or MoE (``cfg.n_experts > 0``)."""

    def __init__(self, cfg: GPTConfig, mixer: Optional[str] = None,
                 indexer: Optional[str] = None,
                 attention: Optional[str] = None,
                 rotates: Optional[int] = None):
        self.cfg = cfg
        self.mixer = mixer
        if mixer == "P":
            # attention and experts side by side on one norm (``attention``:
            # this layer's entry of cfg.attention_types)
            self.is_moe = False
            self.norm = _norm(cfg)
            self.mix, self.experts = ParallelAttention(cfg), MoEFFN(cfg)
            self.scope = "attention"
            self.window = (cfg.sliding_window if attention == "sliding"
                           else None)
            # only window layers rotate (on ADJACENT pairs of a head's
            # lanes); a parallel block's full layers carry no position code
            self.rotates = cfg.rotary and self.window is not None
            return
        if mixer is not None:
            # one mixer a layer (``cfg.layer_pattern``): x + mixer(norm(x))
            self.is_moe = mixer == "E"
            self.norm = _norm(cfg)
            if mixer == "M":
                from apex_tpu.models.mamba2 import Mamba2Mixer
                self.mix, self.scope = Mamba2Mixer(cfg), "mamba"
            elif mixer == "E":
                # the readers the benchmark has bill an expert layer as mlp
                self.mix, self.scope = MoEFFN(cfg), "mlp"
            elif mixer == "C":
                from apex_tpu.models.short_conv import GatedShortConv
                self.mix, self.scope = GatedShortConv(cfg), "conv"
            elif mixer == "D":
                self.mix = ParallelMLP(cfg, cfg.dense_ffn_hidden_size)
                self.scope = "mlp"
            else:
                # ``indexer``: this "*" layer's entry of cfg.indexer_types;
                # ``attention`` of cfg.attention_types, ``rotates`` of
                # cfg.rotary_layers (None: as the rotary tables say)
                self.mix = (LatentAttention(cfg, indexer == "full")
                            if cfg.kv_lora_rank else ParallelAttention(cfg))
                self.scope = "attention"
                self.window = (cfg.sliding_window if attention == "sliding"
                               else None)
                self.rotates = rotates
            return
        self.is_moe = cfg.n_experts > 0
        self.input_layernorm = _norm(cfg)
        self.post_attention_layernorm = _norm(cfg)
        self.attention = ParallelAttention(cfg)
        self.mlp = MoEFFN(cfg) if self.is_moe else ParallelMLP(cfg)

    def init_params(self, key):
        if self.mixer == "P":
            k1, k2 = jax.random.split(key)
            return {"norm": self.norm.init_params(),
                    "attention": self.mix.init_params(k1),
                    "mlp": self.experts.init_params(k2)}
        if self.mixer is not None:
            return {"norm": self.norm.init_params(),
                    "mixer": self.mix.init_params(key)}
        k1, k2 = jax.random.split(key)
        return {"input_layernorm": self.input_layernorm.init_params(),
                "attention": self.attention.init_params(k1),
                "post_attention_layernorm":
                    self.post_attention_layernorm.init_params(),
                "mlp": self.mlp.init_params(k2)}

    def _sp_ln_params(self, params, name):
        """LayerNorms run on the SEQ-SHARDED stream under SP, so their
        per-device grads only cover local tokens; identity-fwd/psum-bwd
        restores the total (Megatron's allreduce of sequence-parallel-
        region layernorm grads)."""
        p = params[name]
        if self.cfg.sequence_parallel and self.cfg.axis_name is not None:
            from apex_tpu.transformer.tensor_parallel import mappings as M
            p = M.copy_to_tensor_model_parallel_region(
                p, self.cfg.axis_name)
        return p

    def __call__(self, params, x, rope_cos=None, rope_sin=None,
                 dropout_seed=None, routing=None):
        # named scopes land in HLO metadata -> visible in xprof traces
        # (the reference's nvtx range annotations, SURVEY §5)
        if self.mixer is not None:
            with jax.named_scope(self.scope):
                h = self.norm(params["norm"], x)
                if self.mixer == "*":
                    if self.rotates == 0:
                        rope_cos = rope_sin = None
                    if self.cfg.sliding_window:
                        # beside window layers: grouped rows through the
                        # banded kernel, the part named by kind
                        with jax.named_scope("attention.window" if self.window
                                             else "attention.full"):
                            return x + self.mix.train_rows(
                                params["mixer"], h, rope_cos, rope_sin,
                                self.window)
                    return x + self.mix(params["mixer"], h, rope_cos,
                                        rope_sin, dropout_seed)
                if self.mixer == "E":
                    # ``routing``: what the router made of the input of the
                    # "*" layer below (cfg.moe_route_before_attention)
                    y, load = self.mix(params["mixer"], h, routing)
                    return x + y, load
                return x + self.mix(params["mixer"], h)
        with jax.named_scope("attention"):
            h = self.input_layernorm(
                self._sp_ln_params(params, "input_layernorm"), x)
            x = x + self.attention(params["attention"], h, rope_cos,
                                   rope_sin, dropout_seed)
        with jax.named_scope("mlp"):
            h = self.post_attention_layernorm(
                self._sp_ln_params(params, "post_attention_layernorm"), x)
            if self.is_moe:
                y, aux = self.mlp(params["mlp"], h)
                return x + y, aux
            return x + self.mlp(params["mlp"], h)

    def _norm32(self, params, x):
        """The layer's norm of ``x`` in float32, unrounded, for a latent-
        attention layer's indexer; None where the layer owns none."""
        if not self.mix.indexer:
            return None
        return _rms_norm(x.astype(_f32), params["norm"]["weight"],
                         self.norm.eps)

    def _parallel(self, params, x, attend):
        """The parallel block on a cache path: ``x + attention(h) +
        experts(h)``, one residual sum, with ``h = norm(x)`` computed once
        (billed to ``attention``, whose part is scoped ``attention.window``
        or ``attention.full``); ``attend(h)`` returns attention's output and
        what it did to the cache."""
        with jax.named_scope("attention"):
            h = self.norm(params["norm"], x)
            with jax.named_scope("attention.window" if self.window
                                 else "attention.full"):
                y, cached = attend(h)
        with jax.named_scope("mlp"):
            z, _ = self.experts(params["mlp"], h)
        return x + y + z, cached

    def _cached_mixer(self, params, x, attend):
        """A one-mixer layer on a cache path: ``x + mixer(norm(x))`` with
        the attention mixer run by ``attend(h)``, which returns its output
        and what it did to the cache; a dense or expert layer keeps no
        state (the experts' load is a training concern)."""
        with jax.named_scope(self.scope):
            h = self.norm(params["norm"], x)
            if self.mixer == "*":
                y, *cached = attend(h)
                return x + y, *cached
            y = self.mix(params["mixer"], h)
            return x + (y[0] if self.mixer == "E" else y), None, None

    def prefill(self, params, x, rope_cos=None, rope_sin=None,
                selection=None):
        """Inference forward returning ``(x_out, (k, v))`` with this
        layer's post-RoPE cache entries (MoE aux is discarded —
        load-balancing loss is a training concern).  A one-mixer layer
        returns ``(x_out, records, selection)``: ``records`` a tuple with,
        for each of the pool's arrays this layer writes, its parts stacked
        ``(parts, b, s, kv_heads, head_dim)`` (K and V; or the latent
        record and, beside it, an indexer's keys), None for a layer that
        caches nothing; ``selection`` what a sparse-attention layer attended
        under, for the shared layers above it.  A parallel block's records
        are ``(full, window)`` with None in the place it does not write."""
        if self.mixer == "P":
            def attend(h):
                y, kv = self.mix.prefill_rows(params["attention"], h,
                                              self.window, self.rotates)
                return y, jnp.stack(kv)
            y, record = self._parallel(params, x, attend)
            return y, ((None, record) if self.window else (record, None)), \
                selection
        if self.mixer is not None:
            if self.cfg.kv_lora_rank:
                def attend(h):
                    y, records, sel = self.mix.prefill(
                        params["mixer"], h, selection, self._norm32(params, x))
                    return y, tuple(r[None] for r in records), sel
            else:
                def attend(h):
                    y, kv = self.mix.prefill(params["mixer"], h, rope_cos,
                                             rope_sin)
                    return y, (jnp.stack(kv),), None
            y, records, sel = self._cached_mixer(params, x, attend)
            return y, records, selection if sel is None else sel
        with jax.named_scope("attention"):
            h = self.input_layernorm(params["input_layernorm"], x)
            attn, kv = self.attention.prefill(params["attention"], h,
                                              rope_cos, rope_sin)
            x = x + attn
        with jax.named_scope("mlp"):
            h = self.post_attention_layernorm(
                params["post_attention_layernorm"], x)
            y = self.mlp(params["mlp"], h)
            if self.is_moe:
                y, _ = y
            return x + y, kv

    def decode(self, params, x, cache, layer_index, positions):
        """One-token decode through this layer; see
        :meth:`ParallelAttention.decode` for the cache contract."""
        with jax.named_scope("attention"):
            h = self.input_layernorm(params["input_layernorm"], x)
            attn, cache = self.attention.decode(
                params["attention"], h, cache, layer_index, positions)
            x = x + attn
        with jax.named_scope("mlp"):
            h = self.post_attention_layernorm(
                params["post_attention_layernorm"], x)
            y = self.mlp(params["mlp"], h)
            if self.is_moe:
                y, _ = y
            return x + y, cache

    def decode_paged(self, params, x, pool, layer_index, block_tables,
                     positions, selection=None):
        """Paged-pool analog of :meth:`decode` (same residual/LN/MLP
        tail — only the attention cache access is indirected).  A
        one-mixer layer returns ``(x_out, pool, selection)``: its
        ``layer_index`` counts the layers that cache (for latent attention
        a pair, its place among the latent records and among the indexers'
        keys), and a dense or expert layer hands the pool back as it
        came.  A parallel block is handed the pool and tables of its kind,
        window or full, and ``layer_index`` among that kind's layers."""
        if self.mixer == "P":
            def attend(h):
                return self.mix.decode_paged_rows(
                    params["attention"], h, pool, layer_index, block_tables,
                    positions, self.window, self.rotates)
            y, pool = self._parallel(params, x, attend)
            return y, pool, selection
        if self.mixer is not None:
            if self.cfg.kv_lora_rank:
                def attend(h):
                    return self.mix.decode_paged(
                        params["mixer"], h, pool, layer_index, block_tables,
                        positions, selection, self._norm32(params, x))
            else:
                def attend(h):
                    return *self.mix.decode_paged(
                        params["mixer"], h, pool, layer_index, block_tables,
                        positions), None
            y, cached, sel = self._cached_mixer(params, x, attend)
            return (y, pool if cached is None else cached,
                    selection if sel is None else sel)
        with jax.named_scope("attention"):
            h = self.input_layernorm(params["input_layernorm"], x)
            attn, pool = self.attention.decode_paged(
                params["attention"], h, pool, layer_index, block_tables,
                positions)
            x = x + attn
        with jax.named_scope("mlp"):
            h = self.post_attention_layernorm(
                params["post_attention_layernorm"], x)
            y = self.mlp(params["mlp"], h)
            if self.is_moe:
                y, _ = y
            return x + y, pool

    def decode_chunk(self, params, x, pool, layer_index, block_tables,
                     positions, write_blocks, write_offsets):
        """Chunked decode through this layer; see
        :meth:`ParallelAttention.decode_chunk`."""
        with jax.named_scope("attention"):
            h = self.input_layernorm(params["input_layernorm"], x)
            attn, pool = self.attention.decode_chunk(
                params["attention"], h, pool, layer_index, block_tables,
                positions, write_blocks, write_offsets)
            x = x + attn
        with jax.named_scope("mlp"):
            h = self.post_attention_layernorm(
                params["post_attention_layernorm"], x)
            y = self.mlp(params["mlp"], h)
            if self.is_moe:
                y, _ = y
            return x + y, pool

    def decode_paged_quant(self, params, x, pool, scales, layer_index,
                           block_tables, positions):
        """Int8-pool analog of :meth:`decode_paged`; see
        :meth:`ParallelAttention.decode_paged_quant`."""
        with jax.named_scope("attention"):
            h = self.input_layernorm(params["input_layernorm"], x)
            attn, pool, scales = self.attention.decode_paged_quant(
                params["attention"], h, pool, scales, layer_index,
                block_tables, positions)
            x = x + attn
        with jax.named_scope("mlp"):
            h = self.post_attention_layernorm(
                params["post_attention_layernorm"], x)
            y = self.mlp(params["mlp"], h)
            if self.is_moe:
                y, _ = y
            return x + y, pool, scales

    def decode_chunk_quant(self, params, x, pool, scales, layer_index,
                           block_tables, positions, write_blocks,
                           write_offsets):
        """Int8-pool analog of :meth:`decode_chunk`; see
        :meth:`ParallelAttention.decode_chunk_quant`."""
        with jax.named_scope("attention"):
            h = self.input_layernorm(params["input_layernorm"], x)
            attn, pool, scales = self.attention.decode_chunk_quant(
                params["attention"], h, pool, scales, layer_index,
                block_tables, positions, write_blocks, write_offsets)
            x = x + attn
        with jax.named_scope("mlp"):
            h = self.post_attention_layernorm(
                params["post_attention_layernorm"], x)
            y = self.mlp(params["mlp"], h)
            if self.is_moe:
                y, _ = y
            return x + y, pool, scales


# what a layer pattern's layers would need for each cache path they do
# not have (``GPTModel._check_decode_supported``)
_MISSING_CACHE_PATHS = {
    "decode_step": "a contiguous ring whose row is the model's record (the "
                   "ring is (slots, layers, 2, max_seq, heads, head_dim) and "
                   "its kernel reads per-head K and V)",
    "decode_chunk": "a multi-query (chunk) attention over the pool's "
                    "records, which chunked prefill and speculative "
                    "verification score several positions a row with (and, "
                    "for sparse attention, a selection a position of the "
                    "chunk)",
    "decode_step_paged_quant": "an int8 record: the pool's scales are one "
                               "a block, part and head of K and V",
    "decode_chunk_quant": "an int8 record and a chunk attention over it",
}


class GPTModel:
    """Full decoder LM: vocab-parallel embedding → N layers → final LN →
    tied vocab-parallel head → (optional) vocab-parallel xent loss."""

    def __init__(self, cfg: GPTConfig):
        self.cfg = cfg
        self.embedding = tp.VocabParallelEmbedding(
            cfg.vocab_size, cfg.hidden_size,
            init_method=_head_init if cfg.layer_pattern is not None
            and cfg.tie_head else None,
            world_size=cfg.tensor_parallel_size, axis_name=cfg.axis_name,
            param_dtype=cfg.param_dtype)
        if cfg.layer_pattern is None:
            self.layers = [ParallelTransformerLayer(cfg)
                           for _ in range(cfg.num_layers)]
        else:
            kinds = iter(cfg.indexer_types or ())
            attends = iter(cfg.attention_types)
            rotates = iter(cfg.rotary_layers or ())
            self.layers = [ParallelTransformerLayer(
                cfg, mixer, next(kinds, None) if mixer == "*" else None,
                next(attends) if mixer in "*P" else None,
                next(rotates, None) if mixer == "*" else None)
                for mixer in cfg.layer_pattern]
        self.final_layernorm = _norm(cfg)
        # the leaf that holds the output head's matrix
        self.head = "embedding" if cfg.tie_head else "lm_head"

    def init_params(self, key):
        keys = jax.random.split(key, self.cfg.num_layers + 2)
        params = {
            "embedding": self.embedding.init_params(keys[0]),
            "layers": [l.init_params(k)
                       for l, k in zip(self.layers, keys[1:-1])],
            "final_layernorm": self.final_layernorm.init_params(),
        }
        if self.cfg.learned_positions:
            params["position_embedding"] = 0.02 * jax.random.normal(
                keys[-1], (self.cfg.max_seq_len, self.cfg.hidden_size),
                self.cfg.param_dtype)
        if not self.cfg.tie_head:
            params["lm_head"] = {"weight": _head_init(
                keys[-1], (self.cfg.vocab_size, self.cfg.hidden_size),
                _f32).astype(self.cfg.param_dtype)}
        return params

    def rope_tables(self, seq_len):
        if not self.cfg.rotary:
            return None, None
        if self.cfg.kv_lora_rank or "P" in (self.cfg.layer_pattern or ""):
            return None, None       # such layers turn their own lanes
        f = rope_freqs(seq_len, self.cfg.head_dim, self.cfg.rope_base)
        return jnp.cos(f), jnp.sin(f)

    def _seq_offset(self, local_len):
        """Global position of this shard's first token (0 without CP)."""
        if self.cfg.context_axis is None:
            return 0
        return jax.lax.axis_index(self.cfg.context_axis) * local_len

    def embed(self, params, tokens):
        x = self.embedding(params["embedding"], tokens)
        if self.cfg.learned_positions:
            pe = jax.lax.dynamic_slice_in_dim(
                params["position_embedding"],
                self._seq_offset(tokens.shape[1]), tokens.shape[1])
            x = x + pe
        return x.astype(self.cfg.dtype)

    def backbone(self, params, x, seq_len=None, dropout_seed=None):
        local = seq_len or x.shape[1]
        if self.cfg.context_axis is not None:
            # rope positions are GLOBAL: build full tables, take the shard
            n_ctx = jax.lax.axis_size(self.cfg.context_axis)
            cos, sin = self.rope_tables(local * n_ctx)
            if cos is not None:
                off = self._seq_offset(local)
                cos = jax.lax.dynamic_slice_in_dim(cos, off, local)
                sin = jax.lax.dynamic_slice_in_dim(sin, off, local)
            return self._backbone_layers(params, x, cos, sin, dropout_seed)
        cos, sin = self.rope_tables(local)
        return self._backbone_layers(params, x, cos, sin, dropout_seed)

    def _backbone_layers(self, params, x, cos, sin, dropout_seed=None):
        """Returns ``(x, moe_aux_total)`` (aux is 0.0 for dense FFNs).

        ``dropout_seed`` (train-time attention dropout): layer ``i`` uses
        ``dropout_seed + i * _SEED_LAYER_STRIDE`` — the same per-layer
        stream walk the pipeline stage_fn reproduces by carrying a
        striding seed.  Advance the base seed by +1 per training step.
        """
        if "P" in (self.cfg.layer_pattern or ""):
            raise NotImplementedError(
                "the parallel block ('P') has cache paths only (prefill, "
                "decode_step_paged): the training kernels take a sliding "
                "window and K and V shared by a query group (grouped_flash), "
                "but the block's training forward is not written (one norm "
                "feeding attention and the experts, rotary on adjacent pairs "
                "of the rows, the shared experts averaged with the routed "
                "sum)")
        if self.cfg.kv_lora_rank:
            raise NotImplementedError(
                "latent attention has cache paths only (prefill, "
                "decode_step_paged): the training forward does not carry "
                "the indexer's selection from a full layer to the shared "
                "layers above it, and the indexer has no training loss")
        aux_total = jnp.zeros((), _f32)
        loads = []              # sorted dispatch: tokens per held expert
        routing = ()            # the next "E" layer's, routed ahead
        for li, (layer, lp) in enumerate(zip(self.layers,
                                             params["layers"])):
            seed = (None if dropout_seed is None
                    else dropout_seed + li * _SEED_LAYER_STRIDE)
            if self.cfg.moe_route_before_attention and layer.mixer == "*":
                # the router reads this layer's input, before attention,
                # and its work is the experts' (XLA is free to run it beside
                # attention)
                with jax.named_scope("mlp"), jax.named_scope("moe.router"):
                    routing = (self.layers[li + 1].mix.route(
                        params["layers"][li + 1]["mixer"], x),)
            extra, routing = ((routing, ()) if layer.mixer == "E"
                              else ((), routing))
            call = layer
            if self.cfg.remat:
                # trade recompute for activation memory (apex
                # tensor_parallel.checkpoint → jax.checkpoint).
                # remat_policy="dots" is Megatron's SELECTIVE activation
                # recompute: GEMM outputs are saved (the expensive MXU
                # work is not redone in the backward), only the cheap
                # elementwise/softmax chain recomputes
                call = jax.checkpoint(
                    lambda lp, x, c, s, sd, *r, _l=layer: _l(lp, x, c, s, sd,
                                                             *r),
                    policy=_remat_policy(self.cfg.remat_policy))
            out = call(lp, x, cos, sin, seed, *extra)
            if layer.is_moe and self.cfg.moe_router != "softmax":
                x, load = out
                loads.append(load)
            elif layer.is_moe:
                x, aux = out
                aux_total = aux_total + aux
            else:
                x = out
        return x, (jnp.stack(loads) if loads else aux_total)

    def _final_ln_params(self, params):
        """Under SP the head's cotangents are per-vocab-shard partials, so
        the (replicated) final-LN params see partial grads; identity-fwd/
        psum-bwd restores the total (see ParallelTransformerLayer)."""
        p = params["final_layernorm"]
        if self._sp_enabled():
            p = tp.copy_to_tensor_model_parallel_region(
                p, self.cfg.axis_name)
        return p

    def _head_logits(self, params, x, eq):
        """Tied-embedding head GEMM in f32.  A quantized tree (the
        ``weight_quant="int8"`` leaves from
        :func:`quantize_decode_params`) routes through the fused
        dequant-GEMM; otherwise the original einsum runs unchanged, so
        the knob-off path stays bitwise."""
        emb = params[self.head]
        if "weight_scale" in emb:
            from apex_tpu.ops.quant_gemm import quant_gemm
            return quant_gemm(x.astype(_f32), emb["weight"],
                              emb["weight_scale"])
        if emb["weight"].dtype == jnp.bfloat16:
            # at the weights' own precision, accumulated in float32: the
            # numbers an upcast would give a bf16 input, without a float32
            # copy of the matrix or a float32 pass of the MXU
            return jnp.einsum(eq, x.astype(jnp.bfloat16), emb["weight"],
                              preferred_element_type=_f32)
        return jnp.einsum(eq, x.astype(_f32), emb["weight"].astype(_f32))

    def logits(self, params, x):
        """Tied LM head: vocab-parallel logits ``(b, s, vocab/t)``."""
        x = self.final_layernorm(self._final_ln_params(params), x)
        return self._head_logits(params, x, "bsh,vh->bsv")

    def head_loss(self, params, x, targets):
        """Per-token CE of the tied head on backbone output ``x``.

        Serial vocab (``axis_name is None``) with ``cfg.fused_lm_head``
        routes through :func:`apex_tpu.ops.lm_head.fused_linear_cross_entropy`
        — the (b·s, vocab) logits never materialize, which is the HBM
        ceiling of the training step (the serial GPT-350M config OOMs at
        batch 24 without it and runs batch 32 with it).  The
        vocab-parallel (TP) path keeps the sharded-logsumexp cross
        entropy.
        """
        b, s = targets.shape
        if self.cfg.axis_name is None and self.cfg.fused_lm_head:
            from apex_tpu.ops.lm_head import fused_linear_cross_entropy
            h = self.final_layernorm(params["final_layernorm"], x)
            # head operands at the COMPUTE dtype: the kernel dots at the
            # operand precision (f32 dots run ~1/8 the bf16 MXU rate),
            # and the head GEMMs are the largest single matmuls in the
            # step; accumulation/logsumexp stay f32 inside the kernel
            return fused_linear_cross_entropy(
                h.reshape(b * s, h.shape[-1]).astype(self.cfg.dtype),
                params[self.head]["weight"].astype(self.cfg.dtype),
                targets.reshape(b * s)).reshape(b, s)
        logits = self.logits(params, x)
        vl = logits.shape[-1]
        return tp.vocab_parallel_cross_entropy(
            logits.reshape(b * s, vl), targets.reshape(b * s),
            axis_name=self.cfg.axis_name).reshape(b, s)

    def _sp_enabled(self):
        return (self.cfg.sequence_parallel
                and self.cfg.axis_name is not None)

    def _sp_scatter(self, x):
        """Megatron SP entry edge: shard activations along the sequence
        dim so LayerNorms, residual adds and (in the backward) their
        grads run on ``(b, s/t, h)``; each block's column gather / row
        reduce-scatter restores and reshards inside the TP regions."""
        if x.shape[1] % self.cfg.tensor_parallel_size:
            raise ValueError(
                f"sequence_parallel requires seq_len divisible by "
                f"tensor_parallel_size ({x.shape[1]} % "
                f"{self.cfg.tensor_parallel_size} != 0)")
        return tp.scatter_to_sequence_parallel_region(
            x, self.cfg.axis_name, 1)

    def _sp_gather(self, x):
        """SP exit edge before the (vocab-parallel) head; the backward is
        a reduce-scatter summing the per-rank vocab-shard contributions."""
        return tp.gather_from_sequence_parallel_region(
            x, self.cfg.axis_name, 1)

    def __call__(self, params, tokens, dropout_seed=None):
        x = self.embed(params, tokens)
        if self._sp_enabled():
            x = self._sp_scatter(x)
        x, _ = self.backbone(params, x, seq_len=tokens.shape[1],
                             dropout_seed=dropout_seed)
        if self._sp_enabled():
            x = self._sp_gather(x)
        return self.logits(params, x)

    apply = __call__

    # -- KV-cache inference --------------------------------------------------

    def _check_decode_supported(self, path=None):
        """Raise unless the model can be served; with ``path``, unless that
        cache path can serve it.  A layer pattern of attention, dense and
        expert layers is served through ``prefill`` and
        ``decode_step_paged``, the paths the paged engine's default
        configuration runs, and through no other."""
        cfg = self.cfg
        pattern = cfg.layer_pattern
        if pattern is not None and set(pattern) - set("*DEP"):
            raise NotImplementedError(
                f"serving a layer_pattern ({pattern!r}) with Mamba or "
                "convolution layers is "
                "not implemented: a Mamba layer needs per-request state of "
                "fixed size (its conv window and its (heads, head_dim, "
                "state) matrix) and a gated short convolution its window, "
                "carried beside the paged KV pool through preempt, "
                "export_kv/adopt_kv and the prefix trie; the model trains "
                "(GPTModel.loss)")
        if pattern is not None and "*" in pattern and not cfg.kv_lora_rank \
                and (cfg.num_kv_heads != cfg.num_attention_heads
                     or cfg.qk_norm or cfg.rotary_layers
                     or cfg.moe_route_before_attention):
            raise NotImplementedError(
                f"serving a layer_pattern ({pattern!r}) with grouped KV "
                "heads, QK-norm, a window or rotary by layer in a '*' layer, "
                "or experts routed on a '*' layer's input, is not "
                "implemented: the plain attention mixer's cache paths write "
                "one K and one V head for every query head, put no norm on "
                "them, attend to every position, rotate every layer alike "
                "and route each expert layer on its own norm (a 'P' "
                "layer's grouped heads are served)")
        if pattern is not None and path is not None:
            what = ("a latent-attention layer" if cfg.kv_lora_rank
                    else "a parallel block" if "P" in pattern
                    else "a one-mixer layer")
            raise NotImplementedError(
                f"{path} is not implemented for a layer_pattern "
                f"({pattern!r}): {what} lacks {_MISSING_CACHE_PATHS[path]}; "
                "it is served through prefill and decode_step_paged "
                "(PagedInferenceEngine's default configuration)")
        if self.cfg.context_axis is not None:
            raise ValueError(
                "KV-cache decode does not compose with context "
                "parallelism (the cache would be sequence-sharded)")
        if self.cfg.sequence_parallel:
            raise ValueError(
                "KV-cache decode requires sequence_parallel=False "
                "(decode steps are single-token)")

    def prefill(self, params, tokens):
        """Process a full prompt; returns ``(logits, kv)``.

        ``logits``: ``(b, s, vocab)`` (vocab-parallel under TP, like
        :meth:`logits`); ``kv``: ``(layers, 2, b, s, local_heads,
        head_dim)`` post-RoPE cache entries in the compute dtype — write
        them into a :class:`~apex_tpu.inference.KVCache` slot (which casts
        to the cache dtype) and continue with :meth:`decode_step`.
        Prompts padded beyond their true length are safe: causal masking
        keeps logits at positions ``< prompt_len`` unaffected, and the
        padded cache rows are masked by the per-slot length at decode.
        """
        self._check_decode_supported()
        with jax.named_scope("embeddings"):
            x = self.embed(params, tokens)
        cos, sin = self.rope_tables(tokens.shape[1])
        if self.cfg.layer_pattern is not None:
            # for each of the pool's arrays (and the window layers' storage
            # after them), one entry a layer that writes it: (layers, parts,
            # b, s, kv_heads, head_dim)
            arrays = [[] for _ in self.cache_record()]
            arrays += [[]] if self.window_record() else []
            selection = None
            for layer, lp in zip(self.layers, params["layers"]):
                x, records, selection = layer.prefill(lp, x, cos, sin,
                                                      selection)
                for array, record in zip(arrays, records or ()):
                    if record is not None:
                        array.append(record)
            with jax.named_scope("lm_head"):
                # accumulated in float32, kept in the compute dtype: the
                # engine reads one row of (1, bucket, vocab), 1.3 GB in
                # float32 at a bucket of 16 384 rows of 19 360
                logits = self.logits(params, x).astype(self.cfg.dtype)
            records = tuple(jnp.stack(a) for a in arrays)
            return logits, records[0] if len(records) == 1 else records
        ks, vs = [], []
        for layer, lp in zip(self.layers, params["layers"]):
            x, (k, v) = layer.prefill(lp, x, cos, sin)
            ks.append(k)
            vs.append(v)
        kv = jnp.stack([jnp.stack(ks), jnp.stack(vs)], axis=1)
        with jax.named_scope("lm_head"):
            return self.logits(params, x), kv

    def cache_record(self):
        """What the model caches, for :class:`~apex_tpu.serving.
        PagedKVCache`: one ``(layers, parts, width)`` for each array of the
        pool, a position's record in one of ``layers`` being ``parts`` rows
        of ``width`` numbers in the cache's dtype (a fourth entry names
        another: the index keys stay float32, as their products are).  The plain block and a pattern's plain
        attention keep K and V (2 parts of ``heads * head_dim``) in one
        array; latent attention its latent and rotary key (one part, padded
        to whole lane tiles) in one array and, for the layers that own an
        indexer alone, their index keys in a second."""
        cfg = self.cfg
        if cfg.layer_pattern is None:
            return ((cfg.num_layers, 2, cfg.local_heads * cfg.head_dim),)
        layers = cfg.layer_pattern.count("*")
        if not cfg.kv_lora_rank:
            # a parallel block's window layers keep theirs apart
            # (:meth:`window_record`)
            return ((cfg.attention_types.count("full"), 2,
                     cfg.num_kv_heads * cfg.head_dim),)
        return ((layers, 1, latent_record_width(cfg.kv_lora_rank,
                                                cfg.qk_rope_head_dim)),
                (cfg.indexer_types.count("full"), 1, cfg.index_head_dim,
                 _f32))

    def window_record(self):
        """``(layers, parts, width, window)`` of the window layers' storage,
        or None for a model without window layers: K and V of the KV heads
        (2 parts of ``kv_heads * head_dim``) for each layer whose
        ``attention_types`` entry is ``"sliding"``, of which a sequence
        holds no more than its last ``window`` positions
        (:class:`~apex_tpu.serving.PagedKVCache`'s window storage)."""
        cfg = self.cfg
        if cfg.layer_pattern is None or not cfg.sliding_window:
            return None
        return (cfg.attention_types.count("sliding"), 2,
                cfg.num_kv_heads * cfg.head_dim, cfg.sliding_window)

    def decode_step(self, params, tokens, cache, positions):
        """One batched autoregressive step over the cache ring.

        ``tokens``: ``(slots,)`` int — the token to feed per cache slot;
        ``cache``: ``(slots, layers, 2, max_seq, local_heads, head_dim)``
        (any float dtype; bf16 caches accumulate attention in f32);
        ``positions``: ``(slots,)`` int — each token's absolute position,
        i.e. the number of valid cache entries before this step.

        Returns ``(logits, cache)`` with ``logits`` ``(slots, vocab)``
        (vocab-parallel under TP) and the cache advanced by one entry per
        row.  Rows are mathematically independent, so inactive slots may
        carry garbage: their writes land at their (stale) position and are
        overwritten by the next prefill before any valid length reaches
        them.
        """
        self._check_decode_supported("decode_step")
        with jax.named_scope("embeddings"):
            x = self.embedding(params["embedding"], tokens[:, None])
            if not self.cfg.rotary:
                x = x + params["position_embedding"][positions][:, None]
            x = x.astype(self.cfg.dtype)
        for li, (layer, lp) in enumerate(zip(self.layers,
                                             params["layers"])):
            x, cache = layer.decode(lp, x, cache, li, positions)
        with jax.named_scope("lm_head"):
            x = self.final_layernorm(params["final_layernorm"], x)
            logits = self._head_logits(params, x[:, 0], "bh,vh->bv")
        return logits, cache

    def decode_step_paged(self, params, tokens, pool, block_tables,
                          positions):
        """One batched decode step against a paged block pool.

        Mirrors :meth:`decode_step` op-for-op — same embed, same RoPE
        rows, same f32 head einsum — with the cache access indirected
        through ``block_tables`` (``(slots, max_blocks)`` int32; see
        :class:`apex_tpu.serving.PagedKVCache`).  Off-TPU the attention
        gathers the table back to the contiguous layout and runs the
        identical reference, which is why the serving engine's
        paged-vs-contiguous parity is bitwise, not approximate.  Rows
        whose table is all-garbage (block 0) compute garbage that is
        never read, like inactive slots in :meth:`decode_step`.  A model
        with window layers (:meth:`window_record`) takes ``pool`` and
        ``block_tables`` as pairs, ``(full layers', window layers')``, and
        returns the pair.
        """
        self._check_decode_supported()
        with jax.named_scope("embeddings"):
            x = self.embedding(params["embedding"], tokens[:, None])
            if not self.cfg.rotary:
                x = x + params["position_embedding"][positions][:, None]
            x = x.astype(self.cfg.dtype)
        if self.window_record() is not None:
            # ``pool`` and ``block_tables``: (full layers', window layers');
            # every layer is a parallel block
            pools, li = list(pool), [0, 0]
            for layer, lp in zip(self.layers, params["layers"]):
                kind = int(layer.window is not None)
                x, pools[kind], _ = layer.decode_paged(
                    lp, x, pools[kind], li[kind], block_tables[kind],
                    positions)
                li[kind] += 1
            with jax.named_scope("lm_head"):
                x = self.final_layernorm(params["final_layernorm"], x)
                return (self._head_logits(params, x[:, 0], "bh,vh->bv"),
                        tuple(pools))
        if self.cfg.layer_pattern is not None:
            latent = bool(self.cfg.kv_lora_rank)
            li, selection = [0, 0], None    # the pool's layers: those that
            for layer, lp in zip(self.layers, params["layers"]):    # cache
                x, pool, selection = layer.decode_paged(
                    lp, x, pool, tuple(li) if latent else li[0],
                    block_tables, positions, selection)
                if layer.mixer in "*P":
                    li[0] += 1
                    li[1] += latent and layer.mix.indexer
            with jax.named_scope("lm_head"):
                x = self.final_layernorm(params["final_layernorm"], x)
                return self._head_logits(params, x[:, 0], "bh,vh->bv"), pool
        for li, (layer, lp) in enumerate(zip(self.layers,
                                             params["layers"])):
            x, pool = layer.decode_paged(lp, x, pool, li, block_tables,
                                         positions)
        with jax.named_scope("lm_head"):
            x = self.final_layernorm(params["final_layernorm"], x)
            logits = self._head_logits(params, x[:, 0], "bh,vh->bv")
        return logits, pool

    def decode_chunk(self, params, tokens, pool, block_tables, positions,
                     write_blocks, write_offsets):
        """Process ``chunk`` tokens per sequence against the paged pool
        in one forward — the workhorse of chunked prefill (a prompt slice
        at a time, mixed into decode ticks) and speculative verification
        (score γ draft tokens in one pass).

        ``tokens``/``positions``/``write_blocks``/``write_offsets``:
        ``(slots, chunk)`` — each token's id, absolute position, and
        host-precomputed pool write coordinates (pad rows target garbage
        block 0).  Returns ``(logits, pool)`` with ``logits``
        ``(slots, chunk, vocab)`` through the same tied head as
        :meth:`prefill`'s — the chunk's final row is what admission
        samples the first token from.
        """
        self._check_decode_supported("decode_chunk")
        with jax.named_scope("embeddings"):
            x = self.embedding(params["embedding"], tokens)
            if not self.cfg.rotary:
                x = x + params["position_embedding"][positions]
            x = x.astype(self.cfg.dtype)
        for li, (layer, lp) in enumerate(zip(self.layers,
                                             params["layers"])):
            x, pool = layer.decode_chunk(lp, x, pool, li, block_tables,
                                         positions, write_blocks,
                                         write_offsets)
        with jax.named_scope("lm_head"):
            return self.logits(params, x), pool

    def decode_step_paged_quant(self, params, tokens, pool, scales,
                                block_tables, positions):
        """:meth:`decode_step_paged` against an int8 scale-per-block
        pool (``pool`` int8 of the same shape, ``scales``
        ``(num_blocks, layers, 2, kv_heads)`` f32; see
        :class:`apex_tpu.serving.QuantizedPagedKVCache`).  Same embed,
        RoPE rows, and f32 head einsum — the only difference is the
        per-block dequantize/requantize around the cache access.
        Returns ``(logits, pool, scales)``."""
        self._check_decode_supported("decode_step_paged_quant")
        with jax.named_scope("embeddings"):
            x = self.embedding(params["embedding"], tokens[:, None])
            if not self.cfg.rotary:
                x = x + params["position_embedding"][positions][:, None]
            x = x.astype(self.cfg.dtype)
        for li, (layer, lp) in enumerate(zip(self.layers,
                                             params["layers"])):
            x, pool, scales = layer.decode_paged_quant(
                lp, x, pool, scales, li, block_tables, positions)
        with jax.named_scope("lm_head"):
            x = self.final_layernorm(params["final_layernorm"], x)
            logits = self._head_logits(params, x[:, 0], "bh,vh->bv")
        return logits, pool, scales

    def decode_chunk_quant(self, params, tokens, pool, scales,
                           block_tables, positions, write_blocks,
                           write_offsets):
        """:meth:`decode_chunk` against an int8 pool — chunked prefill
        on a quantized cache.  Inserts are serialized per token inside
        each layer (see
        :meth:`ParallelAttention.decode_chunk_quant`), which keeps the
        final pool state independent of chunk boundaries.  Returns
        ``(logits, pool, scales)``."""
        self._check_decode_supported("decode_chunk_quant")
        with jax.named_scope("embeddings"):
            x = self.embedding(params["embedding"], tokens)
            if not self.cfg.rotary:
                x = x + params["position_embedding"][positions]
            x = x.astype(self.cfg.dtype)
        for li, (layer, lp) in enumerate(zip(self.layers,
                                             params["layers"])):
            x, pool, scales = layer.decode_chunk_quant(
                lp, x, pool, scales, li, block_tables, positions,
                write_blocks, write_offsets)
        with jax.named_scope("lm_head"):
            return self.logits(params, x), pool, scales

    def loss(self, params, tokens, targets, dropout_seed=None,
             return_expert_load=False):
        """Mean next-token loss via vocab-parallel cross entropy (+ the
        Switch aux load-balancing term when the FFNs are MoE).

        ``return_expert_load`` (sigmoid router): also return the tokens
        each held expert saw, ``(expert layers, held)`` int32 — the
        ``has_aux`` of ``jax.value_and_grad``.

        Under context parallelism the mean over local tokens is pmeaned
        across the context axis (equal shard sizes -> exact global mean).

        ``dropout_seed`` (int or traced scalar) enables the configured
        ``attention_dropout`` for this step — pass the step counter
        (advance by +1 per step; layer/microbatch streams stride the
        seed space so steps never replay each other's masks); omit it
        (None) for eval.
        """
        with jax.named_scope("embeddings"):
            x = self.embed(params, tokens)
        if self._sp_enabled():
            x = self._sp_scatter(x)
        x, aux = self.backbone(params, x, seq_len=tokens.shape[1],
                               dropout_seed=dropout_seed)
        if self._sp_enabled():
            x = self._sp_gather(x)
        with jax.named_scope("lm_head"):
            mean = jnp.mean(self.head_loss(params, x, targets))
        if self.cfg.moe_router != "softmax":
            # no auxiliary loss: the sorted dispatch drops no token
            return (mean, aux) if return_expert_load else mean
        if self.cfg.n_experts > 0:
            mean = mean + self.cfg.moe_aux_weight * aux / len(self.layers)
        if self.cfg.context_axis is not None:
            mean = jax.lax.pmean(mean, self.cfg.context_axis)
        return mean

    # -- GSPMD form ---------------------------------------------------------

    def partition_specs(self):
        """PartitionSpecs for jitting the serial form under GSPMD: the
        compiler inserts the same collectives the shard_map form writes
        explicitly (the idiomatic TPU path)."""
        from jax.sharding import PartitionSpec as P
        if self.cfg.layer_pattern is not None:
            raise ValueError(
                "partition_specs (and pack_for_shard_map, which packs by "
                "them) describe the tensor-parallel split of the plain "
                "block; a layer_pattern's mixers have none yet")
        if self.cfg.n_experts > 0:
            # MoE: each expert's FFN dim shards over the tensor axis
            # (Column/Row inside the expert); the EXPERT-dim sharding is
            # the explicit shard_map path (expert_axis)
            from apex_tpu.transformer.parallel_state import TENSOR_AXIS
            mlp_spec = {"gate": P(),
                        "w1": P(None, None, TENSOR_AXIS),
                        "w2": P(None, TENSOR_AXIS, None)}
        else:
            mlp_spec = {"fc1": self.layers[0].mlp.fc1.partition_spec(),
                        "fc2": self.layers[0].mlp.fc2.partition_spec()}
        layer_spec = {
            "input_layernorm": {"weight": P(), "bias": P()},
            "attention": {"qkv": self.layers[0].attention.qkv
                          .partition_spec(),
                          "proj": self.layers[0].attention.proj
                          .partition_spec()},
            "post_attention_layernorm": {"weight": P(), "bias": P()},
            "mlp": mlp_spec,
        }
        spec = {
            "embedding": self.embedding.partition_spec(),
            "layers": [layer_spec] * self.cfg.num_layers,
            "final_layernorm": {"weight": P(), "bias": P()},
        }
        if not self.cfg.rotary:
            spec["position_embedding"] = P()
        return spec


def shard_params_for_tp(cfg: GPTConfig, params, rank: int):
    """Slice full (serial-init) GPT params into tensor-parallel rank
    ``rank``'s local shards, matching the layer shardings
    (Column: row-block of weight/bias; Row: column-block of weight,
    replicated bias; vocab embedding: row-block).  Test/checkpoint-resharding
    utility — the shard_map form consumes these shards directly."""
    t = cfg.tensor_parallel_size

    def col(w):      # ColumnParallel weight/bias: shard dim 0
        per = w.shape[0] // t
        return w[rank * per:(rank + 1) * per]

    def row(w):      # RowParallel weight: shard dim 1
        per = w.shape[1] // t
        return w[:, rank * per:(rank + 1) * per]

    def colg(g):     # Column group: weight/bias/scale all row-sharded.
        # Per-output-channel scales ride the same dim-0 slice, which is
        # why quantize-then-shard == shard-then-quantize bitwise here
        out = {"weight": col(g["weight"])}
        if "bias" in g:
            out["bias"] = col(g["bias"])
        if "weight_scale" in g:
            out["weight_scale"] = col(g["weight_scale"])
        return out

    def rowg(g):     # Row group: weight column-sharded; bias and the
        # per-OUTPUT-row scales are replicated (the scale dim is not
        # the sharded dim)
        out = {"weight": row(g["weight"])}
        if "bias" in g:
            out["bias"] = g["bias"]
        if "weight_scale" in g:
            out["weight_scale"] = g["weight_scale"]
        return out

    out = {"embedding": colg(params["embedding"]),
           "final_layernorm": params["final_layernorm"],
           "layers": []}
    if "position_embedding" in params:
        out["position_embedding"] = params["position_embedding"]
    for lp in params["layers"]:
        if "gate" in lp["mlp"]:
            # MoE expert stacks: each expert is Column/Row-sharded on
            # its FFN dim (w1 last dim, w2 middle dim); gate replicated
            fl = cfg.ffn_hidden_size // t
            mlp = {"gate": lp["mlp"]["gate"],
                   "w1": lp["mlp"]["w1"][:, :, rank * fl:(rank + 1) * fl],
                   "w2": lp["mlp"]["w2"][:, rank * fl:(rank + 1) * fl, :]}
        else:
            mlp = {
                "fc1": colg(lp["mlp"]["fc1"]),
                "fc2": rowg(lp["mlp"]["fc2"]),
            }
        out["layers"].append({
            "input_layernorm": lp["input_layernorm"],
            "post_attention_layernorm": lp["post_attention_layernorm"],
            "attention": {
                "qkv": colg(lp["attention"]["qkv"]),
                "proj": rowg(lp["attention"]["proj"]),
            },
            "mlp": mlp,
        })
    return out


def quantize_decode_params(params):
    """Quantize a GPT param tree for the int8 decode path
    (``GPTConfig(weight_quant="int8")``) — run ONCE at inference-engine
    init, never per step.

    Every dense GEMM weight — ``embedding.weight`` (the gather *and*
    the tied lm-head), each layer's ``qkv``/``proj``/``fc1``/``fc2`` —
    becomes ``{"weight": int8, "weight_scale": f32-per-output-row}``
    via :func:`apex_tpu.ops.quant_gemm.quantize_weight`; biases,
    LayerNorms and the (tiny, gather-only) position embedding stay in
    their original dtype.  A pure function of the weight values, so
    the quantized tree is bitwise-deterministic across loads.

    TP composes per shard: the tree may already be the local shard
    from :func:`shard_params_for_tp` — per-output-channel scales make
    quantization commute bitwise with the ColumnParallel/vocab row
    slices, and RowParallel column slices only tighten the per-shard
    scale (local amax <= full amax), never loosen the error bound.
    """
    from apex_tpu.ops.quant_gemm import quantize_weight

    def q(group):
        w8, scale = quantize_weight(group["weight"])
        out = dict(group)
        out["weight"] = w8
        out["weight_scale"] = scale
        return out

    out = {"embedding": q(params["embedding"]),
           "final_layernorm": params["final_layernorm"],
           "layers": []}
    if "position_embedding" in params:
        out["position_embedding"] = params["position_embedding"]
    for lp in params["layers"]:
        if "gate" in lp["mlp"]:
            raise ValueError(
                "quantize_decode_params covers dense GPT trees; this "
                "tree has MoE expert stacks (mlp.gate) — "
                "GPTConfig(weight_quant=...) rejects n_experts > 0 for "
                "the same reason")
        out["layers"].append({
            "input_layernorm": lp["input_layernorm"],
            "post_attention_layernorm": lp["post_attention_layernorm"],
            "attention": {"qkv": q(lp["attention"]["qkv"]),
                          "proj": q(lp["attention"]["proj"])},
            "mlp": {"fc1": q(lp["mlp"]["fc1"]),
                    "fc2": q(lp["mlp"]["fc2"])},
        })
    return out


def _is_spec_leaf(x):
    from jax.sharding import PartitionSpec
    return isinstance(x, PartitionSpec)


def _is_sharded(spec) -> bool:
    return any(a is not None for a in spec)


def pack_for_shard_map(model: GPTModel, params, n_stages: Optional[int] = None,
                       tensor_axis: Optional[str] = "model",
                       pipe_axis: str = "pipe",
                       expert_axis: Optional[str] = None,
                       n_virtual: int = 1):
    """Pack serial-init GPT params for an explicit ``shard_map`` step.

    TP-sharded leaves (per :meth:`GPTModel.partition_specs`) are stacked
    along a new leading ``(tp,)`` axis to be split by the mesh; replicated
    leaves pass through whole so they stay device-INVARIANT inside
    ``shard_map`` — that is load-bearing for gradients: the cotangent of a
    replicated param is split arbitrarily across devices by the backward
    collectives, and only JAX's automatic psum-of-invariant-grads restores
    the total.  With ``n_stages`` the layer stack is additionally split
    over the pipe axis (:func:`stack_layers_for_pipeline`).  With
    ``expert_axis`` (MoE models) the expert stacks (``mlp.w1``/``w2``)
    additionally split their EXPERT dim over that axis — leading mesh
    axes are ordered ``(tp, expert, pipe)``.  ``n_virtual > 1`` keeps an
    extra per-device ``(n_virtual,)`` chunk axis on the layer leaves for
    the interleaved schedule (see :func:`stack_layers_for_pipeline`).

    Returns ``(packed, in_specs, local_fn, repack_fn)``:
    ``local_fn`` strips the unit mesh axes inside ``shard_map`` to yield
    the per-device params :class:`GPTModel`/:func:`pipeline_step` consume;
    ``repack_fn`` is its inverse for gradient pytrees (so ``out_specs`` can
    reuse ``in_specs``).
    """
    from jax.sharding import PartitionSpec as P

    cfg = model.cfg
    n_tp = cfg.tensor_parallel_size
    ep = cfg.expert_parallel_size if expert_axis is not None else 1
    if expert_axis is not None and cfg.n_experts <= 0:
        raise ValueError("expert_axis given but the model has no experts")
    specs = model.partition_specs()
    shards = [shard_params_for_tp(cfg, params, r) for r in range(n_tp)]
    if n_stages is not None:
        for sh in shards:
            sh["layers"] = stack_layers_for_pipeline(sh["layers"], n_stages,
                                                     n_virtual)
    elif n_virtual != 1:
        raise ValueError("n_virtual requires n_stages")
    if n_stages is not None:
        specs = dict(specs, layers=specs["layers"][0])

    def tmap(fn, *trees):
        return jax.tree_util.tree_map(fn, specs, *trees,
                                      is_leaf=_is_spec_leaf)

    packed = tmap(lambda s, *xs: jnp.stack(xs) if _is_sharded(s) else xs[0],
                  *shards)

    from apex_tpu.transformer.expert_parallel import is_gpt_expert_leaf

    def _is_expert(path) -> bool:
        return expert_axis is not None and is_gpt_expert_leaf(path)

    def path_aware(fn):
        # layer leaves carry the extra pipe axis when pipelined; expert
        # leaves carry the extra expert axis when expert-sharded
        def run(tree):
            out = {}
            for key, sub in tree.items():
                in_layers = (key == "layers" and n_stages is not None)
                out[key] = jax.tree_util.tree_map_with_path(
                    lambda p, s, x: fn(s, x, in_layers, _is_expert(p)),
                    specs[key], sub, is_leaf=_is_spec_leaf)
            return out
        return run

    if expert_axis is not None:
        # split the expert dim (after the tp stack [+ stage axes]) into
        # (ep, local) and move ep up to sit right after the tp stack
        def expert_split(s, x, lay, exp):
            if not exp:
                return x
            e_pos = (3 + (n_virtual > 1)) if lay else 1
            nl = x.shape[e_pos] // ep
            x = x.reshape(x.shape[:e_pos] + (ep, nl) + x.shape[e_pos + 1:])
            return jnp.moveaxis(x, e_pos, 1)
        packed = path_aware(expert_split)(packed)

    def spec_for(s, x, lay, exp):
        if exp:
            return (P(tensor_axis, expert_axis, pipe_axis) if lay
                    else P(tensor_axis, expert_axis))
        if lay:
            return P(tensor_axis, pipe_axis) if _is_sharded(s) \
                else P(pipe_axis)
        return P(tensor_axis) if _is_sharded(s) else P()

    def local_for(s, x, lay, exp):
        if exp:
            return x[0, 0, 0] if lay else x[0, 0]
        if lay:
            return x[0, 0] if _is_sharded(s) else x[0]
        return x[0] if _is_sharded(s) else x

    def repack_for(s, g, lay, exp):
        if exp:
            return g[None, None, None] if lay else g[None, None]
        if lay:
            return g[None, None] if _is_sharded(s) else g[None]
        return g[None] if _is_sharded(s) else g

    in_specs = path_aware(spec_for)(packed)
    local_fn = path_aware(local_for)
    repack_fn = path_aware(repack_for)
    return packed, in_specs, local_fn, repack_fn


def unpack_from_shard_map(model: GPTModel, packed,
                          n_stages: Optional[int] = None,
                          n_virtual: int = 1):
    """Inverse of :func:`pack_for_shard_map`: recover the serial-init
    param layout from a packed tree.

    TP-stacked leaves are concatenated back along their sharded dim
    (per :meth:`GPTModel.partition_specs` — the same specs that drove
    the packing), stage stacks are un-interleaved and flattened back to
    the per-layer list.  Pure slicing/concat, so f32 values round-trip
    bitwise — which is what makes the serial layout the canonical form
    elastic re-sharding compares topologies in (a ``dp=2 x tp=2``
    state and a ``dp=4`` state unpack to the SAME logical tensors).
    Expert-parallel packings are not invertible here (the ep split
    interleaves expert rows); unpack before applying ``expert_axis``.
    """
    cfg = model.cfg
    if cfg.n_experts > 0:
        raise ValueError(
            "unpack_from_shard_map does not support expert-parallel "
            "packings; unpack applies to dense GPT params only")
    specs = model.partition_specs()

    def shard_dim(s):
        for d, a in enumerate(s):
            if a is not None:
                return d
        return None

    def merge_plain(s, x):
        d = shard_dim(s)
        if d is None:
            return x
        return jnp.concatenate([x[r] for r in range(x.shape[0])], axis=d)

    def unstack_layers(s, x):
        d = shard_dim(s)
        parts = ([x[r] for r in range(x.shape[0])] if d is not None
                 else [x])
        flat_parts = []
        for y in parts:
            if n_virtual == 1:
                flat = y.reshape((n_stages * y.shape[1],) + y.shape[2:])
            else:
                n_logical = n_stages * n_virtual
                lpc = y.shape[2]
                z = y.reshape((n_logical, lpc) + y.shape[3:])
                perm = [c * n_stages + st for st in range(n_stages)
                        for c in range(n_virtual)]
                inv = jnp.asarray([perm.index(i)
                                   for i in range(n_logical)])
                flat = z[inv].reshape((n_logical * lpc,) + z.shape[2:])
            flat_parts.append(flat)
        # the per-layer sharded dim sits behind the layer axis now
        return (flat_parts[0] if d is None
                else jnp.concatenate(flat_parts, axis=d + 1))

    out = {}
    for key, sub in packed.items():
        if key == "layers" and n_stages is not None:
            merged = jax.tree_util.tree_map(
                unstack_layers, specs["layers"][0], sub,
                is_leaf=_is_spec_leaf)
            n_layers = jax.tree_util.tree_leaves(merged)[0].shape[0]
            out[key] = [jax.tree_util.tree_map(
                lambda leaf, i=i: leaf[i], merged)
                for i in range(n_layers)]
        else:
            out[key] = jax.tree_util.tree_map(
                merge_plain, specs[key], sub, is_leaf=_is_spec_leaf)
    return out


# -- pipeline composition ----------------------------------------------------

def stack_layers_for_pipeline(layer_params, n_stages: int,
                              n_virtual: int = 1):
    """Split per-layer params into pipeline stage stacks.

    ``layer_params`` is the ``params["layers"]`` list; returns a pytree
    whose leaves have shape ``(n_stages, layers_per_stage, ...)`` — shard
    the leading axis over the pipe mesh axis (``in_specs`` leading
    ``P("pipe", ...)``), drop the unit axis inside ``shard_map``, and each
    stage holds exactly its contiguous block of layers (apex: layer ranges
    assigned per pipeline rank).

    With ``n_virtual > 1`` (interleaved schedule) the model splits into
    ``n_stages * n_virtual`` logical stages and leaves come back as
    ``(n_stages, n_virtual, layers_per_stage, ...)`` with device ``s``
    chunk ``c`` holding logical stage ``c * n_stages + s`` (Megatron's
    interleaved chunk assignment).
    """
    n_layers = len(layer_params)
    n_logical = n_stages * n_virtual
    if n_layers % n_logical:
        raise ValueError(
            f"num_layers ({n_layers}) must be divisible by the number of "
            f"logical pipeline stages ({n_stages} x {n_virtual})")
    stacked = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs),
                                     *layer_params)
    lpc = n_layers // n_logical
    if n_virtual == 1:
        return jax.tree_util.tree_map(
            lambda x: x.reshape((n_stages, lpc) + x.shape[1:]), stacked)
    perm = jnp.asarray([c * n_stages + s
                        for s in range(n_stages) for c in range(n_virtual)])
    return jax.tree_util.tree_map(
        lambda x: x.reshape((n_logical, lpc) + x.shape[1:])[perm].reshape(
            (n_stages, n_virtual, lpc) + x.shape[1:]),
        stacked)


def make_stage_fn(model: GPTModel, dropout_seed=None,
                  remat: Optional[bool] = None):
    """Build the ring-engine ``stage_fn``: scan this chunk's stacked layer
    params over the activation (``(mb, s, h) -> (mb, s, h)``), signature
    ``stage_fn(stage_params, x, info)`` (see
    :class:`~apex_tpu.transformer.pipeline_parallel.JobInfo`).

    The stage activation is ``x`` or, for MoE models, ``(x, aux)`` —
    each logical stage adds its local layers' Switch aux contributions so
    the last stage holds the per-microbatch total; the tuple rides the
    ppermute ring (and its cotangent the backward ring) like any leaf.

    ``dropout_seed`` enables attention dropout: the per-layer stream is
    derived *arithmetically* from the job identity — layer ``j`` of
    logical stage ``info.stage`` on microbatch ``info.microbatch`` draws
    ``base + m*MB_STRIDE + (stage*lpc + j)*LAYER_STRIDE`` (int32,
    wrapping) — so seeds never ride the ring and the backward recompute
    replays the exact forward masks.

    ``remat`` (default ``cfg.remat``) wraps each layer in
    ``jax.checkpoint`` with the configured policy: inside the engine's
    per-tick vjp this bounds the *within-job* residuals to layer
    boundaries (the schedule itself already recomputes the stage forward
    from the saved stage input).
    """
    layer = model.layers[0]       # all layers share the module config
    moe = model.cfg.n_experts > 0
    if remat is None:
        remat = model.cfg.remat
    call = layer
    if remat:
        call = jax.checkpoint(
            lambda lp, h, c, s, sd, _l=layer: _l(lp, h, c, s, sd),
            policy=_remat_policy(model.cfg.remat_policy))

    def stage_fn(stage_params, carry, info):
        if moe:
            x, aux = carry
        else:
            x, aux = carry, None
        # under SP the carry is sequence-scattered (mb, s/t, h) but rope
        # positions are global: tables span the FULL sequence (the
        # attention block gathers to full seq internally), mirroring
        # __call__'s ``backbone(..., seq_len=tokens.shape[1])``
        seq = x.shape[1]
        if model._sp_enabled():
            seq = seq * model.cfg.tensor_parallel_size
        cos, sin = model.rope_tables(seq)
        seed = None
        if dropout_seed is not None:
            lpc = jax.tree_util.tree_leaves(stage_params)[0].shape[0]
            seed = (jnp.asarray(dropout_seed, jnp.int32)
                    + jnp.asarray(info.microbatch, jnp.int32)
                    * jnp.int32(_SEED_MB_STRIDE)
                    + jnp.asarray(info.stage, jnp.int32) * jnp.int32(lpc)
                    * jnp.int32(_SEED_LAYER_STRIDE))

        def body(c, lp):
            h, a, sd = c
            out = call(lp, h, cos, sin, sd)
            if moe:
                y, la = out
                a = a + la.astype(a.dtype)
            else:
                y = out
            return (y, a,
                    None if sd is None else sd + _SEED_LAYER_STRIDE), None

        (y, a, _), _ = jax.lax.scan(body, (x, aux, seed), stage_params)
        return (y, a) if moe else y

    return stage_fn


def pipeline_step(model: GPTModel, params, tokens, targets, *,
                  pipe_axis: str = "pipe", data_axis: Optional[str] = None,
                  n_virtual: int = 1, remat: Optional[bool] = None,
                  dropout_seed=None):
    """GPT training step (loss AND grads) over the ring pipeline engine —
    call inside ``shard_map``.  Returns ``(loss, grads)`` with ``grads``
    matching ``params`` leaf-for-leaf.

    ``params["layers"]`` holds this device's stacked layers (leaves
    ``(layers_per_stage, ...)``, or ``(n_virtual, layers_per_stage, ...)``
    for the interleaved schedule, from :func:`stack_layers_for_pipeline`);
    embedding/final-LN params are replicated over the pipe axis.
    ``tokens``/``targets`` are ``(M, mb, s)`` local microbatches.

    Gradients are hand-rolled around
    :func:`~apex_tpu.transformer.pipeline_parallel.pipeline_schedule_step`
    rather than taken with ``jax.grad`` over the whole step — on the jax
    0.4.x span, differentiating through ``shard_map`` collectives is
    version-blocked (psum-transpose cotangent scaling, partial grads for
    replicated leaves).  The embedding runs once outside the scan under
    its own ``jax.vjp`` (flattened over microbatches — the lookup is
    per-token, so this is bitwise-identical to per-microbatch embeds) and
    its pullback consumes the engine's psum-reduced ``dx0``; the tied
    embedding weight's gradient is the sum of that pullback and the last
    stage's head contribution.  All cross-device combining is
    forward-mode psum/pmean of one-nonzero-plus-zeros or of identical
    replicas, so pp=1 runs of this same function are the bitwise f32
    reference for any (S, n_virtual).

    Composition: TP requires ``sequence_parallel=True`` (the Megatron SP
    mappings carry custom-VJP psum rules that fully reduce
    replicated-leaf grads *inside* the local vjp; the non-SP TP path
    relies on shard_map's auto-psum, which this engine never crosses).
    ``data_axis`` pmeans loss+grads; an MoE ``expert_axis`` composes via
    the :func:`~apex_tpu.transformer.expert_parallel.reduce_moe_grads`
    recipe (dense pmean, expert leaves divided by the axis size).
    """
    from apex_tpu.transformer.pipeline_parallel.ring import (
        pipeline_schedule_step)

    cfg = model.cfg
    if cfg.layer_pattern is not None:
        raise ValueError(
            "pipeline_step stacks identical layers per stage; a "
            "layer_pattern's layers differ from their neighbours")
    if cfg.weight_quant is not None:
        raise ValueError(
            f"weight_quant={cfg.weight_quant!r} is a decode/prefill-only "
            "knob: pipeline_step builds gradients, and int8 weights have "
            "none — train with weight_quant=None and let the inference "
            "engine quantize at init (quantize_decode_params)")
    if cfg.axis_name is not None and not cfg.sequence_parallel:
        raise ValueError(
            "pipeline_step under tensor parallelism requires "
            "sequence_parallel=True (non-SP TP grads need shard_map's "
            "auto-psum, which the hand-rolled pipeline backward bypasses)")
    moe = cfg.n_experts > 0
    n_mb, mb, seq = tokens.shape
    with_seed = (cfg.attention_dropout > 0.0 and dropout_seed is not None)

    # ---- embedding: one flattened-batch vjp outside the scan ----------
    embed_keys = ["embedding"] + ([] if cfg.rotary
                                  else ["position_embedding"])
    embed_params = {k: params[k] for k in embed_keys}

    def embed_fn(ep):
        x = model.embed(ep, tokens.reshape(n_mb * mb, seq))
        if model._sp_enabled():
            x = model._sp_scatter(x)
        return x.reshape((n_mb, mb) + x.shape[1:])

    x, embed_pull = jax.vjp(embed_fn, embed_params)
    x0 = (x, jnp.zeros((n_mb,), _f32)) if moe else x

    # ---- last stage: final LN + tied vocab-parallel head + CE ---------
    last_params = {"final_layernorm": params["final_layernorm"],
                   "embedding": params["embedding"]}

    def last_fn(lp, y, tgt, info):
        aux = None
        if moe:
            y, aux = y
        if model._sp_enabled():
            y = model._sp_gather(y)
        lm = jnp.mean(model.head_loss(lp, y, tgt))
        if moe:
            lm = lm + cfg.moe_aux_weight * aux / cfg.num_layers
        return lm

    loss, layer_grads, last_grads, dx0 = pipeline_schedule_step(
        make_stage_fn(model, dropout_seed if with_seed else None,
                      remat=remat),
        last_fn, params["layers"], last_params, x0, targets,
        axis_name=pipe_axis, n_virtual=n_virtual)

    # ---- embedding pullback (dx0 is psum-reduced and replicated over
    # the pipe axis, so every device computes the same grads) -----------
    dx = dx0[0] if moe else dx0      # the aux input is a constant zero
    (embed_grads,) = embed_pull(dx)
    grads = dict(embed_grads)
    grads["embedding"] = jax.tree_util.tree_map(
        jnp.add, grads["embedding"], last_grads["embedding"])
    grads["final_layernorm"] = last_grads["final_layernorm"]
    grads["layers"] = layer_grads

    if data_axis is not None:
        loss = jax.lax.pmean(loss, data_axis)
        grads = jax.lax.pmean(grads, data_axis)
    if moe and cfg.expert_axis is not None:
        # the expert axis doubles as a batch axis for the dense compute:
        # dense leaves pmean across it, expert-stack leaves are already
        # per-shard sums of the global batch (divide, don't reduce) —
        # the reduce_moe_grads recipe, applied here as forward ops
        from apex_tpu.transformer.expert_parallel import is_gpt_expert_leaf
        ep_n = jax.lax.axis_size(cfg.expert_axis)

        def red(path, g):
            if is_gpt_expert_leaf(path):
                return (g / ep_n).astype(g.dtype)
            return jax.lax.pmean(g, cfg.expert_axis)

        loss = jax.lax.pmean(loss, cfg.expert_axis)
        grads = jax.tree_util.tree_map_with_path(red, grads)
    return loss, grads
