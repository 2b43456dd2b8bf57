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

Activations are ``(batch, seq, hidden)``.  The mixers are in modules of
their own; a ``layer_pattern`` reaches them through :data:`LAYER_KINDS`.
"""

from __future__ import annotations

import collections
import contextlib
import dataclasses
from functools import partialmethod
from typing import Optional

import jax
import jax.numpy as jnp

# the benchmark's tests read _HEAD_NORM_EPS here
from apex_tpu.models.attention import (  # noqa: F401
    _HEAD_NORM_EPS, INIT_STD, LayerKind, ParallelAttention)
from apex_tpu.models.ffn import MoEFFN, ParallelBlock, ParallelMLP
from apex_tpu.models.latent import LatentAttention, _rms_norm
from apex_tpu.models.mamba2 import Mamba2Mixer
from apex_tpu.models.short_conv import GatedShortConv
from apex_tpu.normalization import MixedFusedLayerNorm, MixedFusedRMSNorm
from apex_tpu.ops.rope import rope_freqs
from apex_tpu.transformer import tensor_parallel as tp

_f32 = jnp.float32

# Dropout-stream strides: layer i / microbatch m walk the seed space at
# large odd strides (bijective mod 2^32, int32 wraparound is fine) so a
# caller advancing the base seed by +1 per training step can never land
# on a neighboring layer's or microbatch's stream from another step —
# with stride 1 ("seed + i"), step t+1 layer i would replay step t
# layer i+1's mask exactly.
_SEED_LAYER_STRIDE = 0x3C6EF35F
_SEED_MB_STRIDE = 0x5BD1E995


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
            LatentAttention.check(self, grouped,
                                  self.layer_pattern.count("*"))

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


def _head_init(key, shape, dtype=_f32):
    """A pattern's head matrix, its own or the embedding it is tied to:
    half the other matrices' deviation, so that unit-RMS rows through it
    give logits well under 1 and a first loss near ln(vocabulary)."""
    return 0.5 * INIT_STD * jax.random.normal(key, shape, dtype)


class ParallelTransformerLayer(LayerKind):
    """Pre-LN transformer block (apex ParallelTransformerLayer); the FFN
    slot is dense (ParallelMLP) or MoE (``cfg.n_experts > 0``).  Without a
    ``layer_pattern`` every layer is one, with every path."""

    def __init__(self, cfg: GPTConfig):
        self.cfg = cfg
        self.is_moe = cfg.n_experts > 0
        self.input_layernorm = _norm(cfg)
        self.post_attention_layernorm = _norm(cfg)
        self.attention = ParallelAttention(cfg)
        self.mlp = MoEFFN(cfg) if self.is_moe else ParallelMLP(cfg)
        self.caches = {0: (2, cfg.local_heads * cfg.head_dim)}

    def init_params(self, key):
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
                 dropout_seed=None):
        # named scopes land in HLO metadata -> visible in xprof traces
        # (the reference's nvtx range annotations, SURVEY §5)
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

    def _through(self, method, params, x, *args, **kw):
        """Each of the six cache paths below: the attention's ``method`` with
        the cache and ``args`` (see :class:`ParallelAttention`), then one
        residual, norm and FFN tail (MoE aux is discarded — load-balancing
        loss is a training concern).  Returns ``(x_out, *rest)``, ``rest``
        what the attention returns beside its output: prefill's post-RoPE
        ``(k, v)``, the cache, or decode_paged's pool (its layer ``at[0]``)
        and the selection as it came."""
        with jax.named_scope("attention"):
            h = self.input_layernorm(params["input_layernorm"], x)
            attn, *rest = getattr(self.attention, method)(
                params["attention"], h, *args, **kw)
            x = x + attn
        with jax.named_scope("mlp"):
            h = self.post_attention_layernorm(
                params["post_attention_layernorm"], x)
            y = self.mlp(params["mlp"], h)
            if self.is_moe:
                y, _ = y
            return (x + y, *rest)

    prefill = partialmethod(_through, "prefill_heads")
    decode = partialmethod(_through, "decode")
    decode_paged = partialmethod(_through, "decode_paged")
    decode_chunk = partialmethod(_through, "decode_chunk")
    decode_paged_quant = partialmethod(_through, "decode_paged_quant")
    decode_chunk_quant = partialmethod(_through, "decode_chunk_quant")


class MixerLayer(LayerKind):
    """A pattern's layer of one mixer, ``x + mixer(norm(x))`` billed to the
    mixer's scope.  ``kind`` is the mixer, and what the model asks of the
    layer its kind answers; a kind that caches nothing runs in a cache path
    as training runs it, and one that caches takes the mixer's params, the
    normed input and the cache, and returns ``(y, records, selection)`` (a
    record for each array of its ``caches``) or ``(y, pool, selection)``,
    ``selection`` what sparse attention attended under."""

    # where decode, decode_chunk and the int8 paths would go
    missing = ("a pattern's layers have prefill and the paged tick alone: no "
               "contiguous ring, no multi-query (chunk) attention over the "
               "pool's records (chunked prefill, speculative verification) "
               "and no int8 record (scales a block, part and head of K and V)")

    def __init__(self, cfg: GPTConfig, kind):
        self.kind, self.norm, self.eps = kind, _norm(cfg), cfg.norm_eps
        self.scope, self.routed, self.own_rotary, self.caches = (
            kind.scope, kind.routed, kind.own_rotary, kind.caches)

    def lacking(self, path):
        return super().lacking(path) or self.kind.lacking(path)

    def init_params(self, key):
        return {"norm": self.norm.init_params(),
                "mixer": self.kind.init_params(key)}

    def __call__(self, params, x, rope_cos=None, rope_sin=None,
                 dropout_seed=None, routing=None):
        with jax.named_scope(self.scope):
            h = self.norm(params["norm"], x)
            if self.routed:
                # ``routing``: what the router made of the input of the
                # layer below (cfg.moe_route_before_attention), or None
                y, load = self.kind(params["mixer"], h, routing)
                return x + y, load
            with (jax.named_scope(self.kind.part) if self.kind.part
                  else contextlib.nullcontext()):
                return x + self.kind(params["mixer"], h, rope_cos, rope_sin,
                                     dropout_seed)

    def _through(self, method, params, x, *args, selection=None):
        """Both cache paths: the kind's ``method`` with ``args`` (the rotary
        tables, or the pool and where to read and write it), or for a kind
        that caches nothing the kind as training runs it; returns
        ``(x_out, records or pool, selection)``.  A kind that asks
        (``norm32``) gets this layer's norm of ``x`` in float32, unrounded,
        as ``h32``."""
        with jax.named_scope(self.scope):
            h = self.norm(params["norm"], x)
            if not self.caches:
                y = self.kind(params["mixer"], h)
                # a prefill leaves no record; a tick hands the pool on
                return (x + (y[0] if self.routed else y),
                        () if method == "prefill" else args[0], selection)
            h32 = ({"h32": _rms_norm(x.astype(_f32), params["norm"]["weight"],
                                     self.eps)} if self.kind.norm32 else {})
            y, cached, selection = getattr(self.kind, method)(
                params["mixer"], h, *args, selection=selection, **h32)
            return x + y, cached, selection

    prefill = partialmethod(_through, "prefill")
    decode_paged = partialmethod(_through, "decode_paged")


def _attention(cfg, entries):
    """A ``*`` layer's kind: latent attention where ``cfg.kv_lora_rank``."""
    attention = next(entries["attention_types"])
    if cfg.kv_lora_rank:
        return LatentAttention(cfg, next(entries["indexer_types"]) == "full")
    return ParallelAttention(cfg, attention,
                             next(entries["rotary_layers"], None))


# One table from a pattern's symbol to its layer's kind (under the one-mixer
# layer, or "P" laying out its own), built from cfg and the layer's entries
# of indexer_types, attention_types and rotary_layers (``entries``: an
# iterator over each).  Past GPTConfig's checks, no other code reads one.
LAYER_KINDS = {
    "M": lambda cfg, entries: MixerLayer(cfg, Mamba2Mixer(cfg)),
    "C": lambda cfg, entries: MixerLayer(cfg, GatedShortConv(cfg)),
    "D": lambda cfg, entries: MixerLayer(
        cfg, ParallelMLP(cfg, cfg.dense_ffn_hidden_size)),
    "E": lambda cfg, entries: MixerLayer(cfg, MoEFFN(cfg)),
    "*": lambda cfg, entries: MixerLayer(cfg, _attention(cfg, entries)),
    "P": lambda cfg, entries: ParallelBlock(
        cfg, next(entries["attention_types"]), _norm(cfg)),
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
            entries = {name: iter(getattr(cfg, name) or ()) for name in (
                "indexer_types", "attention_types", "rotary_layers")}
            self.layers = [LAYER_KINDS[symbol](cfg, entries)
                           for symbol in cfg.layer_pattern]
        self.final_layernorm = _norm(cfg)
        # the leaf that holds the output head's matrix
        self.head = "embedding" if cfg.tie_head else "lm_head"
        # each layer's place in the cache, by its kind's ``caches``: the
        # store it reads and writes (the pool, or the window layers'
        # storage) and its index in each array of that store
        seen, self._places = collections.Counter(), []
        for layer in self.layers:
            self._places.append(("window" if "window" in layer.caches
                                 else "pool", seen.copy()))
            seen.update(layer.caches.keys())
        records = {array: record for layer in self.layers
                   for array, record in layer.caches.items()}
        # (layers, *record) of each array, the pool's in order, then the
        # window layers' storage
        self._records = {array: (seen[array], *records[array])
                         for array in sorted(records, key=str)}

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
        if not self.cfg.rotary or any(layer.own_rotary
                                      for layer in self.layers):
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
        self._check_paths("training forward", "training forward")
        aux_total = jnp.zeros((), _f32)
        loads = []              # sorted dispatch: tokens per held expert
        routing = ()            # the next "E" layer's, routed ahead
        for li, (layer, lp) in enumerate(zip(self.layers,
                                             params["layers"])):
            seed = (None if dropout_seed is None
                    else dropout_seed + li * _SEED_LAYER_STRIDE)
            above = self.layers[li + 1:li + 2]
            if (self.cfg.moe_route_before_attention and above
                    and above[0].routed):
                # the expert layer above routes on this layer's input,
                # before attention, and its work is the experts' (XLA is
                # free to run it beside attention)
                with jax.named_scope("mlp"), jax.named_scope("moe.router"):
                    routing = (above[0].kind.route(
                        params["layers"][li + 1]["mixer"], x),)
            extra, routing = ((routing, ()) if layer.routed
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
            if not isinstance(out, tuple):      # a layer with no experts
                x = out
            elif self.cfg.moe_router != "softmax":
                x, load = out
                loads.append(load)
            else:
                x, aux = out
                aux_total = aux_total + aux
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

    def _check_paths(self, name, *paths):
        """Raise ``NotImplementedError`` naming ``name`` where some layer
        lacks one of ``paths``, with what it would need (its ``lacking``)."""
        lacking = dict.fromkeys(filter(None, (
            layer.lacking(path) for path in paths for layer in self.layers)))
        if lacking:
            raise NotImplementedError(
                f"{name} is not implemented for layer_pattern "
                f"{self.cfg.layer_pattern!r}: " + "; ".join(lacking))

    def _check_decode_supported(self, path=None):
        """Raise unless the model can be served through ``prefill`` and
        ``decode_step_paged`` (the paths the paged engine's default
        configuration runs) and, with ``path``, through that path too."""
        self._check_paths(path or "serving", *dict.fromkeys(
            ("prefill", "decode_step_paged") + ((path,) if path else ())))
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
        self._check_decode_supported("prefill")
        with jax.named_scope("embeddings"):
            x = self.embed(params, tokens)
        cos, sin = self.rope_tables(tokens.shape[1])
        if self.cfg.layer_pattern is not None:
            # for each of the pool's arrays (and the window layers' storage
            # after them), one entry a layer that writes it: (layers, parts,
            # b, s, kv_heads, head_dim)
            arrays = {array: [] for array in self._records}
            selection = None
            for layer, lp in zip(self.layers, params["layers"]):
                x, records, selection = layer.prefill(
                    lp, x, cos, sin, selection=selection)
                for array, record in zip(layer.caches, records):
                    arrays[array].append(record)
            with jax.named_scope("lm_head"):
                # accumulated in float32, kept in the compute dtype: the
                # engine reads one row of (1, bucket, vocab), 1.3 GB in
                # float32 at a bucket of 16 384 rows of 19 360
                logits = self.logits(params, x).astype(self.cfg.dtype)
            records = tuple(jnp.stack(a) for a in arrays.values())
            return logits, records[0] if len(records) == 1 else records
        ks, vs = [], []
        for layer, lp in zip(self.layers, params["layers"]):
            x, (k, v) = layer.prefill(lp, x, cos, sin)
            ks.append(k)
            vs.append(v)
        kv = jnp.stack([jnp.stack(ks), jnp.stack(vs)], axis=1)
        with jax.named_scope("lm_head"):
            return self.logits(params, x), kv

    def _embed_at(self, params, tokens, positions):
        """A cache path's embedding of ``tokens`` at ``positions``, both
        ``(slots,)`` (``x`` ``(slots, 1, hidden)``) or ``(slots, chunk)``."""
        one = tokens.ndim == 1
        with jax.named_scope("embeddings"):
            x = self.embedding(params["embedding"],
                               tokens[:, None] if one else tokens)
            if not self.cfg.rotary:
                pe = params["position_embedding"][positions]
                x = x + (pe[:, None] if one else pe)
            return x.astype(self.cfg.dtype)

    def _token_logits(self, params, x):
        """The head of a step of one token a slot: ``(slots, vocab)``."""
        with jax.named_scope("lm_head"):
            x = self.final_layernorm(params["final_layernorm"], x)
            return self._head_logits(params, x[:, 0], "bh,vh->bv")

    def cache_record(self):
        """What the model caches, for :class:`~apex_tpu.serving.
        PagedKVCache`: one ``(layers, parts, width[, dtype])`` for each array
        of the pool, a position's record in one of ``layers`` being ``parts``
        rows of ``width`` numbers in the cache's dtype (or the dtype named),
        as the layers say (their ``caches``)."""
        return tuple(record for array, record in self._records.items()
                     if array != "window")

    def window_record(self):
        """``(layers, parts, width, window)`` of the window layers' storage,
        of which a sequence holds no more than its last ``window`` positions
        (:class:`~apex_tpu.serving.PagedKVCache`'s window storage), or None
        for a model without window layers."""
        return self._records.get("window")

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
        x = self._embed_at(params, tokens, positions)
        for li, (layer, lp) in enumerate(zip(self.layers,
                                             params["layers"])):
            x, cache = layer.decode(lp, x, cache, li, positions)
        logits = self._token_logits(params, x)
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
        self._check_decode_supported("decode_step_paged")
        x = self._embed_at(params, tokens, positions)
        # the pool and the window layers' storage, each with its tables
        windowed = self.window_record() is not None
        pools = dict(zip(("pool", "window"), pool if windowed else (pool,)))
        tables = dict(zip(("pool", "window"),
                          block_tables if windowed else (block_tables,)))
        selection = None
        for layer, lp, (store, at) in zip(self.layers, params["layers"],
                                          self._places):
            x, pools[store], selection = layer.decode_paged(
                lp, x, pools[store], at, tables[store], positions,
                selection=selection)
        logits = self._token_logits(params, x)
        return logits, tuple(pools.values()) if windowed else pools["pool"]

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
        x = self._embed_at(params, tokens, positions)
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
        x = self._embed_at(params, tokens, positions)
        for li, (layer, lp) in enumerate(zip(self.layers,
                                             params["layers"])):
            x, pool, scales = layer.decode_paged_quant(
                lp, x, pool, scales, li, block_tables, positions)
        logits = self._token_logits(params, x)
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
        x = self._embed_at(params, tokens, positions)
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
        return {name: col(w) for name, w in g.items()}

    def rowg(g):     # Row group: weight column-sharded; bias and the
        # per-OUTPUT-row scales are replicated (the scale dim is not
        # the sharded dim)
        return {name: row(w) if name == "weight" else w
                for name, w in g.items()}

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
        # qkv, proj, fc1 and fc2: every group of the attention and the FFN
        out["layers"].append({**lp, **{
            part: {name: q(g) for name, g in lp[part].items()}
            for part in ("attention", "mlp")}})
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
