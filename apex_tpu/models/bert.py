"""BERT model family — the FusedLAMB/amp-O2 recipe workload (reference:
apex's MLPerf-BERT lineage: ``apex/contrib/fmha`` kernels are built for
BERT seq<=512, ``DistributedFusedLAMB`` exists for BERT-large pretrain,
and BASELINE workload 2 is "BERT-large pretrain, FusedLAMB +
FusedLayerNorm + amp O2").

Same component wiring as the GPT flagship — VocabParallelEmbedding,
Column/RowParallelLinear, MixedFusedLayerNorm (Pallas), flash attention
(non-causal, padding via ``kv_seqlens``), vocab-parallel cross entropy —
in the encoder arrangement: learned position + segment embeddings,
post-LN blocks, MLM head with tied decoder + NSP pooler head.

Masked-LM convention: ``mlm_labels`` holds the original token id at
masked positions and ``-1`` everywhere else (apex/Megatron's
``labels``/``loss_mask`` pair collapsed into one array).
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from apex_tpu.models.gpt import _remat_policy
from apex_tpu.normalization import MixedFusedLayerNorm
from apex_tpu.ops.flash_attention import flash_attention_bshd
from apex_tpu.ops.fused_ffn import fused_ffn_tp
from apex_tpu.transformer import tensor_parallel as tp

_f32 = jnp.float32


@dataclasses.dataclass
class BertConfig:
    vocab_size: int = 30528                    # MLPerf padded vocab
    hidden_size: int = 1024                    # BERT-large
    num_layers: int = 24
    num_attention_heads: int = 16
    max_seq_len: int = 512
    type_vocab_size: int = 2
    fused_lm_head: bool = True                 # logit-free blockwise CE
    ffn_hidden_size: Optional[int] = None      # default 4*hidden
    tensor_parallel_size: int = 1
    axis_name: Optional[str] = None
    sequence_parallel: bool = False
    overlap_chunks: int = 0                    # >0: ppermute-ring TP GEMMs
    fused_ffn: bool = False                    # Pallas fused bias-GELU FFN
    remat: bool = False
    remat_policy: str = "full"                 # "full" | "dots" (selective)
    dtype: jnp.dtype = jnp.float32
    param_dtype: jnp.dtype = jnp.float32
    # one validated ParallelPlan instead of the per-knob kwargs above
    # (see GPTConfig.plan — same supersede-with-warning semantics)
    plan: Optional[object] = None

    def __post_init__(self):
        if self.plan is not None:
            from apex_tpu.parallel.plan import apply_plan_to_config
            apply_plan_to_config(self)
        if self.ffn_hidden_size is None:
            self.ffn_hidden_size = 4 * self.hidden_size
        if self.remat_policy not in ("full", "dots"):
            raise ValueError(
                f"remat_policy must be 'full' or 'dots', got "
                f"{self.remat_policy!r}")
        if self.hidden_size % self.num_attention_heads:
            raise ValueError(
                "hidden_size must be divisible by num_attention_heads")
        if self.num_attention_heads % self.tensor_parallel_size:
            raise ValueError("num_attention_heads must be divisible by "
                             "tensor_parallel_size")
        if self.overlap_chunks < 0:
            raise ValueError(
                f"overlap_chunks must be >= 0, got {self.overlap_chunks}")
        if self.overlap_chunks > 0 and not self.sequence_parallel:
            raise ValueError(
                "overlap_chunks rings the sequence-parallel collective/GEMM "
                "pairs; it requires sequence_parallel=True")

    @property
    def head_dim(self):
        return self.hidden_size // self.num_attention_heads


class BertSelfAttention:
    """Bidirectional self-attention; padding handled by the flash
    kernel's ``kv_seqlens`` (the reference fmha's cu_seqlens packing)."""

    def __init__(self, cfg: BertConfig):
        self.cfg = cfg
        self.qkv = tp.ColumnParallelLinear(
            cfg.hidden_size, 3 * cfg.hidden_size, gather_output=False,
            world_size=cfg.tensor_parallel_size, axis_name=cfg.axis_name,
            sequence_parallel_enabled=cfg.sequence_parallel,
            seq_dim=1, overlap_chunks=cfg.overlap_chunks,
            param_dtype=cfg.param_dtype)
        self.proj = tp.RowParallelLinear(
            cfg.hidden_size, cfg.hidden_size, input_is_parallel=True,
            world_size=cfg.tensor_parallel_size, axis_name=cfg.axis_name,
            sequence_parallel_enabled=cfg.sequence_parallel,
            seq_dim=1, overlap_chunks=cfg.overlap_chunks,
            param_dtype=cfg.param_dtype)

    def init_params(self, key):
        k1, k2 = jax.random.split(key)
        return {"qkv": self.qkv.init_params(k1),
                "proj": self.proj.init_params(k2)}

    def _thirds_apart(self, qkv_params):
        """The fused projection's output features, stored a head at a
        time as ``[q | k | v]`` (Megatron's order), regrouped to
        ``[Q | K | V]``: every number of the product is the one it was,
        and q, k and v leave the matmul as three column ranges whose rows
        the attention kernel reads as they lie.  Splitting the
        activations instead asks XLA for ``(b, s, nh, 64)`` arrays, which
        it lays out with the sequence in the lanes: a transposing copy of
        ``(b, s, 3h)`` and of each third, forward and backward, where
        this moves the weight, a sixteenth of their bytes."""
        hd = self.cfg.head_dim
        return {name: w.reshape(-1, 3, hd, *w.shape[1:]).swapaxes(0, 1)
                .reshape(w.shape) for name, w in qkv_params.items()}

    def __call__(self, params, x, seqlens=None):
        cfg = self.cfg
        b = x.shape[0]
        qkv, _ = self.qkv(self._thirds_apart(params["qkv"]), x)
        s = qkv.shape[1]
        q, k, v = (t.reshape(b, s, -1, cfg.head_dim)
                   for t in jnp.split(qkv, 3, axis=-1))
        ctx = flash_attention_bshd(q, k, v, causal=False,
                                   kv_seqlens=seqlens)
        out, _ = self.proj(params["proj"], ctx.reshape(b, s, -1))
        return out


class BertLayer:
    """Post-LN block (original BERT arrangement: residual→LN)."""

    def __init__(self, cfg: BertConfig):
        self.cfg = cfg
        self.attention = BertSelfAttention(cfg)
        self.attention_layernorm = MixedFusedLayerNorm(cfg.hidden_size)
        self.fc1 = tp.ColumnParallelLinear(
            cfg.hidden_size, cfg.ffn_hidden_size, gather_output=False,
            world_size=cfg.tensor_parallel_size, axis_name=cfg.axis_name,
            sequence_parallel_enabled=cfg.sequence_parallel,
            seq_dim=1, overlap_chunks=cfg.overlap_chunks,
            param_dtype=cfg.param_dtype)
        self.fc2 = tp.RowParallelLinear(
            cfg.ffn_hidden_size, cfg.hidden_size, input_is_parallel=True,
            world_size=cfg.tensor_parallel_size, axis_name=cfg.axis_name,
            sequence_parallel_enabled=cfg.sequence_parallel,
            seq_dim=1, overlap_chunks=cfg.overlap_chunks,
            param_dtype=cfg.param_dtype)
        self.output_layernorm = MixedFusedLayerNorm(cfg.hidden_size)

    def init_params(self, key):
        k1, k2, k3 = jax.random.split(key, 3)
        return {"attention": self.attention.init_params(k1),
                "attention_layernorm":
                    self.attention_layernorm.init_params(),
                "fc1": self.fc1.init_params(k2),
                "fc2": self.fc2.init_params(k3),
                "output_layernorm": self.output_layernorm.init_params()}

    def _sp_ln_params(self, params, name):
        """Under SP the per-layer LNs run on the sequence shard, so their
        (replicated) params see per-shard partial grads; identity-fwd/
        psum-bwd restores the total (Megatron's SP grad allreduce)."""
        p = params[name]
        if self.cfg.sequence_parallel and self.cfg.axis_name is not None:
            p = tp.copy_to_tensor_model_parallel_region(
                p, self.cfg.axis_name)
        return p

    def __call__(self, params, x, seqlens=None):
        # every operation of a layer belongs to one of two scopes: each
        # sublayer's residual add and LayerNorm close it
        with jax.named_scope("attention"):
            h = self.attention(params["attention"], x, seqlens)
            x = self.attention_layernorm(
                self._sp_ln_params(params, "attention_layernorm"), x + h)
        with jax.named_scope("mlp"):
            return self._mlp(params, x)

    def _mlp(self, params, x):
        cfg = self.cfg
        if cfg.fused_ffn:
            # Pallas fused GEMM+bias+GELU+GEMM with the same TP/SP edge
            # collectives the unfused fc1/fc2 pair uses
            h = fused_ffn_tp(
                x, params["fc1"]["weight"], params["fc1"]["bias"],
                params["fc2"]["weight"], params["fc2"]["bias"],
                tensor_parallel_size=cfg.tensor_parallel_size,
                axis_name=cfg.axis_name,
                sequence_parallel=cfg.sequence_parallel, seq_dim=1)
        else:
            h, _ = self.fc1(params["fc1"], x)
            h = jax.nn.gelu(h, approximate=True)
            h, _ = self.fc2(params["fc2"], h)
        return self.output_layernorm(
            self._sp_ln_params(params, "output_layernorm"), x + h)


class BertModel:
    """Encoder + MLM/NSP heads.

    ``apply(params, tokens, token_type_ids=None, seqlens=None)`` returns
    the final hidden states; ``loss`` computes MLM (+ optional NSP) with
    vocab-parallel cross entropy over the tied decoder.
    """

    def __init__(self, cfg: BertConfig):
        self.cfg = cfg
        self.embedding = tp.VocabParallelEmbedding(
            cfg.vocab_size, cfg.hidden_size,
            world_size=cfg.tensor_parallel_size, axis_name=cfg.axis_name,
            param_dtype=cfg.param_dtype)
        self.embedding_layernorm = MixedFusedLayerNorm(cfg.hidden_size)
        self.layers = [BertLayer(cfg) for _ in range(cfg.num_layers)]
        self.mlm_layernorm = MixedFusedLayerNorm(cfg.hidden_size)

    def init_params(self, key):
        keys = jax.random.split(key, self.cfg.num_layers + 4)
        cfg = self.cfg
        init = lambda k, *s: 0.02 * jax.random.normal(k, s, cfg.param_dtype)
        return {
            "embedding": self.embedding.init_params(keys[0]),
            "position_embedding": init(keys[1], cfg.max_seq_len,
                                       cfg.hidden_size),
            "token_type_embedding": init(keys[2], cfg.type_vocab_size,
                                         cfg.hidden_size),
            "embedding_layernorm": self.embedding_layernorm.init_params(),
            "layers": [l.init_params(k)
                       for l, k in zip(self.layers, keys[3:-1])],
            "mlm_transform": {
                "weight": init(keys[-1], cfg.hidden_size, cfg.hidden_size),
                "bias": jnp.zeros((cfg.hidden_size,), cfg.param_dtype)},
            "mlm_layernorm": self.mlm_layernorm.init_params(),
            "nsp_head": {
                "weight": jnp.zeros((cfg.hidden_size, 2), cfg.param_dtype),
                "bias": jnp.zeros((2,), cfg.param_dtype)},
        }

    @jax.named_scope("embeddings")
    def _embed(self, params, tokens, token_type_ids):
        x = self.embedding(params["embedding"], tokens)
        x = x + params["position_embedding"][:tokens.shape[1]]
        if token_type_ids is None:
            x = x + params["token_type_embedding"][0]
        else:
            x = x + jnp.take(params["token_type_embedding"],
                             token_type_ids, axis=0)
        x = self.embedding_layernorm(params["embedding_layernorm"], x)
        return x.astype(self.cfg.dtype)

    def apply(self, params, tokens, token_type_ids=None, seqlens=None):
        cfg = self.cfg
        x = self._embed(params, tokens, token_type_ids)
        sp = cfg.sequence_parallel and cfg.axis_name is not None
        if sp:
            # Megatron SP: the per-layer LNs and residuals run on
            # (b, s/t, h); each block's TP edges gather/reduce-scatter
            if tokens.shape[1] % cfg.tensor_parallel_size:
                raise ValueError(
                    f"sequence_parallel requires seq_len divisible by "
                    f"tensor_parallel_size ({tokens.shape[1]} % "
                    f"{cfg.tensor_parallel_size} != 0)")
            x = tp.scatter_to_sequence_parallel_region(x, cfg.axis_name, 1)
        for layer, lp in zip(self.layers, params["layers"]):
            if cfg.remat:
                x = jax.checkpoint(
                    lambda lp, x, sl, _l=layer: _l(lp, x, sl),
                    policy=_remat_policy(cfg.remat_policy))(
                        lp, x, seqlens)
            else:
                x = layer(lp, x, seqlens)
        if sp:
            x = tp.gather_from_sequence_parallel_region(x, cfg.axis_name, 1)
        return x

    __call__ = apply

    def _mlm_transform(self, params, hidden):
        """Transform + GELU + LN before the tied decoder.

        Under SP the vocab-parallel CE backward delivers per-vocab-shard
        partial cotangents here, so the replicated transform/LN params
        need an identity-fwd/psum-bwd wrap (see BertLayer._sp_ln_params).
        """
        mt, ln = params["mlm_transform"], params["mlm_layernorm"]
        if (self.cfg.sequence_parallel
                and self.cfg.axis_name is not None):
            mt = tp.copy_to_tensor_model_parallel_region(
                mt, self.cfg.axis_name)
            ln = tp.copy_to_tensor_model_parallel_region(
                ln, self.cfg.axis_name)
        h = (hidden.astype(_f32)
             @ mt["weight"].astype(_f32)
             + mt["bias"].astype(_f32))
        h = jax.nn.gelu(h, approximate=True)
        return self.mlm_layernorm(ln, h)

    def mlm_logits(self, params, hidden):
        """Tied-decoder vocab(-parallel) logits ``(b, s, vocab/t)``."""
        h = self._mlm_transform(params, hidden)
        w = params["embedding"]["weight"]
        return jnp.einsum("bsh,vh->bsv", h.astype(_f32), w.astype(_f32))

    @jax.named_scope("mlm_head")
    def _mlm_loss(self, params, hidden, mlm_labels):
        """Transform, tied decoder and cross entropy: the mean loss over
        the masked positions."""
        b, s = mlm_labels.shape
        mask = (mlm_labels >= 0)
        safe = jnp.where(mask, mlm_labels, 0)
        if self.cfg.axis_name is None and self.cfg.fused_lm_head:
            # logit-free tied decoder: the (b*s, vocab) logits never
            # materialize (see ops/lm_head.py; the masked positions'
            # losses are computed on target 0 and masked out below)
            from apex_tpu.ops.lm_head import fused_linear_cross_entropy
            h = self._mlm_transform(params, hidden)
            # compute-dtype operands: the kernel dots at operand
            # precision (see GPTModel.head_loss) — under O2 the tied
            # embedding is bf16 already and h comes out of the f32 LN
            per = fused_linear_cross_entropy(
                h.reshape(b * s, h.shape[-1]).astype(self.cfg.dtype),
                params["embedding"]["weight"].astype(self.cfg.dtype),
                safe.reshape(b * s)).reshape(b, s)
        else:
            logits = self.mlm_logits(params, hidden)
            vl = logits.shape[-1]
            per = tp.vocab_parallel_cross_entropy(
                logits.reshape(b * s, vl), safe.reshape(b * s),
                axis_name=self.cfg.axis_name).reshape(b, s)
        denom = jnp.maximum(jnp.sum(mask), 1)
        return jnp.sum(jnp.where(mask, per, 0.0)) / denom

    def loss(self, params, tokens, mlm_labels, token_type_ids=None,
             seqlens=None, nsp_labels=None):
        """Mean MLM loss over masked positions (+ NSP when labels given).

        ``mlm_labels``: original ids at masked positions, -1 elsewhere.
        """
        hidden = self.apply(params, tokens, token_type_ids, seqlens)
        loss = self._mlm_loss(params, hidden, mlm_labels)
        if nsp_labels is not None:
            pooled = jnp.tanh(hidden[:, 0].astype(_f32))
            nsp = (pooled @ params["nsp_head"]["weight"].astype(_f32)
                   + params["nsp_head"]["bias"].astype(_f32))
            logp = jax.nn.log_softmax(nsp)
            loss = loss - jnp.mean(
                jnp.take_along_axis(logp, nsp_labels[:, None], 1))
        return loss

    # -- GSPMD form ---------------------------------------------------------

    def partition_specs(self):
        """PartitionSpecs for jitting the serial form under GSPMD (same
        contract as :meth:`GPTModel.partition_specs`)."""
        from jax.sharding import PartitionSpec as P
        l0 = self.layers[0]
        ln = {"weight": P(), "bias": P()}
        layer_spec = {
            "attention": {"qkv": l0.attention.qkv.partition_spec(),
                          "proj": l0.attention.proj.partition_spec()},
            "attention_layernorm": ln,
            "fc1": l0.fc1.partition_spec(),
            "fc2": l0.fc2.partition_spec(),
            "output_layernorm": ln,
        }
        return {
            "embedding": self.embedding.partition_spec(),
            "position_embedding": P(),
            "token_type_embedding": P(),
            "embedding_layernorm": ln,
            "layers": [layer_spec] * self.cfg.num_layers,
            "mlm_transform": {"weight": P(), "bias": P()},
            "mlm_layernorm": ln,
            "nsp_head": {"weight": P(), "bias": P()},
        }
