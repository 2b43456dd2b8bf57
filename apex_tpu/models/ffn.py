"""The feed-forward kinds of :mod:`apex_tpu.models.gpt`: the dense FFN (the
plain block's, a shared expert, a pattern's ``D`` layer), the expert FFN
(the plain block's MoE, a pattern's ``E`` layer), and the parallel block
(``P``), experts beside attention."""

from __future__ import annotations

from functools import partialmethod

import jax
import jax.numpy as jnp

from apex_tpu.models.attention import (INIT_STD, LayerKind,
                                       ParallelAttention, _out_init)
from apex_tpu.ops.fused_ffn import fused_ffn_tp
from apex_tpu.transformer import tensor_parallel as tp


class ParallelMLP(LayerKind):
    """Column→GELU→Row block (apex ParallelMLP).  Gated (``swiglu``,
    ``reglu``): ``fc1`` is ``[gate | up]`` side by side, one product of
    twice the width, and ``fc2`` takes ``silu(gate) * up`` or ``relu(gate)
    * up``.  A kind that caches nothing, billed to ``mlp``."""

    scope = "mlp"

    def __init__(self, cfg, ffn_hidden_size=None):
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

    def __call__(self, params, x, *_):
        # (a layer's rotary tables and dropout seed are not an FFN's)
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


class MoEFFN(LayerKind):
    """Switch/GShard FFN in the layer slot (beyond-reference; Megatron's
    MoE lives outside apex).  Flattens ``(b, s, h)`` to tokens for
    :class:`apex_tpu.transformer.expert_parallel.MoEMLP` and returns
    ``(y, aux_loss)``.  A kind that caches nothing, billed to ``mlp``."""

    scope = "mlp"
    routed = True       # a pattern's "E": takes the routing made below

    def __init__(self, cfg):
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


class ParallelBlock(LayerKind):
    """The ``P`` layer of a ``layer_pattern``: attention and experts side by
    side, ``x + attention(h) + experts(h)`` with ``h = norm(x)`` once, billed
    to ``attention`` (attention's part to ``attention.window`` or
    ``attention.full`` inside it) and to ``mlp``; params ``{norm, attention,
    mlp}``.  It lays out its own layer, the one residual of two mixers.
    ``attention``: its entry of cfg.attention_types, a window layer's K and
    V kept in the window layers' storage."""

    own_rotary = True
    # where __call__, decode, decode_chunk and the int8 paths would go
    missing = (
        "the parallel block ('P') has cache paths only (prefill, "
        "decode_step_paged): the training kernels take a sliding window and "
        "K and V shared by a query group (grouped_flash), but the block's "
        "training forward is not written (one norm feeding attention and the "
        "experts, rotary on adjacent pairs of the rows, the shared experts "
        "averaged with the routed sum), and there is no contiguous ring, no "
        "chunk attention over the pool and no int8 record")

    def __init__(self, cfg, attention, norm):
        self.norm, self.experts = norm, MoEFFN(cfg)
        # only window layers rotate (on ADJACENT pairs of a head's lanes);
        # a parallel block's full layers carry no position code
        self.attention = ParallelAttention(
            cfg, attention, cfg.rotary and attention == "sliding")
        self.caches = self.attention.caches
        self.part = ("attention.window" if self.attention.window
                     else "attention.full")

    def init_params(self, key):
        k1, k2 = jax.random.split(key)
        return {"norm": self.norm.init_params(),
                "attention": self.attention.init_params(k1),
                "mlp": self.experts.init_params(k2)}

    def _through(self, method, params, x, *args, selection=None):
        """Both cache paths: the attention's ``method`` with ``args`` (a
        prefill's tables, which it does not read, or the pool and where to
        read and write it) beside the experts; returns ``(x_out, records or
        pool, selection)``, the selection as it came."""
        with jax.named_scope("attention"):
            h = self.norm(params["norm"], x)
            with jax.named_scope(self.part):
                y, cached = getattr(self.attention, method)(
                    params["attention"], h, *args)
        with jax.named_scope("mlp"):
            z, _ = self.experts(params["mlp"], h)
        return x + y + z, cached, selection

    prefill = partialmethod(_through, "prefill_rows")
    decode_paged = partialmethod(_through, "decode_paged_rows")
