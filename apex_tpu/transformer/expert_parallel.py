"""Expert parallelism: Switch-style MoE FFN over an ``expert`` mesh axis
— BEYOND-REFERENCE (SURVEY §2.3: MoE/expert parallelism is NOT in apex;
it lives in Megatron-LM proper.  Built here because EP is a first-class
sharding axis for a complete TPU framework).

Design (the standard TPU MoE dataflow, cf. Switch Transformer / GShard):
every device holds ``n_experts / ep`` expert FFNs and a shard of the
token batch.  Per device: top-k gate (``top_k=1`` Switch with raw top-1
prob, ``top_k=2`` GShard with gates renormalized over the selected
pair) → capacity-bounded dispatch into an ``(n_experts, capacity,
hidden)`` buffer (second choices claim slots after all first choices) →
``all_to_all`` over the expert axis (tokens travel to the device owning
their expert) → batched expert FFN (one einsum over the local expert
stack — MXU-friendly, no ragged loops) → inverse ``all_to_all`` →
gate-weighted combine over the k choices.  Tokens over capacity are
dropped (contribute zero), exactly like the references.

``axis_name=None`` runs the identical math single-device (the serial
golden for tests).  The auxiliary output is the Switch load-balancing
loss (mean fraction·probability product, scaled by ``n_experts``).

MoE composes with tensor parallelism (``tensor_axis``/
``tensor_parallel_size``): each expert's FFN inner dim is sharded over
the tensor axis with the Megatron Column→Row collective pairing
(identity/psum at entry, psum/identity at exit — the same
``mappings`` the dense ``ParallelMLP`` uses), so an expert runs as a
Column-parallel ``w1`` einsum → ReLU → Row-parallel ``w2`` einsum.
The expert axis (all_to_all over tokens) and the tensor axis (psum
over the FFN reduction) are independent mesh axes and compose
orthogonally: the all_to_all moves ``(…, hidden)`` buffers whose
hidden dim is never sharded.

``router="sigmoid"`` is the second router, the one today's large expert
models use: sigmoid scores, the top ``k`` of score + a per-expert bias,
the chosen scores renormalised and scaled, no capacity and no dropped
token.  ``router="softmax_sorted"`` takes the same dispatch with softmax
scores: the top ``k`` logits, weighted by the softmax over the chosen (the
softmax over every expert, renormalised).  Their experts are ``relu``,
``relu2`` or gated (``swiglu``, ``reglu``: ``w1`` holds ``[gate | up]``
side by side, one grouped product of twice the width, and ``w2`` takes
``silu(gate) * up`` or ``relu(gate) * up``).  The routing may be computed
elsewhere and handed in (:meth:`MoEMLP.route`).  The dispatch sorts the
(token, choice) pairs by expert and multiplies by group
(``jax.lax.ragged_dot`` over the expert stack, on tiles read from each
product's shape: :func:`_grouped_tiling`), so the shapes are static
and the result exact under any imbalance.  The
layer is told which experts it holds (``held = (offset, count)``): it
routes over all ``n_experts`` and computes the part of the result that
its own experts give, which is one expert-parallel rank's work without
its exchange.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental.xla_metadata import set_xla_metadata

from apex_tpu.utils.collectives import ensure_varying, vma_tracked

__all__ = ["MoEConfig", "MoEMLP", "is_gpt_expert_leaf",
           "localize_expert_params", "reduce_moe_grads",
           "vary_params_over_axis"]

_f32 = jnp.float32


# -- the grouped products -----------------------------------------------------
#
# On the TPU ``jax.lax.ragged_dot`` is a kernel of XLA's own, tiled
# ``tm, tk, tn`` by the largest of 512, 256 and 128 that divides each
# dimension.  A width that only 128 divides (hidden 2688 = 21 x 128) gets
# tiles of 128, and so do all three products of an expert layer that has one
# on either side (one v5e, the hybrid's expert layer at 6 144 rows: its
# forward, recompute and backward in 21.7 ms on XLA's tiles, 6.7 ms on
# 512 x 896 x 640 with the expert width padded from 1 856 to 1 920).  XLA
# takes another tiling from the ``ragged_dot_tiling`` attribute; autodiff
# would copy the forward's onto its transposes, whose ``k`` and ``n``
# differ, so each of the three products is given its own.

# the weight gradient: the rows' and the cotangent's ragged row dimension
# contracted per group, ``(g, k, n)``
_WEIGHT_GRAD = jax.lax.RaggedDotDimensionNumbers(
    dot_dimension_numbers=(([0], [0]), ([], [])),
    lhs_ragged_dimensions=[0], rhs_group_dimensions=[])


def _grouped_tiling(m, k, n):
    """``"tm,tk,tn"`` for a grouped product of ``m`` ragged rows, ``k`` and
    ``n`` (a forward's contraction and width; a weight gradient's two
    widths), or None where XLA's own tiles are already the largest that fit.

    A dimension that 128 divides and 256 does not takes the largest multiple
    of 128 up to 1024 that divides it (896 for 2688, 640 for 1920); any
    other keeps XLA's tile, and ``tm`` XLA's (the largest power of two up to
    512 that divides ``m``)."""
    def own(d):
        if d % 128 or not d % 256:
            return None
        return max(t for t in range(128, 1025, 128) if not d % t)

    def xla(d):
        return next((t for t in (512, 256) if not d % t), 128)

    tk, tn = own(k), own(n)
    if tk is None and tn is None:
        return None
    return f"{min(m & -m, 512)},{tk or xla(k)},{tn or xla(n)}"


def _ragged(lhs, rhs, sizes, tiling, dims=None):
    with set_xla_metadata(ragged_dot_tiling=tiling):
        if dims is None:
            return jax.lax.ragged_dot(lhs, rhs, sizes,
                                      preferred_element_type=_f32)
        return jax.lax.ragged_dot_general(lhs, rhs, sizes, dims,
                                          preferred_element_type=_f32)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _tiled_grouped_dot(rows, w, sizes, k_minor):
    return _ragged(rows, w, sizes, _grouped_tiling(*rows.shape, w.shape[2]))


def _tiled_fwd(rows, w, sizes, k_minor):
    return _tiled_grouped_dot(rows, w, sizes, k_minor), (rows, w, sizes)


def _tiled_bwd(k_minor, res, dy):
    rows, w, sizes = res
    (m, k), n = rows.shape, w.shape[2]
    # the chip's kernel rounds a float32 operand to the weights' bf16 (the
    # gradients are the same bit for bit): hand it the half-width cotangent
    dy = dy.astype(rows.dtype)
    drows = _ragged(dy, jnp.swapaxes(w, 1, 2), sizes, _grouped_tiling(m, n, k))
    if k_minor:
        dw = jnp.swapaxes(_ragged(dy, rows, sizes, _grouped_tiling(m, n, k),
                                  _WEIGHT_GRAD), 1, 2)
    else:
        dw = _ragged(rows, dy, sizes, _grouped_tiling(m, k, n), _WEIGHT_GRAD)
    return drows.astype(rows.dtype), dw.astype(w.dtype), None


_tiled_grouped_dot.defvjp(_tiled_fwd, _tiled_bwd)


def _grouped_dot(rows, w, sizes, k_minor=False):
    """``rows @ w[g]`` for each group ``g`` of ``sizes`` consecutive rows,
    float32: ``jax.lax.ragged_dot`` with XLA's tiles, or the forward and
    both gradients each with :func:`_grouped_tiling`'s of its own shape.
    ``k_minor``: ``w`` was padded from a stack whose last dimension 128
    does not divide, which the chip lays out, with its optimizer states,
    with ``k`` in the lanes; the weight gradient is then made
    ``(g, n, k)`` and handed on transposed, in that layout, so that the
    states are not copied to and from another."""
    if _grouped_tiling(*rows.shape, w.shape[2]) is None:
        return jax.lax.ragged_dot(rows, w, sizes, preferred_element_type=_f32)
    return _tiled_grouped_dot(rows, w, sizes, k_minor)


def _lane_dense(w1, w2, gated):
    """The expert stacks with a width that no multiple of 128 divides
    carried to the next one (1 856 to 1 920): zero columns of ``w1`` (of
    each half, gated) and zero rows of ``w2``.  An activation of zeros is
    zero, so the result and the gradients of the stacks are the same, and
    the padding never leaves the layer.  At such a width XLA's grouped
    kernel tiles it by 128 and copies the stack and the result to and from
    its layout."""
    pad = -w2.shape[1] % 128
    if not pad:
        return w1, w2

    def wide(w, axis):
        return jnp.pad(w, [(0, pad if a == axis else 0) for a in range(3)])
    if gated:
        w1 = jnp.concatenate([wide(h, 2) for h in jnp.split(w1, 2, 2)], 2)
    else:
        w1 = wide(w1, 2)
    return w1, wide(w2, 1)


@dataclasses.dataclass
class MoEConfig:
    hidden_size: int
    ffn_hidden_size: int
    n_experts: int
    capacity_factor: float = 1.25
    top_k: int = 1                           # 1 = Switch, 2 = GShard
    expert_parallel_size: int = 1
    axis_name: Optional[str] = None          # "expert" inside shard_map
    tensor_parallel_size: int = 1            # shard each expert's FFN dim
    tensor_axis: Optional[str] = None        # "model" inside shard_map
    param_dtype: jnp.dtype = jnp.float32
    compute_dtype: jnp.dtype = jnp.float32   # expert einsums/dispatch
    # (gate softmax + aux loss always run f32)
    # | "sigmoid", "softmax_sorted": the sorted dispatch's routers
    router: str = "softmax"
    routed_scale: float = 1.0                # sorted: scales the k weights
    held: Optional[tuple] = None             # sorted: (offset, count) here
    activation: str = "relu"        # | "relu2" | "swiglu", "reglu" (gated)
    init_std: Optional[float] = None         # None: fan-in scaled stacks
    out_init_std: Optional[float] = None
    router_bias: bool = True                 # sigmoid: the balancer's bias

    def __post_init__(self):
        if self.router not in ("softmax", "sigmoid", "softmax_sorted"):
            raise ValueError(f"router must be 'softmax', 'sigmoid' or "
                             f"'softmax_sorted', got {self.router!r}")
        if self.activation not in ("relu", "relu2", "swiglu", "reglu"):
            raise ValueError(f"activation must be 'relu', 'relu2', "
                             f"'swiglu' or 'reglu', got {self.activation!r}")
        if self.gated and not self.sorted:
            raise ValueError(
                "gated ('swiglu', 'reglu') experts need the sorted "
                "dispatch's router ('sigmoid' or 'softmax_sorted'): only "
                "it has the [gate | up] stack")
        if self.sorted:
            if self.axis_name is not None or self.tensor_axis is not None:
                raise ValueError(
                    "the sorted dispatch runs one rank's share without its "
                    "exchange: no expert or tensor axis yet (say which "
                    "experts live here with held=)")
            if self.held is None:
                self.held = (0, self.n_experts)
            off, count = self.held
            if not (0 <= off and count >= 1
                    and off + count <= self.n_experts):
                raise ValueError(f"held={self.held} is not a range of the "
                                 f"{self.n_experts} experts")
        elif self.held is not None:
            raise ValueError("held= needs the sorted dispatch's router "
                             "('sigmoid' or 'softmax_sorted'; the capacity "
                             "path of 'softmax' shards experts over "
                             "axis_name)")
        if self.n_experts % self.expert_parallel_size:
            raise ValueError("n_experts must be divisible by "
                             "expert_parallel_size")
        if not 1 <= self.top_k <= self.n_experts:
            raise ValueError("top_k must be in [1, n_experts]")
        if self.expert_parallel_size > 1 and self.axis_name is None:
            raise ValueError(
                "expert_parallel_size > 1 requires axis_name (the expert "
                "mesh axis the call runs under)")
        if self.ffn_hidden_size % self.tensor_parallel_size:
            raise ValueError("ffn_hidden_size must be divisible by "
                             "tensor_parallel_size")
        if self.tensor_parallel_size > 1 and self.tensor_axis is None:
            raise ValueError(
                "tensor_parallel_size > 1 requires tensor_axis (the "
                "tensor mesh axis the call runs under)")

    @property
    def sorted(self):
        """The router is the sorted dispatch's (no capacity, ``held=``)."""
        return self.router != "softmax"

    @property
    def gated(self):
        return self.activation in ("swiglu", "reglu")

    @property
    def local_experts(self):
        if self.held is not None:
            return self.held[1]
        return self.n_experts // self.expert_parallel_size

    @property
    def local_ffn(self):
        return self.ffn_hidden_size // self.tensor_parallel_size


class MoEMLP:
    """Top-k MoE FFN (``top_k=1`` Switch, ``top_k=2`` GShard).

    ``params = m.init_params(key)`` holds THIS DEVICE's expert stack
    (``(local_experts, ...)`` leaves) plus the replicated gate;
    ``out, aux_loss = m(params, x)`` with ``x (tokens, hidden)`` local.
    """

    def __init__(self, cfg: MoEConfig):
        self.cfg = cfg

    def init_params(self, key):
        cfg = self.cfg
        k1, k2, k3 = jax.random.split(key, 3)
        e, h, f = cfg.local_experts, cfg.hidden_size, cfg.local_ffn
        std1 = h ** -0.5 if cfg.init_std is None else cfg.init_std
        std2 = f ** -0.5 if cfg.out_init_std is None else cfg.out_init_std
        # gated: an expert's gate and up-projection side by side, [gate | up]
        f1 = 2 * f if cfg.gated else f
        stacks = {
            "w1": std1 * jax.random.normal(k2, (e, h, f1), cfg.param_dtype),
            "w2": std2 * jax.random.normal(k3, (e, f, h), cfg.param_dtype),
        }
        if cfg.sorted:
            # float32 whatever the model's dtype; the bias is a buffer the
            # load balancer would move, not a trained weight (a router
            # without one ranks the scores as they are)
            router = {"weight": 0.02 * jax.random.normal(
                k1, (cfg.n_experts, h), _f32)}
            if cfg.router_bias:
                router["bias"] = jnp.zeros((cfg.n_experts,), _f32)
            return {"router": router, **stacks}
        return {"gate": 0.02 * jax.random.normal(
            k1, (h, cfg.n_experts), cfg.param_dtype), **stacks}

    def _capacity(self, n_tokens: int) -> int:
        cfg = self.cfg
        cap = int(cfg.capacity_factor * cfg.top_k * n_tokens
                  / cfg.n_experts)
        return max(cap, 1)

    # -- sigmoid router, sorted dispatch ------------------------------------

    def route_sigmoid(self, params, x):
        """``(choice (T, k) int32, weight (T, k) float32)`` in float32:
        scores ``sigmoid(x W_r)``, the top ``k`` of score + bias, the
        chosen scores renormalised and scaled."""
        cfg = self.cfg
        r = params["router"]
        scores = jax.nn.sigmoid(jnp.einsum(
            "th,eh->te", x.astype(_f32), r["weight"].astype(_f32),
            precision=jax.lax.Precision.HIGHEST))
        ranked = scores if "bias" not in r else \
            scores + jax.lax.stop_gradient(r["bias"].astype(_f32))
        _, choice = jax.lax.top_k(ranked, cfg.top_k)
        w = jnp.take_along_axis(scores, choice, axis=-1)
        w = cfg.routed_scale * w / (jnp.sum(w, -1, keepdims=True) + 1e-20)
        return choice, w

    def route_softmax(self, params, x):
        """``(choice (T, k) int32, weight (T, k) float32)`` in float32:
        logits ``x W_r``, the top ``k`` of logit + bias, weights the softmax
        over the chosen logits (the softmax over every expert renormalised
        over the chosen), scaled."""
        cfg = self.cfg
        r = params["router"]
        logits = jnp.einsum("th,eh->te", x.astype(_f32),
                            r["weight"].astype(_f32),
                            precision=jax.lax.Precision.HIGHEST)
        ranked = logits if "bias" not in r else \
            logits + jax.lax.stop_gradient(r["bias"].astype(_f32))
        _, choice = jax.lax.top_k(ranked, cfg.top_k)
        w = jax.nn.softmax(jnp.take_along_axis(logits, choice, axis=-1), -1)
        return choice, cfg.routed_scale * w

    def route(self, params, x):
        """The sorted dispatch's routing of ``x`` ``(T, hidden)``: what
        ``__call__`` takes as ``routing``."""
        if self.cfg.router == "sigmoid":
            return self.route_sigmoid(params, x)
        return self.route_softmax(params, x)

    def _chunk_rows(self, n_pairs):
        """Rows of the sorted pairs that one grouped product takes: twice
        what an even router sends to the held experts.  The first chunk
        holds every pair of a balanced batch; the chunks beyond run (under
        a ``cond``) only as far as an uneven one fills them."""
        cfg = self.cfg
        even = n_pairs * cfg.held[1] / cfg.n_experts
        return min(n_pairs, -(-int(2 * even) // 256) * 256)

    def _expert_rows(self, params, x, token, weight, group_ends, lo, n):
        """Rows ``[lo, lo + n)`` of the sorted pairs through their experts,
        weighted and added back to their tokens: ``(T, H)`` float32."""
        cfg = self.cfg
        cdt = cfg.compute_dtype
        ends = jnp.clip(group_ends - lo, 0, n)
        sizes = jnp.diff(ends, prepend=0).astype(jnp.int32)
        valid = (jnp.arange(n) < ends[-1])[:, None]
        # the rows past the held pairs belong to experts held elsewhere:
        # they enter as zeros, so they add nothing, and they ride in the
        # last group, so that the grouped products always take the chunk's
        # n rows.  A step's time then does not follow the router's load
        # (0.12 % across seeds on the chip against 0.77 % with the real
        # rows alone, for 6 % of the step), and no row of a result is left
        # unwritten (the TPU's grouped product writes only rows inside a
        # group, and 0 x NaN from such a row reached every gradient once)
        sizes = sizes.at[-1].add(n - ends[-1])
        tok = jax.lax.dynamic_slice_in_dim(token, lo, n)
        wt = jax.lax.dynamic_slice_in_dim(weight, lo, n)
        rows = jnp.where(valid, x[tok].astype(cdt), 0)
        with jax.named_scope("moe.experts"):
            w1, w2 = _lane_dense(params["w1"].astype(cdt),
                                 params["w2"].astype(cdt), cfg.gated)
            h1 = _grouped_dot(rows, w1, sizes,
                              k_minor=params["w1"].shape[2] % 128 != 0)
            if cfg.gated:
                gate, up = jnp.split(h1, 2, axis=-1)
                h1 = (jax.nn.silu(gate) if cfg.activation == "swiglu"
                      else jnp.maximum(gate, 0.0)) * up
            else:
                h1 = jnp.maximum(h1, 0.0)
                if cfg.activation == "relu2":
                    h1 = h1 * h1
            y = _grouped_dot(h1.astype(cdt), w2, sizes)
        return jnp.zeros(x.shape, _f32).at[tok].add(y * wt[:, None])

    def _call_sorted(self, params, x, routing=None):
        """The sorted dispatch's forward: ``(y, load)`` with ``load`` the
        tokens each held expert saw, ``(count,)`` int32; ``routing`` is
        :meth:`route`'s of the same tokens, made elsewhere, or None."""
        cfg = self.cfg
        off, count = cfg.held
        if routing is None:
            with jax.named_scope("moe.router"):
                choice, w = self.route(params, x)
        else:
            choice, w = routing
        with jax.named_scope("moe.dispatch"):
            local = choice - off
            # experts held elsewhere sort behind the last held one
            key = jnp.where((local >= 0) & (local < count), local,
                            count).reshape(-1)
            order = jnp.argsort(key, stable=True)
            load = jnp.sum(key[:, None] == jnp.arange(count), axis=0,
                           dtype=jnp.int32)
            group_ends = jnp.cumsum(load)
            n_pairs = key.shape[0]
            n = self._chunk_rows(n_pairs)
            pad = -n_pairs % n
            token = jnp.pad(order // cfg.top_k, (0, pad))
            weight = jnp.pad(w.reshape(-1)[order], (0, pad))
            y = self._expert_rows(params, x, token, weight, group_ends, 0, n)
            if n < n_pairs:
                # an uneven batch: one more grouped product per chunk the
                # held pairs reach.  A balanced one skips the whole loop
                # (and, in the backward, its zero cotangents for the
                # expert stacks) under a single cond
                @jax.checkpoint
                def more(y, lo):
                    return jax.lax.cond(
                        group_ends[-1] > lo,
                        lambda: y + self._expert_rows(
                            params, x, token, weight, group_ends, lo, n),
                        lambda: y), None
                y = jax.lax.cond(
                    group_ends[-1] > n,
                    lambda: jax.lax.scan(
                        more, y, jnp.arange(n, n_pairs, n))[0],
                    lambda: y)
        return y.astype(x.dtype), load

    def __call__(self, params, x, routing=None):
        cfg = self.cfg
        if cfg.sorted:
            return self._call_sorted(params, x, routing)
        ep = cfg.expert_parallel_size
        t, h = x.shape
        ne, nl = cfg.n_experts, cfg.local_experts
        k = cfg.top_k
        cap = self._capacity(t)

        xf = x.astype(_f32)
        logits = xf @ params["gate"].astype(_f32)          # (T, E)
        probs = jax.nn.softmax(logits, axis=-1)
        topk_prob, topk_idx = jax.lax.top_k(probs, k)      # (T, k)
        if k > 1:
            # GShard: gates renormalized over the selected experts
            gate_probs = topk_prob / jnp.sum(topk_prob, axis=-1,
                                             keepdims=True)
        else:
            gate_probs = topk_prob      # Switch keeps the raw top-1 prob

        # aux loss over FIRST choices (Switch form; GShard's is the same
        # statistic): n_e * sum_e(fraction_e * mean_prob_e)
        onehot1 = jax.nn.one_hot(topk_idx[:, 0], ne, dtype=_f32)
        fraction = jnp.mean(onehot1, axis=0)
        mean_prob = jnp.mean(probs, axis=0)
        aux_loss = ne * jnp.sum(fraction * mean_prob)

        # deterministic capacity per choice: first choices claim slots
        # first (GShard's assignment order), then second choices append.
        # integer cumsums — f32 counts lose exactness past 2^24
        expert_idx, slot, keep = [], [], []
        claimed = jnp.zeros((ne,), jnp.int32)
        for c in range(k):
            idx_c = topk_idx[:, c]
            onehot_i = jax.nn.one_hot(idx_c, ne, dtype=jnp.int32)
            pos = jnp.cumsum(onehot_i, axis=0) * onehot_i
            # pos_c >= 0 always (own one-hot contributes 1, claimed >= 0)
            pos_c = jnp.max(pos, axis=-1) - 1 + claimed[idx_c]
            keep_c = pos_c < cap
            expert_idx.append(idx_c)
            slot.append(jnp.clip(pos_c, 0, cap - 1))
            keep.append(keep_c)
            claimed = claimed + jnp.sum(onehot_i, axis=0)

        # dispatch: (E, cap, H) buffer in the compute dtype (each slot
        # receives at most one token, so low-precision add is exact);
        # dropped tokens scatter nothing
        cdt = cfg.compute_dtype
        xc = x.astype(cdt)
        buf = jnp.zeros((ne, cap, h), cdt)
        for c in range(k):
            buf = buf.at[expert_idx[c], slot[c]].add(
                xc * keep[c][:, None].astype(cdt), mode="drop")

        if cfg.axis_name is not None and ep > 1:
            # (ep, nl, cap, H): chunk e goes to the device owning expert
            # group e; received chunks stack on axis 0 as SOURCE device
            buf = buf.reshape(ep, nl, cap, h)
            buf = jax.lax.all_to_all(buf, cfg.axis_name, split_axis=0,
                                     concat_axis=0, tiled=False)
            # (ep_src, nl, cap, H) -> per local expert, all sources' slots
            expert_in = buf.transpose(1, 0, 2, 3).reshape(nl, ep * cap, h)
        else:
            expert_in = buf                                # (E, cap, H)

        # batched expert FFN: one einsum over the local expert stack,
        # operands in compute dtype (bf16 rides the MXU), f32 accumulate.
        # Under tensor parallelism w1/w2 hold the f-dim shard and the
        # Column→Row mapping pair brackets the two einsums: copy_to's
        # backward psums the dispatch-buffer cotangent over the tensor
        # ranks, reduce_from's forward psums the partial expert outputs
        # (identical collective structure to the dense ParallelMLP).
        tp_on = cfg.tensor_axis is not None and cfg.tensor_parallel_size > 1
        if tp_on:
            from apex_tpu.transformer.tensor_parallel import mappings as M
            expert_in = M.copy_to_tensor_model_parallel_region(
                expert_in, cfg.tensor_axis)
        h1 = jnp.maximum(jnp.einsum(
            "ech,ehf->ecf", expert_in, params["w1"].astype(cdt),
            preferred_element_type=_f32), 0.0)
        if cfg.activation == "relu2":
            h1 = h1 * h1
        h1 = h1.astype(cdt)
        out_e = jnp.einsum("ecf,efh->ech", h1,
                           params["w2"].astype(cdt),
                           preferred_element_type=_f32)
        if tp_on:
            out_e = M.reduce_from_tensor_model_parallel_region(
                out_e, cfg.tensor_axis)

        if cfg.axis_name is not None and ep > 1:
            # return trip in compute dtype (halves the ICI traffic)
            out_e = out_e.astype(cdt)
            out_e = out_e.reshape(nl, ep, cap, h).transpose(1, 0, 2, 3)
            out_e = jax.lax.all_to_all(out_e, cfg.axis_name, split_axis=0,
                                       concat_axis=0, tiled=False)
            out_e = out_e.reshape(ne, cap, h)

        # combine: gather each choice's slot, weight by its gate prob
        out = jnp.zeros((t, h), _f32)
        for c in range(k):
            out = out + out_e[expert_idx[c], slot[c]].astype(_f32) * (
                gate_probs[:, c] * keep[c].astype(_f32))[:, None]
        return out.astype(x.dtype), aux_loss


# -- EP training-recipe helpers ---------------------------------------------

def is_gpt_expert_leaf(path) -> bool:
    """True for a GPT MoE expert-stack leaf (``mlp.w1`` / ``mlp.w2``)."""
    ks = jax.tree_util.keystr(path)
    return "mlp" in ks and ("'w1'" in ks or "'w2'" in ks)


def localize_expert_params(params, is_expert=is_gpt_expert_leaf):
    """Drop the unit mesh axis from expert-stack leaves inside
    ``shard_map`` (``(1, nl, ...) -> (nl, ...)``)."""
    return jax.tree_util.tree_map_with_path(
        lambda p, x: x[0] if is_expert(p) else x, params)


def vary_params_over_axis(params, axis_name: str):
    """Mark every param leaf device-varying over ``axis_name`` (leaves
    already varying pass through).

    Load-bearing for EP training under ``check_vma=True``: the expert
    axis doubles as a batch axis for the dense compute, so dense-param
    grads must be psummed across it.  JAX's automatic
    psum-of-invariant-grads handles plain-jnp paths, but ``custom_vjp``
    kernels (the Pallas LayerNorm, the TP mappings) compute their own
    cotangents and leave them axis-varying with no way for JAX to insert
    the reduction.  ``pcast``-ing the params varying BEFORE the compute
    moves the reduction into pcast's transpose — a psum over the added
    axis — uniformly for every leaf.  Do NOT use this on
    the TENSOR axis: the Megatron mappings' custom_vjp rules already own
    model-axis grad reduction and would double-reduce.  Without vma
    tracking there is no pcast transpose to carry the reduction, so
    ``check_vma=False`` raises instead of returning partial grads.
    """
    if not vma_tracked(axis_name):
        raise ValueError(
            f"vary_params_over_axis({axis_name!r}) needs "
            "shard_map(check_vma=True): the dense-grad reduction rides "
            "pcast's transpose")
    return ensure_varying(params, axis_name)


def reduce_moe_grads(grads, axis_name: str,
                     is_expert=is_gpt_expert_leaf):
    """The EP gradient reduction recipe (single source of truth for the
    example and the tests).

    Differentiating the LOCAL per-device loss of a mean-over-devices
    objective: dense grads are pmean'd across the axis; expert-stack
    grads — whose cross-device contributions the ``all_to_all``
    transpose already routed to the owning device — divide by the axis
    size and regain the unit mesh axis for ``out_specs``.
    """
    ep = jax.lax.axis_size(axis_name)
    return jax.tree_util.tree_map_with_path(
        lambda p, g: (g / ep)[None] if is_expert(p)
        else jax.lax.pmean(g, axis_name), grads)
