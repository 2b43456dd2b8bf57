"""Quantized int8 weight GEMM — the decode path's weight-bytes half.

PR 16 quantized the KV *cache* (scale-per-block int8,
``serving/paged_cache.py``); this op quantizes the *weights*.  At
batch-per-replica decode every linear in the step — qkv, out-proj,
fc1/fc2, the tied lm-head — is pure HBM bandwidth: the activation tile
is a handful of rows while the weight matrix streams through the MXU
once per token, so weight BYTES, not FLOPs, bound tokens/s.  Weights
are static across a serving process, so quantize once at load (the
EQuARX int8+scale idiom already proven here for KV blocks and
compressed collectives) and dequantize in-register inside the GEMM:

* :func:`quantize_weight`: per-OUTPUT-channel symmetric int8 over the
  ``(out_features, in_features)`` Megatron weight layout — one f32
  scale per row, ``scale = amax(|row|) / 127`` (an all-zero row gets
  scale 1.0 so the zeros round-trip exactly).  Round-to-nearest keeps
  the per-element error ``<= scale / 2``, and because the scale vector
  lives on the OUTPUT dim, slicing rows (the ColumnParallel /
  vocab-parallel shard direction) commutes BITWISE with quantization:
  shard-then-quantize == quantize-then-shard.  RowParallel weights
  shard the *input* dim, where per-shard quantization sees a local
  amax ``<=`` the full-row amax — per-shard scales are never larger,
  so the per-element error bound only tightens (tested, not assumed).
* :func:`quant_gemm`: ``y = x @ dequant(w8, scale)^T`` as one Pallas
  kernel — grid ``(n_blocks, k_blocks)`` with the contraction axis
  innermost; each step loads a ``(block_n, block_k)`` int8 weight tile
  (a quarter of the f32 bytes: the whole point), dequantizes it
  in-register against the ``(block_n, 1)`` scale column, and
  accumulates ``x_tile @ w_tile^T`` in f32 on the MXU
  (``preferred_element_type``) into a ``(m, block_n)`` VMEM scratch.
  Activations stay in their own dtype (bf16 keeps the full MXU rate).

Decode-only by design: there is no VJP — the quantized tree is built
once at inference-engine init (:func:`apex_tpu.models.gpt.
quantize_decode_params`) and the training entry points
(``pipeline_step``, ``GuardedTrainStep``, autotune) reject it.

Off-TPU the public API dispatches to :func:`quant_gemm_reference`,
which replays the EXACT dequantize-then-matmul op order (dequantize to
f32, cast to the activation dtype, the unfused linear's ``x @ w^T``) —
so the ``weight_quant`` model knob is deterministic off-chip and the
unit suite compares the kernel (interpret mode) against the reference
at the flash-attention tolerances.

Padding parity: zero-padded rows quantize to zero (scale 1.0 padding)
and zero-padded lanes contribute zero through the contraction, so
every extent pads to its block multiple inside the op and slices back
exactly.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from apex_tpu.multi_tensor_apply.bucketing import _round_up
from apex_tpu.utils.platform import interpret_mode, use_pallas

_f32 = jnp.float32

__all__ = ["quantize_weight", "dequantize_weight", "quant_gemm",
           "quant_gemm_reference"]


def _sds(shape, dtype, like):
    """vma-aware pallas output ShapeDtypeStruct (see
    :func:`apex_tpu.utils.collectives.sds_like`)."""
    from apex_tpu.utils.collectives import sds_like

    return sds_like(shape, dtype, like)


# ---------------------------------------------------------------------------
# quantize / dequantize
# ---------------------------------------------------------------------------

def quantize_weight(w):
    """``(out, in) -> (int8 (out, in), f32 (out,))`` per-output-channel
    symmetric quantization.

    ``scale[i] = max(|w[i, :]|) / 127`` (1.0 for an all-zero row, so
    zero weights survive the round trip bitwise); the stored value is
    ``round(w / scale)`` clipped to ``[-127, 127]``, which bounds the
    per-element reconstruction error by ``scale / 2``.  A pure
    function of the weight values — the same array quantizes to the
    same ``(w8, scale)`` bitwise on every load.
    """
    if w.ndim != 2:
        raise ValueError(f"quantize_weight expects a 2D (out, in) "
                         f"weight, got shape {w.shape}")
    w32 = jnp.asarray(w, _f32)
    amax = jnp.max(jnp.abs(w32), axis=1)
    scale = jnp.where(amax > 0.0, amax / 127.0,
                      jnp.ones_like(amax)).astype(_f32)
    q = jnp.clip(jnp.round(w32 / scale[:, None]), -127.0, 127.0)
    return q.astype(jnp.int8), scale


def dequantize_weight(w8, scale):
    """``w8 * scale[:, None]`` in f32 — the reconstruction every
    consumer (kernel, reference, embedding gather) replays."""
    return w8.astype(_f32) * scale[:, None].astype(_f32)


# ---------------------------------------------------------------------------
# kernel
# ---------------------------------------------------------------------------

def _qgemm_kernel(x_ref, w_ref, s_ref, y_ref, acc_scr):
    ki = pl.program_id(1)
    nk = pl.num_programs(1)

    @pl.when(ki == 0)
    def _init():
        acc_scr[:] = jnp.zeros_like(acc_scr[:])

    x = x_ref[:]
    # dequantize the int8 tile in-register: (block_n, block_k) f32,
    # then down to the activation dtype so the MXU runs at full rate
    w = (w_ref[:].astype(_f32) * s_ref[:].astype(_f32)).astype(x.dtype)
    # acc += x_tile @ w_tile^T, f32 accumulation on the MXU
    acc_scr[:] += jax.lax.dot_general(x, w, (((1,), (1,)), ((), ())),
                                      preferred_element_type=_f32)

    @pl.when(ki == nk - 1)
    def _finish():
        y_ref[:] = acc_scr[:].astype(y_ref.dtype)


def _vmem(block, index_map):
    return pl.BlockSpec(block, index_map, memory_space=pltpu.VMEM)


def _pad2(a, r, c):
    if a.shape != (r, c):
        a = jnp.pad(a, ((0, r - a.shape[0]), (0, c - a.shape[1])))
    return a


def _qgemm_impl(x, w8, scale, block_n, block_k):
    """Pre-padded 2D operands: x (m_p, k_p), w8 (n_p, k_p) int8,
    scale (n_p, 1) f32; returns padded (m_p, n_p) f32."""
    m_p, k_p = x.shape
    n_p = w8.shape[0]
    nn, nk = n_p // block_n, k_p // block_k
    return pl.pallas_call(
        _qgemm_kernel,
        grid=(nn, nk),
        in_specs=[_vmem((m_p, block_k), lambda ni, ki: (0, ki)),
                  _vmem((block_n, block_k), lambda ni, ki: (ni, ki)),
                  _vmem((block_n, 1), lambda ni, ki: (ni, 0))],
        out_specs=_vmem((m_p, block_n), lambda ni, ki: (0, ni)),
        out_shape=_sds((m_p, n_p), _f32, x),
        scratch_shapes=[pltpu.VMEM((m_p, block_n), _f32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret_mode(),
    )(x, w8, scale)


# ---------------------------------------------------------------------------
# reference + public API
# ---------------------------------------------------------------------------

def quant_gemm_reference(x, w8, scale):
    """Unfused reference: the EXACT dequantize-then-matmul op order —
    reconstruct the f32 weight per output row, cast to the activation
    dtype (the unfused TP linear's GEMM contract), contract.  The
    off-TPU dispatch target, and what the kernel must match in
    interpret mode."""
    w = dequantize_weight(w8, scale)
    y = x @ w.astype(x.dtype).T
    return y.astype(_f32)


def _fit(requested, extent):
    """Largest candidate block <= requested dividing the lane-padded
    extent (the flash-attention block picker)."""
    padded = _round_up(extent, 128)
    for cand in (requested, 512, 384, 256, 128):
        if cand <= requested and padded % cand == 0:
            return cand
    return min(requested, padded)


def quant_gemm(x, w8, scale, *, block_n=512, block_k=512):
    """``x @ dequant(w8, scale)^T`` over ``(..., k)``; returns f32
    ``(..., out)`` (the decode heads' accumulation dtype).

    ``w8`` is int8 ``(out_features, in_features)`` with ``scale`` f32
    ``(out_features,)`` from :func:`quantize_weight` — the TP linear
    layout, so a row-block (ColumnParallel) or column-block
    (RowParallel) weight shard drops in per-rank unchanged with its
    per-shard scales.  Off-TPU (``use_pallas() == False``) dispatches
    to :func:`quant_gemm_reference`, which replays the dequantize →
    cast → matmul op order exactly.
    """
    if w8.dtype != jnp.int8:
        raise ValueError(f"w8 must be int8, got {w8.dtype}")
    if x.shape[-1] != w8.shape[1]:
        raise ValueError(f"x features {x.shape[-1]} != w8 in-dim "
                         f"{w8.shape[1]}")
    if scale.shape != (w8.shape[0],):
        raise ValueError(f"scale shape {scale.shape} != "
                         f"({w8.shape[0]},)")
    lead = x.shape[:-1]
    x2 = x.reshape((-1, x.shape[-1]))
    if not use_pallas():
        y = quant_gemm_reference(x2, w8, scale)
        return y.reshape(lead + (w8.shape[0],))
    m, k = x2.shape
    n = w8.shape[0]
    block_n = _fit(int(block_n), n)
    block_k = _fit(int(block_k), k)
    m_p = _round_up(m, 8)
    k_p = _round_up(k, block_k)
    n_p = _round_up(n, block_n)
    y = _qgemm_impl(_pad2(x2, m_p, k_p), _pad2(w8, n_p, k_p),
                    _pad2(scale[:, None].astype(_f32), n_p, 1),
                    block_n, block_k)
    return y[:m, :n].reshape(lead + (n,))
