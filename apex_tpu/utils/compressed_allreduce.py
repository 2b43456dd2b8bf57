"""Block-quantized collectives — EQuARX-style compressed all-reduce.

EQuARX (arXiv:2506.17615) shows that the dominant cost of data-parallel
gradient all-reduce on TPU ICI is wire bytes, and that block-quantized
int8 transport with full-precision accumulation recovers most of it at
negligible quality loss.  This module is that design over the package's
native ``(rows, 128)`` packed-bucket layout:

* the all-reduce is decomposed into reduce-scatter + all-gather (the
  same decomposition the ZeRO optimizer uses for its sharded update);
* each hop's payload is quantized per LANE=128-element block — one int8
  value per element plus one f32 scale per block (~8.25 bits/element,
  a ~3.9x wire-byte reduction vs f32, ~1.9x vs bf16);
* dequantization and the cross-replica SUM always run in f32 ("quantized
  transport, f32 accumulation"), so error comes only from the rounding
  of each payload, never from low-precision accumulation.

The ``allreduce_dtype`` knob shared by
:class:`~apex_tpu.parallel.DistributedDataParallel` and the distributed
optimizers selects the transport:

=============  ==========================================================
``None``/f32   plain ``psum``/``psum_scatter`` — bitwise-identical to the
               uncompressed path (the safe default)
``bf16``       bf16 payload, f32 accumulation (~2x fewer wire bytes;
               error = one bf16 rounding per element per hop)
``int8``       per-block int8 + f32 scale, f32 accumulation (~3.9x fewer
               wire bytes; observed grad-bucket max relative error vs the
               block max ~0.8% per hop — see tests)
=============  ==========================================================

Implementation note: the quantized reduce-scatter is an ``all_to_all`` of
quantized shards followed by a local f32 tree-sum, i.e. ONE quantization
per producer (not one per ring hop) — on an ICI torus XLA lowers
all-to-all to the same bisection traffic a ring reduce-scatter uses, and
a single quantization is both faster and lower-error than requantizing
at every hop.  Collective inputs/outputs keep shapes static: callers pad
to ``world_size``-divisible rows (:func:`pad_rows`), zero padding rows
quantize to exact zeros, and the f32 accumulation keeps them zero.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from apex_tpu.multi_tensor_apply.bucketing import LANE

_f32 = jnp.float32

#: transports accepted by every ``allreduce_dtype`` knob
MODES = (None, "f32", "bf16", "int8")


def check_mode(mode):
    """Normalize/validate an ``allreduce_dtype`` value (None == "f32")."""
    if mode in (None, "f32", jnp.float32):
        return None
    if mode in ("bf16", jnp.bfloat16):
        return "bf16"
    if mode in ("int8", jnp.int8):
        return "int8"
    raise ValueError(
        f"allreduce_dtype={mode!r} not supported; choose one of "
        "None/'f32' (exact), 'bf16', 'int8'")


# -- per-block int8 codec ----------------------------------------------------

def quantize_int8(x):
    """Symmetric per-block int8 quantization over the last axis.

    ``x`` is any float array whose last axis is the quantization block
    (the packed buffers use LANE=128).  Returns ``(q, scale)`` with ``q``
    int8 in [-127, 127] and ``scale`` f32 shaped like ``x`` with the last
    axis reduced to 1.  All-zero blocks get scale 1 so they round-trip to
    exact zeros.
    """
    x = x.astype(_f32)
    scale = jnp.max(jnp.abs(x), axis=-1, keepdims=True) / 127.0
    scale = jnp.where(scale > 0, scale, 1.0)
    q = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
    return q, scale.astype(_f32)


def dequantize_int8(q, scale):
    """f32 reconstruction of :func:`quantize_int8` output."""
    return q.astype(_f32) * scale


def pad_rows(x, multiple: int):
    """Zero-pad axis 0 of ``(rows, LANE)`` to a multiple (static shape)."""
    rows = x.shape[0]
    target = -(-rows // multiple) * multiple
    if target == rows:
        return x
    return jnp.pad(x, ((0, target - rows), (0, 0)))


# -- collectives (call inside shard_map over ``axis_name``) ------------------

def _all_to_all_rows(x, axis_name):
    return jax.lax.all_to_all(x, axis_name, split_axis=0, concat_axis=0,
                              tiled=True)


def reduce_scatter(x, axis_name, world_size: int, mode=None):
    """Reduce-scatter a packed ``(rows, 128)`` buffer over ``axis_name``.

    ``rows`` must be divisible by ``world_size``; returns the caller's
    ``(rows / world_size, 128)`` shard of the cross-replica SUM, in
    ``x.dtype``.  ``mode=None``/``"f32"`` is ``lax.psum_scatter`` —
    bitwise-identical to the uncompressed path.  The quantized modes
    transport compressed payloads via all-to-all and accumulate the
    ``world_size`` dequantized shards in f32.
    """
    mode = check_mode(mode)
    rows = x.shape[0]
    if rows % world_size:
        raise ValueError(
            f"reduce_scatter: rows={rows} not divisible by "
            f"world_size={world_size}; pad with pad_rows() first")
    if mode is None:
        return jax.lax.psum_scatter(x, axis_name, scatter_dimension=0,
                                    tiled=True)
    local = rows // world_size
    if mode == "bf16":
        payload = _all_to_all_rows(x.astype(jnp.bfloat16), axis_name)
        parts = payload.astype(_f32)
    else:  # int8
        q, s = quantize_int8(x)
        q = _all_to_all_rows(q, axis_name)
        s = _all_to_all_rows(s, axis_name)
        parts = dequantize_int8(q, s)
    total = jnp.sum(parts.reshape(world_size, local, x.shape[1]), axis=0)
    return total.astype(x.dtype)


def all_gather_rows(x, axis_name, mode=None):
    """All-gather shards along axis 0, optionally with compressed payload.

    The inverse of :func:`reduce_scatter`'s layout: every rank contributes
    its ``(local_rows, 128)`` shard and receives the ``(world * local_rows,
    128)`` concatenation.  Quantized modes compress the outgoing shard
    once; the gathered result is dequantized to ``x.dtype``.
    """
    mode = check_mode(mode)
    if mode is None:
        return jax.lax.all_gather(x, axis_name, axis=0, tiled=True)
    if mode == "bf16":
        g = jax.lax.all_gather(x.astype(jnp.bfloat16), axis_name, axis=0,
                               tiled=True)
        return g.astype(x.dtype)
    q, s = quantize_int8(x)
    q = jax.lax.all_gather(q, axis_name, axis=0, tiled=True)
    s = jax.lax.all_gather(s, axis_name, axis=0, tiled=True)
    return dequantize_int8(q, s).astype(x.dtype)


def psum_compressed(x, axis_name, world_size: int, mode=None):
    """All-reduce (SUM) one array with compressed transport.

    Arbitrary shape/float dtype; result has ``x``'s shape and dtype.
    ``mode=None``/``"f32"`` is a plain ``lax.psum``.  Otherwise the leaf
    is flattened into LANE-blocks padded to ``world_size`` rows, reduce-
    scattered (quantized transport, f32 accumulation), and the reduced
    shard is re-quantized once for the all-gather — two quantizations
    total, matching EQuARX's per-direction cost.
    """
    mode = check_mode(mode)
    if mode is None:
        return jax.lax.psum(x, axis_name)
    flat = jnp.ravel(x).astype(_f32)
    n = flat.shape[0]
    rows = -(-n // LANE)
    flat = jnp.pad(flat, (0, rows * LANE - n)).reshape(rows, LANE)
    flat = pad_rows(flat, world_size)
    shard = reduce_scatter(flat, axis_name, world_size, mode)
    full = all_gather_rows(shard, axis_name, mode)
    out = jnp.ravel(full)[:n].reshape(x.shape)
    return out.astype(x.dtype)


def psum_tree_compressed(tree, axis_name, world_size: int, mode=None,
                         strict: bool = False):
    """Compressed :func:`~apex_tpu.utils.collectives.psum_if_varying`.

    Same gradient-only contract: device-invariant leaves (already-summed
    grads under vma tracking) pass through unchanged — ``strict=True``
    raises on them — and varying leaves take :func:`psum_compressed`.
    Non-float leaves always take the exact ``psum`` path (quantizing
    integer counters would corrupt them).
    """
    from apex_tpu.utils.collectives import varying_test

    mode = check_mode(mode)
    varying = varying_test(axis_name)

    def one(path, v):
        if not varying(v):
            if strict:
                raise ValueError(
                    "psum_tree_compressed(strict=True): leaf "
                    f"{jax.tree_util.keystr(path)} is device-invariant "
                    f"over axis {axis_name!r}")
            return v
        if mode is None or not jnp.issubdtype(v.dtype, jnp.floating):
            return jax.lax.psum(v, axis_name)
        return psum_compressed(v, axis_name, world_size, mode)

    return jax.tree_util.tree_map_with_path(one, tree)
