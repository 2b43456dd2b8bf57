"""Flash attention — TPU rebuild of the reference's fused-attention tier
(``apex/contrib/fmha/`` fixed-seqlen fused MHA and
``apex/contrib/multihead_attn/`` fused self/encdec attention kernels).

The CUDA kernels tile QK^T into SRAM and fuse scale+mask+softmax+PV per
tile; the TPU equivalent is the blockwise online-softmax (flash) algorithm
as Pallas kernels:

* forward: grid ``(batch*heads, q_blocks, k_blocks)`` with the k axis
  innermost; running row-max ``m``, row-sum ``l`` and the output
  accumulator live in VMEM scratch across the k iterations, so the
  ``(s, s)`` score matrix is never materialized in HBM.  Saves the
  per-row logsumexp for the backward.
* backward: two passes with the same blocking — one accumulating ``dq``
  (k innermost), one accumulating ``dk``/``dv`` (q innermost) — each
  recomputing ``p = exp(q k^T * scale - lse)`` from the saved logsumexp
  instead of storing probabilities (the flash-attention recompute trade).

Unlike the reference's fmha (seqlen <= 512 templates) there is no sequence
cap; unlike the pre-flash ``multihead_attn`` kernels the memory is O(s)
not O(s^2).  Padding parity: the reference packs variable-length batches
via ``cu_seqlens``; here batches are dense ``(b, h, s, d)`` with an
optional per-batch ``kv_seqlens`` — key positions >= the row's length are
masked out, matching the packed semantics on padded inputs.  Probability
dropout is fused into all three kernels via a counter-hash keep mask
(see the "fused probability dropout" section below), the reference's
philox-fused design without the O(s^2) mask storage.

Off-TPU the same semantics run as a materialized jnp reference (the unit
suite compares the two; on TPU the Pallas path is the default).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from apex_tpu.multi_tensor_apply.bucketing import _round_up
from apex_tpu.utils.platform import interpret_mode, use_pallas

_f32 = jnp.float32
_MASK = -1e30  # finite "minus infinity": exp(_MASK - m) == 0, no NaNs

__all__ = ["flash_attention", "flash_attention_bshd",
           "flash_attention_reference",
           "flash_attention_decode", "flash_attention_decode_reference",
           "flash_attention_decode_paged", "flash_attention_chunk_paged",
           "gather_paged_kv", "grouped_attention", "grouped_flash"]


# ---------------------------------------------------------------------------
# fused probability dropout
# ---------------------------------------------------------------------------
#
# The reference fuses philox-counter dropout into the probability tile
# (apex/contrib/csrc/multihead_attn/dropout.cuh, philox.h): the mask is a
# pure function of (seed, position), so forward and backward regenerate it
# instead of storing an O(s^2) mask.  Same design here, with a
# lowbias32-style integer hash instead of philox: pure jnp/lax integer
# math, so the SAME function runs inside the Pallas kernels (compiled or
# interpret mode) and in the dense jnp fallback — the mask is bit-identical
# across all paths and invariant to the kernel's block-size choice.
#
# Dropout semantics: inverted dropout on the NORMALIZED probabilities —
# the softmax denominator ``l`` accumulates the undropped ``p`` (the saved
# logsumexp is dropout-free), and the keep/(1-rate) factor applies only to
# the PV matmul.  Backward: with D the keep-scale matrix and P the
# undropped probabilities, ``o = (P∘D)V`` gives ``dV = (P∘D)^T dO``,
# ``dS = P∘(D∘(dO V^T) - delta)`` where ``delta = rowsum(dO∘O)`` — the
# delta trick survives dropout unchanged because
# ``rowsum(dO∘O) = rowsum(P∘D∘(dO V^T))``.


def _mix32(x):
    """lowbias32 avalanche mix (public-domain integer hash)."""
    x = (x ^ (x >> 16)) * jnp.uint32(0x7FEB352D)
    x = (x ^ (x >> 15)) * jnp.uint32(0x846CA68B)
    return x ^ (x >> 16)


def _dropout_hash(seed, bh, q_pos, k_pos):
    """uint32 hash of (seed, batch*head index, q position, k position).

    ``seed``/``bh`` are scalars, ``q_pos``/``k_pos`` integer arrays that
    broadcast against each other; chained mixing (not a packed linear
    counter) so large sequence extents cannot alias by overflow.
    """
    h = _mix32(jnp.asarray(bh).astype(jnp.uint32)
               ^ _mix32(jnp.asarray(seed).astype(jnp.uint32)))
    h = _mix32(h ^ q_pos.astype(jnp.uint32))
    return _mix32(h ^ k_pos.astype(jnp.uint32))


def _keep_threshold(rate):
    """Static uint32 threshold with P(hash >= threshold) = 1 - rate."""
    return jnp.uint32(min(max(int(round(rate * 2.0 ** 32)), 0),
                          2 ** 32 - 1))


def _keep_scale_tile(seed, bh, qi, ki, block_q, block_k, rate):
    """(block_q, block_k) f32 tile of keep/(1-rate) factors ("D")."""
    q_pos = qi * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)
    k_pos = ki * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    h = _dropout_hash(seed, bh, q_pos, k_pos)
    return jnp.where(h >= _keep_threshold(rate),
                     jnp.float32(1.0 / (1.0 - rate)), 0.0)


def dropout_keep_scale(seed, n_bh, sq, sk, rate):
    """Dense ``(n_bh, sq, sk)`` keep-scale matrix — the SAME hash the
    fused kernels regenerate per tile, materialized (for the jnp
    fallback and for parity tests against the fused path)."""
    bh = jnp.arange(n_bh, dtype=jnp.int32)[:, None, None]
    q_pos = jnp.arange(sq, dtype=jnp.int32)[None, :, None]
    k_pos = jnp.arange(sk, dtype=jnp.int32)[None, None, :]
    h = _dropout_hash(seed, bh, q_pos, k_pos)
    return jnp.where(h >= _keep_threshold(rate),
                     jnp.float32(1.0 / (1.0 - rate)), 0.0)


def _sds(shape, dtype, like):
    """vma-aware pallas output ShapeDtypeStruct (see
    :func:`apex_tpu.utils.collectives.sds_like`)."""
    from apex_tpu.utils.collectives import sds_like

    return sds_like(shape, dtype, like)


# ---------------------------------------------------------------------------
# forward kernel
# ---------------------------------------------------------------------------

def _head_lanes(x, j, hp):
    """``x`` ``(rows, 128)`` with every lane outside head ``j``'s
    ``128 // hp`` zeroed.  A tile of ``hp`` heads keeps its heads apart
    by what it contracts over: a product with one operand masked to head
    ``j`` sums over that head's lanes only (or leaves zeros outside
    them), so no lane is ever sliced or reshaped, which Mosaic refuses.
    One head a tile (``hp`` 1): ``x`` itself."""
    if hp == 1:
        return x
    d = 128 // hp
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    return jnp.where((lane >= j * d) & (lane < (j + 1) * d), x,
                     jnp.zeros_like(x))


def _per_lane(cols):
    """One ``(rows, 1)`` column a head -> ``(rows, 128)`` in which head
    ``j``'s lanes hold ``cols[j]`` (a lone head: its column, which
    broadcasts)."""
    out = cols[0]
    if len(cols) > 1:
        lane = jax.lax.broadcasted_iota(jnp.int32, (out.shape[0], 128), 1)
        for j in range(1, len(cols)):
            out = jnp.where(lane >= j * (128 // len(cols)), cols[j], out)
    return out


def _fwd_kernel(causal, scale, rate, sq, block_q, block_k, masked, hp,
                len_ref, seed_ref, q_ref, k_ref, v_ref, o_ref, lse_ref,
                m_scr, l_scr, acc_scr):
    # one grid step is one tile of ``hp`` heads: the whole padded head
    # of the ``(b*h, s, d_pad)`` operands (``hp`` 1), or a 128-lane
    # column block of ``(b, s, h*d)`` rows holding ``128 // d`` heads,
    # whose ``b*h + head`` index is ``t * hp + j``.  Head j's running
    # max and sum are rows ``j * block_q ...`` of ``m_scr`` / ``l_scr``.
    t = pl.program_id(0)
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)
    rows = [slice(j * block_q, (j + 1) * block_q) for j in range(hp)]

    @pl.when(ki == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr[:], _MASK)
        l_scr[:] = jnp.zeros_like(l_scr[:])
        acc_scr[:] = jnp.zeros_like(acc_scr[:])

    def compute():
        # operands stay in their native dtype (bf16 rides the MXU at
        # full rate; upcasting first would run the dot at f32 rate,
        # ~1/8 on v5e) — accumulation is f32 via preferred_element_type
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        if masked:
            # ``masked`` is static: dense full-length non-causal calls
            # (the BERT shape) skip the iota/compare/select passes
            # (same-window A/B on v5e measures this neutral-to-slightly
            # -positive — Mosaic overlaps the VPU mask work with the
            # dots — kept because it is free specialization, mirroring
            # the reference fmha's seqlen-templated kernels)
            q_pos = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            k_pos = ki * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            valid = k_pos < len_ref[t]
            if causal:
                valid = valid & (k_pos <= q_pos)
        alphas, pv = [], None
        for j in range(hp):
            s = jax.lax.dot_general(
                q, _head_lanes(k, j, hp), (((1,), (1,)), ((), ())),
                preferred_element_type=_f32) * scale
            if masked:
                s = jnp.where(valid, s, _MASK)
            m_prev = m_scr[rows[j], :1]
            m_cur = jnp.maximum(jnp.max(s, axis=1, keepdims=True), m_prev)
            alphas.append(jnp.exp(m_prev - m_cur))
            p = jnp.exp(s - m_cur)
            if masked:
                p = jnp.where(valid, p, 0.0)
            # l accumulates the UNDROPPED p (softmax normalizes
            # pre-dropout); the keep/(1-rate) factor touches only the
            # PV matmul
            l_cur = alphas[j] * l_scr[rows[j], :1] + jnp.sum(
                p, axis=1, keepdims=True)
            if rate > 0.0:
                p = p * _keep_scale_tile(seed_ref[0], t * hp + j, qi, ki,
                                         block_q, block_k, rate)
            pv_j = jax.lax.dot_general(
                p.astype(v.dtype), _head_lanes(v, j, hp),
                (((1,), (0,)), ((), ())), preferred_element_type=_f32)
            pv = pv_j if pv is None else pv + pv_j
            m_scr[rows[j]] = jnp.broadcast_to(m_cur, (block_q, 128))
            l_scr[rows[j]] = jnp.broadcast_to(l_cur, (block_q, 128))
        acc_scr[:] = acc_scr[:] * _per_lane(alphas) + pv

    if causal:
        # blocks strictly above the diagonal contribute nothing
        @pl.when(ki * block_k <= qi * block_q + block_q - 1)
        def _():
            compute()
    else:
        compute()

    @pl.when(ki == nk - 1)
    def _finish():
        ls = []
        for j in range(hp):
            l = l_scr[rows[j], :1]
            ls.append(jnp.where(l == 0.0, 1.0, l))
            lse_ref[j] = m_scr[rows[j], :1] + jnp.log(ls[j])
        o_ref[0] = (acc_scr[:] / _per_lane(ls)).astype(o_ref.dtype)


# ---------------------------------------------------------------------------
# backward kernels
# ---------------------------------------------------------------------------

def _recompute_p(causal, scale, qi, ki, block_q, block_k, masked, kv_len,
                 q, k, lse):
    """p = exp(q k^T * scale - lse) with the forward's mask re-applied.
    ``q``/``k`` native dtype; accumulation f32 (MXU-rate dots).
    ``masked`` static False (dense full-length non-causal) skips the
    mask recompute, matching the forward."""
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=_f32) * scale
    if not masked:
        return jnp.exp(s - lse), None
    q_pos = qi * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)
    k_pos = ki * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    valid = k_pos < kv_len
    if causal:
        valid = valid & (k_pos <= q_pos)
    p = jnp.where(valid, jnp.exp(s - lse), 0.0)
    return p, valid


def _row_delta(delta_ref, do, j, hp):
    """Head ``j``'s ``rowsum(dO∘O)``, ``(block_q, 1)`` float32.  One
    head a tile: XLA summed it and ``delta_ref`` holds it.  A tile of
    several: ``delta_ref`` is the tile of O, ``do`` the tile of dO
    masked to the head's lanes, and the sum is taken here, so the
    backward reads O once more and no per-head reduction over lanes of
    64, which XLA reaches only through a transposed float32 copy, is
    left around the kernels."""
    if hp == 1:
        return delta_ref[j]
    return jnp.sum(do.astype(_f32) * delta_ref[0].astype(_f32), axis=1,
                   keepdims=True)


def _dq_kernel(causal, scale, rate, sq, block_q, block_k, masked, hp,
               len_ref, seed_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
               delta_ref, dq_ref, dq_scr):
    t = pl.program_id(0)
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr[:])

    def compute():
        q = q_ref[0]
        v = v_ref[0]
        for j in range(hp):
            # K and dO masked to head j: the scores and dP sum over its
            # lanes only, and dS K leaves zeros in the other heads'
            k = _head_lanes(k_ref[0], j, hp)
            do = _head_lanes(do_ref[0], j, hp)
            p, _ = _recompute_p(causal, scale, qi, ki, block_q, block_k,
                                masked, len_ref[t], q, k,
                                lse_ref[j])                # (block_q, 1)
            dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                     preferred_element_type=_f32)
            if rate > 0.0:
                # dP = D∘(dO V^T): regenerate the forward's mask for
                # this tile
                dp = dp * _keep_scale_tile(seed_ref[0], t * hp + j, qi, ki,
                                           block_q, block_k, rate)
            ds = p * (dp - _row_delta(delta_ref, do, j, hp)) * scale
            # ds cast to the operand dtype for the MXU-rate dot (the
            # flash CUDA kernels do the same: dS is written back at
            # input precision)
            dq_scr[:] += jax.lax.dot_general(ds.astype(k.dtype), k,
                                             (((1,), (0,)), ((), ())),
                                             preferred_element_type=_f32)

    if causal:
        @pl.when(ki * block_k <= qi * block_q + block_q - 1)
        def _():
            compute()
    else:
        compute()

    @pl.when(ki == nk - 1)
    def _finish():
        dq_ref[0] = dq_scr[:].astype(dq_ref.dtype)


def _dkv_kernel(causal, scale, rate, sq, block_q, block_k, masked, hp,
                len_ref, seed_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                delta_ref, dk_ref, dv_ref, dk_scr, dv_scr):
    t = pl.program_id(0)
    ki = pl.program_id(1)
    qi = pl.program_id(2)
    nq = pl.num_programs(2)

    @pl.when(qi == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr[:])
        dv_scr[:] = jnp.zeros_like(dv_scr[:])

    def compute():
        k = k_ref[0]
        v = v_ref[0]
        for j in range(hp):
            # Q and dO masked to head j: the scores and dP sum over its
            # lanes only, and P^T dO, dS^T Q leave zeros in the others'
            q = _head_lanes(q_ref[0], j, hp)
            do = _head_lanes(do_ref[0], j, hp)
            p, valid = _recompute_p(causal, scale, qi, ki, block_q,
                                    block_k, masked, len_ref[t], q, k,
                                    lse_ref[j])            # (block_q, 1)
            if masked:
                # zero padded q rows: their lse/delta are garbage and
                # p.T @ do would poison every dk/dv row (forward never
                # reads them — it slices; the backward reduces over
                # them).  ``masked`` is True whenever the q extent is
                # padded.
                q_pos = qi * block_q + jax.lax.broadcasted_iota(
                    jnp.int32, (block_q, block_k), 0)
                p = jnp.where(q_pos < sq, p, 0.0)
            if rate > 0.0:
                # same (seed, b*h + head, qi, ki) stream as the forward —
                # note this kernel's grid is (tiles, k, q), so the logical
                # (qi, ki) pair is (program_id(2), program_id(1))
                dmask = _keep_scale_tile(seed_ref[0], t * hp + j, qi, ki,
                                         block_q, block_k, rate)
                pd = p * dmask
            else:
                pd = p
            dv_scr[:] += jax.lax.dot_general(pd.astype(do.dtype), do,
                                             (((0,), (0,)), ((), ())),
                                             preferred_element_type=_f32)
            dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                     preferred_element_type=_f32)
            if rate > 0.0:
                dp = dp * dmask
            ds = p * (dp - _row_delta(delta_ref, do, j, hp)) * scale
            dk_scr[:] += jax.lax.dot_general(ds.astype(q.dtype), q,
                                             (((0,), (0,)), ((), ())),
                                             preferred_element_type=_f32)

    if causal:
        @pl.when(qi * block_q + block_q - 1 >= ki * block_k)
        def _():
            compute()
    else:
        compute()

    @pl.when(qi == nq - 1)
    def _finish():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


# ---------------------------------------------------------------------------
# pallas_call plumbing
# ---------------------------------------------------------------------------

def _pad_qkv(x, s_pad, d_pad):
    b, s, d = x.shape
    if s != s_pad or d != d_pad:
        x = jnp.pad(x, ((0, 0), (0, s_pad - s), (0, d_pad - d)))
    return x


def _specs(block_q, block_k, width, G, hp, which):
    """BlockSpecs for grid (tiles, i, j); ``which`` selects the role.
    Tile ``t`` is the 128-lane column block ``t % G`` of row
    ``t // G`` of the operand (``G`` 1: the operand's whole last axis),
    and heads ``t * hp ...`` of the ``(b*h, s, 1)`` row statistics."""
    def at(t, rows):
        return (t, rows, 0) if G == 1 else (t // G, rows, t % G)

    if which == "len":
        # whole (tiles,) vector resident in SMEM; kernels index
        # program_id(0)
        return pl.BlockSpec(memory_space=pltpu.SMEM)
    if which == "outer":        # follows grid dim 1 (rows of the output)
        return pl.BlockSpec((1, block_q, width), lambda t, i, j: at(t, i),
                            memory_space=pltpu.VMEM)
    if which == "inner":        # follows grid dim 2 (reduced-over axis)
        return pl.BlockSpec((1, block_k, width), lambda t, i, j: at(t, j),
                            memory_space=pltpu.VMEM)
    if which == "outer_vec":    # (b*h, s, 1) per-row stats following dim 1
        # (block_q, 1) trailing dims: sublane divisible by 8, unit lane
        # matching the array — the TPU-legal layout for row statistics
        return pl.BlockSpec((hp, block_q, 1), lambda t, i, j: (t, i, 0),
                            memory_space=pltpu.VMEM)
    if which == "inner_vec":
        return pl.BlockSpec((hp, block_q, 1), lambda t, i, j: (t, j, 0),
                            memory_space=pltpu.VMEM)
    raise ValueError(which)


def _compiler_params(hp=1):
    # a tile of several heads at the largest blocks keeps more than one
    # head's float32 scores alive: at 1024 x 1024 and two heads the three
    # kernels ask for 16.3-20.9 MB of Mosaic's default 16 MB of scoped
    # VMEM (of 128 on a v5e), so such a tile is given a head's share each
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=None if hp == 1 else hp * (16 << 20))


def _tiling(q, hp):
    """(width of a tile, tiles a row, tiles in all) of operand ``q``:
    ``(b*h, s, d_pad)`` with one padded head a tile (``hp`` 1), or
    ``(b, s, h*d)`` rows cut into 128-lane tiles of ``hp`` heads."""
    width = q.shape[2] if hp == 1 else 128
    G = q.shape[2] // width
    return width, G, q.shape[0] * G


def _flash_fwd_impl(q, k, v, kv_lens, seed, causal, scale, rate,
                    block_q, block_k, masked, hp=1, interpret=None):
    """q,k,v: padded inputs, ``(b*h, s, d_pad)`` or, with ``hp`` heads
    to a 128-lane tile, ``(b, s, h*d)``; returns (o, lse) padded, ``o`` shaped
    like ``q`` and ``lse`` ``(b*h, s, 1)``."""
    if interpret is None:
        interpret = interpret_mode()
    sq, sk = q.shape[1], k.shape[1]
    width, G, tiles = _tiling(q, hp)
    nq, nk = sq // block_q, sk // block_k
    kernel = functools.partial(_fwd_kernel, causal, scale, rate, sq,
                               block_q, block_k, masked, hp)
    spec = functools.partial(_specs, block_q, block_k, width, G, hp)
    o, lse = pl.pallas_call(
        kernel,
        grid=(tiles, nq, nk),
        in_specs=[spec("len"), spec("len"), spec("outer"), spec("inner"),
                  spec("inner")],
        out_specs=[spec("outer"), spec("outer_vec")],
        out_shape=[_sds(q.shape, q.dtype, q),
                   _sds((tiles * hp, sq, 1), _f32, q)],
        scratch_shapes=[pltpu.VMEM((hp * block_q, 128), _f32),
                        pltpu.VMEM((hp * block_q, 128), _f32),
                        pltpu.VMEM((block_q, width), _f32)],
        compiler_params=_compiler_params(hp),
        interpret=interpret,
    )(kv_lens, seed, q, k, v)
    return o, lse


def _flash_bwd_impl(q, k, v, o, lse, do, kv_lens, seed, causal, scale,
                    rate, block_q, block_k, true_sq, masked, hp=1,
                    interpret=None):
    """``true_sq`` is the UNPADDED query length — the dkv kernel's
    padded-row guard must compare against it, not the padded extent."""
    if interpret is None:
        interpret = interpret_mode()
    sq, sk = q.shape[1], k.shape[1]
    width, G, tiles = _tiling(q, hp)
    nq, nk = sq // block_q, sk // block_k
    spec = functools.partial(_specs, block_q, block_k, width, G, hp)
    # (so "inner", the axis summed over, is q's blocks and "outer" k's)
    q_spec = _specs(block_k, block_q, width, G, hp, "inner")
    k_spec = _specs(block_k, block_q, width, G, hp, "outer")
    if hp == 1:
        delta = jnp.sum(do.astype(_f32) * o.astype(_f32), axis=-1,
                        keepdims=True)                     # (b*h, sq, 1)
        delta_specs = spec("outer_vec"), spec("inner_vec")
    else:
        # the kernels sum a head's lanes of dO∘O themselves: _row_delta
        delta = o
        delta_specs = spec("outer"), q_spec

    dq_kernel = functools.partial(_dq_kernel, causal, scale, rate, sq,
                                  block_q, block_k, masked, hp)
    dq = pl.pallas_call(
        dq_kernel,
        grid=(tiles, nq, nk),
        in_specs=[spec("len"), spec("len"), spec("outer"), spec("inner"),
                  spec("inner"), spec("outer"), spec("outer_vec"),
                  delta_specs[0]],
        out_specs=spec("outer"),
        out_shape=_sds(q.shape, q.dtype, q),
        scratch_shapes=[pltpu.VMEM((block_q, width), _f32)],
        compiler_params=_compiler_params(hp),
        interpret=interpret,
    )(kv_lens, seed, q, k, v, do, lse, delta)

    # dk/dv: swap the roles — grid dim 1 walks k blocks, dim 2 walks q
    dkv_kernel = functools.partial(_dkv_kernel, causal, scale, rate,
                                   true_sq, block_q, block_k, masked, hp)
    dk, dv = pl.pallas_call(
        dkv_kernel,
        grid=(tiles, nk, nq),
        in_specs=[spec("len"), spec("len"), q_spec, k_spec, k_spec, q_spec,
                  spec("inner_vec"), delta_specs[1]],
        out_specs=[k_spec, k_spec],
        out_shape=[_sds(k.shape, k.dtype, k),
                   _sds(v.shape, v.dtype, v)],
        scratch_shapes=[pltpu.VMEM((block_k, width), _f32),
                        pltpu.VMEM((block_k, width), _f32)],
        compiler_params=_compiler_params(hp),
        interpret=interpret,
    )(kv_lens, seed, q, k, v, do, lse, delta)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# custom-VJP wrapper over (b, h, s, d): one padded head a tile
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9, 10))
def _flash(q, k, v, kv_seqlens, seed, causal, scale, block_q, block_k,
           rate, masked):
    out, _ = _flash_vjp_fwd(q, k, v, kv_seqlens, seed, causal, scale,
                            block_q, block_k, rate, masked)
    return out


def _flatten(q, k, v, kv_seqlens, block_q, block_k):
    b, h, sq, d = q.shape
    sk = k.shape[2]
    sq_p = _round_up(sq, block_q)
    sk_p = _round_up(sk, block_k)
    d_p = _round_up(d, 128)
    q3 = _pad_qkv(q.reshape(b * h, sq, d), sq_p, d_p)
    k3 = _pad_qkv(k.reshape(b * h, sk, d), sk_p, d_p)
    v3 = _pad_qkv(v.reshape(b * h, sk, d), sk_p, d_p)
    lens = jnp.repeat(kv_seqlens.astype(jnp.int32), h)     # (b*h,)
    return q3, k3, v3, lens


def _flash_vjp_fwd(q, k, v, kv_seqlens, seed, causal, scale, block_q,
                   block_k, rate, masked):
    b, h, sq, d = q.shape
    q3, k3, v3, lens = _flatten(q, k, v, kv_seqlens, block_q, block_k)
    o3, lse = _flash_fwd_impl(q3, k3, v3, lens, seed, causal, scale,
                              rate, block_q, block_k, masked)
    out = o3[:, :sq, :d].reshape(b, h, sq, d)
    return out, (q, k, v, kv_seqlens, seed, o3, lse)


def _flash_vjp_bwd(causal, scale, block_q, block_k, rate, masked, res, g):
    q, k, v, kv_seqlens, seed, o3, lse = res
    b, h, sq, d = q.shape
    sk = k.shape[2]
    q3, k3, v3, lens = _flatten(q, k, v, kv_seqlens, block_q, block_k)
    do3 = _pad_qkv(g.reshape(b * h, sq, d), q3.shape[1], q3.shape[2])
    dq3, dk3, dv3 = _flash_bwd_impl(q3, k3, v3, o3, lse, do3, lens, seed,
                                    causal, scale, rate, block_q, block_k,
                                    sq, masked)
    dq = dq3[:, :sq, :d].reshape(b, h, sq, d).astype(q.dtype)
    dk = dk3[:, :sk, :d].reshape(b, h, sk, d).astype(k.dtype)
    dv = dv3[:, :sk, :d].reshape(b, h, sk, d).astype(v.dtype)
    return dq, dk, dv, None, None


_flash.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


# ---------------------------------------------------------------------------
# custom-VJP wrapper over (b, s, h, d): 128 // d heads a tile, read and
# written as the (b, s, h*d) rows the QKV matmul leaves and the output
# projection takes
# ---------------------------------------------------------------------------

def _heads_per_tile(h, d):
    """Heads in one 128-lane tile of a ``(b, s, h*d)`` row, or 0 where
    such rows do not cut into whole tiles of whole heads (heads of 128
    and more, of 80 or 96, a row that is no multiple of 128)."""
    return 128 // d if d < 128 and 128 % d == 0 and (h * d) % 128 == 0 \
        else 0


# The rows' kernel calls, each jitted on its own so that a model's layers
# share one trace and one Mosaic lowering of each kernel: a tile of two
# heads is twice the body to trace, and 24 layers' 72 bodies were 3.3 s
# of every start of the BERT step (12.5-13.3 s to lower it against the
# 9.3-9.9 s of one head a tile, here on the CPU).  XLA names a custom call
# after the function that holds it, so in a trace these kernels are
# ``flash_rows_fwd`` and ``flash_rows_bwd`` (dQ, then dK and dV).  The
# interpreter's switch is an argument: it is part of what was traced.
_ROWS_STATIC = ("causal", "scale", "rate", "block_q", "block_k", "true_sq",
                "masked", "hp", "interpret")


@functools.partial(jax.jit, static_argnames=_ROWS_STATIC)
def flash_rows_fwd(q, k, v, lens, seed, **static):
    return _flash_fwd_impl(q, k, v, lens, seed, **static)


@functools.partial(jax.jit, static_argnames=_ROWS_STATIC)
def flash_rows_bwd(q, k, v, o, lse, do, lens, seed, **static):
    return _flash_bwd_impl(q, k, v, o, lse, do, lens, seed, **static)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9, 10))
def _flash_rows(q, k, v, kv_seqlens, seed, causal, scale, block_q, block_k,
                rate, masked):
    out, _ = _flash_rows_fwd(q, k, v, kv_seqlens, seed, causal, scale,
                             block_q, block_k, rate, masked)
    return out


def _rows(x, block):
    """``(b, s, h, d)`` as ``(b, s_pad, h*d)``: a bitcast where ``s`` is a
    multiple of the block."""
    b, s, h, d = x.shape
    return _pad_qkv(x.reshape(b, s, h * d), _round_up(s, block), h * d)


def _flash_rows_fwd(q, k, v, kv_seqlens, seed, causal, scale, block_q,
                    block_k, rate, masked):
    b, sq, h, d = q.shape
    hp = _heads_per_tile(h, d)
    lens = jnp.repeat(kv_seqlens.astype(jnp.int32), h // hp)  # (tiles,)
    o3, lse = flash_rows_fwd(
        _rows(q, block_q), _rows(k, block_k), _rows(v, block_k), lens, seed,
        causal=causal, scale=scale, rate=rate, block_q=block_q,
        block_k=block_k, masked=masked, hp=hp, interpret=interpret_mode())
    return o3[:, :sq].reshape(b, sq, h, d), (q, k, v, lens, seed, o3, lse)


def _flash_rows_bwd(causal, scale, block_q, block_k, rate, masked, res, g):
    q, k, v, lens, seed, o3, lse = res
    b, sq, h, d = q.shape
    sk = k.shape[1]
    dq3, dk3, dv3 = flash_rows_bwd(
        _rows(q, block_q), _rows(k, block_k), _rows(v, block_k), o3, lse,
        _rows(g, block_q), lens, seed, causal=causal, scale=scale, rate=rate,
        block_q=block_q, block_k=block_k, true_sq=sq, masked=masked,
        hp=_heads_per_tile(h, d), interpret=interpret_mode())
    return (dq3[:, :sq].reshape(q.shape).astype(q.dtype),
            dk3[:, :sk].reshape(k.shape).astype(k.dtype),
            dv3[:, :sk].reshape(v.shape).astype(v.dtype), None, None)


_flash_rows.defvjp(_flash_rows_fwd, _flash_rows_bwd)


# ---------------------------------------------------------------------------
# public API + jnp reference
# ---------------------------------------------------------------------------

def flash_attention_reference(q, k, v, causal=False, softmax_scale=None,
                              kv_seqlens=None, key_padding_mask=None,
                              dropout=0.0, dropout_rng=None,
                              dropout_mask=None):
    """Materialized-scores reference with identical masking semantics —
    the unfused baseline every fused op is tested against, and the
    single fallback for features the flash kernel cannot express
    (arbitrary ``key_padding_mask``; contrib ``multihead_attn``/``fmha``
    delegate here for those).

    ``key_padding_mask``: ``(b, sk)`` bool, True = masked out (apex
    convention).  A fully masked row yields a zero output, matching the
    kernel's ``l == 0`` guard.

    Dropout: ``dropout_mask`` is an explicit ``(b, h, sq, sk)``
    keep-scale matrix multiplied into the probabilities (how the fused
    kernel's hash mask is replayed for parity tests / the jnp fallback);
    ``dropout``+``dropout_rng`` is the ``jax.random`` variant.
    """
    b, h, sq, d = q.shape
    sk = k.shape[2]
    scale = softmax_scale if softmax_scale is not None else d ** -0.5
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(_f32),
                   k.astype(_f32)) * scale
    k_pos = jnp.arange(sk)
    valid = jnp.ones((b, 1, 1, sk), bool) if kv_seqlens is None else (
        k_pos[None, :] < kv_seqlens[:, None])[:, None, None, :]
    if key_padding_mask is not None:
        valid = valid & ~key_padding_mask[:, None, None, :]
    if causal:
        valid = valid & (k_pos[None, None, None, :]
                         <= jnp.arange(sq)[None, None, :, None])
    s = jnp.where(valid, s, _MASK)
    p = jax.nn.softmax(s, axis=-1)
    p = jnp.where(valid, p, 0.0)
    if dropout_mask is not None:
        p = p * dropout_mask.astype(p.dtype)
    elif dropout > 0.0:
        if dropout_rng is None:
            raise ValueError("dropout > 0 needs dropout_rng")
        keep = jax.random.bernoulli(dropout_rng, 1.0 - dropout, p.shape)
        p = jnp.where(keep, p / (1.0 - dropout), 0.0)
    return jnp.einsum("bhqk,bhkd->bhqd", p.astype(v.dtype), v)


# ---------------------------------------------------------------------------
# single-query decode path (KV-cache inference)
# ---------------------------------------------------------------------------
#
# Autoregressive decode attends ONE query token per sequence against the
# accumulated KV cache — there is no O(s^2) score matrix and no backward
# pass.  The decode kernels keep the flash forward's online-softmax
# accumulation over a grid of (batch, k_blocks), reading K/V directly in
# the cache layout ``(batch, max_seq, heads, head_dim)`` so no transpose
# or lane padding of the cache ever materializes.  Every grid step takes
# ALL heads of one K block: Mosaic requires a block's last two dims to be
# (8, 128)-divisible or equal to the array's, and ``(heads, head_dim)``
# taken whole is the only blocking of that minor pair that holds for
# head_dim 64.  With one query row per head there is nothing for the MXU
# to batch, so scores and PV are VPU broadcast-multiply-reduce in f32
# (decode is bound by the cache bytes, not by these flops).  Blocks
# entirely past the row's length are skipped at runtime (the decode-side
# analogue of the causal block skip).  The paged kernel reads the serving
# pool instead, whose rows are ``heads * head_dim`` wide, and has no grid
# axis over the cache at all: a grid step per row, and inside it a loop
# over the blocks the row's length says its table holds, fetched by the
# kernel's own DMAs (a skipped grid step still cost 0.1-0.2 us, and a
# serving tick held 2 % of its 32 x 128 table entries): see
# :func:`flash_attention_decode_paged`.

# VMEM one K (or V) block may take; the pipeline holds two of each.
_DECODE_BLOCK_BYTES = 1 << 20


def _decode_block_k(requested, S_pad, h, d, dtype):
    """Largest K block <= ``requested`` that divides ``S_pad`` and whose
    VMEM tile-padded footprint fits :data:`_DECODE_BLOCK_BYTES`."""
    itemsize = jnp.dtype(dtype).itemsize
    per_pos = (_round_up(h, 8 * (4 // itemsize)) * _round_up(d, 128)
               * itemsize)
    cap = min(int(requested), max(128, _DECODE_BLOCK_BYTES // per_pos))
    for cand in (512, 384, 256, 128):
        if cand <= cap and S_pad % cand == 0:
            return cand
    return 128


def _decode_init(m_scr, l_scr, acc_scr):
    m_scr[:] = jnp.full_like(m_scr[:], _MASK)
    l_scr[:] = jnp.zeros_like(l_scr[:])
    acc_scr[:] = jnp.zeros_like(acc_scr[:])


def _decode_accumulate(scale, base, n_valid, q, k, v, m_scr, l_scr,
                       acc_scr):
    """Fold one K/V block into the running softmax state.

    ``q``: ``(h, d)``; ``k``/``v``: ``(block, h, d)`` holding key
    positions ``base .. base + block``; positions ``>= n_valid`` are
    masked.  State: ``m_scr``/``l_scr`` ``(h, 128)`` lane-broadcast row
    max / row sum, ``acc_scr`` ``(h, d)`` f32.
    """
    s = jnp.sum(k.astype(_f32) * q.astype(_f32)[None], axis=-1,
                keepdims=True) * scale                     # (block, h, 1)
    k_pos = base + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    valid = k_pos < n_valid
    s = jnp.where(valid, s, _MASK)
    m_prev = m_scr[:, :1]                                  # (h, 1)
    m_cur = jnp.maximum(jnp.max(s, axis=0), m_prev)
    alpha = jnp.exp(m_prev - m_cur)
    p = jnp.where(valid, jnp.exp(s - m_cur[None]), 0.0)    # (block, h, 1)
    l_cur = alpha * l_scr[:, :1] + jnp.sum(p, axis=0)
    acc_scr[:] = acc_scr[:] * alpha + jnp.sum(p * v.astype(_f32), axis=0)
    m_scr[:] = jnp.broadcast_to(m_cur, m_scr.shape)
    l_scr[:] = jnp.broadcast_to(l_cur, l_scr.shape)


def _decode_finish(o_ref, l_scr, acc_scr):
    l = l_scr[:, :1]
    l_safe = jnp.where(l == 0.0, 1.0, l)
    o_ref[0] = (acc_scr[:] / l_safe).astype(o_ref.dtype)


def _decode_scratch(h, d):
    return [pltpu.VMEM((h, 128), _f32), pltpu.VMEM((h, 128), _f32),
            pltpu.VMEM((h, d), _f32)]


_DECODE_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "arbitrary"))


def _decode_kernel(scale, block_k, len_ref, q_ref, k_ref, v_ref, o_ref,
                   m_scr, l_scr, acc_scr):
    b = pl.program_id(0)
    ki = pl.program_id(1)

    @pl.when(ki == 0)
    def _init():
        _decode_init(m_scr, l_scr, acc_scr)

    @pl.when(ki * block_k < len_ref[b])
    def _compute():
        _decode_accumulate(scale, ki * block_k, len_ref[b], q_ref[0],
                           k_ref[0], v_ref[0], m_scr, l_scr, acc_scr)

    @pl.when(ki == pl.num_programs(1) - 1)
    def _finish():
        _decode_finish(o_ref, l_scr, acc_scr)


def flash_attention_decode_reference(q, k_cache, v_cache, cache_lens,
                                     softmax_scale=None):
    """Materialized single-query reference over the cache layout — the
    off-TPU decode path and the parity baseline for the Pallas kernel.

    ``q``: ``(batch, heads, head_dim)`` (one token per sequence);
    ``k_cache``/``v_cache``: ``(batch, max_seq, heads, head_dim)``;
    ``cache_lens``: ``(batch,)`` valid lengths (the query's own position
    is ``cache_lens - 1``).  Scores and the PV reduction run in f32
    regardless of the cache dtype (bf16 cache, f32 accumulation).
    """
    b, S, h, d = k_cache.shape
    scale = softmax_scale if softmax_scale is not None else d ** -0.5
    s = jnp.einsum("bhd,bshd->bhs", q.astype(_f32),
                   k_cache.astype(_f32)) * scale
    valid = (jnp.arange(S)[None, :]
             < cache_lens[:, None])[:, None, :]    # (b, 1, S)
    s = jnp.where(valid, s, _MASK)
    p = jax.nn.softmax(s, axis=-1)
    p = jnp.where(valid, p, 0.0)
    o = jnp.einsum("bhs,bshd->bhd", p, v_cache.astype(_f32))
    return o.astype(q.dtype)


def flash_attention_decode(q, k_cache, v_cache, cache_lens,
                           softmax_scale=None, block_k=512):
    """Single-token decode attention against a KV cache.

    ``q``: ``(batch, heads, head_dim)`` — the current token's query;
    ``k_cache``/``v_cache``: ``(batch, max_seq, heads, head_dim)`` — the
    preallocated cache INCLUDING the current token's K/V (write before
    attending); ``cache_lens``: ``(batch,)`` int, number of valid cache
    entries per row.  Entries at positions >= ``cache_lens`` are masked;
    causality is implied (every cached position <= the query's).

    Returns ``(batch, heads, head_dim)`` in ``q.dtype``; accumulation is
    f32 whatever the cache dtype.  On TPU a Pallas single-query kernel
    reads the cache layout directly; off-TPU the masked jnp reference
    runs (identical semantics, unit-tested against each other).
    """
    b, h, d = q.shape
    S = k_cache.shape[1]
    scale = float(softmax_scale if softmax_scale is not None
                  else d ** -0.5)
    cache_lens = cache_lens.astype(jnp.int32)
    if not use_pallas():
        return flash_attention_decode_reference(q, k_cache, v_cache,
                                                cache_lens, scale)
    S_pad = _round_up(S, 128)
    block_k = _decode_block_k(block_k, S_pad, h, d, k_cache.dtype)

    def _pad_cache(c):
        if S == S_pad:
            return c
        return jnp.pad(c, ((0, 0), (0, S_pad - S), (0, 0), (0, 0)))

    kernel = functools.partial(_decode_kernel, scale, block_k)
    qo_spec = pl.BlockSpec((1, h, d), lambda bi, ki: (bi, 0, 0),
                           memory_space=pltpu.VMEM)
    kv_spec = pl.BlockSpec((1, block_k, h, d),
                           lambda bi, ki: (bi, ki, 0, 0),
                           memory_space=pltpu.VMEM)
    return pl.pallas_call(
        kernel,
        grid=(b, S_pad // block_k),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                  qo_spec, kv_spec, kv_spec],
        out_specs=qo_spec,
        out_shape=_sds((b, h, d), q.dtype, q),
        scratch_shapes=_decode_scratch(h, d),
        compiler_params=_DECODE_PARAMS,
        interpret=interpret_mode(),
    )(cache_lens, q, _pad_cache(k_cache), _pad_cache(v_cache))


def gather_paged_kv(pool, layer_index, kv, block_tables, heads):
    """Materialize one layer's K (``kv=0``) or V (``kv=1``) of a paged
    cache as the contiguous layout.

    ``pool``: ``(num_blocks, layers, 2, block_size, heads * head_dim)``,
    the whole of :class:`apex_tpu.serving.PagedKVCache`'s storage;
    ``block_tables``: ``(batch, max_blocks)`` int.  Returns
    ``(batch, max_blocks * block_size, heads, head_dim)`` — positions
    map as ``p -> (table[p // bs], p % bs)``, so the gathered array is
    elementwise IDENTICAL to a contiguous cache at every valid position
    (garbage-block rows land at masked positions).  This is the off-TPU
    paged path and the parity bridge to the contiguous kernels.
    """
    b, nb = block_tables.shape
    bs, hd = pool.shape[3:]
    return pool[block_tables, layer_index, kv].reshape(
        b, nb * bs, heads, hd // heads)


def scatter_paged_kv(pool, layer_index, kv, block_ids, offsets, x):
    """Write tokens' K (``kv=0``) or V (``kv=1``) where they lie in the
    pool: ``x`` ``(..., heads, head_dim)`` goes to the rows
    ``pool[block_ids, layer_index, kv, offsets]`` (index arrays shaped
    like ``x``'s leading axes), cast to the pool's dtype.  The inverse of
    :func:`gather_paged_kv` at those positions.

    On the TPU the update is the merged ``heads * head_dim`` row: the
    pool must never be seen with ``head_dim`` minor there.  Off the TPU
    the same bytes are addressed through a ``(..., heads, head_dim)``
    view of the pool (a bitcast of a row-major array), so ``x`` reaches
    the scatter in the shape the contiguous cache's write gives it.
    XLA:CPU fuses whatever produced ``x`` (the rotary rotation) into the
    update's loop and contracts its multiply-add by the loop's shape;
    with the shapes equal, a paged K is bitwise the contiguous K.
    """
    lead = x.shape[:-2]
    if use_pallas():
        return pool.at[block_ids, layer_index, kv, offsets].set(
            x.reshape(*lead, -1).astype(pool.dtype))
    view = pool.reshape(*pool.shape[:-1], *x.shape[-2:])
    return view.at[block_ids, layer_index, kv, offsets].set(
        x.astype(pool.dtype)).reshape(pool.shape)


def _lane_group_sum(x, group):
    """Sum every ``group`` consecutive lanes of ``x`` ``(rows, lanes)``
    and leave the sum in each lane of its group: per 128-lane register
    a butterfly of ``log2(group)`` rotate-select-adds on the VPU/XLU,
    plain f32.  A lane's partner at distance ``shift`` never leaves its
    group, so the rotation's wrap-around is never selected.  This is how
    the paged kernel gets per-head sums out of a lane-dense
    ``heads * head_dim`` row: Mosaic refuses the reshape to
    ``(heads, head_dim)`` that would split the lanes.  (On the v5e a
    0/1 selector matmul on the MXU at ``Precision.HIGHEST`` timed the
    same and one rotation over all the lanes 11 % slower.)"""
    lane = jax.lax.broadcasted_iota(jnp.int32, (x.shape[0], 128), 1)
    sums = []
    for c in range(x.shape[1] // 128):
        xc = x[:, c * 128:(c + 1) * 128]
        shift = 1
        while shift < group:
            xc = xc + jnp.where((lane & shift) != 0,
                                pltpu.roll(xc, shift, 1),
                                pltpu.roll(xc, 128 - shift, 1))
            shift *= 2
        sums.append(xc)
    return jnp.concatenate(sums, axis=1)


# Blocks that one step of the paged kernel's loop brings to VMEM (64
# positions at a block size of 8), and the positions folded into the
# softmax at once: one packed bf16 tile of 16 rows.  On the v5e, full
# tables of 128 blocks of 8, 24 calls: 34.2 ms at 16 positions, 57.2 at 8
# (half a tile converts like a whole one), 47.9 at 32 and 43.8 at 64 (the
# operands leave the vector registers); groups of 4 or 16 time as 8 does.
_PAGED_GROUP = 8
_PAGED_FOLD_ROWS = 16


def _decode_paged_kernel(bs, h, d, group, fold, len_ref, tbl_ref, layer_ref,
                         q_ref, pool_ref, o_ref, kv_buf, sem, m_scr, l_scr,
                         acc_scr):
    """Single-query decode of ONE row over the blocks its table HOLDS.

    The pool stays in HBM.  Row ``b`` (the grid's only axis) holds
    ``ceil(len[b] / block_size)`` blocks, and the loop walks them in
    groups of ``group``: each block is one DMA of
    ``pool[tbl[b, k], layer]`` (its K and V lie side by side) into a
    double-buffered VMEM scratch, and the next group is in flight while
    this one is folded, ``fold`` blocks at a time, into the online
    softmax of :func:`_decode_kernel`.  The trip count is data: nothing
    depends on the table's width, and an entry past the row's length is
    neither read nor computed on.  A row whose first entry is block 0
    (the pool's reserved garbage block: a slot that holds no request)
    starts no DMA and writes zeros.  Every head's state is kept
    broadcast over that head's ``head_dim`` lanes (``m``, ``l``, ``acc``
    are ``(1, heads * head_dim)``), f32 throughout; ``q_ref`` comes
    scaled.
    """
    b = pl.program_id(0)
    n = len_ref[b]
    layer = layer_ref[0]
    rows = fold * bs
    n_blocks = jnp.where(tbl_ref[b, 0] == 0, 0, pl.cdiv(n, bs))

    def dma(g, slot, act):
        for j in range(group):
            k = g * group + j

            @pl.when(k < n_blocks)
            def _block():
                act(pltpu.make_async_copy(
                    pool_ref.at[tbl_ref[b, k], layer],
                    kv_buf.at[slot * group + j], sem.at[slot]))

    def fold_blocks(first, at):
        """Blocks ``first .. first + fold`` of the row, which lie at
        ``kv_buf[at]``; the last of them may be ragged or absent (then
        the scratch holds what it held: masked, V too, ``0 * NaN``)."""
        k = kv_buf[at, 0].astype(_f32).reshape(rows, h * d)
        v = kv_buf[at, 1].astype(_f32).reshape(rows, h * d)
        s = _lane_group_sum(k * q_ref[0], d)
        k_pos = first * bs + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        valid = k_pos < n
        s = jnp.where(valid, s, _MASK)
        m_prev = m_scr[:]                                  # (1, h*d)
        m_cur = jnp.maximum(jnp.max(s, axis=0, keepdims=True), m_prev)
        alpha = jnp.exp(m_prev - m_cur)
        p = jnp.where(valid, jnp.exp(s - m_cur), 0.0)      # (rows, h*d)
        l_scr[:] = alpha * l_scr[:] + jnp.sum(p, axis=0, keepdims=True)
        acc_scr[:] = alpha * acc_scr[:] + jnp.sum(
            jnp.where(valid, p * v, 0.0), axis=0, keepdims=True)
        m_scr[:] = m_cur

    def fold_group(g, carry):
        slot = g % 2

        @pl.when((g + 1) * group < n_blocks)
        def _prefetch():
            dma(g + 1, 1 - slot, lambda copy: copy.start())

        dma(g, slot, lambda copy: copy.wait())
        for c in range(0, group, fold):
            pl.when(g * group + c < n_blocks)(functools.partial(
                fold_blocks, g * group + c,
                pl.ds(slot * group + c, fold)))
        return carry

    @pl.when(n_blocks == 0)
    def _empty():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(n_blocks > 0)
    def _live():
        dma(0, 0, lambda copy: copy.start())
        _decode_init(m_scr, l_scr, acc_scr)
        jax.lax.fori_loop(0, pl.cdiv(n_blocks, group), fold_group, None)
        o = acc_scr[:] / l_scr[:]                          # (1, h*d)
        # back to (h, d) without a lane-splitting reshape: row ``i``
        # keeps head i's lanes, the 128-lane columns are summed, and a
        # rotate-add tree brings every head's values to lanes [0, d)
        row = jax.lax.broadcasted_iota(jnp.int32, (h, h * d), 0)
        lane = jax.lax.broadcasted_iota(jnp.int32, (h, h * d), 1)
        spread = jnp.where(lane // d == row, o, 0.0)
        width = max(d, 128)
        out = spread[:, :width]
        for c in range(1, h * d // width):
            out = out + spread[:, c * width:(c + 1) * width]
        shift = d
        while shift < width:
            out = out + pltpu.roll(out, shift, 1)
            shift *= 2
        o_ref[0] = out[:, :d].astype(o_ref.dtype)


def _paged_kernel_reads(h, d):
    """Whether the lane-dense paged kernel can read rows of ``h * d``:
    whole 128-lane registers, each holding whole heads.  That is every
    width with ``head_dim`` 32, 64 or 128 and a row of at least 128
    lanes; outside it lie ``head_dim`` 80 and 96 (a head straddles two
    registers) and 256 (a head is wider than one).  On a TPU such a
    width raises and nothing gathers quietly; the toy widths of the CPU
    tests take the gather path."""
    return (h * d) % 128 == 0 and d <= 128 and d & (d - 1) == 0


def flash_attention_decode_paged(q, pool, layer_index, block_tables,
                                 cache_lens, softmax_scale=None, window=None):
    """Single-token decode attention over a paged KV pool, read in place.

    ``q``: ``(batch, heads, head_dim)``; ``pool``: the WHOLE pool of
    :class:`apex_tpu.serving.PagedKVCache`,
    ``(num_blocks, layers, 2, block_size, heads * head_dim)``, of which
    layer ``layer_index`` is attended;
    ``block_tables``: ``(batch, max_blocks)`` int32 physical block ids
    per logical block (entries past a row's length are never read; a
    row that holds no request is all block 0, the pool's reserved
    garbage block);
    ``cache_lens``: ``(batch,)`` valid lengths.

    Semantics are exactly :func:`flash_attention_decode` on the gathered
    contiguous cache — and the off-TPU path literally IS that: gather +
    the same masked reference, which is what makes paged decode
    token-bitwise-identical to the contiguous engine on CPU.  On TPU a
    Pallas kernel (:func:`_decode_paged_kernel`) leaves the pool in HBM
    and, row by row, brings in the ``ceil(len / block_size)`` blocks the
    table holds for it: its work follows the tokens in the batch, not
    ``batch x max_blocks``, and one compiled program serves every fill.
    Neither the gather nor a slice of the layer ever materializes, and
    because the rows are lane-dense (a multiple of 128 wide) the pool
    keeps the row-major layout Mosaic demands — a minor dimension of
    ``head_dim`` 64 made XLA relay the whole pool before and after every
    step.  One difference from the gather path, in rows nobody reads: a
    row whose table starts with block 0 comes back as zeros from the
    kernel (it is skipped) and as attention over the garbage block from
    the gather.

    A pool of fewer KV heads than ``q`` has (grouped heads), or a
    ``window`` (the row attends to its last ``window`` positions alone),
    takes :func:`grouped_decode` on a TPU and
    :func:`grouped_decode_reference` elsewhere.
    """
    b, h, d = q.shape
    scale = float(softmax_scale if softmax_scale is not None
                  else d ** -0.5)
    cache_lens = cache_lens.astype(jnp.int32)
    block_tables = block_tables.astype(jnp.int32)
    if pool.shape[-1] != h * d or window is not None:
        if use_pallas() and not d % 128:
            return grouped_decode(
                cache_lens, block_tables,
                jnp.full((1,), layer_index, jnp.int32), q, pool,
                window=window, scale=scale, interpret=interpret_mode())
        return grouped_decode_reference(q, pool, layer_index, block_tables,
                                        cache_lens, window, scale)
    kernel_reads = _paged_kernel_reads(h, d)
    if use_pallas() and not kernel_reads and not interpret_mode():
        raise ValueError(
            f"flash_attention_decode_paged: the TPU kernel cannot read "
            f"rows of {h} heads x head_dim {d} (it needs head_dim a power "
            f"of two up to 128 and heads * head_dim a multiple of 128); "
            f"the gather path is for off-TPU runs only")
    if not (use_pallas() and kernel_reads):
        return flash_attention_decode_reference(
            q, gather_paged_kv(pool, layer_index, 0, block_tables, h),
            gather_paged_kv(pool, layer_index, 1, block_tables, h),
            cache_lens, scale)
    return decode_step_paged(
        cache_lens, block_tables, jnp.full((1,), layer_index, jnp.int32), q,
        pool, scale=scale, interpret=interpret_mode())


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def decode_step_paged(cache_lens, block_tables, layer, q, pool, *, scale,
                      interpret):
    """The kernel's call, jitted on its own with the layer as data, so
    that a model's layers share one trace and one lowering of the kernel
    (24 separate ones were 13 s of every process's set-up here, against
    1.9 s).  XLA names a custom call after the function that holds it:
    this one keeps the name the serving tick's kernels have had in every
    trace, ``%decode_step_paged.N``, by which the benchmark finds them.
    """
    b, h, d = q.shape
    bs, hd = pool.shape[3:]
    kernel = functools.partial(
        _decode_paged_kernel, bs, h, d, _PAGED_GROUP,
        min(_PAGED_GROUP, max(1, _PAGED_FOLD_ROWS // bs)))
    q_spec = pl.BlockSpec((1, 1, hd), lambda bi, *prefetched: (bi, 0, 0),
                          memory_space=pltpu.VMEM)
    o_spec = pl.BlockSpec((1, h, d), lambda bi, *prefetched: (bi, 0, 0),
                          memory_space=pltpu.VMEM)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b,),
        in_specs=[q_spec, pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=o_spec,
        scratch_shapes=[pltpu.VMEM((2 * _PAGED_GROUP, 2, bs, hd), pool.dtype),
                        pltpu.SemaphoreType.DMA((2,))]
        + [pltpu.VMEM((1, hd), _f32)] * 3)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=_sds((b, h, d), q.dtype, q),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(cache_lens, block_tables, layer,
      (q.astype(_f32) * scale).reshape(b, 1, hd), pool)


def flash_attention_chunk_paged(q, pool, layer_index, block_tables,
                                q_positions, softmax_scale=None):
    """Multi-query decode attention over a paged pool (chunked prefill
    and speculative verification).

    ``q``: ``(batch, heads, chunk, head_dim)`` — ``chunk`` query tokens
    per sequence, NOT necessarily starting at position 0;
    ``q_positions``: ``(batch, chunk)`` each query's absolute position.
    Key position ``kp`` is visible to query ``j`` iff
    ``kp <= q_positions[:, j]`` — causality over the whole cached
    context, matching prefill exactly for in-order chunks.  Pool, layer
    and tables as in :func:`flash_attention_decode_paged`; the chunk's
    own K/V must be written to the pool before the call.

    Runs as a masked jnp gather on every backend (chunks are short and
    wide enough that XLA fuses this well; the single-token fast path is
    the Pallas kernel above).  f32 scores/accumulation as everywhere.
    """
    b, h, c, d = q.shape
    scale = softmax_scale if softmax_scale is not None else d ** -0.5
    k = gather_paged_kv(pool, layer_index, 0, block_tables, h)
    v = gather_paged_kv(pool, layer_index, 1, block_tables, h)
    S = k.shape[1]
    s = jnp.einsum("bhcd,bshd->bhcs", q.astype(_f32),
                   k.astype(_f32)) * scale
    valid = (jnp.arange(S)[None, None, None, :]
             <= q_positions[:, None, :, None])    # (b, 1, c, S)
    s = jnp.where(valid, s, _MASK)
    p = jax.nn.softmax(s, axis=-1)
    p = jnp.where(valid, p, 0.0)
    o = jnp.einsum("bhcs,bshd->bhcd", p, v.astype(_f32))
    return o.astype(q.dtype)


def quantize_kv_blocks(blocks):
    """Int8 scale-per-block quantization of KV blocks (the EQuARX idiom
    from ``utils.compressed_allreduce``, applied to the paged cache).

    ``blocks``: ``(..., block_size, heads, head_dim)`` float — any
    leading batch/layer/kv axes.  The scale is shared across the block's
    positions and head_dim but kept PER HEAD (attention scores are
    per-head dot products, so a hot head cannot inflate a cold head's
    quantization step).  Returns ``(q8, scales)`` with ``q8`` int8 of
    ``blocks.shape`` and ``scales`` f32 of ``blocks.shape[:-3] +
    (heads,)``.  All-zero blocks get scale 1.0, so dequantization is
    exact zeros — the zero-on-alloc invariant the quantized pool relies
    on for deterministic whole-block requantization.
    """
    x = blocks.astype(_f32)
    amax = jnp.max(jnp.abs(x), axis=(-3, -1))        # (..., heads)
    scale = amax / 127.0
    scale = jnp.where(scale > 0, scale, 1.0)
    q8 = jnp.clip(jnp.round(x / scale[..., None, :, None]),
                  -127, 127).astype(jnp.int8)
    return q8, scale


def dequantize_kv_blocks(q8, scales, dtype=jnp.float32):
    """Inverse of :func:`quantize_kv_blocks`: ``q8``
    ``(..., block_size, heads, head_dim)`` int8, ``scales``
    ``(..., heads)`` f32, returns ``dtype``."""
    return (q8.astype(_f32) * scales[..., None, :, None]).astype(dtype)


def gather_paged_kv_quant(pool, scales, layer_index, kv, block_tables,
                          dtype=jnp.float32):
    """:func:`gather_paged_kv` for an int8 pool: gather the table's
    blocks AND their per-block scales, dequantize only what was
    gathered, and return the contiguous layout in ``dtype``.

    ``pool``: ``(num_blocks, layers, 2, block_size, heads * head_dim)``
    int8; ``scales``: ``(num_blocks, layers, 2, heads)`` f32;
    ``block_tables``: ``(batch, max_blocks)`` int.  Returns
    ``(batch, max_blocks * block_size, heads, head_dim)``.
    """
    b, nb = block_tables.shape
    bs, hd = pool.shape[3:]
    h = scales.shape[-1]
    deq = dequantize_kv_blocks(
        pool[block_tables, layer_index, kv].reshape(b, nb, bs, h, hd // h),
        scales[block_tables, layer_index, kv], dtype)
    return deq.reshape(b, nb * bs, h, hd // h)


def flash_attention_decode_paged_quant(q, pool, scales, layer_index,
                                       block_tables, cache_lens,
                                       softmax_scale=None):
    """Single-token decode attention over an int8 paged pool.

    Same contract as :func:`flash_attention_decode_paged` with the pool
    quantized: ``pool`` int8, ``scales``
    ``(num_blocks, layers, 2, heads)`` f32.  Dequantization rides the
    gather path —
    only the table's blocks are dequantized (into f32, the same
    precision the reference's scores/PV already accumulate in), then the
    masked reference runs unchanged, so the quantized decode differs
    from the bf16/f32 decode ONLY by the per-block rounding, never by
    schedule.  A fused Pallas kernel that dequantizes in-VMEM per block
    is a straightforward extension of ``_decode_paged_kernel`` (the
    scale is one scalar per (block, head)); the gather path keeps CI
    exact and backend-uniform.
    """
    cache_lens = cache_lens.astype(jnp.int32)
    block_tables = block_tables.astype(jnp.int32)
    scale = float(softmax_scale if softmax_scale is not None
                  else q.shape[-1] ** -0.5)
    return flash_attention_decode_reference(
        q, gather_paged_kv_quant(pool, scales, layer_index, 0,
                                 block_tables, _f32),
        gather_paged_kv_quant(pool, scales, layer_index, 1,
                              block_tables, _f32),
        cache_lens, scale)


def _fit(requested, s):
    """Largest block <= ``requested`` that divides ``s`` padded to 128.

    Big default blocks amortize Mosaic grid-step overhead: a sweep on a
    machine of an earlier round (no record kept) had (1024,1024) beating
    (512,512) by ~12% at seq 1024/2048 fwd+bwd and (512,512) optimal at
    seq 512 — grid-step overhead dominates the causal block-skip saving.
    Taking the largest candidate that divides the padded sequence keeps
    arbitrary lengths (e.g. 640) from inflating padding to a whole large
    block."""
    s_pad = _round_up(s, 128)
    for cand in (requested, 512, 384, 256, 128):
        if cand <= requested and s_pad % cand == 0:
            return cand
    return min(requested, s_pad)


def _scale_and_rate(sq, sk, d, causal, softmax_scale, dropout,
                    dropout_seed):
    """The softmax scale and the dropout rate as floats, checked."""
    if causal and sq != sk:
        raise ValueError("causal flash attention requires sq == sk")
    scale = float(softmax_scale if softmax_scale is not None
                  else d ** -0.5)
    rate = float(dropout)
    if not 0.0 <= rate < 1.0:
        raise ValueError(f"dropout must be in [0, 1), got {rate}")
    if rate > 0.0 and dropout_seed is None:
        raise ValueError("dropout > 0 needs dropout_seed")
    return scale, rate


def _kernel_args(b, sq, sk, causal, scale, rate, kv_seqlens, block_q,
                 block_k, dropout_seed):
    """What :func:`_flash` and :func:`_flash_rows` take after the
    operands: ``(kv_seqlens, seed, causal, scale, block_q, block_k, rate,
    masked)``, lengths and seed defaulted, blocks fitted."""
    has_lens = kv_seqlens is not None
    if kv_seqlens is None:
        kv_seqlens = jnp.full((b,), sk, jnp.int32)
    seed = jnp.reshape(jnp.asarray(
        0 if dropout_seed is None else dropout_seed, jnp.int32), (1,))
    block_q = _fit(int(block_q), sq)
    block_k = _fit(int(block_k), sk)
    # static no-mask fast path: dense full-length non-causal attention
    # with block-aligned extents (post-_fit) needs NO iota/compare/
    # select passes in any of the three kernels (zero-padding of
    # head_dim is harmless: padded lanes contribute 0 to every dot)
    masked = bool(causal or has_lens or sq % block_q or sk % block_k)
    return (kv_seqlens, seed, bool(causal), scale, block_q, block_k, rate,
            masked)


def flash_attention(q, k, v, causal=False, softmax_scale=None,
                    kv_seqlens=None, block_q=1024, block_k=1024,
                    dropout=0.0, dropout_seed=None):
    """Fused attention over ``(batch, heads, seq, head_dim)`` operands.

    ``causal=True`` applies the upper-triangular mask (requires
    ``sq == sk``); ``kv_seqlens`` is an optional ``(batch,)`` int array of
    valid key lengths (True padding parity with the reference's
    ``cu_seqlens`` packing).  ``softmax_scale`` defaults to
    ``head_dim**-0.5``.

    ``dropout``: probability dropout fused into the kernel (reference:
    apex's philox-fused attention dropout) — the keep mask is a
    counter-hash of ``(dropout_seed, batch*head, q_pos, k_pos)``
    regenerated in the backward, so memory stays O(s).  ``dropout_seed``
    is an int (or traced int scalar); fold the training step counter in
    for fresh masks per step.  The mask is identical on every backend
    and for every block-size choice.

    Layout: the kernels see ``(batch*heads, seq, head_dim)`` with
    ``head_dim`` zero-padded to a multiple of 128, one head a grid step.
    Heads of 128 (and 256) are read as they lie; a head of 64 is padded
    to twice its bytes, and a caller that holds ``(batch, seq, heads,
    head_dim)`` pays a transpose each way besides:
    :func:`flash_attention_bshd` takes that layout and pads nothing.
    """
    b, h, sq, d = q.shape
    sk = k.shape[2]
    scale, rate = _scale_and_rate(sq, sk, d, causal, softmax_scale, dropout,
                                  dropout_seed)
    if not use_pallas():
        mask = None
        if rate > 0.0:
            mask = dropout_keep_scale(dropout_seed, b * h, sq, sk,
                                      rate).reshape(b, h, sq, sk)
        return flash_attention_reference(q, k, v, causal, scale,
                                         kv_seqlens, dropout_mask=mask)
    return _flash(q, k, v, *_kernel_args(
        b, sq, sk, causal, scale, rate, kv_seqlens, block_q, block_k,
        dropout_seed))


def flash_attention_bshd(q, k, v, causal=False, softmax_scale=None,
                         kv_seqlens=None, block_q=1024, block_k=1024,
                         dropout=0.0, dropout_seed=None):
    """:func:`flash_attention` over ``(batch, seq, heads, head_dim)``
    operands, returning ``(batch, seq, heads, head_dim)``: the layout a
    fused QKV projection leaves and an output projection takes, each a
    reshape away from ``(batch, seq, heads * head_dim)`` rows.  Same
    arguments, same mathematics, same dropout mask.

    Where those rows cut into whole 128-lane tiles of whole heads
    (``head_dim`` under 128 and dividing it, ``heads * head_dim`` a
    multiple of 128: heads of 32 and 64) the kernels read and write the
    rows in place, one tile of ``128 // head_dim`` heads a grid step:
    nothing is transposed to heads-major and nothing is padded to 128
    lanes, and the output kept for the backward is the output.  (A row
    that is a multiple of 128 wide is row-major by the TPU's default
    layout, which is what a Mosaic operand must be; an array with 64
    minor is not, and XLA copies it on the way in and out.)  A tile's
    heads are kept apart by masking one operand of each product to a
    head's lanes (:func:`_head_lanes`); each product is the MXU pass it
    is at a padded head.  Every other shape (heads of 128 and more, of
    80 or 96, a narrower row) is transposed and takes
    :func:`flash_attention`'s operands and kernels; so does every shape
    off the TPU, where both are the jnp reference.  The rule reads the
    operands' shape and nothing else."""
    b, sq, h, d = q.shape
    if not (use_pallas() and _heads_per_tile(h, d)):
        return flash_attention(
            q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
            v.transpose(0, 2, 1, 3), causal, softmax_scale, kv_seqlens,
            block_q, block_k, dropout, dropout_seed).transpose(0, 2, 1, 3)
    sk = k.shape[1]
    scale, rate = _scale_and_rate(sq, sk, d, causal, softmax_scale, dropout,
                                  dropout_seed)
    return _flash_rows(q, k, v, *_kernel_args(
        b, sq, sk, causal, scale, rate, kv_seqlens, block_q, block_k,
        dropout_seed))


# ---------------------------------------------------------------------------
# grouped heads and a sliding window, on a served model's two paths
# ---------------------------------------------------------------------------
#
# A model whose query heads share KV heads (``heads // kv_heads`` queries a
# KV head) and whose window layers attend to the ``window`` positions up to
# and including their own (key ``j`` visible to row ``i`` iff ``i - window <
# j <= i``).  Both kernels read a query head's KV head through an index, so
# K and V are never broadcast to the query heads, and both visit only the
# key blocks that overlap a row's band: a block wholly outside it is never
# fetched, and only the blocks on the diagonal and on the band's lower edge
# are masked.

_WINDOW_BLOCK = 1024        # row and key block of the prefill kernel
_WINDOW_VMEM = 64 << 20


def _band(qi, block_q, block_k, window):
    """``(first, last)`` key blocks that rows ``qi * block_q ..`` see:
    ``last`` on the diagonal, ``first`` holding the first row's oldest key
    (block 0 without a window).  ``qi`` is an int or a traced scalar."""
    last = ((qi + 1) * block_q - 1) // block_k
    if window is None:
        return 0, last
    return jnp.maximum(qi * block_q - window + 1, 0) // block_k, last


def _band_steps(s, block_q, block_k, window):
    """Key blocks the widest band spans: the kernel's innermost grid."""
    if window is None:
        return s // block_k
    steps = 0
    for qi in range(s // block_q):
        last = ((qi + 1) * block_q - 1) // block_k
        steps = max(steps, last - max(qi * block_q - window + 1, 0)
                    // block_k + 1)
    return steps


def _window_flash_kernel(scale, block_q, block_k, window, q_ref, k_ref,
                         v_ref, o_ref, m_scr, l_scr, acc_scr):
    # grid (b, row blocks, heads, band steps): step ``ki`` folds key block
    # ``first + ki``; past ``last`` the index map repeats the last block
    # (no fetch) and nothing is computed
    qi, ki = pl.program_id(1), pl.program_id(3)
    first, last = _band(qi, block_q, block_k, window)
    kb = first + ki

    @pl.when(ki == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr[:], -jnp.inf)
        l_scr[:] = jnp.zeros_like(l_scr[:])
        acc_scr[:] = jnp.zeros_like(acc_scr[:])

    def fold(masked):
        q, k, v = q_ref[0], k_ref[0], v_ref[0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=_f32) * scale
        if masked:
            row = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 0)
            key = kb * block_k + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 1)
            allowed = key <= row
            if window is not None:
                allowed = allowed & (key > row - window)
            s = jnp.where(allowed, s, -jnp.inf)
        m_prev = m_scr[:, :1]
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        # a row that no key has been allowed to yet keeps -inf: measured
        # from 0 its exponentials are exact zeros, not exp(-inf + inf)
        base = jnp.where(m_cur == -jnp.inf, 0.0, m_cur)
        alpha = jnp.exp(m_prev - base)
        p = jnp.exp(s - base)
        l_scr[:] = jnp.broadcast_to(
            alpha * l_scr[:, :1] + jnp.sum(p, axis=1, keepdims=True),
            l_scr.shape)
        m_scr[:] = jnp.broadcast_to(m_cur, m_scr.shape)
        acc_scr[:] = alpha * acc_scr[:] + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=_f32)

    # the diagonal's blocks hold keys past some row; the band's edge keys
    # at or before some row's window
    edge = (kb + 1) * block_k - 1 > qi * block_q
    if window is not None:
        edge = edge | (kb * block_k <= (qi + 1) * block_q - 1 - window)
    pl.when((kb <= last) & edge)(functools.partial(fold, True))
    pl.when((kb <= last) & jnp.logical_not(edge))(
        functools.partial(fold, False))

    @pl.when(kb == last)
    def _finish():
        o_ref[0] = (acc_scr[:] / l_scr[:, :1]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("heads", "window", "scale",
                                             "block_q", "block_k",
                                             "interpret"))
def window_flash(q, k, v, *, heads, window, scale, block_q, block_k,
                 interpret):
    """:func:`grouped_attention` as one Pallas flash forward over the
    ``(b, s, heads * d)`` rows as they lie: jitted on its own, so that a
    model's layers share one trace of the kernel and XLA names the call
    ``%window_flash``.  Query head ``h`` reads KV head ``h // (heads //
    kv_heads)`` through the index map of K and V."""
    b, s, width = q.shape
    d = width // heads
    group = heads // (k.shape[-1] // d)
    kernel = functools.partial(_window_flash_kernel, scale, block_q, block_k,
                               window)

    def rows(bi, qi, hi, ki):
        return bi, qi, hi

    def keys(bi, qi, hi, ki):
        first, last = _band(qi, block_q, block_k, window)
        return bi, jnp.minimum(first + ki, last), hi // group

    vmem = functools.partial(pl.BlockSpec, memory_space=pltpu.VMEM)
    return pl.pallas_call(
        kernel,
        grid=(b, s // block_q, heads, _band_steps(s, block_q, block_k,
                                                  window)),
        in_specs=[vmem((1, block_q, d), rows), vmem((1, block_k, d), keys),
                  vmem((1, block_k, d), keys)],
        out_specs=vmem((1, block_q, d), rows),
        out_shape=jax.ShapeDtypeStruct((b, s, width), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, 128), _f32),
                        pltpu.VMEM((block_q, 128), _f32),
                        pltpu.VMEM((block_q, d), _f32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
            vmem_limit_bytes=_WINDOW_VMEM),
        interpret=interpret,
    )(q, k, v)


def grouped_attention_reference(q, k, v, heads, window=None,
                                softmax_scale=None):
    """Materialised causal attention of rows ``q`` ``(b, s, heads * d)``
    over ``k``/``v`` ``(b, s, kv_heads * d)``, a window of ``window``
    positions or none: the off-TPU path and the kernels' parity baseline.
    Scores, softmax and the sum are float32."""
    b, s, width = q.shape
    d = width // heads
    kv = k.shape[-1] // d
    scale = softmax_scale if softmax_scale is not None else d ** -0.5
    qg = q.astype(_f32).reshape(b, s, kv, heads // kv, d)
    sc = jnp.einsum("bqkgd,bskd->bkgqs", qg,
                    k.astype(_f32).reshape(b, s, kv, d),
                    precision=jax.lax.Precision.HIGHEST) * scale
    row, key = jnp.arange(s)[:, None], jnp.arange(s)[None, :]
    allowed = key <= row
    if window is not None:
        allowed = allowed & (key > row - window)
    p = jax.nn.softmax(jnp.where(allowed, sc, -jnp.inf), -1)
    o = jnp.einsum("bkgqs,bskd->bqkgd", p, v.astype(_f32).reshape(
        b, s, kv, d), precision=jax.lax.Precision.HIGHEST)
    return o.reshape(b, s, width).astype(q.dtype)


def grouped_attention(q, k, v, heads, window=None, softmax_scale=None):
    """Causal attention of a prefill's rows: ``q`` ``(b, s, heads * d)``,
    ``k`` and ``v`` ``(b, s, kv_heads * d)`` as a QKV projection leaves
    them, ``window`` positions a row or all; ``(b, s, heads * d)``.  On a
    TPU, with ``d`` a multiple of 128 and ``s`` of 128, one Pallas forward
    (:func:`window_flash`) whose grid visits only the key blocks of each
    row block's band; elsewhere the materialised reference."""
    b, s, width = q.shape
    d = width // heads
    scale = float(softmax_scale if softmax_scale is not None
                  else d ** -0.5)
    if use_pallas() and not (d % 128 or s % 128):
        block = _fit(_WINDOW_BLOCK, s)
        return window_flash(q, k, v, heads=heads, window=window, scale=scale,
                            block_q=block, block_k=block,
                            interpret=interpret_mode())
    return grouped_attention_reference(q, k, v, heads, window, scale)


def _grouped_decode_kernel(bs, heads, d, kv_heads, window, scale, len_ref,
                           tbl_ref, layer_ref, q_ref, pool_ref, o_ref,
                           kv_buf, sem, m_scr, l_scr, acc_scr):
    """Single-query decode of ONE row over the blocks of its band, the
    pool in HBM.  A row of ``n`` cached positions reads blocks ``first ..
    (n - 1) // bs``, ``first`` holding position ``n - window`` (0 without
    a window): one DMA a block (K and V side by side), the next in flight
    while this one is folded.  In a block every KV head's ``heads //
    kv_heads`` queries are one product with its K and one with its V, on
    the MXU; float32 scores, softmax and accumulator.  A row whose last
    block is the garbage block 0 (a slot that holds no request) starts no
    DMA and writes zeros."""
    b = pl.program_id(0)
    n = len_ref[b]
    layer = layer_ref[0]
    group = heads // kv_heads
    last = (n - 1) // bs
    first = 0 if window is None else jnp.maximum(n - window, 0) // bs

    def copy(k, slot):
        return pltpu.make_async_copy(pool_ref.at[tbl_ref[b, k], layer],
                                     kv_buf.at[slot], sem.at[slot])

    def fold(k, carry):
        slot = (k - first) % 2

        @pl.when(k < last)
        def _prefetch():
            copy(k + 1, 1 - slot).start()

        copy(k, slot).wait()
        pos = k * bs + jax.lax.broadcasted_iota(jnp.int32, (group, bs), 1)
        valid = pos < n
        if window is not None:
            valid = valid & (pos >= n - window)
        for g in range(kv_heads):
            rows = slice(g * group, (g + 1) * group)
            lanes = slice(g * d, (g + 1) * d)
            s = jax.lax.dot_general(
                q_ref[0, rows, :], kv_buf[slot, 0, :, lanes],
                (((1,), (1,)), ((), ())), preferred_element_type=_f32) * scale
            s = jnp.where(valid, s, _MASK)
            m_prev = m_scr[rows, :1]
            m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            alpha = jnp.exp(m_prev - m_cur)
            p = jnp.where(valid, jnp.exp(s - m_cur), 0.0)
            l_scr[rows, :] = jnp.broadcast_to(
                alpha * l_scr[rows, :1] + jnp.sum(p, axis=1, keepdims=True),
                (group, 128))
            m_scr[rows, :] = jnp.broadcast_to(m_cur, (group, 128))
            acc_scr[rows, :] = alpha * acc_scr[rows, :] + jax.lax.dot_general(
                p.astype(kv_buf.dtype), kv_buf[slot, 1, :, lanes],
                (((1,), (0,)), ((), ())), preferred_element_type=_f32)
        return carry

    live = tbl_ref[b, last] != 0

    @pl.when(jnp.logical_not(live))
    def _empty():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(live)
    def _live():
        copy(first, 0).start()
        _decode_init(m_scr, l_scr, acc_scr)
        jax.lax.fori_loop(first, last + 1, fold, None)
        o_ref[0] = (acc_scr[:] / l_scr[:, :1]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("window", "scale", "interpret"))
def grouped_decode(cache_lens, block_tables, layer, q, pool, *, window,
                   scale, interpret):
    """The grouped, windowed tick's kernel, jitted on its own with the layer
    as data: a model's layers share one trace, and XLA names the call
    ``%grouped_decode``.  ``q`` ``(b, heads, d)``; ``pool`` ``(num_blocks,
    layers, 2, block_size, kv_heads * d)``."""
    b, h, d = q.shape
    bs, width = pool.shape[3:]
    kernel = functools.partial(_grouped_decode_kernel, bs, h, d, width // d,
                               window, scale)
    spec = pl.BlockSpec((1, h, d), lambda bi, *prefetched: (bi, 0, 0),
                        memory_space=pltpu.VMEM)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b,),
        in_specs=[spec, pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=spec,
        scratch_shapes=[pltpu.VMEM((2, 2, bs, width), pool.dtype),
                        pltpu.SemaphoreType.DMA((2,))]
        + _decode_scratch(h, d))
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=_sds((b, h, d), q.dtype, q),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(cache_lens, block_tables, layer, q, pool)


def grouped_decode_reference(q, pool, layer_index, block_tables, cache_lens,
                             window=None, softmax_scale=None):
    """The tick's attention over the gathered tables, materialised: query
    head ``h`` of ``q`` ``(b, heads, d)`` against KV head ``h // (heads //
    kv_heads)``, positions ``[len - window, len)`` of each row (all below
    ``len`` without a window); float32 throughout."""
    b, h, d = q.shape
    kv = pool.shape[-1] // d
    k = gather_paged_kv(pool, layer_index, 0, block_tables, kv)
    v = gather_paged_kv(pool, layer_index, 1, block_tables, kv)
    scale = softmax_scale if softmax_scale is not None else d ** -0.5
    s = jnp.einsum("bkgd,bskd->bkgs", q.astype(_f32).reshape(b, kv, -1, d),
                   k.astype(_f32), precision=jax.lax.Precision.HIGHEST) * scale
    pos = jnp.arange(k.shape[1])[None, :]
    valid = pos < cache_lens[:, None]
    if window is not None:
        valid = valid & (pos >= cache_lens[:, None] - window)
    valid = valid[:, None, None, :]
    p = jnp.where(valid, jax.nn.softmax(jnp.where(valid, s, _MASK), -1), 0.0)
    o = jnp.einsum("bkgs,bskd->bkgd", p, v.astype(_f32),
                   precision=jax.lax.Precision.HIGHEST)
    return o.reshape(b, h, d).astype(q.dtype)


# ---------------------------------------------------------------------------
# grouped heads and an optional window, trained
# ---------------------------------------------------------------------------
#
# The training side of the prefill kernel above: a forward that keeps each
# row's log-sum-exp, then dQ and dK/dV as two Pallas calls.  Every call
# visits only the blocks of a band: the forward and dQ walk a row block's
# key blocks (every block up to the diagonal without a window), dK/dV a key
# block's row blocks.  Query head ``h`` reads KV head ``h // group`` through
# the index maps, and dK and dV of KV head ``g`` are summed over its query
# heads ``g * group ..`` inside the dK/dV kernel: K and V are never
# broadcast.  Each call sits in an inner ``jax.jit`` named by its kind
# (``band_flash_*`` with a window, ``grouped_flash_*`` without; ``fwd``,
# ``dq``, ``dkv``), the name XLA gives its custom call.

_BAND_BLOCK = 1024          # row and key block of the three kernels
_BAND_STATIC = ("heads", "window", "scale", "block_q", "block_k",
                "interpret")


def _band_rows(ki, block_q, block_k, window, nq):
    """``(first, last)`` row blocks that see key block ``ki``: ``first``
    holding the block's first key on its diagonal, ``last`` the last row
    whose window still reaches the block's last key (the last row block
    without a window).  ``ki`` is a traced scalar."""
    first = ki * block_k // block_q
    if window is None:
        return first, nq - 1
    return first, jnp.minimum(((ki + 1) * block_k + window - 2) // block_q,
                              nq - 1)


def _band_row_steps(s, block_q, block_k, window):
    """Row blocks the widest band of a key block spans."""
    nq = s // block_q
    if window is None:
        return nq
    return max(min(((ki + 1) * block_k + window - 2) // block_q, nq - 1)
               - ki * block_k // block_q + 1 for ki in range(s // block_k))


def _band_edge(qi, kb, block_q, block_k, window):
    """Whether row block ``qi`` and key block ``kb`` meet on the diagonal
    or on the band's lower edge, where some pair is masked."""
    edge = (kb + 1) * block_k - 1 > qi * block_q
    if window is not None:
        edge = edge | (kb * block_k <= (qi + 1) * block_q - 1 - window)
    return edge


def _band_scores(q, k, scale, qi, kb, block_q, block_k, window, masked):
    """``q k^T * scale`` of one pair of blocks, float32, its masked pairs
    at ``_MASK``."""
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=_f32) * scale
    if masked:
        row = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        key = kb * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        allowed = key <= row
        if window is not None:
            allowed = allowed & (key > row - window)
        s = jnp.where(allowed, s, _MASK)
    return s


def _band_fwd_kernel(scale, block_q, block_k, window, q_ref, k_ref, v_ref,
                     o_ref, lse_ref, m_scr, l_scr, acc_scr):
    # grid (b, row blocks, heads, band steps): step ``ki`` folds key block
    # ``first + ki``; past ``last`` the index map repeats the last block
    # (no fetch) and nothing is computed.  Every row holds its own key, so
    # a row's maximum is finite once its diagonal block is folded
    qi, ki = pl.program_id(1), pl.program_id(3)
    first, last = _band(qi, block_q, block_k, window)
    kb = first + ki

    @pl.when(ki == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr[:], _MASK)
        l_scr[:] = jnp.zeros_like(l_scr[:])
        acc_scr[:] = jnp.zeros_like(acc_scr[:])

    def fold(masked):
        v = v_ref[0]
        s = _band_scores(q_ref[0], k_ref[0], scale, qi, kb, block_q, block_k,
                         window, masked)
        m_prev = m_scr[:, :1]
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_cur)
        p = jnp.exp(s - m_cur)
        if masked:
            # a row whose keys in this block are all masked keeps m at
            # _MASK, where exp(s - m) would read 1
            p = jnp.where(s > _MASK, p, 0.0)
        l_scr[:] = jnp.broadcast_to(
            alpha * l_scr[:, :1] + jnp.sum(p, axis=1, keepdims=True),
            l_scr.shape)
        m_scr[:] = jnp.broadcast_to(m_cur, m_scr.shape)
        acc_scr[:] = alpha * acc_scr[:] + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=_f32)

    edge = _band_edge(qi, kb, block_q, block_k, window)
    pl.when((kb <= last) & edge)(functools.partial(fold, True))
    pl.when((kb <= last) & jnp.logical_not(edge))(
        functools.partial(fold, False))

    @pl.when(kb == last)
    def _finish():
        l = l_scr[:, :1]
        o_ref[0] = (acc_scr[:] / l).astype(o_ref.dtype)
        lse_ref[0, 0] = m_scr[:, :1] + jnp.log(l)


def _band_dq_kernel(scale, block_q, block_k, window, q_ref, k_ref, v_ref,
                    do_ref, lse_ref, delta_ref, dq_ref, dq_scr):
    # the forward's grid and band; dQ accumulates over the band's blocks
    qi, ki = pl.program_id(1), pl.program_id(3)
    first, last = _band(qi, block_q, block_k, window)
    kb = first + ki

    @pl.when(ki == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr[:])

    def fold(masked):
        k = k_ref[0]
        s = _band_scores(q_ref[0], k, scale, qi, kb, block_q, block_k,
                         window, masked)
        p = jnp.exp(s - lse_ref[0, 0])                     # (block_q, 1)
        dp = jax.lax.dot_general(do_ref[0], v_ref[0],
                                 (((1,), (1,)), ((), ())),
                                 preferred_element_type=_f32)
        ds = p * (dp - delta_ref[0, 0]) * scale
        dq_scr[:] += jax.lax.dot_general(ds.astype(k.dtype), k,
                                         (((1,), (0,)), ((), ())),
                                         preferred_element_type=_f32)

    edge = _band_edge(qi, kb, block_q, block_k, window)
    pl.when((kb <= last) & edge)(functools.partial(fold, True))
    pl.when((kb <= last) & jnp.logical_not(edge))(
        functools.partial(fold, False))

    @pl.when(kb == last)
    def _finish():
        dq_ref[0] = dq_scr[:].astype(dq_ref.dtype)


def _band_dkv_kernel(scale, block_q, block_k, window, nq, steps, q_ref,
                     k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref,
                     dk_scr, dv_scr):
    # grid (b, key blocks, KV heads, group x band steps): step ``t`` folds
    # row block ``first + t % steps`` of the group's query head ``t //
    # steps``; past ``last`` the index maps repeat the last row block and
    # nothing is computed.  dK and dV hold the whole group's sum
    ki, t = pl.program_id(1), pl.program_id(3)
    first, last = _band_rows(ki, block_q, block_k, window, nq)
    qb = first + t % steps

    @pl.when(t == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr[:])
        dv_scr[:] = jnp.zeros_like(dv_scr[:])

    def fold(masked):
        q, do = q_ref[0], do_ref[0]
        s = _band_scores(q, k_ref[0], scale, qb, ki, block_q, block_k,
                         window, masked)
        p = jnp.exp(s - lse_ref[0, 0])                     # (block_q, 1)
        dv_scr[:] += jax.lax.dot_general(p.astype(do.dtype), do,
                                         (((0,), (0,)), ((), ())),
                                         preferred_element_type=_f32)
        dp = jax.lax.dot_general(do, v_ref[0], (((1,), (1,)), ((), ())),
                                 preferred_element_type=_f32)
        ds = p * (dp - delta_ref[0, 0]) * scale
        dk_scr[:] += jax.lax.dot_general(ds.astype(q.dtype), q,
                                         (((0,), (0,)), ((), ())),
                                         preferred_element_type=_f32)

    edge = _band_edge(qb, ki, block_q, block_k, window)
    pl.when((qb <= last) & edge)(functools.partial(fold, True))
    pl.when((qb <= last) & jnp.logical_not(edge))(
        functools.partial(fold, False))

    @pl.when(t == pl.num_programs(3) - 1)
    def _finish():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


def _band_specs(heads, group, window, block_q, block_k, s, d):
    """BlockSpecs of the forward's and dQ's grid ``(b, row blocks, heads,
    band steps)``: a row block of one query head, the band's key block of
    its KV head, and the head's row statistics ``(b, heads, s, 1)``."""
    vmem = functools.partial(pl.BlockSpec, memory_space=pltpu.VMEM)

    def keys(bi, qi, hi, ki):
        first, last = _band(qi, block_q, block_k, window)
        return bi, jnp.minimum(first + ki, last), hi // group

    return (vmem((1, block_q, d), lambda bi, qi, hi, ki: (bi, qi, hi)),
            vmem((1, block_k, d), keys),
            vmem((1, 1, block_q, 1), lambda bi, qi, hi, ki: (bi, hi, qi, 0)))


def _band_params():
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "parallel",
                             "arbitrary"),
        vmem_limit_bytes=_WINDOW_VMEM)


def _band_fwd_call(q, k, v, *, heads, window, scale, block_q, block_k,
                   interpret):
    """``(o, lse)``: the output rows and each row's log-sum-exp ``(b,
    heads, s, 1)`` float32."""
    b, s, width = q.shape
    d = width // heads
    group = heads // (k.shape[-1] // d)
    rows, keys, stats = _band_specs(heads, group, window, block_q, block_k,
                                    s, d)
    return pl.pallas_call(
        functools.partial(_band_fwd_kernel, scale, block_q, block_k, window),
        grid=(b, s // block_q, heads,
              _band_steps(s, block_q, block_k, window)),
        in_specs=[rows, keys, keys],
        out_specs=[rows, stats],
        out_shape=[_sds(q.shape, q.dtype, q),
                   _sds((b, heads, s, 1), _f32, q)],
        scratch_shapes=[pltpu.VMEM((block_q, 128), _f32),
                        pltpu.VMEM((block_q, 128), _f32),
                        pltpu.VMEM((block_q, d), _f32)],
        compiler_params=_band_params(),
        interpret=interpret,
    )(q, k, v)


def _band_dq_call(q, k, v, do, lse, delta, *, heads, window, scale, block_q,
                  block_k, interpret):
    b, s, width = q.shape
    d = width // heads
    group = heads // (k.shape[-1] // d)
    rows, keys, stats = _band_specs(heads, group, window, block_q, block_k,
                                    s, d)
    return pl.pallas_call(
        functools.partial(_band_dq_kernel, scale, block_q, block_k, window),
        grid=(b, s // block_q, heads,
              _band_steps(s, block_q, block_k, window)),
        in_specs=[rows, keys, keys, rows, stats, stats],
        out_specs=rows,
        out_shape=_sds(q.shape, q.dtype, q),
        scratch_shapes=[pltpu.VMEM((block_q, d), _f32)],
        compiler_params=_band_params(),
        interpret=interpret,
    )(q, k, v, do, lse, delta)


def _band_dkv_call(q, k, v, do, lse, delta, *, heads, window, scale,
                   block_q, block_k, interpret):
    """``(dk, dv)``, each KV head's summed over its query heads."""
    b, s, width = q.shape
    d = width // heads
    kv = k.shape[-1] // d
    group, nq = heads // kv, s // block_q
    steps = _band_row_steps(s, block_q, block_k, window)
    vmem = functools.partial(pl.BlockSpec, memory_space=pltpu.VMEM)

    def row_block(ki, t):
        first, last = _band_rows(ki, block_q, block_k, window, nq)
        return jnp.minimum(first + t % steps, last)

    rows = vmem((1, block_q, d), lambda bi, ki, g, t: (
        bi, row_block(ki, t), g * group + t // steps))
    stats = vmem((1, 1, block_q, 1), lambda bi, ki, g, t: (
        bi, g * group + t // steps, row_block(ki, t), 0))
    keys = vmem((1, block_k, d), lambda bi, ki, g, t: (bi, ki, g))
    return pl.pallas_call(
        functools.partial(_band_dkv_kernel, scale, block_q, block_k, window,
                          nq, steps),
        grid=(b, s // block_k, kv, group * steps),
        in_specs=[rows, keys, keys, rows, stats, stats],
        out_specs=[keys, keys],
        out_shape=[_sds(k.shape, k.dtype, k), _sds(v.shape, v.dtype, v)],
        scratch_shapes=[pltpu.VMEM((block_k, d), _f32),
                        pltpu.VMEM((block_k, d), _f32)],
        compiler_params=_band_params(),
        interpret=interpret,
    )(q, k, v, do, lse, delta)


def _named_call(name, impl):
    """``impl`` in an inner ``jax.jit`` called ``name``: a model's layers
    share one trace of each kernel, and XLA names the custom call so."""
    def call(*args, **static):
        return impl(*args, **static)
    call.__name__ = call.__qualname__ = name
    return jax.jit(call, static_argnames=_BAND_STATIC)


_BAND_CALLS = {
    (windowed, part): _named_call(
        f"{'band' if windowed else 'grouped'}_flash_{part}", impl)
    for windowed in (True, False)
    for part, impl in (("fwd", _band_fwd_call), ("dq", _band_dq_call),
                       ("dkv", _band_dkv_call))}


def _pad_rows(x, s_pad):
    s = x.shape[1]
    return x if s == s_pad else jnp.pad(x, ((0, 0), (0, s_pad - s), (0, 0)))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _grouped_flash(q, k, v, heads, window, scale, block_q, block_k,
                   interpret):
    return _grouped_flash_fwd(q, k, v, heads, window, scale, block_q,
                              block_k, interpret)[0]


def _grouped_flash_fwd(q, k, v, heads, window, scale, block_q, block_k,
                       interpret):
    s = q.shape[1]
    s_pad = _round_up(s, max(block_q, block_k))
    # rows past the sequence are zeros: no real row sees their keys, and
    # their zero cotangent adds nothing to dK or dV
    q, k, v = (_pad_rows(x, s_pad) for x in (q, k, v))
    static = dict(heads=heads, window=window, scale=scale, block_q=block_q,
                  block_k=block_k, interpret=interpret)
    o, lse = _BAND_CALLS[window is not None, "fwd"](q, k, v, **static)
    return o[:, :s], (q, k, v, o, lse)


def _grouped_flash_bwd(heads, window, scale, block_q, block_k, interpret,
                       res, g):
    q, k, v, o, lse = res
    b, s_pad, width = q.shape
    s = g.shape[1]
    do = _pad_rows(g.astype(q.dtype), s_pad)
    # rowsum(dO * O) a head, as the kernels' row statistics (b, heads, s, 1)
    delta = jnp.sum((do.astype(_f32) * o.astype(_f32)).reshape(
        b, s_pad, heads, width // heads), -1).transpose(0, 2, 1)[..., None]
    static = dict(heads=heads, window=window, scale=scale, block_q=block_q,
                  block_k=block_k, interpret=interpret)
    windowed = window is not None
    dq = _BAND_CALLS[windowed, "dq"](q, k, v, do, lse, delta, **static)
    dk, dv = _BAND_CALLS[windowed, "dkv"](q, k, v, do, lse, delta, **static)
    return dq[:, :s], dk[:, :s], dv[:, :s]


_grouped_flash.defvjp(_grouped_flash_fwd, _grouped_flash_bwd)


def grouped_flash(q, k, v, heads, window=None, softmax_scale=None):
    """Causal attention of a training step's rows, with its gradients:
    ``q`` ``(b, s, heads * d)``, ``k`` and ``v`` ``(b, s, kv_heads * d)`` as
    a QKV projection leaves them, each row over its last ``window``
    positions or all of them; ``(b, s, heads * d)``.  On a TPU, with ``d``
    a multiple of 128, a Pallas forward that keeps each row's log-sum-exp
    and a backward of two Pallas calls, dQ and dK/dV, every grid over the
    blocks of a band alone; elsewhere the materialised reference, which
    autodiff differentiates."""
    d = q.shape[-1] // heads
    scale = float(softmax_scale if softmax_scale is not None
                  else d ** -0.5)
    if not use_pallas() or d % 128:
        return grouped_attention_reference(q, k, v, heads, window, scale)
    block = _fit(_BAND_BLOCK, q.shape[1])
    return _grouped_flash(q, k, v, heads, window, scale, block, block,
                          interpret_mode())
