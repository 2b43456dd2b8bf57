"""Latent attention's cache record, the sparse-attention indexer's exact
selection, and decode attention over the selected records of a paged pool.

A latent-attention layer (MLA, DeepSeek-V2) caches one record a position for
ALL its heads: ``(c_t, k_rope_t)``, the normalised compressed latent and the
one rotary key (512 + 64 numbers in GLM-5.2).  A layer that owns a sparse-
attention indexer (DeepSeek-V3.2's, which GLM-5.2 shares between layers)
caches one index key a position as well (128 numbers).  The pool keeps the
two in arrays of their own,

    ``(num_blocks, latent layers, 1, block_size, 640)`` in the cache's dtype
    and ``(num_blocks, indexer layers, 1, block_size, 128)`` in float32,

so that an indexer reads a block's keys as one contiguous piece and a layer
without an indexer has no index key at all.

The tick is three steps, all exact: the indexer scores EVERY cached position
of a row from the index keys (:func:`index_scores`), ``lax.top_k`` takes the
``k`` best (ties to the lower position), and attention gathers those ``k``
latent records and no other (:func:`sparse_decode_attention`): with the key
up-projection absorbed into the query, every head scores the same record,
``q'_i . c_t + q_rope_i . k_rope_t``, and sums ``p_i(t) c_t``.  A prefill's
indexer scores each row only against the keys it may select: rows go in
segments of ``k`` (:func:`index_segments`), each against the keys up to
its own end, and the first ``k`` rows, which select every key they may,
score nothing.  It attends in the expanded form with the selection as a
mask (:func:`topk_mask`), which at up to eight times ``k`` positions costs
no more than gathering would: on a TPU by one Pallas flash-attention
forward at a head of 256 (:func:`masked_flash`) that reads a row block's
strip of the mask once for all its heads, visits the key blocks up to the
diagonal, and keeps the float32 scores, maxima, sums and the accumulator in
VMEM; elsewhere, and for a sequence shorter than a row block, by a
``jax.numpy`` loop over row blocks (:func:`masked_attention`).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from apex_tpu.ops.flash_attention import _fit
from apex_tpu.utils.platform import interpret_mode, use_pallas

_f32 = jnp.float32
_LANES = 128


def latent_record_width(kv_lora_rank, qk_rope_head_dim):
    """Numbers the pool keeps a position and layer: the record, padded with
    zeros to whole 128-lane tiles.  The TPU's tiled layout pads a bf16 row
    of 576 to 640 in HBM whatever the array's shape says, so the pad costs
    no byte that was not already spent, and a gathered row is whole tiles."""
    return -(-(kv_lora_rank + qk_rope_head_dim) // _LANES) * _LANES


def latent_record(c, k_rope):
    """``[c | k_rope | zeros]``: what is cached for a position, padded to
    :func:`latent_record_width`."""
    record = jnp.concatenate([c, k_rope], -1)
    pad = latent_record_width(c.shape[-1], k_rope.shape[-1]) \
        - record.shape[-1]
    return jnp.pad(record, [(0, 0)] * (record.ndim - 1) + [(0, pad)])


def rotary_pairs(x, positions, rot_dim, base, head_dim=None, first=False):
    """Rotary positions on ADJACENT pairs of lanes (``rope_interleave``:
    lanes ``2i`` and ``2i + 1`` of the rotary part turn by ``position *
    base ** (-2i / rot_dim)``), on rows ``x`` ``(..., heads * head_dim)``
    whose heads lie side by side.  The rotary part of a head is its last
    ``rot_dim`` lanes (latent attention: ``[nope | rope]``) or, with
    ``first``, its first (the indexer's convention).  ``positions``
    broadcasts against ``x.shape[:-1]``.  Angles, sines and the products
    are float32; the frequencies are made on the host in float64 and
    rounded once: a power computed on the device was 5e-6 off, 0.02 rad at
    position 4 096, and the indexer's selection moved with it (PR 35)."""
    width = x.shape[-1]
    head_dim = head_dim or width
    lane = np.arange(head_dim)
    lane = lane if first else lane - (head_dim - rot_dim)
    turns = (lane >= 0) & (lane < rot_dim)
    inv = np.where(turns, float(base) ** (-(lane // 2 * 2) / rot_dim),
                   0.0).astype(np.float32)
    # one head's table, which every head of a row shares
    angle = (jnp.asarray(positions, _f32)[..., None] * inv)[..., None, :]
    x32 = x.astype(_f32).reshape(*x.shape[:-1], width // head_dim, head_dim)
    # the other lane of a pair is its neighbour
    other = jnp.where(np.arange(head_dim) % 2 == 0, -jnp.roll(x32, -1, -1),
                      jnp.roll(x32, 1, -1))
    out = x32 * jnp.cos(angle) + other * jnp.sin(angle)
    return out.reshape(x.shape).astype(x.dtype)


def index_scores(q, w, k):
    """The indexer's score of every key for every query, float32:
    ``I[t, s] = sum_j w[t, j] relu(q[t, j] . k[s])`` with ``q`` ``(b, t,
    heads, d)``, ``w`` ``(b, t, heads)`` and ``k`` ``(b, s, d)``; float32
    operands are multiplied as float32 (HIGHEST: on a TPU a float32 product
    is otherwise one bf16 pass)."""
    exact = jax.lax.Precision.HIGHEST
    dots = jnp.einsum("bthd,bsd->bths", q, k.astype(q.dtype),
                      preferred_element_type=_f32, precision=exact)
    return jnp.einsum("bths,bth->bts", jnp.maximum(dots, 0.0),
                      w.astype(_f32), precision=exact)


def _ordered_bits(x):
    """float32 ``x`` as uint32 whose unsigned order is ``x``'s (no NaN)."""
    u = jax.lax.bitcast_convert_type(x, jnp.uint32)
    return jnp.where(u >> 31 == 1, ~u, u | jnp.uint32(1 << 31))


def topk_mask(scores, allowed, k):
    """``(..., s)`` bool: the ``k`` largest ``scores`` of each row among
    its ``allowed`` entries, the lower position winning a tie; every
    allowed entry of a row that has at most ``k``.  Exact, without a sort:
    the ``k``-th largest value is found bit by bit (32 counts over the row),
    and only a row with ties AT that value pays for a running count."""
    bits = jnp.where(allowed, _ordered_bits(scores.astype(_f32)),
                     jnp.uint32(0))

    def bit(i, th):
        cand = th | (jnp.uint32(1) << (31 - i).astype(jnp.uint32))
        enough = jnp.sum(bits >= cand[..., None], -1) >= k
        return jnp.where(enough, cand, th)

    # the largest value that at least k entries reach: the k-th largest
    # (0, which every entry reaches, where a row allows fewer than k)
    th = jax.lax.fori_loop(0, 32, bit,
                           jnp.zeros(bits.shape[:-1], jnp.uint32))
    above = bits > th[..., None]
    at = (bits == th[..., None]) & allowed
    room = k - jnp.sum(above, -1, keepdims=True)

    def ties():
        return at & (jnp.cumsum(at, -1, dtype=jnp.int32) <= room)

    at = jax.lax.cond(jnp.any(jnp.sum(at, -1, keepdims=True) > room),
                      ties, lambda: at)
    return (above & allowed) | at


def index_segments(s, k):
    """The rows a prefill of ``s`` positions scores, one indexer layer
    selecting ``k`` keys a row: segments ``((r0, r1), ...)`` of ``k`` rows
    from row ``k`` on (the last ends at ``s``), rows ``[r0, r1)`` against
    keys ``[0, r1)``.  A row selects only keys up to its own position, and
    one that may select at most ``k`` selects all of them, so rows ``[0,
    k)`` need no score.  The (row, key) pairs scored are ``sum((r1 - r0) *
    r1)``: 0.547 of ``s**2`` at 16 384 positions and ``k`` 2 048, 0.5625 at
    8 192, none at ``s <= k``."""
    return tuple((r0, min(r0 + k, s)) for r0 in range(k, s, k))


def topk_positions(scores, lengths, k):
    """``(idx (b, k) int32, valid (b, k) bool)``: for each row of
    ``scores`` ``(b, s)`` the positions of the ``k`` largest among its
    first ``lengths[b]``, the lower position winning a tie (``lax.top_k``'s
    rule); all of them, and ``valid`` false on the rest, where a row holds
    fewer than ``k``."""
    s = scores.shape[-1]
    held = jnp.arange(s) < lengths[:, None]
    _, idx = jax.lax.top_k(jnp.where(held, scores, -jnp.inf), min(k, s))
    return idx, idx < lengths[:, None]


def gather_index_keys(pool, layer_index, block_tables):
    """Every index key a row's table names, ``(b, max_blocks * block_size,
    d)``: whole blocks as they lie in ``pool`` ``(num_blocks, layers, 1,
    block_size, d)``."""
    keys = pool[block_tables, layer_index, 0]       # (b, blocks, bs, d)
    return keys.reshape(keys.shape[0], -1, keys.shape[-1])


def scatter_record(pool, layer_index, block_ids, offsets, record):
    """Write one record a row where it lies: ``record`` ``(b, width)`` goes
    to ``pool[block_ids, layer_index, 0, offsets]`` in the pool's dtype."""
    return pool.at[block_ids, layer_index, 0, offsets].set(
        record.astype(pool.dtype))


def sparse_decode_attention(q, pool, layer_index, block_tables, idx, valid,
                            scale, v_width):
    """Absorbed decode attention of ``q`` ``(b, heads, record width)`` over
    the records at positions ``idx`` ``(b, k)`` of each row's table and over
    no other: ``k`` rows of ``pool`` ``(num_blocks, layers, 1, block_size,
    record width)`` are gathered a row and layer, whatever the context
    holds.  A record is the key and, in its first ``v_width`` lanes, the
    value of every head.  Returns ``(b, heads, v_width)`` in ``q``'s dtype;
    scores and the softmax are float32."""
    bs = pool.shape[3]
    blocks = jnp.take_along_axis(block_tables, idx // bs, axis=1)
    records = pool[blocks, layer_index, 0, idx % bs]        # (b, k, width)
    s = jnp.einsum("bhc,bkc->bhk", q, records.astype(q.dtype),
                   preferred_element_type=_f32) * scale
    p = jax.nn.softmax(jnp.where(valid[:, None, :], s, -jnp.inf), -1)
    return jnp.einsum("bhk,bkc->bhc", p.astype(q.dtype),
                      records[..., :v_width].astype(q.dtype),
                      preferred_element_type=_f32).astype(q.dtype)


_LOOP_ROWS = 128        # the fallback loop: query rows a pass,
_LOOP_SEGMENTS = 4      # and the pieces a causal sequence is cut into
# The kernel's blocks, 1024 x 1024 at a head of 256 (my chip runs, PR 36:
# 17.5 ms for 16 heads x 16 384 positions, 125 TF/s).  The row block is what
# a K/V block is reused over: at 128 rows the kernel reads K and V at 128
# FLOP a byte and is bound by HBM (41 ms); the key block is what one rescale
# of the accumulator is spread over (512 keys: 21 ms; 2 048: 18.0, more of
# the diagonal block wasted).  A row block's strip of the mask is held twice
# (one in use, one in flight): rows x keys bytes each, at most 16 MB.
_FLASH_BLOCK = 1024
_FLASH_STRIP_BYTES = 16 << 20
_FLASH_VMEM = 64 << 20


def _diagonal(qi, block_q, block_k):
    """The last key block a causal row block ``qi`` can see."""
    return ((qi + 1) * block_q - 1) // block_k


def _masked_flash_kernel(scale, block_q, block_k, q_ref, k_ref, v_ref,
                         mask_ref, o_ref, m_scr, l_scr, acc_scr):
    # grid (b, row blocks, heads, key blocks): the row block's strip of the
    # mask, (block_q, s) int8, has an index that ignores the two inner
    # axes, so it is fetched once a row block and sliced here
    qi, ki = pl.program_id(1), pl.program_id(3)
    last = _diagonal(qi, block_q, block_k)

    @pl.when(ki == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr[:], -jnp.inf)
        l_scr[:] = jnp.zeros_like(l_scr[:])
        acc_scr[:] = jnp.zeros_like(acc_scr[:])

    @pl.when(ki <= last)    # past the diagonal nothing is allowed: no work
    def _block():
        q, k, v = q_ref[0, 0], k_ref[0, 0], v_ref[0, 0]
        allowed = mask_ref[0, :, pl.ds(pl.multiple_of(ki * block_k, block_k),
                                       block_k)].astype(jnp.int32) != 0
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=_f32) * scale
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

    @pl.when(ki == last)
    def _finish():
        o_ref[0] = (acc_scr[:] / l_scr[:, :1]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "block_q", "block_k",
                                             "interpret"))
def masked_flash(q, k, v, mask, *, scale, block_q, block_k, interpret):
    """:func:`masked_attention` as one Pallas flash-attention forward:
    scores, running maxima and sums and the output accumulator stay in
    VMEM.  Jitted on its own, so that a model's layers and head groups share
    one trace of the kernel and XLA names the call ``%masked_flash``.  A row
    block visits the key blocks up to its diagonal and fetches no other
    (the index of K and V is clamped there); ``mask`` goes in as int8."""
    b, h, s, d = q.shape
    dv = v.shape[-1]
    kernel = functools.partial(_masked_flash_kernel, scale, block_q, block_k)

    def rows(bi, qi, hi, ki):
        return bi, hi, qi, 0

    def keys(bi, qi, hi, ki):
        return bi, hi, jnp.minimum(ki, _diagonal(qi, block_q, block_k)), 0

    vmem = functools.partial(pl.BlockSpec, memory_space=pltpu.VMEM)
    return pl.pallas_call(
        kernel,
        grid=(b, s // block_q, h, s // block_k),
        in_specs=[vmem((1, 1, block_q, d), rows),
                  vmem((1, 1, block_k, d), keys),
                  vmem((1, 1, block_k, dv), keys),
                  vmem((1, block_q, s), lambda bi, qi, hi, ki: (bi, qi, 0))],
        out_specs=vmem((1, block_q, dv), lambda bi, qi, hi, ki: (bi, qi, hi)),
        out_shape=jax.ShapeDtypeStruct((b, s, h * dv), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, _LANES), _f32),
                        pltpu.VMEM((block_q, _LANES), _f32),
                        pltpu.VMEM((block_q, dv), _f32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
            vmem_limit_bytes=_FLASH_VMEM),
        interpret=interpret,
    )(q, k, v, mask.astype(jnp.int8))


def masked_attention(q, k, v, mask, scale):
    """Attention of ``q`` ``(b, h, s, d)`` over ``k`` ``(b, h, s, d)`` and
    ``v`` ``(b, h, s, dv)`` where ``mask`` ``(b, s, s)`` allows (the causal
    rule is the mask's to carry, and every row allows its own position);
    ``(b, s, h * dv)``.  bf16 products accumulate in float32; scale, mask,
    maxima, exponentials and sums are float32; ``p`` is rounded to ``v``'s
    dtype before ``p v``.

    On a TPU one flash kernel (:func:`masked_flash`) where the sequence and
    the heads are whole 128-lane tiles.  Elsewhere, and for a
    shorter sequence, a loop: query rows go ``_LOOP_ROWS`` at a time
    (``lax.map``), so the float32 scores alive at once are ``h x rows x
    keys``; the sequence is cut into ``_LOOP_SEGMENTS`` and the blocks of
    one read only the keys up to its end, which a causal mask allows
    nothing beyond: a quarter more products than the causal half instead
    of twice as many."""
    b, h, s, d = q.shape
    if use_pallas() and not (s % _LANES or d % _LANES
                             or v.shape[-1] % _LANES):
        rows = max(_LANES, min(_FLASH_BLOCK, _FLASH_STRIP_BYTES // s))
        return masked_flash(q, k, v, mask, scale=scale, block_q=_fit(rows, s),
                            block_k=_fit(_FLASH_BLOCK, s),
                            interpret=interpret_mode())
    rows = min(_LOOP_ROWS, s)
    if s % rows:
        raise ValueError(f"sequence {s} is not a multiple of {rows} rows")
    n = s // rows
    per = -(-n // min(_LOOP_SEGMENTS, n))
    outs = []
    for b0 in range(0, n, per):
        end = min(b0 + per, n) * rows
        ks, vs = k[:, :, :end], v[:, :, :end]

        def block(r0, ks=ks, vs=vs, end=end):
            qb = jax.lax.dynamic_slice_in_dim(q, r0, rows, 2)
            mb = jax.lax.dynamic_slice_in_dim(mask, r0, rows, 1)[..., :end]
            sc = jnp.einsum("bhqd,bhkd->bhqk", qb, ks,
                            preferred_element_type=_f32) * scale
            p = jax.nn.softmax(jnp.where(mb[:, None], sc, -jnp.inf), -1)
            return jnp.einsum("bhqk,bhkd->bqhd", p.astype(vs.dtype), vs,
                              preferred_element_type=_f32).astype(q.dtype)

        o = jax.lax.map(block, jnp.arange(b0 * rows, end, rows))
        outs.append(o.transpose(1, 0, 2, 3, 4).reshape(
            b, -1, h * v.shape[-1]))
    return jnp.concatenate(outs, 1)
