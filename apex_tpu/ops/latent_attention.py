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
``q'_i . c_t + q_rope_i . k_rope_t``, and sums ``p_i(t) c_t``.  A prefill
attends in the expanded form over all keys with the same selection as a
mask (:func:`topk_mask`), which at up to eight times ``k`` positions costs
no more than gathering would.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

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


def masked_attention(q, k, v, mask, scale, block_rows=128, segments=4):
    """Attention of ``q`` ``(b, h, s, d)`` over ``k`` ``(b, h, s, d)`` and
    ``v`` ``(b, h, s, dv)`` where ``mask`` ``(b, s, s)`` allows (the causal
    rule is the mask's to carry); ``(b, s, h * dv)``.

    Query rows go ``block_rows`` at a time (``lax.map``), so the float32
    scores alive at once are ``h x block_rows x keys``; the sequence is cut
    into ``segments`` and the blocks of one read only the keys up to its
    end, which a causal mask allows nothing beyond: a quarter more products
    than the causal half instead of twice as many."""
    b, h, s, _ = q.shape
    rows = min(block_rows, s)
    if s % rows:
        raise ValueError(f"sequence {s} is not a multiple of {rows} rows")
    n = s // rows
    per = -(-n // max(min(segments, n), 1))
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
