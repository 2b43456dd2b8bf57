"""Self-attention: the plain block's, a parallel block's, and the kind of a
``layer_pattern``'s ``*`` layer (``gpt.LAYER_KINDS``), with the helpers that
work on grouped heads' ``(b, s, heads * head_dim)`` rows; and
:class:`LayerKind`, what every layer and kind of mixer says of itself."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from apex_tpu.ops.flash_attention import (dequantize_kv_blocks,
                                          flash_attention_bshd,
                                          flash_attention_chunk_paged,
                                          flash_attention_decode,
                                          flash_attention_decode_paged,
                                          flash_attention_decode_paged_quant,
                                          grouped_attention, grouped_flash,
                                          quantize_kv_blocks,
                                          scatter_paged_kv)
from apex_tpu.ops.latent_attention import rotary_pairs
from apex_tpu.ops.rope import (fused_apply_rotary_pos_emb_at_positions,
                               fused_apply_rotary_pos_emb_cached, rope_freqs)
from apex_tpu.transformer import tensor_parallel as tp

_f32 = jnp.float32
INIT_STD = 0.02        # of every matrix (the linear layers' own default)
_SEED_TP_RANK_STRIDE = 0x7F4A7C15  # per-TP-rank dropout stream offset
# GPTModel's entry points, and the method of a layer (or of its kind) that
# each runs through
PATHS = {"training forward": "__call__", "prefill": "prefill",
         "decode_step_paged": "decode_paged", "decode_step": "decode",
         "decode_chunk": "decode_chunk",
         "decode_step_paged_quant": "decode_paged_quant",
         "decode_chunk_quant": "decode_chunk_quant"}


class LayerKind:
    """What a layer, or the kind of mixer in it, says of itself: these are
    the defaults.  It has an entry point's path where it defines the method
    :data:`PATHS` names; where it does not, ``missing`` says what the path
    would need, and None says that it carries nothing from one token to the
    next, so that its layer runs it in a cache path as training does."""

    scope = "attention"     # the named scope it bills to
    part = None             # beside window layers: attention's own scope
    routed = False          # takes the routing made below (an expert layer)
    own_rotary = False      # turns its own rotary lanes (no shared tables)
    norm32 = False          # reads its layer's norm in float32 (``h32``)
    caches = {}             # {array: record}: an int for the pool's, "window"
    lacks = {}              # {path: what it needs} where cfg decides
    missing = None

    def lacking(self, path):
        """What this needs for the entry point ``path``, or None."""
        if path in self.lacks:
            return self.lacks[path]
        return None if hasattr(self, PATHS[path]) else self.missing


_HEAD_NORM_EPS = 1e-5


def _rows_rms_norm(x, weight):
    """RMSNorm over each head's lanes of ``(b, s, heads * head_dim)`` rows
    in float32 (QK-norm: every head alike, one ``weight`` of ``head_dim``).
    The sums over a head's lanes and their way back to the lanes are two
    products with a 0/1 matrix, 1 where lane ``i`` belongs to head ``j``, so
    no array is ever split into heads."""
    head_dim, width = weight.shape[0], x.shape[-1]
    groups = (jnp.arange(width)[:, None] // head_dim
              == jnp.arange(width // head_dim)).astype(_f32)
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


def _out_init(cfg):
    """Initialiser of a layer's output projection: under a layer pattern
    the residual writes are scaled down by sqrt(layers) (the source's
    ``rescale_prenorm_residual``); None keeps the linear layer's default."""
    if cfg.layer_pattern is None:
        return None
    std = INIT_STD / cfg.num_layers ** 0.5
    return lambda key, shape, dtype=_f32: std * jax.random.normal(
        key, shape, dtype)


def _rope_heads(q, k, cos, sin):
    """q and k ``(b, s, heads, head_dim)`` through the fused rotary kernel,
    which takes ``(s, b, heads, head_dim)``."""
    return tuple(fused_apply_rotary_pos_emb_cached(
        rows.transpose(1, 0, 2, 3), cos, sin).transpose(1, 0, 2, 3)
        for rows in (q, k))


class ParallelAttention(LayerKind):
    """Causal self-attention: TP-sharded QKV/proj, fused RoPE + softmax
    (apex ``transformer`` attention with FusedScaleMaskSoftmax.causal).
    As a pattern's ``*`` layer: ``attention``, its entry of
    ``cfg.attention_types``; ``rotates``, of ``cfg.rotary_layers``."""

    def __init__(self, cfg, attention=None, rotates=None):
        self.cfg = cfg
        self.window = cfg.sliding_window if attention == "sliding" else None
        self.rotates = rotates
        # beside window layers: [q | k | v] rows through the banded kernel,
        # attention and residual billed by kind
        self.banded = attention is not None and cfg.sliding_window > 0
        self.part = (("attention.window" if self.window else "attention.full")
                     if self.banded else None)
        # the pool's first array, or a window layer's storage
        self.array = "window" if self.window else 0
        self.caches = {self.array: (2, cfg.num_kv_heads * cfg.head_dim)
                       + ((self.window,) if self.window else ())}
        if attention is not None and (
                cfg.num_kv_heads != cfg.num_attention_heads or cfg.qk_norm
                or cfg.rotary_layers or cfg.moe_route_before_attention):
            self.lacks = dict.fromkeys(
                ("prefill", "decode_step_paged"),
                "a '*' layer's cache paths write one K and one V head for "
                "every query head, put no norm on them, attend to every "
                "position, rotate every layer alike and route each expert "
                "layer on its own norm: no grouped KV heads, QK-norm, window "
                "or rotary by layer (a 'P' layer's grouped heads are served)")
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
            return tuple(rows.reshape(b, rows.shape[1], -1, cfg.head_dim)
                         for rows in self._rows(params, x, rope_cos, rope_sin))
        qkv, _ = self.qkv(params["qkv"], x)      # (b, s, 3h/t)
        s = qkv.shape[1]
        nh = qkv.shape[-1] // (3 * self.cfg.head_dim)
        qkv = qkv.reshape(b, s, nh, 3 * self.cfg.head_dim)
        return jnp.split(qkv, 3, axis=-1)

    def _rows(self, params, x, rope_cos=None, rope_sin=None, positions=None):
        """Grouped heads' ``(q, k, v)`` as rows ``(b, s, heads * head_dim)``
        and ``(b, s, kv_heads * head_dim)``: ``[q | k | v]`` side by side,
        k and v of num_kv_heads.  QK-norm and the rotary code work on the
        rows, all heads' lanes side by side: XLA lays a (b, s, heads, 64)
        array out with the sequence in the lanes and pays a transposing
        copy on each side of it.  Given ``positions`` (a parallel block's
        layer that rotates), q and k are turned to them on adjacent pairs
        of every head's lanes instead of by the tables."""
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
        if positions is not None:
            q, k = (_rotary_rows(rows, positions, cfg.head_dim, cfg.rope_base)
                    for rows in (q, k))
        return q, k, v

    def __call__(self, params, x, rope_cos=None, rope_sin=None,
                 dropout_seed=None):
        cfg = self.cfg
        if self.rotates == 0:       # a "*" layer with no position code
            rope_cos = rope_sin = None
        if self.banded:
            # rows in and out of :func:`grouped_flash`, every row over its
            # last ``window`` positions (None: all of them), no KV head
            # broadcast
            q, k, v = self._rows(params, x, rope_cos, rope_sin)
            out, _ = self.proj(params["proj"], grouped_flash(
                q, k, v, cfg.num_attention_heads, self.window))
            return out
        b = x.shape[0]
        grouped = cfg.num_kv_heads != cfg.num_attention_heads
        # (b, s, nh, hd); grouped heads come back with their positions
        q, k, v = self._qkv(params, x, rope_cos, rope_sin)
        s = q.shape[1]
        nh = q.shape[2]
        if rope_cos is not None and not grouped:
            q, k = _rope_heads(q, k, rope_cos, rope_sin)
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

    def prefill_heads(self, params, x, rope_cos=None, rope_sin=None):
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
            q, k = _rope_heads(q, k, rope_cos, rope_sin)
        ctx = flash_attention_bshd(q, k, v, causal=True)
        ctx = ctx.reshape(b, s, nh * cfg.head_dim)
        out, _ = self.proj(params["proj"], ctx)
        return out, (k, v)

    def prefill(self, params, x, rope_cos=None, rope_sin=None,
                selection=None):
        """A ``*`` layer's: :meth:`prefill_heads`, K and V its one record."""
        y, kv = self.prefill_heads(params, x, rope_cos, rope_sin)
        return y, (jnp.stack(kv),), selection

    def _turn(self, q, k, depth, positions):
        """q and k ``(*positions.shape, heads, head_dim)`` turned to
        ``positions`` by rotary tables ``depth`` positions deep (not at all
        without rotary); the tables are constant-folded under jit."""
        if not self.cfg.rotary:
            return q, k
        f = rope_freqs(depth, self.cfg.head_dim, self.cfg.rope_base)
        return tuple(fused_apply_rotary_pos_emb_at_positions(
            rows.reshape(-1, *rows.shape[-2:]), jnp.cos(f), jnp.sin(f),
            positions.reshape(-1)).reshape(rows.shape) for rows in (q, k))

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
        q, k = self._turn(q, k, cache.shape[3], positions)
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

    def decode_paged(self, params, x, pool, at, block_tables, positions,
                     selection=None):
        """One-token decode against a paged block pool, the layer ``at[0]``
        of it — op-for-op the
        contiguous :meth:`decode` with the cache read/write indirected
        through ``block_tables`` (``(b, max_blocks)``; ``pool``:
        ``(num_blocks, layers, 2, block_size, kv_heads * head_dim)``).
        The token's K and V rows are written where they lie and the
        kernel reads the whole pool in place: no slice of the layer.
        RoPE tables are built at the pool's logical depth
        ``max_blocks * block_size``, whose rows are bitwise independent
        of the total length — paged and contiguous rows match exactly.
        Returns ``(out, pool, selection)``, the selection as it came.
        """
        cfg = self.cfg
        b = x.shape[0]
        bs = pool.shape[3]
        layer_index = at[0]
        q, k, v = self._qkv(params, x)           # (b, 1, nh, hd)
        q, k, v = q[:, 0], k[:, 0], v[:, 0]      # (b, nh, hd)
        q, k = self._turn(q, k, block_tables.shape[1] * bs, positions)
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
        return out, pool, selection

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
        q, k = self._turn(q, k, block_tables.shape[1] * pool.shape[3],
                          positions)
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

    def prefill_rows(self, params, x, rope_cos=None, rope_sin=None):
        """A parallel block's prefill of the grouped heads from position 0,
        every row causal over its last ``window`` positions (None: all of
        them), read in the rows by :func:`grouped_attention` (the rows turn
        their own lanes: no tables); returns ``(out, (record,))``, the record
        K and V ``(2, b, s, kv_heads, head_dim)`` as the cache keeps them."""
        cfg = self.cfg
        b, s = x.shape[:2]
        q, k, v = self._rows(params, x, positions=jnp.arange(s)
                             if self.rotates else None)
        out, _ = self.proj(params["proj"], grouped_attention(
            q, k, v, cfg.num_attention_heads, self.window))
        kv = (b, s, cfg.num_kv_heads, cfg.head_dim)
        return out, (jnp.stack((k.reshape(kv), v.reshape(kv))),)

    def decode_paged_rows(self, params, x, pool, at, block_tables, positions):
        """A parallel block's token a row of the grouped heads against
        ``pool`` ``(blocks, layers, 2, block_size, kv_heads * head_dim)``, its
        layer ``at[self.array]``: the token's K and V written where they lie,
        then every query head over its KV head's last ``window`` positions
        (None: all) through the table."""
        cfg = self.cfg
        b = x.shape[0]
        bs = pool.shape[3]
        layer_index = at[self.array]
        q, k, v = self._rows(params, x, positions=positions[:, None]
                             if self.rotates else None)
        bids = block_tables[jnp.arange(b), positions // bs]
        offs = positions % bs
        with jax.named_scope("attention.kv_write"):
            for part, rows in enumerate((k, v)):
                pool = scatter_paged_kv(
                    pool, layer_index, part, bids, offs,
                    rows.reshape(b, cfg.num_kv_heads, cfg.head_dim))
        ctx = flash_attention_decode_paged(
            q.reshape(b, cfg.num_attention_heads, cfg.head_dim), pool,
            layer_index, block_tables, positions + 1, window=self.window)
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
        q, k = self._turn(q, k, block_tables.shape[1] * bs, positions)
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
        q, k = self._turn(q, k, block_tables.shape[1] * pool.shape[3],
                          positions)

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
