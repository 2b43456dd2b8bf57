"""Latent attention with a sparse-attention indexer: the kind of a
``layer_pattern``'s ``*`` layers where ``cfg.kv_lora_rank > 0``."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from apex_tpu.models.attention import LayerKind, _HEAD_NORM_EPS, _out_init
from apex_tpu.ops.latent_attention import (gather_index_keys, index_scores,
                                           index_segments, latent_record,
                                           latent_record_width,
                                           masked_attention, rotary_pairs,
                                           scatter_record,
                                           sparse_decode_attention,
                                           topk_mask, topk_positions)
from apex_tpu.transformer import tensor_parallel as tp

_f32 = jnp.float32


def _rms_norm(x, weight, eps=_HEAD_NORM_EPS):
    """RMSNorm over the last axis in float32, back in ``x``'s dtype (the
    two norms inside latent attention; every norm keeps float32 weights)."""
    x32 = x.astype(_f32)
    rstd = jax.lax.rsqrt(jnp.mean(x32 * x32, -1, keepdims=True) + eps)
    return (x32 * rstd * weight.astype(_f32)).astype(x.dtype)


_INDEX_NORM_EPS = 1e-6      # the indexer's LayerNorm on its keys
_SELECT_ROWS = 128          # query rows a pass of a prefill's selection
_PREFILL_HEADS = 16         # heads a pass of a prefill's attention


class LatentAttention(LayerKind):
    """Multi-head latent attention (MLA) with a learned sparse-attention
    indexer (DeepSeek-V3.2's, as GLM-5.2 shares it between layers).

    For ``u`` the layer's normed input: ``c_q = RMSNorm(u W_qa)``,
    ``[q_nope_i | q_rope_i] = c_q W_qb``; ``[c_kv | k_r] = u W_kva``,
    ``c = RMSNorm(c_kv)``, ``k_rope = rope(k_r)``; expanded, ``[k_nope_i |
    v_i] = c W_kvb`` and head ``i`` attends with ``[q_nope_i |
    rope(q_rope_i)]`` to ``[k_nope_i | k_rope]`` at scale ``(nope + rope) **
    -0.5``, over the positions ``S_t`` its selection allows.  Rotary
    positions turn adjacent pairs (``rope_interleave``).  What is cached for
    a position is ``(c, k_rope)`` and nothing else.

    The selection.  A layer that owns an indexer (``indexer_types``
    ``"full"``) computes ``q_I = c_q W_Iq`` (heads of ``index_head_dim``),
    ``k_I = LayerNorm(u W_Ik)``, both with rotary positions on their first
    ``qk_rope_head_dim`` lanes, ``w = u W_Iw / sqrt(heads * index_head_dim)``
    and ``I[t, s] = sum_j w[t, j] relu(q_I[t, j] . k_I[s])``; ``S_t`` is the
    ``index_topk`` positions ``s <= t`` of largest ``I[t, s]``, the lower
    position winning a tie, and all of them while there are no more.  It
    caches ``k_I`` too.  A ``"shared"`` layer has no indexer parameters and
    attends over the ``S_t`` handed to it.  One code for every length:
    there is no dense path beside the sparse one.

    Two forms of the same numbers.  :meth:`prefill` (expanded) expands
    ``c`` into per-head keys and values and attends over all keys with
    ``S_t`` as a mask.  :meth:`decode_paged` (absorbed, a tick) absorbs
    ``W_uk`` into the query and applies ``W_uv`` after the sum, scores
    every cached ``k_I`` of the row, and gathers the ``index_topk`` selected
    records and no other
    (:func:`apex_tpu.ops.latent_attention.sparse_decode_attention`).

    Layout of the up-projections' output features (the same numbers, an
    order of ours): ``W_qb`` head by head ``[q_nope_i | q_rope_i]``, a whole
    number of 128-lane tiles a head; ``W_kvb`` all heads' ``k_nope`` then
    all heads' ``v``, whose rows ``W_uk`` and ``W_uv`` are views of.
    A layer caches its record in the pool's first array, and an indexer's
    keys in the second."""

    own_rotary = True
    # where __call__ would go: it has cache paths only
    missing = ("latent attention has cache paths only (prefill, "
               "decode_step_paged): the training forward does not carry the "
               "indexer's selection from a full layer to the shared layers "
               "above it, and the indexer has no training loss")

    @staticmethod
    def check(cfg, grouped, n):
        """GPTConfig's checks of latent attention's fields (``n``: the
        pattern's ``*`` layers); fills in ``indexer_types``."""
        if (min(cfg.q_lora_rank, cfg.qk_nope_head_dim, cfg.v_head_dim,
                cfg.index_topk, cfg.index_n_heads) < 1
                or cfg.qk_rope_head_dim < 2 or cfg.qk_rope_head_dim % 2
                or cfg.index_head_dim < cfg.qk_rope_head_dim
                or not cfg.rotary or grouped or cfg.qk_norm
                or cfg.attention_dropout > 0.0):
            raise ValueError(
                "latent attention (kv_lora_rank > 0) needs q_lora_rank, "
                "qk_nope_head_dim, v_head_dim, an even qk_rope_head_dim, its "
                "indexer (index_topk, index_n_heads, index_head_dim >= "
                "qk_rope_head_dim: attention over every position is "
                "index_topk >= the context), rotary positions, and neither "
                "grouped heads, QK-norm nor attention dropout")
        if cfg.indexer_types is None:
            cfg.indexer_types = ("full",) * n
        cfg.indexer_types = tuple(cfg.indexer_types)
        if (len(cfg.indexer_types) != n or n < 1
                or set(cfg.indexer_types) - {"full", "shared"}
                or cfg.indexer_types[0] != "full"):
            raise ValueError(
                f"indexer_types {cfg.indexer_types!r}: 'full' or 'shared' "
                f"for each of the pattern's {n} '*' layers, the first "
                "'full' (a shared layer uses the selection of the nearest "
                "full layer below it)")

    def __init__(self, cfg, indexer: bool):
        self.cfg = cfg
        # a layer that owns an indexer selects from the layer's norm of its
        # input in float32, unrounded (``h32``)
        self.indexer = self.norm32 = indexer
        h = cfg.num_attention_heads
        self.nope, self.rope, self.vd = (cfg.qk_nope_head_dim,
                                         cfg.qk_rope_head_dim, cfg.v_head_dim)
        self.scale = float((self.nope + self.rope) ** -0.5)
        self.width = latent_record_width(cfg.kv_lora_rank, self.rope)
        self.caches = {0: (1, self.width)}
        if indexer:
            self.caches[1] = (1, cfg.index_head_dim, _f32)

        def linear(n_in, n_out):
            return tp.ColumnParallelLinear(
                n_in, n_out, bias=False, gather_output=False, world_size=1,
                axis_name=None, param_dtype=cfg.param_dtype)

        self.linears = {
            "q_a": linear(cfg.hidden_size, cfg.q_lora_rank),
            "q_b": linear(cfg.q_lora_rank, h * (self.nope + self.rope)),
            "kv_a": linear(cfg.hidden_size, cfg.kv_lora_rank + self.rope),
            "kv_b": linear(cfg.kv_lora_rank, h * (self.nope + self.vd)),
            "proj": tp.RowParallelLinear(
                h * self.vd, cfg.hidden_size, bias=False,
                input_is_parallel=True, init_method=_out_init(cfg),
                world_size=1, axis_name=None, param_dtype=cfg.param_dtype)}
        if indexer:
            ih, idim = cfg.index_n_heads, cfg.index_head_dim
            self.linears.update(
                index_q=linear(cfg.q_lora_rank, ih * idim),
                index_k=linear(cfg.hidden_size, idim),
                index_w=linear(cfg.hidden_size, ih))

    def init_params(self, key):
        cfg = self.cfg
        keys = jax.random.split(key, len(self.linears))
        params = {name: lin.init_params(k)
                  for (name, lin), k in zip(self.linears.items(), keys)}
        params["q_norm"] = {"weight": jnp.ones((cfg.q_lora_rank,), _f32)}
        params["kv_norm"] = {"weight": jnp.ones((cfg.kv_lora_rank,), _f32)}
        if self.indexer:
            params["index_k_norm"] = {
                "weight": jnp.ones((cfg.index_head_dim,), _f32),
                "bias": jnp.zeros((cfg.index_head_dim,), _f32)}
        return params

    def _linear(self, params, name, x):
        return self.linears[name](params[name], x)[0]

    def _query_latent(self, params, x):
        """``c_q = RMSNorm(u W_qa)`` ``(b, s, q_lora_rank)``."""
        with jax.named_scope("mla.q"):
            return _rms_norm(self._linear(params, "q_a", x),
                             params["q_norm"]["weight"])

    def _queries(self, c_q, w_qb, positions):
        """``(b, s, heads * (nope + rope))``: the heads whose rows of
        ``W_qb`` are ``w_qb``, the rotary part of each turned to
        ``positions``."""
        with jax.named_scope("mla.q"):
            return rotary_pairs(c_q @ w_qb.astype(c_q.dtype).T, positions,
                                self.rope, self.cfg.rope_base,
                                head_dim=self.nope + self.rope)

    def _latent(self, params, x, positions):
        """``(c (b, s, kv_lora_rank), k_rope (b, s, rope))``: the normed
        latent and the one rotary key, turned."""
        with jax.named_scope("mla.kv"):
            kv = self._linear(params, "kv_a", x)
            r = self.cfg.kv_lora_rank
            return (_rms_norm(kv[..., :r], params["kv_norm"]["weight"]),
                    rotary_pairs(kv[..., r:], positions, self.rope,
                                 self.cfg.rope_base))

    def _index(self, params, h32, positions):
        """The indexer's ``(c_q (b, s, q_lora_rank), q_I (b, s, heads, d), w
        (b, s, heads), k_I (b, s, d))``, all float32 and every product at
        HIGHEST precision, from the layer's normed input in float32;
        ``k_I`` is what a position leaves in the cache, as it is.

        Why not bf16: a selection is a discrete choice no near tie excuses,
        attention's output is a sum of ``index_topk`` nearly equal terms of
        random sign, and swapping ``k`` of them moves it by ``sqrt(2 k /
        index_topk)``: the 5 of 2 048 positions a row that bf16 index
        products changed (measured on a TPU v5e) moved the first layer's
        output by 7 %, where the embedding is no larger than attention's
        output, and every first-step row past 2 048 read 0.026-0.039 of the
        logits' range off the reference against 0.01 before it."""
        cfg = self.cfg
        b, s = h32.shape[:2]
        ih, idim = cfg.index_n_heads, cfg.index_head_dim

        def exact(x, name):
            return jnp.einsum("bsi,oi->bso", x,
                              params[name]["weight"].astype(_f32),
                              precision=jax.lax.Precision.HIGHEST)

        with jax.named_scope("indexer.score"):
            c_q = _rms_norm(exact(h32, "q_a"), params["q_norm"]["weight"])
            q_i = rotary_pairs(exact(c_q, "index_q"), positions, self.rope,
                               cfg.rope_base, head_dim=idim, first=True)
            k = exact(h32, "index_k")
            k = k - jnp.mean(k, -1, keepdims=True)
            k = k * jax.lax.rsqrt(jnp.mean(k * k, -1, keepdims=True)
                                  + _INDEX_NORM_EPS)
            k = k * params["index_k_norm"]["weight"] \
                + params["index_k_norm"]["bias"]
            k_i = rotary_pairs(k, positions, self.rope, cfg.rope_base,
                               first=True)
            w = exact(h32, "index_w") * float((ih * idim) ** -0.5)
        return c_q, q_i.reshape(b, s, ih, idim), w, k_i

    def _selection_mask(self, q_i, w, k_i):
        """``(b, s, s)`` bool, ``S_t`` of every row ``t`` of a prefill:
        :func:`topk_mask` of the index scores over the segments of
        :func:`index_segments`, ``_SELECT_ROWS`` queries at a time against
        the keys up to the segment's end (no later key is allowed to any
        of its rows); the rows before ``index_topk``, which select every
        key they may, are the causal mask and score nothing."""
        b, s = q_i.shape[:2]
        k = self.cfg.index_topk
        first = jnp.arange(min(k, s))[:, None] >= jnp.arange(s)
        parts = [jnp.broadcast_to(first, (b, *first.shape))]
        for r0, r1 in index_segments(s, k):
            rows = min(_SELECT_ROWS, r1 - r0)
            if (r1 - r0) % rows:
                raise ValueError(f"rows {r0}-{r1} of a prefill are not a "
                                 f"multiple of {rows}")
            keys = jnp.arange(r1)
            # the segment's own rows, sliced before the loop: sliced from
            # the whole prefill inside it, the TPU compiler fused the slice
            # into the product, and 3 rows of 16 384 took another 2 048th
            # key than the full-width selection (scores 1 ulp apart)
            q_seg, w_seg, k_pre = q_i[:, r0:r1], w[:, r0:r1], k_i[:, :r1]

            def block(t0):
                with jax.named_scope("indexer.score"):
                    scores = index_scores(
                        jax.lax.dynamic_slice_in_dim(q_seg, t0 - r0, rows, 1),
                        jax.lax.dynamic_slice_in_dim(w_seg, t0 - r0, rows, 1),
                        k_pre)
                with jax.named_scope("indexer.topk"):
                    causal = (t0 + jnp.arange(rows))[:, None] >= keys
                    return topk_mask(scores, causal, k)

            # (n, b, rows, r1), widened with False to s keys
            seg = jax.lax.map(block, jnp.arange(r0, r1, rows))
            parts.append(jnp.pad(seg.transpose(1, 0, 2, 3).reshape(b, -1, r1),
                                 ((0, 0), (0, 0), (0, s - r1))))
        return jnp.concatenate(parts, 1)

    def prefill(self, params, x, rope_cos=None, rope_sin=None,
                selection=None, h32=None):
        """The expanded form over a whole sequence from position 0 (``h32``:
        the same normed input in float32, which a layer that owns an indexer
        computes its selection from; it turns its own rotary lanes, so the
        tables are None); returns
        ``(out, records, selection)``: ``records`` the parts a position
        leaves in the cache, ``(latent record (1, b, s, 1, width),)`` and for
        a layer with an indexer its ``(1, b, s, 1, index_head_dim)`` keys as
        well; ``selection`` the ``(b, s, s)`` mask this layer attended
        under, its own or the one it was handed."""
        cfg = self.cfg
        b, s = x.shape[:2]
        h, r = cfg.num_attention_heads, cfg.kv_lora_rank
        positions = jnp.arange(s)
        c, k_rope = self._latent(params, x, positions)
        records = (latent_record(c, k_rope)[:, :, None, :],)
        if self.indexer:
            c_q, q_i, w, k_i = self._index(params, h32, positions)
            c_q = c_q.astype(x.dtype)       # the query path's, rounded once
            selection = self._selection_mask(q_i, w, k_i)
            records += (k_i[:, :, None, :],)
        else:
            c_q = self._query_latent(params, x)
        # the heads go through the up-projections and the attention
        # _PREFILL_HEADS at a time, one group after another (lax.map over
        # the groups' rows of W_qb and W_kvb): the same products a head,
        # and the expanded q, k, v and scores alive at once are a group's
        # (all 64 heads' were 4.8 GB of temporaries at 16 384 positions)
        hb = max(d for d in range(1, min(h, _PREFILL_HEADS) + 1)
                 if h % d == 0)
        w_kvb = params["kv_b"]["weight"]
        groups = (params["q_b"]["weight"].reshape(h // hb, -1,
                                                  cfg.q_lora_rank),
                  w_kvb[:h * self.nope].reshape(h // hb, -1, r),
                  w_kvb[h * self.nope:].reshape(h // hb, -1, r))

        def heads_major(y):
            return y.reshape(b, s, hb, -1).transpose(0, 2, 1, 3)

        def group(ws):
            w_q, w_k, w_v = ws
            q = heads_major(self._queries(c_q, w_q, positions))
            with jax.named_scope("mla.kv"):
                k = jnp.concatenate([
                    heads_major(c @ w_k.astype(c.dtype).T),
                    jnp.broadcast_to(k_rope[:, None],
                                     (b, hb, s, self.rope))], -1)
                v = heads_major(c @ w_v.astype(c.dtype).T)
            with jax.named_scope("attention.sparse"):
                return masked_attention(q, k, v, selection, self.scale)

        ctx = jax.lax.map(group, groups)            # (h / hb, b, s, hb * vd)
        ctx = ctx.transpose(1, 2, 0, 3).reshape(b, s, h * self.vd)
        return (self._linear(params, "proj", ctx),
                tuple(r[None] for r in records), selection)

    def decode_paged(self, params, x, pools, at, block_tables, positions,
                     selection=None, h32=None):
        """The absorbed form, one token a row against the paged pools
        ``(latent records, index keys)``; ``at`` is this layer's index
        in each.  The token's record (and index key) is written where it
        lies; a layer with an indexer scores every cached key of the row
        and selects, and every head then reads the selected records alone.
        Returns ``(out, pools, selection)`` with ``selection`` ``(idx,
        valid)``, each ``(b, index_topk)``."""
        cfg = self.cfg
        b = x.shape[0]
        h, r = cfg.num_attention_heads, cfg.kv_lora_rank
        latents, keys = pools
        bs = latents.shape[3]
        blocks = block_tables[jnp.arange(b), positions // bs]
        now = positions[:, None]
        if self.indexer:
            c_q, q_i, w, k_i = self._index(params, h32, now)
            c_q = c_q.astype(x.dtype)
        else:
            c_q = self._query_latent(params, x)
        q = self._queries(c_q, params["q_b"]["weight"], now)
        c, k_rope = self._latent(params, x, now)
        with jax.named_scope("mla.kv"):
            latents = scatter_record(latents, at[0], blocks,
                                     positions % bs,
                                     latent_record(c[:, 0], k_rope[:, 0]))
        if self.indexer:
            with jax.named_scope("indexer.score"):
                keys = scatter_record(keys, at[1], blocks,
                                      positions % bs, k_i[:, 0])
                scores = index_scores(
                    q_i, w, gather_index_keys(keys, at[1],
                                              block_tables))[:, 0]
            with jax.named_scope("indexer.topk"):
                selection = topk_positions(scores, positions + 1,
                                           cfg.index_topk)
        w_kvb = params["kv_b"]["weight"].astype(x.dtype)
        q = q.reshape(b, h, self.nope + self.rope)
        with jax.named_scope("mla.absorb"):
            w_uk = w_kvb[:h * self.nope].reshape(h, self.nope, r)
            q_lat = jnp.einsum("bhd,hdc->bhc", q[..., :self.nope], w_uk)
            q_abs = jnp.concatenate([
                q_lat, q[..., self.nope:],
                jnp.zeros((b, h, self.width - r - self.rope), x.dtype)], -1)
        with jax.named_scope("attention.sparse"):
            o_lat = sparse_decode_attention(
                q_abs, latents, at[0], block_tables, *selection,
                scale=self.scale, v_width=r)
        with jax.named_scope("mla.absorb"):
            w_uv = w_kvb[h * self.nope:].reshape(h, self.vd, r)
            ctx = jnp.einsum("bhc,hdc->bhd", o_lat, w_uv)
        out = self._linear(params, "proj", ctx.reshape(b, 1, h * self.vd))
        return out, (latents, keys), selection
