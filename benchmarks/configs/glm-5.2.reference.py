"""The plain reference of glm-5.2: the model's forward pass in straightforward
float32 ``jax.numpy`` (no kernels, no cache, no batching, latent attention in
its EXPANDED form only with the indexer's selection as a mask on a dense
softmax, a loop over the held experts) that the served logits are held
against.  The benchmark's own copy of the ``glm_dsa_*`` functions of
``apex_tpu/models/reference.py``, so that no later change to the program
moves the yardstick.  It imports nothing of the program and is written from
the equations of the issue that brought it (``glm-5.2.README.md``).

On a TPU a float32 matmul runs at reduced precision unless
``jax.default_matmul_precision("highest")`` is set; ``_forward`` sets it.

**How it blocks** (blocking changes no number: every block computes the sums
the unblocked expression would, in the same float32).  The harness calls
each function under one ``jax.jit`` on one row of 16 384 positions, beside
the engine's 7.8 GB of bf16 weights and its pool, so nothing here may upcast
all parameters at once, and 64 heads x 16 384^2 float32 scores would be
69 GB:

* a weight is upcast to float32 where it is used (``_w``): one matrix, one
  expert, one block of heads' rows at a time;
* the index scores and the selection run ``SELECT_ROWS`` query rows at a
  time against all keys; the selection is a ``(t, t)`` mask;
* attention runs ``HEAD_BLOCK`` heads at a time (``jax.lax.map`` over blocks
  of the per-head rows of ``W_qb`` and ``W_kvb``) and, inside a block of
  heads, ``ROW_BLOCK`` query rows at a time against the keys up to the
  block's last row (a causal mask allows nothing beyond);
* the dense FFN runs ``FFN_BLOCK`` of its hidden columns at a time and adds
  the blocks' down-projections;
* the experts run one after another (``jax.lax.scan`` over the held stack,
  the sum carried), each over every token, weighted where the router chose
  it.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

_f32 = jnp.float32
_NORM_EPS = 1e-5        # the source's rms_norm_eps
_INDEX_NORM_EPS = 1e-6  # the indexer's LayerNorm
HEAD_BLOCK = 16
ROW_BLOCK = 1024
SELECT_ROWS = 256
FFN_BLOCK = 4096


def _w(p):
    """A linear layer's ``(out, in)`` weight in float32."""
    return p["weight"].astype(_f32)


def _rms_norm(x, p):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True)
                             + _NORM_EPS) * p["weight"].astype(_f32)


def _block(n, cap):
    """The largest divisor of ``n`` that is at most ``cap``."""
    return max(d for d in range(1, min(n, cap) + 1) if n % d == 0)


def _rope(x, base):
    """Rotary positions over the whole last axis of ``x`` ``(..., t, d)``,
    adjacent pairs (``rope_interleave``): lanes ``2i`` and ``2i + 1`` of the
    row at position ``p`` turn by ``p * base ** (-2i / d)``; the
    frequencies in float64 on the host, rounded to float32 once (a power
    computed on a TPU is 5e-6 off, 0.02 rad at position 4 096)."""
    t, d = x.shape[-2:]
    inv = np.asarray(float(base) ** (-np.arange(0, d, 2) / d), np.float32)
    angle = jnp.arange(t, dtype=_f32)[:, None] * inv            # (t, d / 2)
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    x0, x1 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x0 * cos - x1 * sin, x1 * cos + x0 * sin],
                     -1).reshape(x.shape)


def selection(p, u, c_q, cfg):
    """``(b, t, t)`` bool: ``S_t`` of the indexer ``p`` owns.  ``q_I = c_q
    W_Iq`` (heads of ``index_head_dim``), ``k_I = LayerNorm(u W_Ik)``, both
    with rotary positions on their first ``qk_rope_head_dim`` lanes, ``w =
    u W_Iw / sqrt(heads) / sqrt(index_head_dim)``, ``I[t, s] = sum_j w[t, j]
    relu(q_I[t, j] . k_I[s])``; ``S_t`` is every ``s <= t`` while there are
    at most ``index_topk`` of them and after that the ``index_topk`` of
    largest ``I[t, s]``, the lower position winning a tie."""
    b, t, _ = u.shape
    ih, d, rope = cfg.index_n_heads, cfg.index_head_dim, cfg.qk_rope_head_dim
    k_top = min(cfg.index_topk, t)
    q = (c_q @ _w(p["index_q"]).T).reshape(b, t, ih, d).transpose(0, 2, 1, 3)
    q = jnp.concatenate([_rope(q[..., :rope], cfg.rope_base),
                         q[..., rope:]], -1)                # (b, ih, t, d)
    k = u @ _w(p["index_k"]).T
    k = k - jnp.mean(k, -1, keepdims=True)
    k = k * jax.lax.rsqrt(jnp.mean(k * k, -1, keepdims=True)
                          + _INDEX_NORM_EPS)
    k = k * p["index_k_norm"]["weight"].astype(_f32) \
        + p["index_k_norm"]["bias"].astype(_f32)
    k = jnp.concatenate([_rope(k[..., :rope], cfg.rope_base),
                         k[..., rope:]], -1)                # (b, t, d)
    w = (u @ _w(p["index_w"]).T) / ih ** 0.5 / d ** 0.5     # (b, t, ih)
    rb = _block(t, SELECT_ROWS)
    keys = jnp.arange(t)

    def rows(r0):
        qb = jax.lax.dynamic_slice_in_dim(q, r0, rb, 2)
        wb = jax.lax.dynamic_slice_in_dim(w, r0, rb, 1)
        dots = jnp.maximum(jnp.einsum("bhqd,bkd->bhqk", qb, k), 0.0)
        score = jnp.einsum("bhqk,bqh->bqk", dots, wb)
        causal = (r0 + jnp.arange(rb))[:, None] >= keys[None, :]
        score = jnp.where(causal, score, -jnp.inf)
        kth = jax.lax.top_k(score, k_top)[0][..., -1:]      # k-th largest
        above = score > kth
        at = (score == kth) & causal
        room = k_top - jnp.sum(above, -1, keepdims=True)
        return above | (at & (jnp.cumsum(at, -1) <= room))

    m = jax.lax.map(rows, jnp.arange(0, t, rb))             # (t / rb, b, rb, t)
    return m.transpose(1, 0, 2, 3).reshape(b, t, t)


def mla(p, u, cfg, chosen):
    """The ``*`` mixer, expanded: ``c_q = RMSNorm(u W_qa)``; ``[q_nope_i |
    q_rope_i] = c_q W_qb`` head by head; ``[c_kv | k_r] = u W_kva``; ``c =
    RMSNorm(c_kv)``; ``[k_nope_i | v_i] = c W_kvb`` (the program keeps
    ``W_kvb``'s rows as all heads' ``k_nope`` then all heads' ``v``); one
    rotary key ``rope(k_r)`` for all heads; ``softmax over s in S_t of (q_i
    . k_i / sqrt(nope + rope)) v_i``; the heads side by side times ``W_o``.
    ``chosen`` is the ``(b, t, t)`` selection of the nearest layer below
    that owns an indexer, or None if this layer owns one; returns ``(out,
    selection)``."""
    b, t, _ = u.shape
    h, r = cfg.num_attention_heads, cfg.kv_lora_rank
    nope, rope, vd = (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                      cfg.v_head_dim)
    c_q = _rms_norm(u @ _w(p["q_a"]).T, p["q_norm"])
    if "index_q" in p:
        chosen = selection(p, u, c_q, cfg)
    kv = u @ _w(p["kv_a"]).T
    c = _rms_norm(kv[..., :r], p["kv_norm"])
    k_rope = _rope(kv[..., r:], cfg.rope_base)                # (b, t, rope)
    hb, rb = _block(h, HEAD_BLOCK), _block(t, ROW_BLOCK)
    w_kvb = p["kv_b"]["weight"]
    blocks = (p["q_b"]["weight"].reshape(h // hb, hb, nope + rope, -1),
              w_kvb[:h * nope].reshape(h // hb, hb, nope, r),
              w_kvb[h * nope:].reshape(h // hb, hb, vd, r))
    scale = (nope + rope) ** -0.5

    def heads(ws):
        wq, wk, wv = (w.astype(_f32) for w in ws)
        q = jnp.einsum("btq,hdq->bhtd", c_q, wq)
        q_nope, q_rope = q[..., :nope], _rope(q[..., nope:], cfg.rope_base)
        k_nope = jnp.einsum("btc,hdc->bhtd", c, wk)
        v = jnp.einsum("btc,hdc->bhtd", c, wv)
        out = []
        for r0 in range(0, t, rb):
            end = r0 + rb           # no row of the block sees a later key
            scores = (jnp.einsum("bhqd,bhkd->bhqk", q_nope[:, :, r0:end],
                                 k_nope[:, :, :end])
                      + jnp.einsum("bhqd,bkd->bhqk", q_rope[:, :, r0:end],
                                   k_rope[:, :end])) * scale
            allowed = chosen[:, None, r0:end, :end]
            probs = jax.nn.softmax(jnp.where(allowed, scores, -jnp.inf), -1)
            out.append(jnp.einsum("bhqk,bhkd->bhqd", probs, v[:, :, :end]))
        return jnp.concatenate(out, 2)

    o = jax.lax.map(heads, blocks)                    # (h / hb, b, hb, t, vd)
    o = o.transpose(1, 3, 0, 2, 4).reshape(b, t, h * vd)
    return o @ _w(p["proj"]).T, chosen


def _swiglu(u, w1, w2):
    """``(silu(u W_g) * (u W_u)) W_d`` with ``w1`` holding ``[W_g | W_u]``
    side by side (in, 2 x width) and ``w2`` (width, out)."""
    gate, up = jnp.split(u @ w1, 2, -1)
    return (jax.nn.silu(gate) * up) @ w2


def dense(p, u, cfg):
    """The ``D`` mixer (and the shared expert): the gated FFN, ``fc1``'s
    rows ``[gate | up]``, a block of hidden columns at a time."""
    w = p["fc2"]["weight"].shape[1]
    fb = _block(w, FFN_BLOCK)
    out = jnp.zeros_like(u)
    for c0 in range(0, w, fb):
        w1 = jnp.concatenate([p["fc1"]["weight"][c0:c0 + fb],
                              p["fc1"]["weight"][w + c0:w + c0 + fb]])
        out = out + _swiglu(u, w1.astype(_f32).T,
                            p["fc2"]["weight"][:, c0:c0 + fb].astype(_f32).T)
    return out


def route(p, u, cfg):
    """``(ranked, choice, weight)`` of the router on ``u`` ``(tokens,
    hidden)``: ``s = sigmoid(u W_r)`` over all experts, the ``k`` largest of
    ``s + b`` (``noaux_tc``; one group, none excluded), the chosen ``s`` over
    their sum plus 1e-20 (``norm_topk_prob``), times the scaling factor."""
    scores = jax.nn.sigmoid(u @ _w(p["router"]).T)
    ranked = scores + p["router"]["bias"].astype(_f32)
    _, choice = jax.lax.top_k(ranked, cfg.moe_top_k)
    w = jnp.take_along_axis(scores, choice, -1)
    w = cfg.moe_routed_scale * w / (w.sum(-1, keepdims=True) + 1e-20)
    return ranked, choice, w


def experts(p, u, cfg):
    """The ``E`` mixer: ``(out, ranked scores)``.  The held experts one
    after another, each the gated FFN at the expert width over every token,
    weighted where the router chose it, plus the shared expert once.  What
    the experts held elsewhere would add is left out, as in the program."""
    shape = u.shape
    u = u.reshape(-1, shape[-1])
    ranked, choice, w = route(p, u, cfg)
    off, count = cfg.moe_held or (0, cfg.n_experts)

    def one(out, args):
        e, w1, w2 = args
        gate = jnp.sum(jnp.where(choice == off + e, w, 0.0), -1)  # (tokens,)
        return out + gate[:, None] * _swiglu(u, w1.astype(_f32),
                                             w2.astype(_f32)), None

    out, _ = jax.lax.scan(one, dense(p["shared"], u, cfg),
                          (jnp.arange(count), p["w1"], p["w2"]))
    return out.reshape(shape), ranked.reshape(*shape[:-1], -1)


def _forward(params, tokens, cfg):
    """``(logits (b, t, vocab), [ranked scores (b, t, experts) per expert
    layer])``.  A symbol of the pattern is ``x <- x + mixer(RMSNorm(x))``,
    eps 1e-5, no bias on any linear layer; a published layer is two
    symbols, its attention and its feed-forward.  After the last, RMSNorm
    and the untied head."""
    ranked = []
    chosen = None
    with jax.default_matmul_precision("highest"):
        x = params["embedding"]["weight"][tokens].astype(_f32)
        for kind, lp in zip(cfg.layer_pattern, params["layers"]):
            u = _rms_norm(x, lp["norm"])
            if kind == "*":
                y, chosen = mla(lp["mixer"], u, cfg, chosen)
            elif kind == "D":
                y = dense(lp["mixer"], u, cfg)
            else:
                y, s = experts(lp["mixer"], u, cfg)
                ranked.append(s)
            x = x + y
        x = _rms_norm(x, params["final_layernorm"])
        return x @ _w(params["lm_head"]).T, ranked


def gpt_reference_logits(params, tokens, cfg):
    """``(b, t, vocab)`` float32 logits of ``tokens`` ``(b, t)``, every
    position attending to its selection of the whole prefix, from the
    engine's parameters as they are (bf16; upcast where used)."""
    return _forward(params, tokens, cfg)[0]


def tie_gaps(ranked, cfg):
    """``(b, t)``: over the expert layers' ``ranked`` scores, the least
    distance between the last chosen and the first unchosen at which an
    expert held here is one of the two; infinite where neither is held in
    any layer (the harness's contract: ``benchmarks/README.md``)."""
    k = cfg.moe_top_k
    off, count = cfg.moe_held or (0, cfg.n_experts)
    gaps = []
    for s in ranked:
        top, who = jax.lax.top_k(s, k + 1)
        pair = who[..., k - 1:]                     # the two at the cut
        held = jnp.any((pair >= off) & (pair < off + count), -1)
        gaps.append(jnp.where(held, top[..., k - 1] - top[..., k], jnp.inf))
    return jnp.min(jnp.stack(gaps), 0)


def near_ties(params, tokens, cfg, margin):
    """``(b, t)``: the positions at which, in any expert layer of this
    float32 forward, the last chosen and the first unchosen score (biased,
    as the router ranks them) lie within ``margin`` and an expert held here
    is among the two."""
    return tie_gaps(_forward(params, tokens, cfg)[1], cfg) < margin
