"""The plain reference of command-a-plus-05-2026: the model's forward pass in
straightforward float32 ``jax.numpy`` (no kernels, no cache, no batching;
grouped attention with each query head against its KV head, a window as a
mask on a dense softmax; a loop over the held experts) that the served
logits are held against.  The benchmark's own copy of the ``command_a_*``
functions of ``apex_tpu/models/reference.py``, so that no later change to the
program moves the yardstick.  It imports nothing of the program and is
written from the equations of the configuration's row:

    h   = LN(x)                      LayerNorm, a weight and no bias, eps 1e-5
    q   = h Wq (heads x 128), k = h Wk, v = h Wv (kv heads x 128); no biases
    window layers: q, k turned on ADJACENT pairs of all 128 lanes, base
    50 000; full layers: no position code
    a_h = softmax(q_h k_{h // (heads / kv heads)}^T / sqrt(128) + M) v_...
          M causal, and on window layers j > i - sliding_window
    A   = concat_h(a_h) Wo
    s   = sigmoid(h Wr) (float32), T = top-k of s, g_e = s_e / sum_T s
    R   = sum_{e in T, held} g_e (silu(h W1_e) * h W3_e) W2_e
    Sh  = (silu(h U1) * h U3) U2     the shared experts side by side
    x'  = x + A + (R + Sh) / 2
    logits = LN_f(x_L) E^T           tied, logit_scale 1

On a TPU a float32 matmul runs at reduced precision unless
``jax.default_matmul_precision("highest")`` is set; ``_forward`` sets it.

**How it blocks** (blocking changes no number: every block computes the sums
the unblocked expression would, in the same float32).  The harness calls
each function under one ``jax.jit`` on one row of 16 384 positions beside
the engine's 6.25 GB of bf16 weights and its pool, so nothing here may
upcast all parameters at once:

* a weight is upcast to float32 where it is used (``_w``): one matrix, one
  expert, one KV head's group of rows at a time;
* attention runs one KV head's group of query heads at a time (``jax.lax.map``
  over the groups' rows of the QKV weight) and, inside a group, ``ROW_BLOCK``
  query rows at a time against the keys that the causal mask and the window
  allow some row of the block (none beyond);
* the shared experts run ``FFN_BLOCK`` of their hidden columns at a time and
  add the blocks' down-projections;
* the experts run one after another (``jax.lax.scan`` over the held stack,
  the sum carried), each over every token, weighted where the router chose
  it.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

_f32 = jnp.float32
_NORM_EPS = 1e-5        # the source's layer_norm_eps
ROW_BLOCK = 512
FFN_BLOCK = 4096


def _w(p):
    """A linear layer's ``(out, in)`` weight in float32."""
    return p["weight"].astype(_f32)


def _layer_norm(x, p):
    m = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - m), -1, keepdims=True)
    return (x - m) * jax.lax.rsqrt(var + _NORM_EPS) \
        * p["weight"].astype(_f32)


def _block(n, cap):
    """The largest divisor of ``n`` that is at most ``cap``."""
    return max(d for d in range(1, min(n, cap) + 1) if n % d == 0)


def _rope(x, base):
    """Rotary positions over the whole last axis of ``x`` ``(..., t, d)``,
    adjacent pairs (``rope_gptj``): lanes ``2i`` and ``2i + 1`` of the row
    at position ``p`` turn by ``p * base ** (-2i / d)``; the frequencies in
    float64 on the host, rounded to float32 once."""
    t, d = x.shape[-2:]
    inv = np.asarray(float(base) ** (-np.arange(0, d, 2) / d), np.float32)
    angle = jnp.arange(t, dtype=_f32)[:, None] * inv            # (t, d / 2)
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    x0, x1 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x0 * cos - x1 * sin, x1 * cos + x0 * sin],
                     -1).reshape(x.shape)


def attention(p, u, cfg, window):
    """Grouped attention of one layer over ``u`` ``(b, t, hidden)``:
    ``window`` positions a row with rotary positions (a window layer), or
    every earlier position and no position code (``window`` None)."""
    b, t, hidden = u.shape
    h, kv, d = cfg.num_attention_heads, cfg.num_kv_heads, cfg.head_dim
    g = h // kv
    w = p["qkv"]["weight"]                  # rows [q | k | v]
    groups = (w[:h * d].reshape(kv, g * d, hidden),
              w[h * d:(h + kv) * d].reshape(kv, d, hidden),
              w[(h + kv) * d:].reshape(kv, d, hidden))
    rb = _block(t, ROW_BLOCK)

    def group(ws):
        wq, wk, wv = (x.astype(_f32) for x in ws)
        q = (u @ wq.T).reshape(b, t, g, d).transpose(0, 2, 1, 3)
        k, v = u @ wk.T, u @ wv.T                               # (b, t, d)
        if window is not None:
            q, k = _rope(q, cfg.rope_base), _rope(k, cfg.rope_base)
        out = []
        for r0 in range(0, t, rb):
            end = r0 + rb
            lo = 0 if window is None else max(0, r0 + 1 - window)
            scores = jnp.einsum("bgqd,bkd->bgqk", q[:, :, r0:end],
                                k[:, lo:end]) * d ** -0.5
            row = jnp.arange(r0, end)[:, None]
            key = jnp.arange(lo, end)[None, :]
            allowed = key <= row
            if window is not None:
                allowed = allowed & (key > row - window)
            probs = jax.nn.softmax(jnp.where(allowed, scores, -jnp.inf), -1)
            out.append(jnp.einsum("bgqk,bkd->bgqd", probs, v[:, lo:end]))
        return jnp.concatenate(out, 2)                      # (b, g, t, d)

    o = jax.lax.map(group, groups)                      # (kv, b, g, t, d)
    o = o.transpose(1, 3, 0, 2, 4).reshape(b, t, h * d)
    return o @ _w(p["proj"]).T


def _swiglu(u, w1, w2):
    """``(silu(u W_g) * (u W_u)) W_d`` with ``w1`` holding ``[W_g | W_u]``
    side by side (in, 2 x width) and ``w2`` (width, out)."""
    gate, up = jnp.split(u @ w1, 2, -1)
    return (jax.nn.silu(gate) * up) @ w2


def shared(p, u):
    """The shared experts side by side, one gated FFN of their summed width
    (``fc1``'s rows ``[gate | up]``), a block of hidden columns at a time."""
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
    """``(scores, choice, weight)`` of the router on ``u`` ``(tokens,
    hidden)``: ``s = sigmoid(u W_r)`` over all experts, the ``k`` largest
    (no correction bias), the chosen ``s`` over their sum
    (``norm_topk_prob``)."""
    scores = jax.nn.sigmoid(u @ _w(p["router"]).T)
    _, choice = jax.lax.top_k(scores, cfg.moe_top_k)
    w = jnp.take_along_axis(scores, choice, -1)
    return scores, choice, w / w.sum(-1, keepdims=True)


def experts(p, u, cfg):
    """``((R + Sh) / 2, scores)``: the held experts one after another, each
    the gated FFN at the expert width over every token, weighted where the
    router chose it, and the shared experts once.  What the experts held
    elsewhere would add to ``R`` is left out, as in the program."""
    shape = u.shape
    u = u.reshape(-1, shape[-1])
    scores, choice, w = route(p, u, cfg)
    off, count = cfg.moe_held or (0, cfg.n_experts)

    def one(out, args):
        e, w1, w2 = args
        gate = jnp.sum(jnp.where(choice == off + e, w, 0.0), -1)  # (tokens,)
        return out + gate[:, None] * _swiglu(u, w1.astype(_f32),
                                             w2.astype(_f32)), None

    routed, _ = jax.lax.scan(one, jnp.zeros_like(u),
                             (jnp.arange(count), p["w1"], p["w2"]))
    out = (routed + shared(p["shared"], u)) / 2
    return out.reshape(shape), scores.reshape(*shape[:-1], -1)


def _forward(params, tokens, cfg):
    """``(logits (b, t, vocab), [router scores (b, t, experts) a layer])``.
    Every layer is the parallel block, ``x <- x + A(h) + MoE(h)`` with ``h =
    LN(x)``; its entry of ``attention_types`` says window or full.  After
    the last, LN and the tied head."""
    ranked = []
    with jax.default_matmul_precision("highest"):
        x = params["embedding"]["weight"][tokens].astype(_f32)
        for kind, lp in zip(cfg.attention_types, params["layers"]):
            u = _layer_norm(x, lp["norm"])
            window = cfg.sliding_window if kind == "sliding" else None
            y, s = experts(lp["mlp"], u, cfg)
            x = x + attention(lp["attention"], u, cfg, window) + y
            ranked.append(s)
        x = _layer_norm(x, params["final_layernorm"])
        return x @ _w(params["embedding"]).T, ranked


def gpt_reference_logits(params, tokens, cfg):
    """``(b, t, vocab)`` float32 logits of ``tokens`` ``(b, t)`` from the
    engine's parameters as they are (bf16; upcast where used)."""
    return _forward(params, tokens, cfg)[0]


def tie_gaps(ranked, cfg):
    """``(b, t)``: over the layers' router scores, the least distance
    between the last chosen and the first unchosen at which an expert held
    here is one of the two; infinite where neither is held in any layer
    (the harness's contract: ``benchmarks/README.md``)."""
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
    """``(b, t)``: the positions at which, in any layer of this float32
    forward, the last chosen and the first unchosen router score lie within
    ``margin`` and an expert held here is among the two."""
    return tie_gaps(_forward(params, tokens, cfg)[1], cfg) < margin
