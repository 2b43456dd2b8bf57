"""The plain reference of smallthinker-21ba3b-instruct: the model's forward
pass and loss in straightforward float32 ``jax.numpy`` (no kernels, no
dispatch: a dense ``(tokens, 64)`` score matrix, every held expert applied
to every token times its weight, 0 where not chosen; grouped attention with
each query head against its KV head, a window as a mask on a dense softmax)
that the trained step's loss and gradients are held against.  The
benchmark's own copy of the ``smallthinker_*`` functions of
``apex_tpu/models/reference.py``, so that no later change to the program
moves the yardstick.  It imports nothing of the program and is written from
the equations of the configuration's row:

    r   = x W_r                      router on the layer's INPUT x, before
                                     attention (64 logits, float32, no bias)
    h   = RMSNorm_in(x)              eps 1e-6, weight only
    q   = h Wq (28 heads x 128), k = h Wk, v = h Wv (4 heads x 128); no
          biases, no QK-norm
    window layers: q, k rotated by halves, base 1 500 000; the full layer:
    no position code
    a_h = softmax(q_h k_{h // 7}^T / sqrt(128) + M) v_{h // 7}
          M causal; on window layers also j > i - 4096
    x1  = x + concat_h(a_h) Wo
    u   = RMSNorm_post(x1)
    T   = top-6 of r;  g_e = softmax(r)_e / sum_T softmax(r)
    x'  = x1 + sum_{e in T, e held} g_e (relu(u G_e) * u U_e) D_e
    logits = RMSNorm_f(x_L) W_head^T        untied

On a TPU a float32 matmul runs at reduced precision unless
``jax.default_matmul_precision("highest")`` is set; ``smallthinker_reference``
sets it, and a caller of the layer functions sets it itself.

**How it blocks** (blocking changes no number: every block computes the sums
the unblocked expression would, in the same float32), so that one chip's
share at 16 384 positions and its gradients fit beside the trained program:
attention ``_ST_ROW_BLOCK`` query rows at a time over every key, the experts
and the loss ``_ST_TOKEN_BLOCK`` tokens at a time, each block under
``jax.checkpoint`` (recomputed in the backward); a weight is upcast to
float32 where it is used.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

_f32 = jnp.float32


def _ca_block(n, cap):
    """The largest divisor of ``n`` that is at most ``cap``."""
    return max(d for d in range(1, min(n, cap) + 1) if n % d == 0)


_ST_NORM_EPS = 1e-6        # the source's rms_norm_eps
_ST_ROW_BLOCK = 256
_ST_TOKEN_BLOCK = 2048


def _st_rms_norm(x, p):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True)
                             + _ST_NORM_EPS) * p["weight"].astype(_f32)


def _st_rope(x, base):
    """Rotary positions over the last axis of ``x`` ``(..., t, d)``,
    rotate-half: lanes ``i`` and ``i + d / 2`` of the row at position ``p``
    turn by ``p * base ** (-2i / d)``; the frequencies in float64 on the
    host, rounded to float32 once."""
    t, d = x.shape[-2:]
    inv = np.asarray(float(base) ** (-np.arange(0, d, 2) / d), np.float32)
    angle = jnp.arange(t, dtype=_f32)[:, None] * inv            # (t, d / 2)
    angle = jnp.concatenate([angle, angle], -1)
    x1, x2 = jnp.split(x, 2, -1)
    return x * jnp.cos(angle) + jnp.concatenate([-x2, x1], -1) * jnp.sin(angle)


def smallthinker_kinds(cfg):
    """One static descriptor a layer of the pattern: ``("*", window or
    None, rotates)`` or ``("E",)``."""
    attends = iter(cfg.attention_types)
    rotates = iter(cfg.rotary_layers or ())
    return tuple(
        ("*", cfg.sliding_window if next(attends) == "sliding" else None,
         next(rotates, 1)) if m == "*" else (m,)
        for m in cfg.layer_pattern)


def smallthinker_attention(p, u, cfg, window, rotates):
    """Grouped causal attention over ``u`` ``(b, t, hidden)``, query head
    ``h`` against KV head ``h // (heads / kv_heads)``, each row over its
    last ``window`` positions (None: all), q and k rotated where the layer
    ``rotates``; ``_ST_ROW_BLOCK`` rows at a time over every key."""
    b, t, _ = u.shape
    h, kv, d = cfg.num_attention_heads, cfg.num_kv_heads, cfg.head_dim
    qkv = u @ p["qkv"]["weight"].astype(_f32).T
    q, k, v = jnp.split(qkv, [h * d, (h + kv) * d], -1)
    q = q.reshape(b, t, kv, h // kv, d).transpose(0, 2, 3, 1, 4)
    k = k.reshape(b, t, kv, d).transpose(0, 2, 1, 3)           # (b, kv, t, d)
    v = v.reshape(b, t, kv, d).transpose(0, 2, 1, 3)
    if rotates:
        q, k = _st_rope(q, cfg.rope_base), _st_rope(k, cfg.rope_base)
    rb = _ca_block(t, _ST_ROW_BLOCK)

    @jax.checkpoint
    def rows(r0):
        s = jnp.einsum("bkgqd,bksd->bkgqs",
                       jax.lax.dynamic_slice_in_dim(q, r0, rb, 3), k) \
            * d ** -0.5
        row = r0 + jnp.arange(rb)[:, None]
        key = jnp.arange(t)[None, :]
        allowed = key <= row
        if window is not None:
            allowed = allowed & (key > row - window)
        probs = jax.nn.softmax(jnp.where(allowed, s, -jnp.inf), -1)
        return jnp.einsum("bkgqs,bksd->bkgqd", probs, v)

    o = jax.lax.map(rows, jnp.arange(0, t, rb))        # (n, b, kv, g, rb, d)
    o = o.transpose(1, 0, 4, 2, 3, 5).reshape(b, t, h * d)
    return o @ p["proj"]["weight"].astype(_f32).T


def smallthinker_route(p, x, cfg):
    """``(logits, choice, weight)`` of the router on ``x`` ``(tokens,
    hidden)``: ``r = x W_r`` over all experts (no bias), the ``k`` largest,
    their weights ``softmax(r)`` over all experts renormalised over the
    chosen (``norm_topk_prob``)."""
    logits = x @ p["router"]["weight"].astype(_f32).T
    _, choice = jax.lax.top_k(logits, cfg.moe_top_k)
    probs = jax.nn.softmax(logits, -1)
    w = jnp.take_along_axis(probs, choice, -1)
    return logits, choice, w / w.sum(-1, keepdims=True)


def smallthinker_experts(p, u, choice, w, cfg):
    """The held experts on ``u`` ``(tokens, hidden)``, each ``(relu(u G_e)
    * (u U_e)) D_e`` over every token, weighted where ``choice`` holds it (0
    elsewhere) and summed; ``_ST_TOKEN_BLOCK`` tokens at a time.  What the
    experts held elsewhere would add is left out, as in the program."""
    n, hidden = u.shape
    off, count = cfg.moe_held or (0, cfg.n_experts)
    tb = _ca_block(n, _ST_TOKEN_BLOCK)

    @jax.checkpoint
    def tokens(args):
        u, choice, w = args
        out = jnp.zeros_like(u)
        for e in range(count):
            gate = jnp.sum(jnp.where(choice == off + e, w, 0.0), -1)
            g, up = jnp.split(u @ p["w1"][e].astype(_f32), 2, -1)
            out = out + gate[:, None] * (
                (jnp.maximum(g, 0.0) * up) @ p["w2"][e].astype(_f32))
        return out

    blocks = (u.reshape(-1, tb, hidden),
              choice.reshape(-1, tb, choice.shape[-1]),
              w.reshape(-1, tb, w.shape[-1]))
    return jax.lax.map(tokens, blocks).reshape(n, hidden)


def smallthinker_layer(kind, lp, x, cfg, below=None):
    """One symbol of the pattern, ``x + mixer(RMSNorm(x))``.  An ``"E"``
    layer is routed on ``below``, the input of the ``"*"`` layer under it
    (``cfg.moe_route_before_attention``), or on its own norm's output."""
    u = _st_rms_norm(x, lp["norm"])
    if kind[0] == "*":
        return x + smallthinker_attention(lp["mixer"], u, cfg, *kind[1:])
    shape = x.shape
    ahead = below if cfg.moe_route_before_attention else u
    _, choice, w = smallthinker_route(lp["mixer"],
                                      ahead.reshape(-1, shape[-1]), cfg)
    return x + smallthinker_experts(lp["mixer"], u.reshape(-1, shape[-1]),
                                    choice, w, cfg).reshape(shape)


def smallthinker_head(params, x, cfg, targets=None):
    """``(logits, mean next-token loss or None)``: the final RMSNorm, then
    the untied head over the held rows; the loss ``_ST_TOKEN_BLOCK`` tokens
    at a time."""
    x = _st_rms_norm(x, params["final_layernorm"])
    w = params["lm_head"]["weight"].astype(_f32)
    logits = x @ w.T
    if targets is None:
        return logits, None
    hidden = x.shape[-1]
    tb = _ca_block(targets.size, _ST_TOKEN_BLOCK)

    @jax.checkpoint
    def nll(args):
        xb, tb_ = args
        logp = jax.nn.log_softmax(xb @ w.T, -1)
        return -jnp.sum(jnp.take_along_axis(logp, tb_[:, None], -1))

    total = jax.lax.map(nll, (x.reshape(-1, tb, hidden),
                              targets.reshape(-1, tb)))
    return logits, jnp.sum(total) / targets.size


def smallthinker_reference(params, tokens, cfg, targets=None):
    """``(logits (b, t, vocab), loss)`` of the model the ``smallthinker``
    family describes, for :class:`apex_tpu.models.gpt.GPTModel` under a
    ``layer_pattern`` of ``*`` and ``E`` (float32, ``"highest"`` matmul
    precision, no kernels).

    A published layer is ``x1 = x + A(RMSNorm_in(x))`` then ``x' = x1 +
    MoE(RMSNorm_post(x1))``, eps 1e-6, no bias anywhere: two symbols of the
    pattern.  After the last, RMSNorm, then ``logits = x W_head^T``
    (untied).

    * ``*``, attention.  ``[q | k | v] = h W_qkv`` (``heads``, ``kv_heads``,
      ``kv_heads`` of ``head_dim``), no QK-norm; a window layer rotates q
      and k by halves at ``cfg.rope_base`` and each row attends to its last
      ``sliding_window`` positions (its own included); a full layer has no
      position code and attends to every earlier position; scale
      ``head_dim^-0.5``, each KV head serving ``heads / kv_heads`` query
      heads; ``out = o W_o``.
    * ``E``, experts.  ``r = x W_r`` on the input ``x`` of the attention
      layer below, before its norm ("router placed before attention");
      ``T = top_k(r)``; ``g = softmax(r)_T / sum_T softmax(r)``; ``out =
      sum_{e in T, e held} g_e (relu(u G_e) * (u U_e)) D_e`` with ``u`` this
      layer's norm of ``x1``; no shared expert, no capacity, no auxiliary
      loss.

    The program keeps ``[G | U]`` as one matrix (an expert's ``w1``): the
    same numbers side by side.
    """
    p = jax.tree_util.tree_map(lambda a: jnp.asarray(a, _f32), params)
    with jax.default_matmul_precision("highest"):
        x = p["embedding"]["weight"][tokens]
        below = None
        for kind, lp in zip(smallthinker_kinds(cfg), p["layers"]):
            x, below = smallthinker_layer(kind, lp, x, cfg, below), x
        return smallthinker_head(p, x, cfg, targets)
