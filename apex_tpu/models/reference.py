"""Plain references for the models: the forward pass in straightforward
float32 ``jax.numpy`` — no kernels, no cache, no TP layers — that the
system's logits are held against (tier-1 at a tiny size, ``chip_smoke.py``
at the published widths).

On a TPU a float32 matmul runs at reduced precision unless
``jax.default_matmul_precision("highest")`` is set; the functions here set
it themselves.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

_f32 = jnp.float32


def _layer_norm(x, p, eps=1e-5):
    m = x.mean(-1, keepdims=True)
    v = ((x - m) ** 2).mean(-1, keepdims=True)
    return (x - m) / jnp.sqrt(v + eps) * p["weight"] + p["bias"]


def _rope(x, head_dim):
    # half-split rotation, ops.rope.rope_freqs conventions; x (b, s, nh, hd)
    s = x.shape[1]
    inv = 1.0 / (10000.0 ** (jnp.arange(0, head_dim, 2, dtype=_f32)
                             / head_dim))
    f = jnp.outer(jnp.arange(s, dtype=_f32), inv)
    f = jnp.concatenate([f, f], axis=-1)[None, :, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return x * jnp.cos(f) + jnp.concatenate([-x2, x1], axis=-1) * jnp.sin(f)


def _linear(x, p):
    return x @ p["weight"].T + p["bias"]


def gpt_reference_logits(params, tokens, cfg):
    """``(b, s, vocab)`` float32 logits of the serial dense GPT
    (:class:`apex_tpu.models.gpt.GPTModel` with ``n_experts == 0``) for
    ``tokens`` ``(b, s)``: every position attends causally to the whole
    prefix, so row ``i`` is what prefill-then-decode must reproduce for
    the token at position ``i``."""
    p = jax.tree_util.tree_map(lambda a: jnp.asarray(a, _f32), params)
    b, s = tokens.shape
    nh, hd = cfg.num_attention_heads, cfg.head_dim
    with jax.default_matmul_precision("highest"):
        x = p["embedding"]["weight"][tokens]                   # (b, s, h)
        if not cfg.rotary:
            x = x + p["position_embedding"][:s]
        causal = jnp.tril(jnp.ones((s, s), bool))
        for lp in p["layers"]:
            h = _layer_norm(x, lp["input_layernorm"])
            qkv = _linear(h, lp["attention"]["qkv"])
            q, k, v = jnp.split(qkv.reshape(b, s, nh, 3 * hd), 3, axis=-1)
            if cfg.rotary:
                q, k = _rope(q, hd), _rope(k, hd)
            scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(
                jnp.asarray(hd, _f32))
            probs = jax.nn.softmax(
                jnp.where(causal, scores, -jnp.inf), axis=-1)
            ctx = jnp.einsum("bhqk,bkhd->bqhd", probs, v)
            x = x + _linear(ctx.reshape(b, s, nh * hd),
                            lp["attention"]["proj"])
            h = _layer_norm(x, lp["post_attention_layernorm"])
            h = jax.nn.gelu(_linear(h, lp["mlp"]["fc1"]), approximate=True)
            x = x + _linear(h, lp["mlp"]["fc2"])
        x = _layer_norm(x, p["final_layernorm"])
        return x @ p["embedding"]["weight"].T


# -- nemotron_h: Mamba-2, sigmoid-routed experts, grouped attention ---------

_NORM_EPS = 1e-5        # the source's norm_eps and layer_norm_epsilon


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _relu2(x):
    return jnp.maximum(x, 0.0) ** 2


def nemotron_h_mamba(p, u, cfg):
    """The ``M`` mixer on ``u`` ``(b, t, hidden)``: the recurrence one time
    step after another.  (The scan over ``t`` is nested, an outer scan over
    blocks of a checkpointed inner one, so that its gradient fits a chip at
    8k steps; the arithmetic is the plain recurrence's.)"""
    b, t, _ = u.shape
    H, P = cfg.mamba_num_heads, cfg.mamba_head_dim
    G, N, K = cfg.mamba_groups, cfg.mamba_state_size, cfg.mamba_conv_kernel
    d_inner = H * P
    zxbcdt = u @ p["in_proj"]["weight"].T
    z, xbc, dt = jnp.split(zxbcdt, [d_inner, 2 * d_inner + 2 * G * N], -1)
    padded = jnp.pad(xbc, ((0, 0), (K - 1, 0), (0, 0)))
    xbc = sum(padded[:, j:j + t] * p["conv"]["weight"][:, j]
              for j in range(K)) + p["conv"]["bias"]
    xbc = jax.nn.silu(xbc)
    x, B, C = jnp.split(xbc, [d_inner, d_inner + G * N], -1)
    x = x.reshape(b, t, H, P)
    B = jnp.repeat(B.reshape(b, t, G, N), H // G, axis=2)      # per head
    C = jnp.repeat(C.reshape(b, t, G, N), H // G, axis=2)
    dt = jax.nn.softplus(dt + p["dt_bias"])                    # (b, t, H)
    a = -jnp.exp(p["A_log"])

    def step(S, inp):
        x_t, B_t, C_t, dt_t = inp
        S = jnp.exp(dt_t * a)[..., None, None] * S \
            + (dt_t[..., None] * x_t)[..., None] * B_t[:, :, None, :]
        return S, jnp.einsum("bhpn,bhn->bhp", S, C_t)

    blk = max(d for d in range(1, 129) if t % d == 0)
    inner = jax.checkpoint(lambda S, inp: jax.lax.scan(step, S, inp))
    seq = jax.tree_util.tree_map(
        lambda v: v.swapaxes(0, 1).reshape((t // blk, blk) + v.shape[:1]
                                           + v.shape[2:]), (x, B, C, dt))
    _, y = jax.lax.scan(inner, jnp.zeros((b, H, P, N), _f32), seq)
    y = y.reshape((t,) + y.shape[2:]).swapaxes(0, 1)           # (b, t, H, P)
    y = y + p["D"][:, None] * x
    y = y.reshape(b, t, G, -1) * jax.nn.silu(z).reshape(b, t, G, -1)
    y = y * jax.lax.rsqrt(jnp.mean(y * y, -1, keepdims=True) + _NORM_EPS)
    return (y.reshape(b, t, d_inner) * p["norm"]["weight"]) \
        @ p["out_proj"]["weight"].T


def nemotron_h_attention(p, u, cfg):
    """The ``*`` mixer: causal softmax attention, each KV head serving
    ``heads / kv_heads`` query heads, one query head at a time (the
    ``(t, t)`` scores of all 32 at 8k tokens would not fit a chip)."""
    b, t, _ = u.shape
    nh, nkv, hd = cfg.num_attention_heads, cfg.num_kv_heads, cfg.head_dim
    qkv = u @ p["qkv"]["weight"].T
    q, k, v = jnp.split(qkv, [nh * hd, (nh + nkv) * hd], -1)
    q = q.reshape(b, t, nh, hd).transpose(2, 0, 1, 3)
    k = k.reshape(b, t, nkv, hd).transpose(2, 0, 1, 3)
    v = v.reshape(b, t, nkv, hd).transpose(2, 0, 1, 3)
    return _causal_grouped_softmax(q, k, v) @ p["proj"]["weight"].T


def _causal_grouped_softmax(q, k, v):
    """``(b, t, heads * head_dim)`` from heads-major ``q`` ``(heads, b, t,
    head_dim)`` and ``k``, ``v`` ``(kv_heads, b, t, head_dim)``."""
    nh, b, t, hd = q.shape
    nkv = k.shape[0]
    causal = jnp.tril(jnp.ones((t, t), bool))

    @jax.checkpoint
    def head(args):
        q_h, j = args
        scores = jnp.einsum("bqd,bkd->bqk", q_h, k[j // (nh // nkv)]) \
            * hd ** -0.5
        probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), -1)
        return probs @ v[j // (nh // nkv)]

    o = jax.lax.map(head, (q, jnp.arange(nh)))                 # (nh, b, t, hd)
    return o.transpose(1, 2, 0, 3).reshape(b, t, nh * hd)


def nemotron_h_route(p, u, cfg):
    """``(scores + bias, choice, weight)`` of the sigmoid router on ``u``
    ``(tokens, hidden)``."""
    scores = jax.nn.sigmoid(u @ p["router"]["weight"].T)
    biased = scores + p["router"]["bias"]
    _, choice = jax.lax.top_k(biased, cfg.moe_top_k)
    w = jnp.take_along_axis(scores, choice, -1)
    w = cfg.moe_routed_scale * w / (w.sum(-1, keepdims=True) + 1e-20)
    return biased, choice, w


def nemotron_h_experts(p, u, cfg):
    """The ``E`` mixer: the held experts one after another, each over every
    token, weighted where the router chose it; then the shared expert.  What
    the experts held elsewhere would add is left out, as in the program."""
    shape = u.shape
    u = u.reshape(-1, shape[-1])
    _, choice, w = nemotron_h_route(p, u, cfg)
    off, count = cfg.moe_held or (0, cfg.n_experts)
    out = jnp.zeros_like(u)
    for e in range(count):
        gate = jnp.sum(jnp.where(choice == off + e, w, 0.0), -1)   # (tokens,)
        out = out + gate[:, None] * (_relu2(u @ p["w1"][e]) @ p["w2"][e])
    shared = _relu2(u @ p["shared"]["fc1"]["weight"].T) \
        @ p["shared"]["fc2"]["weight"].T
    return (out + shared).reshape(shape)


NEMOTRON_H_MIXERS = {"M": nemotron_h_mamba, "*": nemotron_h_attention,
                     "E": nemotron_h_experts}


def nemotron_h_layer(kind, lp, x, cfg):
    """``x + mixer(RMSNorm(x))`` for one symbol of the pattern."""
    return x + NEMOTRON_H_MIXERS[kind](
        lp["mixer"], _rms_norm(x, lp["norm"]["weight"], _NORM_EPS), cfg)


def nemotron_h_head(params, x, cfg, targets=None):
    """``(logits, mean next-token loss or None)`` from the last layer's
    output: the final RMSNorm, then the untied head without a bias."""
    x = _rms_norm(x, params["final_layernorm"]["weight"], _NORM_EPS)
    logits = x @ params["lm_head"]["weight"].T
    if targets is None:
        return logits, None
    logp = jax.nn.log_softmax(logits, -1)
    return logits, -jnp.mean(
        jnp.take_along_axis(logp, targets[..., None], -1))


def nemotron_h_reference(params, tokens, cfg, targets=None):
    """``(logits (b, t, vocab), loss)`` of the hybrid the ``nemotron_h``
    family describes, for :class:`apex_tpu.models.gpt.GPTModel` under a
    ``layer_pattern`` (float32, ``"highest"`` matmul precision, no kernels).

    Every layer is ``x <- x + mixer(RMSNorm(x))``, eps 1e-5, one
    mixer a layer by ``cfg.layer_pattern``; after the last layer RMSNorm,
    then ``logits = x W_head`` (untied, no bias).

    * ``M``, Mamba-2.  ``d_inner = mamba_num_heads x mamba_head_dim``;
      ``[z | xBC | dt] = u W_in`` (widths ``d_inner | d_inner + 2 G N |
      H``); ``xBC <- silu(causal depthwise conv_k(xBC) + b)``; ``[x | B |
      C] = xBC`` with ``x (t, H, P)``, ``B, C (t, G, N)``, head ``j`` reads
      group ``j // (H/G)``; ``dt <- softplus(dt + dt_bias)``, ``a =
      -exp(A_log)``; per head ``S_t = exp(dt_t a) S_{t-1} + dt_t x_t (x)
      B_t``, ``y_t = S_t C_t + D x_t``; ``y <- RMSNorm_by_group(y *
      silu(z)) * w``; ``out = y W_out``.
    * ``*``, attention.  ``[q | k | v] = u W_qkv`` (``heads``, ``kv_heads``,
      ``kv_heads`` of ``head_dim``), no bias, causal softmax at scale
      ``head_dim^-0.5``, each KV head serves ``heads / kv_heads`` query
      heads, ``out = o W_o``.  No position code (departure: the source's
      ``rope_theta`` is not read; the family's attention layers carry none).
    * ``E``, experts.  ``s = sigmoid(u W_r)``; ``choice = top_k(s + b)``
      with ``b`` the per-expert correction bias; ``w = s[choice]``, ``w <-
      scale w / (sum w + 1e-20)``; ``routed = sum_{e in choice, e held} w_e
      W2_e relu(u W1_e)^2``; ``shared = W2_s relu(u W1_s)^2``; ``out =
      routed + shared``.  Held here: experts ``[offset, offset + count)``
      of ``cfg.moe_held``; no capacity, no dropped token, no auxiliary
      loss (departures: the bias is not updated, no group-limited routing
      since ``n_group = topk_group = 1``).
    """
    p = jax.tree_util.tree_map(lambda a: jnp.asarray(a, _f32), params)
    with jax.default_matmul_precision("highest"):
        x = p["embedding"]["weight"][tokens]
        for kind, lp in zip(cfg.layer_pattern, p["layers"]):
            x = nemotron_h_layer(kind, lp, x, cfg)
        return nemotron_h_head(p, x, cfg, targets)


# -- lfm2: gated short convolutions, gated experts, QK-norm grouped attention --

def _swiglu(u, w1, w2):
    """``W_2 (silu(u W_1) * (u W_3))`` with ``w1`` holding ``[W_1 | W_3]``
    side by side (in, 2 x width) and ``w2`` (width, out)."""
    gate, up = jnp.split(u @ w1, 2, -1)
    return (jax.nn.silu(gate) * up) @ w2


def lfm2_conv(p, u, cfg):
    """The ``C`` mixer on ``u`` ``(b, t, hidden)``: ``[B | C | u] = h W_in``,
    ``z = B * u``, then the convolution one time step after another over a
    window of the last ``K`` values of ``z`` (zeros before the sequence),
    ``y = (C * c) W_out``."""
    K = cfg.short_conv_kernel
    B, C, x = jnp.split(u @ p["in_proj"]["weight"].T, 3, -1)
    z = B * x
    k = p["conv"]["weight"]                                    # (hidden, K)

    def step(window, z_t):
        window = jnp.concatenate([window[:, 1:], z_t[:, None]], 1)
        return window, jnp.einsum("bjc,cj->bc", window, k)

    _, c = jax.lax.scan(step, jnp.zeros((z.shape[0], K, z.shape[2]), _f32),
                        z.swapaxes(0, 1))
    return (C * c.swapaxes(0, 1)) @ p["out_proj"]["weight"].T


def _rope_rotate_half(x, base):
    """Rotary positions over the whole last axis of heads-major ``x``
    ``(heads, b, t, head_dim)``, rotate-half form."""
    t, hd = x.shape[-2:]
    inv = 1.0 / (base ** (jnp.arange(0, hd, 2, dtype=_f32) / hd))
    f = jnp.outer(jnp.arange(t, dtype=_f32), inv)
    f = jnp.concatenate([f, f], -1)                            # (t, hd)
    x1, x2 = jnp.split(x, 2, -1)
    return x * jnp.cos(f) + jnp.concatenate([-x2, x1], -1) * jnp.sin(f)


def lfm2_attention(p, u, cfg):
    """The ``*`` mixer: q, k, v without a bias; RMSNorm over ``head_dim`` of
    every query head (one weight) and every key head (another); rotary over
    the whole head at ``cfg.rope_base``; causal softmax at scale
    ``head_dim^-0.5``, each KV head serving ``heads / kv_heads`` query
    heads; the output projection."""
    b, t, _ = u.shape
    nh, nkv, hd = cfg.num_attention_heads, cfg.num_kv_heads, cfg.head_dim
    qkv = u @ p["qkv"]["weight"].T
    q, k, v = jnp.split(qkv, [nh * hd, (nh + nkv) * hd], -1)
    q = q.reshape(b, t, nh, hd).transpose(2, 0, 1, 3)
    k = k.reshape(b, t, nkv, hd).transpose(2, 0, 1, 3)
    v = v.reshape(b, t, nkv, hd).transpose(2, 0, 1, 3)
    q = _rope_rotate_half(
        _rms_norm(q, p["q_norm"]["weight"], _NORM_EPS), cfg.rope_base)
    k = _rope_rotate_half(
        _rms_norm(k, p["k_norm"]["weight"], _NORM_EPS), cfg.rope_base)
    return _causal_grouped_softmax(q, k, v) @ p["proj"]["weight"].T


def lfm2_dense(p, u, cfg):
    """The ``D`` mixer: the gated FFN at the dense width."""
    return _swiglu(u, p["fc1"]["weight"].T, p["fc2"]["weight"].T)


def lfm2_route(p, u, cfg):
    """``(scores + bias, choice, weight)`` of the router on ``u`` ``(tokens,
    hidden)``: sigmoid scores, the top ``k`` of score + bias, the chosen
    scores over their sum + 1e-6 (the source's), times the scaling factor."""
    scores = jax.nn.sigmoid(u @ p["router"]["weight"].T)
    biased = scores + p["router"]["bias"]
    _, choice = jax.lax.top_k(biased, cfg.moe_top_k)
    w = jnp.take_along_axis(scores, choice, -1)
    w = cfg.moe_routed_scale * w / (w.sum(-1, keepdims=True) + 1e-6)
    return biased, choice, w


def lfm2_experts(p, u, cfg):
    """The ``E`` mixer: the held experts one after another, each the gated
    FFN at the expert width over every token, weighted where the router
    chose it.  No shared expert.  What the experts held elsewhere would add
    is left out, as in the program."""
    shape = u.shape
    u = u.reshape(-1, shape[-1])
    _, choice, w = lfm2_route(p, u, cfg)
    off, count = cfg.moe_held or (0, cfg.n_experts)
    out = jnp.zeros_like(u)
    for e in range(count):
        gate = jnp.sum(jnp.where(choice == off + e, w, 0.0), -1)   # (tokens,)
        out = out + gate[:, None] * _swiglu(u, p["w1"][e], p["w2"][e])
    return out.reshape(shape)


LFM2_MIXERS = {"C": lfm2_conv, "*": lfm2_attention, "D": lfm2_dense,
               "E": lfm2_experts}


def lfm2_layer(kind, lp, x, cfg):
    """``x + mixer(RMSNorm(x))`` for one symbol of the pattern: a published
    layer is two of them, its operator and then its feed-forward."""
    return x + LFM2_MIXERS[kind](
        lp["mixer"], _rms_norm(x, lp["norm"]["weight"], _NORM_EPS), cfg)


def lfm2_head(params, x, cfg, targets=None):
    """``(logits, mean next-token loss or None)`` from the last layer's
    output: the final RMSNorm (``embedding_norm``), then the head tied to
    the embedding."""
    x = _rms_norm(x, params["final_layernorm"]["weight"], _NORM_EPS)
    logits = x @ params["embedding"]["weight"].T
    if targets is None:
        return logits, None
    logp = jax.nn.log_softmax(logits, -1)
    return logits, -jnp.mean(
        jnp.take_along_axis(logp, targets[..., None], -1))


def lfm2_reference(params, tokens, cfg, targets=None):
    """``(logits (b, t, vocab), loss)`` of the model the ``lfm2_moe`` family
    describes, for :class:`apex_tpu.models.gpt.GPTModel` under a
    ``layer_pattern`` of ``C``, ``*``, ``D`` and ``E`` (float32,
    ``"highest"`` matmul precision, no kernels, no remat).

    A published layer ``i`` is ``x <- x + op_i(RMSNorm(x))`` then ``x <- x +
    ffn_i(RMSNorm(x))``, eps 1e-5, no bias anywhere: two symbols of the
    pattern.  After the last layer RMSNorm, then ``logits = x E^T`` with
    ``E`` the embedding (tied).

    * ``C``, gated short convolution.  ``[B | C | u] = h W_in``; ``z = B *
      u``; ``c_t = sum_{j<K} k[:, j] z_{t-(K-1)+j}`` per channel, zeros
      before the sequence; ``out = (C * c) W_out``.  No activation.
    * ``*``, attention.  ``[q | k | v] = h W_qkv`` (``heads``, ``kv_heads``,
      ``kv_heads`` of ``head_dim``); RMSNorm over ``head_dim`` of each q and
      each k head; rotary over the whole head, rotate-half, base
      ``cfg.rope_base``; causal softmax at scale ``head_dim^-0.5``, each KV
      head serving ``heads / kv_heads`` query heads; ``out = o W_o``.
    * ``D``, dense FFN.  ``W_2 (silu(h W_1) * (h W_3))``.
    * ``E``, experts.  ``s = sigmoid(h W_r)``; ``choice = top_k(s + b)``;
      ``w = scale s[choice] / (sum s[choice] + 1e-6)``; ``out = sum_{e in
      choice, e held} w_e FFN_e(h)``, each expert the dense FFN's form at
      the expert width; no shared expert, no capacity, no dropped token, no
      auxiliary loss (departures: the bias ``b`` is a buffer at zero that no
      balancer moves; the program renormalises over ``sum + 1e-20``).

    The program keeps ``[W_1 | W_3]`` as one matrix (``fc1``, an expert's
    ``w1``): the same numbers side by side.
    """
    p = jax.tree_util.tree_map(lambda a: jnp.asarray(a, _f32), params)
    with jax.default_matmul_precision("highest"):
        x = p["embedding"]["weight"][tokens]
        for kind, lp in zip(cfg.layer_pattern, p["layers"]):
            x = lfm2_layer(kind, lp, x, cfg)
        return lfm2_head(p, x, cfg, targets)


# -- glm_dsa: latent attention under a shared sparse-attention indexer, a ------
# -- shared expert and sigmoid top-k gated experts, as one chip's share --------
# (the program's copy of ``benchmarks/configs/glm-5.2.reference.py``: written
# from the layers' equations, blocked so that 16 384 positions fit beside the
# engine; ``tests/test_glm_dsa.py`` holds the two to each other)

_GLM_NORM_EPS = 1e-5        # the source's rms_norm_eps
_GLM_INDEX_NORM_EPS = 1e-6  # the indexer's LayerNorm
_GLM_HEAD_BLOCK = 16
_GLM_ROW_BLOCK = 1024
_GLM_SELECT_ROWS = 256
_GLM_FFN_BLOCK = 4096


def _glm_w(p):
    """A linear layer's ``(out, in)`` weight in float32."""
    return p["weight"].astype(_f32)


def _glm_rms_norm(x, p):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True)
                             + _GLM_NORM_EPS) * p["weight"].astype(_f32)


def _glm_block(n, cap):
    """The largest divisor of ``n`` that is at most ``cap``."""
    return max(d for d in range(1, min(n, cap) + 1) if n % d == 0)


def _glm_rope_pairs(x, base):
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


def glm_dsa_selection(p, u, c_q, cfg):
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
    q = (c_q @ _glm_w(p["index_q"]).T).reshape(b, t, ih, d).transpose(0, 2, 1, 3)
    q = jnp.concatenate([_glm_rope_pairs(q[..., :rope], cfg.rope_base),
                         q[..., rope:]], -1)                # (b, ih, t, d)
    k = u @ _glm_w(p["index_k"]).T
    k = k - jnp.mean(k, -1, keepdims=True)
    k = k * jax.lax.rsqrt(jnp.mean(k * k, -1, keepdims=True)
                          + _GLM_INDEX_NORM_EPS)
    k = k * p["index_k_norm"]["weight"].astype(_f32) \
        + p["index_k_norm"]["bias"].astype(_f32)
    k = jnp.concatenate([_glm_rope_pairs(k[..., :rope], cfg.rope_base),
                         k[..., rope:]], -1)                # (b, t, d)
    w = (u @ _glm_w(p["index_w"]).T) / ih ** 0.5 / d ** 0.5     # (b, t, ih)
    rb = _glm_block(t, _GLM_SELECT_ROWS)
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


def glm_dsa_attention(p, u, cfg, chosen):
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
    c_q = _glm_rms_norm(u @ _glm_w(p["q_a"]).T, p["q_norm"])
    if "index_q" in p:
        chosen = glm_dsa_selection(p, u, c_q, cfg)
    kv = u @ _glm_w(p["kv_a"]).T
    c = _glm_rms_norm(kv[..., :r], p["kv_norm"])
    k_rope = _glm_rope_pairs(kv[..., r:], cfg.rope_base)                # (b, t, rope)
    hb, rb = _glm_block(h, _GLM_HEAD_BLOCK), _glm_block(t, _GLM_ROW_BLOCK)
    w_kvb = p["kv_b"]["weight"]
    blocks = (p["q_b"]["weight"].reshape(h // hb, hb, nope + rope, -1),
              w_kvb[:h * nope].reshape(h // hb, hb, nope, r),
              w_kvb[h * nope:].reshape(h // hb, hb, vd, r))
    scale = (nope + rope) ** -0.5

    def heads(ws):
        wq, wk, wv = (w.astype(_f32) for w in ws)
        q = jnp.einsum("btq,hdq->bhtd", c_q, wq)
        q_nope, q_rope = q[..., :nope], _glm_rope_pairs(q[..., nope:], cfg.rope_base)
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
    return o @ _glm_w(p["proj"]).T, chosen


def glm_dsa_dense(p, u, cfg):
    """The ``D`` mixer (and the shared expert): the gated FFN, ``fc1``'s
    rows ``[gate | up]``, a block of hidden columns at a time."""
    w = p["fc2"]["weight"].shape[1]
    fb = _glm_block(w, _GLM_FFN_BLOCK)
    out = jnp.zeros_like(u)
    for c0 in range(0, w, fb):
        w1 = jnp.concatenate([p["fc1"]["weight"][c0:c0 + fb],
                              p["fc1"]["weight"][w + c0:w + c0 + fb]])
        out = out + _swiglu(u, w1.astype(_f32).T,
                            p["fc2"]["weight"][:, c0:c0 + fb].astype(_f32).T)
    return out


def glm_dsa_route(p, u, cfg):
    """``(ranked, choice, weight)`` of the router on ``u`` ``(tokens,
    hidden)``: ``s = sigmoid(u W_r)`` over all experts, the ``k`` largest of
    ``s + b`` (``noaux_tc``; one group, none excluded), the chosen ``s`` over
    their sum plus 1e-20 (``norm_topk_prob``), times the scaling factor."""
    scores = jax.nn.sigmoid(u @ _glm_w(p["router"]).T)
    ranked = scores + p["router"]["bias"].astype(_f32)
    _, choice = jax.lax.top_k(ranked, cfg.moe_top_k)
    w = jnp.take_along_axis(scores, choice, -1)
    w = cfg.moe_routed_scale * w / (w.sum(-1, keepdims=True) + 1e-20)
    return ranked, choice, w


def glm_dsa_experts(p, u, cfg):
    """The ``E`` mixer: ``(out, ranked scores)``.  The held experts one
    after another, each the gated FFN at the expert width over every token,
    weighted where the router chose it, plus the shared expert once.  What
    the experts held elsewhere would add is left out, as in the program."""
    shape = u.shape
    u = u.reshape(-1, shape[-1])
    ranked, choice, w = glm_dsa_route(p, u, cfg)
    off, count = cfg.moe_held or (0, cfg.n_experts)

    def one(out, args):
        e, w1, w2 = args
        gate = jnp.sum(jnp.where(choice == off + e, w, 0.0), -1)  # (tokens,)
        return out + gate[:, None] * _swiglu(u, w1.astype(_f32),
                                             w2.astype(_f32)), None

    out, _ = jax.lax.scan(one, glm_dsa_dense(p["shared"], u, cfg),
                          (jnp.arange(count), p["w1"], p["w2"]))
    return out.reshape(shape), ranked.reshape(*shape[:-1], -1)


def glm_dsa_forward(params, tokens, cfg):
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
            u = _glm_rms_norm(x, lp["norm"])
            if kind == "*":
                y, chosen = glm_dsa_attention(lp["mixer"], u, cfg, chosen)
            elif kind == "D":
                y = glm_dsa_dense(lp["mixer"], u, cfg)
            else:
                y, s = glm_dsa_experts(lp["mixer"], u, cfg)
                ranked.append(s)
            x = x + y
        x = _glm_rms_norm(x, params["final_layernorm"])
        return x @ _glm_w(params["lm_head"]).T, ranked


def glm_dsa_reference(params, tokens, cfg):
    """``(b, t, vocab)`` float32 logits of ``tokens`` ``(b, t)``, every
    position attending to its selection of the whole prefix, from the
    engine's parameters as they are (bf16; upcast where used)."""
    return glm_dsa_forward(params, tokens, cfg)[0]


def glm_dsa_tie_gaps(ranked, cfg):
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


def glm_dsa_near_ties(params, tokens, cfg, margin):
    """``(b, t)``: the positions at which, in any expert layer of this
    float32 forward, the last chosen and the first unchosen score (biased,
    as the router ranks them) lie within ``margin`` and an expert held here
    is among the two."""
    return glm_dsa_tie_gaps(glm_dsa_forward(params, tokens, cfg)[1], cfg) < margin


# -- command-a-plus-05-2026 (Cohere2 MoE) ------------------------------------
#
# Every layer is the parallel block, ``x + A(h) + (R + Sh) / 2`` with ``h`` a
# weight-only LayerNorm of ``x``: grouped attention (each query head against
# the KV head of its group), window layers with rotary positions on adjacent
# pairs and a ``sliding_window`` mask, full layers with no position code; a
# sigmoid top-k router over float32 scores, no correction bias, the chosen
# scores over their sum; the routed sum over the held experts and the shared
# experts side by side, averaged; a tied head.  The benchmark's copy is
# ``benchmarks/configs/command-a-plus-05-2026.reference.py``; it computes in
# blocks and upcasts each matrix where it is used, so that it runs beside a
# chip's share of bf16 weights.

_CA_NORM_EPS = 1e-5        # the source's layer_norm_eps
_CA_ROW_BLOCK = 512
_CA_FFN_BLOCK = 4096


def _ca_w(p):
    """A linear layer's ``(out, in)`` weight in float32."""
    return p["weight"].astype(_f32)


def _ca_layer_norm(x, p):
    m = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - m), -1, keepdims=True)
    return (x - m) * jax.lax.rsqrt(var + _CA_NORM_EPS) \
        * p["weight"].astype(_f32)


def _ca_block(n, cap):
    """The largest divisor of ``n`` that is at most ``cap``."""
    return max(d for d in range(1, min(n, cap) + 1) if n % d == 0)


def _ca_rope(x, base):
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


def command_a_attention(p, u, cfg, window):
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
    rb = _ca_block(t, _CA_ROW_BLOCK)

    def group(ws):
        wq, wk, wv = (x.astype(_f32) for x in ws)
        q = (u @ wq.T).reshape(b, t, g, d).transpose(0, 2, 1, 3)
        k, v = u @ wk.T, u @ wv.T                               # (b, t, d)
        if window is not None:
            q, k = _ca_rope(q, cfg.rope_base), _ca_rope(k, cfg.rope_base)
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
    return o @ _ca_w(p["proj"]).T


def _ca_swiglu(u, w1, w2):
    """``(silu(u W_g) * (u W_u)) W_d`` with ``w1`` holding ``[W_g | W_u]``
    side by side (in, 2 x width) and ``w2`` (width, out)."""
    gate, up = jnp.split(u @ w1, 2, -1)
    return (jax.nn.silu(gate) * up) @ w2


def command_a_shared(p, u):
    """The shared experts side by side, one gated FFN of their summed width
    (``fc1``'s rows ``[gate | up]``), a block of hidden columns at a time."""
    w = p["fc2"]["weight"].shape[1]
    fb = _ca_block(w, _CA_FFN_BLOCK)
    out = jnp.zeros_like(u)
    for c0 in range(0, w, fb):
        w1 = jnp.concatenate([p["fc1"]["weight"][c0:c0 + fb],
                              p["fc1"]["weight"][w + c0:w + c0 + fb]])
        out = out + _ca_swiglu(u, w1.astype(_f32).T,
                            p["fc2"]["weight"][:, c0:c0 + fb].astype(_f32).T)
    return out


def command_a_route(p, u, cfg):
    """``(scores, choice, weight)`` of the router on ``u`` ``(tokens,
    hidden)``: ``s = sigmoid(u W_r)`` over all experts, the ``k`` largest
    (no correction bias), the chosen ``s`` over their sum
    (``norm_topk_prob``)."""
    scores = jax.nn.sigmoid(u @ _ca_w(p["router"]).T)
    _, choice = jax.lax.top_k(scores, cfg.moe_top_k)
    w = jnp.take_along_axis(scores, choice, -1)
    return scores, choice, w / w.sum(-1, keepdims=True)


def command_a_experts(p, u, cfg):
    """``((R + Sh) / 2, scores)``: the held experts one after another, each
    the gated FFN at the expert width over every token, weighted where the
    router chose it, and the shared experts once.  What the experts held
    elsewhere would add to ``R`` is left out, as in the program."""
    shape = u.shape
    u = u.reshape(-1, shape[-1])
    scores, choice, w = command_a_route(p, u, cfg)
    off, count = cfg.moe_held or (0, cfg.n_experts)

    def one(out, args):
        e, w1, w2 = args
        gate = jnp.sum(jnp.where(choice == off + e, w, 0.0), -1)  # (tokens,)
        return out + gate[:, None] * _ca_swiglu(u, w1.astype(_f32),
                                             w2.astype(_f32)), None

    routed, _ = jax.lax.scan(one, jnp.zeros_like(u),
                             (jnp.arange(count), p["w1"], p["w2"]))
    out = (routed + command_a_shared(p["shared"], u)) / 2
    return out.reshape(shape), scores.reshape(*shape[:-1], -1)


def command_a_forward(params, tokens, cfg):
    """``(logits (b, t, vocab), [router scores (b, t, experts) a layer])``.
    Every layer is the parallel block, ``x <- x + A(h) + MoE(h)`` with ``h =
    LN(x)``; its entry of ``attention_types`` says window or full.  After
    the last, LN and the tied head."""
    ranked = []
    with jax.default_matmul_precision("highest"):
        x = params["embedding"]["weight"][tokens].astype(_f32)
        for kind, lp in zip(cfg.attention_types, params["layers"]):
            u = _ca_layer_norm(x, lp["norm"])
            window = cfg.sliding_window if kind == "sliding" else None
            y, s = command_a_experts(lp["mlp"], u, cfg)
            x = x + command_a_attention(lp["attention"], u, cfg, window) + y
            ranked.append(s)
        x = _ca_layer_norm(x, params["final_layernorm"])
        return x @ _ca_w(params["embedding"]).T, ranked


def command_a_reference(params, tokens, cfg):
    """``(b, t, vocab)`` float32 logits of ``tokens`` ``(b, t)`` from the
    engine's parameters as they are (bf16; upcast where used)."""
    return command_a_forward(params, tokens, cfg)[0]


def command_a_tie_gaps(ranked, cfg):
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


def command_a_near_ties(params, tokens, cfg, margin):
    """``(b, t)``: the positions at which, in any layer of this float32
    forward, the last chosen and the first unchosen router score lie within
    ``margin`` and an expert held here is among the two."""
    return command_a_tie_gaps(command_a_forward(params, tokens, cfg)[1],
                              cfg) < margin


# -- smallthinker: window and full layers 3:1 over grouped heads, the router --
# -- on a layer's input before attention, softmax top-k ReGLU experts ----------
# (the program's copy of ``benchmarks/configs/smallthinker-21ba3b-instruct.
# reference.py``; blocked so that one chip's share at 16 384 positions and
# its gradients fit beside the trained program: attention ``_ST_ROW_BLOCK``
# query rows at a time over every key, the experts and the head
# ``_ST_TOKEN_BLOCK`` tokens at a time, each block recomputed in the
# backward; blocking changes no number)

_ST_NORM_EPS = 1e-6        # the source's rms_norm_eps
_ST_ROW_BLOCK = 256
_ST_TOKEN_BLOCK = 2048


def _st_rms_norm(x, p):
    return _rms_norm(x, p["weight"].astype(_f32), _ST_NORM_EPS)


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
