"""The plain reference of gpt2-medium: the forward pass in straightforward
float32 ``jax.numpy`` (no kernels, no cache, no batching) that the served
logits are held against.  The benchmark's own copy of
``apex_tpu/models/reference.py``, so that no later change to the program
moves the yardstick.

On a TPU a float32 matmul runs at reduced precision unless
``jax.default_matmul_precision("highest")`` is set; the function sets it.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

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
