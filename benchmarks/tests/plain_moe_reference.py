"""The plain reference of a small expert model (``GPTConfig(n_experts=8,
moe_top_k=2)``: pre-LayerNorm blocks, learned positions, a softmax router
whose two best experts are renormalised, ReLU experts without biases), and
the worked example of a serving configuration's reference module
(``benchmarks/README.md``): float32 ``jax.numpy``, nothing of the program.
``test_serving_check.py`` serves the model in bf16 and holds it to this.
"""

import jax
import jax.numpy as jnp

_f32 = jnp.float32


def _layer_norm(x, p, eps=1e-5):
    m = x.mean(-1, keepdims=True)
    v = ((x - m) ** 2).mean(-1, keepdims=True)
    return (x - m) / jnp.sqrt(v + eps) * p["weight"] + p["bias"]


def _linear(x, p):
    return x @ p["weight"].T + p["bias"]


def _forward(params, tokens, cfg):
    """``(logits, scores)``: ``(b, s, vocab)`` and per layer the router's
    ``(b, s, experts)`` softmax scores."""
    p = jax.tree_util.tree_map(lambda a: jnp.asarray(a, _f32), params)
    b, s = tokens.shape
    nh, hd, k = cfg.num_attention_heads, cfg.head_dim, cfg.moe_top_k
    scores = []
    with jax.default_matmul_precision("highest"):
        x = p["embedding"]["weight"][tokens] + p["position_embedding"][:s]
        causal = jnp.tril(jnp.ones((s, s), bool))
        for lp in p["layers"]:
            h = _layer_norm(x, lp["input_layernorm"])
            qkv = _linear(h, lp["attention"]["qkv"])
            q, kk, v = jnp.split(qkv.reshape(b, s, nh, 3 * hd), 3, axis=-1)
            att = jnp.einsum("bqhd,bkhd->bhqk", q, kk) / jnp.sqrt(
                jnp.asarray(hd, _f32))
            att = jax.nn.softmax(jnp.where(causal, att, -jnp.inf), axis=-1)
            ctx = jnp.einsum("bhqk,bkhd->bqhd", att, v)
            x = x + _linear(ctx.reshape(b, s, nh * hd),
                            lp["attention"]["proj"])
            h = _layer_norm(x, lp["post_attention_layernorm"])
            score = jax.nn.softmax(h @ lp["mlp"]["gate"], axis=-1)
            scores.append(score)
            kth = jnp.sort(score, axis=-1)[..., -k, None]
            w = jnp.where(score >= kth, score, 0.0)
            w = w / w.sum(-1, keepdims=True)
            every = jnp.einsum(
                "bsef,efh->bseh",
                jax.nn.relu(jnp.einsum("bsh,ehf->bsef", h, lp["mlp"]["w1"])),
                lp["mlp"]["w2"])
            x = x + jnp.einsum("bse,bseh->bsh", w, every)
        x = _layer_norm(x, p["final_layernorm"])
        return x @ p["embedding"]["weight"].T, scores


def gpt_reference_logits(params, tokens, cfg):
    return _forward(params, tokens, cfg)[0]


def near_ties(params, tokens, cfg, margin):
    """``(b, s)``: the positions at which, in any expert layer, the last
    chosen and the first unchosen score lie within ``margin``.  Every
    expert is held here, so a held one is always among the two."""
    k = cfg.moe_top_k
    ranked = [jnp.sort(score, axis=-1)
              for score in _forward(params, tokens, cfg)[1]]
    return jnp.any(jnp.stack(
        [r[..., -k] - r[..., -k - 1] < margin for r in ranked]), axis=0)
