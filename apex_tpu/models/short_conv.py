"""Gated short convolution (the operator of the ``lfm2`` family) for the
layer slot of :class:`apex_tpu.models.gpt.ParallelTransformerLayer` — the
``C`` of a ``GPTConfig.layer_pattern``::

    [B | C | u] = h W_in        z = B * u
    c_t = sum_j k[:, j] z_{t - (K-1) + j}      (depthwise, causal, K taps)
    y = (C * c) W_out

No bias, no activation.  The depthwise convolution is the one the Mamba-2
mixer runs (:func:`apex_tpu.models.mamba2.causal_depthwise_conv`), here at
``K = 3`` over ``hidden`` channels.  The two products and the convolution are
float32 inside one fusion whatever the activations are; the projections take
their operands at the activation dtype.

Training only: a served request would carry the last ``K - 1`` values of
``z`` beside the KV cache, the smallest fixed-size per-request state a cache
could hold, and no cache here holds any yet (``GPTModel`` refuses the
serving entry points for a pattern).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from apex_tpu.models.mamba2 import _INIT_STD, _normal, causal_depthwise_conv

_f32 = jnp.float32


class GatedShortConv:
    """``params = m.init_params(key)``; ``y = m(params, x)`` with ``x``
    ``(batch, seq, hidden)``, any ``seq``."""

    def __init__(self, cfg):
        self.cfg = cfg

    def init_params(self, key):
        cfg = self.cfg
        h, k = cfg.hidden_size, cfg.short_conv_kernel
        k_in, k_conv, k_out = jax.random.split(key, 3)
        bound = k ** -0.5                           # torch's Conv1d default
        return {
            "in_proj": {"weight": _normal(
                k_in, (3 * h, h), _INIT_STD, cfg.param_dtype)},
            "conv": {"weight": jax.random.uniform(
                k_conv, (h, k), cfg.param_dtype, -bound, bound)},
            "out_proj": {"weight": _normal(
                k_out, (h, h), _INIT_STD / math.sqrt(cfg.num_layers),
                cfg.param_dtype)},
        }

    def __call__(self, params, x):
        with jax.named_scope("conv.in_proj"):
            bcu = x @ params["in_proj"]["weight"].astype(x.dtype).T
            B, C, u = jnp.split(bcu.astype(_f32), 3, axis=-1)
        with jax.named_scope("conv.gate"):
            c = causal_depthwise_conv(B * u, params["conv"]["weight"])
            y = (C * c).astype(x.dtype)
        with jax.named_scope("conv.out_proj"):
            return y @ params["out_proj"]["weight"].astype(x.dtype).T
