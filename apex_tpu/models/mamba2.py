"""Mamba-2 mixer (state-space duality, Dao & Gu 2024) for the layer slot of
:class:`apex_tpu.models.gpt.ParallelTransformerLayer` — the ``M`` of a
``GPTConfig.layer_pattern``.

Per head ``j`` (``H`` heads of ``P`` channels, ``G`` groups of ``N`` state
columns, head ``j`` reads group ``j // (H/G)``)::

    S_t = exp(dt_t a) S_{t-1} + dt_t x_t (x) B_t        y_t = S_t C_t + D x_t

evaluated in chunks of ``chunk_size``: inside a chunk the recurrence is a
masked ``(L, L)`` matrix product (``C_t . B_s`` times the decay from ``s`` to
``t``), and across chunks a scan over one ``(P, N)`` state per head.  Plain
``jax.numpy``/``lax``: the backward pass is autodiff's.  The decays (the
cumulative sums of ``dt a`` and their exponentials) and the states are
float32 whatever the activations are; the matrix products take their
operands at the activation dtype and accumulate in float32.

Training only: a served request would need this layer's conv window and
state carried beside the KV cache, which no cache here holds yet
(``GPTModel`` refuses the serving entry points for such a pattern).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

_f32 = jnp.float32
_INIT_STD = 0.02
_NORM_EPS = 1e-5
# dt at initialisation: log-uniform between the first two, floored at the
# third (the Mamba-2 reference's time_step_min, _max and _floor)
_DT_LIMITS = (1e-3, 1e-1, 1e-4)


def _normal(key, shape, std, dtype):
    return (std * jax.random.normal(key, shape, _f32)).astype(dtype)


def causal_depthwise_conv(x, weight, bias=None):
    """Causal depthwise conv over ``x`` ``(b, t, c)`` with ``weight``
    ``(c, k)``: tap ``j`` multiplies the input ``k - 1 - j`` steps back,
    zeros before the sequence.  Shared with the gated short convolution
    (:mod:`apex_tpu.models.short_conv`), which has 3 taps and no bias."""
    k = weight.shape[1]
    t = x.shape[1]
    padded = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    w = weight.astype(x.dtype)
    y = sum(padded[:, j:j + t] * w[:, j] for j in range(k))
    return y if bias is None else y + bias.astype(x.dtype)


class Mamba2Mixer:
    """``params = m.init_params(key)``; ``y = m(params, x)`` with ``x``
    ``(batch, seq, hidden)`` (any ``seq``: the tail chunk is padded with
    steps that neither decay nor write the state)."""

    def __init__(self, cfg):
        self.cfg = cfg
        self.heads = cfg.mamba_num_heads
        self.head_dim = cfg.mamba_head_dim
        self.groups = cfg.mamba_groups
        self.state = cfg.mamba_state_size
        self.d_inner = self.heads * self.head_dim
        self.conv_dim = self.d_inner + 2 * self.groups * self.state

    def init_params(self, key):
        cfg = self.cfg
        k_in, k_conv, k_dt, k_a, k_out = jax.random.split(key, 5)
        d_proj = self.d_inner + self.conv_dim + self.heads
        # dt log-uniform on [time_step_min, time_step_max], floored; the
        # bias is its inverse softplus (the Mamba-2 reference initialiser)
        lo, hi, floor = _DT_LIMITS
        dt = jnp.exp(jax.random.uniform(k_dt, (self.heads,), _f32)
                     * (math.log(hi) - math.log(lo)) + math.log(lo))
        dt = jnp.maximum(dt, floor)
        bound = cfg.mamba_conv_kernel ** -0.5       # torch's Conv1d default
        return {
            "in_proj": {"weight": _normal(
                k_in, (d_proj, cfg.hidden_size), _INIT_STD,
                cfg.param_dtype)},
            "conv": {"weight": jax.random.uniform(
                         k_conv, (self.conv_dim, cfg.mamba_conv_kernel),
                         cfg.param_dtype, -bound, bound),
                     "bias": jnp.zeros((self.conv_dim,), cfg.param_dtype)},
            "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
            "A_log": jnp.log(jax.random.uniform(
                k_a, (self.heads,), _f32, 1.0, 16.0)),
            "D": jnp.ones((self.heads,), _f32),
            "norm": {"weight": jnp.ones((self.d_inner,), _f32)},
            "out_proj": {"weight": _normal(
                k_out, (cfg.hidden_size, self.d_inner),
                _INIT_STD / math.sqrt(cfg.num_layers), cfg.param_dtype)},
        }

    def _scan(self, x, dt, a, B, C):
        """The chunked recurrence.  ``x`` ``(b, t, G, K, P)`` (``K`` heads a
        group), ``dt`` ``(b, t, G, K)`` float32, ``a`` ``(G, K)`` float32,
        ``B``/``C`` ``(b, t, G, N)``; returns ``y`` like ``x`` in float32."""
        b, t, g, k, p = x.shape
        L = self.cfg.mamba_chunk_size
        pad = -t % L
        if pad:
            # dt = 0: the step keeps the state as it is and adds nothing
            widen = lambda z: jnp.pad(                       # noqa: E731
                z, ((0, 0), (0, pad)) + ((0, 0),) * (z.ndim - 2))
            x, dt, B, C = widen(x), widen(dt), widen(B), widen(C)
        nc = (t + pad) // L
        chunks = lambda z: z.reshape((b, nc, L) + z.shape[2:])  # noqa: E731
        x, dt, B, C = chunks(x), chunks(dt), chunks(B), chunks(C)
        cdt = x.dtype

        cs = jnp.cumsum(dt * a, axis=2)                 # (b, c, L, G, K) <= 0
        xdt = x.astype(_f32) * dt[..., None]

        # within a chunk: y_l += sum_{s<=l} (C_l . B_s) exp(cs_l - cs_s) xdt_s
        cb = jnp.einsum("bclgn,bcsgn->bcgls", C, B,
                        preferred_element_type=_f32)
        diff = cs.transpose(0, 1, 3, 4, 2)              # (b, c, G, K, L)
        diff = diff[..., :, None] - diff[..., None, :]  # (.., l, s)
        causal = jnp.tril(jnp.ones((L, L), bool))
        decay = jnp.exp(jnp.where(causal, diff, -jnp.inf))
        m = (cb[:, :, :, None] * decay).astype(cdt)     # (b, c, G, K, l, s)
        y = jnp.einsum("bcgkls,bcsgkp->bclgkp", m, xdt.astype(cdt),
                       preferred_element_type=_f32)

        # what each chunk adds to the state by its end, and the state each
        # chunk starts from (float32 throughout)
        to_end = jnp.exp(cs[:, :, -1:] - cs)            # (b, c, L, G, K)
        local = jnp.einsum(
            "bcsgkp,bcsgn->bcgkpn",
            (xdt * to_end[..., None]).astype(cdt), B,
            preferred_element_type=_f32)
        chunk_decay = jnp.exp(cs[:, :, -1])             # (b, c, G, K)

        def step(s, inp):
            dec, loc = inp
            return dec[..., None, None] * s + loc, s

        _, entering = jax.lax.scan(
            step, jnp.zeros((b, g, k, p, self.state), _f32),
            (chunk_decay.swapaxes(0, 1), local.swapaxes(0, 1)))
        entering = entering.swapaxes(0, 1)              # (b, c, G, K, P, N)
        y = y + jnp.einsum("bclgn,bcgkpn->bclgkp", C, entering.astype(cdt),
                           preferred_element_type=_f32) \
            * jnp.exp(cs)[..., None]
        return y.reshape(b, nc * L, g, k, p)[:, :t]

    def __call__(self, params, x):
        cfg = self.cfg
        b, t, _ = x.shape
        g, n = self.groups, self.state
        k = self.heads // g
        with jax.named_scope("mamba.in_proj"):
            zxbcdt = x @ params["in_proj"]["weight"].astype(x.dtype).T
            z, xbc, dt = jnp.split(
                zxbcdt, [self.d_inner, self.d_inner + self.conv_dim], axis=-1)
        with jax.named_scope("mamba.conv"):
            xbc = jax.nn.silu(causal_depthwise_conv(
                xbc, params["conv"]["weight"], params["conv"]["bias"]))
            xs, B, C = jnp.split(xbc, [self.d_inner, self.d_inner + g * n],
                                 axis=-1)
        with jax.named_scope("mamba.scan"):
            dt = jax.nn.softplus(dt.astype(_f32)
                                 + params["dt_bias"].astype(_f32))
            a = -jnp.exp(params["A_log"].astype(_f32))
            xs = xs.reshape(b, t, g, k, self.head_dim)
            y = self._scan(xs, dt.reshape(b, t, g, k), a.reshape(g, k),
                           B.reshape(b, t, g, n), C.reshape(b, t, g, n))
            y = y + params["D"].astype(_f32).reshape(g, k, 1) \
                * xs.astype(_f32)
            # gated RMSNorm over each group's channels
            y = y.reshape(b, t, g, -1) \
                * jax.nn.silu(z.astype(_f32)).reshape(b, t, g, -1)
            y = y * jax.lax.rsqrt(
                jnp.mean(y * y, axis=-1, keepdims=True) + _NORM_EPS)
            y = (y.reshape(b, t, self.d_inner)
                 * params["norm"]["weight"]).astype(x.dtype)
        with jax.named_scope("mamba.out_proj"):
            return y @ params["out_proj"]["weight"].astype(x.dtype).T
