"""Fused LayerNorm / RMSNorm kernels — TPU rebuild of
``csrc/layer_norm_cuda.cpp`` + ``csrc/layer_norm_cuda_kernel.cu``.

Design: rows are normalized over the last (hidden) axis.  The forward Pallas
kernel computes per-row mean/rstd with the E[x²]−E[x]² form in f32 (zero
padding of the hidden axis then needs no correction) and saves ``rstd`` (and
``mean`` for LN) for the backward.  The backward kernel produces ``dx`` plus
*per-block* partial ``dgamma``/``dbeta`` sums; the wrapper reduces partials
across blocks — the same two-stage reduction the CUDA kernel does across
thread blocks.

``memory_efficient=True`` (apex flag): the forward saves the *output* ``y``
instead of the input, and the backward reconstructs the normalized value as
``(y - beta) / gamma`` (RMS: ``y / gamma``), halving residual memory.  Like
apex, this requires gamma to be nonzero everywhere.

Inputs of any shape are flattened to ``(rows, hidden)``; hidden is padded to
a lane multiple and rows to a block multiple with zeros (sliced away after).
Off-TPU the same math runs as plain jnp.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from apex_tpu.multi_tensor_apply.bucketing import LANE, _round_up
from apex_tpu.utils.collectives import sds_like, vary_like
from apex_tpu.utils.platform import interpret_mode, use_pallas

_f32 = jnp.float32
# Per-operand block budget.  The bwd kernel materializes ~10 f32
# block-sized temporaries on Mosaic's scoped-vmem stack (16 MB limit), so
# the per-operand budget must stay well under limit/10 — 4 MB blocks OOM
# the scoped stack at hidden=1024 on v5e.
_VMEM_BUDGET = 1024 * 1024  # bytes per operand block


def _pick_block_rows(hidden_p: int) -> int:
    rows = _VMEM_BUDGET // (hidden_p * 4)
    return int(max(8, min(512, _round_up(rows, 8) - 8 if rows % 8 else rows)))


# ---------------------------------------------------------------------------
# shared math (single source of truth for Pallas kernel + jnp fallback)
# ---------------------------------------------------------------------------

def _ln_fwd_math(x, w, b, eps, hidden: int, rms: bool):
    """x: (rows, hidden_p) f32 zero-padded; returns (y, mean, rstd)."""
    inv_h = 1.0 / hidden
    if rms:
        mean = jnp.zeros((x.shape[0], 1), _f32)
        ms = jnp.sum(x * x, axis=1, keepdims=True) * inv_h
        rstd = jax.lax.rsqrt(ms + eps)
        xhat = x * rstd
    else:
        mean = jnp.sum(x, axis=1, keepdims=True) * inv_h
        ms = jnp.sum(x * x, axis=1, keepdims=True) * inv_h
        var = ms - mean * mean
        rstd = jax.lax.rsqrt(var + eps)
        xhat = (x - mean) * rstd
    y = xhat * w
    if b is not None:
        y = y + b
    return y, mean, rstd


def _ln_bwd_math(dy, xhat, w, rstd, hidden: int, rms: bool):
    """Returns (dx, dw_rowsum(hidden,), db_rowsum(hidden,))."""
    inv_h = 1.0 / hidden
    wdy = dy * w
    c1 = jnp.sum(wdy * xhat, axis=1, keepdims=True) * inv_h
    if rms:
        dx = (wdy - xhat * c1) * rstd
    else:
        c2 = jnp.sum(wdy, axis=1, keepdims=True) * inv_h
        dx = (wdy - xhat * c1 - c2) * rstd
    dw = jnp.sum(dy * xhat, axis=0)
    db = jnp.sum(dy, axis=0)
    return dx, dw, db


# ---------------------------------------------------------------------------
# Pallas kernels
# ---------------------------------------------------------------------------

def _fwd_kernel(rms, has_bias, eps, hidden, x_ref, w_ref, b_ref,
                y_ref, mean_ref, rstd_ref):
    x = x_ref[:].astype(_f32)
    w = w_ref[:].astype(_f32)
    b = b_ref[:].astype(_f32) if has_bias else None
    y, mean, rstd = _ln_fwd_math(x, w, b, eps, hidden, rms)
    y_ref[:] = y.astype(y_ref.dtype)
    mean_ref[:] = mean
    rstd_ref[:] = rstd


def _bwd_kernel(rms, from_y, has_bias, hidden, dy_ref, res_ref, w_ref, b_ref,
                mean_ref, rstd_ref, dx_ref, dwp_ref, dbp_ref):
    dy = dy_ref[:].astype(_f32)
    w = w_ref[:].astype(_f32)
    rstd = rstd_ref[:]
    if from_y:
        y = res_ref[:].astype(_f32)
        if has_bias:
            y = y - b_ref[:].astype(_f32)
        # guard the hidden-axis zero padding of gamma (0/0 → NaN would
        # poison the row reductions)
        xhat = y / jnp.where(w == 0.0, 1.0, w)
    else:
        x = res_ref[:].astype(_f32)
        xhat = (x - mean_ref[:]) * rstd if not rms else x * rstd
    dx, dw, db = _ln_bwd_math(dy, xhat, w, rstd, hidden, rms)
    dx_ref[:] = dx.astype(dx_ref.dtype)
    # partials blocks are 8 sublanes tall (TPU tiling minimum); row 0 holds
    # the sums, rows 1-7 stay zero and wash out in the cross-block reduce
    dwp_ref[:] = jnp.zeros_like(dwp_ref[:])
    dbp_ref[:] = jnp.zeros_like(dbp_ref[:])
    dwp_ref[0:1, :] = dw[None, :]
    dbp_ref[0:1, :] = db[None, :]


def _pallas_fwd(x2, w, b, eps, hidden, rms):
    rows, hidden_p = x2.shape
    br = _pick_block_rows(hidden_p)
    rows_p = _round_up(rows, br)
    if rows_p != rows:
        x2 = jnp.pad(x2, ((0, rows_p - rows), (0, 0)))
    has_bias = b is not None
    args = (x2, w.reshape(1, -1)) + ((b.reshape(1, -1),) if has_bias else ())
    row_spec = pl.BlockSpec((br, hidden_p), lambda i: (i, 0),
                            memory_space=pltpu.VMEM)
    wb_spec = pl.BlockSpec((1, hidden_p), lambda i: (0, 0),
                           memory_space=pltpu.VMEM)
    stat_spec = pl.BlockSpec((br, 1), lambda i: (i, 0),
                             memory_space=pltpu.VMEM)
    if has_bias:
        kernel = functools.partial(_fwd_kernel, rms, True, eps, hidden)
    else:
        def kernel(x_ref, w_ref, y_ref, mean_ref, rstd_ref,
                   _rms=rms, _eps=eps, _h=hidden):
            _fwd_kernel(_rms, False, _eps, _h, x_ref, w_ref, None,
                        y_ref, mean_ref, rstd_ref)
    y, mean, rstd = pl.pallas_call(
        kernel,
        grid=(rows_p // br,),
        in_specs=[row_spec, wb_spec] + ([wb_spec] if has_bias else []),
        out_specs=[row_spec, stat_spec, stat_spec],
        out_shape=[sds_like((rows_p, hidden_p), x2.dtype, x2),
                   sds_like((rows_p, 1), _f32, x2),
                   sds_like((rows_p, 1), _f32, x2)],
        interpret=interpret_mode(),
    )(*args)
    return y[:rows], mean[:rows], rstd[:rows]


def _pallas_bwd(dy2, res2, w, b, mean, rstd, hidden, rms, from_y):
    rows, hidden_p = dy2.shape
    br = _pick_block_rows(hidden_p)
    rows_p = _round_up(rows, br)
    pad = rows_p - rows
    if pad:
        dy2 = jnp.pad(dy2, ((0, pad), (0, 0)))
        res2 = jnp.pad(res2, ((0, pad), (0, 0)))
        mean = jnp.pad(mean, ((0, pad), (0, 0)))
        rstd = jnp.pad(rstd, ((0, pad), (0, 0)), constant_values=1.0)
    has_bias = b is not None
    nblocks = rows_p // br
    row_spec = pl.BlockSpec((br, hidden_p), lambda i: (i, 0),
                            memory_space=pltpu.VMEM)
    wb_spec = pl.BlockSpec((1, hidden_p), lambda i: (0, 0),
                           memory_space=pltpu.VMEM)
    stat_spec = pl.BlockSpec((br, 1), lambda i: (i, 0),
                             memory_space=pltpu.VMEM)
    part_spec = pl.BlockSpec((8, hidden_p), lambda i: (i, 0),
                             memory_space=pltpu.VMEM)
    b_arr = b.reshape(1, -1) if has_bias else jnp.zeros((1, hidden_p), _f32)

    def kernel(dy_ref, res_ref, w_ref, b_ref, mean_ref, rstd_ref,
               dx_ref, dwp_ref, dbp_ref,
               _rms=rms, _fy=from_y, _hb=has_bias, _h=hidden):
        _bwd_kernel(_rms, _fy, _hb, _h, dy_ref, res_ref, w_ref, b_ref,
                    mean_ref, rstd_ref, dx_ref, dwp_ref, dbp_ref)

    dx, dwp, dbp = pl.pallas_call(
        kernel,
        grid=(nblocks,),
        in_specs=[row_spec, row_spec, wb_spec, wb_spec, stat_spec,
                  stat_spec],
        out_specs=[row_spec, part_spec, part_spec],
        out_shape=[sds_like((rows_p, hidden_p), dy2.dtype, dy2),
                   sds_like((nblocks * 8, hidden_p), _f32, dy2),
                   sds_like((nblocks * 8, hidden_p), _f32, dy2)],
        interpret=interpret_mode(),
    )(dy2, res2, w.reshape(1, -1), b_arr, mean, rstd)
    return dx[:rows], jnp.sum(dwp, axis=0), jnp.sum(dbp, axis=0)


# ---------------------------------------------------------------------------
# public functional ops with custom VJP
# ---------------------------------------------------------------------------

def _prep(x, hidden):
    """Flatten to (rows, hidden) and zero-pad hidden to a lane multiple."""
    rows = x.size // hidden
    x2 = x.reshape(rows, hidden)
    hidden_p = _round_up(hidden, LANE)
    if hidden_p != hidden:
        x2 = jnp.pad(x2, ((0, 0), (0, hidden_p - hidden)))
    return x2, hidden_p


def _pad_vec(v, hidden_p, dtype=_f32):
    v = v.reshape(-1).astype(dtype)
    if v.shape[0] != hidden_p:
        v = jnp.pad(v, (0, hidden_p - v.shape[0]))
    return v


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _norm_affine(x, weight, bias, hidden, eps, rms, memory_efficient):
    (y, _, _), _ = _norm_fwd(x, weight, bias, hidden, eps, rms,
                             memory_efficient)
    return y


def _norm_fwd(x, weight, bias, hidden, eps, rms, memory_efficient):
    orig_shape = x.shape
    x2, hidden_p = _prep(x, hidden)
    wp = _pad_vec(weight, hidden_p)
    bp = _pad_vec(bias, hidden_p) if bias is not None else None
    if use_pallas() and x2.dtype != jnp.float16:
        y2, mean, rstd = _pallas_fwd(x2, wp, bp, eps, hidden, rms)
    else:
        y2, mean, rstd = _ln_fwd_math(x2.astype(_f32), wp, bp, eps, hidden,
                                      rms)
        y2 = y2.astype(x2.dtype)
    y = y2[:, :hidden].reshape(orig_shape)
    res2 = y2 if memory_efficient else x2
    # dtypes ride along as zero-size carrier arrays (residuals must be
    # arrays; dx/dw/db cotangent dtypes must match the primals)
    carriers = (jnp.zeros((0,), x.dtype), jnp.zeros((0,), weight.dtype),
                None if bias is None else jnp.zeros((0,), bias.dtype))
    return (y, mean, rstd), (res2, wp, bp, mean, rstd, carriers)


def _norm_fwd_vjp(x, weight, bias, hidden, eps, rms, memory_efficient):
    (y, _, _), residuals = _norm_fwd(x, weight, bias, hidden, eps, rms,
                                     memory_efficient)
    return y, residuals


def _norm_bwd_vjp(hidden, eps, rms, memory_efficient, residuals, dy):
    res2, wp, bp, mean, rstd, (xc, wc, bc) = residuals
    orig_shape = dy.shape
    dy2, _ = _prep(dy, hidden)
    dy2 = dy2.astype(res2.dtype)
    if use_pallas() and res2.dtype != jnp.float16:
        dx2, dw, db = _pallas_bwd(dy2, res2, wp, bp, mean, rstd, hidden,
                                  rms, memory_efficient)
    else:
        dyf = dy2.astype(_f32)
        resf = res2.astype(_f32)
        if memory_efficient:
            yf = resf - bp if bp is not None else resf
            xhat = yf / jnp.where(wp == 0.0, 1.0, wp)
        else:
            xhat = (resf - mean) * rstd if not rms else resf * rstd
        dx2, dw, db = _ln_bwd_math(dyf, xhat, wp, rstd, hidden, rms)
    dx = dx2[:, :hidden].reshape(orig_shape).astype(xc.dtype)
    dw = dw[:hidden].astype(wc.dtype)
    if bc is None:
        return dx, dw, None
    return dx, dw, db[:hidden].astype(bc.dtype)


_norm_affine.defvjp(_norm_fwd_vjp, _norm_bwd_vjp)


def _affine(x, weight, bias, eps, rms, memory_efficient):
    hidden = int(weight.size)

    def flat(p):       # typed like x: see collectives.vary_like
        return None if p is None else vary_like(p, x).reshape(-1)

    return _norm_affine(x, flat(weight), flat(bias), hidden, float(eps),
                        rms, bool(memory_efficient))


def fused_layer_norm_affine(x, weight, bias, normalized_shape=None,
                            eps=1e-5, memory_efficient=False):
    """apex ``fused_layer_norm_affine``: LN over the trailing dims with
    learnable gamma/beta."""
    return _affine(x, weight, bias, eps, False, memory_efficient)


def fused_rms_norm_affine(x, weight, normalized_shape=None, eps=1e-5,
                          memory_efficient=False):
    """apex ``fused_rms_norm_affine``: RMSNorm with learnable gamma."""
    return _affine(x, weight, None, eps, True, memory_efficient)


def fused_layer_norm(x, normalized_shape, eps=1e-5):
    """Non-affine LN (apex ``fused_layer_norm``)."""
    hidden = 1
    for d in normalized_shape:
        hidden *= d
    w = jnp.ones((hidden,), _f32)
    b = jnp.zeros((hidden,), _f32)
    return _norm_affine(x, w, b, hidden, float(eps), False, False)


def fused_rms_norm(x, normalized_shape, eps=1e-5):
    """Non-affine RMSNorm."""
    hidden = 1
    for d in normalized_shape:
        hidden *= d
    w = jnp.ones((hidden,), _f32)
    return _norm_affine(x, w, None, hidden, float(eps), True, False)
