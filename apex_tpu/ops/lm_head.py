"""Fused LM-head cross entropy — blockwise (logit-free) linear + softmax
cross entropy as Pallas kernels.

The reference's ``apex/contrib/xentropy`` fuses softmax+CE to avoid
recomputing softmax in the backward; the logits themselves still
materialize (O(N·V)).  On TPU the LM head is memory-bound on exactly that
(b·s × vocab) logits round-trip — ~3.3 GB for GPT-350M at batch 16 — so
this op goes one step further and never forms logits at all (the
flash-attention trade applied to the classifier: blockwise online
logsumexp over vocab tiles, recompute probabilities in the backward from
the saved per-token logsumexp).  Beyond-reference; the contrib xentropy
surface is unchanged.

Math (per token i with target y): ``loss_i = lse_i − x_i·W_{y_i}`` where
``lse_i = logsumexp_v(x_i·W_v)``.  Backward with upstream cotangent g_i:
``dX_i = g_i (p_i − onehot(y_i)) W`` and ``dW = Σ_i g_i (p_i −
onehot(y_i))^T x_i`` with ``p_iv = exp(x_i·W_v − lse_i)`` recomputed per
tile.

Forward grid ``(token_blocks, vocab_blocks)`` (vocab innermost): running
row-max/row-sum scratch like the flash kernel, plus the target logit
captured by an in-tile one-hot select.  Backward runs two kernels with
transposed grids: dX accumulates over vocab blocks, dW over token blocks.

Off-TPU the same semantics run as a materialized jnp reference.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from apex_tpu.multi_tensor_apply.bucketing import _round_up
from apex_tpu.utils.collectives import sds_like as _sds
from apex_tpu.utils.platform import interpret_mode, use_pallas

_f32 = jnp.float32
_MASK = -1e30

__all__ = ["fused_linear_cross_entropy",
           "fused_linear_cross_entropy_reference"]


# ---------------------------------------------------------------------------
# forward kernel
# ---------------------------------------------------------------------------

def _dot_dtype(x_dtype, w_dtype):
    """Operand dtype for the logit dots: the bf16 fast path is taken only
    when BOTH operands are bf16 (accumulation stays f32 via
    ``preferred_element_type``) — under O2 the whole tied head IS bf16,
    and upcasting matched-bf16 operands to f32 costs MXU rate for
    accumulation precision the f32 path already provides.  A MIXED
    f32/bf16 pair upcasts to f32: downcasting the f32 side would silently
    drop operand precision in the loss and both gradient GEMMs for any
    caller passing f32 hidden states with a bf16 tied embedding (ADVICE
    round 5).  (Only bf16 is special: Mosaic has no f16 vector type, so
    f16 operands never reach these kernels.)"""
    if (jnp.dtype(x_dtype) == jnp.bfloat16
            and jnp.dtype(w_dtype) == jnp.bfloat16):
        return jnp.bfloat16
    return _f32


def _fwd_kernel(n_valid, v_valid, block_t, block_v,
                tgt_ref, x_ref, w_ref, loss_ref, lse_ref,
                m_scr, l_scr, t_scr):
    vi = pl.program_id(1)
    nv = pl.num_programs(1)

    @pl.when(vi == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr[:], _MASK)
        l_scr[:] = jnp.zeros_like(l_scr[:])
        t_scr[:] = jnp.zeros_like(t_scr[:])

    dt = _dot_dtype(x_ref.dtype, w_ref.dtype)
    x = x_ref[:].astype(dt)
    w = w_ref[:].astype(dt)
    s = jax.lax.dot_general(x, w, (((1,), (1,)), ((), ())),
                            preferred_element_type=_f32)
    v_pos = vi * block_v + jax.lax.broadcasted_iota(
        jnp.int32, (block_t, block_v), 1)
    valid = v_pos < v_valid
    s = jnp.where(valid, s, _MASK)

    m_prev = m_scr[:, :1]
    m_cur = jnp.maximum(jnp.max(s, axis=1, keepdims=True), m_prev)
    alpha = jnp.exp(m_prev - m_cur)
    p = jnp.where(valid, jnp.exp(s - m_cur), 0.0)
    l_scr[:] = jnp.broadcast_to(
        alpha * l_scr[:, :1] + jnp.sum(p, axis=1, keepdims=True),
        l_scr.shape)
    m_scr[:] = jnp.broadcast_to(m_cur, m_scr.shape)
    # capture the target logit when this tile owns the row's target
    hit = v_pos == tgt_ref[:]          # (block_t, 1) broadcasts over cols
    t_scr[:] = t_scr[:] + jnp.broadcast_to(
        jnp.sum(jnp.where(hit, s, 0.0), axis=1, keepdims=True),
        t_scr.shape)

    @pl.when(vi == nv - 1)
    def _finish():
        m = m_scr[:, :1]
        l = jnp.where(l_scr[:, :1] == 0.0, 1.0, l_scr[:, :1])
        lse = m + jnp.log(l)
        lse_ref[:] = lse
        loss_ref[:] = lse - t_scr[:, :1]


# ---------------------------------------------------------------------------
# backward kernels
# ---------------------------------------------------------------------------

def _p_minus_onehot(s_valid, vi, block_t, block_v, v_valid, tgt, lse, s):
    """g-free ``p − onehot(target)`` for one tile, invalid columns zero."""
    v_pos = vi * block_v + jax.lax.broadcasted_iota(
        jnp.int32, (block_t, block_v), 1)
    valid = v_pos < v_valid
    p = jnp.where(valid, jnp.exp(s - lse), 0.0)
    hit = v_pos == tgt                 # (block_t, 1) broadcasts over cols
    return p - jnp.where(hit, 1.0, 0.0)


def _dx_kernel(v_valid, block_t, block_v,
               tgt_ref, x_ref, w_ref, lse_ref, g_ref, dx_ref, dx_scr):
    vi = pl.program_id(1)
    nv = pl.num_programs(1)

    @pl.when(vi == 0)
    def _init():
        dx_scr[:] = jnp.zeros_like(dx_scr[:])

    dt = _dot_dtype(x_ref.dtype, w_ref.dtype)
    x = x_ref[:].astype(dt)
    w = w_ref[:].astype(dt)
    s = jax.lax.dot_general(x, w, (((1,), (1,)), ((), ())),
                            preferred_element_type=_f32)
    ds = _p_minus_onehot(None, vi, x.shape[0], block_v, v_valid,
                         tgt_ref[:], lse_ref[:], s)
    ds = ds * g_ref[:]                       # per-token upstream cotangent
    # dS cast to the operand dtype for the MXU-rate dot (same trade as
    # the flash backward: dS is written back at input precision)
    dx_scr[:] += jax.lax.dot_general(ds.astype(dt), w,
                                     (((1,), (0,)), ((), ())),
                                     preferred_element_type=_f32)

    @pl.when(vi == nv - 1)
    def _finish():
        dx_ref[:] = dx_scr[:].astype(dx_ref.dtype)


def _dw_kernel(n_valid, v_valid, block_t, block_v,
               tgt_ref, x_ref, w_ref, lse_ref, g_ref, dw_ref, dw_scr):
    vi = pl.program_id(0)
    ti = pl.program_id(1)
    nt = pl.num_programs(1)

    @pl.when(ti == 0)
    def _init():
        dw_scr[:] = jnp.zeros_like(dw_scr[:])

    dt = _dot_dtype(x_ref.dtype, w_ref.dtype)
    x = x_ref[:].astype(dt)
    w = w_ref[:].astype(dt)
    s = jax.lax.dot_general(x, w, (((1,), (1,)), ((), ())),
                            preferred_element_type=_f32)
    block_t_ = x.shape[0]
    ds = _p_minus_onehot(None, vi, block_t_, block_v, v_valid,
                         tgt_ref[:], lse_ref[:], s)
    ds = ds * g_ref[:]
    # zero padded token rows: their lse is garbage
    t_pos = ti * block_t + jax.lax.broadcasted_iota(
        jnp.int32, (block_t_, block_v), 0)
    ds = jnp.where(t_pos < n_valid, ds, 0.0)
    dw_scr[:] += jax.lax.dot_general(ds.astype(dt), x,
                                     (((0,), (0,)), ((), ())),
                                     preferred_element_type=_f32)

    @pl.when(ti == nt - 1)
    def _finish():
        dw_ref[:] = dw_scr[:].astype(dw_ref.dtype)


# ---------------------------------------------------------------------------
# pallas_call plumbing + custom VJP
# ---------------------------------------------------------------------------

def _pad2(x, rows, cols):
    r, c = x.shape
    if r != rows or c != cols:
        x = jnp.pad(x, ((0, rows - r), (0, cols - c)))
    return x


def _compiler_params():
    return pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary"))


def _fwd_impl(x, w, targets, block_t, block_v):
    N, H = x.shape
    V = w.shape[0]
    Np, Vp = _round_up(N, block_t), _round_up(V, block_v)
    Hp = _round_up(H, 128)
    xp = _pad2(x, Np, Hp)
    wp = _pad2(w, Vp, Hp)
    # padded token rows target -1: never matches a vocab position;
    # column layout — Mosaic rejects 1-D int operands whose XLA tiling
    # disagrees with the block shape
    tp = jnp.pad(targets.astype(jnp.int32), (0, Np - N),
                 constant_values=-1).reshape(Np, 1)
    kernel = functools.partial(_fwd_kernel, N, V, block_t, block_v)
    loss, lse = pl.pallas_call(
        kernel,
        grid=(Np // block_t, Vp // block_v),
        in_specs=[
            pl.BlockSpec((block_t, 1), lambda t, v: (t, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((block_t, Hp), lambda t, v: (t, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((block_v, Hp), lambda t, v: (v, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((block_t, 1), lambda t, v: (t, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((block_t, 1), lambda t, v: (t, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=[_sds((Np, 1), _f32, xp),
                   _sds((Np, 1), _f32, xp)],
        scratch_shapes=[pltpu.VMEM((block_t, 128), _f32),
                        pltpu.VMEM((block_t, 128), _f32),
                        pltpu.VMEM((block_t, 128), _f32)],
        compiler_params=_compiler_params(),
        interpret=interpret_mode(),
    )(tp, xp, wp)
    return loss[:N, 0], lse


def _bwd_impl(x, w, targets, lse, g, block_t, block_v):
    N, H = x.shape
    V = w.shape[0]
    Np, Vp = _round_up(N, block_t), _round_up(V, block_v)
    Hp = _round_up(H, 128)
    xp = _pad2(x, Np, Hp)
    wp = _pad2(w, Vp, Hp)
    tp = jnp.pad(targets.astype(jnp.int32), (0, Np - N),
                 constant_values=-1).reshape(Np, 1)
    gp = jnp.pad(g.astype(_f32).reshape(N, 1), ((0, Np - N), (0, 0)))

    dx = pl.pallas_call(
        functools.partial(_dx_kernel, V, block_t, block_v),
        grid=(Np // block_t, Vp // block_v),
        in_specs=[
            pl.BlockSpec((block_t, 1), lambda t, v: (t, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((block_t, Hp), lambda t, v: (t, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((block_v, Hp), lambda t, v: (v, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((block_t, 1), lambda t, v: (t, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((block_t, 1), lambda t, v: (t, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((block_t, Hp), lambda t, v: (t, 0),
                               memory_space=pltpu.VMEM),
        out_shape=_sds((Np, Hp), x.dtype, xp),
        scratch_shapes=[pltpu.VMEM((block_t, Hp), _f32)],
        compiler_params=_compiler_params(),
        interpret=interpret_mode(),
    )(tp, xp, wp, lse, gp)

    dw = pl.pallas_call(
        functools.partial(_dw_kernel, N, V, block_t, block_v),
        grid=(Vp // block_v, Np // block_t),
        in_specs=[
            pl.BlockSpec((block_t, 1), lambda v, t: (t, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((block_t, Hp), lambda v, t: (t, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((block_v, Hp), lambda v, t: (v, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((block_t, 1), lambda v, t: (t, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((block_t, 1), lambda v, t: (t, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((block_v, Hp), lambda v, t: (v, 0),
                               memory_space=pltpu.VMEM),
        out_shape=_sds((Vp, Hp), w.dtype, xp),
        scratch_shapes=[pltpu.VMEM((block_v, Hp), _f32)],
        compiler_params=_compiler_params(),
        interpret=interpret_mode(),
    )(tp, xp, wp, lse, gp)
    return dx[:N, :H], dw[:V, :H]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _fused(x, w, targets, block_t, block_v):
    loss, _ = _fwd_impl(x, w, targets, block_t, block_v)
    return loss


def _fused_fwd(x, w, targets, block_t, block_v):
    loss, lse = _fwd_impl(x, w, targets, block_t, block_v)
    return loss, (x, w, targets, lse)


def _fused_bwd(block_t, block_v, res, g):
    x, w, targets, lse = res
    dx, dw = _bwd_impl(x, w, targets, lse, g, block_t, block_v)
    return dx.astype(x.dtype), dw.astype(w.dtype), None


_fused.defvjp(_fused_fwd, _fused_bwd)


# ---------------------------------------------------------------------------
# public API + reference
# ---------------------------------------------------------------------------

def fused_linear_cross_entropy_reference(x, w, targets):
    """Materialized reference: ``-log softmax(x @ w.T)[targets]``."""
    logits = (x.astype(_f32) @ w.astype(_f32).T)
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(
        logp, targets.reshape(-1, 1).astype(jnp.int32), axis=1)[:, 0]


def fused_linear_cross_entropy(x, w, targets, *, block_t=256,
                               block_v=512):
    """Per-token CE of the tied LM head WITHOUT materializing logits.

    ``x``: ``(N, H)`` hidden states; ``w``: ``(V, H)`` (tied embedding);
    ``targets``: ``(N,)`` int.  Returns per-token loss ``(N,)`` f32,
    differentiable in ``x`` and ``w``.  O(N·H + V·H) memory instead of
    O(N·V); fwd + both backward GEMMs run on vocab tiles in VMEM.
    """
    N, H = x.shape
    V = w.shape[0]
    if not use_pallas() or jnp.float16 in (x.dtype, w.dtype):
        # f16: Mosaic has no f16 vector type (same gate as
        # ops/multi_tensor.py::_use_kernel)
        return fused_linear_cross_entropy_reference(x, w, targets)
    # the weight-gradient kernel holds the most: block_t rows of x and
    # block_v rows of w and of its result, each of width H and twice over
    # (double buffering), beside a float32 accumulator of block_v rows.
    # Mosaic gives a kernel 16 MB of scoped VMEM; the default blocks ask for
    # 14 MB of it at H = 2048 in bf16, which one program passed and the next
    # did not, and for 19 MB at H = 2688
    itemsize = max(x.dtype.itemsize, w.dtype.itemsize)
    while H * (2 * itemsize * (block_t + 2 * block_v) + 4 * block_v) \
            > 12 * 2 ** 20 and block_v > 128:
        block_v //= 2
    return _fused(x, w, targets, int(block_t), int(block_v))
