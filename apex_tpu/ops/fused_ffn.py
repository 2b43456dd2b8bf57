"""Fused bias-GELU FFN — the transformer MLP pair as one Pallas op.

The reference ships this block as ``apex/fused_dense`` (CUDA cublasLt
epilogue GEMMs: ``Linear -> bias -> GELU`` fused into the first GEMM's
epilogue, the second GEMM consuming it in-register).  On TPU, XLA's own
epilogue fusion covers the *elementwise* half (bias+GELU fuse into the
MXU matmul's output — pinned by ``tests/test_on_chip.py::
TestXlaFusionClaim``) but still materializes the ``(tokens, ffn_hidden)``
activation between the two GEMMs in HBM twice per direction.  This
kernel closes that gap the same way ``ops/flash_attention.py`` does for
attention:

* forward: grid ``(m_blocks, f_blocks)`` with the ffn-hidden axis
  innermost; each step computes one ``(block_m, block_f)`` tile of
  ``z = x @ W1^T + b1`` (f32 accumulation on the MXU), applies the tanh
  GELU, and accumulates ``gelu(z) @ W2^T`` into a ``(block_m, n)`` f32
  VMEM scratch — the second GEMM consumes the activation tile while it
  is still in VMEM, so the full ``(m, f)`` activation never round-trips
  through HBM inside one grid row.  The pre-activation ``z`` is written
  out as the backward's residual (the flash-attention recompute trade:
  save the small thing, recompute the nonlinearity).
* backward: two kernels with the same blocking, both recomputing the
  GELU terms from the saved pre-activation — one accumulating ``dx``
  (f innermost), one walking ``(f_blocks, m_blocks)`` to accumulate
  ``dW1``/``db1``/``dW2`` in f32 scratch (m innermost).  ``db2`` is a
  plain row-sum of the output cotangent (one XLA reduce on an input —
  nothing to fuse).

Numerics: both GEMMs accumulate in f32 via ``preferred_element_type``
with operands kept in the activation dtype (full MXU bf16 rate); the
GELU and its hand-written tanh derivative run in f32.  Off-TPU the
public API dispatches to :func:`fused_ffn_reference`, which replays the
EXACT op order of the unfused ``ColumnParallelLinear -> gelu ->
RowParallelLinear`` path — so flipping the ``fused_ffn`` model knob is
bitwise-neutral on CPU f32, and the unit suite compares the kernel
(interpret mode) against the reference at the flash-attention
tolerances.

Padding parity: every extent is zero-padded to its block/lane multiple
inside the op and sliced back; zero rows/lanes are exact no-ops through
both GEMMs and the backward (``gelu(0) = 0`` kills the padded ffn
columns in the forward, zero cotangent rows kill them in the backward).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from apex_tpu.multi_tensor_apply.bucketing import _round_up
from apex_tpu.utils.platform import interpret_mode, use_pallas

_f32 = jnp.float32

__all__ = ["fused_ffn", "fused_ffn_reference", "fused_ffn_tp"]


def _sds(shape, dtype, like):
    """vma-aware pallas output ShapeDtypeStruct (see
    :func:`apex_tpu.utils.collectives.sds_like`)."""
    from apex_tpu.utils.collectives import sds_like

    return sds_like(shape, dtype, like)


# ---------------------------------------------------------------------------
# tanh-GELU and its derivative (f32, shared by all kernels)
# ---------------------------------------------------------------------------

_GELU_C = 0.7978845608028654   # sqrt(2/pi)
_GELU_A = 0.044715


def _gelu(z):
    """tanh-approximate GELU on an f32 tile (same closed form
    ``jax.nn.gelu(z, approximate=True)`` lowers to)."""
    return jax.nn.gelu(z, approximate=True)


def _gelu_grad(z):
    """d/dz of the tanh GELU, in closed form so the backward recomputes
    it from the saved pre-activation instead of storing it."""
    z2 = z * z
    t = jnp.tanh(_GELU_C * z * (1.0 + _GELU_A * z2))
    return (0.5 * (1.0 + t)
            + 0.5 * z * (1.0 - t * t) * _GELU_C * (1.0 + 3.0 * _GELU_A * z2))


def _dot_t(a, b):
    """``a @ b^T`` contracting the trailing dims, f32 accumulation."""
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                               preferred_element_type=_f32)


def _dot_colsum(a, b):
    """``a^T @ b`` contracting the leading dims, f32 accumulation."""
    return jax.lax.dot_general(a, b, (((0,), (0,)), ((), ())),
                               preferred_element_type=_f32)


# ---------------------------------------------------------------------------
# forward kernel
# ---------------------------------------------------------------------------

def _ffn_fwd_kernel(x_ref, w1_ref, b1_ref, w2_ref, b2_ref, y_ref, z1_ref,
                    acc_scr):
    fi = pl.program_id(1)
    nf = pl.num_programs(1)

    @pl.when(fi == 0)
    def _init():
        acc_scr[:] = jnp.zeros_like(acc_scr[:])

    x = x_ref[:]
    # z tile: (block_m, block_f) pre-activation, f32 accumulation
    z = _dot_t(x, w1_ref[:].astype(x.dtype)) + b1_ref[:].astype(_f32)
    z1_ref[:] = z.astype(z1_ref.dtype)
    h = _gelu(z).astype(x.dtype)
    # second GEMM consumes the activation tile straight from registers/
    # VMEM: acc += gelu(z) @ W2_block^T  ->  (block_m, n_pad)
    acc_scr[:] += _dot_t(h, w2_ref[:].astype(x.dtype))

    @pl.when(fi == nf - 1)
    def _finish():
        y_ref[:] = (acc_scr[:] + b2_ref[:].astype(_f32)).astype(y_ref.dtype)


# ---------------------------------------------------------------------------
# backward kernels
# ---------------------------------------------------------------------------

def _ffn_dx_kernel(dy_ref, z1_ref, w1_ref, w2_ref, dx_ref, dx_scr):
    fi = pl.program_id(1)
    nf = pl.num_programs(1)

    @pl.when(fi == 0)
    def _init():
        dx_scr[:] = jnp.zeros_like(dx_scr[:])

    dy = dy_ref[:]
    z = z1_ref[:].astype(_f32)
    # dh = dy @ W2_block: (block_m, n_pad) x (n_pad, block_f)
    dh = jax.lax.dot_general(dy, w2_ref[:].astype(dy.dtype),
                             (((1,), (0,)), ((), ())),
                             preferred_element_type=_f32)
    dz = (dh * _gelu_grad(z)).astype(dy.dtype)
    # dx += dz @ W1_block: (block_m, block_f) x (block_f, k_pad)
    dx_scr[:] += jax.lax.dot_general(dz, w1_ref[:].astype(dy.dtype),
                                     (((1,), (0,)), ((), ())),
                                     preferred_element_type=_f32)

    @pl.when(fi == nf - 1)
    def _finish():
        dx_ref[:] = dx_scr[:].astype(dx_ref.dtype)


def _ffn_dw_kernel(x_ref, dy_ref, z1_ref, w2_ref, dw1_ref, db1_ref,
                   dw2_ref, dw1_scr, db1_scr, dw2_scr):
    mi = pl.program_id(1)
    nm = pl.num_programs(1)

    @pl.when(mi == 0)
    def _init():
        dw1_scr[:] = jnp.zeros_like(dw1_scr[:])
        db1_scr[:] = jnp.zeros_like(db1_scr[:])
        dw2_scr[:] = jnp.zeros_like(dw2_scr[:])

    x = x_ref[:]
    dy = dy_ref[:]
    z = z1_ref[:].astype(_f32)
    h = _gelu(z)
    dh = jax.lax.dot_general(dy, w2_ref[:].astype(dy.dtype),
                             (((1,), (0,)), ((), ())),
                             preferred_element_type=_f32)
    dz = dh * _gelu_grad(z)
    dzc = dz.astype(x.dtype)
    # dW1 += dz^T @ x: (block_f, block_m) x (block_m, k_pad)
    dw1_scr[:] += _dot_colsum(dzc, x)
    # dW2 += dy^T @ gelu(z): (n_pad, block_m) x (block_m, block_f)
    dw2_scr[:] += _dot_colsum(dy, h.astype(dy.dtype))
    # db1 += column-sum of dz as an MXU reduction to a (block_f, 1)
    # column (broadcast over the scratch's 128 lanes; lane 0 is read
    # back at the end — the flash lse unit-lane layout)
    ones = jnp.ones((dz.shape[0], 1), _f32)
    db1_scr[:] += _dot_colsum(dz, ones)

    @pl.when(mi == nm - 1)
    def _finish():
        dw1_ref[:] = dw1_scr[:].astype(dw1_ref.dtype)
        db1_ref[:] = db1_scr[:, 0:1]
        dw2_ref[:] = dw2_scr[:].astype(dw2_ref.dtype)


# ---------------------------------------------------------------------------
# pallas_call plumbing
# ---------------------------------------------------------------------------

def _pad2(a, r, c):
    if a.shape != (r, c):
        a = jnp.pad(a, ((0, r - a.shape[0]), (0, c - a.shape[1])))
    return a


def _vmem(block, index_map):
    return pl.BlockSpec(block, index_map, memory_space=pltpu.VMEM)


def _ffn_fwd_impl(x, w1, b1, w2, b2, block_m, block_f):
    """All operands pre-padded 2D: x (m_p, k_p), w1 (f_p, k_p),
    b1 (1, f_p), w2 (n_p, f_p), b2 (1, n_p); returns padded (y, z1)."""
    m_p, k_p = x.shape
    f_p = w1.shape[0]
    n_p = w2.shape[0]
    nm, nf = m_p // block_m, f_p // block_f
    return pl.pallas_call(
        _ffn_fwd_kernel,
        grid=(nm, nf),
        in_specs=[_vmem((block_m, k_p), lambda mi, fi: (mi, 0)),
                  _vmem((block_f, k_p), lambda mi, fi: (fi, 0)),
                  _vmem((1, block_f), lambda mi, fi: (0, fi)),
                  _vmem((n_p, block_f), lambda mi, fi: (0, fi)),
                  _vmem((1, n_p), lambda mi, fi: (0, 0))],
        out_specs=[_vmem((block_m, n_p), lambda mi, fi: (mi, 0)),
                   _vmem((block_m, block_f), lambda mi, fi: (mi, fi))],
        out_shape=[_sds((m_p, n_p), x.dtype, x),
                   _sds((m_p, f_p), x.dtype, x)],
        scratch_shapes=[pltpu.VMEM((block_m, n_p), _f32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret_mode(),
    )(x, w1, b1, w2, b2)


def _ffn_bwd_impl(x, w1, w2, z1, dy, block_m, block_f):
    """Padded operands; returns padded (dx, dw1, db1, dw2) with db1 as
    an (f_p, 1) f32 column."""
    m_p, k_p = x.shape
    f_p = w1.shape[0]
    n_p = w2.shape[0]
    nm, nf = m_p // block_m, f_p // block_f
    dx = pl.pallas_call(
        _ffn_dx_kernel,
        grid=(nm, nf),
        in_specs=[_vmem((block_m, n_p), lambda mi, fi: (mi, 0)),
                  _vmem((block_m, block_f), lambda mi, fi: (mi, fi)),
                  _vmem((block_f, k_p), lambda mi, fi: (fi, 0)),
                  _vmem((n_p, block_f), lambda mi, fi: (0, fi))],
        out_specs=_vmem((block_m, k_p), lambda mi, fi: (mi, 0)),
        out_shape=_sds((m_p, k_p), x.dtype, x),
        scratch_shapes=[pltpu.VMEM((block_m, k_p), _f32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret_mode(),
    )(dy, z1, w1, w2)

    # weight grads: swap the walk — f blocks outer (parallel), m inner
    dw1, db1, dw2 = pl.pallas_call(
        _ffn_dw_kernel,
        grid=(nf, nm),
        in_specs=[_vmem((block_m, k_p), lambda fi, mi: (mi, 0)),
                  _vmem((block_m, n_p), lambda fi, mi: (mi, 0)),
                  _vmem((block_m, block_f), lambda fi, mi: (mi, fi)),
                  _vmem((n_p, block_f), lambda fi, mi: (0, fi))],
        out_specs=[_vmem((block_f, k_p), lambda fi, mi: (fi, 0)),
                   _vmem((block_f, 1), lambda fi, mi: (fi, 0)),
                   _vmem((n_p, block_f), lambda fi, mi: (0, fi))],
        out_shape=[_sds((f_p, k_p), w1.dtype, w1),
                   _sds((f_p, 1), _f32, w1),
                   _sds((n_p, f_p), w2.dtype, w2)],
        scratch_shapes=[pltpu.VMEM((block_f, k_p), _f32),
                        pltpu.VMEM((block_f, 128), _f32),
                        pltpu.VMEM((n_p, block_f), _f32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret_mode(),
    )(x, dy, z1, w2)
    return dx, dw1, db1, dw2


# ---------------------------------------------------------------------------
# custom-VJP wrapper over (m, k) 2D operands
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _ffn(x, w1, b1, w2, b2, block_m, block_f):
    y, _ = _ffn_vjp_fwd(x, w1, b1, w2, b2, block_m, block_f)
    return y


def _ffn_vjp_fwd(x, w1, b1, w2, b2, block_m, block_f):
    m, k = x.shape
    f = w1.shape[0]
    n = w2.shape[0]
    m_p, k_p = _round_up(m, block_m), _round_up(k, 128)
    f_p, n_p = _round_up(f, block_f), _round_up(n, 128)
    xp = _pad2(x, m_p, k_p)
    w1p = _pad2(w1, f_p, k_p)
    w2p = _pad2(w2, n_p, f_p)
    yp, z1p = _ffn_fwd_impl(xp, w1p, _pad2(b1[None, :], 1, f_p), w2p,
                            _pad2(b2[None, :], 1, n_p), block_m, block_f)
    # residuals: inputs + the saved pre-activation (activation dtype);
    # the GELU terms are recomputed from z1 in both backward kernels
    return yp[:m, :n], (x, w1, b1, w2, b2, z1p)


def _ffn_vjp_bwd(block_m, block_f, res, dy):
    x, w1, b1, w2, b2, z1p = res
    m, k = x.shape
    f = w1.shape[0]
    n = w2.shape[0]
    m_p, f_p = z1p.shape
    k_p = _round_up(k, 128)
    n_p = _round_up(n, 128)
    dyp = _pad2(dy, m_p, n_p)
    dx, dw1, db1, dw2 = _ffn_bwd_impl(
        _pad2(x, m_p, k_p), _pad2(w1, f_p, k_p), _pad2(w2, n_p, f_p),
        z1p, dyp, block_m, block_f)
    db2 = jnp.sum(dy.astype(_f32), axis=0)
    return (dx[:m, :k],
            dw1[:f, :k],
            db1[:f, 0].astype(b1.dtype),
            dw2[:n, :f],
            db2.astype(b2.dtype))


_ffn.defvjp(_ffn_vjp_fwd, _ffn_vjp_bwd)


# ---------------------------------------------------------------------------
# reference + public API
# ---------------------------------------------------------------------------

def fused_ffn_reference(x, w1, b1, w2, b2=None):
    """Unfused reference: the EXACT op order of the model FFN path
    (``ColumnParallelLinear`` GEMM+bias -> tanh GELU ->
    ``RowParallelLinear`` GEMM [+ bias]) — so the off-TPU fallback is
    bitwise-identical to running the unfused layers."""
    h = x @ w1.astype(x.dtype).T
    h = h + b1.astype(h.dtype)
    h = jax.nn.gelu(h, approximate=True)
    y = h @ w2.astype(h.dtype).T
    if b2 is not None:
        y = y + b2.astype(y.dtype)
    return y


def _fit(requested, extent):
    """Largest candidate block <= requested dividing the lane-padded
    extent (the flash-attention block picker)."""
    padded = _round_up(extent, 128)
    for cand in (requested, 512, 384, 256, 128):
        if cand <= requested and padded % cand == 0:
            return cand
    return min(requested, padded)


def fused_ffn(x, w1, b1, w2, b2=None, *, block_m=256, block_f=512):
    """Fused ``gelu(x @ w1^T + b1) @ w2^T [+ b2]`` over ``(..., k)``.

    ``w1`` is ``(ffn_hidden, k)`` and ``w2`` ``(out, ffn_hidden)`` —
    the ``(out_features, in_features)`` layout of the TP linear layers,
    so a column-sharded ``w1`` / row-sharded ``w2`` pair drops in
    per-rank unchanged.  ``b2=None`` skips the second bias (the
    RowParallel case, where the bias is added *after* the cross-rank
    reduce).  Forward saves only the ``(m, ffn_hidden)`` pre-activation
    (activation dtype) for the backward; both GEMMs accumulate f32.

    Off-TPU (``use_pallas() == False``) this dispatches to
    :func:`fused_ffn_reference`, which replays the unfused op order
    bitwise.
    """
    if x.shape[-1] != w1.shape[1]:
        raise ValueError(f"x features {x.shape[-1]} != w1 in-dim "
                         f"{w1.shape[1]}")
    if b1.shape != (w1.shape[0],):
        raise ValueError(f"b1 shape {b1.shape} != ({w1.shape[0]},)")
    if w2.shape[1] != w1.shape[0]:
        raise ValueError(f"w2 in-dim {w2.shape[1]} != w1 out-dim "
                         f"{w1.shape[0]}")
    if b2 is not None and b2.shape != (w2.shape[0],):
        raise ValueError(f"b2 shape {b2.shape} != ({w2.shape[0]},)")
    if not use_pallas():
        return fused_ffn_reference(x, w1, b1, w2, b2)
    lead = x.shape[:-1]
    x2 = x.reshape((-1, x.shape[-1]))
    block_m = _fit(int(block_m), x2.shape[0])
    block_f = _fit(int(block_f), w1.shape[0])
    b2_arr = b2 if b2 is not None else jnp.zeros((w2.shape[0],), w2.dtype)
    y = _ffn(x2, w1, b1, w2, b2_arr, block_m, block_f)
    return y.reshape(lead + (w2.shape[0],))


def fused_ffn_tp(x, w1, b1, w2, b2, *, tensor_parallel_size=1,
                 axis_name=None, sequence_parallel=False, seq_dim=1):
    """The model-side fused FFN block: the kernel wrapped in the exact
    Megatron TP/SP edge collectives the unfused ``ColumnParallelLinear
    -> gelu -> RowParallelLinear`` pair uses.

    ``w1``/``b1`` are the column-sharded fc1 params (ffn dim over the
    tensor axis), ``w2`` the row-sharded fc2 weight, ``b2`` the
    UNsharded fc2 bias — added after the cross-rank reduce, wrapped in
    ``copy_to_tensor_model_parallel_region`` under SP so the replicated
    bias's cotangent is psummed over ranks (the RowParallelLinear
    ``_bias()`` discipline).  At ``overlap_chunks > 0`` the unfused
    path rings its collective+GEMM pairs; the fused kernel takes
    precedence for the FFN pair and uses the plain SP edges (the
    in-VMEM fusion replaces what the ring was hiding), so parity vs
    the ringed path is the SP epsilon bound, not bitwise.
    """
    if tensor_parallel_size <= 1:
        return fused_ffn(x, w1, b1, w2, b2)
    from apex_tpu.transformer import tensor_parallel as tp

    if sequence_parallel:
        x = tp.gather_from_sequence_parallel_region(x, axis_name, seq_dim)
    else:
        x = tp.copy_to_tensor_model_parallel_region(x, axis_name)
    y = fused_ffn(x, w1, b1, w2, None)
    if sequence_parallel:
        y = tp.reduce_scatter_to_sequence_parallel_region(y, axis_name,
                                                          seq_dim)
        b2 = tp.copy_to_tensor_model_parallel_region(b2, axis_name)
    else:
        y = tp.reduce_from_tensor_model_parallel_region(y, axis_name)
    return y + b2.astype(y.dtype)
