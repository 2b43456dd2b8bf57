"""TP region mappings — TPU rebuild of
``apex/transformer/tensor_parallel/mappings.py``.

Each mapping is a forward/backward-paired collective over the ``model`` mesh
axis, for use inside ``shard_map`` (the explicit-collective expression of
Megatron TP).  Under pure GSPMD (sharding annotations) these calls are not
needed — the compiler inserts them — but the explicit forms are the
load-bearing semantics for the 1:1 apex surface and for tests.

| apex function                                   | fwd            | bwd            |
|-------------------------------------------------|----------------|----------------|
| ``copy_to_tensor_model_parallel_region``         | identity       | all-reduce     |
| ``reduce_from_tensor_model_parallel_region``     | all-reduce     | identity       |
| ``scatter_to_tensor_model_parallel_region``      | split (last)   | all-gather     |
| ``gather_from_tensor_model_parallel_region``     | all-gather     | split (last)   |
| ``scatter_to_sequence_parallel_region``          | split (seq)    | all-gather     |
| ``gather_from_sequence_parallel_region``         | all-gather     | reduce-scatter |
| ``reduce_scatter_to_sequence_parallel_region``   | reduce-scatter | all-gather     |

The sequence mappings take a ``seq_dim`` (default 0, the apex ``(s, b, h)``
layout; GPT/BERT activations are ``(b, s, h)`` and pass ``seq_dim=1``).

Latency-hiding forms: :func:`column_parallel_linear_overlap` and
:func:`row_parallel_linear_overlap` fuse the sequence-parallel collective
with its adjacent GEMM as a ``ppermute`` ring — the gather→GEMM (column)
and GEMM→reduce-scatter (row) pairs decompose into per-shard steps where
each ICI transfer runs concurrently with the previous shard's GEMM, and a
custom VJP applies the same decomposition to the backward
all-gather/reduce-scatter (with the weight-grad partials accumulated
chunkwise during the same ring, Megatron's
``linear_with_grad_accumulation_and_async_allreduce`` overlap).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from apex_tpu.transformer.parallel_state import TENSOR_AXIS


from apex_tpu.utils.collectives import ensure_varying as _vary


def _reduce(x, axis):
    return jax.lax.psum(_vary(x, axis), axis)


def _split_along_dim(x, dim, axis):
    n = jax.lax.axis_size(axis)
    r = jax.lax.axis_index(axis)
    size = x.shape[dim] // n
    return jax.lax.dynamic_slice_in_dim(x, r * size, size, axis=dim)


def _gather_along_dim(x, dim, axis):
    return jax.lax.all_gather(_vary(x, axis), axis, axis=dim, tiled=True)


def _reduce_scatter_along_dim(x, dim, axis):
    return jax.lax.psum_scatter(_vary(x, axis), axis, scatter_dimension=dim,
                                tiled=True)


def _mk(name, fwd_fn, bwd_fn):
    @functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
    def f(x, axis=TENSOR_AXIS):
        return fwd_fn(x, axis)

    def f_fwd(x, axis):
        return fwd_fn(x, axis), None

    def f_bwd(axis, _, g):
        return (bwd_fn(g, axis),)

    f.defvjp(f_fwd, f_bwd)
    f.__name__ = name
    f.__qualname__ = name
    return f


copy_to_tensor_model_parallel_region = _mk(
    "copy_to_tensor_model_parallel_region",
    lambda x, ax: _vary(x, ax),
    lambda g, ax: _reduce(g, ax))

reduce_from_tensor_model_parallel_region = _mk(
    "reduce_from_tensor_model_parallel_region",
    lambda x, ax: _reduce(x, ax),
    lambda g, ax: _vary(g, ax))

scatter_to_tensor_model_parallel_region = _mk(
    "scatter_to_tensor_model_parallel_region",
    lambda x, ax: _split_along_dim(_vary(x, ax), -1, ax),
    lambda g, ax: _gather_along_dim(g, -1, ax))

gather_from_tensor_model_parallel_region = _mk(
    "gather_from_tensor_model_parallel_region",
    lambda x, ax: _gather_along_dim(x, -1, ax),
    lambda g, ax: _split_along_dim(_vary(g, ax), -1, ax))

def _mk_seq(name, fwd_fn, bwd_fn):
    """Like :func:`_mk` but with a ``seq_dim`` knob (nondiff, like the
    axis name) selecting which dimension is sequence-sharded."""
    @functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2))
    def f(x, axis=TENSOR_AXIS, seq_dim=0):
        return fwd_fn(x, axis, seq_dim)

    def f_fwd(x, axis, seq_dim):
        return fwd_fn(x, axis, seq_dim), None

    def f_bwd(axis, seq_dim, _, g):
        return (bwd_fn(g, axis, seq_dim),)

    f.defvjp(f_fwd, f_bwd)
    f.__name__ = name
    f.__qualname__ = name
    return f


scatter_to_sequence_parallel_region = _mk_seq(
    "scatter_to_sequence_parallel_region",
    lambda x, ax, d: _split_along_dim(_vary(x, ax), d, ax),
    lambda g, ax, d: _gather_along_dim(g, d, ax))

gather_from_sequence_parallel_region = _mk_seq(
    "gather_from_sequence_parallel_region",
    lambda x, ax, d: _gather_along_dim(x, d, ax),
    lambda g, ax, d: _reduce_scatter_along_dim(g, d, ax))

reduce_scatter_to_sequence_parallel_region = _mk_seq(
    "reduce_scatter_to_sequence_parallel_region",
    lambda x, ax, d: _reduce_scatter_along_dim(x, d, ax),
    lambda g, ax, d: _gather_along_dim(g, d, ax))


# -- latency-hiding ring forms (sequence parallelism + overlap) --------------

def _ring_perm(t):
    """Send-left ring: device ``i`` sends to ``i-1`` (receives from
    ``i+1``), so after ``k`` hops device ``r`` holds shard ``(r+k) % t``."""
    return [(i, (i - 1) % t) for i in range(t)]


def _chunked_matmul(block, w_t, chunks, seq_dim):
    """``block @ w_t`` split into ``chunks`` independent sub-GEMMs along
    ``seq_dim``.  Numerically identical to the monolithic product (row
    partitioning does not reorder any output element's contraction); the
    split lets the latency-hiding scheduler start the next ring transfer
    after the first sub-GEMM instead of after the whole block."""
    if chunks <= 1:
        return block @ w_t
    pieces = jnp.split(block, chunks, axis=seq_dim)
    return jnp.concatenate([p @ w_t for p in pieces], axis=seq_dim)


def _ring_gather_matmul(x, w_t, axis, seq_dim, chunks):
    """``all_gather(x, seq_dim, tiled) @ w_t`` without materializing the
    gather: a send-left ``ppermute`` ring where each step's GEMM overlaps
    the next shard's ICI transfer.  ``x``: the local sequence shard
    ``(..., s/t, ..., in)``; returns ``(..., s, ..., out)``."""
    t = int(jax.lax.axis_size(axis))
    r = jax.lax.axis_index(axis)
    s_local = x.shape[seq_dim]
    out_shape = list(x.shape)
    out_shape[seq_dim] = s_local * t
    out_shape[-1] = w_t.shape[-1]
    y = jnp.zeros(out_shape, x.dtype)
    buf = _vary(x, axis)
    for k in range(t):
        blk = _chunked_matmul(buf, w_t, chunks, seq_dim)
        y = jax.lax.dynamic_update_slice_in_dim(
            y, blk.astype(y.dtype), ((r + k) % t) * s_local, axis=seq_dim)
        if k + 1 < t:
            buf = jax.lax.ppermute(buf, axis, _ring_perm(t))
    return y


def _ring_matmul_reduce_scatter(x, w_t, axis, seq_dim, chunks):
    """``psum_scatter(x @ w_t, seq_dim, tiled)`` without materializing the
    full product: at step ``k`` device ``d`` computes the partial product
    for target shard ``(d+k+1) % t``, adds the accumulator arriving from
    its ring neighbour, and forwards the sum — the partial GEMMs overlap
    the accumulator transfers, and after ``t`` steps each device holds its
    own fully-reduced shard.  ``x``: ``(..., s, ..., in)`` (full sequence,
    partial values); returns ``(..., s/t, ..., out)`` (reduced)."""
    t = int(jax.lax.axis_size(axis))
    r = jax.lax.axis_index(axis)
    s_local = x.shape[seq_dim] // t
    x = _vary(x, axis)
    acc = None
    for k in range(t):
        blk = jax.lax.dynamic_slice_in_dim(
            x, ((r + k + 1) % t) * s_local, s_local, axis=seq_dim)
        part = _chunked_matmul(blk, w_t, chunks, seq_dim)
        acc = part if acc is None else acc + part
        if k + 1 < t:
            acc = jax.lax.ppermute(acc, axis, _ring_perm(t))
    return acc


def _mk_overlap(name, fwd_fn, bwd_fn):
    @functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
    def f(x, weight, axis=TENSOR_AXIS, seq_dim=0, chunks=1):
        return fwd_fn(x, weight, axis, seq_dim, chunks)

    def f_fwd(x, weight, axis, seq_dim, chunks):
        return fwd_fn(x, weight, axis, seq_dim, chunks), (x, weight)

    def f_bwd(axis, seq_dim, chunks, res, g):
        return bwd_fn(res, g, axis, seq_dim, chunks)

    f.defvjp(f_fwd, f_bwd)
    f.__name__ = name
    f.__qualname__ = name
    return f


def _column_overlap_fwd(x, weight, axis, seq_dim, chunks):
    # gather(x, seq) @ Wᵀ as one ring; W (out/t, in), x the local seq shard
    return _ring_gather_matmul(x, weight.astype(x.dtype).T, axis, seq_dim,
                               chunks)


def _column_overlap_bwd(res, g, axis, seq_dim, chunks):
    # dx = reduce_scatter(g @ W, seq) and dW = Σₖ g[shard k]ᵀ x[shard k]
    # share one fused ring: the dx accumulator and the regathered x shard
    # travel together, and each step's two partial GEMMs overlap both
    # transfers (the backward half of apex's
    # linear_with_grad_accumulation_and_async_allreduce).
    x, weight = res
    w_c = weight.astype(g.dtype)
    t = int(jax.lax.axis_size(axis))
    r = jax.lax.axis_index(axis)
    s_local = x.shape[seq_dim]
    acc = None
    xbuf = _vary(x, axis)
    dw = jnp.zeros(weight.shape, jnp.float32)
    g = _vary(g, axis)
    for k in range(t):
        blk = jax.lax.dynamic_slice_in_dim(
            g, ((r + k + 1) % t) * s_local, s_local, axis=seq_dim)
        part = _chunked_matmul(blk, w_c, chunks, seq_dim)
        acc = part if acc is None else acc + part
        gk = jax.lax.dynamic_slice_in_dim(
            g, ((r + k) % t) * s_local, s_local, axis=seq_dim)
        dw = dw + jnp.einsum("...o,...h->oh", gk, xbuf,
                             preferred_element_type=jnp.float32)
        if k + 1 < t:
            acc = jax.lax.ppermute(acc, axis, _ring_perm(t))
            xbuf = jax.lax.ppermute(xbuf, axis, _ring_perm(t))
    return acc.astype(x.dtype), dw.astype(weight.dtype)


def _row_overlap_fwd(x, weight, axis, seq_dim, chunks):
    # (x @ Wᵀ) reduce-scattered over seq as one ring; W (out, in/t)
    return _ring_matmul_reduce_scatter(x, weight.astype(x.dtype).T, axis,
                                       seq_dim, chunks)


def _row_overlap_bwd(res, g, axis, seq_dim, chunks):
    # dx = gather(g, seq) @ W and dW = Σₖ g[shard k]ᵀ x[shard k] share the
    # g-regather ring: each arriving g shard feeds both partial GEMMs.
    x, weight = res
    w_c = weight.astype(g.dtype)
    t = int(jax.lax.axis_size(axis))
    r = jax.lax.axis_index(axis)
    s_local = g.shape[seq_dim]
    dx = jnp.zeros(x.shape, x.dtype)
    dw = jnp.zeros(weight.shape, jnp.float32)
    buf = _vary(g, axis)
    for k in range(t):
        j = (r + k) % t
        blk = _chunked_matmul(buf, w_c, chunks, seq_dim)
        dx = jax.lax.dynamic_update_slice_in_dim(
            dx, blk.astype(dx.dtype), j * s_local, axis=seq_dim)
        xk = jax.lax.dynamic_slice_in_dim(
            x, j * s_local, s_local, axis=seq_dim)
        dw = dw + jnp.einsum("...o,...h->oh", buf, xk,
                             preferred_element_type=jnp.float32)
        if k + 1 < t:
            buf = jax.lax.ppermute(buf, axis, _ring_perm(t))
    return dx, dw.astype(weight.dtype)


column_parallel_linear_overlap = _mk_overlap(
    "column_parallel_linear_overlap",
    _column_overlap_fwd, _column_overlap_bwd)

row_parallel_linear_overlap = _mk_overlap(
    "row_parallel_linear_overlap",
    _row_overlap_fwd, _row_overlap_bwd)
