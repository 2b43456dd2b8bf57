"""Vocab-parallel cross entropy — TPU rebuild of
``apex/transformer/tensor_parallel/cross_entropy.py``.

Computes softmax cross-entropy over logits whose vocab (last) dim is sharded
across the tensor axis WITHOUT gathering them: max and sum-exp reduce with
``pmax``/``psum``, the target logit is picked locally (masked where the
label falls outside this shard's vocab range) and summed.  The backward is
the analytic ``softmax - onehot`` on the local shard — no collective needed,
exactly apex's ``_VocabParallelCrossEntropy``.  Label smoothing matches the
apex formula.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from apex_tpu.transformer.parallel_state import TENSOR_AXIS

_f32 = jnp.float32


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def vocab_parallel_cross_entropy(vocab_parallel_logits, target,
                                 label_smoothing: float = 0.0,
                                 axis_name: str = TENSOR_AXIS):
    """Per-token loss for logits ``(..., vocab/t)`` and int targets
    ``(...)`` (global vocab ids).  Use inside ``shard_map`` with the vocab
    dim sharded over ``axis_name``; pass ``axis_name=None`` for the serial
    reference."""
    loss, _ = _vp_xent_fwd(vocab_parallel_logits, target, label_smoothing,
                           axis_name)
    return loss


def _vary(x, axis_name):
    if axis_name is None:
        return x
    from apex_tpu.utils.collectives import ensure_varying
    return ensure_varying(x, axis_name)


def _vp_xent_fwd(logits, target, label_smoothing, axis_name):
    x = _vary(logits.astype(_f32), axis_name)
    partition_vocab = x.shape[-1]
    if axis_name is not None:
        rank = jax.lax.axis_index(axis_name)
        world = jax.lax.axis_size(axis_name)
        local_max = jnp.max(x, axis=-1)
        gmax = jax.lax.pmax(local_max, axis_name)
    else:
        rank, world = 0, 1
        gmax = jnp.max(x, axis=-1)
    x = x - gmax[..., None]
    exp_x = jnp.exp(x)
    local_sum = jnp.sum(exp_x, axis=-1)
    sum_exp = (jax.lax.psum(local_sum, axis_name)
               if axis_name is not None else local_sum)

    start = rank * partition_vocab
    local_t = target - start
    in_range = (local_t >= 0) & (local_t < partition_vocab)
    safe_t = jnp.where(in_range, local_t, 0)
    picked = jnp.take_along_axis(x, safe_t[..., None], axis=-1)[..., 0]
    picked = jnp.where(in_range, picked, 0.0)
    if axis_name is not None:
        picked = jax.lax.psum(picked, axis_name)

    log_z = jnp.log(sum_exp)
    loss = log_z - picked
    if label_smoothing > 0.0:
        # apex scales the mix: s_adj = s * V/(V-1), then
        # loss = (1-s_adj)*nll + s_adj * mean_i(log_z - logit_i)
        # INTENTIONAL DEVIATION from apex/Megatron for TP>1: the reference
        # forward averages logits over the LOCAL vocab shard only
        # (inconsistent with its own backward, which smooths over the full
        # vocab); here the mean is over the GLOBAL vocab (psum of shard
        # sums / full V), making fwd and bwd self-consistent.  Loss values
        # therefore differ from the reference when tp>1 and smoothing>0.
        assert 1.0 > label_smoothing > 0.0, label_smoothing
        vocab = partition_vocab * world if axis_name is not None else \
            partition_vocab
        s_adj = label_smoothing * vocab / (vocab - 1)
        local_logit_sum = jnp.sum(x, axis=-1)
        logit_sum = (jax.lax.psum(local_logit_sum, axis_name)
                     if axis_name is not None else local_logit_sum)
        smooth = log_z - logit_sum / vocab
        loss = (1.0 - s_adj) * loss + s_adj * smooth
    residuals = (exp_x, sum_exp, in_range, safe_t,
                 jnp.zeros((0,), logits.dtype))
    return loss, residuals


def _vp_xent_bwd(label_smoothing, axis_name, res, dloss):
    exp_x, sum_exp, in_range, safe_t, carrier = res
    softmax = exp_x / sum_exp[..., None]
    vocab_local = softmax.shape[-1]
    onehot = jax.nn.one_hot(safe_t, vocab_local, dtype=_f32)
    onehot = onehot * in_range[..., None]
    if label_smoothing > 0.0:
        world = (jax.lax.axis_size(axis_name)
                 if axis_name is not None else 1)
        vocab = vocab_local * world
        s_adj = label_smoothing * vocab / (vocab - 1)
        grad = softmax - (1.0 - s_adj) * onehot - s_adj / vocab
    else:
        grad = softmax - onehot
    grad = grad * dloss.astype(_f32)[..., None]
    return grad.astype(carrier.dtype), None


vocab_parallel_cross_entropy.defvjp(_vp_xent_fwd, _vp_xent_bwd)
