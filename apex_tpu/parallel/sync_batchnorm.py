"""SyncBatchNorm — TPU rebuild of ``apex/parallel/optimized_sync_batchnorm.py``
(+ ``csrc/syncbn.cpp``/``csrc/welford.cu`` and the pure-python variant).

Apex computes per-GPU Welford stats with a CUDA kernel, all-gathers
(mean, var, count) across the process group, combines, then normalizes.
The TPU translation: local sums in f32 + one ``psum`` of
``(sum, sum_sq, count)`` over the data-parallel mesh axis inside the jitted
step — mathematically the same chunk-parallel Welford combine, expressed as
a collective the compiler schedules.  Outside ``shard_map``/``pmap`` (plain
GSPMD jit over a batch-sharded array) the plain batch mean IS the global
mean, so the module also works with no axis at all.

``channel_last=True`` treats the trailing axis as channels (apex NHWC);
default layout is NCHW like torch.
"""

from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

_f32 = jnp.float32


class BatchNormState(NamedTuple):
    """Running stats (the mutable part of torch BN modules)."""

    running_mean: jax.Array
    running_var: jax.Array
    num_batches_tracked: jax.Array


def _axis_reduce(total, axis_name):
    if axis_name is not None:
        return jax.lax.psum(total, axis_name)
    return total


def sync_batch_norm(x, weight, bias, state: BatchNormState, *,
                    training: bool, momentum: float = 0.1, eps: float = 1e-5,
                    axis_name: Optional[str] = None,
                    channel_last: bool = False,
                    update_running_stats: bool = True):
    """Functional SyncBatchNorm.  Returns ``(y, new_state)``.

    In training mode, batch stats combine across ``axis_name`` (the
    ``process_group`` analogue); running stats update with the *unbiased*
    variance like torch/apex.  ``update_running_stats=False`` still
    normalizes with batch statistics in training mode (torch semantics for
    ``track_running_stats=False``) but leaves ``state`` untouched.
    """
    c_axis = x.ndim - 1 if channel_last else 1
    red_axes = tuple(i for i in range(x.ndim) if i != c_axis)
    shape_bc = [1] * x.ndim
    shape_bc[c_axis] = x.shape[c_axis]

    xf = x.astype(_f32)
    if training:
        count = 1.0
        for i in red_axes:
            count *= x.shape[i]
        local_sum = jnp.sum(xf, axis=red_axes)
        local_sqsum = jnp.sum(xf * xf, axis=red_axes)
        total = _axis_reduce(jnp.stack([local_sum, local_sqsum]), axis_name)
        if axis_name is not None:
            count = count * jax.lax.axis_size(axis_name)
        mean = total[0] / count
        var = total[1] / count - mean * mean          # biased (normalization)
        unbiased = var * (count / max(count - 1.0, 1.0))
        if update_running_stats:
            new_state = BatchNormState(
                (1 - momentum) * state.running_mean + momentum * mean,
                (1 - momentum) * state.running_var + momentum * unbiased,
                state.num_batches_tracked + 1)
        else:
            new_state = state
    else:
        mean, var = state.running_mean, state.running_var
        new_state = state

    rstd = jax.lax.rsqrt(var + eps)
    y = (xf - mean.reshape(shape_bc)) * rstd.reshape(shape_bc)
    if weight is not None:
        y = y * weight.astype(_f32).reshape(shape_bc)
    if bias is not None:
        y = y + bias.astype(_f32).reshape(shape_bc)
    return y.astype(x.dtype), new_state


class SyncBatchNorm:
    """Module form (apex ``SyncBatchNorm(num_features, ..., process_group,
    channel_last)``).  ``process_group`` maps to a mesh axis name."""

    def __init__(self, num_features, eps=1e-5, momentum=0.1, affine=True,
                 track_running_stats=True, process_group: str | None = None,
                 channel_last: bool = False, fuse_relu: bool = False):
        self.num_features = int(num_features)
        self.eps = float(eps)
        self.momentum = float(momentum)
        self.affine = bool(affine)
        self.track_running_stats = bool(track_running_stats)
        self.axis_name = process_group
        self.channel_last = bool(channel_last)
        self.fuse_relu = bool(fuse_relu)

    def init_params(self):
        if not self.affine:
            return {}
        return {"weight": jnp.ones((self.num_features,), _f32),
                "bias": jnp.zeros((self.num_features,), _f32)}

    def init_state(self) -> BatchNormState:
        return BatchNormState(jnp.zeros((self.num_features,), _f32),
                              jnp.ones((self.num_features,), _f32),
                              jnp.zeros((), jnp.int32))

    def __call__(self, params, state, x, training: bool = True):
        # torch semantics: with track_running_stats=False there are no
        # running stats to fall back on, so batch statistics are used in
        # BOTH train and eval mode (and never written back).
        y, new_state = sync_batch_norm(
            x, params.get("weight") if self.affine else None,
            params.get("bias") if self.affine else None,
            state, training=training or not self.track_running_stats,
            momentum=self.momentum, eps=self.eps, axis_name=self.axis_name,
            channel_last=self.channel_last,
            update_running_stats=self.track_running_stats)
        if self.fuse_relu:
            y = jax.nn.relu(y)
        return y, new_state

    apply = __call__


def convert_syncbn_model(module, process_group: str | None = None,
                         channel_last: bool = False):
    """apex ``convert_syncbn_model``: rewrite BN layers to SyncBatchNorm.

    Operates on this package's module objects: any attribute or nested
    element that is a plain ``SyncBatchNorm``-shaped BN config gets its
    ``axis_name`` set.  For flax users, prefer constructing
    ``SyncBatchNorm`` directly; this helper exists for recipe parity.
    """
    if isinstance(module, SyncBatchNorm):
        module.axis_name = process_group
        module.channel_last = channel_last
        return module
    for name, child in vars(module).items():
        if name.startswith("_"):
            continue
        if isinstance(child, SyncBatchNorm):
            child.axis_name = process_group
            child.channel_last = channel_last
    return module
