"""Stage-to-stage transfers — TPU rebuild of
``apex/transformer/pipeline_parallel/p2p_communication.py``.

Apex moves activations between pipeline ranks with NCCL
``batch_isend_irecv`` (plus a shape handshake for variable shapes).  On TPU
a stage hop is ``lax.ppermute`` over the ``pipe`` mesh axis — compiled to a
collective-permute riding ICI neighbors — and shapes are static under jit so
there is no handshake.  These helpers are the explicit building blocks; the
scan-based engine in ``ring.py`` is what the schedules actually use.

Every hop is a ``custom_vjp`` primitive: the transpose of a forward
activation hop is the same masked permute run in the opposite direction, so
cotangents ride a counter-rotating ring instead of whatever jax's ppermute
transpose rule produces (which is version-dependent and, on the jax
0.4.x-era psum-transpose path, wrong inside ``shard_map``).  The engine
never differentiates *through* these hops — it moves cotangents as plain
data — but user code composing the half-ops under ``jax.grad`` gets correct
rings for free.

All functions must run inside ``shard_map`` with the pipe axis in scope.
With ``wrap=False`` (default) the boundary stages receive zeros (a ring
permute wraps; the extra wrap value is masked to match apex's "first stage
receives nothing"); ``wrap=True`` keeps the wrap value, which the
interleaved schedule uses to hand a microbatch to the next virtual chunk.
All helpers are pytree-aware.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from apex_tpu.transformer.parallel_state import PIPELINE_AXIS
from apex_tpu.utils.collectives import ensure_varying


def _ring_perm(n):
    """Forward ring: stage ``i`` sends to ``i + 1`` (mod n)."""
    return [(i, (i + 1) % n) for i in range(n)]


def _shift_impl(x, axis_name, forward: bool, wrap: bool):
    n = jax.lax.axis_size(axis_name)
    perm = _ring_perm(n) if forward else [(d, s) for s, d in _ring_perm(n)]
    x = ensure_varying(x, axis_name)
    out = jax.tree_util.tree_map(
        lambda v: jax.lax.ppermute(v, axis_name, perm), x)
    if not wrap:
        s = jax.lax.axis_index(axis_name)
        edge = (s == 0) if forward else (s == n - 1)
        out = jax.tree_util.tree_map(
            lambda v: jnp.where(edge, jnp.zeros_like(v), v), out)
    return out


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3))
def _shift(x, axis_name, forward: bool, wrap: bool):
    return _shift_impl(x, axis_name, forward, wrap)


def _shift_fwd(x, axis_name, forward, wrap):
    return _shift_impl(x, axis_name, forward, wrap), None


def _shift_bwd(axis_name, forward, wrap, _res, ct):
    # Transpose of (edge-mask ∘ permute) is (permute⁻¹ ∘ edge-mask), which
    # equals the opposite-direction masked shift: the value masked at the
    # receive edge going one way is the value masked at the send edge coming
    # back.  With wrap=True the permute is a bijection and the transpose is
    # exactly the inverse permute.
    return (_shift_impl(ct, axis_name, not forward, wrap),)


_shift.defvjp(_shift_fwd, _shift_bwd)


def send_forward_recv_forward(output_tensor, *,
                              axis_name: str = PIPELINE_AXIS,
                              wrap: bool = False):
    """Send to the next stage, receive from the previous (one hop).  In an
    SPMD program send and recv are the same permute; this single primitive
    backs apex's ``send_forward``/``recv_forward`` pair."""
    return _shift(output_tensor, axis_name, True, wrap)


def send_backward_recv_backward(input_tensor_grad, *,
                                axis_name: str = PIPELINE_AXIS,
                                wrap: bool = False):
    """Gradient hop toward earlier stages (apex ``send_backward`` /
    ``recv_backward``)."""
    return _shift(input_tensor_grad, axis_name, False, wrap)


# apex's four half-ops map onto the two fused permutes above; aliases keep
# recipe code readable.
def send_forward(output_tensor, **kw):
    return send_forward_recv_forward(output_tensor, **kw)


def recv_forward(tensor_like, **kw):
    return send_forward_recv_forward(tensor_like, **kw)


def send_backward(input_tensor_grad, **kw):
    return send_backward_recv_backward(input_tensor_grad, **kw)


def recv_backward(tensor_like, **kw):
    return send_backward_recv_backward(tensor_like, **kw)


def send_forward_recv_backward(output_tensor, grad_like, *,
                               axis_name: str = PIPELINE_AXIS):
    """1F1B steady-state fused exchange: activations go forward while
    gradients come backward (two counter-rotating permutes XLA can
    overlap)."""
    return (send_forward_recv_forward(output_tensor, axis_name=axis_name),
            send_backward_recv_backward(grad_like, axis_name=axis_name))


def send_backward_recv_forward(input_tensor_grad, act_like, *,
                               axis_name: str = PIPELINE_AXIS):
    return (send_backward_recv_backward(input_tensor_grad,
                                        axis_name=axis_name),
            send_forward_recv_forward(act_like, axis_name=axis_name))
