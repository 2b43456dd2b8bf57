"""Scan-compiled pipeline-parallel engine on ppermute rings.

This is the heart of the rebuilt ``pipeline_parallel`` subsystem: one
``lax.scan`` whose body is a *uniform* SPMD tick — every pipeline device
runs the same program every tick, executing (at most) one forward job and
one backward job.  That uniform tick is exactly the 1F1B steady state; the
warmup and cooldown phases fall out as ticks whose forward or backward job
is masked invalid.  Interleaved virtual stages are the same scan with each
device owning ``n_virtual`` model chunks and the ring wrap carrying a
microbatch from chunk ``c`` on the last device to chunk ``c+1`` on the
first.

Why hand-rolled backward instead of ``jax.grad`` over the scan: on the jax
0.4.x era this package supports, differentiating collectives inside
``shard_map`` hits the psum-transpose bug (cotangents multiplied by axis
size) and replicated-operand grads come back as per-device partials.  The
engine therefore never differentiates through a collective: activations hop
forward and cotangents hop backward via ``ppermute`` as *plain data*, and
each backward job recomputes its stage forward under a local ``jax.vjp``
(activation recompute; only the stage-boundary inputs are saved, in an
O(n_virtual · n_stages) ring buffer).  All cross-device reductions of the
results are forward-mode ``psum`` of one-nonzero-plus-zeros, which is
bitwise-exact.

Schedule arithmetic (S = pipe axis size, v = virtual chunks per device,
L = v·S logical stages, M microbatches, logical stage ℓ = c·S + s):

* forward job of device ``s`` at tick ``t``:  ``z = t − s``; valid iff
  ``0 ≤ z < M·v``; decode ``q = z // (vS)``, ``c = (z % (vS)) // S``,
  ``i = z % S``; the job runs microbatch ``m = q·S + i`` through chunk
  ``c``.
* backward job at tick ``t``:  ``z = t + s + 2 − (v+1)·S``; same decode
  except the chunk runs in reverse: ``c = v − 1 − (z % (vS)) // S``.
* total ticks ``T = M·v + (v+1)·S − 2`` (for v=1: ``M + 2S − 2``).

Both rings advance one hop per tick, so a message sent at tick ``t``
arrives exactly when the receiving job needs it at ``t+1``; the wrap hop
(device S−1 → 0 forward, 0 → S−1 backward) carries the virtual-chunk
advance.  The backward job for microbatch ``m`` at logical stage ℓ runs
``Δ = 2S(v−c) − 2s − 2`` ticks after its forward job, bounded by 2L−2, so a
ring buffer of ``B = 2L−1`` saved stage inputs suffices (Δ = 0 on the last
logical stage: the buffer is written before it is read within the tick).

Grounding: 1F1B/interleaved schedules follow Megatron/apex
(``forward_backward_pipelining_{without,with}_interleaving``); the
single-executable collective-permute formulation follows the GSPMD
(arxiv 2105.04663) and MPMD-pipeline (arxiv 2412.14374) shifted-buffer
pattern.
"""

from __future__ import annotations

from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp

from apex_tpu.transformer.parallel_state import PIPELINE_AXIS
from apex_tpu.transformer.pipeline_parallel import p2p_communication as p2p

__all__ = [
    "JobInfo", "pipeline_schedule_step", "pipeline_forward",
    "pipeline_value_and_grad", "schedule_ticks", "bubble_fraction",
]


class JobInfo(NamedTuple):
    """Identity of the job a stage function is running (traced scalars).

    ``stage`` is the *logical* stage index ``chunk·S + device`` in
    ``[0, n_virtual·S)`` — what a layer-offset or dropout-seed computation
    wants.  ``microbatch`` indexes the leading axis of the engine's
    ``x0``/``targets``.
    """
    microbatch: Any
    stage: Any
    chunk: Any


def schedule_ticks(n_microbatches: int, n_stages: int,
                   n_virtual: int = 1) -> int:
    """Scan length of the schedule: ``M·v + (v+1)·S − 2`` uniform ticks."""
    return n_microbatches * n_virtual + (n_virtual + 1) * n_stages - 2


def bubble_fraction(n_microbatches: int, n_stages: int,
                    n_virtual: int = 1) -> float:
    """Idle fraction of the schedule in tick units: each device has
    ``M·v`` forward and ``M·v`` backward job slots over ``T`` ticks of two
    slots each, so the bubble is ``1 − M·v/T``.  Interleaving shrinks the
    fill/drain ramps from ``2S`` to ``S·(1+1/v)`` stage-times."""
    t = schedule_ticks(n_microbatches, n_stages, n_virtual)
    return 1.0 - (n_microbatches * n_virtual) / t


def _tmap(f, *trees):
    return jax.tree_util.tree_map(f, *trees)


def _dyn_index(tree, i):
    return _tmap(
        lambda a: jax.lax.dynamic_index_in_dim(a, i, 0, keepdims=False),
        tree)


def _take_chunk(tree, c, n_virtual):
    if n_virtual == 1:
        return tree
    return _dyn_index(tree, c)


def _static_axis_size(axis_name):
    n = jax.lax.axis_size(axis_name)
    try:
        return int(n)
    except (TypeError, jax.errors.TracerIntegerConversionError) as e:
        raise ValueError(
            f"pipeline axis {axis_name!r} size is not statically known "
            "inside this trace; the scan-based schedule needs a concrete "
            "mesh axis (run under shard_map over the pipe axis)") from e


def _microbatch_count(x0):
    leaves = jax.tree_util.tree_leaves(x0)
    if not leaves:
        raise ValueError("x0 has no array leaves")
    return int(leaves[0].shape[0])


def pipeline_schedule_step(stage_fn: Callable, last_fn: Callable,
                           stage_params, last_params, x0, targets, *,
                           axis_name: str = PIPELINE_AXIS,
                           n_virtual: int = 1):
    """Run one full pipeline training step (loss + grads) as one scan.

    Args:
      stage_fn: ``stage_fn(chunk_params, x, info: JobInfo) -> y`` — applies
        one model chunk.  ``x``/``y`` must share pytree structure, shapes
        and dtypes (the ring carries them); a ``(hidden, aux)`` tuple works
        (MoE aux-loss cotangents ride the backward ring like any leaf).
      last_fn: ``last_fn(last_params, y, target, info) -> scalar`` —
        per-microbatch loss from the final chunk's output (e.g. final LN +
        LM head + CE).  Called every tick on every device for SPMD
        uniformity; only the last logical stage's value is kept.
      stage_params: this device's chunk parameters.  With ``n_virtual > 1``
        every leaf carries a leading ``(n_virtual, ...)`` axis (chunk ``c``
        on device ``s`` is logical stage ``c·S + s``).
      last_params: parameters of ``last_fn`` (replicated over the pipe
        axis; their gradient is psum-reduced).
      x0: first-stage inputs, leaves ``(M, ...)`` — one slice per
        microbatch.  Replicated over the pipe axis.
      targets: per-microbatch targets, leaves ``(M, ...)``.

    Returns:
      ``(loss, stage_grads, last_grads, dx0)`` where ``loss`` is the mean
      per-microbatch loss (replicated), ``stage_grads`` matches
      ``stage_params`` (device-local), ``last_grads`` matches
      ``last_params`` (replicated), and ``dx0`` is the cotangent of ``x0``
      (replicated) for chaining into an embedding pullback.

    The accumulation order (ascending microbatch, loss cotangent seeded at
    ``1/M``) is identical at every ``(S, v)`` including S=1, so schedules
    match each other — and the no-pipelining reference — bitwise in f32.
    """
    S = _static_axis_size(axis_name)
    v = int(n_virtual)
    if v < 1:
        raise ValueError(f"n_virtual must be >= 1, got {n_virtual}")
    M = _microbatch_count(x0)
    if v > 1 and M % S != 0:
        raise ValueError(
            f"interleaved schedule needs n_microbatches % n_stages == 0, "
            f"got M={M}, S={S}")
    L = v * S
    B = 2 * L - 1
    T = schedule_ticks(M, S, v)
    s = jax.lax.axis_index(axis_name)
    inv_m = jnp.float32(1.0 / M)

    x_tmpl = _tmap(lambda a: jnp.zeros(a.shape[1:], a.dtype), x0)
    carry0 = (
        x_tmpl,                                             # fwd ring msg
        x_tmpl,                                             # bwd ring msg
        _tmap(lambda a: jnp.zeros((B,) + a.shape[1:], a.dtype), x0),
        _tmap(jnp.zeros_like, stage_params),                # stage grads
        _tmap(jnp.zeros_like, last_params),                 # last-fn grads
        _tmap(jnp.zeros_like, x0),                          # dx0 scatter
        jnp.float32(0.0),                                   # loss sum
    )

    def tick(carry, t):
        fwd_msg, bwd_msg, xsave, sgrad, lgrad, dx0_acc, loss_acc = carry

        # ---- forward job indices -------------------------------------
        zf = t - s
        fwd_valid = (zf >= 0) & (zf < M * v)
        zfc = jnp.clip(zf, 0, M * v - 1)
        cf = (zfc % (v * S)) // S
        mf = (zfc // (v * S)) * S + zfc % S
        stage_f = cf * S + s

        # ---- forward job ---------------------------------------------
        inject = (s == 0) & (cf == 0)
        x_f = _tmap(lambda xi, msg: jnp.where(inject, xi, msg),
                    _dyn_index(x0, mf), fwd_msg)
        y_f = stage_fn(_take_chunk(stage_params, cf, v), x_f,
                       JobInfo(mf, stage_f, cf))
        slot_w = jnp.mod(t, B)
        xsave = _tmap(
            lambda buf, xx: jax.lax.dynamic_update_index_in_dim(
                buf, xx, slot_w, 0),
            xsave, x_f)

        # ---- backward job indices ------------------------------------
        zb = t + s + 2 - (v + 1) * S
        bwd_valid = (zb >= 0) & (zb < M * v)
        zbc = jnp.clip(zb, 0, M * v - 1)
        cb = (v - 1) - (zbc % (v * S)) // S
        mb = (zbc // (v * S)) * S + zbc % S
        stage_b = cb * S + s
        is_last = stage_b == (L - 1)

        # ---- backward job: recompute forward under a local vjp -------
        delta = 2 * S * (v - cb) - 2 * s - 2
        x_b = _dyn_index(xsave, jnp.mod(t - delta, B))
        tgt_b = _dyn_index(targets, mb)
        info_b = JobInfo(mb, stage_b, cb)

        def job(cp, lp, xx):
            y = stage_fn(cp, xx, info_b)
            return y, last_fn(lp, y, tgt_b, info_b)

        (y_b, lm), pull = jax.vjp(
            job, _take_chunk(stage_params, cb, v), last_params, x_b)
        # Joint cotangent: interior stages pull the ring message through
        # the chunk (the loss path gets a structural-zero seed); the last
        # logical stage seeds the loss at 1/M and zeros the ring message.
        dy = _tmap(lambda m, yy: jnp.where(is_last, jnp.zeros_like(yy), m),
                   bwd_msg, y_b)
        dlm = jnp.where(is_last, inv_m, jnp.float32(0.0))
        dcp, dlp, dx = pull((dy, dlm))

        # ---- masked accumulation -------------------------------------
        def acc_chunk(a, g):
            g = jnp.where(bwd_valid, g, jnp.zeros_like(g))
            return a + g if v == 1 else a.at[cb].add(g)
        sgrad = _tmap(acc_chunk, sgrad, dcp)
        lvalid = bwd_valid & is_last
        lgrad = _tmap(lambda a, g: a + jnp.where(lvalid, g,
                                                 jnp.zeros_like(g)),
                      lgrad, dlp)
        loss_acc = loss_acc + jnp.where(lvalid, lm, jnp.float32(0.0))
        first_b = bwd_valid & (s == 0) & (cb == 0)
        dx0_acc = _tmap(
            lambda a, g: a.at[mb].add(jnp.where(first_b, g,
                                                jnp.zeros_like(g))),
            dx0_acc, dx)

        # ---- ring hops (wrap carries the virtual-chunk advance) ------
        fwd_msg = p2p.send_forward_recv_forward(
            y_f, axis_name=axis_name, wrap=True)
        bwd_msg = p2p.send_backward_recv_backward(
            dx, axis_name=axis_name, wrap=True)
        return (fwd_msg, bwd_msg, xsave, sgrad, lgrad, dx0_acc,
                loss_acc), None

    carry, _ = jax.lax.scan(tick, carry0, jnp.arange(T))
    _, _, _, sgrad, lgrad, dx0_acc, loss_acc = carry

    # Forward-mode reductions of one-nonzero-plus-zeros: bitwise-exact and
    # never differentiated through.
    loss = jax.lax.psum(loss_acc, axis_name) * inv_m
    last_grads = jax.lax.psum(lgrad, axis_name)
    dx0 = jax.lax.psum(dx0_acc, axis_name)
    return loss, sgrad, last_grads, dx0


def pipeline_forward(stage_fn: Callable, stage_params, x0, *,
                     axis_name: str = PIPELINE_AXIS, n_virtual: int = 1):
    """Forward-only pipeline: run every microbatch through all logical
    stages and return the last stage's outputs stacked ``(M, ...)``,
    replicated over the pipe axis.  Same job arithmetic as
    :func:`pipeline_schedule_step` with the backward half dropped
    (``T = M·v + S − 1`` ticks)."""
    S = _static_axis_size(axis_name)
    v = int(n_virtual)
    M = _microbatch_count(x0)
    if v > 1 and M % S != 0:
        raise ValueError(
            f"interleaved schedule needs n_microbatches % n_stages == 0, "
            f"got M={M}, S={S}")
    T = M * v + S - 1
    s = jax.lax.axis_index(axis_name)

    x_tmpl = _tmap(lambda a: jnp.zeros(a.shape[1:], a.dtype), x0)
    outs0 = _tmap(jnp.zeros_like, x0)

    def tick(carry, t):
        fwd_msg, outs = carry
        zf = t - s
        fwd_valid = (zf >= 0) & (zf < M * v)
        zfc = jnp.clip(zf, 0, M * v - 1)
        cf = (zfc % (v * S)) // S
        mf = (zfc // (v * S)) * S + zfc % S
        inject = (s == 0) & (cf == 0)
        x_f = _tmap(lambda xi, msg: jnp.where(inject, xi, msg),
                    _dyn_index(x0, mf), fwd_msg)
        y_f = stage_fn(_take_chunk(stage_params, cf, v), x_f,
                       JobInfo(mf, cf * S + s, cf))
        done = fwd_valid & (s == S - 1) & (cf == v - 1)
        outs = _tmap(
            lambda a, yy: a.at[mf].add(jnp.where(done, yy,
                                                 jnp.zeros_like(yy))),
            outs, y_f)
        fwd_msg = p2p.send_forward_recv_forward(
            y_f, axis_name=axis_name, wrap=True)
        return (fwd_msg, outs), None

    (_, outs), _ = jax.lax.scan(tick, (x_tmpl, outs0), jnp.arange(T))
    return jax.lax.psum(outs, axis_name)


def pipeline_value_and_grad(stage_fn: Callable, loss_fn: Callable, params,
                            microbatches, targets, *,
                            axis_name: str = PIPELINE_AXIS,
                            n_virtual: int = 1):
    """Convenience wrapper for parameter-free losses: adapts plain
    ``stage_fn(params, x)`` / ``loss_fn(y, target)`` callables onto
    :func:`pipeline_schedule_step` and returns ``(loss, stage_grads)``."""
    loss, sgrad, _, _ = pipeline_schedule_step(
        lambda p, x, info: stage_fn(p, x),
        lambda lp, y, tgt, info: loss_fn(y, tgt),
        params, (), microbatches, targets,
        axis_name=axis_name, n_virtual=n_virtual)
    return loss, sgrad
