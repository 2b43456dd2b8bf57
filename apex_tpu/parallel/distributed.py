"""Data parallelism — TPU rebuild of ``apex/parallel/distributed.py``.

Apex's ``DistributedDataParallel`` registers per-param backward hooks,
buckets gradients in reverse creation order (``message_size`` bytes per
bucket), flattens them (``apex_C.flatten``) and overlaps NCCL allreduce with
the remaining backward.  On TPU every one of those jobs belongs to the
compiler: gradients produced inside a jitted step with a sharded batch are
reduced by XLA-inserted collectives over ICI, and the XLA latency-hiding
scheduler overlaps them with compute.  What remains for the API is:

* expressing the data-parallel layout (mesh axis, batch sharding,
  replicated params) — :class:`DistributedDataParallel`;
* the explicit-collective path for ``shard_map`` training loops —
  :func:`allreduce_gradients` (= apex's bucketed allreduce, one ``psum``);
* the manual-trigger variant — :class:`Reducer`;
* ``delay_allreduce`` semantics → gradient-accumulation boundary control.

Knobs that only make sense for NCCL stream management
(``num_allreduce_streams``, ``allreduce_communicators``) are accepted and
ignored so apex recipes run unchanged.  ``message_size`` keeps apex's
meaning — a per-bucket BYTE cap — and is honored where buckets become
explicit collectives: the fused/distributed optimizers
(``FusedOptimizer(message_size=...)``,
:mod:`apex_tpu.parallel.distributed_optimizer`).
"""

from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from apex_tpu.utils import compressed_allreduce as _CA
from apex_tpu.utils.collectives import ensure_varying, psum_if_varying

DEFAULT_DATA_AXIS = "data"


def _has_axis(axis_name) -> bool:
    # Unbound axis names have raised a different exception in nearly
    # every JAX generation: classic NameError, KeyError from the
    # axis-env lookup, ValueError ("unbound axis name"), and TypeError
    # when the frame stack is empty.  Treat them all as "no such axis".
    try:
        jax.lax.axis_index(axis_name)
        return True
    except (NameError, KeyError, ValueError, TypeError):
        return False


def allreduce_gradients(grads, axis_name: str = DEFAULT_DATA_AXIS,
                        average: bool = True, strict: bool = False):
    """Reduce a gradient pytree across the data-parallel axis.

    Inside ``shard_map``/``pmap`` this is one fused ``psum`` over the whole
    pytree (XLA concatenates it into large transfers — the moral equivalent
    of apex's flatten+bucket).  ``average=True`` mirrors apex's
    ``gradient_average`` (divide by world size).

    Leaves that are already device-invariant over a ``shard_map`` axis are
    treated as already-summed gradients (JAX auto-psums grads of replicated
    params): the psum is skipped but averaging still divides by world size.
    This is a gradient-reduction helper, not a general replicated-value
    allreduce; ``strict=True`` raises on device-invariant leaves instead
    of passing them through.
    """
    # Grads computed without mark_local arrive device-INVARIANT — JAX 0.9
    # auto-psummed them during grad-of-replicated-params — and psumming
    # again would multiply by axis size.  Reduce only the varying leaves.
    reduced = psum_if_varying(grads, axis_name, strict=strict)
    if average:
        n = jax.lax.axis_size(axis_name)
        reduced = jax.tree_util.tree_map(lambda g: g / n, reduced)
    return reduced


class DistributedDataParallel:
    """API-compat DP wrapper (apex ``apex.parallel.DistributedDataParallel``).

    Functional usage over a named mesh::

        mesh = jax.make_mesh((n_devices,), ("data",))
        ddp = DistributedDataParallel(apply_fn, mesh=mesh)
        params = ddp.broadcast_params(params)       # replicate (init bcast)
        batch  = ddp.scatter(batch)                 # shard along batch dim
        # inside jit: grads come out correct — GSPMD inserts the reduction

    For explicit-collective loops (``shard_map``), use
    ``ddp.reduce(grads)`` where apex called the bucketed allreduce.

    ``delay_allreduce=True`` (apex: allreduce only at the end of backward)
    maps to gradient accumulation: accumulate with ``ddp.accumulate`` and
    reduce once via ``ddp.reduce`` at the boundary.
    """

    def __init__(self, module: Optional[Callable] = None, *,
                 mesh: Optional[Mesh] = None,
                 axis_name: str = DEFAULT_DATA_AXIS,
                 message_size: int = 10_000_000,
                 delay_allreduce: bool = False,
                 shared_param: bool = None,
                 allreduce_trigger_params=None,
                 retain_allreduce_buffers: bool = False,
                 allreduce_always_fp32: bool = False,
                 num_allreduce_streams: int = 1,
                 allreduce_communicators=None,
                 gradient_average: bool = True,
                 gradient_predivide_factor: float = 1.0,
                 allreduce_dtype=None,
                 prof: bool = False):
        del (shared_param, allreduce_trigger_params,
             retain_allreduce_buffers, num_allreduce_streams,
             allreduce_communicators, prof)  # NCCL-only knobs
        # message_size is apex's per-bucket BYTE cap.  DDP's own reduce is
        # one fused psum (XLA chunks it), so the cap matters only where
        # buckets become explicit collectives: kept here so recipes can
        # forward it to the distributed optimizers, which honor it
        # (FusedOptimizer(message_size=...), dtype-aware bytes).
        self.message_size = int(message_size)
        self.module = module
        self.mesh = mesh
        self.axis_name = axis_name
        self.delay_allreduce = bool(delay_allreduce)
        self.allreduce_always_fp32 = bool(allreduce_always_fp32)
        self.gradient_average = bool(gradient_average)
        self.gradient_predivide_factor = float(gradient_predivide_factor)
        self.allreduce_dtype = _CA.check_mode(allreduce_dtype)
        if self.allreduce_dtype is not None and mesh is None:
            raise ValueError(
                "allreduce_dtype={!r} needs the compressed collectives' "
                "static world size — pass mesh= so it can be read from "
                "mesh.shape[axis_name]".format(allreduce_dtype))

    # -- GSPMD path --------------------------------------------------------

    def broadcast_params(self, params):
        """Replicate params across the mesh (apex: init-time
        ``flat_dist_call`` broadcast from rank 0)."""
        if self.mesh is None:
            return params
        repl = NamedSharding(self.mesh, P())
        return jax.tree_util.tree_map(
            lambda x: jax.device_put(x, repl), params)

    def scatter(self, batch):
        """Shard a host batch along its leading dim over the data axis."""
        if self.mesh is None:
            return batch
        sh = NamedSharding(self.mesh, P(self.axis_name))
        return jax.tree_util.tree_map(lambda x: jax.device_put(x, sh), batch)

    def __call__(self, params, *args, **kwargs):
        if self.module is None:
            raise ValueError("DistributedDataParallel wrapped no module")
        return self.module(params, *args, **kwargs)

    # -- explicit-collective path (shard_map) ------------------------------

    def mark_local(self, params):
        """Mark replicated params device-varying inside ``shard_map``.

        JAX's varying-axes tracking makes ``jax.grad`` w.r.t. *replicated*
        inputs insert the cross-device ``psum`` automatically (the transpose
        of the implicit broadcast).  To reproduce apex's DDP staging — local
        gradients first, one explicit bucketed allreduce after — cast params
        to varying before ``jax.grad``, then call :meth:`reduce` yourself::

            def step(params, x, y):
                params = ddp.mark_local(params)
                grads = jax.grad(loss_fn)(params, x, y)   # local grads
                grads = ddp.reduce(grads)                 # ONE allreduce
                ...

        Skip both calls and grads come out already summed (not averaged) —
        the compiler-managed path.  Under ``check_vma=False`` nothing is
        tracked: grads are always local, this is a no-op and
        :meth:`reduce` always reduces.
        """
        return ensure_varying(params, self.axis_name)

    def _psum_grads(self, grads):
        # one fused psum, or the compressed all-reduce when
        # allreduce_dtype asks for bf16/int8 transport
        if self.allreduce_dtype is None:
            return psum_if_varying(grads, self.axis_name)
        world = int(self.mesh.shape[self.axis_name])
        return _CA.psum_tree_compressed(grads, self.axis_name, world,
                                        self.allreduce_dtype)

    @jax.named_scope("ddp.reduce")
    def reduce(self, grads):
        """The bucketed allreduce, as one collective (use inside
        ``shard_map``).  Transport follows the constructor's
        ``allreduce_dtype`` (None/'f32' exact, 'bf16'/'int8' compressed —
        see :mod:`apex_tpu.utils.compressed_allreduce`)."""
        if self.allreduce_always_fp32:
            grads = jax.tree_util.tree_map(
                lambda g: g.astype(jnp.float32), grads)
        factor = self.gradient_predivide_factor
        if factor != 1.0:
            # apex staging: divide by `factor` before the reduce
            # unconditionally (fp16 overflow safety), then by `world/factor`
            # after only when averaging — net sum/factor otherwise.
            grads = jax.tree_util.tree_map(lambda g: g / factor, grads)
            out = self._psum_grads(grads)
            if self.gradient_average:
                n = jax.lax.axis_size(self.axis_name)
                out = jax.tree_util.tree_map(lambda g: g * (factor / n), out)
            return out
        out = self._psum_grads(grads)
        if self.gradient_average:
            n = jax.lax.axis_size(self.axis_name)
            out = jax.tree_util.tree_map(lambda g: g / n, out)
        return out

    @staticmethod
    def accumulate(acc, grads, main_grad_dtype=None):
        """Microbatch gradient accumulation (``delay_allreduce`` interior).

        ``main_grad_dtype=jnp.float32`` reproduces apex's
        ``gradient_accumulation_fusion`` / ``main_grad`` contract: each
        microbatch's (possibly bf16) grads are accumulated into an fp32
        buffer (reference ``fused_weight_gradient_mlp_cuda`` accumulates
        the wgrad GEMM into ``weight.main_grad`` in fp32).
        """
        def cast(g):
            return g if main_grad_dtype is None else \
                g.astype(main_grad_dtype)
        if acc is None:
            return jax.tree_util.tree_map(cast, grads)
        return jax.tree_util.tree_map(
            lambda a, g: a + cast(g), acc, grads)


class Reducer:
    """Manual-trigger allreduce helper (apex ``apex.parallel.Reducer``):
    call ``reduce`` on whatever pytree you like, when you like."""

    def __init__(self, module_or_grads_list=None,
                 axis_name: str = DEFAULT_DATA_AXIS):
        self.axis_name = axis_name

    def reduce(self, tree, average: bool = True):
        return allreduce_gradients(tree, self.axis_name, average=average)
