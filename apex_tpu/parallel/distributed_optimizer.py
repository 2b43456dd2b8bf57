"""ZeRO-style distributed fused optimizers — TPU rebuild of
``apex/contrib/optimizers/distributed_fused_adam.py`` and
``distributed_fused_lamb.py`` (+ their ``multi_tensor_distopt_*`` CUDA
helpers).

The reference pipeline is: bucketed reduce-scatter of gradients during
backward, each rank runs the fused update on its shard of params +
optimizer state, then an all-gather of updated params.  The TPU-native
equivalent keeps exactly that dataflow but over the packed ``(rows, 128)``
multi-tensor buckets the fused-optimizer engine already uses:

* buckets are padded to ``block_rows * world_size`` rows so each device
  owns ``rows / world_size`` whole kernel blocks;
* grads: one reduce-scatter (tiled) per bucket over the data axis — the
  XLA collective riding ICI, optionally with compressed transport
  (``allreduce_dtype`` — see :mod:`apex_tpu.utils.compressed_allreduce`);
* the fused Pallas update runs on the local shard only (optimizer state —
  moments, master weights — exists ONLY as ``1/world_size`` shards, the
  ZeRO memory saving);
* params: one ``lax.all_gather`` (tiled) per bucket, always exact —
  quantizing the gather would write rounding error straight into the
  weights every step, so compression applies to gradients only (upstream
  ``DistributedFusedAdam`` likewise gathers params at full precision).

``init``/``step`` are written to run INSIDE ``shard_map`` over the data
axis, params replicated, grads device-varying (the per-device microbatch
gradients — no prior allreduce needed, the scatter IS the reduction).
The gathered params are replicated in value but conservatively
device-varying in JAX's vma typing, which requires running the region
with replication checking off (``check_vma=False``).

**Use :meth:`~_DistributedMixin.make_init` /
:meth:`~_DistributedMixin.make_step` rather than wrapping by hand**: they
own that unchecked shard_map region — validating the mesh axis, the
stacked-gradient shapes, and the param/grad tree agreement loudly at
trace time — and return jitted callables.  (Hand-wrapping remains
supported for embedding the step inside a larger shard_map region, e.g.
a full train step; ``tests/test_distributed_optimizers.py`` keeps the
manual recipe covered.)
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from apex_tpu.multi_tensor_apply import bucketing as B
from apex_tpu.optimizers.base import _f32
from apex_tpu.optimizers.fused_adam import FusedAdam
from apex_tpu.optimizers.fused_lamb import FusedLAMB
from apex_tpu.utils import compressed_allreduce as CA

__all__ = ["DistributedFusedAdam", "DistributedFusedLAMB"]


class _DistributedMixin:
    """Reduce-scatter → local fused update → all-gather over ``axis_name``."""

    # the packed (rows, 128) buckets ARE the ZeRO sharding unit, so the
    # distributed subclasses keep bucketed as their default even though
    # the single-chip base default is per-leaf
    _default_bucketed = True

    @staticmethod
    def _resolve_plan(plan, world_size, allreduce_dtype):
        """Fold a :class:`~apex_tpu.parallel.plan.ParallelPlan` into the
        ctor's per-knob args.  The per-knob kwargs stay the back-compat
        surface (silent without a plan); a non-default knob that
        CONFLICTS with the attached plan is superseded — the plan wins
        and a DeprecationWarning names it."""
        if plan is None:
            return world_size, allreduce_dtype
        import warnings
        kw = plan.optimizer_kwargs()
        if world_size != 1 and world_size != kw["world_size"]:
            warnings.warn(
                f"world_size={world_size} is superseded by the attached "
                f"ParallelPlan (zero_shard={kw['world_size']}); set "
                "zero_shard on the plan instead", DeprecationWarning,
                stacklevel=3)
        if allreduce_dtype is not None \
                and allreduce_dtype != kw["allreduce_dtype"]:
            warnings.warn(
                f"allreduce_dtype={allreduce_dtype!r} is superseded by "
                f"the attached ParallelPlan "
                f"({kw['allreduce_dtype']!r})", DeprecationWarning,
                stacklevel=3)
        return kw["world_size"], kw["allreduce_dtype"]

    def _dist_init(self, world_size, axis_name, average_grads,
                   allreduce_dtype=None, plan=None):
        world_size, allreduce_dtype = self._resolve_plan(
            plan, world_size, allreduce_dtype)
        self.plan = plan
        if world_size < 1:
            raise ValueError("world_size must be >= 1")
        self.world_size = int(world_size)
        self.axis_name = axis_name
        self.average_grads = bool(average_grads)
        self.allreduce_dtype = CA.check_mode(allreduce_dtype)
        # ZeRO sharding IS the packed layout: the reduce-scatter /
        # all-gather shard whole (rows, 128) blocks.  The per-leaf
        # layout has nothing to shard evenly — force bucketed.
        if not self.bucketed:
            raise ValueError(
                "distributed (ZeRO) optimizers require bucketed=True — "
                "the packed (rows, 128) buckets are what reduce-scatter/"
                "all-gather shard")

    def _meta_block_rows(self):
        return self.block_rows * self.world_size

    def _local_rows(self, info):
        return info.meta.nrows // self.world_size

    # -- state --------------------------------------------------------------

    def init(self, params):
        """Per-device state SHARDS (call inside ``shard_map``; out_specs
        ``state_specs()`` reassemble the global row-sharded buckets)."""
        layout = self._layout(params)
        leaves = jax.tree_util.tree_leaves(params)
        rank = jax.lax.axis_index(self.axis_name)
        buckets = {}
        for info in layout.buckets:
            rows = self._local_rows(info)
            st = {k: jnp.zeros((rows, 128), _f32)
                  for k in self._moment_keys()}
            if self.master_weights and info.meta.dtype != _f32:
                f32_meta = info.meta._replace(dtype=_f32)
                full = B.flatten_bucket([leaves[i] for i in info.indices],
                                        f32_meta)
                st["master"] = jax.lax.dynamic_slice(
                    full, (rank * rows, 0), (rows, 128))
            buckets[info.key] = st
        return {"step": jnp.zeros((), jnp.int32), "buckets": buckets}

    def _full_master_bucket(self, packed_master):
        # master buckets are ROW SHARDS here; all-gather to the full
        # rows before the base class unflattens (call master_params
        # inside shard_map, like step)
        return jax.lax.all_gather(packed_master, self.axis_name, axis=0,
                                  tiled=True)

    def state_specs(self, params):
        """PartitionSpec pytree for ``shard_map`` out/in_specs: moment and
        master buckets row-sharded over the data axis, step replicated —
        the per-device footprint IS ``1/world_size`` of the global state."""
        from jax.sharding import PartitionSpec as P
        layout = self._layout(params)
        buckets = {}
        for info in layout.buckets:
            keys = list(self._moment_keys())
            if self.master_weights and info.meta.dtype != _f32:
                keys.append("master")
            buckets[info.key] = {k: P(self.axis_name) for k in keys}
        return {"step": P(), "buckets": buckets}

    # -- step ---------------------------------------------------------------

    @jax.named_scope("optimizer")
    def step(self, grads, params, state, *, lr=None, grad_scale=1.0,
             noop_flag=None):
        ax = self.axis_name
        layout = self._layout(params)
        p_leaves, treedef = jax.tree_util.tree_flatten(params)
        g_leaves = jax.tree_util.tree_leaves(grads)
        rank = jax.lax.axis_index(ax)
        noop = (None if noop_flag is None
                else jnp.asarray(noop_flag).reshape(()))
        step_count = state["step"] + 1
        if noop is not None:
            step_count = state["step"] + (noop == 0).astype(jnp.int32)

        packed_local = {}
        for info in layout.buckets:
            gs = [g_leaves[i] for i in info.indices]
            g_meta = info.meta._replace(dtype=jnp.dtype(gs[0].dtype))
            g_full = B.flatten_bucket(gs, g_meta)
            # the reduce-scatter IS the DDP gradient reduction (ZeRO-2);
            # allreduce_dtype selects exact vs compressed transport
            g_loc = CA.reduce_scatter(g_full, ax, self.world_size,
                                      self.allreduce_dtype)
            if self.average_grads:
                g_loc = g_loc / self.world_size
            packed_local[info.key] = g_loc

        extras = self._pre_step_sharded(layout, packed_local, state, lr=lr,
                                        grad_scale=grad_scale)
        new_p_leaves = list(p_leaves)
        new_buckets = {}
        for info in layout.buckets:
            bucket_state = dict(state["buckets"][info.key])
            rows = self._local_rows(info)
            use_master = "master" in bucket_state
            if use_master:
                p_meta = info.meta._replace(dtype=_f32)
                p_loc = bucket_state["master"]
            else:
                p_meta = info.meta
                p_full = B.flatten_bucket(
                    [p_leaves[i] for i in info.indices], p_meta)
                p_loc = jax.lax.dynamic_slice(p_full, (rank * rows, 0),
                                              (rows, 128))
            hyper = self._hyper(info.group, lr)
            new_p_loc, new_bucket = self._update_bucket_sharded(
                info, packed_local[info.key], p_loc, bucket_state, hyper,
                step_count, grad_scale, noop, extras, rank)
            if use_master:
                new_bucket["master"] = new_p_loc
            new_buckets[info.key] = new_bucket
            new_p_full = jax.lax.all_gather(new_p_loc, ax, axis=0,
                                            tiled=True)
            outs = B.unflatten_bucket(new_p_full, p_meta)
            for i, t in zip(info.indices, outs):
                new_p_leaves[i] = t.astype(p_leaves[i].dtype)
        new_params = jax.tree_util.tree_unflatten(treedef, new_p_leaves)
        return new_params, {"step": step_count, "buckets": new_buckets}

    # -- owned shard_map region ---------------------------------------------

    def _check_mesh(self, mesh):
        ax = self.axis_name
        if ax not in mesh.axis_names:
            raise ValueError(
                f"optimizer reduces over axis {ax!r} but the mesh has axes "
                f"{tuple(mesh.axis_names)}; pass axis_name={ax!r} at "
                "construction or build the mesh with that axis")
        size = mesh.shape[ax]
        if size != self.world_size:
            raise ValueError(
                f"optimizer was built with world_size={self.world_size} "
                f"but mesh axis {ax!r} has size {size}; the ZeRO shards "
                "must match the mesh")

    def _check_stacked_grads(self, grads, params):
        p_tree = jax.tree_util.tree_structure(params)
        g_tree = jax.tree_util.tree_structure(grads)
        if p_tree != g_tree:
            raise ValueError(
                f"grads tree {g_tree} does not match params tree {p_tree}")

        def chk(path, g, p):
            want = (self.world_size,) + p.shape
            if g.shape != want:
                raise ValueError(
                    f"grad leaf {jax.tree_util.keystr(path)} has shape "
                    f"{g.shape}, expected {want}: make_step takes STACKED "
                    "per-device gradients (leading axis = the "
                    f"{self.axis_name!r} mesh axis, one microbatch grad "
                    "per device — the reduce-scatter inside the step IS "
                    "the DDP reduction).  For grads already reduced or "
                    "produced inside your own shard_map region, call "
                    ".step there instead.")

        jax.tree_util.tree_map_with_path(chk, grads, params)

    def make_init(self, mesh):
        """Jitted state init owning the unchecked shard_map region;
        returns per-device ZeRO state shards laid out by
        :meth:`state_specs`."""
        from jax.sharding import PartitionSpec as P
        self._check_mesh(mesh)

        def init(params):
            return jax.shard_map(
                self.init, mesh=mesh, in_specs=(P(),),
                out_specs=self.state_specs(params), check_vma=False)(params)

        return jax.jit(init)

    def make_step(self, mesh, donate=False):
        """Jitted ZeRO step owning the unchecked shard_map region (the
        API form of the recipe this module's docstring used to hand
        users).

        The returned callable is
        ``step(grads, params, state, lr=None, grad_scale=1.0,
        noop_flag=None) -> (new_params, new_state)`` where ``grads`` are
        the STACKED per-device microbatch gradients: leading axis =
        ``world_size`` (sharded over the optimizer's mesh axis), one
        unreduced gradient per device — the step's reduce-scatter is the
        gradient reduction.  Misuse (wrong mesh axis, unstacked grads,
        mismatched trees) raises at trace time with a message naming the
        offending leaf.  ``donate=True`` donates params+state buffers.
        """
        from jax.sharding import PartitionSpec as P
        self._check_mesh(mesh)
        ax = self.axis_name

        def step(grads, params, state, lr=None, grad_scale=1.0,
                 noop_flag=None):
            self._check_stacked_grads(grads, params)
            specs = self.state_specs(params)
            g_specs = jax.tree_util.tree_map(lambda _: P(ax), grads)
            # lr=None must REACH self.step as None — a concrete default
            # would read as an explicit override in _hyper and stomp
            # per-group lr settings
            lr_args = () if lr is None else (jnp.asarray(lr, _f32),)
            gs_val = jnp.asarray(grad_scale, _f32)
            # an explicit zero noop flag is the identity: the kernels'
            # select keeps the updated values and step_count advances
            noop = (jnp.zeros((), _f32) if noop_flag is None
                    else jnp.reshape(jnp.asarray(noop_flag, _f32), ()))

            def local(g, p, s, gs_, noop_, *lr_):
                g = jax.tree_util.tree_map(lambda x: x[0], g)
                return self.step(g, p, s,
                                 lr=lr_[0] if lr_ else None,
                                 grad_scale=gs_, noop_flag=noop_)

            return jax.shard_map(
                local, mesh=mesh,
                in_specs=(g_specs, P(), specs, P(), P())
                         + (P(),) * len(lr_args),
                out_specs=(P(), specs), check_vma=False)(
                    grads, params, state, gs_val, noop, *lr_args)

        return jax.jit(step, donate_argnums=(1, 2) if donate else ())

    # -- subclass hooks ------------------------------------------------------

    def _moment_keys(self):
        return ("m", "v")

    def _pre_step_sharded(self, layout, packed_local, state, *, lr,
                          grad_scale):
        return None

    def _update_bucket_sharded(self, info, g_loc, p_loc, bucket_state,
                               hyper, step_count, grad_scale, noop, extras,
                               rank):
        # element-wise updates (Adam) are shard-oblivious
        return self._update_bucket(info, g_loc, p_loc, bucket_state, hyper,
                                   step_count, grad_scale, noop, extras)


class DistributedFusedAdam(_DistributedMixin, FusedAdam):
    """ZeRO-sharded FusedAdam (apex ``DistributedFusedAdam``).

    ``DistributedFusedAdam(lr=..., world_size=N, axis_name="data")``;
    run ``init``/``step`` inside ``shard_map`` over the data axis.
    ``allreduce_dtype`` in ``{None/'f32', 'bf16', 'int8'}`` selects the
    gradient reduce-scatter transport (see
    :mod:`apex_tpu.utils.compressed_allreduce`).  ``plan`` (a
    :class:`~apex_tpu.parallel.plan.ParallelPlan`) supplies
    ``world_size``/``allreduce_dtype`` from its
    ``zero_shard``/transport fields instead.
    """

    def __init__(self, params=None, lr=1e-3, world_size=1,
                 axis_name="data", average_grads=True,
                 allreduce_dtype=None, plan=None, **kw):
        super().__init__(params, lr=lr, **kw)
        self._dist_init(world_size, axis_name, average_grads,
                        allreduce_dtype, plan=plan)


class DistributedFusedLAMB(_DistributedMixin, FusedLAMB):
    """ZeRO-sharded FusedLAMB (apex ``DistributedFusedLAMB``, the
    MLPerf-BERT full-pod optimizer).

    Cross-shard couplings are handled explicitly: the global grad-norm
    clip is a ``psum`` of per-shard sums; the per-tensor trust ratios need
    per-tensor ‖p‖/‖u‖ over tensors that straddle shard boundaries, so the
    per-ROW partial sums (tiny: ``rows × 1``) are all-gathered and reduced
    against the full row→tensor map, then the ratios are applied to the
    local rows only (apex: clip-after-allreduce + two-stage
    ``multi_tensor_lamb``).  ``allreduce_dtype``/``plan`` select the
    gradient reduce-scatter transport and shard factor, same as
    :class:`DistributedFusedAdam`.
    """

    def __init__(self, params=None, lr=1e-3, world_size=1,
                 axis_name="data", average_grads=True,
                 allreduce_dtype=None, plan=None, **kw):
        super().__init__(params, lr=lr, **kw)
        self._dist_init(world_size, axis_name, average_grads,
                        allreduce_dtype, plan=plan)

    def _pre_step_sharded(self, layout, packed_local, state, *, lr,
                          grad_scale):
        from apex_tpu.ops import multi_tensor as K
        total_sq = jnp.zeros((), _f32)
        for info in layout.buckets:
            rowsq, _ = K.l2norm_rowsq_packed(packed_local[info.key],
                                             block_rows=self.block_rows)
            total_sq = total_sq + jnp.sum(rowsq)
        total_sq = jax.lax.psum(total_sq, self.axis_name)
        gnorm = jnp.sqrt(total_sq) * jnp.asarray(grad_scale, _f32)
        max_norm = jnp.asarray(self.defaults["max_grad_norm"], _f32)
        clip = jnp.where(gnorm > max_norm, max_norm / gnorm, 1.0)
        return {"global_grad_clip": clip}

    def _update_bucket_sharded(self, info, g, p, st, hyper, step_count,
                               grad_scale, noop, extras, rank):
        from apex_tpu.multi_tensor_apply.functional import _row_ids_cached
        from apex_tpu.ops import multi_tensor as K
        from apex_tpu.optimizers.base import per_tensor_sums

        beta1, beta2 = hyper["betas"]
        if hyper["bias_correction"]:
            t = step_count.astype(_f32)
            bc1 = 1.0 - beta1 ** t
            bc2 = 1.0 - beta2 ** t
        else:
            bc1 = bc2 = 1.0
        u, m_new, v_new, usq, psq = K.lamb_stage1_packed(
            g, p, st["m"], st["v"], beta1=beta1, beta2=beta2,
            eps=hyper["eps"], weight_decay=hyper["weight_decay"],
            bias_correction1=bc1, bias_correction2=bc2,
            grad_scale=grad_scale,
            global_grad_clip=extras["global_grad_clip"],
            grad_averaging=hyper["grad_averaging"],
            adam_w_mode=hyper["adam_w_mode"], noop_flag=noop,
            block_rows=self.block_rows)
        # per-tensor norms across ALL shards: gather the (rows, 1) row
        # sums (negligible traffic), reduce on the full row→tensor map
        usq_full = jax.lax.all_gather(usq, self.axis_name, axis=0,
                                      tiled=True)
        psq_full = jax.lax.all_gather(psq, self.axis_name, axis=0,
                                      tiled=True)
        p_norm = jnp.sqrt(per_tensor_sums(info.meta, psq_full))
        u_norm = jnp.sqrt(per_tensor_sums(info.meta, usq_full))
        if hyper["use_nvlamb"]:
            ratio = jnp.where(u_norm > 0, p_norm / u_norm, 1.0)
        else:
            ratio = jnp.where((p_norm > 0) & (u_norm > 0),
                              p_norm / u_norm, 1.0)
        rows = self._local_rows(info)
        ids = jnp.asarray(_row_ids_cached(info.meta))
        ids_loc = jax.lax.dynamic_slice_in_dim(ids, rank * rows, rows)
        row_ratio = ratio[ids_loc][:, None]
        p_new = K.lamb_stage2_packed(u, p, row_ratio, lr=hyper["lr"],
                                     noop_flag=noop,
                                     block_rows=self.block_rows)
        return p_new, {"m": m_new, "v": v_new}
