"""Shared machinery for the fused optimizers (apex ``apex/optimizers/*``).

Apex optimizers hold mutable per-param ``state`` and update params in place
with one ``multi_tensor_apply`` launch per dtype group per step.  The JAX
equivalent is functional: ``opt.init(params) -> state`` and
``opt.step(grads, params, state) -> (new_params, new_state)``, where state
holds the moments as *packed* ``(rows, 128)`` buckets (one per param-group ×
dtype) so each step is one Pallas kernel sweep per bucket — the same
O(#dtypes) launch count apex achieves, not O(#params).

Param groups: apex takes a list of ``{"params": [...], "lr": ..., ...}``
dicts.  Pytrees have no identity-based grouping, so groups are expressed as
``param_group_fn(path_str) -> group_name`` plus per-group hyperparameter
overrides in ``param_groups={name: {...}}``; ungrouped leaves fall into
``"default"``.

Two execution layouts (``bucketed`` ctor flag, default ``None`` =
per-class default):

* ``bucketed=False`` (the single-chip DEFAULT): state lives per leaf and
  the step is the same single-source ``_*_math`` update applied per leaf
  as plain jnp, which XLA fuses into the surrounding train step.  On a
  single chip this is the FASTER path: a pallas_call's operands must be
  materialized buffers, so the packed path pays a pack (concat) + unpack
  (slice) HBM round trip per step that per-leaf fusion never performs.
  The two layouts have not been timed against each other on the chip
  (not measured; ``ROADMAP.md`` D1); the per-leaf step is 4.6 % of the
  BERT-large step there (``PERF.md`` section 5).  apex has no equivalent switch because CUDA
  launch overhead forces fusion the other way (see SURVEY §3.2); on TPU
  the launch-count argument inverts.
* ``bucketed=True`` (apex parity layout): state lives in packed
  ``(rows, 128)`` buckets and each step is one Pallas kernel sweep per
  bucket.  This is the layout the ZeRO/distributed optimizers REQUIRE —
  the packed rows are what reduce-scatter/all-gather shard evenly — so
  it stays THEIR default.  It is no longer a public opt-in on plain
  optimizers: requesting it explicitly on a plain optimizer
  raises (whether a single-chip regime exists
  where it wins is not measured on the chip; ``ROADMAP.md`` D1).
  The engine itself survives as the distributed optimizers'
  sharding unit (and the parity tests flip ``opt.bucketed`` by
  attribute to keep pinning the kernel path).
"""

from __future__ import annotations

import functools
from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp

from apex_tpu.multi_tensor_apply import bucketing as B

_f32 = jnp.float32


class BucketInfo(NamedTuple):
    key: str               # "group/dtype" — state dict key
    group: str
    indices: tuple         # leaf positions in the flattened param list
    meta: B.BucketMeta     # layout in the *param* dtype


class Layout(NamedTuple):
    buckets: tuple         # tuple[BucketInfo]
    n_leaves: int


def _leaf_key(path, leaf):
    return (jax.tree_util.keystr(path), tuple(leaf.shape), str(leaf.dtype))


class FusedOptimizer:
    """Base class: bucket layout, hyperparameter resolution, master weights."""

    # per-leaf is the single-chip default (see module docstring); the
    # distributed/ZeRO mixin overrides this to True — its sharding IS the
    # packed layout
    _default_bucketed = False

    def __init__(self, lr, *, weight_decay=0.0,
                 param_group_fn: Optional[Callable[[str], str]] = None,
                 param_groups: Optional[dict] = None,
                 master_weights: bool = False,
                 block_rows: int = B.DEFAULT_BLOCK_ROWS,
                 bucketed: Optional[bool] = None,
                 message_size: Optional[int] = None,
                 **defaults):
        self.defaults = dict(lr=lr, weight_decay=weight_decay, **defaults)
        self.param_group_fn = param_group_fn
        self.param_groups = dict(param_groups or {})
        self.master_weights = bool(master_weights)
        self.block_rows = int(block_rows)
        if bucketed is None:
            bucketed = self._default_bucketed
        elif bucketed and not self._default_bucketed:
            raise ValueError(
                "bucketed=True (packed multi_tensor layout) is not "
                "supported on plain optimizers: it measured ~2x slower "
                "than the per-leaf default for single-chip steps across "
                "two bench rounds (packed_vs_optax_speedup=0.49-0.53) — "
                "the pack/unpack HBM round trip outweighs the launch "
                "savings on TPU.  Use the per-leaf default; the packed "
                "layout remains the distributed (ZeRO) optimizers' "
                "internal sharding unit.")
        self.bucketed = bool(bucketed)
        # apex semantics: cap each packed bucket at ``message_size`` BYTES
        # (dtype-aware — the cap bounds the flattened collective payload,
        # so a bf16 bucket holds twice the elements of an f32 one).
        # None = one bucket per (group, dtype), the prior behavior.
        self.message_size = None if message_size is None else int(message_size)
        self._layout_cache: dict = {}

    # -- layout ------------------------------------------------------------

    def _meta_block_rows(self) -> int:
        """Row multiple for bucket padding.  Distributed (ZeRO) subclasses
        align to ``block_rows * world_size`` so every per-device shard is a
        whole number of kernel blocks."""
        return self.block_rows

    def _layout(self, params) -> Layout:
        leaves_with_path, treedef = jax.tree_util.tree_flatten_with_path(
            params)
        cache_key = tuple(_leaf_key(p, l) for p, l in leaves_with_path)
        hit = self._layout_cache.get(cache_key)
        if hit is not None:
            return hit
        groups: dict = {}
        for i, (path, leaf) in enumerate(leaves_with_path):
            name = "default"
            if self.param_group_fn is not None:
                name = self.param_group_fn(jax.tree_util.keystr(path))
            groups.setdefault((name, jnp.dtype(leaf.dtype)), []).append(i)
        leaves = [l for _, l in leaves_with_path]
        buckets = []
        for (name, dtype), idxs in groups.items():
            shapes = tuple(tuple(leaves[i].shape) for i in idxs)
            if self.message_size is None:
                parts = [list(range(len(idxs)))]
            else:
                parts = B.split_by_message_size(shapes, dtype,
                                                self.message_size)
            for j, part in enumerate(parts):
                sub_idxs = tuple(idxs[k] for k in part)
                sub_shapes = tuple(shapes[k] for k in part)
                meta = B.bucket_meta(sub_shapes, dtype,
                                     self._meta_block_rows())
                key = (f"{name}/{dtype}" if len(parts) == 1
                       else f"{name}/{dtype}/{j}")
                buckets.append(BucketInfo(key, name, sub_idxs, meta))
        layout = Layout(tuple(buckets), len(leaves))
        self._layout_cache[cache_key] = layout
        return layout

    def _hyper(self, group: str, lr=None) -> dict:
        h = dict(self.defaults)
        h.update(self.param_groups.get(group, {}))
        if lr is not None:
            h["lr"] = lr
        return h

    # -- state -------------------------------------------------------------

    def init(self, params):
        """Build optimizer state for a param pytree — packed moment
        buckets (``bucketed=True``) or per-leaf moment lists."""
        layout = self._layout(params)
        leaves = jax.tree_util.tree_leaves(params)
        buckets = {}
        for info in layout.buckets:
            ps = [leaves[i] for i in info.indices]
            if self.bucketed:
                st = self._init_bucket(info)
                if self.master_weights and info.meta.dtype != _f32:
                    f32_meta = info.meta._replace(dtype=_f32)
                    st["master"] = B.flatten_bucket(ps, f32_meta)
            else:
                st = self._init_leaves(info, ps)
                if self.master_weights and info.meta.dtype != _f32:
                    st["master"] = [p.astype(_f32) for p in ps]
            buckets[info.key] = st
        return {"step": jnp.zeros((), jnp.int32), "buckets": buckets}

    def _full_master_bucket(self, packed_master):
        """The bucket's FULL packed master rows (hook: the ZeRO mixin
        stores row shards and all-gathers here)."""
        return packed_master

    def master_params(self, params, state):
        """fp32 master copies as a pytree shaped like ``params`` (apex
        ``amp.master_params(optimizer)``).  Buckets without a master copy
        (already-fp32 params) return the params upcast as-is."""
        layout = self._layout(params)
        leaves, treedef = jax.tree_util.tree_flatten(params)
        out = [l.astype(_f32) if jnp.issubdtype(l.dtype, jnp.floating)
               else l for l in leaves]
        for info in layout.buckets:
            bucket_state = state["buckets"][info.key]
            if "master" not in bucket_state:
                continue
            if self.bucketed:
                masters = B.unflatten_bucket(
                    self._full_master_bucket(bucket_state["master"]),
                    info.meta._replace(dtype=_f32))
            else:
                masters = bucket_state["master"]
            for i, t in zip(info.indices, masters):
                out[i] = t
        return jax.tree_util.tree_unflatten(treedef, out)

    # -- step --------------------------------------------------------------

    @jax.named_scope("optimizer")
    def step(self, grads, params, state, *, lr=None, grad_scale=1.0,
             noop_flag=None):
        """One fused optimizer step.

        ``grad_scale`` multiplies gradients (pass ``1/loss_scale`` to fuse
        amp unscaling); a non-zero ``noop_flag`` skips the update entirely
        on-device (dynamic loss scaling overflow skip, apex's ``noop``
        buffer) including the step counter.
        """
        layout = self._layout(params)
        p_leaves, treedef = jax.tree_util.tree_flatten(params)
        g_leaves = jax.tree_util.tree_leaves(grads)
        if len(g_leaves) != len(p_leaves) or any(
                tuple(g.shape) != tuple(p.shape)
                for g, p in zip(g_leaves, p_leaves)):
            raise ValueError(
                "grads pytree does not match params: "
                f"{[tuple(g.shape) for g in g_leaves]} vs "
                f"{[tuple(p.shape) for p in p_leaves]}")
        noop = (None if noop_flag is None
                else jnp.asarray(noop_flag).reshape(()))
        if not self.bucketed:
            return self._step_per_leaf(layout, g_leaves, p_leaves, treedef,
                                       state, lr, grad_scale, noop)
        packed = {}
        for info in layout.buckets:
            gs = [g_leaves[i] for i in info.indices]
            g_meta = info.meta._replace(dtype=jnp.dtype(gs[0].dtype))
            packed[info.key] = B.flatten_bucket(gs, g_meta)
        extras = self._pre_step(layout, packed, state, lr=lr,
                                grad_scale=grad_scale)
        new_p_leaves = list(p_leaves)
        new_buckets = {}
        step_count = state["step"] + 1
        if noop is not None:
            step_count = state["step"] + (noop == 0).astype(jnp.int32)
        for info in layout.buckets:
            bucket_state = dict(state["buckets"][info.key])
            use_master = "master" in bucket_state
            if use_master:
                p_meta = info.meta._replace(dtype=_f32)
                p_packed = bucket_state["master"]
            else:
                p_meta = info.meta
                p_packed = B.flatten_bucket(
                    [p_leaves[i] for i in info.indices], p_meta)
            hyper = self._hyper(info.group, lr)
            new_p_packed, new_bucket = self._update_bucket(
                info, packed[info.key], p_packed, bucket_state, hyper,
                step_count, grad_scale, noop, extras)
            if use_master:
                new_bucket["master"] = new_p_packed
            new_buckets[info.key] = new_bucket
            outs = B.unflatten_bucket(new_p_packed, p_meta)
            for i, t in zip(info.indices, outs):
                new_p_leaves[i] = t.astype(p_leaves[i].dtype)
        new_params = jax.tree_util.tree_unflatten(treedef, new_p_leaves)
        return new_params, {"step": step_count, "buckets": new_buckets}

    def _step_per_leaf(self, layout, g_leaves, p_leaves, treedef, state,
                       lr, grad_scale, noop):
        """The ``bucketed=False`` step: per-leaf jnp updates XLA fuses
        into the surrounding graph — no pack/unpack HBM round trips.
        Same ``_*_math`` single-source update as the packed kernels."""
        step_count = state["step"] + 1
        if noop is not None:
            step_count = state["step"] + (noop == 0).astype(jnp.int32)
        extras = self._pre_step_leaves(layout, g_leaves, state, lr=lr,
                                       grad_scale=grad_scale)
        new_p_leaves = list(p_leaves)
        new_buckets = {}
        for info in layout.buckets:
            bucket_state = dict(state["buckets"][info.key])
            gs = [g_leaves[i] for i in info.indices]
            use_master = "master" in bucket_state
            if use_master:
                ps = bucket_state["master"]
            else:
                ps = [p_leaves[i] for i in info.indices]
            hyper = self._hyper(info.group, lr)
            new_ps, new_bucket = self._update_leaves(
                info, gs, ps, bucket_state, hyper, step_count, grad_scale,
                noop, extras)
            if use_master:
                new_bucket["master"] = new_ps
            new_buckets[info.key] = new_bucket
            for i, t in zip(info.indices, new_ps):
                new_p_leaves[i] = t.astype(p_leaves[i].dtype)
        new_params = jax.tree_util.tree_unflatten(treedef, new_p_leaves)
        return new_params, {"step": step_count, "buckets": new_buckets}

    # -- shared helpers ----------------------------------------------------

    @staticmethod
    def _bias_corrections(hyper, step_count):
        """Adam-family ``1 - beta^t`` terms (1.0 when disabled)."""
        beta1, beta2 = hyper["betas"]
        if hyper["bias_correction"]:
            t = step_count.astype(jnp.float32)
            return 1.0 - beta1 ** t, 1.0 - beta2 ** t
        return 1.0, 1.0

    # -- subclass hooks ----------------------------------------------------

    def _init_bucket(self, info: BucketInfo) -> dict:
        raise NotImplementedError

    def _pre_step(self, layout, packed_grads, state, *, lr, grad_scale):
        """Cross-bucket pre-pass (e.g. LAMB's global grad norm)."""
        return None

    def _update_bucket(self, info, g_packed, p_packed, bucket_state, hyper,
                       step_count, grad_scale, noop, extras):
        raise NotImplementedError

    def _init_leaves(self, info: BucketInfo, ps) -> dict:
        """Per-leaf state for ``bucketed=False`` — dict of LISTS aligned
        with ``info.indices``."""
        raise NotImplementedError(
            f"{type(self).__name__} does not implement the per-leaf "
            "(bucketed=False) layout")

    def _pre_step_leaves(self, layout, g_leaves, state, *, lr, grad_scale):
        """Cross-leaf pre-pass for ``bucketed=False``."""
        return None

    def _update_leaves(self, info, gs, ps, bucket_state, hyper, step_count,
                       grad_scale, noop, extras):
        """Per-leaf update: returns ``(new_ps, new_bucket_state)`` with
        lists aligned like ``_init_leaves``."""
        raise NotImplementedError

    # -- interop -----------------------------------------------------------

    def as_optax(self):
        """Adapter to an ``optax.GradientTransformation``.

        ``update`` returns deltas (``new_params - params``) so it composes
        with ``optax.apply_updates``; params must be passed (like any
        params-dependent optax transform).
        """
        import optax

        def init_fn(params):
            return self.init(params)

        def update_fn(grads, state, params=None):
            if params is None:
                raise ValueError(
                    "apex_tpu fused optimizers require params in update()")
            new_params, new_state = self.step(grads, params, state)
            updates = jax.tree_util.tree_map(
                lambda n, p: (n.astype(_f32) - p.astype(_f32)).astype(p.dtype),
                new_params, params)
            return updates, new_state

        return optax.GradientTransformation(init_fn, update_fn)

    # -- checkpoint parity helpers ------------------------------------------

    @staticmethod
    def state_dict(state):
        """Device → host copy of optimizer state (checkpoint surface)."""
        return jax.device_get(state)

    @staticmethod
    def load_state_dict(state_dict):
        return jax.tree_util.tree_map(jnp.asarray, state_dict)


def per_tensor_ratio_rows(meta: B.BucketMeta, per_tensor_vals: jax.Array):
    """Broadcast per-tensor scalars to per-row ``(rows, 1)`` via the
    row→tensor map (used by LAMB trust ratios and NovoGrad's v)."""
    from apex_tpu.multi_tensor_apply.functional import _row_ids_cached
    ids = _row_ids_cached(meta)
    return per_tensor_vals[ids][:, None]


def per_tensor_sums(meta: B.BucketMeta, rowsq: jax.Array):
    from apex_tpu.multi_tensor_apply.functional import _per_tensor_from_rowsq
    return _per_tensor_from_rowsq(rowsq, meta)
