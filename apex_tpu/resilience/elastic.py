"""Elastic, preemption-native training (ISSUE 9 / ROADMAP item 4).

Preemptible TPU pods change SHAPE, not just liveness: a maintenance
event takes half the slice away, a restored reservation gives it back.
Surviving that is a layout problem, not a retrain problem — *Automatic
Cross-Replica Sharding of Weight Update* (arXiv:2004.13336) and *GSPMD*
(arXiv:2105.04663) make the point this module operationalizes: sharded
optimizer state is a pure partition of the same logical tensors, so a
``dp=8 -> dp=4`` shrink is a deterministic re-partition.

Three pieces close the loop from :class:`CheckpointManager`'s
topology-tolerant restore and :class:`FaultInjector`'s preemption model
into genuinely elastic training:

* :class:`TopologySpec` / :class:`ElasticPlan` — the (dp, tp, pp, SP,
  ZeRO-shard) descriptor plus the concrete mesh it resolves to.  The
  checkpoint manager stamps the spec into every manifest; restore
  validates it and warns (with BOTH specs) before re-sharding.
* :func:`reshard_optimizer_state` — re-partitions optimizer state
  across a topology change.  ZeRO reduce-scatter shards gather to the
  LOGICAL per-leaf tensors (``unflatten_bucket`` under the old
  ``block_rows * world_size`` padding) and re-split under the new world
  size; per-leaf fused-optimizer slots re-layout through the caller's
  param transform.  f32 moments and master weights are preserved
  bitwise — only the padding moves.
* :class:`ElasticTrainer` — the driver loop around
  :class:`~apex_tpu.resilience.guard.GuardedTrainStep`.  On a
  preemption/arrival signal (an injected ``topology_change`` fault or a
  :class:`HostSignals` delivery, the SIGTERM-with-grace-period
  analogue) it drains in-flight saves, checkpoints under the OLD
  topology, builds the new plan's components (fresh compile),
  re-shards the live state, checkpoints again under the NEW topology —
  so the guard's K-anomaly rollback can never restore an
  old-topology layout — and resumes.  A hard
  :class:`~apex_tpu.resilience.faults.Preemption` still propagates
  (no grace period); the next trainer reads the manifest's stamped
  topology, restores onto it, and re-shards to its own plan.

Which transitions are BITWISE: with the global batch replicated over
the data axis, a pmean over any power-of-two group of identical values
is exact (``n*x`` then ``/n``), so the gradient math is
topology-invariant and dp changes (including ZeRO re-shards — the
reduce-scatter sums ``ws`` identical copies, ``average_grads`` divides
them back out) resume bitwise.  With the batch SHARDED, the reduction
tree changes with dp and the run is trajectory-equivalent instead
(asserted ``allclose`` at a re-aligned step) — the documented cell in
``tools/crash_matrix.py --topology``.  See ``docs/source/resilience.md``.
"""

from __future__ import annotations

import collections
import dataclasses
import signal as _signal
import threading
import time
from typing import Any, Callable, Dict, Optional

import numpy as np

from apex_tpu.observability.spans import span
from apex_tpu.resilience.guard import GuardedTrainStep

_DATA_AXIS = "data"
_PIPE_AXIS = "pipe"
_TENSOR_AXIS = "model"


# -- topology descriptors -----------------------------------------------------


@dataclasses.dataclass(frozen=True)
class TopologySpec:
    """The logical parallelism layout a train state lives under.

    ``zero_shard`` is the ZeRO optimizer-state shard factor over the
    data axis — 1 (replicated optimizer state, the per-leaf fused
    optimizers) or ``dp`` (the distributed optimizers' reduce-scatter
    sharding).  Anything in between would shard rows unevenly against
    the data axis, so it is rejected.
    """
    dp: int = 1
    tp: int = 1
    pp: int = 1
    sequence_parallel: bool = False
    zero_shard: int = 1

    def __post_init__(self):
        for name in ("dp", "tp", "pp", "zero_shard"):
            v = getattr(self, name)
            if not isinstance(v, (int, np.integer)) or v < 1:
                raise ValueError(f"{name} must be a positive int, got {v!r}")
        if self.zero_shard not in (1, self.dp):
            raise ValueError(
                f"zero_shard must be 1 or dp ({self.dp}), got "
                f"{self.zero_shard}: ZeRO shards the data axis")
        if self.sequence_parallel and self.tp == 1:
            raise ValueError("sequence_parallel requires tp > 1")

    @property
    def n_devices(self) -> int:
        return self.dp * self.tp * self.pp

    def to_dict(self) -> dict:
        return {"dp": int(self.dp), "tp": int(self.tp), "pp": int(self.pp),
                "sequence_parallel": bool(self.sequence_parallel),
                "zero_shard": int(self.zero_shard)}

    @classmethod
    def from_dict(cls, d: dict) -> "TopologySpec":
        return cls(dp=int(d.get("dp", 1)), tp=int(d.get("tp", 1)),
                   pp=int(d.get("pp", 1)),
                   sequence_parallel=bool(d.get("sequence_parallel", False)),
                   zero_shard=int(d.get("zero_shard", 1)))

    def describe(self) -> str:
        return (f"dp={self.dp} tp={self.tp} pp={self.pp} "
                f"sp={'on' if self.sequence_parallel else 'off'} "
                f"zero={self.zero_shard}")

    def to_plan(self, **overrides):
        """Lift into the full :class:`~apex_tpu.parallel.plan.
        ParallelPlan` this spec is a projection of; ``overrides``
        supply the knobs the spec does not carry (schedule, remat,
        transport).  ``spec.to_plan().topology() == spec`` — the
        lossless round-trip old stamped manifests rely on."""
        from apex_tpu.parallel.plan import ParallelPlan
        return ParallelPlan.from_topology(self, **overrides)


@dataclasses.dataclass(frozen=True)
class ElasticPlan:
    """A :class:`TopologySpec` resolved onto concrete devices.

    The mesh always carries the full ``("data", "pipe", "model")`` axis
    set with sizes ``(dp, pp, tp)`` — unit axes are free, and one
    canonical axis order means every component (ZeRO reduce-scatter
    over ``"data"``, ring pipeline over ``"pipe"``, TP collectives over
    ``"model"``) addresses the same mesh regardless of which axes the
    plan actually uses.
    """
    spec: TopologySpec
    mesh: Any                      # jax.sharding.Mesh
    parallel: Any = None           # full ParallelPlan when built from one

    @classmethod
    def build(cls, spec, devices=None) -> "ElasticPlan":
        """``spec`` is a :class:`TopologySpec` or a full
        :class:`~apex_tpu.parallel.plan.ParallelPlan` — the latter is
        kept on :attr:`parallel` so factories can read the schedule/
        remat/transport knobs the topology projection drops."""
        import jax
        parallel = None
        if not isinstance(spec, TopologySpec) and hasattr(spec, "topology"):
            parallel = spec
            if getattr(parallel, "n_pods", 1) > 1:
                raise ValueError(
                    f"plan has n_pods={parallel.n_pods}: a cross-pod "
                    "MPMD plan spans multiple meshes and cannot build "
                    "one ElasticPlan — run it with "
                    "apex_tpu.mpmd.MpmdPipeline (per-stage programs), "
                    "or set n_pods=1 for a single-mesh ring pipeline")
            spec = spec.topology()
        devices = list(devices) if devices is not None else jax.devices()
        n = spec.n_devices
        if len(devices) < n:
            raise ValueError(
                f"plan {spec.describe()} needs {n} devices, have "
                f"{len(devices)}")
        # Auto axes: arrays placed on this mesh also pass through plain
        # jit (optimizer step, resharding), where make_mesh's Explicit
        # default would type every value by its sharding
        mesh = jax.make_mesh((spec.dp, spec.pp, spec.tp),
                             (_DATA_AXIS, _PIPE_AXIS, _TENSOR_AXIS),
                             devices=devices[:n],
                             axis_types=(jax.sharding.AxisType.Auto,) * 3)
        return cls(spec=spec, mesh=mesh, parallel=parallel)

    def replicated(self):
        from jax.sharding import NamedSharding, PartitionSpec
        return NamedSharding(self.mesh, PartitionSpec())

    def sharded(self, *axes):
        from jax.sharding import NamedSharding, PartitionSpec
        return NamedSharding(self.mesh, PartitionSpec(*axes))

    def put(self, tree):
        """``device_put`` a pytree replicated onto this plan's mesh."""
        import jax
        return jax.device_put(tree, self.replicated())

    @property
    def mesh_shape(self) -> dict:
        return dict(zip(self.mesh.axis_names,
                        (int(s) for s in self.mesh.devices.shape)))


# -- optimizer state re-sharding ----------------------------------------------


def _as_f32_meta(meta):
    import jax.numpy as jnp
    return meta._replace(dtype=jnp.float32)


def _zero_reshard(state, new_plan, optimizer, params, new_optimizer,
                  new_params):
    """Gather-to-logical -> re-split for ZeRO (bucketed) state.

    Bucket padding is ``block_rows * world_size`` rows, so the packed
    layout itself depends on dp — but the pad rows are identically zero
    (zero grads keep Adam/LAMB moments at zero and the noop'd master
    rows at their initial zero), so dropping them via
    ``unflatten_bucket`` under the OLD meta and re-padding via
    ``flatten_bucket`` under the NEW meta moves only zeros.  The
    logical f32 values (moments AND master weights) transfer bitwise.
    """
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from apex_tpu.multi_tensor_apply import bucketing as B

    old_layout = optimizer._layout(params)
    new_layout = new_optimizer._layout(new_params)
    old_by_key = {info.key: info for info in old_layout.buckets}
    new_by_key = {info.key: info for info in new_layout.buckets}
    if set(old_by_key) != set(new_by_key):
        raise ValueError(
            f"bucket keys changed across the re-shard: "
            f"{sorted(old_by_key)} vs {sorted(new_by_key)} — elastic "
            "re-sharding requires a layout-stable bucketing "
            "(message_size=None, same param grouping)")
    shard = NamedSharding(new_plan.mesh, P(new_optimizer.axis_name))
    rep = NamedSharding(new_plan.mesh, P())
    buckets = {}
    for key, old_info in old_by_key.items():
        new_info = new_by_key[key]
        src = state["buckets"][key]
        dst = {}
        for slot, arr in src.items():
            full = jnp.asarray(np.asarray(arr))   # gather the global rows
            leaves = B.unflatten_bucket(full, _as_f32_meta(old_info.meta))
            repacked = B.flatten_bucket(leaves, _as_f32_meta(new_info.meta))
            dst[slot] = jax.device_put(repacked, shard)
        buckets[key] = dst
    step = jax.device_put(jnp.asarray(np.asarray(state["step"])), rep)
    return {"step": step, "buckets": buckets}


def _per_leaf_reshard(state, new_plan, optimizer, params, new_optimizer,
                      new_params, transform):
    """Re-layout per-leaf fused-optimizer slots across a param-layout
    change: each slot kind (m / v / master / ...) is lifted into a
    params-shaped tree, run through the SAME transform the params take
    (e.g. unpack-then-repack for a tp/pp change — pure slicing, so f32
    values are preserved bitwise), and redistributed into the new
    layout's buckets."""
    import jax
    import jax.numpy as jnp

    _f32 = jnp.float32
    old_layout = optimizer._layout(params)
    new_layout = new_optimizer._layout(new_params)
    old_leaves, old_treedef = jax.tree_util.tree_flatten(params)
    slot_keys = sorted({k for key in state["buckets"]
                        for k in state["buckets"][key]})
    slot_leaves: Dict[str, list] = {}
    for sk in slot_keys:
        filled: list = [None] * old_layout.n_leaves
        for info in old_layout.buckets:
            vals = state["buckets"][info.key].get(sk)
            if vals is None:
                continue
            for i, v in zip(info.indices, vals):
                filled[i] = v
        # leaves whose bucket lacks this slot (e.g. no master for f32
        # buckets) get the value a fresh init would give them; they are
        # dropped again on redistribution unless the new bucket wants
        # the slot
        filled = [
            v if v is not None else (
                old_leaves[i].astype(_f32) if sk == "master"
                else jnp.zeros(np.shape(old_leaves[i]), _f32))
            for i, v in enumerate(filled)]
        tree = jax.tree_util.tree_unflatten(old_treedef, filled)
        if transform is not None:
            tree = transform(tree)
        leaves = jax.tree_util.tree_leaves(tree)
        if len(leaves) != new_layout.n_leaves:
            raise ValueError(
                f"slot {sk!r} transformed to {len(leaves)} leaves but the "
                f"new layout has {new_layout.n_leaves}: the param "
                "transform must map old-layout trees onto the new plan's "
                "param structure")
        slot_leaves[sk] = leaves
    rep = new_plan.replicated()
    new_buckets = {}
    old_slot_sets = {key: set(state["buckets"][key]) for key
                     in state["buckets"]}
    for info in new_layout.buckets:
        wanted = old_slot_sets.get(info.key)
        if wanted is None:
            raise ValueError(
                f"bucket {info.key!r} does not exist in the old state "
                f"(old buckets: {sorted(old_slot_sets)}) — elastic "
                "re-sharding requires dtype/group-stable transforms")
        nb = {}
        for sk in wanted:
            nb[sk] = [jax.device_put(slot_leaves[sk][i], rep)
                      for i in info.indices]
        new_buckets[info.key] = nb
    step = jax.device_put(jnp.asarray(np.asarray(state["step"])), rep)
    return {"step": step, "buckets": new_buckets}


def reshard_optimizer_state(state, old_plan: ElasticPlan,
                            new_plan: ElasticPlan, *, optimizer, params,
                            new_optimizer=None, new_params=None,
                            transform: Optional[Callable] = None):
    """Re-partition optimizer ``state`` from ``old_plan`` onto
    ``new_plan``.

    ``optimizer``/``params`` are the instance and param tree the state
    was built against; ``new_optimizer``/``new_params`` the ones it
    must serve next (default: unchanged).  ``transform`` maps an
    old-layout params-shaped tree to the new layout (identity for pure
    dp changes; unpack/re-pack for tp/pp changes) and is applied to
    every per-leaf slot.

    ZeRO (distributed, bucketed) state takes the gather-to-logical ->
    re-split path — f32 moments and master weights bitwise, only the
    ``block_rows * world_size`` padding moves.  Per-leaf fused state is
    re-laid-out slot-by-slot through ``transform``.  Both paths
    ``device_put`` onto the new plan's mesh.
    """
    from apex_tpu.parallel.distributed_optimizer import _DistributedMixin

    new_optimizer = new_optimizer if new_optimizer is not None else optimizer
    new_params = new_params if new_params is not None else params
    if not (isinstance(state, dict) and "buckets" in state):
        raise ValueError(
            "expected a fused-optimizer state dict with a 'buckets' entry")
    if (optimizer.param_group_fn is not None
            or new_optimizer.param_group_fn is not None) \
            and transform is not None:
        raise ValueError(
            "param_group_fn + a layout transform cannot re-shard safely: "
            "leaf paths change across the transform, so group membership "
            "would be recomputed against different names")
    if isinstance(optimizer, _DistributedMixin):
        if not isinstance(new_optimizer, _DistributedMixin):
            raise ValueError(
                "old optimizer is ZeRO-sharded but the new one is not; "
                "build the new plan's optimizer before re-sharding")
        if transform is not None:
            raise ValueError(
                "ZeRO re-sharding supports dp/world-size changes only "
                "(the packed buckets assume an unchanged leaf set); "
                "compose tp/pp transforms at the per-leaf layer instead")
        return _zero_reshard(state, new_plan, optimizer, params,
                             new_optimizer, new_params)
    return _per_leaf_reshard(state, new_plan, optimizer, params,
                             new_optimizer, new_params, transform)


# -- ZeRO under the guard -----------------------------------------------------


class ZeROGuardAdapter:
    """Adapts a distributed (ZeRO) optimizer to
    :class:`GuardedTrainStep`'s flat ``init``/``step`` contract.

    The guard calls ``optimizer.step`` OUTSIDE any shard_map region, on
    replicated grads; the adapter opens the ZeRO region itself, feeding
    each device the SAME fully-reduced gradient.  The reduce-scatter
    inside then sums ``world_size`` identical copies and
    ``average_grads`` divides them back out — exact for power-of-two
    world sizes — so wrapping is numerically the identity while the
    state stays row-sharded (the ZeRO memory saving survives).
    """

    def __init__(self, optimizer, mesh):
        import jax.numpy as jnp
        optimizer._check_mesh(mesh)
        self.inner = optimizer
        self.mesh = mesh
        self._f32 = jnp.float32

    def init(self, params):
        import jax
        from jax.sharding import PartitionSpec as P

        return jax.shard_map(
            self.inner.init, mesh=self.mesh, in_specs=(P(),),
            out_specs=self.inner.state_specs(params),
            check_vma=False)(params)

    def step(self, grads, params, state, *, lr=None, grad_scale=1.0,
             noop_flag=None):
        import jax
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P

        specs = self.inner.state_specs(params)
        gs = jnp.asarray(grad_scale, self._f32)
        noop = (jnp.zeros((), self._f32) if noop_flag is None
                else jnp.reshape(jnp.asarray(noop_flag, self._f32), ()))
        lr_args = () if lr is None else (jnp.asarray(lr, self._f32),)

        def local(g, p, s, gs_, noop_, *lr_):
            return self.inner.step(g, p, s, lr=lr_[0] if lr_ else None,
                                   grad_scale=gs_, noop_flag=noop_)

        return jax.shard_map(
            local, mesh=self.mesh,
            in_specs=(P(), P(), specs, P(), P()) + (P(),) * len(lr_args),
            out_specs=(P(), specs),
            check_vma=False)(grads, params, state, gs, noop, *lr_args)


# -- host signals -------------------------------------------------------------


class ElasticSignal(collections.namedtuple("ElasticSignal",
                                           ("kind", "spec"))):
    """``kind`` is ``"preempt"`` (drain + checkpoint + stop — the
    SIGTERM-with-grace analogue) or ``"replan"`` (re-shard onto
    ``spec`` and keep training — the arrival/defrag analogue)."""

    def __new__(cls, kind: str, spec=None):
        if kind not in ("preempt", "replan"):
            raise ValueError(f"unknown signal kind {kind!r}")
        if kind == "replan" and spec is None:
            raise ValueError("replan signals need a target TopologySpec "
                             "or ParallelPlan")
        return super().__new__(cls, kind, spec)


class HostSignals:
    """Thread/handler-safe mailbox for preemption & arrival signals.

    Programmatic delivery (:meth:`request_preempt` /
    :meth:`request_replan`) covers tests and schedulers with an API;
    :meth:`install` binds a POSIX signal (the real SIGTERM grace
    window) to the same mailbox.  :class:`ElasticTrainer` polls once
    per step — signals land between steps, never mid-step.
    """

    def __init__(self):
        self._pending: collections.deque = collections.deque()
        self._lock = threading.Lock()
        self._installed: dict = {}

    def request(self, sig: ElasticSignal) -> None:
        with self._lock:
            self._pending.append(sig)

    def request_preempt(self) -> None:
        self.request(ElasticSignal("preempt"))

    def request_replan(self, spec) -> None:
        """``spec`` is a :class:`TopologySpec` or a full
        :class:`~apex_tpu.parallel.plan.ParallelPlan` (e.g. the winner
        ``tools/autotune.py`` emitted)."""
        self.request(ElasticSignal("replan", spec))

    def poll(self) -> Optional[ElasticSignal]:
        with self._lock:
            return self._pending.popleft() if self._pending else None

    def install(self, signum: int = _signal.SIGTERM, *,
                kind: str = "preempt",
                spec: Optional[TopologySpec] = None) -> None:
        """Bind a POSIX signal to this mailbox (main thread only, like
        any ``signal.signal`` use); :meth:`uninstall` restores the
        previous handlers."""
        sig = ElasticSignal(kind, spec)   # validate before binding

        def handler(_signum, _frame):
            self.request(sig)

        self._installed[signum] = _signal.signal(signum, handler)

    def uninstall(self) -> None:
        while self._installed:
            signum, prev = self._installed.popitem()
            _signal.signal(signum, prev)


# -- the elastic driver loop --------------------------------------------------


@dataclasses.dataclass
class ElasticComponents:
    """What a plan factory returns: a guard wired to the trainer's
    checkpoint manager plus freshly-initialized state in THIS plan's
    layout.  ``optimizer`` is the instance
    :func:`reshard_optimizer_state` should reason about (the ZeRO inner
    optimizer when the guard holds a :class:`ZeROGuardAdapter`;
    defaults to ``guard.optimizer``).  ``transform(tree, old_plan)``
    maps a params-shaped tree from ``old_plan``'s layout into this
    plan's (``None`` = layouts agree, e.g. pure dp changes)."""
    guard: GuardedTrainStep
    params: Any
    opt_state: Any
    guard_state: Any
    scaler_state: Any = None
    optimizer: Any = None
    transform: Optional[Callable[[Any, ElasticPlan], Any]] = None

    def reshard_optimizer(self):
        return self.optimizer if self.optimizer is not None \
            else self.guard.optimizer


class ElasticTrainer:
    """Signal-driven elastic training around :class:`GuardedTrainStep`.

    ``factory(plan, checkpoint, fault_injector) -> ElasticComponents``
    builds (and implicitly compiles, on first step) everything a
    topology needs; the trainer owns the plan lifecycle::

        RUNNING --signal--> DRAIN (async saves) --> CHECKPOINT (old
        topology) --> REPLAN (factory on the new plan) --> RESHARD
        (params/optimizer/guard/scaler onto the new mesh) -->
        CHECKPOINT (new topology) --> RUNNING (recompile on first step)

    Signals come from the injector's deterministic ``topology_change``
    faults and from a :class:`HostSignals` mailbox; a hard
    :class:`~apex_tpu.resilience.faults.Preemption` propagates
    uncaught, and the NEXT trainer run auto-resumes: the manifest's
    stamped :class:`TopologySpec` picks the restore layout, the restore
    warns about the mismatch, and the state re-shards onto this
    trainer's plan before the first step.  The post-reshard checkpoint
    keeps the guard's K-anomaly rollback inside the current topology —
    a shrinking pod never resumes from (or into) a stale layout.

    Observability: ``elastic_preempt_signals`` / ``elastic_replans``
    counters, the ``elastic_reshard_seconds`` histogram and the
    ``elastic_resume_step`` gauge on ``registry``; ``elastic/replan``
    and ``elastic/restore`` spans (plus signal instants) on ``tracer``
    — a replan shows up on the same Perfetto timeline as the train
    steps around it.
    """

    def __init__(self, factory, plan: ElasticPlan, *, directory: str,
                 fault_injector=None, signals: Optional[HostSignals] = None,
                 registry=None, tracer=None, recorder=None, keep: int = 3,
                 save_every: int = 1, devices=None,
                 clock: Callable[[], float] = time.perf_counter):
        from apex_tpu.resilience.checkpoint import CheckpointManager

        self.factory = factory
        self.plan = plan
        self._base_spec = plan.spec
        self.fault_injector = fault_injector
        self.signals = signals
        self.tracer = tracer
        # optional flight recorder (fleetobs.FlightRecorder): per-step
        # entries feed its "trainer" ring; a guard rollback cuts a
        # correlated snapshot — the training-side black-box trigger
        self.recorder = recorder
        self.save_every = max(1, int(save_every))
        self.clock = clock
        self._devices = (list(devices) if devices is not None
                         else list(plan.mesh.devices.flat))
        self.checkpoint = CheckpointManager(
            directory, keep=keep, fault_injector=fault_injector,
            topology=plan.spec, parallel_plan=plan.parallel)
        self._comp: Optional[ElasticComponents] = None
        self._params = self._opt = self._gstate = self._sstate = None
        self._preempt_requested = False
        self._step = 0
        self.stats = {"replans": 0, "preempt_signals": 0,
                      "resume_step": 0, "last_checkpoint_s": 0.0,
                      "last_reshard_s": 0.0}
        self._c_signals = self._c_replans = None
        self._h_reshard = self._g_resume = None
        if registry is not None:
            self._c_signals = registry.counter(
                "elastic_preempt_signals",
                "preemption/arrival signals received")
            self._c_replans = registry.counter(
                "elastic_replans", "topology re-plans executed")
            self._h_reshard = registry.histogram(
                "elastic_reshard_seconds",
                "checkpoint+rebuild+reshard wall time per re-plan")
            self._g_resume = registry.gauge(
                "elastic_resume_step",
                "step training (re)started from after the last "
                "restore/re-plan")

    # -- small observability helpers ----------------------------------------

    def _span(self, name: str, **args):
        return span(name, tracer=self.tracer, **args)

    def _signal_seen(self, step: int, kind: str) -> None:
        self.stats["preempt_signals"] += 1
        if self._c_signals is not None:
            self._c_signals.inc()
        if self.tracer is not None:
            self.tracer.instant("elastic/signal", step=step, kind=kind)
        if self.recorder is not None:
            self.recorder.record("trainer", "signal", step=step,
                                 kind=kind)

    def _resumed_at(self, step: int) -> None:
        self.stats["resume_step"] = int(step)
        if self._g_resume is not None:
            self._g_resume.set(int(step))

    # -- component lifecycle -------------------------------------------------

    def _build(self, plan: ElasticPlan,
               injector="inherit") -> ElasticComponents:
        inj = self.fault_injector if injector == "inherit" else injector
        comp = self.factory(plan, self.checkpoint, inj)
        if comp.guard.checkpoint is not self.checkpoint:
            raise ValueError(
                "the factory must attach the trainer's CheckpointManager "
                "to the guard (guard.checkpoint is the rollback store)")
        return comp

    def _adopt(self, comp: ElasticComponents, state: dict) -> None:
        self._comp = comp
        self._params = state["params"]
        self._opt = state["opt"]
        self._gstate = state["guard"]
        self._sstate = state.get("scaler")

    def _save(self, step: int) -> None:
        self._comp.guard.save(step, self._params, self._opt, self._gstate,
                              self._sstate)

    def _reshard_onto(self, old_plan: ElasticPlan,
                      old_comp: ElasticComponents, new_plan: ElasticPlan,
                      new_comp: ElasticComponents) -> None:
        tr = None
        if new_comp.transform is not None:
            tr = lambda t: new_comp.transform(t, old_plan)  # noqa: E731
        old_params = self._params
        new_params = tr(old_params) if tr is not None else old_params
        self._params = new_plan.put(new_params)
        self._opt = reshard_optimizer_state(
            self._opt, old_plan, new_plan,
            optimizer=old_comp.reshard_optimizer(), params=old_params,
            new_optimizer=new_comp.reshard_optimizer(),
            new_params=new_params, transform=tr)
        self._gstate = new_plan.put(self._gstate)
        if self._sstate is not None:
            self._sstate = new_plan.put(self._sstate)

    # -- restore / replan ----------------------------------------------------

    def _restore_or_init(self, resume: bool) -> int:
        if not resume or self.checkpoint.latest_step() is None:
            comp = self._build(self.plan)
            self._adopt(comp, {"params": comp.params, "opt": comp.opt_state,
                               "guard": comp.guard_state,
                               "scaler": comp.scaler_state})
            self._resumed_at(0)
            return 0
        saved = self.checkpoint.topology_of(self.checkpoint.latest_step())
        saved_spec = (TopologySpec.from_dict(saved) if saved
                      else self.plan.spec)
        with self._span("elastic/restore"):
            if saved_spec == self.plan.spec:
                comp = self._build(self.plan)
                template = GuardedTrainStep._template(
                    comp.params, comp.opt_state, comp.guard_state,
                    comp.scaler_state)
                restored, _ = self.checkpoint.restore(
                    template, topology=self.plan.spec)
                self._adopt(comp, restored)
                step = int(np.asarray(restored["step"]))
                # identity re-partition: places every leaf (params AND
                # optimizer slots) consistently on this plan's mesh —
                # per-leaf init templates carry default single-device
                # placements that the restore would otherwise keep
                self._params = self.plan.put(self._params)
                self._opt = reshard_optimizer_state(
                    self._opt, self.plan, self.plan,
                    optimizer=comp.reshard_optimizer(),
                    params=self._params)
                self._gstate = self.plan.put(self._gstate)
                if self._sstate is not None:
                    self._sstate = self.plan.put(self._sstate)
            else:
                # restore onto the SAVED topology's layout, then re-plan
                # onto ours — the restart half of a shrink/grow cycle
                old_plan = ElasticPlan.build(saved_spec,
                                             devices=self._devices)
                old_comp = self._build(old_plan, injector=None)
                template = GuardedTrainStep._template(
                    old_comp.params, old_comp.opt_state,
                    old_comp.guard_state, old_comp.scaler_state)
                restored, _ = self.checkpoint.restore(
                    template, topology=self.plan.spec)
                self._adopt(old_comp, restored)
                step = int(np.asarray(restored["step"]))
                target = (self.plan.parallel
                          if self.plan.parallel is not None
                          else self.plan.spec)
                self._replan(target, step, from_plan=old_plan,
                             checkpoint_first=False)
        self._resumed_at(step)
        return step

    def _replan(self, new_spec, step: int, *,
                from_plan: Optional[ElasticPlan] = None,
                checkpoint_first: bool = True) -> None:
        t0 = self.clock()
        old_plan = from_plan if from_plan is not None else self.plan
        old_comp = self._comp
        with self._span("elastic/replan", step=step,
                        old=old_plan.spec.describe(),
                        new=new_spec.describe()):
            if checkpoint_first:
                # drain in-flight async writes, then a boundary
                # checkpoint stamped with the OLD topology — the state a
                # hard kill mid-reshard falls back to
                self.checkpoint.wait()
                self._save(step)
            t_ck = self.clock()
            new_plan = ElasticPlan.build(new_spec, devices=self._devices)
            self.checkpoint.topology = new_plan.spec
            self.checkpoint.parallel_plan = new_plan.parallel
            try:
                new_comp = self._build(new_plan)
                self._reshard_onto(old_plan, old_comp, new_plan,
                                   new_comp)
            except Exception:
                # a failed build/re-shard must leave the manifest
                # stamped with the topology the live state still has —
                # otherwise the next save (or a crash-restart restore)
                # would claim a layout that never materialized
                self.checkpoint.topology = old_plan.spec
                self.checkpoint.parallel_plan = old_plan.parallel
                raise
            self._comp, self.plan = new_comp, new_plan
            # post-reshard checkpoint in the NEW layout: the guard's
            # K-anomaly rollback must never restore an old-topology
            # layout into the new mesh
            self._save(step)
        dt = self.clock() - t0
        self.stats["replans"] += 1
        self.stats["last_checkpoint_s"] = t_ck - t0
        self.stats["last_reshard_s"] = dt - (t_ck - t0)
        if self._c_replans is not None:
            self._c_replans.inc()
        if self._h_reshard is not None:
            self._h_reshard.observe(dt)
        if self.recorder is not None:
            self.recorder.record("trainer", "replan", step=step,
                                 old=old_plan.spec.describe(),
                                 new=new_spec.describe(),
                                 reshard_s=dt)
        self._resumed_at(step)

    # -- signal polling ------------------------------------------------------

    def _auto_spec(self, magnitude: float) -> TopologySpec:
        """Target spec for an injected ``topology_change``: magnitude >
        0 names the new dp; 0 toggles shrink-to-half / grow-to-base."""
        cur = self.plan.spec
        if magnitude > 0:
            new_dp = int(magnitude)
        else:
            new_dp = (max(1, cur.dp // 2) if cur.dp == self._base_spec.dp
                      else self._base_spec.dp)
        zero = new_dp if cur.zero_shard > 1 else 1
        return dataclasses.replace(cur, dp=new_dp, zero_shard=zero)

    def _poll_signals(self, step: int):
        target = None
        inj = self.fault_injector
        if inj is not None:
            fault = inj.check_topology_change(step)
            if fault is not None:
                self._signal_seen(step, "topology_change")
                target = self._auto_spec(fault.magnitude)
        if self.signals is not None:
            sig = self.signals.poll()
            while sig is not None:
                self._signal_seen(step, sig.kind)
                if sig.kind == "preempt":
                    self._preempt_requested = True
                else:
                    target = sig.spec
                sig = self.signals.poll()
        return target

    # -- the loop ------------------------------------------------------------

    def start(self, resume: bool = True) -> int:
        """Build — or restore, with ``resume`` and a checkpoint present
        — the live components.  Idempotent: once the trainer is live
        this is a no-op, so external drivers (the capacity controller,
        :meth:`step_once` callers) can call it freely.  Returns the
        current step."""
        if self._comp is None:
            self._step = self._restore_or_init(resume)
        return self._step

    @property
    def current_step(self) -> int:
        """The step the next :meth:`step_once` will run."""
        return self._step

    def replan_to(self, new_spec, *, checkpoint_first: bool = True) -> None:
        """Synchronous externally-driven re-plan to ``new_spec``
        (:class:`TopologySpec` or ``ParallelPlan``) at the current step
        boundary — the capacity controller's drain-training primitive.
        The boundary checkpoint inside :meth:`_replan` IS the drain;
        failures propagate so the caller can roll back (the checkpoint
        stamp is already restored by then)."""
        self.start()
        self._replan(new_spec, self._step,
                     checkpoint_first=checkpoint_first)

    def step_once(self, batch_fn) -> str:
        """Advance exactly one guarded step (after signal polling).
        Returns ``"ran"``, or ``"preempted"`` when a preempt signal
        checkpointed and stopped the trainer instead.  This is
        :meth:`train`'s loop body exposed so an external driver can
        interleave training steps with fleet ticks."""
        self.start()
        step = self._step
        target = self._poll_signals(step)
        if self._preempt_requested:
            self.checkpoint.wait()
            self._save(step)
            self._preempt_requested = False
            return "preempted"
        if target is not None:
            # a target equal to the current spec is an IN-PLACE
            # rebuild (checkpoint, recompile, identity re-partition)
            # — the device-swap case where counts survive but the
            # hardware underneath changed
            self._replan(target, step)
        comp = self._comp
        res = comp.guard(self._params, self._opt, self._gstate,
                         *batch_fn(step, self.plan),
                         scaler_state=self._sstate, step=step)
        self._params, self._opt = res.params, res.opt_state
        self._gstate, self._sstate = res.guard_state, res.scaler_state
        step = res.next_step
        if self.recorder is not None:
            self.recorder.record("trainer", "step", step=step,
                                 loss=float(res.loss_value),
                                 rolled_back=bool(res.rolled_back))
            if res.rolled_back:
                self.recorder.trigger("guard_rollback", step=step,
                                      loss=float(res.loss_value))
        if step % self.save_every == 0 or res.rolled_back:
            self._save(step)
        self._step = step
        return "ran"

    def train(self, batch_fn, n_steps: int, *, resume: bool = True) -> dict:
        """Run up to ``n_steps`` guarded steps, reacting to signals.

        ``batch_fn(step, plan) -> batch args`` supplies data laid out
        for the CURRENT plan (a constant global batch across plans is
        what makes dp transitions comparable).  Returns a summary dict;
        the live state stays readable as :attr:`params` /
        :attr:`opt_state` / :attr:`guard_state` / :attr:`scaler_state`.
        A hard :class:`Preemption` propagates to the caller — restart
        semantics are a fresh trainer with ``resume=True`` (the
        default), which restores the stamped topology and re-shards.
        """
        self.start(resume)
        status = "completed"
        while self._step < n_steps:
            if self.step_once(batch_fn) == "preempted":
                status = "preempted"
                break
        self._final_step = self._step
        return {"status": status, "step": self._step,
                "replans": self.stats["replans"],
                "preempt_signals": self.stats["preempt_signals"],
                "rollbacks": (self._comp.guard.counters["rollbacks"]
                              if self._comp else 0)}

    # -- live state ----------------------------------------------------------

    @property
    def params(self):
        return self._params

    @property
    def opt_state(self):
        return self._opt

    @property
    def guard_state(self):
        return self._gstate

    @property
    def scaler_state(self):
        return self._sstate

    @property
    def guard(self) -> Optional[GuardedTrainStep]:
        return self._comp.guard if self._comp is not None else None
