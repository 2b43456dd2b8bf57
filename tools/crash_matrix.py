#!/usr/bin/env python
"""Kill-matrix sweep for the resilience stack (ISSUE 4 satellite).

``tests/test_resilience.py`` proves
kill-and-resume parity at ONE kill step; this tool sweeps the full
matrix — every kill step x every fault kind — and prints one PASS/FAIL
cell per combination:

* ``preempt``           — :class:`Preemption` raised before the kill
  step runs; a fresh manager restores the latest complete checkpoint
  and the resumed run must match the uninterrupted run BITWISE (f32
  params and optimizer slots) after ``--steps`` total steps.
* ``corrupt``           — same preemption, but the latest checkpoint's
  payload is also torn post-commit; restore must detect the sha256
  mismatch, fall back one step, and the resumed run (replaying the
  lost step) must STILL be bitwise identical.
* ``nan`` / ``inf`` / ``spike`` — the anomaly fires AT the kill step
  instead of a preemption; the guard must skip exactly that one update
  (optimizer state stays consistent) and the run must finish with
  finite parameters.

Runs on the fake 8-device CPU mesh by default (same two-lane contract
as ``tests/conftest.py``); ``APEX_TPU_ON_CHIP=1`` leaves the real
backend in place.  ``--sp`` adds the dp=2 x tp=2 sequence-parallel GPT
component next to the default dp=2 data-parallel one; ``--pp`` adds the
ring-pipeline components — dp=2 x pp=2 and tp2 x pp=2 + SP — whose
grad_fn is the 1F1B ``pipeline_step`` scan under shard_map.

``--topology`` sweeps the ELASTIC kill-step x topology matrix instead
(ISSUE 9): each cell schedules a ``topology_change`` at the kill step
(the pod shrinks; the step runs on the new plan) and a hard
``preempt_at_step`` one step later, then restarts a fresh
:class:`~apex_tpu.resilience.elastic.ElasticTrainer` on the cell's
restart topology — restoring the shrunken-topology checkpoint,
re-sharding, and finishing.  Transitions and what each asserts:

* ``dp8->dp4->dp8``    per-leaf FusedAdam, replicated batch, no
  collectives: gradient math is topology-invariant, so params AND
  every optimizer slot must match the uninterrupted run BITWISE.
* ``zero4->zero2->zero4``  ZeRO (DistributedFusedAdam) reduce-scatter
  shards re-partitioned across the world-size change: the LOGICAL f32
  moments/master weights must match BITWISE (the packed padding moves;
  the values may not).  World sizes pinned to {2, 4}: XLA CPU's
  reduction of identical per-replica copies is pairwise-exact up to 4
  participants but not at 8 (measured), so an 8-way ZeRO transition is
  trajectory-equivalent, not bitwise, on this backend.
* ``dp2xtp2+sp->dp4``  the TP dimension collapses into dp; TP grads
  differ from serial at rounding level (~1e-7), so this cell is the
  documented TRAJECTORY-EQUIVALENT one: unpacked serial params must
  be allclose, not bitwise.
* ``dp2xpp2->dp4->dp2xpp2``  pipeline on -> off -> on via
  ``pipeline_step`` at pp=2 and pp=1 (pp=1 is the bitwise reference
  schedule), replicated batch: BITWISE.

Usage::

    python tools/crash_matrix.py [--steps 5] [--sp] [--pp] [--topology]
"""

from __future__ import annotations

import argparse
import os
import shutil
import sys
import tempfile
import warnings

# env must be set before jax initializes (see tests/conftest.py)
ON_CHIP = os.environ.get("APEX_TPU_ON_CHIP") == "1"
if not ON_CHIP:
    os.environ["JAX_PLATFORMS"] = "cpu"
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8").strip()

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402

if not ON_CHIP:
    jax.config.update("jax_platforms", "cpu")

import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import NamedSharding  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

from apex_tpu.models.gpt import (GPTConfig, GPTModel,  # noqa: E402
                                 pack_for_shard_map)
from apex_tpu.optimizers import FusedAdam  # noqa: E402
from apex_tpu.resilience import (CheckpointManager,  # noqa: E402
                                 CheckpointNotFound, Fault, FaultInjector,
                                 GuardedTrainStep, Preemption)

ANOMALY_KINDS = {"nan": "nan_grads", "inf": "inf_loss",
                 "spike": "grad_spike"}


def _tree_bitwise(a, b) -> bool:
    for x, y in zip(jax.tree_util.tree_leaves(a),
                    jax.tree_util.tree_leaves(b), strict=True):
        if not np.array_equal(np.asarray(x), np.asarray(y)):
            return False
    return True


def _drive(guard, params, opt_state, gstate, batch_fn, n_steps,
           start=0):
    step = start
    while step < n_steps:
        x, y = batch_fn(step)
        res = guard(params, opt_state, gstate, x, y, step=step)
        params, opt_state, gstate = (res.params, res.opt_state,
                                     res.guard_state)
        step = res.next_step
        guard.save(step, params, opt_state, gstate)
    return params, opt_state


def _run_cell(make_parts, batch_fn, n_steps, kill_at, fault, ref):
    """One matrix cell; returns (ok, detail)."""
    root = tempfile.mkdtemp(prefix="apex_tpu_crash_")
    try:
        if fault in ANOMALY_KINDS:
            # anomaly at kill_at: no restart — the guard must skip
            # exactly that one update and the run must end finite
            inj = FaultInjector([Fault(step=kill_at,
                                       kind=ANOMALY_KINDS[fault],
                                       magnitude=1e6)])
            guard, params, opt_state, gstate = make_parts(root, inj)
            got_p, _ = _drive(guard, params, opt_state, gstate,
                              batch_fn, n_steps)
            if guard.counters["skipped"] != 1:
                return False, f"skipped={guard.counters['skipped']}"
            for leaf in jax.tree_util.tree_leaves(got_p):
                if not np.all(np.isfinite(np.asarray(leaf))):
                    return False, "non-finite params leaked through"
            return True, f"skipped@{kill_at}"

        faults = [Fault(step=kill_at, kind="preempt_at_step")]
        if fault == "corrupt":
            # tear the last checkpoint that commits before the kill
            faults.append(Fault(step=kill_at, kind="corrupt_checkpoint"))
        inj = FaultInjector(faults)
        guard, params, opt_state, gstate = make_parts(root, inj)
        try:
            _drive(guard, params, opt_state, gstate, batch_fn, n_steps)
            return False, "preemption did not fire"
        except Preemption:
            pass

        # fresh restart: only the checkpoint directory survives
        guard2, p0, o0, g0 = make_parts(root, None)
        try:
            with warnings.catch_warnings():
                warnings.simplefilter("ignore")  # corruption noise
                restored, ck_step = guard2.checkpoint.restore(
                    guard2._template(p0, o0, g0, None))
            start = int(np.asarray(restored["step"]))
            p, o, g = (restored["params"], restored["opt"],
                       restored["guard"])
        except CheckpointNotFound:
            # every candidate torn (corrupt at kill@1): start over —
            # the init state is deterministic, so parity must still hold
            ck_step, start, p, o, g = 0, 0, p0, o0, g0
        expect = kill_at - 1 if fault == "corrupt" else kill_at
        if ck_step != expect:
            return False, f"resumed@{ck_step}, expected {expect}"
        got_p, got_o = _drive(guard2, p, o, g, batch_fn, n_steps,
                              start=start)
    finally:
        shutil.rmtree(root, ignore_errors=True)
    if not _tree_bitwise(got_p, ref[0]):
        return False, "params diverged"
    if not _tree_bitwise(got_o, ref[1]):
        return False, "opt slots diverged"
    return True, f"resume@{ck_step} bitwise"


def _component_dp2():
    mesh = jax.make_mesh((2,), ("data",), devices=jax.devices()[:2])

    def loss_fn(p, x, y):
        return jnp.mean(jnp.square(x @ p["w"] + p["b"] - y))

    def body(p, x, y):
        loss, g = jax.value_and_grad(loss_fn)(p, x, y)
        return (jax.lax.pmean(loss, "data"),
                jax.tree_util.tree_map(
                    lambda a: jax.lax.pmean(a, "data"), g))

    grad_fn = jax.shard_map(body, mesh=mesh,
                            in_specs=(P(), P("data"), P("data")),
                            out_specs=(P(), P()), check_vma=False)

    def make_parts(ckpt_dir, injector):
        opt = FusedAdam(lr=1e-2)
        guard = GuardedTrainStep(
            grad_fn=grad_fn, optimizer=opt, warmup_steps=1,
            checkpoint=CheckpointManager(ckpt_dir, keep=3,
                                         fault_injector=injector),
            fault_injector=injector)
        r = np.random.RandomState(0)
        rep = NamedSharding(mesh, P())
        params = jax.device_put(
            {"w": jnp.asarray(r.randn(8, 4).astype(np.float32)),
             "b": jnp.zeros((4,), jnp.float32)}, rep)
        return (guard, params, jax.device_put(opt.init(params), rep),
                jax.device_put(guard.init_state(), rep))

    def batch_fn(step):
        r = np.random.RandomState(50_000 + step)
        return (jnp.asarray(r.randn(8, 8).astype(np.float32)),
                jnp.asarray(r.randn(8, 4).astype(np.float32)))

    return make_parts, batch_fn


def _component_dp2tp2_sp():
    kw = dict(vocab_size=32, hidden_size=16, num_layers=2,
              num_attention_heads=4, max_seq_len=8)
    par = GPTModel(GPTConfig(tensor_parallel_size=2, axis_name="model",
                             sequence_parallel=True, **kw))
    init = GPTModel(GPTConfig(**kw)).init_params(jax.random.PRNGKey(9))
    mesh = jax.make_mesh((2, 2), ("data", "model"),
                         devices=jax.devices()[:4])
    packed, in_specs, local_fn, repack_fn = pack_for_shard_map(par, init)

    def body(sp, tk, tg):
        loss, g = jax.value_and_grad(par.loss)(local_fn(sp), tk, tg)
        return (jax.lax.pmean(loss, "data"),
                jax.tree_util.tree_map(
                    lambda a: jax.lax.pmean(a, "data"), repack_fn(g)))

    grad_fn = jax.shard_map(body, mesh=mesh,
                            in_specs=(in_specs, P("data"), P("data")),
                            out_specs=(P(), in_specs), check_vma=False)

    def make_parts(ckpt_dir, injector):
        opt = FusedAdam(lr=1e-2)
        guard = GuardedTrainStep(
            grad_fn=grad_fn, optimizer=opt, warmup_steps=1,
            checkpoint=CheckpointManager(ckpt_dir, keep=3,
                                         fault_injector=injector),
            fault_injector=injector)
        rep = NamedSharding(mesh, P())
        p = jax.device_put(packed, rep)
        return (guard, p, jax.device_put(opt.init(p), rep),
                jax.device_put(guard.init_state(), rep))

    def batch_fn(step):
        r = np.random.RandomState(50_000 + step)
        return (jnp.asarray(r.randint(0, 32, (4, 8))),
                jnp.asarray(r.randint(0, 32, (4, 8))))

    return make_parts, batch_fn


def _component_dp2pp2():
    from apex_tpu.models.gpt import pipeline_step

    model = GPTModel(GPTConfig(vocab_size=32, hidden_size=16,
                               num_layers=2, num_attention_heads=4,
                               max_seq_len=8))
    init = model.init_params(jax.random.PRNGKey(7))
    mesh = jax.make_mesh((2, 2), ("data", "pipe"),
                         devices=jax.devices()[:4])
    packed, in_specs, local_fn, repack_fn = pack_for_shard_map(
        model, init, n_stages=2, tensor_axis=None)
    M, mb, seq = 2, 2, 8

    def body(sp, tk, tg):
        # pipeline_step reduces loss/grads over data_axis itself
        loss, g = pipeline_step(model, local_fn(sp),
                                tk.reshape(M, mb, seq),
                                tg.reshape(M, mb, seq),
                                pipe_axis="pipe", data_axis="data")
        return loss, repack_fn(g)

    grad_fn = jax.shard_map(body, mesh=mesh,
                            in_specs=(in_specs, P("data"), P("data")),
                            out_specs=(P(), in_specs), check_vma=False)

    def make_parts(ckpt_dir, injector):
        opt = FusedAdam(lr=1e-2)
        guard = GuardedTrainStep(
            grad_fn=grad_fn, optimizer=opt, warmup_steps=1,
            checkpoint=CheckpointManager(ckpt_dir, keep=3,
                                         fault_injector=injector),
            fault_injector=injector)
        rep = NamedSharding(mesh, P())
        p = jax.device_put(packed, rep)
        return (guard, p, jax.device_put(opt.init(p), rep),
                jax.device_put(guard.init_state(), rep))

    def batch_fn(step):
        r = np.random.RandomState(50_000 + step)
        return (jnp.asarray(r.randint(0, 32, (2 * M * mb, seq))),
                jnp.asarray(r.randint(0, 32, (2 * M * mb, seq))))

    return make_parts, batch_fn


def _component_tp2pp2_sp():
    from apex_tpu.models.gpt import pipeline_step

    kw = dict(vocab_size=32, hidden_size=16, num_layers=2,
              num_attention_heads=4, max_seq_len=8)
    # the ring pipeline's TP composition requires sequence parallelism
    par = GPTModel(GPTConfig(tensor_parallel_size=2, axis_name="model",
                             sequence_parallel=True, **kw))
    init = GPTModel(GPTConfig(**kw)).init_params(jax.random.PRNGKey(9))
    mesh = jax.make_mesh((2, 2), ("model", "pipe"),
                         devices=jax.devices()[:4])
    packed, in_specs, local_fn, repack_fn = pack_for_shard_map(
        par, init, n_stages=2, tensor_axis="model")
    M, mb, seq = 2, 2, 8

    def body(sp, tk, tg):
        loss, g = pipeline_step(par, local_fn(sp),
                                tk.reshape(M, mb, seq),
                                tg.reshape(M, mb, seq),
                                pipe_axis="pipe")
        return loss, repack_fn(g)

    grad_fn = jax.shard_map(body, mesh=mesh,
                            in_specs=(in_specs, P(), P()),
                            out_specs=(P(), in_specs), check_vma=False)

    def make_parts(ckpt_dir, injector):
        opt = FusedAdam(lr=1e-2)
        guard = GuardedTrainStep(
            grad_fn=grad_fn, optimizer=opt, warmup_steps=1,
            checkpoint=CheckpointManager(ckpt_dir, keep=3,
                                         fault_injector=injector),
            fault_injector=injector)
        rep = NamedSharding(mesh, P())
        p = jax.device_put(packed, rep)
        return (guard, p, jax.device_put(opt.init(p), rep),
                jax.device_put(guard.init_state(), rep))

    def batch_fn(step):
        r = np.random.RandomState(50_000 + step)
        return (jnp.asarray(r.randint(0, 32, (M * mb, seq))),
                jnp.asarray(r.randint(0, 32, (M * mb, seq))))

    return make_parts, batch_fn


# -- elastic topology matrix (ISSUE 9) ---------------------------------------

def _toggle_trainer(shrink_spec):
    """An :class:`ElasticTrainer` whose injected ``topology_change``
    toggles base <-> the cell's shrink spec (the stock auto-toggle only
    moves dp; these cells also move tp/pp/zero)."""
    from apex_tpu.resilience import ElasticTrainer

    class _Toggle(ElasticTrainer):
        def _auto_spec(self, magnitude):
            return (shrink_spec if self.plan.spec == self._base_spec
                    else self._base_spec)

    return _Toggle


def _flat_state(trainer):
    """Params + per-leaf optimizer slots, flattened deterministically."""
    out = list(jax.tree_util.tree_leaves(trainer.params))
    st = trainer.opt_state
    for key in sorted(st["buckets"]):
        for slot in sorted(st["buckets"][key]):
            v = st["buckets"][key][slot]
            out.extend(v if isinstance(v, list) else [v])
    return [np.asarray(x) for x in out]


def _topo_component_dp8():
    """dp=8 -> dp=4 -> dp=8, per-leaf FusedAdam: bitwise."""
    from apex_tpu.resilience import ElasticComponents, TopologySpec

    base, shrink = TopologySpec(dp=8), TopologySpec(dp=4)

    def loss_fn(p, x, y):
        return jnp.mean(jnp.square(x @ p["w"] + p["b"] - y))

    def factory(plan, ckpt, inj):
        opt = FusedAdam(lr=1e-2)
        guard = GuardedTrainStep(loss_fn, opt, warmup_steps=1,
                                 checkpoint=ckpt, fault_injector=inj)
        r = np.random.RandomState(0)
        params = plan.put(
            {"w": jnp.asarray(r.randn(8, 4).astype(np.float32)),
             "b": jnp.zeros((4,), jnp.float32)})
        return ElasticComponents(guard, params, opt.init(params),
                                 guard.init_state())

    def batch_fn(step, plan):
        r = np.random.RandomState(50_000 + step)
        return (jnp.asarray(r.randn(8, 8).astype(np.float32)),
                jnp.asarray(r.randn(8, 4).astype(np.float32)))

    return dict(base=base, shrink=shrink, restart=base, factory=factory,
                batch_fn=batch_fn, canon=_flat_state,
                compare="bitwise", n_dev=8)


def _topo_component_zero():
    """ZeRO dp=4/ws=4 -> dp=2/ws=2 -> dp=4/ws=4: logical state bitwise."""
    from apex_tpu.multi_tensor_apply import bucketing as B
    from apex_tpu.parallel import DistributedFusedAdam
    from apex_tpu.resilience import (ElasticComponents, TopologySpec,
                                     ZeROGuardAdapter)

    base = TopologySpec(dp=4, zero_shard=4)
    shrink = TopologySpec(dp=2, zero_shard=2)

    def loss_fn(p, x, y):
        return jnp.mean(jnp.square(x @ p["w"] + p["b"] - y))

    def _params(plan):
        r = np.random.RandomState(1)
        return plan.put(
            {"w": jnp.asarray((r.randn(8, 4) * 0.1).astype(np.float32)),
             "b": jnp.zeros((4,), jnp.float32)})

    def factory(plan, ckpt, inj):
        inner = DistributedFusedAdam(lr=1e-2,
                                     world_size=plan.spec.zero_shard,
                                     axis_name="data", block_rows=8)
        adapter = ZeROGuardAdapter(inner, plan.mesh)
        guard = GuardedTrainStep(loss_fn, adapter, warmup_steps=1,
                                 checkpoint=ckpt, fault_injector=inj)
        params = _params(plan)
        return ElasticComponents(guard, params, adapter.init(params),
                                 guard.init_state(), optimizer=inner)

    def batch_fn(step, plan):
        r = np.random.RandomState(50_000 + step)
        return (jnp.asarray(r.randn(8, 8).astype(np.float32)),
                jnp.asarray(r.randn(8, 4).astype(np.float32)))

    def canon(trainer):
        # compare LOGICAL leaves: the packed padding depends on the
        # world size, the values must not
        opt = DistributedFusedAdam(lr=1e-2, world_size=base.zero_shard,
                                   axis_name="data", block_rows=8)
        lay = opt._layout(trainer.params)
        out = [np.asarray(x)
               for x in jax.tree_util.tree_leaves(trainer.params)]
        st = trainer.opt_state
        for info in lay.buckets:
            for slot in sorted(st["buckets"][info.key]):
                arr = jnp.asarray(np.asarray(st["buckets"][info.key][slot]))
                out.extend(np.asarray(x) for x in B.unflatten_bucket(
                    arr, info.meta._replace(dtype=jnp.float32)))
        return out

    return dict(base=base, shrink=shrink, restart=base, factory=factory,
                batch_fn=batch_fn, canon=canon, compare="bitwise", n_dev=4)


def _topo_component_tp_collapse():
    """dp=2 x tp=2 + SP -> dp=4 serial: trajectory-equivalent.

    TP matmul partial sums round differently from the serial product
    (~1e-7 per step), so after the collapse the run tracks the
    uninterrupted dp2xtp2 reference to allclose tolerance, not bitwise
    — the documented data-order/reduction-order cell of the matrix.
    """
    from apex_tpu.models.gpt import unpack_from_shard_map
    from apex_tpu.resilience import ElasticComponents, TopologySpec

    kw = dict(vocab_size=32, hidden_size=16, num_layers=2,
              num_attention_heads=4, max_seq_len=8)
    serial = GPTModel(GPTConfig(**kw))
    par = GPTModel(GPTConfig(tensor_parallel_size=2, axis_name="model",
                             sequence_parallel=True, **kw))
    init = serial.init_params(jax.random.PRNGKey(9))
    base = TopologySpec(dp=2, tp=2, sequence_parallel=True)
    shrink = TopologySpec(dp=4)

    def factory(plan, ckpt, inj):
        opt = FusedAdam(lr=1e-2)
        if plan.spec.tp == 2:
            packed, in_specs, local_fn, repack_fn = pack_for_shard_map(
                par, init)

            def body(sp, tk, tg):
                loss, g = jax.value_and_grad(par.loss)(local_fn(sp),
                                                       tk, tg)
                return (jax.lax.pmean(loss, "data"),
                        jax.tree_util.tree_map(
                            lambda a: jax.lax.pmean(a, "data"),
                            repack_fn(g)))

            grad_fn = jax.shard_map(
                body, mesh=plan.mesh,
                in_specs=(in_specs, P("data"), P("data")),
                out_specs=(P(), in_specs), check_vma=False)
            params = plan.put(packed)
            transform = None          # the cell never grows back to tp=2
        else:
            def body(p, tk, tg):
                loss, g = jax.value_and_grad(serial.loss)(p, tk, tg)
                return (jax.lax.pmean(loss, "data"),
                        jax.tree_util.tree_map(
                            lambda a: jax.lax.pmean(a, "data"), g))

            grad_fn = jax.shard_map(
                body, mesh=plan.mesh,
                in_specs=(P(), P("data"), P("data")),
                out_specs=(P(), P()), check_vma=False)
            params = plan.put(init)

            def transform(tree, old_plan):
                if old_plan.spec.tp == 2:
                    return unpack_from_shard_map(par, tree)
                return tree

        guard = GuardedTrainStep(grad_fn=grad_fn, optimizer=opt,
                                 warmup_steps=1, checkpoint=ckpt,
                                 fault_injector=inj)
        return ElasticComponents(guard, params, opt.init(params),
                                 guard.init_state(), transform=transform)

    def batch_fn(step, plan):
        r = np.random.RandomState(50_000 + step)
        return (jnp.asarray(r.randint(0, 32, (4, 8))),
                jnp.asarray(r.randint(0, 32, (4, 8))))

    def canon(trainer):
        p = trainer.params
        if trainer.plan.spec.tp == 2:
            p = unpack_from_shard_map(par, p)
        return [np.asarray(x) for x in jax.tree_util.tree_leaves(p)]

    return dict(base=base, shrink=shrink, restart=shrink, factory=factory,
                batch_fn=batch_fn, canon=canon, compare="allclose",
                n_dev=4)


def _topo_component_pp_toggle():
    """dp=2 x pp=2 -> dp=4 (pp off) -> dp=2 x pp=2: bitwise.

    Both plans run :func:`pipeline_step` — at pp=1 it is the bitwise
    reference schedule for pp=2 (PR 6 contract) — on a batch
    REPLICATED over the data axis, so the pmean folds identical copies
    (exact at 2 and 4 participants) and the whole cycle stays bitwise.
    """
    from apex_tpu.models.gpt import pipeline_step, unpack_from_shard_map
    from apex_tpu.resilience import ElasticComponents, TopologySpec

    model = GPTModel(GPTConfig(vocab_size=32, hidden_size=16,
                               num_layers=2, num_attention_heads=4,
                               max_seq_len=8))
    init = model.init_params(jax.random.PRNGKey(7))
    base = TopologySpec(dp=2, pp=2)
    shrink = TopologySpec(dp=4)
    M, mb, seq = 2, 2, 8

    def factory(plan, ckpt, inj):
        pp = plan.spec.pp
        packed, in_specs, local_fn, repack_fn = pack_for_shard_map(
            model, init, n_stages=pp, tensor_axis=None)

        def body(sp, tk, tg):
            loss, g = pipeline_step(model, local_fn(sp),
                                    tk.reshape(M, mb, seq),
                                    tg.reshape(M, mb, seq),
                                    pipe_axis="pipe", data_axis="data")
            return loss, repack_fn(g)

        grad_fn = jax.shard_map(body, mesh=plan.mesh,
                                in_specs=(in_specs, P(), P()),
                                out_specs=(P(), in_specs), check_vma=False)

        def transform(tree, old_plan):
            serial = unpack_from_shard_map(model, tree,
                                           n_stages=old_plan.spec.pp)
            return pack_for_shard_map(model, serial, n_stages=pp,
                                      tensor_axis=None)[0]

        opt = FusedAdam(lr=1e-2)
        guard = GuardedTrainStep(grad_fn=grad_fn, optimizer=opt,
                                 warmup_steps=1, checkpoint=ckpt,
                                 fault_injector=inj)
        params = plan.put(packed)
        return ElasticComponents(guard, params, opt.init(params),
                                 guard.init_state(), transform=transform)

    def batch_fn(step, plan):
        r = np.random.RandomState(50_000 + step)
        return (jnp.asarray(r.randint(0, 32, (M * mb, seq))),
                jnp.asarray(r.randint(0, 32, (M * mb, seq))))

    return dict(base=base, shrink=shrink, restart=base, factory=factory,
                batch_fn=batch_fn, canon=_flat_state, compare="bitwise",
                n_dev=4)


def _topo_cell(comp, kill_at, steps, ref_canon):
    """One elastic matrix cell: shrink@kill_at, hard kill one step
    later, restart on the cell's restart topology, compare against the
    uninterrupted reference.  Returns (ok, detail)."""
    from apex_tpu.resilience import ElasticPlan, ElasticTrainer

    root = tempfile.mkdtemp(prefix="apex_tpu_topo_")
    try:
        inj = FaultInjector([
            Fault(kill_at, "topology_change"),
            Fault(kill_at + 1, "preempt_at_step")])
        Toggle = _toggle_trainer(comp["shrink"])
        tr = Toggle(comp["factory"], ElasticPlan.build(comp["base"]),
                    directory=root, fault_injector=inj)
        try:
            tr.train(comp["batch_fn"], steps)
            return False, "preemption did not fire"
        except Preemption:
            pass
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")   # the mismatch warning is
            tr2 = ElasticTrainer(             # the expected path here
                comp["factory"], ElasticPlan.build(comp["restart"]),
                directory=root)
            out = tr2.train(comp["batch_fn"], steps)
        if out["step"] != steps:
            return False, f"restart ended at step {out['step']}"
        got = comp["canon"](tr2)
    finally:
        shutil.rmtree(root, ignore_errors=True)
    worst = 0.0
    for x, y in zip(ref_canon, got):
        if comp["compare"] == "bitwise":
            if not np.array_equal(x, y):
                return False, f"diverged, max|d|={np.abs(x - y).max():.3g}"
        else:
            worst = max(worst, float(np.abs(x - y).max()))
            if not np.allclose(x, y, rtol=2e-3, atol=1e-4):
                return False, f"beyond tolerance, max|d|={worst:.3g}"
    tag = ("bitwise" if comp["compare"] == "bitwise"
           else f"allclose max|d|={worst:.3g}")
    return True, tag


def _run_topology_matrix(steps: int) -> int:
    n_dev = len(jax.devices())
    builders = [("dp8->dp4->dp8", _topo_component_dp8),
                ("zero4->zero2->zero4", _topo_component_zero),
                ("dp2xtp2+sp->dp4", _topo_component_tp_collapse),
                ("dp2xpp2->dp4->dp2xpp2", _topo_component_pp_toggle)]
    failures = 0
    # kill_at runs the shrunken step; the hard kill lands one step
    # later, and the restart still needs >=1 step to run
    kill_steps = range(1, steps - 1)
    for name, build in builders:
        comp = build()
        if n_dev < comp["n_dev"]:
            print(f"\ncomponent: {name} — needs {comp['n_dev']} devices, "
                  f"have {n_dev}; skipped")
            continue
        from apex_tpu.resilience import ElasticPlan, ElasticTrainer
        ref_root = tempfile.mkdtemp(prefix="apex_tpu_topo_ref_")
        try:
            ref = ElasticTrainer(comp["factory"],
                                 ElasticPlan.build(comp["base"]),
                                 directory=ref_root)
            ref.train(comp["batch_fn"], steps)
            ref_canon = comp["canon"](ref)
        finally:
            shutil.rmtree(ref_root, ignore_errors=True)
        print(f"\ncomponent: {name}  ({steps} steps, "
              f"{comp['compare']} contract)")
        for k in kill_steps:
            ok, detail = _topo_cell(comp, k, steps, ref_canon)
            print(f"  shrink@{k} kill@{k + 1} restart@"
                  f"{comp['restart'].describe()}: "
                  f"{'PASS' if ok else 'FAIL'} ({detail})")
            if not ok:
                failures += 1
    print(f"\ncrash_matrix --topology: "
          f"{'OK' if failures == 0 else 'FAILED'} "
          f"({failures} failing cell(s))")
    return 1 if failures else 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--steps", type=int, default=5,
                    help="total train steps per run (default 5)")
    ap.add_argument("--sp", action="store_true",
                    help="also sweep the dp=2 x tp=2 + SP GPT component")
    ap.add_argument("--pp", action="store_true",
                    help="also sweep the ring-pipeline components: "
                         "dp=2 x pp=2 and tp=2 x pp=2 + SP")
    ap.add_argument("--topology", action="store_true",
                    help="sweep the elastic kill-step x topology matrix "
                         "(shrink, hard kill, restart+reshard) instead "
                         "of the fault-kind matrix")
    args = ap.parse_args(argv)

    n_dev = len(jax.devices())
    if n_dev < 2:
        print(f"crash_matrix: needs >=2 devices, have {n_dev} — skipped")
        return 0

    if args.topology:
        return _run_topology_matrix(args.steps)

    components = [("dp2", _component_dp2)]
    if args.sp:
        if n_dev < 4:
            print("crash_matrix: --sp needs >=4 devices — skipped")
        else:
            components.append(("dp2xtp2+sp", _component_dp2tp2_sp))
    if args.pp:
        if n_dev < 4:
            print("crash_matrix: --pp needs >=4 devices — skipped")
        else:
            components.append(("dp2xpp2", _component_dp2pp2))
            components.append(("tp2xpp2+sp", _component_tp2pp2_sp))

    faults = ["preempt", "corrupt", "nan", "inf", "spike"]
    kill_steps = range(1, args.steps)   # step 0 has no checkpoint yet
    failures = 0
    for name, build in components:
        make_parts, batch_fn = build()
        # the reference arm: one clean uninterrupted run per component
        guard, params, opt_state, gstate = make_parts(
            tempfile.mkdtemp(prefix="apex_tpu_crash_ref_"), None)
        ref = _drive(guard, params, opt_state, gstate, batch_fn,
                     args.steps)
        shutil.rmtree(guard.checkpoint.directory, ignore_errors=True)

        print(f"\ncomponent: {name}  ({args.steps} steps)")
        header = "kill@ " + "".join(f"{f:>10}" for f in faults)
        print(header)
        for k in kill_steps:
            cells = []
            for fault in faults:
                ok, detail = _run_cell(make_parts, batch_fn, args.steps,
                                       k, fault, ref)
                cells.append("PASS" if ok else "FAIL")
                if not ok:
                    failures += 1
                    print(f"  FAIL {name} kill@{k} {fault}: {detail}")
            print(f"{k:>5} " + "".join(f"{c:>10}" for c in cells))

    print(f"\ncrash_matrix: {'OK' if failures == 0 else 'FAILED'} "
          f"({failures} failing cell(s))")
    return 1 if failures else 0


if __name__ == "__main__":
    from apex_tpu.utils.platform import setup_compile_cache
    setup_compile_cache()
    sys.exit(main())
