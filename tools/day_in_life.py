#!/usr/bin/env python
"""Day-in-the-life capacity-shifting chaos sim (ROADMAP item 4).

One virtual day for a pod whose chip budget is SHARED between training
and serving: diurnal traffic (the ``capacity_diurnal`` loadgen
scenario) drives a fleet of paged engines while an
:class:`~apex_tpu.resilience.elastic.ElasticTrainer` trains on the
same budget, and a burn-driven
:class:`~apex_tpu.resilience.capacity.CapacityController` shifts chips
between them — under injected chaos:

* a ``capacity_change`` serving fault fails the FIRST shift mid-flight
  (partial mutation, then the recovery rollback; the retry commits);
* an injected hard :class:`~apex_tpu.resilience.faults.Preemption`
  kills the trainer mid-day; a fresh trainer restores the stamped
  topology and resumes;
* three consecutive ``nan_grads`` anomalies trigger the guard's
  K-anomaly rollback (``once=True``: the rolled-back re-run is clean).

Hard gates (the run FAILS unless every one holds):

* exactly-once serving delivery: ``lost == []`` and zero duplicates,
  across every migration, drain, replica add/remove and rollback;
* SLO attainment >= 0.9 over the virtual clock;
* the trainer finishes all its steps and its params + every optimizer
  slot match an UNINTERRUPTED fixed-capacity reference at the same
  step count BITWISE;
* at least one mid-shift-fault rollback AND >= 2 committed shifts;
* :meth:`CapacityController.audit` returns ``[]`` — no shift ever
  started inside the hysteresis band or before cooldown expiry;
* all leased capacity is returned: training ends at its base dp with
  zero outstanding leases.

Run directly (forces 4 XLA CPU devices when jax is not yet loaded)::

    python tools/day_in_life.py --json

or through the loadgen scenario suite (set
``XLA_FLAGS=--xla_force_host_platform_device_count=4`` first)::

    python tools/loadgen.py --scenario capacity_diurnal

``--autopilot`` runs the self-driving-parallelism day instead
(= loadgen ``--scenario autopilot_drift``): same fleet + diurnal
traffic, but the capacity controller is replaced by a
:class:`~apex_tpu.resilience.autopilot.ParallelismAutopilot` and the
chaos is a mid-day interconnect drift — links go 16x slower (the
autopilot must DETECT it from refitted telemetry and commit dp 4 -> 2
through the measured gate), then recover with an injected
``plan_regression`` poisoning the re-adoption's commit gate (forced
measured rollback).  Gates: exactly-once delivery, SLO attainment
>= 0.9, >= 1 commit AND >= 1 rollback, adoption counters matching the
applied-fault log, a flap-free :meth:`ParallelismAutopilot.audit`, and
the finished training state bitwise vs an uninterrupted fixed-plan
reference.
"""

from __future__ import annotations

import argparse
import bisect
import json
import os
import shutil
import sys
import tempfile

_HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(_HERE))
if _HERE not in sys.path:
    sys.path.insert(1, _HERE)

# the training side needs >= base_dp devices; force them before jax
# loads (same idiom as tools/crash_matrix.py) — a no-op when the caller
# (loadgen, pytest) already imported jax or set XLA_FLAGS itself.
# This is a control-plane scenario on a toy model, so its default is four
# VIRTUAL CPU devices and its wall times are host times.  It starts no
# process; to drive real chips set JAX_PLATFORMS/XLA_FLAGS yourself and
# run it as the one process on that host.
if "jax" not in sys.modules and "XLA_FLAGS" not in os.environ:
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"

import jax            # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np    # noqa: E402

import loadgen        # noqa: E402


def day_args(seed: int = 0, requests: int = 240,
             json_out: bool = False, **overrides) -> argparse.Namespace:
    """The full knob set, loadgen-compatible where the helpers are
    shared (workload/model/replica shape) plus the capacity-side knobs.
    ``overrides`` patch any field."""
    ns = argparse.Namespace(
        scenario="capacity_diurnal", seed=seed, requests=requests,
        json_out=json_out,
        # traffic + drive loop
        rate=100.0, period_s=3.0, tick_s=0.02, max_ticks=4000,
        client_retries=3, e2e_slo_s=3.0,
        # workload shape
        min_prompt=8, pareto_shape=2.5, max_new=8,
        shared_prefix_prob=0.5, shared_prefix_len=16, num_prefixes=2,
        # model (tiny: the sim measures the CONTROL plane)
        vocab=64, hidden=32, layers=2, heads=2, max_seq=128,
        # base fleet
        replicas=2, max_slots=4, max_queue=64, max_queue_depth=8,
        block_size=8, chunked=False, token_budget=64,
        ttft_slo_s=0.05, burn_threshold=14.4, burn_window_s=60.0,
        retry_budget=4, hedge_after_s=None,
        # training side
        base_dp=4, min_train_dp=2, train_steps=40, train_every=8,
        preempt_step=12, anomaly_step=20,
        # capacity controller
        burn_high=6.0, burn_low=1.0, cap_burn_window_s=1.0,
        confirm_ticks=5, cooldown_s=2.0, drain_timeout_ticks=150,
    )
    for k, v in overrides.items():
        setattr(ns, k, v)
    return ns


# -- training side (tests/test_elastic.py's model: tiny linear regression,
# replicated global batch => dp changes resume bitwise) ----------------------


def _loss_fn(p, x, y):
    return jnp.mean(jnp.square(x @ p["w"] + p["b"] - y))


def _batch_fn(step, plan):
    r = np.random.RandomState(60_000 + step)
    return (jnp.asarray(r.randn(8, 8).astype(np.float32)),
            jnp.asarray(r.randn(8, 4).astype(np.float32)))


def _factory(plan, ckpt, inj):
    from apex_tpu.optimizers import FusedAdam
    from apex_tpu.resilience import ElasticComponents, GuardedTrainStep

    opt = FusedAdam(lr=1e-2)
    guard = GuardedTrainStep(_loss_fn, opt, warmup_steps=1,
                             checkpoint=ckpt, fault_injector=inj)
    r = np.random.RandomState(3)
    params = plan.put(
        {"w": jnp.asarray(r.randn(8, 4).astype(np.float32)),
         "b": jnp.zeros((4,), jnp.float32)})
    return ElasticComponents(guard, params, opt.init(params),
                             guard.init_state())


def _flat(tr):
    out = list(jax.tree_util.tree_leaves(tr.params))
    st = tr.opt_state
    for key in sorted(st["buckets"]):
        for slot in sorted(st["buckets"][key]):
            v = st["buckets"][key][slot]
            out.extend(v if isinstance(v, list) else [v])
    return [np.asarray(x) for x in out]


def _bitwise_ok(got, ref):
    return (len(got) == len(ref)
            and all(np.array_equal(a, b) for a, b in zip(got, ref)))


def _train_injector(args, with_preempt: bool):
    """Three consecutive nan_grads (=> one terminating guard rollback;
    ``once=True`` makes the rolled-back re-run clean) and, for the day
    run only, a hard preemption.  The reference run gets the SAME
    anomalies so the two trajectories are comparable bitwise."""
    from apex_tpu.resilience import Fault, FaultInjector

    faults = [Fault(args.anomaly_step + k, "nan_grads", once=True)
              for k in range(3)]
    if with_preempt:
        faults.append(Fault(args.preempt_step, "preempt_at_step",
                            once=True))
    return FaultInjector(faults)


# -- the day -----------------------------------------------------------------


def run_day(args) -> dict:
    from apex_tpu.observability import (FlightRecorder, MetricsRegistry,
                                        Tracer)
    from apex_tpu.observability.slo import SLOMonitor, SLOTarget
    from apex_tpu.resilience import (CapacityController, ElasticPlan,
                                     ElasticTrainer, Preemption,
                                     TopologySpec)
    from apex_tpu.serving import (FleetRouter, PagedInferenceEngine,
                                  RequestShed, ServingFault,
                                  ServingFaultInjector, TickScheduler,
                                  VirtualClock)
    from apex_tpu.utils.profiling import ServingMetrics

    if jax.device_count() < args.base_dp:
        return {"skipped": f"needs >= {args.base_dp} devices "
                           f"(have {jax.device_count()}); set XLA_FLAGS="
                           "--xla_force_host_platform_device_count=4",
                "gates": {}}

    clock = VirtualClock()
    recorder = FlightRecorder(clock=clock)
    registry = MetricsRegistry()
    devices = jax.devices()[:args.base_dp]

    model, params = loadgen._build_model(args)
    replicas = loadgen._build_replicas(args, model, params, clock)
    # one fleet-scoped capacity_change active all day: the FIRST shift
    # (whenever burn triggers it) crashes mid-flight; consume-once, so
    # the post-rollback retry commits
    injector = ServingFaultInjector([ServingFault(
        0, 0, "capacity_change", magnitude=0.0, duration=10 ** 9)])
    fleet = FleetRouter(
        replicas, injector=injector, clock=clock,
        max_queue_depth=args.max_queue_depth,
        burn_threshold=args.burn_threshold,
        burn_window_s=args.burn_window_s,
        retry_budget=args.retry_budget,
        hedge_after_s=args.hedge_after_s,
        seed=args.seed, tracer=Tracer(clock=clock, id_tag="router"),
        recorder=recorder)

    def make_replica():
        slo = SLOMonitor([SLOTarget("ttft", args.ttft_slo_s,
                                    objective=0.9)], clock=clock)
        return PagedInferenceEngine(
            model, params, max_slots=args.max_slots,
            block_size=args.block_size, chunked_prefill=args.chunked,
            scheduler=TickScheduler(token_budget=args.token_budget),
            metrics=ServingMetrics(clock, slo=slo),
            max_queue=args.max_queue, clock=clock)

    root = tempfile.mkdtemp(prefix="apex_tpu_day_")
    try:
        el_inj = _train_injector(args, with_preempt=True)
        base = TopologySpec(dp=args.base_dp)
        trainer = ElasticTrainer(
            _factory, ElasticPlan.build(base, devices=devices),
            directory=root + "/day", fault_injector=el_inj,
            save_every=1, devices=devices, recorder=recorder)
        controller = CapacityController(
            trainer, fleet, make_replica,
            min_train_dp=args.min_train_dp,
            burn_high=args.burn_high, burn_low=args.burn_low,
            burn_window_s=args.cap_burn_window_s,
            confirm_ticks=args.confirm_ticks,
            cooldown_s=args.cooldown_s,
            drain_timeout_ticks=args.drain_timeout_ticks,
            injector=el_inj, serving_injector=injector,
            registry=registry, recorder=recorder, clock=clock)

        work = loadgen.synthesize_scenario(args)
        crng = np.random.RandomState(args.seed + 1)
        pending = [(t, i, req, int(args.client_retries))
                   for i, (t, req) in enumerate(work)]
        seq = len(pending)
        submit_t: dict = {}
        finish_t: dict = {}
        submitted: set = set()
        shed_client: dict = {}
        ticks = seen = preemptions = 0
        while True:
            now = clock()
            while pending and pending[0][0] <= now:
                _, _, req, retries = pending.pop(0)
                try:
                    fleet.submit(req)
                    submitted.add(req.request_id)
                    submit_t.setdefault(req.request_id, now)
                    shed_client.pop(req.request_id, None)
                except RequestShed as e:
                    if retries > 0:
                        back = e.retry_after_s * (1.0 + 0.5 * crng.rand())
                        bisect.insort(
                            pending, (now + back, seq, req, retries - 1))
                        seq += 1
                    else:
                        shed_client[req.request_id] = e.reason.value
            busy = fleet.step()
            if ticks % args.train_every == 0 \
                    and trainer.current_step < args.train_steps:
                try:
                    trainer.step_once(_batch_fn)
                except Preemption:
                    # hard kill: restart semantics are a FRESH trainer
                    # on the CURRENT topology, same directory + same
                    # injector (once-consumed faults stay consumed)
                    preemptions += 1
                    trainer = ElasticTrainer(
                        _factory,
                        ElasticPlan.build(trainer.plan.spec,
                                          devices=devices),
                        directory=root + "/day", fault_injector=el_inj,
                        save_every=1, devices=devices,
                        recorder=recorder)
                    trainer.start()
                    controller.trainer = trainer
            controller.tick()
            clock.advance(args.tick_s)
            ticks += 1
            done = fleet.completed
            while seen < len(done):
                finish_t[done[seen].request_id] = clock()
                seen += 1
            if not pending and not busy \
                    and trainer.current_step >= args.train_steps \
                    and not controller.shifting \
                    and controller.outstanding_leases == 0 \
                    and not any(e is not None and (e._queue or e._active)
                                for e in fleet.replicas):
                break
            if ticks >= args.max_ticks:
                break

        responses = {r.request_id: r for r in fleet.completed}
        dup = len(fleet.completed) - len(responses)
        lost = sorted(submitted - set(responses))
        e2e_ok = [finish_t[rid] - submit_t[rid]
                  for rid, rep in responses.items()
                  if rep.finish_reason in ("eos", "length")
                  and rid in finish_t and rid in submit_t]
        attainment = (sum(1 for v in e2e_ok if v <= args.e2e_slo_s)
                      / len(e2e_ok)) if e2e_ok else 0.0

        # the uninterrupted fixed-capacity reference: same anomalies,
        # no preemption, no shifts — the elastic day must match it
        # bitwise at the same step count
        ref = ElasticTrainer(
            _factory, ElasticPlan.build(base, devices=devices),
            directory=root + "/ref",
            fault_injector=_train_injector(args, with_preempt=False),
            save_every=1, devices=devices)
        ref.train(_batch_fn, args.train_steps)
        bitwise = (trainer.current_step >= args.train_steps
                   and trainer.plan.spec.dp == args.base_dp
                   and _bitwise_ok(_flat(trainer), _flat(ref)))

        audit = controller.audit()
        gates = {
            "exactly_once_lost": lost == [],
            "exactly_once_dup": dup == 0,
            "slo_attainment": attainment >= 0.9,
            "train_completed":
                trainer.current_step >= args.train_steps,
            "train_bitwise": bitwise,
            "shift_rollback": controller.stats["rollbacks"] >= 1,
            "shifts_committed": controller.stats["shifts"] >= 2,
            "no_out_of_band_flaps": audit == [],
            "capacity_returned":
                trainer.plan.spec.dp == args.base_dp
                and controller.outstanding_leases == 0,
        }
        return {
            "scenario": "capacity_diurnal",
            "requests": args.requests,
            "submitted": len(submitted),
            "responses": len(responses),
            "lost": lost,
            "duplicated": dup,
            "shed_client": len(shed_client),
            "outcomes": loadgen._outcome_counts(responses,
                                                len(shed_client)),
            "ticks": ticks,
            "virtual_s": clock(),
            "e2e_served": len(e2e_ok),
            "e2e_p50_s": loadgen._pct(e2e_ok, 50),
            "e2e_p99_s": loadgen._pct(e2e_ok, 99),
            "slo_attainment": attainment,
            "migrations": fleet.migrations,
            "preemptions": preemptions,
            "train": {
                "steps": trainer.current_step,
                "final_dp": trainer.plan.spec.dp,
                "anomalies_injected": sum(
                    1 for _, k in el_inj.log if k == "nan_grads"),
            },
            "capacity": {
                "shifts": controller.stats["shifts"],
                "rollbacks": controller.stats["rollbacks"],
                "outstanding_leases": controller.outstanding_leases,
                "split": list(controller.split),
                "last_shift": controller.stats["last_shift"],
                "shift_log": controller.shift_log,
                "audit": audit,
                "serving_fault_log": list(injector.log),
            },
            "flight_snapshots": len(recorder.dumps),
            "gates": gates,
        }
    finally:
        shutil.rmtree(root, ignore_errors=True)


def print_report(report: dict) -> None:
    if report.get("skipped"):
        print(f"day_in_life SKIPPED: {report['skipped']}")
        return
    cap = report["capacity"]
    print(f"day_in_life: {report['responses']}/{report['submitted']} "
          f"answered (lost {len(report['lost'])}, "
          f"dup {report['duplicated']}, "
          f"client-shed {report['shed_client']}) over "
          f"{report['ticks']} ticks / {report['virtual_s']:.1f}s virtual")
    print(f"  outcomes {report['outcomes']}")
    print(f"  slo attainment {report['slo_attainment']:.0%} "
          f"(e2e p50 {report['e2e_p50_s'] * 1e3:.0f} ms, "
          f"p99 {report['e2e_p99_s'] * 1e3:.0f} ms)")
    print(f"  train: {report['train']['steps']} steps, "
          f"final dp={report['train']['final_dp']}, "
          f"{report['preemptions']} preemption(s), "
          f"{report['train']['anomalies_injected']} injected anomalies")
    print(f"  capacity: {cap['shifts']} shift(s) committed, "
          f"{cap['rollbacks']} rollback(s), split {cap['split']}, "
          f"{cap['outstanding_leases']} outstanding lease(s)")
    for e in cap["shift_log"]:
        print(f"    tick {e['tick']:5d} {e['direction']:<12} "
              f"burn {e['burn']:5.2f} -> {e['outcome']}"
              + (f" ({e['reason']})" if e["reason"] else ""))
    print(f"  {report['flight_snapshots']} flight snapshot(s)")
    ok = all(report["gates"].values())
    for name, passed in report["gates"].items():
        print(f"  gate {name:<22} {'PASS' if passed else 'FAIL'}")
    print(f"day_in_life {'OK: all gates pass' if ok else 'FAILED'}")


# -- the autopilot day (ROADMAP item 3: self-driving parallelism) ------------


def autopilot_args(seed: int = 0, requests: int = 240,
                   json_out: bool = False,
                   **overrides) -> argparse.Namespace:
    """Knobs for the ``autopilot_drift`` day: the capacity day's fleet
    + workload shape, with the capacity controller replaced by a
    :class:`~apex_tpu.resilience.autopilot.ParallelismAutopilot` and a
    mid-day interconnect drift schedule."""
    ns = day_args(seed=seed, requests=requests, json_out=json_out)
    ns.scenario = "autopilot_drift"
    # the simulated interconnect: dcn-class alpha-beta coefficients
    # shared by the autopilot's loaded profile and the driver's
    # synthetic step-time model, so detection is honest (refit-driven)
    ns.link_alpha = 2e-3
    ns.link_beta = 1e-9
    ns.serial_s = 0.12
    # drift schedule, in TRAINER steps: links drift_scale x slower
    # mid-morning (=> commit dp 4 -> 2), recover mid-afternoon with an
    # injected plan_regression poisoning the re-adoption's commit gate
    # (=> measured rollback to dp 2)
    ns.drift_step = 6
    ns.recover_step = 22
    ns.drift_scale = 16.0
    ns.regression_scale = 4.0
    # autopilot knobs (cooldown on the VIRTUAL clock)
    ns.drift_threshold = 0.3
    ns.confirm_windows = 2
    ns.min_measurements = 8
    ns.adopt_cooldown_s = 0.5
    ns.gate_steps = 2
    ns.gate_tolerance = 1.2
    for k, v in overrides.items():
        setattr(ns, k, v)
    return ns


_GRAD_BYTES = 8 * 4 * 4 + 4 * 4   # _factory's params: w (8x4 f32) + b


def _drift_dt(step: int, dp: int, args) -> float:
    """Synthetic measured step time under the drift schedule: perfectly
    dp-scalable serial compute + the alpha-beta price of the gradient
    all-reduce at the CURRENTLY drifted link coefficients."""
    from apex_tpu.observability.costmodel import CostFit

    scale = 1.0
    if step >= args.drift_step:
        scale *= args.drift_scale
    if step >= args.recover_step:
        scale /= args.drift_scale
    fit = CostFit(args.link_alpha * scale, args.link_beta * scale)
    comm = fit.predict("psum", _GRAD_BYTES, dp) if dp > 1 else 0.0
    return args.serial_s / dp + comm


def run_autopilot_day(args) -> dict:
    from apex_tpu.observability import (FlightRecorder, MetricsRegistry,
                                        Tracer)
    from apex_tpu.observability.costmodel import (
        fit_cost_model, simulate_link_measurements)
    from apex_tpu.observability.slo import SLOMonitor, SLOTarget
    from apex_tpu.resilience import (ElasticPlan, ElasticTrainer, Fault,
                                     FaultInjector, ParallelismAutopilot,
                                     TopologySpec)
    from apex_tpu.serving import (FleetRouter, PagedInferenceEngine,
                                  RequestShed, TickScheduler, VirtualClock)
    from apex_tpu.utils.profiling import ServingMetrics

    if jax.device_count() < args.base_dp:
        return {"skipped": f"needs >= {args.base_dp} devices "
                           f"(have {jax.device_count()}); set XLA_FLAGS="
                           "--xla_force_host_platform_device_count=4",
                "gates": {}}

    clock = VirtualClock()
    recorder = FlightRecorder(clock=clock)
    registry = MetricsRegistry()
    devices = jax.devices()[:args.base_dp]

    model, params = loadgen._build_model(args)
    replicas = loadgen._build_replicas(args, model, params, clock)
    fleet = FleetRouter(
        replicas, clock=clock,
        max_queue_depth=args.max_queue_depth,
        burn_threshold=args.burn_threshold,
        burn_window_s=args.burn_window_s,
        retry_budget=args.retry_budget,
        hedge_after_s=args.hedge_after_s,
        seed=args.seed, tracer=Tracer(clock=clock, id_tag="router"),
        recorder=recorder)

    profile = fit_cost_model(
        simulate_link_measurements(args.link_alpha, args.link_beta,
                                   link_class="dcn", ops=("psum",))
        + simulate_link_measurements(1e-6, 1e-10, link_class="ici",
                                     ops=("psum",)),
        meta={"source": "autopilot_day"})
    inj = FaultInjector([
        Fault(args.drift_step, "cost_drift",
              magnitude=args.drift_scale),
        Fault(args.recover_step, "cost_drift",
              magnitude=1.0 / args.drift_scale),
        Fault(args.recover_step, "plan_regression",
              magnitude=args.regression_scale)])

    root = tempfile.mkdtemp(prefix="apex_tpu_autopilot_day_")
    try:
        base = TopologySpec(dp=args.base_dp)
        trainer = ElasticTrainer(
            _factory, ElasticPlan.build(base, devices=devices),
            directory=root + "/day", fault_injector=inj,
            save_every=1, devices=devices, recorder=recorder)
        autopilot = ParallelismAutopilot(
            trainer, profile, min_dp=args.min_train_dp,
            link_class="dcn", drift_threshold=args.drift_threshold,
            confirm_windows=args.confirm_windows,
            min_measurements=args.min_measurements,
            cooldown_s=args.adopt_cooldown_s,
            gate_steps=args.gate_steps,
            gate_tolerance=args.gate_tolerance,
            injector=inj, registry=registry, recorder=recorder,
            tracer=Tracer(clock=clock, id_tag="autopilot"),
            clock=clock)

        work = loadgen.synthesize_scenario(args)
        crng = np.random.RandomState(args.seed + 1)
        pending = [(t, i, req, int(args.client_retries))
                   for i, (t, req) in enumerate(work)]
        seq = len(pending)
        submit_t: dict = {}
        finish_t: dict = {}
        submitted: set = set()
        shed_client: dict = {}
        ticks = seen = 0
        while True:
            now = clock()
            while pending and pending[0][0] <= now:
                _, _, req, retries = pending.pop(0)
                try:
                    fleet.submit(req)
                    submitted.add(req.request_id)
                    submit_t.setdefault(req.request_id, now)
                    shed_client.pop(req.request_id, None)
                except RequestShed as e:
                    if retries > 0:
                        back = e.retry_after_s * (1.0 + 0.5 * crng.rand())
                        bisect.insort(
                            pending, (now + back, seq, req, retries - 1))
                        seq += 1
                    else:
                        shed_client[req.request_id] = e.reason.value
            busy = fleet.step()
            if ticks % args.train_every == 0 \
                    and trainer.current_step < args.train_steps:
                step = trainer.current_step
                trainer.step_once(_batch_fn)
                autopilot.record_step(
                    _drift_dt(step, trainer.plan.spec.dp, args))
                autopilot.tick()
                autopilot.tick()
            clock.advance(args.tick_s)
            ticks += 1
            done = fleet.completed
            while seen < len(done):
                finish_t[done[seen].request_id] = clock()
                seen += 1
            if not pending and not busy \
                    and trainer.current_step >= args.train_steps \
                    and not autopilot.adopting \
                    and not any(e is not None and (e._queue or e._active)
                                for e in fleet.replicas):
                break
            if ticks >= args.max_ticks:
                break

        responses = {r.request_id: r for r in fleet.completed}
        dup = len(fleet.completed) - len(responses)
        lost = sorted(submitted - set(responses))
        e2e_ok = [finish_t[rid] - submit_t[rid]
                  for rid, rep in responses.items()
                  if rep.finish_reason in ("eos", "length")
                  and rid in finish_t and rid in submit_t]
        attainment = (sum(1 for v in e2e_ok if v <= args.e2e_slo_s)
                      / len(e2e_ok)) if e2e_ok else 0.0

        # the full cycle must leave training bit-identical to a run
        # that never drifted: same batches, fixed plan, no autopilot
        ref = ElasticTrainer(
            _factory, ElasticPlan.build(base, devices=devices),
            directory=root + "/ref", save_every=1, devices=devices)
        ref.train(_batch_fn, args.train_steps)
        bitwise = (trainer.current_step >= args.train_steps
                   and _bitwise_ok(_flat(trainer), _flat(ref)))

        audit = autopilot.audit()
        drifts = sum(1 for _, k in inj.log if k == "cost_drift")
        regressions = sum(1 for _, k in inj.log
                          if k == "plan_regression")
        commits = registry.get("autopilot_adoptions_total").value(
            outcome="commit")
        rollbacks = registry.get("autopilot_adoptions_total").value(
            outcome="rollback")
        gates = {
            "exactly_once_lost": lost == [],
            "exactly_once_dup": dup == 0,
            "slo_attainment": attainment >= 0.9,
            "train_completed":
                trainer.current_step >= args.train_steps,
            "train_bitwise": bitwise,
            "adoption_committed": autopilot.stats["adoptions"] >= 1,
            "regression_rolled_back":
                autopilot.stats["rollbacks"] >= 1,
            "no_out_of_band_flaps": audit == [],
            "counters_match_faults":
                commits + rollbacks == drifts
                and rollbacks == regressions
                and autopilot.queued == 0,
        }
        return {
            "scenario": "autopilot_drift",
            "requests": args.requests,
            "submitted": len(submitted),
            "responses": len(responses),
            "lost": lost,
            "duplicated": dup,
            "shed_client": len(shed_client),
            "outcomes": loadgen._outcome_counts(responses,
                                                len(shed_client)),
            "ticks": ticks,
            "virtual_s": clock(),
            "e2e_served": len(e2e_ok),
            "e2e_p50_s": loadgen._pct(e2e_ok, 50),
            "e2e_p99_s": loadgen._pct(e2e_ok, 99),
            "slo_attainment": attainment,
            "migrations": fleet.migrations,
            "train": {
                "steps": trainer.current_step,
                "final_dp": trainer.plan.spec.dp,
            },
            "autopilot": {
                "refits": autopilot.stats["refits"],
                "drift_confirmed": autopilot.stats["drift_confirmed"],
                "adoptions": autopilot.stats["adoptions"],
                "rollbacks": autopilot.stats["rollbacks"],
                "no_change": autopilot.stats["no_change"],
                "last_drift": autopilot.stats["last_drift"],
                "last_adoption": autopilot.stats["last_adoption"],
                "adoption_log": autopilot.adoption_log,
                "audit": audit,
                "fault_log": list(inj.log),
            },
            "flight_snapshots": len(recorder.dumps),
            "gates": gates,
        }
    finally:
        shutil.rmtree(root, ignore_errors=True)


def print_autopilot_report(report: dict) -> None:
    if report.get("skipped"):
        print(f"autopilot_day SKIPPED: {report['skipped']}")
        return
    ap = report["autopilot"]
    print(f"autopilot_day: {report['responses']}/{report['submitted']} "
          f"answered (lost {len(report['lost'])}, "
          f"dup {report['duplicated']}, "
          f"client-shed {report['shed_client']}) over "
          f"{report['ticks']} ticks / {report['virtual_s']:.1f}s virtual")
    print(f"  outcomes {report['outcomes']}")
    print(f"  slo attainment {report['slo_attainment']:.0%} "
          f"(e2e p50 {report['e2e_p50_s'] * 1e3:.0f} ms, "
          f"p99 {report['e2e_p99_s'] * 1e3:.0f} ms)")
    print(f"  train: {report['train']['steps']} steps, "
          f"final dp={report['train']['final_dp']}")
    print(f"  autopilot: {ap['refits']} refit windows, "
          f"{ap['drift_confirmed']} drift confirmation(s), "
          f"{ap['adoptions']} commit(s), {ap['rollbacks']} rollback(s)")
    for e in ap["adoption_log"]:
        print(f"    tick {e['tick']:5d} {e['old']} -> {e['new']}: "
              f"{e['outcome']}"
              + (f" ({e['reason']})" if e["reason"] else ""))
    print(f"  faults applied: {ap['fault_log']}")
    print(f"  {report['flight_snapshots']} flight snapshot(s)")
    ok = all(report["gates"].values())
    for name, passed in report["gates"].items():
        print(f"  gate {name:<22} {'PASS' if passed else 'FAIL'}")
    print(f"autopilot_day {'OK: all gates pass' if ok else 'FAILED'}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--requests", type=int, default=140)
    ap.add_argument("--train-steps", type=int, default=40)
    ap.add_argument("--max-ticks", type=int, default=4000)
    ap.add_argument("--autopilot", action="store_true",
                    help="run the autopilot_drift day (self-driving "
                         "parallelism) instead of the capacity day")
    ap.add_argument("--json", action="store_true")
    a = ap.parse_args(argv)
    if a.autopilot:
        report = run_autopilot_day(autopilot_args(
            seed=a.seed, requests=a.requests, json_out=a.json,
            train_steps=a.train_steps, max_ticks=a.max_ticks))
    else:
        report = run_day(day_args(seed=a.seed, requests=a.requests,
                                  json_out=a.json,
                                  train_steps=a.train_steps,
                                  max_ticks=a.max_ticks))
    if a.json:
        print(json.dumps(report, indent=2))
    elif a.autopilot:
        print_autopilot_report(report)
    else:
        print_report(report)
    return 0 if report["gates"] and all(report["gates"].values()) else 1


if __name__ == "__main__":
    from apex_tpu.utils.platform import setup_compile_cache
    setup_compile_cache()
    sys.exit(main())
