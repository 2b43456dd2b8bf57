"""apex_tpu.resilience.capacity: burn-driven train<->serve shifting.

The controller's correctness contract:

* hysteresis: burn oscillating strictly inside ``(burn_low,
  burn_high)`` NEVER shifts, no matter how long; burn AT the band edge
  counts toward the confirm streak (>= / <= semantics); a broken
  streak resets the count;
* cooldown: no shift starts within ``cooldown_s`` of the previous
  commit OR rollback; :meth:`CapacityController.audit` proves both
  properties over the full shift history;
* one shift at a time: requests made mid-shift queue and run after —
  the shift log never interleaves;
* every injected failure mode (mid-shift crash, stuck drain, failed
  re-shard) rolls the split back to the prior one exactly — and, with
  a real :class:`ElasticTrainer` underneath, restores the trainer's
  params and optimizer slots BITWISE;
* appending ``capacity_change`` to the fault-kind tuples changed no
  pre-existing ``from_seed`` schedule (rate-0 kinds consume no rng
  stream state) — the determinism promise both docstrings make.

One shrink -> grow cycle over a LIVE fleet of paged engines and a real
trainer is ``test_shrink_grow_cycle_over_a_live_fleet``; the full day
(diurnal traffic, preemptions, guard rollbacks) is ``tools/day_in_life.py``.
"""

import dataclasses
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.resilience import (CAPACITY_FAULT_MODES, CapacityBudget,
                                 CapacityController, ElasticComponents,
                                 ElasticPlan, ElasticTrainer, Fault,
                                 FaultInjector, GuardedTrainStep,
                                 TopologySpec, fault_mode)
from apex_tpu.resilience.faults import FAULT_KINDS, seeded_schedule
from apex_tpu.serving import (SERVING_FAULT_KINDS, ServingFault,
                              ServingFaultInjector)


# -- fakes: the controller only needs the trainer/fleet surface --------------


class FakeSLO:
    def __init__(self, owner):
        self.owner = owner
        self.targets = [SimpleNamespace(name="ttft")]
        self.resets = []

    def burn_rate(self, target, window_s):
        return self.owner.burn

    def reset_windows(self, epoch=None):
        self.resets.append(epoch)


class FakeEngine:
    def __init__(self, burn=0.0):
        self.burn = burn
        self.metrics = SimpleNamespace(slo=FakeSLO(self))


class FakeFleet:
    def __init__(self, n=2, clock=lambda: 0.0):
        self.clock = clock
        self.replicas = [FakeEngine() for _ in range(n)]
        self.draining = set()
        self.drain_done = True       # tests flip this for slow drains

    def _live(self):
        return [(i, e) for i, e in enumerate(self.replicas)
                if e is not None]

    def add_replica(self, engine):
        for j, e in enumerate(self.replicas):
            if e is None:
                self.replicas[j] = engine
                return j
        self.replicas.append(engine)
        return len(self.replicas) - 1

    def begin_drain(self, i):
        if self.replicas[i] is None:
            raise ValueError(f"replica {i} was removed")
        self.draining.add(i)

    def cancel_drain(self, i):
        self.draining.discard(i)

    def drained(self, i):
        return self.drain_done

    def remove_replica(self, i):
        eng = self.replicas[i]
        self.replicas[i] = None
        self.draining.discard(i)
        return eng

    def set_burn(self, burn):
        for _, e in self._live():
            e.burn = burn


class FakeTrainer:
    def __init__(self, dp=4):
        self.plan = SimpleNamespace(spec=TopologySpec(dp=dp))
        self.stats = {"last_checkpoint_s": 0.0, "last_reshard_s": 0.0}
        self.current_step = 0
        self.replans = []

    def replan_to(self, spec, *, checkpoint_first=True):
        self.replans.append(spec.dp)
        self.plan = SimpleNamespace(spec=spec)


def make_controller(clockv=None, *, dp=4, fleet=None, trainer=None, **kw):
    clockv = clockv if clockv is not None else [0.0]
    clock = lambda: clockv[0]                                # noqa: E731
    fleet = fleet if fleet is not None else FakeFleet(clock=clock)
    trainer = trainer if trainer is not None else FakeTrainer(dp=dp)
    kw.setdefault("min_train_dp", 2)
    kw.setdefault("burn_high", 6.0)
    kw.setdefault("burn_low", 1.0)
    kw.setdefault("confirm_ticks", 3)
    kw.setdefault("cooldown_s", 10.0)
    ctl = CapacityController(trainer, fleet, FakeEngine, clock=clock,
                             **kw)
    return ctl, trainer, fleet, clockv


# -- basics ------------------------------------------------------------------


def test_fault_mode_mapping():
    assert fault_mode(0) == "mid_shift_crash"
    assert fault_mode(1) == "mid_shift_crash"
    assert fault_mode(2) == "stuck_drain"
    assert fault_mode(3) == "failed_reshard"
    assert fault_mode(99) == "mid_shift_crash"
    assert set(CAPACITY_FAULT_MODES) == {
        "mid_shift_crash", "stuck_drain", "failed_reshard"}


def test_budget_validates_split():
    CapacityBudget(6, 4, 2)
    with pytest.raises(ValueError):
        CapacityBudget(6, 4, 3)
    with pytest.raises(ValueError):
        CapacityBudget(6, 4, 2, chips_per_replica=0)


def test_controller_rejects_inverted_band():
    with pytest.raises(ValueError):
        make_controller(burn_high=1.0, burn_low=6.0)


# -- hysteresis + cooldown ---------------------------------------------------


def test_burn_inside_band_never_shifts():
    ctl, trainer, fleet, _ = make_controller()
    for i in range(200):
        # oscillate hard against both edges but strictly inside
        fleet.set_burn(1.0001 if i % 2 else 5.9999)
        ctl.tick()
    assert ctl.stats["shifts"] == 0 and ctl.shift_log == []
    assert trainer.replans == []
    assert ctl.audit() == []


def test_burn_at_threshold_counts_toward_streak():
    # exactly AT burn_high for confirm_ticks ticks => shift (>= edge)
    ctl, trainer, fleet, _ = make_controller(confirm_ticks=3)
    fleet.set_burn(6.0)
    for _ in range(3):
        ctl.tick()
    assert ctl.stats["shifts"] == 1
    assert trainer.plan.spec.dp == 2 and ctl.split == (2, 4)
    # the audit treats an at-edge start as outside the band
    assert ctl.audit() == []


def test_burn_just_below_threshold_never_shifts():
    ctl, trainer, fleet, _ = make_controller(confirm_ticks=3)
    fleet.set_burn(5.999999)
    for _ in range(50):
        ctl.tick()
    assert ctl.stats["shifts"] == 0 and trainer.replans == []


def test_broken_streak_resets_confirm_count():
    ctl, trainer, fleet, _ = make_controller(confirm_ticks=3)
    for _ in range(10):
        fleet.set_burn(7.0)
        ctl.tick()
        ctl.tick()
        fleet.set_burn(3.0)           # inside band: streak resets
        ctl.tick()
    assert ctl.stats["shifts"] == 0


def test_cooldown_blocks_followup_shift():
    ctl, trainer, fleet, clockv = make_controller(
        confirm_ticks=2, cooldown_s=10.0)
    fleet.set_burn(8.0)
    ctl.tick()
    ctl.tick()
    assert ctl.stats["shifts"] == 1             # dp 4 -> 2
    # burn collapses, but the cooldown holds the reverse shift
    fleet.set_burn(0.0)
    for _ in range(20):
        clockv[0] += 0.1
        ctl.tick()
    assert ctl.stats["shifts"] == 1
    clockv[0] += 10.0                           # past the cooldown
    for _ in range(3):
        ctl.tick()
    assert ctl.stats["shifts"] == 2
    assert trainer.plan.spec.dp == 4 and ctl.split == (4, 2)
    assert ctl.outstanding_leases == 0
    assert ctl.audit() == []


def test_slo_windows_reset_on_commit():
    ctl, trainer, fleet, _ = make_controller(confirm_ticks=1)
    survivors = [e for _, e in fleet._live()]
    fleet.set_burn(9.0)
    ctl.tick()
    assert ctl.stats["shifts"] == 1
    for e in survivors:
        assert e.metrics.slo.resets == ["shift-1"]


# -- one shift at a time -----------------------------------------------------


def test_shift_during_shift_queues_never_interleaves():
    ctl, trainer, fleet, clockv = make_controller(cooldown_s=0.0)
    assert ctl.request_shift("to_serving") == "queued"
    ctl.tick()
    assert ctl.stats["shifts"] == 1 and ctl.outstanding_leases == 1
    # a slow drain keeps the to_training shift in flight for ticks
    fleet.drain_done = False
    ctl.request_shift("to_training")
    ctl.tick()
    assert ctl.shifting
    ctl.request_shift("to_serving")             # arrives mid-shift
    for _ in range(5):
        ctl.tick()
    # still the SAME in-flight shift; the request queued, not mixed in
    assert ctl.shifting and ctl._shift.direction == "to_training"
    assert len(ctl.shift_log) == 2
    fleet.drain_done = True
    ctl.tick()                                  # drain converges, commit
    assert not ctl.shifting and ctl.stats["shifts"] == 2
    ctl.tick()                                  # queued request starts
    assert ctl.stats["shifts"] == 3
    assert [e["direction"] for e in ctl.shift_log] == [
        "to_serving", "to_training", "to_serving"]
    assert all(e["outcome"] == "commit" for e in ctl.shift_log)


def test_infeasible_queued_shift_is_dropped():
    ctl, trainer, fleet, _ = make_controller()
    ctl.request_shift("to_training")            # nothing leased
    ctl.tick()
    assert ctl.stats["shifts"] == 0 and not ctl.shifting
    with pytest.raises(ValueError):
        ctl.request_shift("sideways")


# -- injected failure modes roll back the split ------------------------------


def test_stuck_drain_times_out_and_rolls_back():
    sinj = ServingFaultInjector([ServingFault(
        0, 0, "capacity_change", magnitude=2.0, duration=10 ** 9)])
    ctl, trainer, fleet, _ = make_controller(
        cooldown_s=0.0, drain_timeout_ticks=5, serving_injector=sinj)
    ctl.request_shift("to_serving")
    for _ in range(8):
        ctl.tick()
    assert ctl.stats["rollbacks"] == 1 and ctl.stats["shifts"] == 0
    assert ctl.split == (4, 2) and trainer.replans == []
    assert "timed out" in ctl.shift_log[0]["reason"]


def test_failed_reshard_rolls_back_without_mutation():
    sinj = ServingFaultInjector([ServingFault(
        0, 0, "capacity_change", magnitude=3.0, duration=10 ** 9)])
    ctl, trainer, fleet, _ = make_controller(
        cooldown_s=0.0, serving_injector=sinj)
    ctl.request_shift("to_serving")
    ctl.tick()
    assert ctl.stats["rollbacks"] == 1
    assert ctl.split == (4, 2) and trainer.replans == []
    assert len(fleet._live()) == 2
    # the fault was consumed: the retry commits
    ctl.request_shift("to_serving")
    ctl.tick()
    assert ctl.stats["shifts"] == 1 and ctl.split == (2, 4)


def test_mid_shift_crash_on_drain_back_cancels_drain():
    ctl, trainer, fleet, clockv = make_controller(cooldown_s=0.0)
    ctl.request_shift("to_serving")
    ctl.tick()
    assert ctl.outstanding_leases == 1
    inj = FaultInjector([Fault(0, "capacity_change")])
    ctl.injector = inj
    ctl.request_shift("to_training")
    ctl.tick()
    assert ctl.stats["rollbacks"] == 1
    assert ctl.outstanding_leases == 1          # lease survives rollback
    assert fleet.draining == set()              # drain was cancelled
    assert ctl.split == (2, 4)
    # consumed: the retry drains and commits
    ctl.request_shift("to_training")
    for _ in range(3):
        ctl.tick()
    assert ctl.stats["shifts"] == 2 and ctl.split == (4, 2)


# -- rollback restores a REAL trainer bitwise --------------------------------


def _loss(p, x, y):
    return jnp.mean(jnp.square(x @ p["w"] + p["b"] - y))


def _batch(step, plan):
    r = np.random.RandomState(60_000 + step)
    return (jnp.asarray(r.randn(8, 8).astype(np.float32)),
            jnp.asarray(r.randn(8, 4).astype(np.float32)))


def _factory(plan, ckpt, inj):
    from apex_tpu.optimizers import FusedAdam

    opt = FusedAdam(lr=1e-2)
    guard = GuardedTrainStep(_loss, opt, warmup_steps=1,
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


@pytest.mark.skipif(len(jax.devices()) < 4, reason="needs 4 devices")
def test_mid_shift_crash_restores_real_trainer_bitwise(tmp_path):
    devices = jax.devices()[:4]
    trainer = ElasticTrainer(
        _factory, ElasticPlan.build(TopologySpec(dp=4), devices=devices),
        directory=str(tmp_path), save_every=1, devices=devices)
    clockv = [0.0]
    fleet = FakeFleet(clock=lambda: clockv[0])
    inj = FaultInjector([Fault(2, "capacity_change")])
    ctl = CapacityController(trainer, fleet, FakeEngine, min_train_dp=2,
                             cooldown_s=0.0, injector=inj,
                             clock=lambda: clockv[0])
    for _ in range(2):
        trainer.step_once(_batch)
    pre = _flat(trainer)
    ctl.request_shift("to_serving")
    ctl.tick()
    # the injected mid-shift crash rolled back: split AND state bitwise
    assert ctl.stats["rollbacks"] == 1 and ctl.stats["shifts"] == 0
    assert trainer.plan.spec.dp == 4 and ctl.split == (4, 2)
    for got, want in zip(_flat(trainer), pre, strict=True):
        np.testing.assert_array_equal(got, want)
    # the retry commits; training continues on the shrunk plan
    ctl.request_shift("to_serving")
    ctl.tick()
    assert ctl.stats["shifts"] == 1 and trainer.plan.spec.dp == 2
    trainer.step_once(_batch)
    assert trainer.current_step == 3


@pytest.mark.skipif(len(jax.devices()) < 4, reason="needs 4 devices")
def test_shrink_grow_cycle_over_a_live_fleet(tmp_path):
    """The controller between a real :class:`ElasticTrainer` and a live
    ``FleetRouter`` of paged engines: a mid-shift crash on the first
    shift rolls split and trainer back bitwise, the retry commits
    (dp 4 -> 2, replicas 2 -> 4), traffic runs on the grown fleet, the
    shift back drains the leased replicas (their in-flight work
    migrates) and regrows training; delivery is exactly-once across
    the add / drain / remove cycle and the finished run is bitwise the
    fixed-capacity reference."""
    from apex_tpu.inference import Request
    from apex_tpu.models.gpt import GPTConfig, GPTModel
    from apex_tpu.observability import FlightRecorder, MetricsRegistry
    from apex_tpu.observability.slo import SLOMonitor, SLOTarget
    from apex_tpu.serving import (FleetRouter, PagedInferenceEngine,
                                  TickScheduler, VirtualClock)
    from apex_tpu.utils.profiling import ServingMetrics

    n_steps = 8
    devices = jax.devices()[:4]
    clock = VirtualClock()
    model = GPTModel(GPTConfig(vocab_size=32, hidden_size=16, num_layers=2,
                               num_attention_heads=2, max_seq_len=64))
    mparams = model.init_params(jax.random.PRNGKey(0))

    def make_replica():
        slo = SLOMonitor([SLOTarget("ttft", 0.1, objective=0.9)],
                         clock=clock)
        return PagedInferenceEngine(
            model, mparams, max_slots=4, block_size=8,
            scheduler=TickScheduler(token_budget=64),
            metrics=ServingMetrics(clock, slo=slo), max_queue=32,
            clock=clock)

    def busy(fleet):
        return any(e is not None and (e._queue or e._active)
                   for e in fleet.replicas)

    def tick(n=1):
        for _ in range(n):
            fleet.step()
            ctl.tick()
            clock.advance(0.01)

    sinj = ServingFaultInjector([ServingFault(
        0, 0, "capacity_change", magnitude=0.0, duration=10 ** 9)])
    recorder = FlightRecorder(clock=clock)
    fleet = FleetRouter([make_replica(), make_replica()], injector=sinj,
                        clock=clock, recorder=recorder)
    reg = MetricsRegistry()
    trainer = ElasticTrainer(
        _factory, ElasticPlan.build(TopologySpec(dp=4), devices=devices),
        directory=str(tmp_path / "day"), save_every=1, devices=devices,
        recorder=recorder)
    ctl = CapacityController(
        trainer, fleet, make_replica, min_train_dp=2, cooldown_s=0.05,
        serving_injector=sinj, registry=reg, recorder=recorder, clock=clock)
    for _ in range(3):
        trainer.step_once(_batch)
    pre = _flat(trainer)

    # shift 1: crashed mid-flight; split AND trainer restored bitwise
    ctl.request_shift("to_serving")
    tick()
    assert ctl.stats["rollbacks"] == 1 and ctl.stats["shifts"] == 0
    assert ctl.split == (4, 2) and trainer.plan.spec.dp == 4
    for got, want in zip(_flat(trainer), pre, strict=True):
        np.testing.assert_array_equal(got, want)

    # retry past the cooldown: commits dp 4 -> 2, replicas 2 -> 4
    clock.advance(0.1)
    ctl.request_shift("to_serving")
    tick()
    assert ctl.stats["shifts"] == 1 and ctl.split == (2, 4)
    assert len(fleet._live()) == 4 and ctl.outstanding_leases == 1

    # live traffic on the grown fleet while training continues
    rng = np.random.RandomState(7)
    for i in range(12):
        fleet.submit(Request(i, list(rng.randint(1, 32, 6).astype(int)),
                             max_new_tokens=4, seed=i))
    for _ in range(2):
        fleet.step()
        clock.advance(0.01)
    trainer.step_once(_batch)

    # shift back: drain the leased replicas, remove them, regrow training
    clock.advance(0.1)
    ctl.request_shift("to_training")
    for _ in range(500):
        if not (ctl.outstanding_leases or ctl.shifting or busy(fleet)):
            break
        tick()
    else:
        pytest.fail("capacity drain did not converge")
    assert ctl.stats["shifts"] == 2 and ctl.split == (4, 2)
    assert trainer.plan.spec.dp == 4 and len(fleet._live()) == 2
    while busy(fleet) or fleet.pending:
        fleet.step()
        clock.advance(0.01)

    # exactly-once across the whole add / drain / remove cycle
    assert sorted(r.request_id for r in fleet.completed) == list(range(12))

    # the finished run is the uninterrupted dp=4 reference, bitwise
    while trainer.current_step < n_steps:
        trainer.step_once(_batch)
    ref = ElasticTrainer(
        _factory, ElasticPlan.build(TopologySpec(dp=4), devices=devices),
        directory=str(tmp_path / "ref"), save_every=1, devices=devices)
    ref.train(_batch, n_steps)
    for got, want in zip(_flat(trainer), _flat(ref), strict=True):
        np.testing.assert_array_equal(got, want)
    assert ctl.audit() == []
    assert reg.get("capacity_rollbacks_total").value() == 1
    shifts = reg.get("capacity_shifts_total")
    assert shifts.value(direction="to_serving") == 1
    assert shifts.value(direction="to_training") == 1
    assert len(recorder.dumps) >= 3


# -- schedule determinism across the kind-tuple append -----------------------


def test_train_from_seed_schedule_unchanged_by_capacity_kind():
    # kinds newer than capacity_change (e.g. dcn_fault) append AFTER it
    idx = FAULT_KINDS.index("capacity_change")
    rates = {k: 0.15 for k in FAULT_KINDS[:idx]}
    inj = FaultInjector.from_seed(5, 40, rates)
    # the schedule must equal the one generated over the PRE-EXISTING
    # kind tuple: a rate-0 kind consumes no rng stream state
    expected = seeded_schedule(5, 40, FAULT_KINDS[:idx], rates)
    assert [(f.step, f.kind) for f in inj.schedule] == expected
    assert expected                               # non-vacuous


def test_serving_from_seed_schedule_unchanged_by_capacity_kind():
    assert SERVING_FAULT_KINDS[-1] == "capacity_change"
    rates = {k: 0.1 for k in SERVING_FAULT_KINDS
             if k != "capacity_change"}
    inj = ServingFaultInjector.from_seed(3, 30, 2, rates)
    old = [k for k in SERVING_FAULT_KINDS if k != "capacity_change"]
    keys = [(rep, kind) for rep in range(2) for kind in old]
    expected = seeded_schedule(3, 30, keys,
                               {(rep, k): rates[k] for rep, k in keys})
    assert [(f.tick, (f.replica, f.kind)) for f in inj.schedule] \
        == expected
    assert expected


def test_capacity_change_consumed_once():
    inj = FaultInjector([Fault(4, "capacity_change", magnitude=3.0)])
    f = inj.check_capacity_change(4)
    assert f is not None and fault_mode(f.magnitude) == "failed_reshard"
    assert inj.check_capacity_change(4) is None
    assert inj.log == [(4, "capacity_change")]

    sinj = ServingFaultInjector([ServingFault(
        2, 1, "capacity_change", duration=100)])
    assert sinj.capacity_change_at(1) is None     # not active yet
    f = sinj.capacity_change_at(10)
    assert f is not None
    assert sinj.capacity_change_at(11) is None    # consume-once
    assert sinj.log == [(10, 1, "capacity_change")]
