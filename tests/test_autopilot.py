"""apex_tpu.resilience.autopilot: drift detection -> gated adoption.

The contract under test (ROADMAP item 3):

* too few fresh measurements never even refit — and therefore never
  move a plan (absence of data is not evidence of drift OR stability:
  the confirmation streak holds);
* a one-window drift spike is debounced: ``confirm_windows`` refit
  windows must agree before a drift confirms, and a clean window
  RESETS the streak (the ``CapacityController`` hysteresis discipline);
* a confirmed drift re-ranks the plan space against the refreshed
  profile and commits the winner through the measured
  baseline -> drain -> gate protocol;
* an injected ``plan_regression`` inflates the commit-gate measurements
  past ``gate_tolerance`` and the adoption ROLLS BACK —
  ``replan_to(old)`` — as does a replan that raises mid-adoption;
* drifts confirmed while an adoption is busy or cooling down QUEUE
  (coalesced to the latest refit candidate, never a stale pile-up) and
  never interleave; :meth:`ParallelismAutopilot.audit` stays ``[]``;
* appending ``cost_drift``/``plan_regression`` to ``FAULT_KINDS``
  changed no pre-existing ``from_seed`` schedule (rate-0 kinds consume
  no rng stream state), and the consume-once ``check_*`` hooks are
  window-tolerant (a controller tick polls BETWEEN training steps).

The closed loop on a real :class:`ElasticTrainer` (drain, re-shard,
bitwise rollback vs an uninterrupted reference) is
``TestAdoption::test_full_cycle_on_a_live_elastic_trainer`` (and
``tools/loadgen.py --scenario autopilot_drift``); the other tests drive a
fake trainer so the CONTROLLER's state machine is what's under test.
"""

import dataclasses
from types import SimpleNamespace

import pytest

from apex_tpu.observability import MetricsRegistry
from apex_tpu.observability.costmodel import (CostFit, fit_cost_model,
                                              simulate_link_measurements)
from apex_tpu.resilience import (Fault, FaultInjector,
                                 ParallelismAutopilot, TopologySpec)
from apex_tpu.resilience.faults import FAULT_KINDS, seeded_schedule

ALPHA0, BETA0 = 2e-3, 1e-9      # dcn-ish: latency dominates small psums
GRAD_BYTES = 144
SERIAL_S = 0.12


class FakeTrainer:
    """The trainer surface the autopilot drives: a plan with a spec,
    a device pool, replan_to, and the drain/re-shard stats."""

    def __init__(self, dp=4, n_devices=4, fail_replans=0):
        self.plan = SimpleNamespace(spec=TopologySpec(dp=dp))
        self._devices = list(range(n_devices))
        self.stats = {"last_checkpoint_s": 1e-3, "last_reshard_s": 2e-3}
        self.current_step = 0
        self.replans = []
        self.params = {}
        self._fail = fail_replans

    def replan_to(self, spec, **kw):
        if self._fail > 0:
            self._fail -= 1
            raise RuntimeError("injected reshard failure")
        self.replans.append(spec)
        self.plan = SimpleNamespace(spec=spec)


def dcn_profile():
    return fit_cost_model(
        simulate_link_measurements(ALPHA0, BETA0, link_class="dcn",
                                   ops=("psum",)),
        meta={"source": "test"})


def step_dt(dp, scale=1.0):
    """The synthetic machine: dp-scalable serial compute + the
    alpha-beta psum price at the current drift scale."""
    fit = CostFit(ALPHA0 * scale, BETA0 * scale)
    comm = fit.predict("psum", GRAD_BYTES, dp) if dp > 1 else 0.0
    return SERIAL_S / dp + comm


def make_autopilot(trainer, clockv, **kw):
    kw.setdefault("min_dp", 2)
    kw.setdefault("link_class", "dcn")
    kw.setdefault("drift_threshold", 0.3)
    kw.setdefault("confirm_windows", 2)
    kw.setdefault("min_measurements", 8)
    kw.setdefault("cooldown_s", 0.0)
    kw.setdefault("gate_steps", 2)
    kw.setdefault("gate_tolerance", 1.2)
    kw.setdefault("grad_bytes", GRAD_BYTES)
    return ParallelismAutopilot(trainer, dcn_profile(),
                                clock=lambda: clockv[0], **kw)


def drive(tr, ap, clockv, n_steps, scale_at, ticks_per_step=2):
    """The train loop shape: one step, one recorded dt, controller
    ticks; ``scale_at(step)`` is the machine's true drift scale."""
    for step in range(tr.current_step, tr.current_step + n_steps):
        tr.current_step = step + 1
        ap.record_step(step_dt(tr.plan.spec.dp, scale_at(step)))
        for _ in range(ticks_per_step):
            ap.tick()
        clockv[0] += 0.1


# -- detection discipline ----------------------------------------------------


class TestDetection:
    def test_too_few_measurements_never_refit_or_replan(self):
        tr = FakeTrainer()
        clockv = [0.0]
        ap = make_autopilot(tr, clockv, min_measurements=8)
        # a trickle of fresh points that stays below the window floor:
        # ticks keep coming, refits never happen, plans never move
        for i in range(20):
            if i < 5:
                ap.observe(simulate_link_measurements(
                    ALPHA0 * 16, BETA0 * 16, link_class="dcn",
                    ops=("psum",), dtypes=("f32",), sizes=(1 << 12,),
                    group_sizes=(2,))[:1])
            tr.current_step += 1
            ap.record_step(step_dt(4, 16.0))
            ap.tick()
            clockv[0] += 0.1
        assert ap.stats["refits"] == 0
        assert ap.stats["drift_confirmed"] == 0
        assert tr.replans == []
        # the buffer was KEPT: once it crosses the floor, one tick fits
        assert len(ap.profile.fresh_measurements) == 5
        ap.observe(simulate_link_measurements(
            ALPHA0 * 16, BETA0 * 16, link_class="dcn", ops=("psum",)))
        ap.tick()
        assert ap.stats["refits"] == 1

    def test_one_window_spike_debounced(self):
        tr = FakeTrainer()
        clockv = [0.0]
        ap = make_autopilot(tr, clockv, confirm_windows=2)
        drifted = simulate_link_measurements(
            ALPHA0 * 16, BETA0 * 16, link_class="dcn", ops=("psum",))
        clean = simulate_link_measurements(
            ALPHA0, BETA0, link_class="dcn", ops=("psum",))
        for window in [drifted, clean, drifted, clean, drifted]:
            ap.observe(window)
            tr.current_step += 1
            ap.record_step(step_dt(4))
            ap.tick()                   # one refit window per tick
            clockv[0] += 0.1
        # every drifted window was isolated: streak reset each time
        assert ap.stats["refits"] == 5
        assert ap.stats["drift_confirmed"] == 0
        assert ap.stats["adoptions"] == 0 and tr.replans == []

    def test_consecutive_windows_confirm(self):
        tr = FakeTrainer()
        clockv = [0.0]
        ap = make_autopilot(tr, clockv, confirm_windows=2)
        drifted = simulate_link_measurements(
            ALPHA0 * 16, BETA0 * 16, link_class="dcn", ops=("psum",))
        for _ in range(2):
            ap.observe(drifted)
            tr.current_step += 1
            ap.record_step(step_dt(4, 16.0))
            ap.tick()
            clockv[0] += 0.1
        assert ap.stats["drift_confirmed"] == 1
        assert ap.stats["last_drift"] == pytest.approx(15.0, rel=1e-3)


# -- the adoption state machine ----------------------------------------------


class TestAdoption:
    def test_confirmed_drift_commits_through_gate(self):
        tr = FakeTrainer(dp=4)
        clockv = [0.0]
        ap = make_autopilot(tr, clockv)
        inj = FaultInjector([Fault(2, "cost_drift", magnitude=16.0)])
        ap.injector = inj
        drive(tr, ap, clockv, 10,
              lambda s: 16.0 if s >= 2 else 1.0)
        assert ap.stats["adoptions"] == 1 and ap.stats["rollbacks"] == 0
        assert tr.plan.spec.dp == 2
        assert [e["outcome"] for e in ap.adoption_log] == ["commit"]
        e = ap.adoption_log[0]
        assert e["drift"] >= ap.drift_threshold and not e["manual"]
        assert e["gate_s"] <= e["baseline_s"] * ap.gate_tolerance
        assert ap.audit() == []
        assert inj.log == [(2, "cost_drift")]

    def test_plan_regression_rolls_back(self):
        tr = FakeTrainer(dp=4)
        clockv = [0.0]
        reg = MetricsRegistry()
        ap = make_autopilot(tr, clockv, registry=reg)
        ap.injector = FaultInjector([
            Fault(2, "cost_drift", magnitude=16.0),
            Fault(2, "plan_regression", magnitude=4.0)])
        drive(tr, ap, clockv, 10,
              lambda s: 16.0 if s >= 2 else 1.0)
        assert ap.stats["adoptions"] == 0 and ap.stats["rollbacks"] == 1
        # the replan happened, then the gate measured the 4x inflation
        # and replanned straight back: [new, old]
        assert [s.dp for s in tr.replans] == [2, 4]
        assert tr.plan.spec.dp == 4
        e = ap.adoption_log[0]
        assert e["outcome"] == "rollback" and e["fault"]
        assert "measured regression" in e["reason"]
        assert reg.get("autopilot_adoptions_total").value(
            outcome="rollback") == 1
        assert reg.get("autopilot_drift_detected").value() == 0
        assert ap.audit() == []

    def test_replan_failure_rolls_back_without_reshard(self):
        tr = FakeTrainer(dp=4, fail_replans=1)
        clockv = [0.0]
        ap = make_autopilot(tr, clockv)
        ap.injector = FaultInjector([
            Fault(2, "cost_drift", magnitude=16.0)])
        drive(tr, ap, clockv, 10,
              lambda s: 16.0 if s >= 2 else 1.0)
        e = ap.adoption_log[0]
        assert e["outcome"] == "rollback"
        assert e["reason"].startswith("replan failed")
        # the forward replan raised, so there was nothing to reshard
        # back from — the trainer never left the old plan
        assert tr.replans == [] and tr.plan.spec.dp == 4
        assert not ap.adopting and ap.audit() == []

    def test_full_cycle_commit_then_regression_rollback(self):
        # the live-trainer test's choreography on the fake trainer:
        # drift 16x -> commit dp 4 -> 2, links recover + injected
        # regression -> gate rollback to dp=2
        tr = FakeTrainer(dp=4)
        clockv = [0.0]
        reg = MetricsRegistry()
        ap = make_autopilot(tr, clockv, cooldown_s=0.5, registry=reg)
        inj = FaultInjector([Fault(2, "cost_drift", magnitude=16.0),
                             Fault(8, "cost_drift", magnitude=1 / 16),
                             Fault(8, "plan_regression", magnitude=4.0)])
        ap.injector = inj

        def scale_at(step):
            return 16.0 if 2 <= step < 8 else 1.0

        drive(tr, ap, clockv, 24, scale_at)
        assert [e["outcome"] for e in ap.adoption_log] \
            == ["commit", "rollback"]
        assert tr.plan.spec.dp == 2
        assert ap.queued == 0 and not ap.adopting
        assert ap.audit() == []
        # counters match the applied-fault log exactly
        assert sorted(inj.log) == [(2, "cost_drift"), (8, "cost_drift"),
                                   (8, "plan_regression")]
        c = reg.get("autopilot_adoptions_total")
        assert (c.value(outcome="commit"),
                c.value(outcome="rollback")) == (1.0, 1.0)


    def test_full_cycle_on_a_live_elastic_trainer(self, tmp_path,
                                                  linear_elastic):
        """The same choreography closed over a real trainer on four
        devices: the commit re-shards dp 4 -> 2, the gate's rollback
        resumes from the stamped manifest, the counters match the
        applied-fault log, and the finished run is bitwise an
        uninterrupted fixed-plan run (same batches, no autopilot)."""
        import jax
        import numpy as np

        from apex_tpu.observability import FlightRecorder
        from apex_tpu.resilience import ElasticPlan, ElasticTrainer

        if len(jax.devices()) < 4:
            pytest.skip("needs 4 devices")
        le = linear_elastic
        n_steps = 16
        devices = jax.devices()[:4]

        def trainer(name, **kw):
            return ElasticTrainer(
                le.factory, ElasticPlan.build(TopologySpec(dp=4),
                                              devices=devices),
                directory=str(tmp_path / name), save_every=1,
                devices=devices, **kw)

        profile = fit_cost_model(
            simulate_link_measurements(ALPHA0, BETA0, link_class="dcn",
                                       ops=("psum",))
            + simulate_link_measurements(1e-6, 1e-10, link_class="ici",
                                         ops=("psum",)),
            meta={"source": "test"})
        inj = FaultInjector([Fault(2, "cost_drift", magnitude=16.0),
                             Fault(8, "cost_drift", magnitude=1.0 / 16.0),
                             Fault(8, "plan_regression", magnitude=4.0)])
        recorder = FlightRecorder()
        reg = MetricsRegistry()
        tr = trainer("run", fault_injector=inj, recorder=recorder)
        ap = ParallelismAutopilot(
            tr, profile, min_dp=2, link_class="dcn", drift_threshold=0.3,
            confirm_windows=2, min_measurements=8, cooldown_s=0.0,
            gate_steps=2, gate_tolerance=1.2, injector=inj, registry=reg,
            recorder=recorder)
        for step in range(n_steps):
            tr.step_once(le.batch_fn)
            ap.record_step(step_dt(tr.plan.spec.dp,
                                   16.0 if 2 <= step < 8 else 1.0))
            ap.tick()
            ap.tick()

        assert tr.plan.spec.dp == 2
        assert ap.stats["adoptions"] == 1 and ap.stats["rollbacks"] == 1
        assert ap.stats["refits"] > 0 and ap.stats["drift_confirmed"] >= 2
        assert [e["outcome"] for e in ap.adoption_log] \
            == ["commit", "rollback"]
        assert ap.audit() == []
        drifts = sum(1 for _, k in inj.log if k == "cost_drift")
        regressions = sum(1 for _, k in inj.log if k == "plan_regression")
        c = reg.get("autopilot_adoptions_total")
        commits, rollbacks = c.value(outcome="commit"), \
            c.value(outcome="rollback")
        assert (commits, rollbacks) == (1.0, 1.0)
        assert commits + rollbacks == drifts == 2
        assert rollbacks == regressions == 1
        assert reg.get("autopilot_drift_detected").value() == 0

        ref = trainer("ref")
        ref.train(le.batch_fn, n_steps)
        for x, y in zip(le.flat(tr), le.flat(ref), strict=True):
            np.testing.assert_array_equal(x, y)


# -- cooldown + queue discipline ---------------------------------------------


class TestCooldownQueue:
    def test_confirmations_during_cooldown_queue_and_coalesce(self):
        tr = FakeTrainer(dp=4)
        clockv = [0.0]
        ap = make_autopilot(tr, clockv, cooldown_s=100.0)
        # a SECOND drift lands mid-cooldown (relative to the profile
        # adopted at the first commit, the machine moves again)
        ap.injector = FaultInjector([
            Fault(2, "cost_drift", magnitude=16.0),
            Fault(9, "cost_drift", magnitude=16.0)])

        def scale_at(step):
            s = 1.0
            if step >= 2:
                s *= 16.0
            if step >= 9:
                s *= 16.0
            return s

        drive(tr, ap, clockv, 10, scale_at)
        assert ap.stats["adoptions"] == 1       # the first commit
        n_replans = len(tr.replans)
        # the re-drifted environment keeps re-confirming during
        # cooldown; every re-confirmation coalesces into ONE pending
        # request
        drive(tr, ap, clockv, 20, scale_at)
        assert ap.stats["drift_confirmed"] >= 2
        assert ap.queued <= 1
        assert len(tr.replans) == n_replans     # nothing interleaved
        assert ap.audit() == []
        # past cooldown expiry the queued request may start; with the
        # plan already optimal for the drifted machine it's a no_change
        clockv[0] += 200.0
        drive(tr, ap, clockv, 2, scale_at)
        assert ap.queued == 0
        assert ap.stats["no_change"] >= 1
        assert len(tr.replans) == n_replans
        assert ap.audit() == []

    def test_manual_request_is_audit_exempt(self):
        tr = FakeTrainer(dp=4)
        clockv = [0.0]
        ap = make_autopilot(tr, clockv)
        for _ in range(4):
            tr.current_step += 1
            ap.record_step(step_dt(4))
        ap.request_adoption()
        drive(tr, ap, clockv, 4, lambda s: 1.0)
        assert ap.adoption_log and ap.adoption_log[0]["manual"]
        assert ap.adoption_log[0]["drift"] is None
        assert ap.audit() == []                 # manual => exempt


# -- constructor validation --------------------------------------------------


class TestValidation:
    @pytest.mark.parametrize("kw", [
        {"drift_threshold": 0.0},
        {"confirm_windows": 0},
        {"gate_steps": 0},
        {"gate_tolerance": 0.9},
        {"refit_every": 0},
    ])
    def test_bad_knobs_refused(self, kw):
        with pytest.raises(ValueError):
            make_autopilot(FakeTrainer(), [0.0], **kw)


# -- fault plumbing ----------------------------------------------------------


class TestFaultKinds:
    def test_new_kinds_appended_last(self):
        assert FAULT_KINDS[-2:] == ("cost_drift", "plan_regression")

    def test_from_seed_schedule_unchanged_by_new_kinds(self):
        idx = FAULT_KINDS.index("cost_drift")
        rates = {k: 0.15 for k in FAULT_KINDS[:idx]}
        inj = FaultInjector.from_seed(5, 40, rates)
        # byte-identical to the schedule over the PRE-EXISTING kind
        # tuple: a rate-0 kind consumes no rng stream state
        expected = seeded_schedule(5, 40, FAULT_KINDS[:idx], rates)
        assert [(f.step, f.kind) for f in inj.schedule] == expected
        assert expected                         # non-vacuous

    def test_check_hooks_window_tolerant_and_consume_once(self):
        inj = FaultInjector([Fault(3, "cost_drift", magnitude=2.0),
                             Fault(5, "plan_regression")])
        assert inj.check_cost_drift(2) is None          # not due yet
        f = inj.check_cost_drift(5)                     # due (late poll)
        assert f is not None and f.step == 3
        assert inj.check_cost_drift(5) is None          # consumed
        assert inj.check_plan_regression(4) is None
        assert inj.check_plan_regression(7) is not None
        assert inj.check_plan_regression(7) is None
        # recorded at the SCHEDULED step, not the poll step
        assert inj.log == [(3, "cost_drift"), (5, "plan_regression")]

    def test_earliest_due_fault_consumed_first(self):
        inj = FaultInjector([Fault(8, "cost_drift", magnitude=0.5),
                             Fault(2, "cost_drift", magnitude=4.0)])
        assert inj.check_cost_drift(10).magnitude == 4.0
        assert inj.check_cost_drift(10).magnitude == 0.5


# -- drift scale semantics ---------------------------------------------------


class TestDriftEnvironment:
    def test_magnitude_scales_profile_and_zero_defaults(self):
        tr = FakeTrainer()
        clockv = [0.0]
        ap = make_autopilot(tr, clockv)
        ap.injector = FaultInjector([Fault(0, "cost_drift")])  # mag 0
        tr.current_step = 1
        ap.tick()
        key = ("psum", "f32", "dcn")
        assert ap._drift_env[key][0] == pytest.approx(ALPHA0 * 2.0)
        # a second fault compounds on the drifted environment
        ap.injector = FaultInjector([Fault(1, "cost_drift",
                                           magnitude=0.5)])
        ap.tick()
        assert ap._drift_env[key][0] == pytest.approx(ALPHA0)
        assert ap.stats["drift_faults"] == 2
