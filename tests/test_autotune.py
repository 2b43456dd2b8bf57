"""ParallelPlan unification + tools/autotune.py (ISSUE 11).

The contract under test:

* ``ParallelPlan`` rejects every invalid knob combination the engines
  would choke on — overlap without SP, SP without TP, interleaved
  schedules whose microbatch count doesn't divide by the stage count,
  ``zero_shard`` not in ``{1, dp}``, unknown transport dtypes — so a
  plan that constructs is a plan every consumer accepts;
* ``TopologySpec`` is a lossless projection: ``plan.topology()`` /
  ``spec.to_plan()`` round-trip, and a PR-9-format stamped manifest
  dict (version-less) lifts into a plan whose projection equals the
  original spec;
* per-knob kwargs keep working WITHOUT warnings (back-compat shims);
  a conflicting non-default knob next to an attached plan warns
  ``DeprecationWarning`` and the plan wins;
* checkpoint manifests keep the PR-9 ``topology`` schema byte-for-byte
  and stamp the full plan under the separate ``parallel_plan`` key;
* the planner's memory prune orders canonical programs by their real
  compiled peaks, and the emitted report round-trips through
  ``load_plan`` version-checked;
* (8-device mesh) the full prune -> rank -> measure pass at
  dp/tp/pp <= 2 lands the cost-model-ranked winner inside the measured
  top-3.

The mesh-driving tests carry ``needs8`` (``conftest.py`` gives tier-1
eight CPU devices).
"""

import json
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.models.gpt import GPTConfig
from apex_tpu.parallel import (DistributedFusedAdam, ParallelPlan,
                               PLAN_VERSION)
from apex_tpu.resilience import (CheckpointManager, ElasticPlan,
                                 ElasticSignal, ElasticTrainer,
                                 GuardedTrainStep, HostSignals, TopologySpec)
from tools.autotune import (AUTOTUNE_VERSION, Candidate, autotune,
                            emit_plan, enumerate_space, load_plan,
                            predict_compute_s)

needs8 = pytest.mark.skipif(len(jax.devices()) < 8,
                            reason="needs the 8-device CPU mesh")


# -- plan validation ----------------------------------------------------------


class TestPlanValidation:
    def test_defaults_are_serial(self):
        p = ParallelPlan()
        assert p.n_devices == 1 and p.axis_name is None

    @pytest.mark.parametrize("kw", [
        dict(overlap_chunks=2, tp=2, sequence_parallel=False),
        dict(overlap_chunks=2),                      # overlap without SP
        dict(sequence_parallel=True),                # SP without TP
        dict(dp=2, zero_shard=3),                    # zero not in {1, dp}
        dict(n_virtual=2),                           # interleave without pp
        dict(pp=2, n_virtual=2, n_microbatches=3),   # M % pp != 0
        dict(allreduce_dtype="int4"),
        dict(remat_policy="everything"),
        dict(dp=0),
        dict(tp=-2),
        dict(overlap_chunks=-1),
    ])
    def test_invalid_combinations_raise(self, kw):
        with pytest.raises(ValueError):
            ParallelPlan(**kw)

    def test_interleaved_divisibility_matches_ring_engine(self):
        # the plan-level gate mirrors the ring engine's trace-time
        # raise ("interleaved schedule needs n_microbatches % n_stages
        # == 0", ring.py) so a bad schedule never reaches compile
        with pytest.raises(ValueError, match="n_microbatches"):
            ParallelPlan(pp=2, n_virtual=2, n_microbatches=3)

    def test_f32_transport_normalizes_to_none(self):
        assert ParallelPlan(allreduce_dtype="f32").allreduce_dtype is None

    def test_describe_and_dict_round_trip(self):
        p = ParallelPlan(dp=2, tp=2, pp=2, sequence_parallel=True,
                         overlap_chunks=2, n_virtual=2, n_microbatches=4,
                         remat=True, remat_policy="dots",
                         allreduce_dtype="bf16")
        d = p.to_dict()
        assert d["version"] == PLAN_VERSION
        assert ParallelPlan.from_dict(d) == p
        assert "tp=2" in p.describe()

    def test_version_mismatch_refuses(self):
        d = ParallelPlan(dp=2).to_dict()
        d["version"] = PLAN_VERSION + 1
        with pytest.raises(ValueError, match="version"):
            ParallelPlan.from_dict(d)


# -- TopologySpec projection + PR-9 manifest compat ---------------------------


class TestTopologyProjection:
    def test_round_trip(self):
        p = ParallelPlan(dp=2, tp=2, pp=2, sequence_parallel=True,
                         n_microbatches=2, zero_shard=1)
        spec = p.topology()
        assert isinstance(spec, TopologySpec)
        assert (spec.dp, spec.tp, spec.pp) == (2, 2, 2)
        assert spec.to_plan(n_microbatches=2) == p

    def test_pr9_manifest_dict_lifts_losslessly(self):
        # a version-less topology dict exactly as PR 9's
        # CheckpointManager stamped it
        spec = TopologySpec(dp=4, tp=2, pp=1, sequence_parallel=True,
                            zero_shard=1)
        old_manifest_dict = spec.to_dict()
        assert "version" not in old_manifest_dict
        p = ParallelPlan.from_dict(old_manifest_dict)
        assert p.topology() == spec
        assert p.topology().to_dict() == old_manifest_dict

    def test_elastic_plan_builds_from_parallel_plan(self):
        ep = ElasticPlan.build(ParallelPlan(dp=1),
                               devices=jax.devices()[:1])
        assert isinstance(ep.spec, TopologySpec)
        assert ep.parallel == ParallelPlan(dp=1)
        # plain spec keeps parallel unset
        ep2 = ElasticPlan.build(TopologySpec(dp=1),
                                devices=jax.devices()[:1])
        assert ep2.parallel is None

    def test_signals_accept_plans(self):
        hs = HostSignals()
        hs.request_replan(ParallelPlan(dp=2))
        sig = hs.poll()
        assert sig.kind == "replan" and sig.spec == ParallelPlan(dp=2)
        with pytest.raises(ValueError, match="target"):
            ElasticSignal("replan")


# -- back-compat shims --------------------------------------------------------


class TestBackCompat:
    _kw = dict(vocab_size=32, hidden_size=16, num_layers=2,
               num_attention_heads=4, max_seq_len=8)

    def test_per_knob_kwargs_still_work_silently(self):
        with warnings.catch_warnings():
            warnings.simplefilter("error")
            cfg = GPTConfig(tensor_parallel_size=2, axis_name="model",
                            sequence_parallel=True, **self._kw)
            opt = DistributedFusedAdam(lr=1e-3, world_size=1,
                                       allreduce_dtype="bf16")
        assert cfg.tensor_parallel_size == 2
        assert opt.allreduce_dtype == "bf16"

    def test_plan_fills_config_knobs(self):
        with warnings.catch_warnings():
            warnings.simplefilter("error")
            cfg = GPTConfig(plan=ParallelPlan(tp=2, sequence_parallel=True,
                                              remat=True,
                                              remat_policy="dots"),
                            **self._kw)
        assert cfg.tensor_parallel_size == 2
        assert cfg.sequence_parallel and cfg.remat
        assert cfg.remat_policy == "dots"
        assert cfg.axis_name == "model"

    def test_conflicting_knob_warns_and_plan_wins(self):
        with pytest.warns(DeprecationWarning, match="superseded"):
            cfg = GPTConfig(tensor_parallel_size=4, axis_name="model",
                            sequence_parallel=True,
                            plan=ParallelPlan(tp=2,
                                              sequence_parallel=True),
                            **self._kw)
        assert cfg.tensor_parallel_size == 2

    def test_optimizer_conflict_warns_and_plan_wins(self):
        plan = ParallelPlan(dp=2, zero_shard=2, allreduce_dtype="bf16")
        with pytest.warns(DeprecationWarning, match="zero_shard"):
            opt = DistributedFusedAdam(lr=1e-3, world_size=4, plan=plan)
        assert opt.world_size == 2
        assert opt.allreduce_dtype == "bf16"

    def test_guard_cross_checks_zero_shard(self):
        opt = DistributedFusedAdam(lr=1e-3, world_size=2)
        with pytest.raises(ValueError, match="zero_shard"):
            GuardedTrainStep(lambda p, x, y: 0.0, opt,
                             plan=ParallelPlan(dp=4, zero_shard=4))

    def test_engine_rejects_mismatched_plan(self):
        from apex_tpu.inference.engine import InferenceEngine
        from apex_tpu.models.gpt import GPTModel
        model = GPTModel(GPTConfig(**self._kw))
        params = model.init_params(jax.random.PRNGKey(0))
        with pytest.raises(ValueError, match="pipeline"):
            InferenceEngine(model, params, plan=ParallelPlan(pp=2))
        with pytest.raises(ValueError, match="sequence_parallel"):
            InferenceEngine(model, params,
                            plan=ParallelPlan(tp=2,
                                              sequence_parallel=True))
        with pytest.raises(ValueError, match="tensor_parallel_size"):
            InferenceEngine(model, params, plan=ParallelPlan(tp=2))
        # a matching plan is fine
        eng = InferenceEngine(model, params, plan=ParallelPlan())
        assert eng.plan == ParallelPlan()


# -- checkpoint manifest stamping ---------------------------------------------


class TestManifestPlan:
    def test_topology_key_schema_unchanged(self, tmp_path):
        plan = ParallelPlan(dp=2, n_microbatches=2, remat=True)
        mgr = CheckpointManager(str(tmp_path), topology=plan.topology(),
                                parallel_plan=plan)
        mgr.save(3, {"a": jnp.arange(4.0)})
        man = json.loads(
            (tmp_path / "step_00000003" / "MANIFEST.json").read_text())
        # the PR-9 consumers keep reading exactly what they always did
        assert man["topology"] == plan.topology().to_dict()
        assert man["mesh_shape"] == {"data": 2, "pipe": 1, "model": 1}
        # the full plan rides in its own key and round-trips
        assert ParallelPlan.from_dict(man["parallel_plan"]) == plan
        assert ParallelPlan.from_dict(mgr.plan_of(3)) == plan

    def test_old_checkpoints_read_none(self, tmp_path):
        mgr = CheckpointManager(str(tmp_path), topology=TopologySpec(dp=2))
        mgr.save(1, {"a": jnp.arange(4.0)})
        assert mgr.plan_of(1) is None

    def test_restore_stays_silent_with_plan_attached(self, tmp_path):
        plan = ParallelPlan(dp=2)
        mgr = CheckpointManager(str(tmp_path), topology=plan.topology(),
                                parallel_plan=plan)
        mgr.save(1, {"a": jnp.arange(4.0)})
        with warnings.catch_warnings():
            warnings.simplefilter("error")
            _, step = mgr.restore({"a": jnp.zeros(4)},
                                  topology=plan.topology())
        assert step == 1


# -- search-space enumeration -------------------------------------------------


class TestEnumeration:
    def test_engine_constraints_recorded_as_rejections(self):
        cands = enumerate_space(8, n_layers=4, n_heads=4, batch=8,
                                seq=16)
        reasons = [c.reason for c in cands if c.status == "rejected"]
        assert any("requires sequence parallelism" in r for r in reasons)
        assert any("not divisible" in r for r in reasons)
        # every surviving plan is a real validated ParallelPlan
        valid = [c for c in cands if c.status == "enumerated"]
        assert valid and all(isinstance(c.plan, ParallelPlan)
                             for c in valid)
        assert all(c.plan.n_devices == 8 for c in valid)

    def test_zero_gated_to_unit_tp_pp(self):
        cands = enumerate_space(8, n_layers=4, n_heads=4, batch=8,
                                seq=16)
        for c in cands:
            if c.status == "enumerated" and c.plan.zero_shard > 1:
                assert c.plan.tp == 1 and c.plan.pp == 1

    def test_restriction_flags(self):
        cands = enumerate_space(8, n_layers=4, n_heads=4, batch=8,
                                seq=16, max_tp=1, max_pp=1, zero=False,
                                remat_options=(False,))
        valid = [c.plan for c in cands if c.status == "enumerated"]
        assert valid == [ParallelPlan(dp=8)]


# -- cost + memory models -----------------------------------------------------


class TestCostModel:
    def test_roofline_monotonic_in_devices_and_remat(self):
        base = predict_compute_s(ParallelPlan(dp=2), 1000, 8, 16, 1e9)
        more_dev = predict_compute_s(ParallelPlan(dp=4), 1000, 8, 16, 1e9)
        remat = predict_compute_s(ParallelPlan(dp=2, remat=True),
                                  1000, 8, 16, 1e9)
        assert more_dev < base < remat

    def test_pipeline_bubble_penalizes_few_microbatches(self):
        few = predict_compute_s(
            ParallelPlan(pp=2, n_microbatches=2), 1000, 8, 16, 1e9)
        many = predict_compute_s(
            ParallelPlan(pp=2, n_microbatches=8), 1000, 8, 16, 1e9)
        assert many < few

    def test_memory_prune_orders_canonical_programs(self):
        # two programs with a known peak ordering: the prune criterion
        # (estimated peak vs budget) must separate them at any budget
        # between the two compiled peaks
        from apex_tpu.analysis.memory import estimate_peak_memory
        small = jax.jit(lambda x: (x * 2.0).sum()).lower(
            jnp.ones((64,), jnp.float32)).compile()
        big = jax.jit(lambda x: (x @ x.T).sum()).lower(
            jnp.ones((256, 256), jnp.float32)).compile()
        e_small = estimate_peak_memory(small)
        e_big = estimate_peak_memory(big)
        assert e_small.peak_bytes < e_big.peak_bytes
        budget = (e_small.peak_bytes + e_big.peak_bytes) / 2
        assert e_small.peak_bytes <= budget < e_big.peak_bytes

    def test_candidate_report_dict(self):
        c = Candidate(plan=ParallelPlan(dp=2), status="ranked",
                      peak_bytes=123, predicted_s=0.5)
        d = c.to_dict()
        assert d["plan"]["dp"] == 2 and d["peak_bytes"] == 123
        assert "measured_s" not in d


# -- emitted-report round-trip ------------------------------------------------


class TestReportRoundTrip:
    def test_load_plan_version_checked(self, tmp_path):
        plan = ParallelPlan(dp=2, remat=True)
        path = tmp_path / "plan.json"
        emit_plan(str(path), {"version": AUTOTUNE_VERSION,
                              "plan": plan.to_dict(), "candidates": []})
        assert load_plan(str(path)) == plan
        emit_plan(str(path), {"version": AUTOTUNE_VERSION + 1,
                              "plan": plan.to_dict()})
        with pytest.raises(ValueError, match="version"):
            load_plan(str(path))


# -- the full planner on the 8-device mesh ------------------------------------


@needs8
class TestAutotuneOnMesh:
    @pytest.mark.slow
    def test_rank_agreement_dp_tp_pp_2(self, tmp_path):
        """Prune -> rank -> measure over the dp/tp/pp <= 2 corner of the
        space (includes the full 2x2x2 mesh): every survivor's memory
        estimate is inside the 1.5x XLA gate, the cost-model-ranked
        winner lands inside the measured top-3, and its measured time is
        within bounded regret of the measured best — on a 1-core host
        the measured spread between good candidates is scheduler noise,
        so the agreement bound is a regret ratio, not a strict rank."""
        cfg_kw = dict(vocab_size=64, hidden_size=32, num_layers=2,
                      num_attention_heads=4, max_seq_len=16)
        report = autotune(8, cfg_kw=cfg_kw, batch=8, hbm_bytes=1 << 30,
                          top_k=3, max_tp=2, max_pp=2, zero=False,
                          remat_options=(False,), verbose=False)
        cands = report["candidates"]
        ranked = [c for c in cands
                  if c["status"] in ("ranked", "measured")]
        assert any(c["plan"]["dp"] == 2 and c["plan"]["tp"] == 2
                   and c["plan"]["pp"] == 2 for c in ranked)
        for c in ranked:
            if c.get("xla_ratio") is not None:
                assert 1 / 1.5 <= c["xla_ratio"] <= 1.5, c
        measured = sorted((c for c in cands if c["status"] == "measured"),
                          key=lambda c: c["measured_s"])
        assert len(measured) == 3
        # the measured set IS the predicted top-3 of the ranked pool
        pred_sorted = sorted(ranked, key=lambda c: c["predicted_s"])
        assert {json.dumps(c["plan"], sort_keys=True) for c in measured} \
            == {json.dumps(c["plan"], sort_keys=True)
                for c in pred_sorted[:3]}
        predicted_best = min(measured, key=lambda c: c["predicted_s"])
        top3 = [c["plan"] for c in measured[:3]]
        assert predicted_best["plan"] in top3, (
            f"cost-model winner {predicted_best['plan']} not in "
            f"measured top-3 {top3}")
        assert predicted_best["measured_s"] <= 2.5 * \
            measured[0]["measured_s"]
        # the emitted winner is the measured fastest and round-trips
        path = tmp_path / "plan.json"
        emit_plan(str(path), report)
        assert load_plan(str(path)) == ParallelPlan.from_dict(
            measured[0]["plan"])
        assert report["plan"] == measured[0]["plan"]

    def test_memory_budget_prunes(self):
        cfg_kw = dict(vocab_size=64, hidden_size=32, num_layers=2,
                      num_attention_heads=4, max_seq_len=16)
        with pytest.raises(RuntimeError, match="budget"):
            autotune(8, cfg_kw=cfg_kw, batch=8, hbm_bytes=1024,
                     max_tp=1, max_pp=1, zero=False,
                     remat_options=(False,), verbose=False)

    def test_emitted_winner_drives_a_live_replan_bitwise(self, tmp_path,
                                                         linear_elastic):
        """The whole loop the tool exists for: prune -> rank -> measure
        over the dp-only, per-leaf corner on four devices, emit the
        plan, and hand it to a RUNNING :class:`ElasticTrainer` at dp=2,
        which re-shards onto it and ends bitwise where an uninterrupted
        run under the winner ends (world sizes <= 4: XLA:CPU's psum is a
        pairwise tree there, so reductions are exact)."""
        le = linear_elastic
        devices = jax.devices()[:4]
        cfg_kw = dict(vocab_size=32, hidden_size=16, num_layers=2,
                      num_attention_heads=4, max_seq_len=8)
        report = autotune(4, cfg_kw=cfg_kw, batch=8, hbm_bytes=1 << 30,
                          top_k=3, max_tp=1, max_pp=1, zero=False,
                          devices=devices, verbose=False)
        cands = report["candidates"]
        measured = sorted((c for c in cands if c["status"] == "measured"),
                          key=lambda c: c["measured_s"])
        assert measured and report["plan"] == measured[0]["plan"]
        for c in cands:
            if c.get("xla_ratio") is not None:
                assert 1 / 1.5 <= c["xla_ratio"] <= 1.5, c
        emit_plan(str(tmp_path / "plan.json"), report)
        winner = load_plan(str(tmp_path / "plan.json"))
        assert winner == ParallelPlan.from_dict(report["plan"])
        assert (winner.dp, winner.tp, winner.pp, winner.zero_shard) \
            == (4, 1, 1, 1)

        n_steps = 4
        ref = ElasticTrainer(le.factory,
                             ElasticPlan.build(winner, devices=devices),
                             directory=str(tmp_path / "ref"))
        ref.train(le.batch_fn, n_steps)

        signals = HostSignals()
        el = ElasticTrainer(le.factory,
                            ElasticPlan.build(TopologySpec(dp=2),
                                              devices=devices),
                            directory=str(tmp_path / "el"), signals=signals,
                            devices=devices)

        def sig_batch(step, plan):
            if step == 1:
                signals.request_replan(winner)
            return le.batch_fn(step, plan)

        out = el.train(sig_batch, n_steps)
        assert out["status"] == "completed" and out["replans"] == 1, out
        assert el.plan.spec == winner.topology()
        assert el.plan.parallel == winner
        for x, y in zip(le.flat(el), le.flat(ref), strict=True):
            np.testing.assert_array_equal(x, y)
        # the re-plan stamped the full plan into the manifest
        stamped = el.checkpoint.plan_of(el.checkpoint.latest_step())
        assert ParallelPlan.from_dict(stamped) == winner
