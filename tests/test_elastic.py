"""apex_tpu.resilience.elastic: elastic, preemption-native training.

The contract under test (ISSUE 9):

* :class:`TopologySpec` round-trips through the checkpoint manifest,
  restore warns on a topology mismatch, and ``topology_of`` reads the
  stamp without touching the payload;
* ``reshard_optimizer_state`` re-partitions optimizer state across dp
  changes with the LOGICAL values bitwise intact — per-leaf FusedAdam
  slots and packed ZeRO (reduce-scatter) buckets whose padding is
  world-size dependent;
* ``unpack_from_shard_map`` inverts ``pack_for_shard_map`` exactly —
  tp leaf splits, pp stage stacking, and the interleaved virtual-stage
  permutation;
* :class:`ElasticTrainer` reacts to injected ``topology_change`` faults
  and :class:`HostSignals` requests by drain -> checkpoint(old) ->
  replan -> reshard -> checkpoint(new) -> resume, and a shrink -> grow
  cycle is BITWISE vs. the uninterrupted run (collective world sizes
  stay <= 4: XLA CPU's psum/psum_scatter is exact there, see
  tools/crash_matrix.py);
* a hard :class:`Preemption` mid-shrink restarts into a fresh trainer
  that restores the shrunk manifest, warns, re-shards, and resumes;
* the serving engine's ``preempt()`` requeues in-flight requests with
  the (seed, token-index) sampling stream intact — greedy outputs are
  token-identical across the interruption — and the requeue count
  lands on :class:`ServingMetrics`.
"""

import dataclasses
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from apex_tpu.inference import InferenceEngine, Request
from apex_tpu.models.gpt import (GPTConfig, GPTModel, pack_for_shard_map,
                                 unpack_from_shard_map)
from apex_tpu.multi_tensor_apply import bucketing as B
from apex_tpu.optimizers import FusedAdam
from apex_tpu.parallel import DistributedFusedAdam
from apex_tpu.resilience import (CheckpointManager, ElasticComponents,
                                 ElasticPlan, ElasticSignal, ElasticTrainer,
                                 Fault, FaultInjector, GuardedTrainStep,
                                 HostSignals, Preemption, TopologySpec,
                                 ZeROGuardAdapter, reshard_optimizer_state)

needs8 = pytest.mark.skipif(len(jax.devices()) < 8,
                            reason="needs the 8-device CPU mesh")


def _loss_fn(p, x, y):
    return jnp.mean(jnp.square(x @ p["w"] + p["b"] - y))


def _params(seed=0, scale=1.0):
    r = np.random.RandomState(seed)
    return {"w": jnp.asarray((r.randn(8, 4) * scale).astype(np.float32)),
            "b": jnp.zeros((4,), jnp.float32)}


def _batch(step, plan=None):
    r = np.random.RandomState(70_000 + step)
    return (jnp.asarray(r.randn(8, 8).astype(np.float32)),
            jnp.asarray(r.randn(8, 4).astype(np.float32)))


def _tree_equal(a, b):
    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def _zero_logical_slots(st, params, ws):
    """A ZeRO state's LOGICAL m/v/master leaves: the packed padding
    depends on the world size, the values must not."""
    lay = DistributedFusedAdam(lr=1e-2, world_size=ws, axis_name="data",
                               block_rows=8)._layout(params)
    out = []
    for info in lay.buckets:
        for slot in sorted(st["buckets"][info.key]):
            arr = jnp.asarray(np.asarray(st["buckets"][info.key][slot]))
            out.extend(np.asarray(x) for x in B.unflatten_bucket(
                arr, info.meta._replace(dtype=jnp.float32)))
    return out


# -- TopologySpec / ElasticPlan ----------------------------------------------

class TestTopologySpec:
    def test_round_trip(self):
        spec = TopologySpec(dp=4, tp=2, pp=1, sequence_parallel=True,
                            zero_shard=4)
        assert TopologySpec.from_dict(spec.to_dict()) == spec
        assert spec.n_devices == 8
        assert "dp=4" in spec.describe() and "tp=2" in spec.describe()

    def test_validation(self):
        with pytest.raises(ValueError):
            TopologySpec(dp=0)
        with pytest.raises(ValueError):
            TopologySpec(dp=4, zero_shard=2)   # zero_shard must be 1 or dp
        with pytest.raises(ValueError):
            TopologySpec(sequence_parallel=True)   # SP requires tp > 1

    @needs8
    def test_plan_builds_canonical_mesh(self):
        plan = ElasticPlan.build(TopologySpec(dp=4, tp=2))
        assert plan.mesh_shape == {"data": 4, "pipe": 1, "model": 2}
        # put() replicates onto the plan's devices
        t = plan.put({"a": jnp.arange(8.0)})
        assert len(t["a"].sharding.device_set) == 8


# -- manifest topology stamping ----------------------------------------------

class TestManifestTopology:
    def test_stamp_and_read(self, tmp_path):
        spec = TopologySpec(dp=2)
        mgr = CheckpointManager(str(tmp_path), topology=spec)
        mgr.save(3, {"a": jnp.arange(4.0)})
        assert mgr.topology_of(3) == spec.to_dict()
        # mesh_shape rides along for dashboards
        import json
        man = json.loads(
            (tmp_path / "step_00000003" / "MANIFEST.json").read_text())
        assert man["mesh_shape"] == {"data": 2, "pipe": 1, "model": 1}

    def test_mismatch_warns(self, tmp_path):
        mgr = CheckpointManager(str(tmp_path), topology=TopologySpec(dp=2))
        mgr.save(1, {"a": jnp.arange(4.0)})
        with pytest.warns(UserWarning, match="topology"):
            mgr.restore({"a": jnp.zeros(4)}, topology=TopologySpec(dp=4))

    def test_match_silent(self, tmp_path):
        spec = TopologySpec(dp=2)
        mgr = CheckpointManager(str(tmp_path), topology=spec)
        mgr.save(1, {"a": jnp.arange(4.0)})
        with warnings.catch_warnings():
            warnings.simplefilter("error")
            restored, step = mgr.restore({"a": jnp.zeros(4)}, topology=spec)
        assert step == 1

    def test_unstamped_manifest_reads_none(self, tmp_path):
        mgr = CheckpointManager(str(tmp_path))
        mgr.save(1, {"a": jnp.arange(4.0)})
        assert mgr.topology_of(1) is None


# -- optimizer re-sharding ----------------------------------------------------

@needs8
class TestReshard:
    def test_per_leaf_identity_values(self):
        """dp=8 -> dp=4: per-leaf slots are replicated, so the reshard
        is a re-placement — every slot value bitwise."""
        old = ElasticPlan.build(TopologySpec(dp=8))
        new = ElasticPlan.build(TopologySpec(dp=4))
        opt = FusedAdam(lr=1e-2)
        params = old.put(_params())
        state = opt.init(params)
        g = jax.grad(_loss_fn)(params, *_batch(0))
        params, state = jax.jit(opt.step)(g, params, state)

        out = reshard_optimizer_state(state, old, new, optimizer=opt,
                                      params=params)
        _tree_equal(out, state)
        for leaf in jax.tree_util.tree_leaves(out):
            assert len(leaf.sharding.device_set) == 4

    @pytest.mark.slow
    def test_zero_round_trip_logical_bitwise(self):
        """ws=4 -> ws=2 -> ws=4: the packed padding changes with the
        world size but every LOGICAL m/v/master leaf is bitwise."""
        def mk(ws, dp):
            plan = ElasticPlan.build(TopologySpec(dp=dp, zero_shard=ws))
            opt = DistributedFusedAdam(lr=1e-2, world_size=ws,
                                       axis_name="data", block_rows=8)
            return plan, opt

        plan4, opt4 = mk(4, 4)
        plan2, opt2 = mk(2, 2)
        params = plan4.put(_params(1, scale=0.1))
        adapter = ZeROGuardAdapter(opt4, plan4.mesh)
        state = adapter.init(params)
        g = jax.grad(_loss_fn)(params, *_batch(0))
        params, state = adapter.step(g, params, state)

        ref = _zero_logical_slots(state, params, 4)
        shrunk = reshard_optimizer_state(
            state, plan4, plan2, optimizer=opt4, params=params,
            new_optimizer=opt2)
        for a, b in zip(_zero_logical_slots(shrunk, params, 2), ref):
            np.testing.assert_array_equal(a, b)
        grown = reshard_optimizer_state(
            shrunk, plan2, plan4, optimizer=opt2, params=params,
            new_optimizer=opt4)
        for a, b in zip(_zero_logical_slots(grown, params, 4), ref):
            np.testing.assert_array_equal(a, b)

    def test_zero_to_per_leaf_rejected(self):
        plan = ElasticPlan.build(TopologySpec(dp=2, zero_shard=2))
        opt = DistributedFusedAdam(lr=1e-2, world_size=2,
                                   axis_name="data", block_rows=8)
        params = plan.put(_params(1, scale=0.1))
        adapter = ZeROGuardAdapter(opt, plan.mesh)
        state = adapter.init(params)
        with pytest.raises(ValueError):
            reshard_optimizer_state(
                state, plan, ElasticPlan.build(TopologySpec(dp=2)),
                optimizer=opt, params=params,
                new_optimizer=FusedAdam(lr=1e-2))


# -- pack/unpack round trip ---------------------------------------------------

@needs8
class TestUnpackRoundTrip:
    def _model(self, tp, n_layers=4, sp=False):
        kw = dict(vocab_size=32, hidden_size=16, num_layers=n_layers,
                  num_attention_heads=4, max_seq_len=8)
        serial = GPTModel(GPTConfig(**kw))
        par = GPTModel(GPTConfig(
            tensor_parallel_size=tp,
            axis_name="model" if tp > 1 else None,
            sequence_parallel=sp, **kw))
        return serial, par, serial.init_params(jax.random.PRNGKey(3))

    def test_tp2(self):
        _, par, init = self._model(2, sp=True)
        packed, _, _, _ = pack_for_shard_map(par, init)
        _tree_equal(unpack_from_shard_map(par, packed), init)

    def test_pp2(self):
        _, par, init = self._model(1)
        packed, _, _, _ = pack_for_shard_map(par, init, n_stages=2)
        _tree_equal(unpack_from_shard_map(par, packed, n_stages=2), init)

    def test_pp2_tp2(self):
        _, par, init = self._model(2, sp=True)
        packed, _, _, _ = pack_for_shard_map(par, init, n_stages=2,
                                             tensor_axis="model")
        _tree_equal(unpack_from_shard_map(par, packed, n_stages=2), init)

    def test_interleaved_virtual_stages(self):
        _, par, init = self._model(1, n_layers=8)
        packed, _, _, _ = pack_for_shard_map(par, init, n_stages=2,
                                             n_virtual=2)
        _tree_equal(
            unpack_from_shard_map(par, packed, n_stages=2, n_virtual=2),
            init)


# -- HostSignals --------------------------------------------------------------

class TestHostSignals:
    def test_fifo_and_empty(self):
        s = HostSignals()
        assert s.poll() is None
        s.request_preempt()
        s.request_replan(TopologySpec(dp=2))
        first, second = s.poll(), s.poll()
        assert first.kind == "preempt" and first.spec is None
        assert second.kind == "replan" and second.spec == TopologySpec(dp=2)
        assert s.poll() is None

    def test_replan_requires_spec(self):
        with pytest.raises(ValueError):
            ElasticSignal("replan")
        with pytest.raises(ValueError):
            ElasticSignal("bogus")


# -- fault kind ---------------------------------------------------------------

class TestTopologyChangeFault:
    def test_fires_at_step_and_records(self):
        inj = FaultInjector([Fault(step=2, kind="topology_change",
                                   magnitude=4.0)])
        assert inj.check_topology_change(1) is None
        f = inj.check_topology_change(2)
        assert f is not None and f.magnitude == 4.0
        assert inj.check_topology_change(3) is None
        assert (2, "topology_change") in inj.log


# -- ElasticTrainer -----------------------------------------------------------

def _factory(plan, ckpt, inj):
    opt = FusedAdam(lr=1e-2)
    guard = GuardedTrainStep(_loss_fn, opt, warmup_steps=1,
                             checkpoint=ckpt, fault_injector=inj)
    params = plan.put(_params(5))
    return ElasticComponents(guard, params, opt.init(params),
                             guard.init_state())


def _flat(trainer):
    out = list(jax.tree_util.tree_leaves(trainer.params))
    st = trainer.opt_state
    for key in sorted(st["buckets"]):
        for slot in sorted(st["buckets"][key]):
            v = st["buckets"][key][slot]
            out.extend(v if isinstance(v, list) else [v])
    return [np.asarray(x) for x in out]


@needs8
class TestElasticTrainer:
    N = 5

    def _ref(self, tmp_path, spec=TopologySpec(dp=4)):
        ref = ElasticTrainer(_factory, ElasticPlan.build(spec),
                             directory=str(tmp_path / "ref"))
        ref.train(_batch, self.N)
        return _flat(ref)

    def test_injected_shrink_grow_bitwise(self, tmp_path):
        ref = self._ref(tmp_path)
        inj = FaultInjector([Fault(step=1, kind="topology_change"),
                             Fault(step=3, kind="topology_change")])
        tr = ElasticTrainer(_factory, ElasticPlan.build(TopologySpec(dp=4)),
                            directory=str(tmp_path / "a"),
                            fault_injector=inj)
        out = tr.train(_batch, self.N)
        assert out == {"status": "completed", "step": self.N, "replans": 2,
                       "preempt_signals": 2, "rollbacks": 0}
        assert tr.plan.spec == TopologySpec(dp=4)
        for a, b in zip(_flat(tr), ref):
            np.testing.assert_array_equal(a, b)
        assert tr.checkpoint.topology_of(self.N) == \
            TopologySpec(dp=4).to_dict()

    def test_zero_injected_shrink_grow_logical_bitwise(self, tmp_path):
        """The same cycle with ZeRO state: dp=4/ws=4 -> dp=2/ws=2 ->
        dp=4/ws=4 through injected ``topology_change`` faults ends on the
        uninterrupted run's params and logical optimizer slots, bitwise
        (world sizes <= 4: XLA:CPU's psum / psum_scatter is a pairwise
        tree there, so reductions of identical copies are exact)."""
        devices = jax.devices()[:4]
        zbase = TopologySpec(dp=4, zero_shard=4)

        def zfactory(plan, ckpt, inj):
            inner = DistributedFusedAdam(lr=1e-2,
                                         world_size=plan.spec.zero_shard,
                                         axis_name="data", block_rows=8)
            adapter = ZeROGuardAdapter(inner, plan.mesh)
            guard = GuardedTrainStep(_loss_fn, adapter, warmup_steps=1,
                                     checkpoint=ckpt, fault_injector=inj)
            params = plan.put(_params(4, scale=0.1))
            return ElasticComponents(guard, params, adapter.init(params),
                                     guard.init_state(), optimizer=inner)

        def canon(tr):
            return ([np.asarray(x)
                     for x in jax.tree_util.tree_leaves(tr.params)]
                    + _zero_logical_slots(tr.opt_state, tr.params,
                                          zbase.zero_shard))

        ref = ElasticTrainer(zfactory,
                             ElasticPlan.build(zbase, devices=devices),
                             directory=str(tmp_path / "ref"))
        ref.train(_batch, self.N)
        inj = FaultInjector([Fault(step=1, kind="topology_change"),
                             Fault(step=3, kind="topology_change")])
        tr = ElasticTrainer(zfactory,
                            ElasticPlan.build(zbase, devices=devices),
                            directory=str(tmp_path / "a"),
                            fault_injector=inj)
        out = tr.train(_batch, self.N)
        assert out["status"] == "completed" and out["replans"] == 2, out
        assert tr.plan.spec == zbase
        for a, b in zip(canon(tr), canon(ref), strict=True):
            np.testing.assert_array_equal(a, b)

    def test_host_signal_replan_and_in_place_rebuild(self, tmp_path):
        """A replan request to the SAME spec is an in-place rebuild —
        it must execute (replans += 1) and be bitwise-neutral."""
        ref = self._ref(tmp_path)
        signals = HostSignals()
        tr = ElasticTrainer(_factory, ElasticPlan.build(TopologySpec(dp=4)),
                            directory=str(tmp_path / "a"), signals=signals)

        def batch(step, plan):
            if step == 1:
                signals.request_replan(TopologySpec(dp=4))
            return _batch(step, plan)

        out = tr.train(batch, self.N)
        assert out["status"] == "completed" and out["replans"] == 1
        for a, b in zip(_flat(tr), ref):
            np.testing.assert_array_equal(a, b)

    def test_soft_preempt_drains_and_checkpoints(self, tmp_path):
        signals = HostSignals()
        tr = ElasticTrainer(_factory, ElasticPlan.build(TopologySpec(dp=4)),
                            directory=str(tmp_path / "a"), signals=signals)

        def batch(step, plan):
            if step == 1:
                signals.request_preempt()
            return _batch(step, plan)

        out = tr.train(batch, self.N)
        assert out["status"] == "preempted" and out["step"] == 2
        # a fresh trainer resumes from the drain checkpoint and matches
        ref = self._ref(tmp_path)
        tr2 = ElasticTrainer(_factory, ElasticPlan.build(TopologySpec(dp=4)),
                             directory=str(tmp_path / "a"))
        out2 = tr2.train(_batch, self.N)
        assert out2["status"] == "completed"
        for a, b in zip(_flat(tr2), ref):
            np.testing.assert_array_equal(a, b)

    def test_hard_preempt_while_shrunk_restores_and_regrows(self, tmp_path):
        """The restart-as-grow path: shrink at step 1, hard kill at
        step 2, fresh dp=4 trainer restores the dp=2-stamped manifest
        (with a mismatch warning), re-shards, resumes — bitwise."""
        ref = self._ref(tmp_path)
        inj = FaultInjector([Fault(step=1, kind="topology_change"),
                             Fault(step=2, kind="preempt_at_step")])
        tr = ElasticTrainer(_factory, ElasticPlan.build(TopologySpec(dp=4)),
                            directory=str(tmp_path / "a"),
                            fault_injector=inj)
        with pytest.raises(Preemption):
            tr.train(_batch, self.N)

        tr2 = ElasticTrainer(_factory, ElasticPlan.build(TopologySpec(dp=4)),
                             directory=str(tmp_path / "a"))
        with pytest.warns(UserWarning, match="topology"):
            out = tr2.train(_batch, self.N)
        assert out["status"] == "completed"
        assert tr2.plan.spec == TopologySpec(dp=4)
        for a, b in zip(_flat(tr2), ref):
            np.testing.assert_array_equal(a, b)

    def test_registry_series(self, tmp_path):
        from apex_tpu.observability import MetricsRegistry
        reg = MetricsRegistry()
        inj = FaultInjector([Fault(step=1, kind="topology_change")])
        tr = ElasticTrainer(_factory, ElasticPlan.build(TopologySpec(dp=4)),
                            directory=str(tmp_path / "a"),
                            fault_injector=inj, registry=reg)
        tr.train(_batch, 3)
        assert reg.get("elastic_replans").value() == 1
        assert reg.get("elastic_preempt_signals").value() == 1
        assert reg.get("elastic_resume_step").value() == 1
        assert tr.stats["last_reshard_s"] > 0


@needs8
class TestSteppableAPI:
    """The externally-driven surface the capacity controller consumes:
    start/step_once/replan_to must compose to exactly what train()
    does — same steps, same checkpoints, bitwise-same state."""

    N = 5

    def test_step_once_loop_matches_train_bitwise(self, tmp_path):
        ref = ElasticTrainer(_factory, ElasticPlan.build(TopologySpec(dp=4)),
                             directory=str(tmp_path / "ref"))
        ref.train(_batch, self.N)
        tr = ElasticTrainer(_factory, ElasticPlan.build(TopologySpec(dp=4)),
                            directory=str(tmp_path / "a"))
        assert tr.start() == 0
        assert tr.start() == 0                   # idempotent no-op
        while tr.current_step < self.N:
            assert tr.step_once(_batch) == "ran"
        assert tr.current_step == self.N
        for a, b in zip(_flat(tr), _flat(ref)):
            np.testing.assert_array_equal(a, b)

    def test_external_replan_to_matches_injected_shrink_grow(self, tmp_path):
        """Driving the SAME shrink->grow cycle through replan_to() as
        an injected topology_change fault produces must land bitwise on
        the uninterrupted reference — the two drain paths are one."""
        ref = ElasticTrainer(_factory, ElasticPlan.build(TopologySpec(dp=4)),
                             directory=str(tmp_path / "ref"))
        ref.train(_batch, self.N)
        tr = ElasticTrainer(_factory, ElasticPlan.build(TopologySpec(dp=4)),
                            directory=str(tmp_path / "a"))
        for step in range(self.N):
            if step == 1:
                tr.replan_to(TopologySpec(dp=2))
                assert tr.plan.spec == TopologySpec(dp=2)
            if step == 3:
                tr.replan_to(TopologySpec(dp=4))
            assert tr.step_once(_batch) == "ran"
        assert tr.plan.spec == TopologySpec(dp=4)
        assert tr.stats["last_reshard_s"] > 0
        assert tr.stats["last_checkpoint_s"] > 0
        for a, b in zip(_flat(tr), _flat(ref)):
            np.testing.assert_array_equal(a, b)

    def test_step_once_surfaces_preempt_then_resumes(self, tmp_path):
        signals = HostSignals()
        tr = ElasticTrainer(_factory, ElasticPlan.build(TopologySpec(dp=4)),
                            directory=str(tmp_path / "a"), signals=signals)
        assert tr.step_once(_batch) == "ran"
        signals.request_preempt()
        assert tr.step_once(_batch) == "preempted"
        assert tr.current_step == 1              # drained at the boundary
        # the day-in-the-life restart idiom: fresh trainer, same
        # directory, resumes from the drain checkpoint and matches
        ref = ElasticTrainer(_factory, ElasticPlan.build(TopologySpec(dp=4)),
                             directory=str(tmp_path / "ref"))
        ref.train(_batch, self.N)
        tr2 = ElasticTrainer(_factory, ElasticPlan.build(TopologySpec(dp=4)),
                             directory=str(tmp_path / "a"))
        assert tr2.start() == 1
        while tr2.current_step < self.N:
            tr2.step_once(_batch)
        for a, b in zip(_flat(tr2), _flat(ref)):
            np.testing.assert_array_equal(a, b)

    def test_failed_replan_restores_stamp_and_continues(self, tmp_path):
        ref = ElasticTrainer(_factory, ElasticPlan.build(TopologySpec(dp=4)),
                             directory=str(tmp_path / "ref"))
        ref.train(_batch, self.N)
        tr = ElasticTrainer(_factory, ElasticPlan.build(TopologySpec(dp=4)),
                            directory=str(tmp_path / "a"))
        tr.step_once(_batch)
        with pytest.raises(ValueError, match="devices"):
            tr.replan_to(TopologySpec(dp=16))    # only 8 devices exist
        # the failure left the trainer consistent: stamp still dp=4,
        # training continues and still lands bitwise on the reference
        assert tr.plan.spec == TopologySpec(dp=4)
        assert tr.checkpoint.topology_of(tr.current_step) == \
            TopologySpec(dp=4).to_dict()
        while tr.current_step < self.N:
            assert tr.step_once(_batch) == "ran"
        for a, b in zip(_flat(tr), _flat(ref)):
            np.testing.assert_array_equal(a, b)


# -- serving-engine preemption ------------------------------------------------

class TestEnginePreempt:
    def _model(self):
        cfg = GPTConfig(vocab_size=32, hidden_size=16, num_layers=2,
                        num_attention_heads=2, max_seq_len=16)
        model = GPTModel(cfg)
        return model, model.init_params(jax.random.PRNGKey(0))

    def _reqs(self, n=3):
        return [Request(request_id=i, prompt=[1 + i, 2, 3],
                        max_new_tokens=5) for i in range(n)]

    def test_requeue_token_parity(self):
        model, params = self._model()
        ref_eng = InferenceEngine(model, params, max_slots=2,
                                  cache_dtype=jnp.float32)
        for r in self._reqs():
            ref_eng.submit(r)
        ref = {r.request_id: r.tokens for r in ref_eng.run()}

        eng = InferenceEngine(model, params, max_slots=2,
                              cache_dtype=jnp.float32)
        for r in self._reqs():
            eng.submit(r)
        eng.step()
        eng.step()
        n = eng.preempt()
        assert n >= 1
        assert eng.metrics.summary()["requeued"] == n
        got = {r.request_id: r.tokens for r in eng.run()}
        assert got == ref
        # no leaks across the interruption
        assert eng.trace.pending == 0
        assert eng._progress == {}

    def test_preempt_overflow_finishes_preempted(self):
        """A request whose prompt + generated no longer fits a cache
        row cannot be requeued: it finishes with reason='preempted'.
        The step loop finishes such requests with 'length' first, so
        the branch is defensive — force the state directly."""
        model, params = self._model()
        eng = InferenceEngine(model, params, max_slots=1,
                              cache_dtype=jnp.float32)
        eng.submit(Request(request_id=0, prompt=[1, 2],
                           max_new_tokens=8))
        eng.step()
        st = next(iter(eng._active.values()))
        pad = eng.cache.max_seq - len(st.request.prompt)
        st.generated.extend([1] * (pad - len(st.generated)))
        assert eng.preempt() == 0
        byid = {r.request_id: r for r in eng.completed}
        assert byid[0].finish_reason == "preempted"

    def test_preempt_idle_noop(self):
        model, params = self._model()
        eng = InferenceEngine(model, params, max_slots=1,
                              cache_dtype=jnp.float32)
        assert eng.preempt() == 0
        assert eng.metrics.summary()["requeued"] == 0
