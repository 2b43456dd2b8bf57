"""apex_tpu.resilience: checkpointing, anomaly guard, fault injection.

The contract under test (ISSUE 4):

* checkpoint round-trips are BITWISE across optimizer-state layouts —
  per-leaf FusedAdam, packed ZeRO DistributedFusedAdam (dp=2, state
  row-sharded under shard_map), and TP=2 sequence-parallel params — and
  the restored state produces bitwise-identical next-step grads;
* the commit protocol survives a kill at any point: tmp dirs and
  manifest-less dirs are never candidates, a corrupted payload is
  caught by the content hash and restore falls back to the previous
  complete checkpoint;
* kill-and-resume parity: training interrupted by an injected
  :class:`Preemption` and resumed from the latest checkpoint is
  bitwise identical (f32 params AND optimizer slots) to the
  uninterrupted run — at dp=2 and at dp=2 x tp=2 + sequence parallel;
* the guard skips NaN/inf/spike steps with optimizer state untouched
  (the loss-scaler overflow-skip semantics) and rolls back after K
  consecutive anomalies;
* the serving engine quarantines poison requests (reason="error"),
  enforces per-request timeouts distinct from deadline eviction, and
  applies bounded-queue backpressure (QueueFull).
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from apex_tpu.amp.scaler import LossScaler
from apex_tpu.contrib.optimizers import DistributedFusedAdam
from apex_tpu.inference import (InferenceEngine, QueueFull, Request,
                                SamplingParams)
from apex_tpu.models.gpt import GPTConfig, GPTModel, pack_for_shard_map
from apex_tpu.optimizers import FusedAdam
from apex_tpu.resilience import (CheckpointManager, CheckpointNotFound,
                                 Fault, FaultInjector, GuardedTrainStep,
                                 Preemption)
from jax import shard_map

DIN, DOUT, BATCH = 8, 4, 8


def _params(seed=0):
    r = np.random.RandomState(seed)
    return {"w": jnp.asarray(r.randn(DIN, DOUT).astype(np.float32)),
            "b": jnp.asarray(r.randn(DOUT).astype(np.float32))}


def _loss_fn(p, x, y):
    return jnp.mean(jnp.square(x @ p["w"] + p["b"] - y))


def _batch(step, batch=BATCH, din=DIN, dout=DOUT):
    """Per-step seeded batch: both arms of a parity test replay the
    exact same data stream."""
    r = np.random.RandomState(10_000 + step)
    return (jnp.asarray(r.randn(batch, din).astype(np.float32)),
            jnp.asarray(r.randn(batch, dout).astype(np.float32)))


def _tree_equal(a, b):
    la = jax.tree_util.tree_leaves(a)
    lb = jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# -- checkpoint round-trips across state layouts ------------------------------

class TestCheckpointRoundTrip:
    def test_per_leaf_fused_adam(self, tmp_path):
        """Default layout: FusedAdam per-leaf moments.  Restored state is
        bitwise AND the next optimizer step from it is bitwise."""
        params = _params()
        opt = FusedAdam(lr=1e-2)
        state = opt.init(params)
        x, y = _batch(0)
        grads = jax.grad(_loss_fn)(params, x, y)
        params, state = jax.jit(opt.step)(grads, params, state)

        mgr = CheckpointManager(str(tmp_path))
        mgr.save(1, {"params": params, "opt": state})
        template = jax.tree_util.tree_map(
            jnp.zeros_like, {"params": params, "opt": state})
        restored, step = mgr.restore(template)
        assert step == 1
        _tree_equal(restored, {"params": params, "opt": state})

        x, y = _batch(1)
        g = jax.grad(_loss_fn)(params, x, y)
        g_r = jax.grad(_loss_fn)(restored["params"], x, y)
        _tree_equal(g, g_r)
        p1, s1 = jax.jit(opt.step)(g, params, state)
        p2, s2 = jax.jit(opt.step)(g_r, restored["params"],
                                   restored["opt"])
        _tree_equal(p1, p2)
        _tree_equal(s1, s2)

    def test_packed_zero_dp2(self, tmp_path):
        """ZeRO layout: DistributedFusedAdam's packed (rows, 128) buckets
        are row-sharded over dp=2 — each shard saves its slice, restore
        re-places onto the template's sharding, and the next distributed
        step is bitwise."""
        mesh = jax.make_mesh((2,), ("data",))
        params = _params()
        opt = DistributedFusedAdam(lr=1e-2, world_size=2, block_rows=8)
        state = opt.make_init(mesh)(params)
        step = opt.make_step(mesh)
        r = np.random.RandomState(7)
        stacked = jax.tree_util.tree_map(
            lambda p: jnp.asarray(
                r.randn(2, *p.shape).astype(np.float32) * 0.1), params)
        params, state = step(stacked, params, state)

        mgr = CheckpointManager(str(tmp_path))
        mgr.save(1, {"params": params, "opt": state})
        # the live state is the template: structure + target shardings
        restored, _ = mgr.restore({"params": params, "opt": state})
        _tree_equal(restored, {"params": params, "opt": state})
        for got, want in zip(
                jax.tree_util.tree_leaves(restored["opt"]),
                jax.tree_util.tree_leaves(state)):
            if hasattr(want, "sharding"):
                assert got.sharding == want.sharding

        p1, s1 = step(stacked, params, state)
        p2, s2 = step(stacked, restored["params"], restored["opt"])
        _tree_equal(p1, p2)
        _tree_equal(s1, s2)

    def test_tp2_sequence_parallel_params(self, tmp_path):
        """TP=2 + SP: packed params (TP leaves stacked over the model
        axis) round-trip bitwise and the restored pack produces bitwise
        next-step grads through the sequence-parallel step."""
        cfg = GPTConfig(vocab_size=32, hidden_size=16, num_layers=2,
                        num_attention_heads=4, max_seq_len=8,
                        tensor_parallel_size=2, axis_name="model",
                        sequence_parallel=True)
        par = GPTModel(cfg)
        serial = GPTModel(GPTConfig(vocab_size=32, hidden_size=16,
                                    num_layers=2, num_attention_heads=4,
                                    max_seq_len=8))
        params = serial.init_params(jax.random.PRNGKey(1))
        mesh = jax.make_mesh((2,), ("model",))
        packed, in_specs, local_fn, repack_fn = pack_for_shard_map(
            par, params)
        packed = jax.tree_util.tree_map(
            lambda x, s: jax.device_put(x, NamedSharding(mesh, s)),
            packed, in_specs, is_leaf=lambda x: isinstance(x, P))

        mgr = CheckpointManager(str(tmp_path))
        mgr.save(0, packed)
        restored, _ = mgr.restore(packed)
        _tree_equal(restored, packed)

        r = np.random.RandomState(3)
        tokens = jnp.asarray(r.randint(0, 32, (2, 8)))
        targets = jnp.asarray(r.randint(0, 32, (2, 8)))

        def body(sp, tk, tg):
            loss, g = jax.value_and_grad(par.loss)(local_fn(sp), tk, tg)
            return loss, repack_fn(g)

        f = jax.jit(shard_map(body, mesh=mesh,
                              in_specs=(in_specs, P(), P()),
                              out_specs=(P(), in_specs), check_vma=False))
        loss1, g1 = f(packed, tokens, targets)
        loss2, g2 = f(restored, tokens, targets)
        assert float(loss1) == float(loss2)
        _tree_equal(g1, g2)

    def test_restore_onto_different_topology(self, tmp_path):
        """A checkpoint saved from 2-way-sharded arrays restores onto an
        unsharded template (gather) and onto a 4-way mesh (re-shard)."""
        mesh2 = jax.make_mesh((2,), ("data",))
        arr = jnp.arange(8 * 4, dtype=jnp.float32).reshape(8, 4)
        sharded = jax.device_put(arr, NamedSharding(mesh2, P("data")))
        mgr = CheckpointManager(str(tmp_path))
        mgr.save(0, {"a": sharded})

        gathered, _ = mgr.restore({"a": jnp.zeros_like(arr)})
        np.testing.assert_array_equal(np.asarray(gathered["a"]),
                                      np.asarray(arr))

        mesh4 = jax.make_mesh((4,), ("data",))
        tmpl = jax.device_put(jnp.zeros_like(arr),
                              NamedSharding(mesh4, P("data")))
        resharded, _ = mgr.restore({"a": tmpl})
        np.testing.assert_array_equal(np.asarray(resharded["a"]),
                                      np.asarray(arr))
        assert resharded["a"].sharding == tmpl.sharding


# -- commit protocol / corruption ---------------------------------------------

class TestCommitProtocol:
    def test_corrupt_payload_falls_back(self, tmp_path):
        state0 = {"a": jnp.arange(4.0)}
        state1 = {"a": jnp.arange(4.0) + 100.0}
        mgr = CheckpointManager(str(tmp_path), keep=3)
        mgr.save(1, state0)
        path2 = mgr.save(2, state1)
        with open(os.path.join(path2, "state.bin"), "r+b") as f:
            f.seek(4)
            f.write(b"\xff\xff\xff\xff")
        with pytest.warns(UserWarning, match="corrupt"):
            restored, step = mgr.restore({"a": jnp.zeros(4)})
        assert step == 1
        np.testing.assert_array_equal(np.asarray(restored["a"]),
                                      np.asarray(state0["a"]))

    def test_injected_corruption(self, tmp_path):
        """The corrupt_checkpoint fault flips bytes after commit; the
        hash must catch it and the injector log must show it landed."""
        inj = FaultInjector([Fault(step=2, kind="corrupt_checkpoint")])
        mgr = CheckpointManager(str(tmp_path), keep=3,
                                fault_injector=inj)
        mgr.save(1, {"a": jnp.arange(6.0)})
        mgr.save(2, {"a": jnp.arange(6.0) * 2})
        assert (2, "corrupt_checkpoint") in inj.log
        with pytest.warns(UserWarning, match="corrupt"):
            _, step = mgr.restore({"a": jnp.zeros(6)})
        assert step == 1

    def test_torn_and_manifestless_dirs_ignored(self, tmp_path):
        mgr = CheckpointManager(str(tmp_path))
        mgr.save(3, {"a": jnp.ones(2)})
        # a kill mid-write leaves a tmp dir; a kill between payload and
        # manifest leaves a dir without a manifest — neither is a
        # candidate
        os.makedirs(tmp_path / "step_00000007.tmp")
        (tmp_path / "step_00000007.tmp" / "state.bin").write_bytes(b"xx")
        os.makedirs(tmp_path / "step_00000009")
        (tmp_path / "step_00000009" / "state.bin").write_bytes(b"yy")
        assert mgr.all_steps() == [3]
        _, step = mgr.restore({"a": jnp.zeros(2)})
        assert step == 3

    def test_latest_symlink_and_retire(self, tmp_path):
        mgr = CheckpointManager(str(tmp_path), keep=2)
        for s in (1, 2, 3):
            mgr.save(s, {"a": jnp.full((2,), float(s))})
        assert os.readlink(tmp_path / "latest") == "step_00000003"
        assert mgr.all_steps() == [2, 3]      # keep=2 retired step 1

    def test_empty_dir_raises(self, tmp_path):
        mgr = CheckpointManager(str(tmp_path))
        with pytest.raises(CheckpointNotFound):
            mgr.restore({"a": jnp.zeros(2)})

    def test_async_double_buffered(self, tmp_path):
        mgr = CheckpointManager(str(tmp_path), keep=4)
        states = [{"a": jnp.full((3,), float(s))} for s in range(3)]
        for s, st in enumerate(states):
            mgr.save_async(s, st)
        mgr.wait()
        assert mgr.all_steps() == [0, 1, 2]
        restored, step = mgr.restore({"a": jnp.zeros(3)})
        assert step == 2
        np.testing.assert_array_equal(np.asarray(restored["a"]),
                                      np.asarray(states[2]["a"]))


# -- fault injector ------------------------------------------------------------

class TestFaultInjector:
    def test_from_seed_deterministic(self):
        rates = {"nan_grads": 0.3, "grad_spike": 0.3, "slow_host": 0.2}
        a = FaultInjector.from_seed(11, 50, rates)
        b = FaultInjector.from_seed(11, 50, rates)
        assert a.schedule == b.schedule
        assert len(a.schedule) > 0
        c = FaultInjector.from_seed(12, 50, rates)
        assert c.schedule != a.schedule

    def test_unknown_kind_rejected(self):
        with pytest.raises(ValueError, match="unknown fault kind"):
            Fault(step=0, kind="cosmic_ray")
        with pytest.raises(ValueError, match="unknown fault kinds"):
            FaultInjector.from_seed(0, 10, {"cosmic_ray": 1.0})

    def test_grad_flags_identity_on_clean_steps(self):
        inj = FaultInjector([Fault(step=3, kind="nan_grads")])
        assert inj.grad_flags(0) == {"nan_grads": 0.0, "inf_loss": 0.0,
                                     "spike_scale": 1.0}
        flags = inj.grad_flags(3)
        assert flags["nan_grads"] == 1.0
        assert inj.log == [(3, "nan_grads")]

    def test_preempt_raises(self):
        inj = FaultInjector([Fault(step=5, kind="preempt_at_step")])
        inj.check_preempt(4)
        with pytest.raises(Preemption) as e:
            inj.check_preempt(5)
        assert e.value.step == 5


# -- anomaly guard ------------------------------------------------------------

def _make_guard(**kw):
    opt = FusedAdam(lr=1e-2)
    guard = GuardedTrainStep(_loss_fn, opt, **kw)
    params = _params()
    return guard, params, opt.init(params), guard.init_state()


class TestGuardedTrainStep:
    def test_clean_steps_update_params(self):
        guard, params, opt_state, gstate = _make_guard()
        for step in range(3):
            x, y = _batch(step)
            res = guard(params, opt_state, gstate, x, y, step=step)
            assert not res.skipped and res.anomaly is None
            params, opt_state, gstate = (res.params, res.opt_state,
                                         res.guard_state)
        assert guard.stats["skipped"] == 0
        assert int(gstate.clean_steps) == 3

    @pytest.mark.parametrize("kind,field", [("nan_grads", "nonfinite"),
                                            ("inf_loss", "nonfinite")])
    def test_nonfinite_step_skipped(self, kind, field):
        inj = FaultInjector([Fault(step=1, kind=kind)])
        guard, params, opt_state, gstate = _make_guard(fault_injector=inj)
        x, y = _batch(0)
        res = guard(params, opt_state, gstate, x, y, step=0)
        p1, o1, g1 = res.params, res.opt_state, res.guard_state
        x, y = _batch(1)
        res = guard(p1, o1, g1, x, y, step=1)
        assert res.skipped and res.anomaly == "nonfinite"
        # the skip left params AND optimizer slots untouched (the
        # loss-scaler overflow-skip contract, on-device)
        _tree_equal(res.params, p1)
        _tree_equal(res.opt_state, o1)
        assert guard.stats[field] == 1
        assert int(res.guard_state.anomalies) == 1

    def test_grad_spike_skipped_after_warmup(self):
        inj = FaultInjector([Fault(step=4, kind="grad_spike",
                                   magnitude=1000.0)])
        guard, params, opt_state, gstate = _make_guard(
            fault_injector=inj, warmup_steps=2, spike_factor=10.0)
        for step in range(5):
            x, y = _batch(step)
            res = guard(params, opt_state, gstate, x, y, step=step)
            if step < 4:
                assert not res.skipped
                params, opt_state, gstate = (res.params, res.opt_state,
                                             res.guard_state)
        assert res.skipped and res.anomaly == "spike"
        assert guard.stats["spikes"] == 1
        # the spike did not feed the EMA
        assert int(res.guard_state.clean_steps) == 4

    def test_rollback_after_k_consecutive(self, tmp_path):
        mgr = CheckpointManager(str(tmp_path))
        inj = FaultInjector([Fault(step=s, kind="nan_grads")
                             for s in (2, 3, 4)])
        guard, params, opt_state, gstate = _make_guard(
            fault_injector=inj, max_consecutive=3, checkpoint=mgr)
        step = 0
        while step < 2:
            x, y = _batch(step)
            res = guard(params, opt_state, gstate, x, y, step=step)
            params, opt_state, gstate = (res.params, res.opt_state,
                                         res.guard_state)
            step = res.next_step
        guard.save(2, params, opt_state, gstate)
        good = jax.tree_util.tree_map(lambda x: np.asarray(x), params)
        for step in (2, 3, 4):
            x, y = _batch(step)
            res = guard(params, opt_state, gstate, x, y, step=step)
            params, opt_state, gstate = (res.params, res.opt_state,
                                         res.guard_state)
        assert res.rolled_back and res.restored_from == 2
        assert res.next_step == 2
        assert guard.stats["rollbacks"] == 1
        _tree_equal(params, good)

    def test_scaler_skip_and_checkpoint_roundtrip(self, tmp_path):
        """Dynamic loss scaling through the guard: an injected inf loss
        counts as an overflow (scale halves, cumulative skipped
        increments) and the scaler state round-trips through the
        checkpoint."""
        scaler = LossScaler("dynamic", init_scale=2.0 ** 8)
        inj = FaultInjector([Fault(step=1, kind="inf_loss")])
        opt = FusedAdam(lr=1e-2)
        guard = GuardedTrainStep(_loss_fn, opt, scaler=scaler,
                                 fault_injector=inj)
        params = _params()
        opt_state, gstate = opt.init(params), guard.init_state()
        sstate = scaler.init()
        for step in range(2):
            x, y = _batch(step)
            res = guard(params, opt_state, gstate, x, y,
                        scaler_state=sstate, step=step)
            params, opt_state, gstate, sstate = (
                res.params, res.opt_state, res.guard_state,
                res.scaler_state)
        assert float(sstate.loss_scale) == 2.0 ** 7       # halved
        assert int(sstate.skipped) == 1
        assert guard.stats["scaler_skipped_steps"] == 1

        mgr = CheckpointManager(str(tmp_path))
        guard.checkpoint = mgr
        guard.save(2, params, opt_state, gstate, sstate)
        restored, _ = mgr.restore(guard._template(params, opt_state,
                                                  gstate, sstate))
        assert int(restored["scaler"].skipped) == 1
        _tree_equal(restored["scaler"], sstate)

    def test_misuse_raises(self):
        opt = FusedAdam(lr=1e-2)
        with pytest.raises(ValueError, match="exactly one"):
            GuardedTrainStep(_loss_fn, opt, grad_fn=lambda p: None)
        with pytest.raises(ValueError, match="loss_fn form"):
            GuardedTrainStep(None, opt, grad_fn=lambda p: None,
                             scaler=LossScaler())
        guard, params, opt_state, gstate = _make_guard()
        x, y = _batch(0)
        with pytest.raises(ValueError, match="scaler_state"):
            guard(params, opt_state, gstate, x, y,
                  scaler_state=LossScaler().init())


# -- kill-and-resume parity (the tentpole proof) ------------------------------

def _dp_grad_fn(mesh, loss_fn=_loss_fn):
    """Data-parallel grads: batch sharded over 'data', loss and grads
    pmean-reduced inside the shard_map region."""
    def body(p, x, y):
        loss, g = jax.value_and_grad(loss_fn)(p, x, y)
        loss = jax.lax.pmean(loss, "data")
        g = jax.tree_util.tree_map(
            lambda a: jax.lax.pmean(a, "data"), g)
        return loss, g
    return shard_map(body, mesh=mesh,
                     in_specs=(P(), P("data"), P("data")),
                     out_specs=(P(), P()), check_vma=False)


def _drive(guard, n_steps, params, opt_state, gstate, batch_fn,
           start=0, save_every=1):
    """The train loop a resilient job runs: step, then checkpoint the
    state ABOUT TO run ``next_step``.  Raises Preemption through."""
    step = start
    while step < n_steps:
        x, y = batch_fn(step)
        res = guard(params, opt_state, gstate, x, y, step=step)
        params, opt_state, gstate = (res.params, res.opt_state,
                                     res.guard_state)
        step = res.next_step
        if step % save_every == 0:
            guard.save(step, params, opt_state, gstate)
    return params, opt_state, gstate


class TestKillAndResumeDP2:
    N_STEPS = 5
    KILL_AT = 3

    def _fresh(self, ckpt_dir, injector=None):
        mesh = jax.make_mesh((2,), ("data",))
        opt = FusedAdam(lr=1e-2)
        mgr = CheckpointManager(str(ckpt_dir)) if ckpt_dir else None
        guard = GuardedTrainStep(grad_fn=_dp_grad_fn(mesh), optimizer=opt,
                                 checkpoint=mgr, fault_injector=injector)
        # the train state lives on the mesh (replicated), like a real
        # dp job's — single-device-committed arrays can't enter a jit
        # whose shard_map spans the mesh
        rep = NamedSharding(mesh, P())
        params = jax.device_put(_params(), rep)
        return (guard, params, jax.device_put(opt.init(params), rep),
                jax.device_put(guard.init_state(), rep))

    def test_resume_is_bitwise(self, tmp_path):
        # arm A: uninterrupted
        guard, params, opt_state, gstate = self._fresh(tmp_path / "a")
        ref_p, ref_o, _ = _drive(guard, self.N_STEPS, params, opt_state,
                                 gstate, _batch)

        # arm B: preempted at KILL_AT, resumed from the checkpoint
        inj = FaultInjector([Fault(step=self.KILL_AT,
                                   kind="preempt_at_step")])
        guard, params, opt_state, gstate = self._fresh(tmp_path / "b",
                                                       injector=inj)
        with pytest.raises(Preemption):
            _drive(guard, self.N_STEPS, params, opt_state, gstate, _batch)

        # restart: a FRESH process has only the checkpoint directory
        guard2, params0, opt0, g0 = self._fresh(tmp_path / "b")
        restored, step = guard2.checkpoint.restore(
            guard2._template(params0, opt0, g0, None))
        assert step == self.KILL_AT
        got_p, got_o, _ = _drive(guard2, self.N_STEPS, restored["params"],
                                 restored["opt"], restored["guard"],
                                 _batch, start=int(
                                     np.asarray(restored["step"])))
        _tree_equal(got_p, ref_p)         # f32 params: bitwise
        _tree_equal(got_o, ref_o)         # optimizer slots: bitwise


class TestKillAndResumeDP2TP2SP:
    """dp=2 x tp=2 + sequence parallelism on the (2, 2) mesh: the
    checkpoint carries TP-stacked params and per-leaf Adam slots; resume
    must be bitwise against the uninterrupted run."""
    N_STEPS = 3
    KILL_AT = 2
    B, S = 4, 8

    @staticmethod
    def _gpt_batch(step):
        r = np.random.RandomState(20_000 + step)
        return (jnp.asarray(r.randint(0, 32, (4, 8))),
                jnp.asarray(r.randint(0, 32, (4, 8))))

    def _fresh(self, ckpt_dir, injector=None):
        cfg = GPTConfig(vocab_size=32, hidden_size=16, num_layers=2,
                        num_attention_heads=4, max_seq_len=8,
                        tensor_parallel_size=2, axis_name="model",
                        sequence_parallel=True)
        par = GPTModel(cfg)
        serial_params = GPTModel(GPTConfig(
            vocab_size=32, hidden_size=16, num_layers=2,
            num_attention_heads=4,
            max_seq_len=8)).init_params(jax.random.PRNGKey(5))
        mesh = jax.make_mesh((2, 2), ("data", "model"),
                             axis_types=(jax.sharding.AxisType.Auto,) * 2)
        packed, in_specs, local_fn, repack_fn = pack_for_shard_map(
            par, serial_params)

        def body(sp, tk, tg):
            loss, g = jax.value_and_grad(par.loss)(local_fn(sp), tk, tg)
            loss = jax.lax.pmean(loss, "data")
            g = jax.tree_util.tree_map(
                lambda a: jax.lax.pmean(a, "data"), g)
            return loss, repack_fn(g)

        grad_fn = shard_map(body, mesh=mesh,
                            in_specs=(in_specs, P("data"), P("data")),
                            out_specs=(P(), in_specs), check_vma=False)
        opt = FusedAdam(lr=1e-2)
        mgr = CheckpointManager(str(ckpt_dir))
        guard = GuardedTrainStep(grad_fn=grad_fn, optimizer=opt,
                                 checkpoint=mgr, fault_injector=injector)
        rep = NamedSharding(mesh, P())
        packed = jax.device_put(packed, rep)
        return (guard, packed, jax.device_put(opt.init(packed), rep),
                jax.device_put(guard.init_state(), rep))

    def test_resume_is_bitwise(self, tmp_path):
        guard, params, opt_state, gstate = self._fresh(tmp_path / "a")
        ref_p, ref_o, _ = _drive(guard, self.N_STEPS, params, opt_state,
                                 gstate, self._gpt_batch)

        inj = FaultInjector([Fault(step=self.KILL_AT,
                                   kind="preempt_at_step")])
        guard, params, opt_state, gstate = self._fresh(tmp_path / "b",
                                                       injector=inj)
        with pytest.raises(Preemption):
            _drive(guard, self.N_STEPS, params, opt_state, gstate,
                   self._gpt_batch)

        guard2, params0, opt0, g0 = self._fresh(tmp_path / "b")
        restored, step = guard2.checkpoint.restore(
            guard2._template(params0, opt0, g0, None))
        assert step == self.KILL_AT
        got_p, got_o, _ = _drive(guard2, self.N_STEPS,
                                 restored["params"], restored["opt"],
                                 restored["guard"], self._gpt_batch,
                                 start=int(np.asarray(restored["step"])))
        _tree_equal(got_p, ref_p)
        _tree_equal(got_o, ref_o)


class TestKillAndResumeDP2PP2:
    """dp=2 x pp=2 ring pipeline: the checkpoint carries stage-stacked
    params and the grad_fn is a 1F1B scan under shard_map; resume must
    be bitwise against the uninterrupted run.  (tools/crash_matrix.py
    sweeps the full kill-step x fault matrix for this component and the
    tp=2 x pp=2 + SP one.)"""
    N_STEPS = 3
    KILL_AT = 2
    M, MB, SEQ = 2, 2, 8

    @staticmethod
    def _gpt_batch(step):
        r = np.random.RandomState(30_000 + step)
        return (jnp.asarray(r.randint(0, 32, (8, 8))),
                jnp.asarray(r.randint(0, 32, (8, 8))))

    def _fresh(self, ckpt_dir, injector=None):
        from apex_tpu.models.gpt import pipeline_step

        model = GPTModel(GPTConfig(
            vocab_size=32, hidden_size=16, num_layers=2,
            num_attention_heads=4, max_seq_len=8))
        init = model.init_params(jax.random.PRNGKey(7))
        mesh = jax.make_mesh((2, 2), ("data", "pipe"),
                             axis_types=(jax.sharding.AxisType.Auto,) * 2)
        packed, in_specs, local_fn, repack_fn = pack_for_shard_map(
            model, init, n_stages=2, tensor_axis=None)
        M, mb, seq = self.M, self.MB, self.SEQ

        def body(sp, tk, tg):
            # pipeline_step reduces loss/grads over data_axis itself
            loss, g = pipeline_step(model, local_fn(sp),
                                    tk.reshape(M, mb, seq),
                                    tg.reshape(M, mb, seq),
                                    pipe_axis="pipe", data_axis="data")
            return loss, repack_fn(g)

        grad_fn = shard_map(body, mesh=mesh,
                            in_specs=(in_specs, P("data"), P("data")),
                            out_specs=(P(), in_specs), check_vma=False)
        opt = FusedAdam(lr=1e-2)
        mgr = CheckpointManager(str(ckpt_dir))
        guard = GuardedTrainStep(grad_fn=grad_fn, optimizer=opt,
                                 checkpoint=mgr, fault_injector=injector)
        rep = NamedSharding(mesh, P())
        packed = jax.device_put(packed, rep)
        return (guard, packed, jax.device_put(opt.init(packed), rep),
                jax.device_put(guard.init_state(), rep))

    def test_resume_is_bitwise(self, tmp_path):
        guard, params, opt_state, gstate = self._fresh(tmp_path / "a")
        ref_p, ref_o, _ = _drive(guard, self.N_STEPS, params, opt_state,
                                 gstate, self._gpt_batch)

        inj = FaultInjector([Fault(step=self.KILL_AT,
                                   kind="preempt_at_step")])
        guard, params, opt_state, gstate = self._fresh(tmp_path / "b",
                                                       injector=inj)
        with pytest.raises(Preemption):
            _drive(guard, self.N_STEPS, params, opt_state, gstate,
                   self._gpt_batch)

        guard2, params0, opt0, g0 = self._fresh(tmp_path / "b")
        restored, step = guard2.checkpoint.restore(
            guard2._template(params0, opt0, g0, None))
        assert step == self.KILL_AT
        got_p, got_o, _ = _drive(guard2, self.N_STEPS,
                                 restored["params"], restored["opt"],
                                 restored["guard"], self._gpt_batch,
                                 start=int(np.asarray(restored["step"])))
        _tree_equal(got_p, ref_p)
        _tree_equal(got_o, ref_o)


# -- serving-engine resilience ------------------------------------------------

def _engine(**kw):
    cfg = GPTConfig(vocab_size=32, hidden_size=16, num_layers=2,
                    num_attention_heads=2, max_seq_len=16)
    model = GPTModel(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    return InferenceEngine(model, params, cache_dtype=jnp.float32, **kw)


class TestEngineResilience:
    def test_submit_validation(self):
        eng = _engine(max_slots=1)
        with pytest.raises(ValueError, match="prompt token"):
            eng.submit(Request(request_id=0, prompt=[1, 99]))   # >= vocab
        with pytest.raises(ValueError, match="prompt token"):
            eng.submit(Request(request_id=1, prompt=[1, 2.5]))
        with pytest.raises(ValueError, match="max_new_tokens"):
            eng.submit(Request(request_id=2, prompt=[1], max_new_tokens=0))
        with pytest.raises(ValueError, match="SamplingParams"):
            eng.submit(Request(request_id=3, prompt=[1],
                               sampling={"temperature": 1.0}))
        with pytest.raises(ValueError, match="timeout"):
            eng.submit(Request(request_id=4, prompt=[1], timeout=0.0))
        with pytest.raises(ValueError, match="eos_id"):
            eng.submit(Request(request_id=5, prompt=[1], eos_id=1.5))
        assert eng.queue_depth == 0      # nothing slipped through

    def test_bounded_queue_backpressure(self):
        eng = _engine(max_slots=1, max_queue=2)
        eng.submit(Request(request_id=0, prompt=[1], max_new_tokens=1))
        eng.submit(Request(request_id=1, prompt=[2], max_new_tokens=1))
        with pytest.raises(QueueFull):
            eng.submit(Request(request_id=2, prompt=[3],
                               max_new_tokens=1))
        eng.step()                        # drains one into a slot
        eng.submit(Request(request_id=2, prompt=[3], max_new_tokens=1))
        out = eng.run()
        assert sorted(r.request_id for r in out) == [0, 1, 2]
        with pytest.raises(ValueError, match="max_queue"):
            _engine(max_slots=1, max_queue=0)

    def test_poison_request_quarantined(self):
        """A sampling config that passes static validation but detonates
        at decode time finishes with reason="error"; its slot frees and
        every other request completes normally."""
        eng = _engine(max_slots=2)
        # top_k=2.5 passes SamplingParams' >0 check but breaks sampling
        eng.submit(Request(request_id=0, prompt=[1, 2],
                           max_new_tokens=3,
                           sampling=SamplingParams(temperature=1.0,
                                                   top_k=2.5)))
        eng.submit(Request(request_id=1, prompt=[3, 4], max_new_tokens=3))
        out = {r.request_id: r for r in eng.run()}
        assert out[0].finish_reason == "error"
        assert out[0].error is not None
        assert out[1].finish_reason == "length"
        assert len(out[1].tokens) == 3
        assert eng.cache.free_slots == 2         # the slot was freed
        assert eng.metrics.summary()["errors"] == 1

    def test_device_program_failure_is_not_quarantined(self):
        """A compile or runtime failure of the prefill program is the
        engine's, not the request's: it must raise out of run() instead
        of becoming a quiet reason="error" response."""
        eng = _engine(max_slots=2)

        def broken_prefill(params, tokens):
            raise RuntimeError("RESOURCE_EXHAUSTED: out of HBM")

        eng._prefill = broken_prefill
        eng.submit(Request(request_id=0, prompt=[1, 2], max_new_tokens=3))
        with pytest.raises(RuntimeError, match="out of HBM"):
            eng.run()
        assert eng.completed == []

    def test_per_request_timeout_distinct_from_eviction(self):
        t = [0.0]

        def clock():
            t[0] += 1.0
            return t[0]

        eng = _engine(max_slots=3, clock=clock)
        eng.submit(Request(request_id=0, prompt=[1, 2],
                           max_new_tokens=100, timeout=25.0))
        eng.submit(Request(request_id=1, prompt=[3, 4],
                           max_new_tokens=100, deadline=40.0))
        eng.submit(Request(request_id=2, prompt=[5, 6], max_new_tokens=2))
        out = {r.request_id: r for r in eng.run(max_steps=200)}
        assert out[0].finish_reason == "timeout"
        assert 0 < len(out[0].tokens) < 100      # partial output kept
        assert out[1].finish_reason == "evicted"
        assert out[2].finish_reason == "length"
        s = eng.metrics.summary()
        assert s["timeouts"] == 1 and s["evicted"] == 1

    def test_queued_timeout_expires_empty(self):
        t = [0.0]

        def clock():
            t[0] += 1.0
            return t[0]

        eng = _engine(max_slots=1, clock=clock)
        eng.submit(Request(request_id=0, prompt=[1], max_new_tokens=50))
        eng.submit(Request(request_id=1, prompt=[2], max_new_tokens=50,
                           timeout=5.0))        # starved in the queue
        out = {r.request_id: r for r in eng.run(max_steps=200)}
        assert out[1].finish_reason == "timeout" and out[1].tokens == []
        assert eng.metrics.summary()["timeouts"] == 1
