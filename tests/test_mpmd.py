"""Cross-pod MPMD pipeline (ISSUE 14): plan validation, two-tier cost
model, DCN channel + faults, schedules, and the engine's bitwise parity
against the single-mesh ring engine."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from jax import shard_map
from jax.sharding import PartitionSpec as P

from apex_tpu.models.gpt import (GPTConfig, GPTModel, _is_sharded,
                                 _is_spec_leaf, pack_for_shard_map,
                                 pipeline_step)
from apex_tpu.mpmd import (SCHEDULES, DcnTimeout, Edge, LocalDcnChannel,
                           MpmdPipeline, Op, edge_link_classes,
                           merge_stage_ops, schedule_1f1b,
                           schedule_dcn_hiding, simulate, stage_ops_1f1b,
                           validate_order)
from apex_tpu.mpmd.engine import MPMD_PLAN_FILE
from apex_tpu.parallel.plan import ParallelPlan
from apex_tpu.resilience.faults import (FAULT_KINDS, Fault, FaultInjector,
                                        seeded_schedule)


# ---------------------------------------------------------------------------
# ParallelPlan cross-pod validation (each message pinned)
# ---------------------------------------------------------------------------


def test_plan_n_pods_must_divide_pp():
    with pytest.raises(ValueError, match=r"n_pods \(3\) must divide pp"):
        ParallelPlan(pp=4, n_pods=3)


def test_plan_n_pods_positive_int():
    with pytest.raises(ValueError, match="n_pods must be a positive int"):
        ParallelPlan(n_pods=0)


def test_plan_n_pods_rejects_interleaving():
    with pytest.raises(ValueError,
                       match="does not compose with n_pods"):
        ParallelPlan(pp=4, n_pods=2, n_virtual=2, n_microbatches=4)


def test_plan_stage_plans_need_pods():
    with pytest.raises(ValueError,
                       match="stage_plans given but n_pods is 1"):
        ParallelPlan(pp=2, stage_plans=(ParallelPlan(), ParallelPlan()))


def test_plan_stage_plans_count_must_match():
    with pytest.raises(ValueError,
                       match="has 1 entries but n_pods is 2"):
        ParallelPlan(pp=2, n_pods=2, stage_plans=(ParallelPlan(),))


def test_plan_stage_plans_must_be_intra_pod():
    with pytest.raises(ValueError, match=r"stage_plans\[0\] must be an "
                                         "intra-pod SPMD plan"):
        ParallelPlan(pp=2, n_pods=2,
                     stage_plans=(ParallelPlan(pp=2, n_microbatches=2),
                                  ParallelPlan()))


def test_plan_stage_plans_dp_must_match():
    with pytest.raises(ValueError, match=r"stage_plans\[1\].dp \(2\) "
                                         "must equal"):
        ParallelPlan(dp=1, pp=2, n_pods=2,
                     stage_plans=(ParallelPlan(dp=1),
                                  ParallelPlan(dp=2)))


def test_plan_stage_plans_not_a_sequence():
    with pytest.raises(ValueError, match="must be a sequence"):
        ParallelPlan(pp=2, n_pods=2, stage_plans=ParallelPlan())


def test_plan_cross_pod_dict_round_trip():
    plan = ParallelPlan(dp=2, pp=4, n_microbatches=4, n_pods=2,
                        stage_plans=(
                            ParallelPlan(dp=2),
                            ParallelPlan(dp=2, tp=2,
                                         sequence_parallel=True)))
    back = ParallelPlan.from_dict(plan.to_dict())
    assert back == plan
    assert back.stage_plans[1].tp == 2
    # heterogeneous pods: 2 stages/pod x (2*1 + 2*2) devices
    assert plan.n_devices == 2 * (2 + 4)
    assert "pods=2" in plan.describe()


def test_plan_single_pod_dict_stays_pre_mpmd():
    d = ParallelPlan(dp=2).to_dict()
    assert "n_pods" not in d and "stage_plans" not in d


# ---------------------------------------------------------------------------
# dcn_fault kind: appended last, byte-identical schedules, consume-once
# ---------------------------------------------------------------------------


def test_dcn_fault_precedes_later_appended_kinds():
    # dcn_fault was appended last in its PR; later kinds (cost_drift,
    # plan_regression) append AFTER it, never before — rate-0 kinds
    # consume no rng, so the relative order is what keeps every
    # pre-existing from_seed schedule byte-identical.
    assert FAULT_KINDS.index("dcn_fault") == len(FAULT_KINDS) - 3
    assert FAULT_KINDS[-2:] == ("cost_drift", "plan_regression")


def test_dcn_fault_rate0_consumes_no_rng():
    # schedules for the pre-existing kinds must be byte-identical
    # whether or not the dcn_fault kind exists in the key list
    rates = {"nan_grads": 0.2, "preempt_at_step": 0.1}
    old = seeded_schedule(3, 50, FAULT_KINDS[:-1], rates)
    new = seeded_schedule(3, 50, FAULT_KINDS, rates)
    assert old == new
    inj = FaultInjector.from_seed(3, 50, rates)
    assert [(f.step, f.kind) for f in inj.schedule] == old


def test_check_dcn_consumes_once():
    inj = FaultInjector([Fault(4, "dcn_fault")])
    assert inj.check_dcn(3) is None
    f = inj.check_dcn(4)
    assert f is not None and f.kind == "dcn_fault"
    assert inj.check_dcn(4) is None            # consumed: retry runs clean
    assert inj.log == [(4, "dcn_fault")]


# ---------------------------------------------------------------------------
# the DCN channel
# ---------------------------------------------------------------------------


def test_channel_send_is_byte_exact_and_accounted():
    ch = LocalDcnChannel(alpha_s=1e-3, beta_s_per_byte=1e-9)
    x = {"a": jnp.arange(6, dtype=jnp.float32),
         "b": jnp.ones((2, 3), jnp.int32)}
    out = ch.send(x, step=0, edge=Edge(0, 1, "dcn"))
    for a, b in zip(jax.tree_util.tree_leaves(out),
                    jax.tree_util.tree_leaves(x), strict=True):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert ch.sends == 1
    assert ch.bytes_sent == 6 * 4 + 6 * 4
    assert ch.simulated_seconds == pytest.approx(
        1e-3 + 1e-9 * ch.bytes_sent)


def test_channel_ici_edge_never_faults_or_bills():
    inj = FaultInjector([Fault(0, "dcn_fault")])
    ch = LocalDcnChannel(alpha_s=1.0, fault_injector=inj)
    ch.send(jnp.zeros(4), step=0, edge=Edge(0, 1, "ici"))
    assert ch.simulated_seconds == 0.0
    assert inj.log == []                        # fault left un-consumed


def test_channel_retry_recovers_one_fault():
    inj = FaultInjector([Fault(2, "dcn_fault")])
    ch = LocalDcnChannel(fault_injector=inj, max_retries=2)
    out = ch.send_with_retry(jnp.arange(4), step=2, edge=Edge(0, 1))
    np.testing.assert_array_equal(np.asarray(out), np.arange(4))
    assert ch.retries == 1 and ch.sends == 1
    assert inj.log == [(2, "dcn_fault")]


def test_channel_retry_budget_exhausts():
    inj = FaultInjector([Fault(0, "dcn_fault") for _ in range(5)])
    ch = LocalDcnChannel(fault_injector=inj, max_retries=1)
    with pytest.raises(DcnTimeout) as e:
        ch.send_with_retry(jnp.zeros(2), step=0, edge=Edge(1, 2))
    assert e.value.attempt == 1 and e.value.edge.src == 1
    assert ch.retries == 2


def test_channel_places_on_dst_sharding():
    dev = jax.devices()[1]
    sh = jax.sharding.SingleDeviceSharding(dev)
    ch = LocalDcnChannel()
    out = ch.send(jnp.arange(3), sh)
    assert out.devices() == {dev}


def test_channel_from_cost_model():
    from apex_tpu.observability.costmodel import (
        fit_cost_model, simulate_link_measurements)
    model = fit_cost_model(simulate_link_measurements(1e-3, 1e-8))
    ch = LocalDcnChannel.from_cost_model(model)
    assert ch.alpha_s == pytest.approx(1e-3, rel=1e-3)
    assert ch.beta_s_per_byte == pytest.approx(1e-8, rel=1e-3)


# ---------------------------------------------------------------------------
# two-tier cost model (link_class) round trip
# ---------------------------------------------------------------------------


def test_costmodel_link_class_fits_and_fallback(tmp_path):
    from apex_tpu.observability.costmodel import (
        Measurement, fit_cost_model, load_profile)
    ms = ([Measurement("ppermute", "f32", 2, 1 << 14, 1e-5)]
          + [Measurement("ppermute", "f32", 2, n, 1e-3 + 1e-8 * n,
                         link_class="dcn")
             for n in (1 << 12, 1 << 16, 1 << 20)])
    model = fit_cost_model(ms)
    assert model.link_classes == ("dcn", "ici")
    slow = model.predict("ppermute", 1 << 16, 2, link_class="dcn")
    fast = model.predict("ppermute", 1 << 16, 2)
    assert slow > 10 * fast
    # un-probed link class falls back to ici curves
    assert model.predict("ppermute", 1 << 16, 2,
                         link_class="pcie") == pytest.approx(fast)
    path = os.path.join(tmp_path, "profile.json")
    model.save(path, measurements=ms)
    loaded, back = load_profile(path)
    assert loaded.curves().keys() == model.curves().keys()
    assert {m.link_class for m in back} == {"ici", "dcn"}


def test_costmodel_pre_link_class_measurement_loads_as_ici():
    from apex_tpu.observability.costmodel import Measurement
    m = Measurement.from_dict({"op": "psum", "dtype": "f32",
                               "group_size": 4, "nbytes": 1024,
                               "time_s": 1e-5})
    assert m.link_class == "ici"


def test_comms_probe_simulate_dcn_cli(tmp_path):
    from tools.comms_probe import main
    out = os.path.join(tmp_path, "profile.json")
    rc = main(["--out", out, "--ops", "ppermute", "--dtypes", "f32",
               "--sizes", "4096,65536", "--groups", "2", "--iters", "1",
               "--rounds", "1", "--holdout", "0",
               "--simulate-dcn", "1e-3,1e-8", "--quiet"])
    assert rc in (0, None)
    from apex_tpu.observability.costmodel import load_profile
    model, ms = load_profile(out)
    assert "dcn" in model.link_classes and "ici" in model.link_classes
    assert any(m.link_class == "dcn" for m in ms)


# ---------------------------------------------------------------------------
# schedules + simulator
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("S,M", [(2, 2), (2, 4), (4, 8), (3, 5)])
@pytest.mark.parametrize("name", ["1f1b", "dcn_hiding"])
def test_schedules_are_valid_orders(S, M, name):
    order = SCHEDULES[name](S, M)
    validate_order(order, S, M)
    assert len(order) == 2 * S * M


def test_1f1b_warmup_depth():
    # warmup of S-1-s fwds, then the steady state opens with one more
    # fwd before the first bwd: S-s leading fwds per stage
    per_stage = stage_ops_1f1b(4, 8)
    for s, ops in enumerate(per_stage):
        warm = 0
        for op in ops:
            if op.kind != "fwd":
                break
            warm += 1
        assert warm == 4 - s


def test_backwards_drain_in_ascending_microbatch_order():
    # the ring accumulates grads ascending m; both schedules must
    # replay that per-stage order for bitwise parity
    for name in SCHEDULES:
        for op_list in (SCHEDULES[name](2, 4), SCHEDULES[name](4, 4)):
            by_stage = {}
            for op in op_list:
                if op.kind == "bwd":
                    by_stage.setdefault(op.stage, []).append(op.mb)
            for mbs in by_stage.values():
                assert mbs == sorted(mbs)


def test_merge_stage_ops_deadlock_raises():
    bad = [[Op(0, "bwd", 0), Op(0, "fwd", 0)],
           [Op(1, "fwd", 0), Op(1, "bwd", 0)]]
    with pytest.raises(ValueError, match="deadlock"):
        merge_stage_ops(bad)


def test_validate_order_pins_violations():
    with pytest.raises(ValueError, match="before upstream fwd"):
        validate_order([Op(1, "fwd", 0)], 2, 1)
    with pytest.raises(ValueError, match="before its own fwd"):
        validate_order([Op(1, "bwd", 0)], 2, 1)
    with pytest.raises(ValueError, match="issued twice"):
        validate_order([Op(0, "fwd", 0), Op(0, "fwd", 0)], 1, 1)
    with pytest.raises(ValueError, match="want 4"):
        validate_order([Op(0, "fwd", 0), Op(0, "bwd", 0)], 1, 2)


def test_edge_link_classes_two_tier():
    assert edge_link_classes(4, 2) == {0: "ici", 1: "dcn", 2: "ici"}
    assert edge_link_classes(4, 1) == {0: "ici", 1: "ici", 2: "ici"}
    assert edge_link_classes(2, 2) == {0: "dcn"}
    with pytest.raises(ValueError, match="must divide"):
        edge_link_classes(4, 3)


def test_simulator_no_links_matches_analytic_bubble():
    S, M = 4, 8
    sim = simulate(schedule_1f1b(S, M), S, M, t_fwd=1.0, t_bwd=2.0)
    # ideal 1F1B with t_bwd = 2*t_fwd: makespan = (M + S - 1) * 3
    assert sim["makespan"] == pytest.approx((M + S - 1) * 3.0)
    assert sim["bubble_fraction"] == pytest.approx(
        (S - 1) / (M + S - 1))
    assert sim["hidden_fraction"] == {"ici": 1.0, "dcn": 1.0}


def test_dcn_hiding_beats_blocking_1f1b_under_slow_link():
    S, M = 4, 8
    classes = edge_link_classes(S, 2)
    link = {e: (1.5 if lc == "dcn" else 0.05)
            for e, lc in classes.items()}
    base = simulate(schedule_1f1b(S, M), S, M, t_fwd=1.0, t_bwd=2.0,
                    link_seconds=link, link_classes=classes,
                    blocking_sends=True)
    tuned = simulate(schedule_dcn_hiding(S, M), S, M, t_fwd=1.0,
                     t_bwd=2.0, link_seconds=link, link_classes=classes,
                     blocking_sends=False)
    assert tuned["bubble_fraction"] < base["bubble_fraction"]
    assert tuned["makespan"] < base["makespan"]
    # some (not necessarily all) DCN time stays hidden under compute
    assert 0.0 < tuned["hidden_fraction"]["dcn"] <= 1.0


# ---------------------------------------------------------------------------
# the engine: bitwise parity, faults, checkpoints, tracing
# ---------------------------------------------------------------------------

_KW = dict(vocab_size=32, hidden_size=16, num_layers=4,
           num_attention_heads=4, max_seq_len=16)
_DP, _S, _M, _MB, _SEQ = 2, 2, 4, 2, 16


def _data():
    rng = np.random.RandomState(11)
    tokens = jnp.asarray(rng.randint(0, 32, (_DP * _M * _MB, _SEQ)))
    targets = jnp.asarray(rng.randint(0, 32, (_DP * _M * _MB, _SEQ)))
    return tokens, targets


def _ring_reference(model, params, tokens, targets):
    packed, in_specs, local_fn, repack_fn = pack_for_shard_map(
        model, params, n_stages=_S, tensor_axis=None)
    mesh = jax.make_mesh((_DP, _S), ("data", "pipe"),
                         devices=jax.devices()[:_DP * _S])

    def grad_step(sp, tk, tg):
        tk = tk.reshape(_M, _MB, _SEQ)
        tg = tg.reshape(_M, _MB, _SEQ)
        loss, g = pipeline_step(model, local_fn(sp), tk, tg,
                                pipe_axis="pipe", data_axis="data")
        return loss, repack_fn(g)

    return jax.jit(shard_map(
        grad_step, mesh=mesh,
        in_specs=(in_specs, P("data"), P("data")),
        out_specs=(P(), in_specs),
        check_vma=False))(packed, tokens, targets)


@pytest.fixture(scope="module")
def parity_run():
    model = GPTModel(GPTConfig(**_KW))
    params = model.init_params(jax.random.PRNGKey(11))
    tokens, targets = _data()
    ring_loss, ring_grads = _ring_reference(model, params, tokens,
                                            targets)
    plan = ParallelPlan(dp=_DP, pp=_S, n_microbatches=_M, n_pods=_S)
    inj = FaultInjector([Fault(0, "dcn_fault")])
    eng = MpmdPipeline(_KW, params, plan,
                       devices=jax.devices()[:_DP * _S],
                       fault_injector=inj, schedule="dcn_hiding",
                       trace=True)
    loss, grads = eng.loss_and_grads(tokens, targets, step=0)
    return dict(model=model, ring_loss=ring_loss, ring_grads=ring_grads,
                eng=eng, inj=inj, loss=loss, grads=grads,
                tokens=tokens, targets=targets)


def test_engine_loss_bitwise_vs_ring(parity_run):
    assert (np.float32(parity_run["loss"]).tobytes()
            == np.float32(parity_run["ring_loss"]).tobytes())


def test_engine_grads_bitwise_vs_ring(parity_run):
    grads, ring_grads = parity_run["grads"], parity_run["ring_grads"]
    layer_specs = parity_run["model"].partition_specs()["layers"][0]
    for i in range(_S):
        def cmp(s, a, b):
            ax = 1 if _is_sharded(s) else 0
            np.testing.assert_array_equal(
                np.take(np.asarray(a), 0, ax),
                np.take(np.asarray(b), i, ax))
        jax.tree_util.tree_map(cmp, layer_specs, grads[i]["layers"],
                               ring_grads["layers"],
                               is_leaf=_is_spec_leaf)
    # tied embedding: BOTH replicas carry the identical total gradient
    for sub in (grads[0]["embedding"], grads[-1]["embedding"]):
        for a, b in zip(jax.tree_util.tree_leaves(sub),
                        jax.tree_util.tree_leaves(
                            ring_grads["embedding"]), strict=True):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for a, b in zip(
            jax.tree_util.tree_leaves(grads[-1]["final_layernorm"]),
            jax.tree_util.tree_leaves(ring_grads["final_layernorm"]),
            strict=True):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_engine_retried_scheduled_dcn_fault(parity_run):
    # the Fault(0, "dcn_fault") dropped one transfer; the bitwise
    # results above came from the retry
    assert parity_run["eng"].channel.retries == 1
    assert (0, "dcn_fault") in parity_run["inj"].log


def test_engine_flow_chains_unbroken(parity_run):
    cont = parity_run["eng"].collector().continuity()
    assert not cont["broken"] and not cont["orphans"]
    assert len(cont["complete"]) == _M + 1   # per-microbatch + sync


def test_engine_checkpoint_kill_one_stage(parity_run, tmp_path):
    eng = parity_run["eng"]
    tokens, targets = parity_run["tokens"], parity_run["targets"]
    root = os.path.join(tmp_path, "ckpt")
    eng.save_checkpoint(root, step=0)
    assert os.path.exists(os.path.join(root, MPMD_PLAN_FILE))
    before = jax.tree_util.tree_map(np.asarray, eng.stages[0].state)
    eng.train_step(tokens, targets)
    assert eng.restore_stage(0, root) == 0
    for a, b in zip(jax.tree_util.tree_leaves(eng.stages[0].state),
                    jax.tree_util.tree_leaves(before), strict=True):
        np.testing.assert_array_equal(np.asarray(a), b)
    assert eng.restore_checkpoint(root) == 0


def test_engine_checkpoint_plan_stamp_mismatch(parity_run, tmp_path):
    eng = parity_run["eng"]
    root = os.path.join(tmp_path, "stamp")
    eng.save_checkpoint(root, step=0)
    with open(os.path.join(root, MPMD_PLAN_FILE)) as f:
        doc = json.load(f)
    doc["plan"]["n_microbatches"] = 64
    with open(os.path.join(root, MPMD_PLAN_FILE), "w") as f:
        json.dump(doc, f)
    with pytest.raises(ValueError, match="saved under cross-pod plan"):
        eng.restore_checkpoint(root)


def test_engine_rejects_bad_plans():
    model = GPTModel(GPTConfig(**_KW))
    params = model.init_params(jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="MPMD needs pp >= 2"):
        MpmdPipeline(_KW, params, ParallelPlan(dp=2))
    with pytest.raises(ValueError, match="unknown schedule"):
        MpmdPipeline(_KW, params,
                     ParallelPlan(pp=2, n_microbatches=2, n_pods=2),
                     schedule="zigzag")


def test_elastic_build_rejects_cross_pod_plans():
    from apex_tpu.resilience.elastic import ElasticPlan
    with pytest.raises(ValueError, match="MpmdPipeline"):
        ElasticPlan.build(ParallelPlan(pp=2, n_microbatches=2,
                                       n_pods=2))


def test_stage_rejects_moe_and_bare_tp():
    from apex_tpu.mpmd.stage import StageProgram
    cfg = GPTConfig(n_experts=2, **_KW)
    with pytest.raises(ValueError, match="does not support MoE"):
        StageProgram(cfg, {}, stage_index=0, n_stages=2,
                     n_microbatches=2, plan=ParallelPlan(),
                     devices=jax.devices()[:1])
    cfg = GPTConfig(tensor_parallel_size=2, axis_name="model", **_KW)
    with pytest.raises(ValueError, match="require\\s+sequence_parallel"):
        StageProgram(cfg, {}, stage_index=0, n_stages=2,
                     n_microbatches=2, plan=ParallelPlan(tp=2),
                     devices=jax.devices()[:2])


# ---------------------------------------------------------------------------
# the two-tier autotune planner
# ---------------------------------------------------------------------------


def test_autotune_mpmd_enumeration_and_ranking(tmp_path):
    from tools.autotune import autotune_mpmd, emit_plan, load_plan
    report = autotune_mpmd(
        8, cfg_kw=dict(_KW, num_layers=4), batch=8, n_pods=2,
        dcn=(1e-3, 1e-9), verbose=False)
    assert report["mode"] == "mpmd" and report["n_pods"] == 2
    win = ParallelPlan.from_dict(report["plan"])
    assert win.n_pods == 2 and win.pp % 2 == 0
    assert report["schedule"] in SCHEDULES
    # ranking is total order over (plan, schedule) rows
    preds = [r["predicted_s"] for r in report["ranked"]]
    assert preds == sorted(preds)
    # rejections carry reasons
    rej = [c for c in report["candidates"] if c["status"] == "rejected"]
    assert all(c["reason"] for c in rej)
    path = os.path.join(tmp_path, "plan.json")
    emit_plan(path, report)
    assert load_plan(path) == win


def test_autotune_mpmd_rejects_impossible_pods():
    from tools.autotune import autotune_mpmd
    with pytest.raises(RuntimeError, match="no valid MPMD plan"):
        autotune_mpmd(8, cfg_kw=dict(_KW, num_layers=4), batch=8,
                      n_pods=5, dcn=(1e-3, 1e-9), verbose=False)
