"""apex_tpu.observability.anatomy: measured critical-path attribution.

The contract under test (ISSUE 20):

* ``synthesize_events`` -> ``reconstruct`` round-trips a ``simulate()``
  schedule exactly — op census, per-stage order, makespan — from any
  of the three accepted trace forms (event list, Chrome trace dict,
  JSON string);
* ``attribute`` partitions every stage's window into the five
  categories with per-stage sums equal to the makespan (telescoping
  cursor walk — exact, not approximate), and a slow DCN edge shows up
  as ``exposed_dcn``, not as unexplained ``host_gap``;
* ``diff_timelines`` self-diffs clean (drift ~ 0, per-op ratios cover
  EVERY op), divides out a uniform slowdown (median normalization:
  that is curve drift, the cost model's job), and flags the two
  structural failures it exists for — an injected slow-DCN world
  (unpredicted bubbles) and injected op reordering;
* ``ParallelismAutopilot.observe_anatomy`` debounces the structural
  score over ``confirm_windows``, queues ONE coalesced adoption pass
  tagged ``source="anatomy"``, and the audit trail stays clean;
* the ``tools/step_anatomy.py`` ``--json`` schema is pinned — it is
  the machine interface other tooling parses.

The real-engine path (``measure_ops=True`` on a dp2 x pp2 CPU mesh) is
``test_real_engine_step_reconstructs_and_attributes``; the other tests
drive the pure-host layers so they stay cheap.
"""

import importlib
import json
import math
import os
import sys
from types import SimpleNamespace

import pytest

from apex_tpu.mpmd.schedule import SCHEDULES, edge_link_classes, simulate
from apex_tpu.observability.anatomy import (
    CATEGORIES, MeasuredTimeline, attribute, attribution_counter_events,
    diff_timelines, reconstruct, render_attribution_table, render_diff,
    synthesize_events)
from apex_tpu.observability.costmodel import (fit_cost_model,
                                              simulate_link_measurements)
from apex_tpu.resilience import ParallelismAutopilot, TopologySpec

_TOOLS = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "tools")

S, M = 4, 8
T_FWD, T_BWD = 1.0, 2.0
ICI_S, DCN_S = 0.05, 1.5


def _import_tool(name):
    sys.path.insert(0, _TOOLS)
    try:
        return importlib.import_module(name)
    finally:
        sys.path.pop(0)


def make_sim(*, t_fwd=T_FWD, t_bwd=T_BWD, ici=ICI_S, dcn=DCN_S,
             schedule="1f1b", s=S, m=M, pods=2):
    classes = edge_link_classes(s, pods)
    link = {e: (dcn if lc == "dcn" else ici)
            for e, lc in classes.items()}
    return simulate(SCHEDULES[schedule](s, m), s, m, t_fwd=t_fwd,
                    t_bwd=t_bwd, link_seconds=link,
                    link_classes=classes, blocking_sends=False)


@pytest.fixture(scope="module")
def sim():
    return make_sim()


@pytest.fixture(scope="module")
def timeline(sim):
    return reconstruct(synthesize_events(sim, n_stages=S,
                                         n_microbatches=M))


# -- reconstruction -----------------------------------------------------------


def test_round_trip_census_and_order(sim, timeline):
    tl = timeline
    assert tl.n_stages == S and tl.n_microbatches == M
    assert len(tl.ops) == 2 * S * M
    assert tl.schedule == "1f1b" and tl.step == 0
    assert tl.makespan == pytest.approx(sim["makespan"])
    # per-stage measured order is the simulated issue order exactly
    sim_order = {}
    for r in sim["op_times"]:
        sim_order.setdefault(int(r["stage"]), []).append(
            (r["kind"], int(r["mb"])))
    for s in range(S):
        got = [(o["kind"], o["mb"]) for o in tl.stage_ops(s)]
        assert got == sim_order[s], f"stage {s} order diverged"
    # and the Op-vocabulary view matches row-for-row
    for op, o in zip(tl.order(), tl.ops, strict=True):
        assert (op.stage, op.kind, op.mb) == (o["stage"], o["kind"],
                                              o["mb"])


def test_reconstruct_accepts_all_trace_forms(sim, timeline):
    evs = synthesize_events(sim, n_stages=S, n_microbatches=M)
    for form in (evs, {"traceEvents": evs},
                 json.dumps({"traceEvents": evs}), json.dumps(evs)):
        tl = reconstruct(form)
        assert len(tl.ops) == len(timeline.ops)
        assert tl.makespan == pytest.approx(timeline.makespan)


def test_reconstruct_step_selection(sim):
    evs = (synthesize_events(sim, n_stages=S, n_microbatches=M, step=3)
           + synthesize_events(sim, n_stages=S, n_microbatches=M,
                               step=7, t0=100.0))
    assert reconstruct(evs).step == 7          # default: newest
    assert reconstruct(evs, step=3).step == 3
    with pytest.raises(ValueError, match="not in trace"):
        reconstruct(evs, step=5)


def test_reconstruct_rejects_bad_traces(sim):
    with pytest.raises(ValueError, match="no 'mpmd_op' events"):
        reconstruct([{"name": "something_else", "ph": "X"}])
    evs = synthesize_events(sim, n_stages=S, n_microbatches=M)
    dup = [e for e in evs if e["name"] == "mpmd_op"][0]
    with pytest.raises(ValueError, match="duplicate op event"):
        reconstruct(evs + [dup])


# -- attribution --------------------------------------------------------------


def test_attribution_sums_exact(timeline):
    attr = attribute(timeline)
    assert attr["makespan"] == pytest.approx(timeline.makespan)
    for st in attr["per_stage"]:
        assert sum(st[c] for c in CATEGORIES) == pytest.approx(
            st["total"])
        err = abs(st["total"] - attr["makespan"]) / attr["makespan"]
        assert err < 1e-9, (st["stage"], err)
        for seg in st["segments"]:        # segments tile monotonically
            assert seg["t1"] >= seg["t0"]
            assert seg["category"] in CATEGORIES
    assert sum(attr["fractions"][c] for c in CATEGORIES) \
        == pytest.approx(1.0)
    for c in CATEGORIES:
        assert attr["totals"][c] == pytest.approx(
            sum(st[c] for st in attr["per_stage"]))


def test_slow_dcn_is_exposed_not_unexplained(timeline):
    attr = attribute(timeline)
    # the 1.5s DCN edge vs 0.05s ICI: waiting on it must be billed to
    # exposed_dcn, dominate exposed_ici, and leave nothing mysterious
    assert attr["fractions"]["exposed_dcn"] > 0.0
    assert (attr["totals"]["exposed_dcn"]
            > attr["totals"]["exposed_ici"])
    assert attr["fractions"]["host_gap"] == pytest.approx(0.0)
    fast = attribute(reconstruct(synthesize_events(
        make_sim(dcn=ICI_S), n_stages=S, n_microbatches=M)))
    assert (attr["fractions"]["exposed_dcn"]
            > fast["fractions"]["exposed_dcn"])


def test_counter_events_one_hot(timeline):
    attr = attribute(timeline)
    evs = attribution_counter_events(attr)
    lanes = {e["name"] for e in evs}
    assert lanes == {f"anatomy/stage{s}" for s in range(S)}
    for e in evs:
        assert e["ph"] == "C"
        assert set(e["args"]) == set(CATEGORIES)
        assert sum(e["args"].values()) in (0, 1)   # one-hot or closing
    n_segs = sum(len(st["segments"]) for st in attr["per_stage"])
    assert len(evs) == n_segs + S                  # + one zero row each



def test_real_engine_step_reconstructs_and_attributes():
    """The measured path end to end: a dp2 x pp2 ``MpmdPipeline`` step
    with ``measure_ops=True`` reconstructs to the engine's op census
    (the last stage's fwd is folded into its joint bwd program: 2SM - M),
    every second of the makespan is attributed, and the simulated
    schedule priced at the measured medians covers every measured op.
    A DCN channel that really sleeps moves the attribution toward
    exposed-dcn.  (Wall-time drift SCORES are not compared here: the
    differ's thresholds are held on simulated timelines above.)"""
    import time

    import jax
    import jax.numpy as jnp
    import numpy as np

    from apex_tpu.models.gpt import GPTConfig, GPTModel
    from apex_tpu.mpmd import MpmdPipeline
    from apex_tpu.mpmd.channel import LocalDcnChannel
    from apex_tpu.parallel.plan import ParallelPlan

    m, mb, seq, dp, s = 4, 2, 16, 2, 2
    kw = dict(vocab_size=32, hidden_size=16, num_layers=4,
              num_attention_heads=4, max_seq_len=seq)
    params = GPTModel(GPTConfig(**kw)).init_params(jax.random.PRNGKey(7))
    rng = np.random.RandomState(7)
    tokens = jnp.asarray(rng.randint(0, 32, (dp * m * mb, seq)))
    targets = jnp.asarray(rng.randint(0, 32, (dp * m * mb, seq)))
    plan = ParallelPlan(dp=dp, pp=s, n_microbatches=m, n_pods=s)

    def measured_step(channel=None):
        eng = MpmdPipeline(kw, params, plan, devices=jax.devices()[:dp * s],
                           channel=channel, measure_ops=True)
        eng.loss_and_grads(tokens, targets, step=0)      # compile warm-up
        for tr in eng.tracers:
            tr.clear()
        eng.loss_and_grads(tokens, targets, step=1)
        tl = reconstruct(eng.anatomy_events())
        attr = attribute(tl)
        for st in attr["per_stage"]:        # none left over, none invented
            assert abs(st["total"] - attr["makespan"]) \
                < 1e-9 * attr["makespan"]
        return eng, tl, attr

    eng, tl, attr = measured_step()
    assert len(tl.ops) == 2 * s * m - m

    def med(xs):
        return sorted(xs)[len(xs) // 2] if xs else 1e-6

    durs = {"fwd": [], "bwd": []}
    for o in tl.ops:
        durs[o["kind"]].append(o["end"] - o["start"])
    by_edge = {}
    for x in tl.xfers:
        if x["mb"] >= 0:
            by_edge.setdefault(min(x["src"], x["dst"]), []).append(
                x["end"] - x["start"])
    sim = simulate(eng.order, s, m, t_fwd=med(durs["fwd"]),
                   t_bwd=med(durs["bwd"]),
                   link_seconds={e: med(ts) for e, ts in by_edge.items()},
                   link_classes=edge_link_classes(s, s),
                   blocking_sends=False)
    base = diff_timelines(tl, sim, fold_last_fwd=True)
    assert base["matched"] == base["n_ops"] == len(tl.ops)
    assert not base["missing"] and not base["extra"]

    class SleepyDcn(LocalDcnChannel):
        def send_with_retry(self, value, dst_shardings=None, *,
                            step=0, edge=None):
            if edge is not None and edge.link_class == "dcn":
                time.sleep(0.02)
            return super().send_with_retry(value, dst_shardings,
                                           step=step, edge=edge)

    _, _, attr_slow = measured_step(SleepyDcn())
    assert (attr_slow["fractions"]["exposed_dcn"]
            > attr["fractions"]["exposed_dcn"])

# -- the differ ---------------------------------------------------------------


def test_self_diff_is_clean(sim, timeline):
    d = diff_timelines(timeline, sim)
    assert d["n_ops"] == d["matched"] == 2 * S * M
    assert len(d["ratios"]) == 2 * S * M           # EVERY op has a ratio
    assert not d["missing"] and not d["extra"] and not d["misordered"]
    assert d["median_ratio"] == pytest.approx(1.0)
    assert d["makespan_ratio"] == pytest.approx(1.0)
    assert d["drift_score"] < 1e-9


def test_uniform_slowdown_is_not_structural_drift(sim):
    # 2x everything: curve drift, the cost model's business — the
    # median normalization must divide it out of the structural score
    slow = reconstruct(synthesize_events(
        make_sim(t_fwd=2 * T_FWD, t_bwd=2 * T_BWD, ici=2 * ICI_S,
                 dcn=2 * DCN_S), n_stages=S, n_microbatches=M))
    d = diff_timelines(slow, sim)
    assert d["median_ratio"] == pytest.approx(2.0)
    assert d["max_ratio_deviation"] < 1e-9
    assert d["drift_score"] < 1e-6


def test_differ_flags_injected_slow_dcn(sim):
    # the world's DCN got 4x slower but the prediction still prices it
    # healthy: ops run on time, the stages just WAIT — unpredicted
    # bubbles, a structural signal past the autopilot threshold
    chaos = reconstruct(synthesize_events(
        make_sim(dcn=4 * DCN_S), n_stages=S, n_microbatches=M))
    d = diff_timelines(chaos, sim)
    assert d["matched"] == d["n_ops"]              # same ops, same order
    assert d["max_ratio_deviation"] < 1e-9         # op durations clean
    assert d["unpredicted_bubble_fraction"] > 0.1
    assert d["drift_score"] == pytest.approx(
        d["unpredicted_bubble_fraction"])
    clean = diff_timelines(reconstruct(synthesize_events(
        sim, n_stages=S, n_microbatches=M)), sim)
    assert d["drift_score"] > 100 * max(clean["drift_score"], 1e-12)


def test_differ_flags_injected_reordering(sim, timeline):
    ops = [dict(o) for o in timeline.ops]
    swapped = [i for i, o in enumerate(ops) if o["stage"] == 1][:2]
    a, b = swapped
    for k in ("kind", "mb"):                       # swap identities,
        ops[a][k], ops[b][k] = ops[b][k], ops[a][k]  # keep the slots
    mangled = MeasuredTimeline(
        n_stages=S, n_microbatches=M, ops=ops,
        xfers=timeline.xfers, schedule=timeline.schedule,
        step=timeline.step)
    d = diff_timelines(mangled, sim)
    assert len(d["misordered"]) == 2
    assert all(r["stage"] == 1 for r in d["misordered"])
    assert d["drift_score"] >= 2 / (2 * S * M)


def test_fold_last_fwd_matches_engine_execution_model():
    # the engine runs the last stage as ONE joint fwd+bwd program per
    # microbatch: 2SM - M measured ops; fold_last_fwd merges the
    # prediction to the same shape so the diff covers every op
    s, m = 2, 2
    sim2 = make_sim(s=s, m=m)
    tl = reconstruct(synthesize_events(sim2, n_stages=s,
                                       n_microbatches=m))
    folded = []
    by_key = {(o["stage"], o["kind"], o["mb"]): dict(o)
              for o in tl.ops}
    for o in tl.ops:
        if o["stage"] == s - 1 and o["kind"] == "fwd":
            continue
        row = dict(o)
        if o["stage"] == s - 1 and o["kind"] == "bwd":
            fwd = by_key[(s - 1, "fwd", o["mb"])]
            row["start"] = fwd["start"]            # joint program span
            row["folded_fwd"] = True
        folded.append(row)
    folded.sort(key=lambda o: (o["start"], o["stage"]))
    jtl = MeasuredTimeline(n_stages=s, n_microbatches=m, ops=folded,
                           xfers=tl.xfers, schedule=tl.schedule,
                           step=tl.step)
    assert len(jtl.ops) == 2 * s * m - m
    d = diff_timelines(jtl, sim2, fold_last_fwd=True)
    assert d["n_ops"] == d["matched"] == 2 * s * m - m
    assert not d["missing"] and not d["extra"]
    assert d["drift_score"] < 1e-6
    attr = attribute(jtl)                          # still sums exactly
    for st in attr["per_stage"]:
        assert abs(st["total"] - attr["makespan"]) \
            < 1e-9 * attr["makespan"]


def test_renderers_smoke(sim, timeline):
    attr = attribute(timeline)
    table = render_attribution_table(attr)
    assert "makespan" in table and "exposed_dcn" in table
    assert "1.0000" in table                       # fractions row closes
    text = render_diff(diff_timelines(timeline, sim))
    assert "drift_score" in text
    assert f"ops matched {2 * S * M}/{2 * S * M}" in text


# -- the autopilot's structural channel ---------------------------------------


def _autopilot(**kw):
    cur = TopologySpec(dp=2)
    trainer = SimpleNamespace(
        plan=SimpleNamespace(spec=cur), _devices=list(range(4)),
        stats={"last_checkpoint_s": 1e-3, "last_reshard_s": 2e-3},
        current_step=0, replans=[], params={})
    profile = fit_cost_model(
        simulate_link_measurements(2e-3, 1e-9, link_class="dcn",
                                   ops=("psum",)),
        meta={"source": "test"})
    kw.setdefault("ranker",
                  lambda prof: [{"spec": cur, "predicted_s": 0.1}])
    kw.setdefault("cooldown_s", 0.0)
    kw.setdefault("structural_threshold", 0.3)
    return ParallelismAutopilot(trainer, profile, min_dp=2,
                                link_class="dcn", **kw)


def test_observe_anatomy_debounces_and_queues(sim):
    chaos = diff_timelines(reconstruct(synthesize_events(
        make_sim(dcn=8 * DCN_S), n_stages=S, n_microbatches=M)), sim)
    assert chaos["drift_score"] >= 0.3
    ap = _autopilot()
    ap.record_step(0.1)
    assert not ap.observe_anatomy(chaos)           # window 1: no confirm
    assert ap.observe_anatomy(chaos)               # window 2: confirmed
    assert ap.stats["structural_confirmed"] == 1
    assert ap.stats["last_structural"] == pytest.approx(
        chaos["drift_score"])
    assert ap.queued == 1
    # an ongoing divergence re-confirms: coalesce, never pile up
    assert not ap.observe_anatomy(chaos)
    assert ap.observe_anatomy(chaos)
    assert ap.stats["structural_confirmed"] == 2
    assert ap.queued == 1
    ap.tick()
    entry = ap.adoption_log[0]
    assert entry["source"] == "anatomy"
    assert entry["outcome"] == "no_change"
    assert entry["drift"] == pytest.approx(chaos["drift_score"])
    assert entry["detail"]["unpredicted_bubble_fraction"] \
        == pytest.approx(chaos["unpredicted_bubble_fraction"])
    assert entry["detail"]["misordered"] == 0
    assert ap.audit() == []


def test_observe_anatomy_clean_window_resets_streak(sim, timeline):
    ap = _autopilot()
    chaos = diff_timelines(reconstruct(synthesize_events(
        make_sim(dcn=8 * DCN_S), n_stages=S, n_microbatches=M)), sim)
    clean = diff_timelines(timeline, sim)
    assert not ap.observe_anatomy(chaos)
    assert not ap.observe_anatomy(clean)           # streak reset
    assert not ap.observe_anatomy(chaos)           # back to window 1
    assert ap.observe_anatomy(chaos)
    assert ap.stats["structural_confirmed"] == 1


def test_observe_anatomy_bare_score_and_threshold():
    ap = _autopilot(structural_threshold=0.5, confirm_windows=1)
    assert not ap.observe_anatomy(0.49)            # below threshold
    assert ap.observe_anatomy(0.5)                 # bare float accepted
    assert ap.stats["structural_confirmed"] == 1
    with pytest.raises(ValueError, match="structural_threshold"):
        _autopilot(structural_threshold=0.0)


# -- the CLI ------------------------------------------------------------------


def _write_trace(tmp_path, sim):
    path = tmp_path / "step.trace.json"
    path.write_text(json.dumps({"traceEvents": synthesize_events(
        sim, n_stages=S, n_microbatches=M)}))
    return str(path)


def test_cli_json_schema_pinned(tmp_path, capsys, sim):
    step_anatomy = _import_tool("step_anatomy")
    rc = step_anatomy.main(["--trace", _write_trace(tmp_path, sim),
                            "--diff-simulated", "--json"])
    assert rc == 0
    report = json.loads(capsys.readouterr().out)
    assert set(report) == {"schedule", "attribution", "diff",
                           "predicted"}
    assert set(report["schedule"]) == {
        "name", "step", "n_stages", "n_microbatches", "n_ops",
        "makespan_s", "busy_s"}
    assert report["schedule"]["n_stages"] == S
    assert report["schedule"]["n_ops"] == 2 * S * M
    assert set(report["attribution"]) == {"makespan", "totals",
                                          "fractions", "per_stage"}
    assert set(report["attribution"]["totals"]) == set(CATEGORIES)
    for st in report["attribution"]["per_stage"]:
        assert "segments" not in st                # table view, not lanes
    assert report["diff"]["matched"] == 2 * S * M
    assert report["diff"]["drift_score"] < 1e-9
    assert set(report["predicted"]) == {"schedule", "t_fwd", "t_bwd",
                                        "link_seconds"}
    # predicted prices at the measured medians by construction
    assert report["predicted"]["t_fwd"] == pytest.approx(T_FWD)
    assert report["predicted"]["t_bwd"] == pytest.approx(T_BWD)


def test_cli_table_and_merged_out(tmp_path, capsys, sim):
    step_anatomy = _import_tool("step_anatomy")
    out = tmp_path / "merged.trace.json"
    rc = step_anatomy.main(["--trace", _write_trace(tmp_path, sim),
                            "--out", str(out)])
    assert rc == 0
    text = capsys.readouterr().out
    assert f"{S} stages x {M} microbatches" in text
    assert "exposed_dcn" in text
    merged = json.loads(out.read_text())["traceEvents"]
    names = {e["name"] for e in merged}
    assert "mpmd_op" in names                      # original events kept
    assert f"anatomy/stage{S - 1}" in names        # + counter lanes
    assert any(e["ph"] == "C" for e in merged)


def test_cli_plan_stage_mismatch_rejected(tmp_path, sim):
    step_anatomy = _import_tool("step_anatomy")
    plan = tmp_path / "MPMD_PLAN.json"
    plan.write_text(json.dumps({"n_stages": S + 1,
                                "plan": {"schedule": "1f1b"}}))
    with pytest.raises(SystemExit, match="wrong trace/plan pair"):
        step_anatomy.main(["--trace", _write_trace(tmp_path, sim),
                           "--plan", str(plan)])
