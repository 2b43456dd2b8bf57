"""apex_tpu.observability.fleetobs: causal traces, merged fleet
timelines and the anomaly flight recorder.

The fleet-observability contract:

* a :class:`TraceContext` minted at submission threads one request's
  flow events (``ph: "s"/"t"/"f"``) through every hop with unbroken
  ``parent -> span`` linkage, and :func:`check_flows` MEASURES that
  linkage — one start, a terminal end, no dangling parents, migrated
  chains spanning >= 2 replicas, no orphan request slices;
* :class:`FleetCollector` folds N replicas' traces and JSONL streams
  onto one clock (overlap = shared clock, disjoint = min-to-min),
  per-replica process lanes, fleet-level SLO burn and ``fleet_*``
  rollups;
* :class:`FlightRecorder` keeps bounded rings and cuts bounded,
  window-filtered snapshots;
* the replica_kill chaos scenario ends with every flow chain complete
  and connected — the acceptance criterion of the observability PR.
"""

import argparse
import importlib
import io
import json
import os
import sys

import pytest

from apex_tpu.observability import (FleetCollector, FlightRecorder,
                                    MetricsRegistry, Tracer,
                                    TraceContext, check_flows,
                                    emit_flow)
from apex_tpu.observability.fleetobs import align_offset


class FakeClock:
    def __init__(self, t=0.0):
        self.t = t

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt
        return self.t


# -- TraceContext ------------------------------------------------------------

class TestTraceContext:
    def test_mint(self):
        ctx = TraceContext.mint(7)
        assert ctx.trace_id == "req:7"
        assert ctx.parent == "root"
        assert ctx.hop == 0 and not ctx.started and ctx.seq == 0

    def test_next_hop_mutates_in_place(self):
        ctx = TraceContext.mint(1)
        out = ctx.next_hop("r2")
        assert out is ctx
        assert ctx.hop == 1 and ctx.replica == "r2"
        ctx.next_hop("r0")
        assert ctx.hop == 2 and ctx.replica == "r0"

    def test_dict_roundtrip(self):
        ctx = TraceContext.mint(3)
        ctx.next_hop("r1")
        ctx.started = True
        ctx.parent = "req:3#0.enqueue.0"
        assert TraceContext.from_dict(ctx.to_dict()) == ctx


class TestEmitFlow:
    def test_s_t_f_sequence_and_parent_chain(self):
        clk = FakeClock()
        tr = Tracer(clock=clk, id_tag="r0")
        ctx = TraceContext.mint(1)
        e1 = emit_flow(tr, ctx, "enqueue", request_id=1)
        clk.advance(0.5)
        e2 = emit_flow(tr, ctx, "prefill")
        clk.advance(0.5)
        e3 = emit_flow(tr, ctx, "finish", final=True)
        assert [e["ph"] for e in (e1, e2, e3)] == ["s", "t", "f"]
        assert e3["bp"] == "e"      # flow end binds to enclosing slice
        assert e1["args"]["parent"] == "root"
        assert e2["args"]["parent"] == e1["args"]["span"]
        assert e3["args"]["parent"] == e2["args"]["span"]
        assert e1["args"]["span"] == "req:1#0.enqueue.0"
        assert all(e["id"] == "req:1" for e in (e1, e2, e3))
        assert all(e["args"]["replica"] == "r0" for e in (e1, e2, e3))
        rep = check_flows(tr.events)
        assert rep["complete"] == ["req:1"] and not rep["broken"]
        info = rep["chains"]["req:1"]
        assert info["replicas"] == ["r0"] and not info["migrated"]

    def test_noop_without_tracer_or_context(self):
        ctx = TraceContext.mint(1)
        assert emit_flow(None, ctx, "enqueue") is None
        assert not ctx.started and ctx.seq == 0     # untouched
        assert emit_flow(Tracer(clock=FakeClock()), None, "x") is None

    def test_hop_lands_in_span_id(self):
        tr = Tracer(clock=FakeClock(), id_tag="r1")
        ctx = TraceContext.mint(4)
        emit_flow(tr, ctx, "enqueue")
        ctx.next_hop("r1")
        ev = emit_flow(tr, ctx, "migrate_in")
        assert ev["args"]["span"].startswith("req:4#1.migrate_in.")
        assert ev["args"]["hop"] == 1


# -- check_flows -------------------------------------------------------------

def _flow(ph, tid, ts, span, parent, phase, replica, **extra):
    args = {"span": span, "parent": parent, "phase": phase,
            "replica": replica, **extra}
    ev = {"name": "request", "ph": ph, "cat": "reqflow", "id": tid,
          "ts": ts, "pid": 1, "tid": 1, "args": args}
    if ph == "f":
        ev["bp"] = "e"
    return ev


def _chain(tid="req:0", replica="r0"):
    return [
        _flow("s", tid, 0.0, "a", "root", "enqueue", replica),
        _flow("t", tid, 1.0, "b", "a", "prefill", replica),
        _flow("f", tid, 2.0, "c", "b", "finish", replica),
    ]


class TestCheckFlows:
    def test_happy_path(self):
        rep = check_flows(_chain())
        assert rep["complete"] == ["req:0"]
        assert rep["broken"] == {} and rep["orphans"] == []
        assert rep["chains"]["req:0"]["phases"] == \
            ["enqueue", "prefill", "finish"]

    def test_double_start(self):
        evs = _chain() + [_flow("s", "req:0", 0.5, "z", "root",
                                "enqueue", "r0")]
        rep = check_flows(evs)
        assert any("flow starts" in p
                   for p in rep["broken"]["req:0"])

    def test_missing_finish(self):
        evs = _chain()[:2]
        rep = check_flows(evs)
        assert any("no flow end" in p for p in rep["broken"]["req:0"])
        # the in-flight view tolerates unfinished chains
        assert check_flows(evs, require_finish=False)["broken"] == {}

    def test_dangling_parent(self):
        evs = _chain()
        evs[1]["args"]["parent"] = "never-emitted"
        rep = check_flows(evs)
        assert any("dangling parent" in p
                   for p in rep["broken"]["req:0"])

    def test_event_after_last_end(self):
        evs = _chain() + [_flow("t", "req:0", 5.0, "d", "c",
                                "late", "r0")]
        rep = check_flows(evs)
        assert any("after the last flow end" in p
                   for p in rep["broken"]["req:0"])

    def test_migrated_must_span_two_replicas(self):
        evs = [
            _flow("s", "req:1", 0.0, "a", "root", "enqueue", "r0"),
            _flow("t", "req:1", 1.0, "b", "a", "migrate_out", "r0"),
            _flow("f", "req:1", 2.0, "c", "b", "finish", "r0"),
        ]
        rep = check_flows(evs)
        assert any("single replica" in p
                   for p in rep["broken"]["req:1"])
        evs[2]["args"]["replica"] = "r2"     # the adopted hop
        rep = check_flows(evs)
        assert rep["complete"] == ["req:1"]
        assert rep["chains"]["req:1"]["migrated"]
        assert rep["chains"]["req:1"]["replicas"] == ["r0", "r2"]

    def test_orphan_request_slices(self):
        claimed = _chain(replica="r0")
        claimed[0]["args"]["request_id"] = 5
        slices = [
            {"name": "request", "ph": "b", "cat": "request",
             "id": "r0/5", "ts": 0.0},
            {"name": "request", "ph": "b", "cat": "request",
             "id": "r9/42", "ts": 0.0},
        ]
        rep = check_flows(claimed + slices)
        assert rep["orphans"] == ["r9/42"]


# -- clock alignment and the merged timeline ---------------------------------

class TestAlignment:
    def test_align_offset_rules(self):
        assert align_offset(None, (0.0, 1.0)) == 0.0
        assert align_offset((0.0, 1.0), None) == 0.0
        # overlapping ranges share a clock: no shift
        assert align_offset((0.0, 10.0), (5.0, 15.0)) == 0.0
        # disjoint ranges: min-to-min
        assert align_offset((0.0, 10.0), (100.0, 110.0)) == -100.0
        assert align_offset((100.0, 110.0), (0.0, 10.0)) == 100.0

    def test_collector_incremental_union(self):
        fc = FleetCollector()
        # r0 anchors at 100..200 us; r1 is on a disjoint epoch;
        # r2 overlaps the union after r1 folded in, so it stays put
        fc.add_replica("r0", trace_events=[
            {"name": "x", "ph": "X", "ts": 100.0, "dur": 1.0},
            {"name": "x", "ph": "X", "ts": 200.0, "dur": 1.0}])
        fc.add_replica("r1", trace_events=[
            {"name": "y", "ph": "X", "ts": 1e6, "dur": 1.0}])
        fc.add_replica("r2", trace_events=[
            {"name": "z", "ph": "X", "ts": 150.0, "dur": 1.0}])
        offs = fc.offsets_us()
        assert offs["r0"] == 0.0
        assert offs["r1"] == 100.0 - 1e6
        assert offs["r2"] == 0.0

    def test_events_lanes_and_order(self):
        fc = FleetCollector()
        fc.add_replica("r0", trace_events=[
            {"name": "a0", "ph": "X", "ts": 5.0, "tid": 7},
            {"name": "a1", "ph": "X", "ts": 50.0, "tid": 7}])
        fc.add_replica("r1", trace_events=[
            {"name": "b", "ph": "X", "ts": 10.0, "tid": 9}])
        evs = fc.events()
        # overlapping ranges share the clock; output is ts-sorted
        assert [e["name"] for e in evs] == ["a0", "b", "a1"]
        by_name = {e["name"]: e for e in evs}
        assert by_name["a0"]["pid"] == FleetCollector.PID_BASE
        assert by_name["b"]["pid"] == FleetCollector.PID_BASE + 1
        assert by_name["a0"]["tid"] == by_name["a0"]["pid"]

    def test_merged_timeline_shape(self, tmp_path):
        fc = FleetCollector()
        fc.add_replica("r0", trace_events=[
            {"name": "a", "ph": "X", "ts": 1.0}])
        fc.add_replica("r1", trace_events=[])
        tl = fc.merged_timeline()
        lanes = [e for e in tl["traceEvents"] if e["ph"] == "M"]
        assert [e["args"]["name"] for e in lanes] == \
            ["replica:r0", "replica:r1"]
        assert "apex_tpu.fleet_offsets_us" in tl["metadata"]
        path = fc.save(str(tmp_path / "merged.json"))
        with open(path, encoding="utf-8") as f:
            assert json.load(f)["displayTimeUnit"] == "ms"


# -- fleet-level aggregation over real registries ----------------------------

def _replica_stream(clk, ttfts, requests, occupancy, health=None):
    """One replica's JSONL stream, produced by the real registry."""
    buf = io.StringIO()
    reg = MetricsRegistry(clock=clk)
    reg.attach_stream(buf)
    c = reg.counter("serving_requests_total", "done",
                    labelnames=("reason",))
    g = reg.gauge("serving_slot_occupancy", "busy/total")
    h = reg.histogram("serving_ttft_seconds", "ttft",
                      buckets=(0.05, 0.1, 0.25, 0.5, 1.0))
    for v in ttfts:
        clk.advance(0.1)
        h.observe(v)
    for _ in range(requests):
        clk.advance(0.1)
        c.inc(reason="finished")
    clk.advance(0.1)
    g.set(occupancy)
    if health is not None:
        reg.event("replica_health", replica=health[0], state=health[1])
    return buf.getvalue().splitlines()


class TestFleetAggregation:
    def test_fleet_series_sums_across_replicas(self):
        clk = FakeClock(10.0)
        fc = FleetCollector()
        fc.add_replica("r0", jsonl_lines=_replica_stream(
            clk, [0.02, 0.03], requests=3, occupancy=0.5))
        fc.add_replica("r1", jsonl_lines=_replica_stream(
            clk, [0.04], requests=2, occupancy=0.25))
        series = fc.fleet_series()
        assert series["fleet_serving_requests_total"] == 5.0
        assert series["fleet_serving_ttft_seconds_count"] == 3.0
        assert series["fleet_serving_ttft_seconds_sum"] == \
            pytest.approx(0.09)

    def test_fleet_burn_counts_bad_observations(self):
        clk = FakeClock(10.0)
        good = FleetCollector()
        good.add_replica("r0", jsonl_lines=_replica_stream(
            clk, [0.01] * 8, requests=0, occupancy=0.0))
        assert good.fleet_burn()["ttft_le_0.5"] == 0.0
        bad = FleetCollector()
        bad.add_replica("r0", jsonl_lines=_replica_stream(
            clk, [0.01] * 4, requests=0, occupancy=0.0))
        bad.add_replica("r1", jsonl_lines=_replica_stream(
            clk, [2.0] * 4, requests=0, occupancy=0.0))
        # 4/8 observations blow the 0.5 s target, objective 0.95:
        # burn = (4/8) / 0.05 = 10x budget
        assert bad.fleet_burn()["ttft_le_0.5"] == pytest.approx(10.0)

    def test_replica_table(self):
        clk = FakeClock(10.0)
        fc = FleetCollector()
        fc.add_replica("r0", jsonl_lines=_replica_stream(
            clk, [0.02], requests=4, occupancy=0.75,
            health=(0, "healthy")))
        fc.add_replica("r1", jsonl_lines=_replica_stream(
            clk, [], requests=1, occupancy=0.0, health=(1, "dead")))
        rows = {r["replica"]: r for r in fc.replica_table()}
        assert rows["r0"]["requests"] == 4
        assert rows["r0"]["occupancy"] == 0.75
        assert rows["r0"]["health"] == "healthy"
        assert rows["r1"]["health"] == "dead"
        assert "ttft_le_0.5" in rows["r0"]["burn"]


# -- flight recorder ---------------------------------------------------------

class TestFlightRecorder:
    def test_ring_is_bounded(self):
        clk = FakeClock()
        fr = FlightRecorder(clock=clk, keep=4)
        for i in range(10):
            clk.advance(0.1)
            fr.record("router", "tick", n=i)
        snap = fr.trigger("test")
        ns = [e["n"] for e in snap["sources"]["router"]]
        assert ns == [6, 7, 8, 9]

    def test_window_filter(self):
        clk = FakeClock()
        fr = FlightRecorder(clock=clk, window_s=30.0)
        fr.record("eng", "early", n=0)          # t=0
        clk.t = 100.0
        fr.record("eng", "late", n=1)           # t=100
        clk.t = 105.0
        snap = fr.trigger("replica_dead", replica=1)
        kinds = [e["kind"] for e in snap["sources"]["eng"]]
        assert kinds == ["late"]                # t=0 outside +/-30 s
        assert snap["details"] == {"replica": 1}
        assert snap["ts"] == 105.0

    def test_dump_retention_and_counter(self):
        clk = FakeClock()
        reg = MetricsRegistry(clock=clk)
        fr = FlightRecorder(clock=clk, max_dumps=2, registry=reg)
        assert fr.last is None
        for i in range(3):
            fr.trigger("ladder_escalation", step=i)
        assert len(fr.dumps) == 2
        assert fr.last["seq"] == 2              # newest survives
        assert fr.dumps[0]["seq"] == 1          # oldest evicted
        snap = reg.snapshot()["flight_recorder_snapshots_total"]
        assert sum(snap["series"].values()) == 3.0

    def test_save(self, tmp_path):
        fr = FlightRecorder(clock=FakeClock())
        fr.record("src", "k", a=1)
        fr.trigger("guard_rollback")
        path = fr.save(str(tmp_path / "blackbox.json"))
        with open(path, encoding="utf-8") as f:
            data = json.load(f)
        assert data["snapshots"][0]["trigger"] == "guard_rollback"


# -- the acceptance criterion: continuity under chaos ------------------------

def _scenario_ns(**kw):
    base = dict(
        scenario="replica_kill", requests=8, rate=1e9, replicas=3,
        max_slots=2, max_queue=64, max_queue_depth=4,
        burn_threshold=14.4, burn_window_s=60.0, ttft_slo_s=0.5,
        block_size=4, chunked=False, token_budget=32, client_retries=3,
        tick_s=0.02, e2e_slo_s=3.0, max_ticks=600, retry_budget=4,
        hedge_after_s=None, ladder_step_down_s=0.5, kill_tick=3,
        kill_replica=1, kill_duration=10 ** 6, slow_tick=4, slow_s=0.1,
        slow_duration=40, burst_n=4, burst_gap_s=0.3, period_s=2.0,
        seed=0, min_prompt=4, pareto_shape=2.5, max_new=4,
        shared_prefix_prob=0.5, shared_prefix_len=8, num_prefixes=2,
        vocab=32, hidden=16, layers=2, heads=2, max_seq=32)
    base.update(kw)
    return argparse.Namespace(**base)


def _loadgen():
    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "tools"))
    try:
        return importlib.import_module("loadgen")
    finally:
        sys.path.pop(0)


class TestChaosContinuity:
    def test_replica_kill_chains_stay_connected(self):
        rep = _loadgen().run_scenario(_scenario_ns())
        cont = rep["trace_continuity"]
        # every submitted request's flow chain survived the kill,
        # migration and resume with linkage intact
        assert cont["chains"] == rep["submitted"]
        assert cont["complete"] == cont["chains"]
        assert cont["broken"] == {} and cont["orphans"] == []
        # the kill actually migrated work, and the migrated chains are
        # visible as such on the merged timeline
        assert rep["migrations"] > 0
        assert len(cont["migrated_chains"]) > 0
        # the replica death cut exactly one flight-recorder snapshot
        assert rep["flight_snapshots"] == 1
