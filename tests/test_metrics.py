"""apex_tpu.observability: registry, spans, training monitor, comms.

The contract under test (ISSUE 5):

* the metrics registry enforces Prometheus label semantics (declared
  label NAMES, full label VALUES per sample, mismatches raise), is
  thread-safe, and exports through two lossless surfaces — the JSONL
  event stream round-trips byte-identically through ``replay_jsonl``,
  and the text snapshot is valid Prometheus exposition format
  (cumulative histogram buckets, ``_sum``/``_count``);
* spans nest per-thread, emit valid Chrome trace-event JSON, and
  compose with ``jax.named_scope`` so the span name lands in the
  lowered HLO of ops traced inside;
* ``TrainingMonitor`` on a guarded GPT step reports anomaly counts
  that MATCH ``GuardedTrainStep.stats``, emits per-step JSONL records
  with the alerting keys, and taps grad-norm/loss/loss-scale without
  adding device->host syncs (the series come from StepResult's host
  fields);
* ``collective_stats`` byte counts match hand-computed payloads for
  tp=2 shard_map collectives;
* ``ServingMetrics`` drops per-request transient state at every
  terminal transition (the leak fix) while ``summary()`` values are
  unchanged; ``range_pop`` warns once on an unmatched pop.
"""

import io
import json
import re
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from apex_tpu.amp.scaler import LossScaler
from apex_tpu.models.gpt import GPTConfig, GPTModel
from apex_tpu.observability import (Counter, Gauge, Histogram,
                                    MetricsRegistry, Tracer,
                                    TrainingMonitor, collective_stats,
                                    format_stats, hlo_collective_stats,
                                    replay_jsonl, span, wire_bytes)
from apex_tpu.optimizers import FusedAdam
from apex_tpu.resilience import Fault, FaultInjector, GuardedTrainStep
from apex_tpu.utils import profiling
from apex_tpu.utils.profiling import ServingMetrics


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------

class TestRegistry:
    def test_label_semantics(self):
        reg = MetricsRegistry()
        c = reg.counter("reqs_total", "reqs", labelnames=("route",))
        c.inc(route="a")
        c.inc(2, route="b")
        assert c.value(route="a") == 1 and c.value(route="b") == 2
        with pytest.raises(ValueError):
            c.inc()                       # missing label
        with pytest.raises(ValueError):
            c.inc(route="a", extra="x")   # unknown label
        with pytest.raises(ValueError):
            c.inc(-1, route="a")          # counters only go up

    def test_redeclaration(self):
        reg = MetricsRegistry()
        c1 = reg.counter("c_total", "c")
        assert reg.counter("c_total") is c1      # idempotent
        with pytest.raises(ValueError):
            reg.gauge("c_total")                 # kind mismatch
        with pytest.raises(ValueError):
            reg.counter("c_total", labelnames=("x",))  # labels mismatch
        with pytest.raises(ValueError):
            reg.counter("bad name")              # invalid name

    def test_gauge_and_histogram(self):
        reg = MetricsRegistry()
        g = reg.gauge("g")
        g.set(5.0)
        g.inc(2.0)
        g.dec(3.0)
        assert g.value() == 4.0
        h = reg.histogram("h_seconds", buckets=(0.1, 1.0))
        for v in (0.05, 0.5, 2.0):
            h.observe(v)
        assert h.count() == 3 and h.sum() == pytest.approx(2.55)

    def test_prometheus_format(self):
        reg = MetricsRegistry()
        reg.counter("a_total", "things", labelnames=("k",)).inc(k="x")
        h = reg.histogram("lat_seconds", "lat", buckets=(0.1, 1.0))
        h.observe(0.05)
        h.observe(0.5)
        h.observe(2.0)
        prom = reg.prometheus()
        assert "# HELP a_total things\n# TYPE a_total counter" in prom
        assert 'a_total{k="x"} 1' in prom
        # cumulative buckets + +Inf == count
        assert 'lat_seconds_bucket{le="0.1"} 1' in prom
        assert 'lat_seconds_bucket{le="1"} 2' in prom
        assert 'lat_seconds_bucket{le="+Inf"} 3' in prom
        assert "lat_seconds_sum 2.55" in prom
        assert "lat_seconds_count 3" in prom

    def test_jsonl_replay_round_trip(self):
        reg = MetricsRegistry(clock=lambda: 1.0)
        buf = io.StringIO()
        reg.attach_stream(buf)
        c = reg.counter("reqs_total", "requests", labelnames=("route",))
        c.inc(route="a")
        c.inc(3, route="b")
        reg.gauge("tps", "throughput").set(123.5)
        h = reg.histogram("lat_seconds", "lat", buckets=(0.1, 1.0))
        h.observe(0.5)
        reg.event("train_step", step=1, loss=2.5)
        lines = buf.getvalue().splitlines()
        for ln in lines:
            json.loads(ln)               # every line is one JSON object
        reg2, records = replay_jsonl(lines)
        # byte-identical snapshot: declares carry help text + buckets
        assert reg2.prometheus() == reg.prometheus()
        assert reg2.get("lat_seconds").buckets == (0.1, 1.0)
        assert records == [{"ts": 1.0, "event": "train_step",
                            "step": 1, "loss": 2.5}]

    def test_late_attach_emits_declares(self):
        reg = MetricsRegistry()
        c = reg.counter("c_total", "help text")
        buf = io.StringIO()
        reg.attach_stream(buf)           # after declaration
        c.inc()
        reg2, _ = replay_jsonl(buf.getvalue().splitlines())
        assert reg2.get("c_total").help == "help text"
        assert reg2.get("c_total").value() == 1

    def test_thread_safety(self):
        reg = MetricsRegistry()
        c = reg.counter("n_total")
        h = reg.histogram("h")

        def work():
            for _ in range(200):
                c.inc()
                h.observe(0.01)

        threads = [threading.Thread(target=work) for _ in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert c.value() == 800
        assert h.count() == 800

    def test_snapshot(self):
        reg = MetricsRegistry()
        reg.counter("c_total").inc(2)
        reg.histogram("h").observe(1.5)
        snap = reg.snapshot()
        assert snap["c_total"]["series"][()] == 2
        assert snap["h"]["series"][()] == {"count": 1, "sum": 1.5}


# ---------------------------------------------------------------------------
# spans
# ---------------------------------------------------------------------------

class TestSpans:
    def test_nesting_and_trace_json(self):
        t = [0.0]

        def clk():
            t[0] += 0.25
            return t[0]

        tr = Tracer(clock=clk)
        assert tr.depth() == 0
        with tr.span("outer", device=False):
            assert tr.depth() == 1
            with tr.span("inner", device=False, shard=3):
                assert tr.depth() == 2
        tr.instant("mark")
        assert tr.depth() == 0
        doc = json.loads(tr.to_json())
        evs = doc["traceEvents"]
        # inner closes (and records) first
        assert [e["name"] for e in evs] == ["inner", "outer", "mark"]
        inner, outer, mark = evs
        assert inner["ph"] == "X" and outer["ph"] == "X"
        assert mark["ph"] == "i"
        assert inner["args"] == {"shard": 3, "depth": 2}
        # microsecond complete events, inner contained within outer
        assert outer["ts"] <= inner["ts"]
        assert (inner["ts"] + inner["dur"]
                <= outer["ts"] + outer["dur"] + 1e-6)
        for e in (inner, outer, mark):
            assert isinstance(e["pid"], int) and isinstance(e["tid"], int)

    def test_save_and_clear(self, tmp_path):
        tr = Tracer()
        with tr.span("s", device=False):
            pass
        p = tr.save(str(tmp_path / "trace.json"))
        assert json.load(open(p))["traceEvents"]
        tr.clear()
        assert tr.events == []

    def test_out_of_order_close_raises(self):
        tr = Tracer()
        a = tr.span("a", device=False)
        b = tr.span("b", device=False)
        a.__enter__()
        b.__enter__()
        with pytest.raises(RuntimeError):
            a.__exit__(None, None, None)
        tr._stack()[:] = ["b"]           # restore so b can close cleanly
        b.__exit__(None, None, None)

    def test_named_scope_composition(self):
        """A span names HOST code: by default it enters no
        ``jax.named_scope``; with ``device=True`` ops traced inside carry
        its name into compiled-HLO metadata (StableHLO drops debug
        locations; the compiled text is where profilers read scope names
        from)."""
        tr = Tracer()

        def fn(x, device):
            # two programs: the compile cache's key leaves metadata out,
            # so one program would come back with the other's names
            with tr.span("my_unique_scope", device=device):
                return x * 2.0 if device else x * 3.0

        def text(device):
            return jax.jit(fn, static_argnums=1).lower(
                jnp.ones((4,)), device).compile().as_text()

        assert "my_unique_scope" in text(True)
        assert "my_unique_scope" not in text(False)
        assert [e["name"] for e in tr.events] == ["my_unique_scope"] * 2

    def test_span_without_tracer_records_nothing(self):
        """``span()`` with no tracer is a profiler annotation and nothing
        else: the module keeps no default tracer for it to grow."""
        from apex_tpu import observability as obs
        from apex_tpu.observability import spans
        assert not hasattr(spans, "default_tracer")
        assert not hasattr(obs, "default_tracer")
        with span("free", shard=1) as sp:
            sp.set_metadata(more=2)
        assert type(sp) is jax.profiler.TraceAnnotation
        assert not [v for v in vars(spans).values()
                    if isinstance(v, Tracer)]

    def test_span_with_tracer_records_args_and_late_metadata(self):
        tr = Tracer(clock=lambda: 1.0)
        with span("outer", tracer=tr, a=1) as sp:
            sp.set_metadata(b=2)
        (ev,) = tr.events
        assert ev["name"] == "outer" and ev["ph"] == "X"
        assert ev["args"] == {"a": 1, "b": 2, "depth": 1}


# ---------------------------------------------------------------------------
# training monitor
# ---------------------------------------------------------------------------

def _tiny_gpt_guard(scaler=None, injector=None):
    cfg = GPTConfig(vocab_size=32, hidden_size=16, num_layers=2,
                    num_attention_heads=4, max_seq_len=8)
    model = GPTModel(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    adam = FusedAdam(lr=1e-3)
    guard = GuardedTrainStep(model.loss, adam, scaler=scaler,
                             fault_injector=injector)
    rng = np.random.RandomState(0)
    tokens = jnp.asarray(rng.randint(0, 32, (2, 8)))
    targets = jnp.asarray(rng.randint(0, 32, (2, 8)))
    return guard, params, adam.init(params), tokens, targets


class TestTrainingMonitor:
    def test_guarded_step_series_and_anomaly_parity(self):
        inj = FaultInjector([Fault(step=1, kind="nan_grads")])
        guard, params, opt_state, tokens, targets = _tiny_gpt_guard(
            injector=inj)
        buf = io.StringIO()
        reg = MetricsRegistry()
        reg.attach_stream(buf)
        mon = TrainingMonitor(reg, tokens_per_step=16)
        h = {"p": params, "o": opt_state, "g": guard.init_state()}

        def step(tokens, targets, step):
            r = guard(h["p"], h["o"], h["g"], tokens, targets, step=step)
            h["p"], h["o"], h["g"] = r.params, r.opt_state, r.guard_state
            return r

        monitored = mon.wrap(step)
        for i in range(3):
            monitored(tokens, targets, step=i)

        # anomaly accounting agrees with the guard's own counters
        assert guard.stats["steps"] == 3 and guard.stats["skipped"] == 1
        assert mon.stats["steps"] == 3
        assert mon.stats["skipped"] == guard.stats["skipped"]

        # per-step JSONL records carry the alerting keys
        records = [json.loads(ln) for ln in buf.getvalue().splitlines()]
        steps = [r for r in records if r.get("event") == "train_step"]
        assert len(steps) == 3
        for r in steps:
            assert {"step", "step_time_s", "tokens_per_s", "grad_norm",
                    "loss", "anomalies"} <= set(r)
        anomalous = [r for r in steps if r.get("anomaly")]
        assert len(anomalous) == 1
        assert anomalous[0]["anomaly"] == "nonfinite"
        assert steps[-1]["anomalies"] == 1

        # Prometheus snapshot exposes the series
        prom = reg.prometheus()
        for series in ("train_step_time_seconds", "train_tokens_per_s",
                       "train_grad_norm", "train_loss",
                       "train_steps_total"):
            assert series in prom
        assert 'train_anomalies_total{kind="nonfinite"} 1' in prom

    def test_loss_scale_series_with_scaler(self):
        scaler = LossScaler("dynamic", init_scale=8.0)
        guard, params, opt_state, tokens, targets = _tiny_gpt_guard(
            scaler=scaler)
        reg = MetricsRegistry()
        mon = TrainingMonitor(reg)
        h = {"p": params, "o": opt_state, "g": guard.init_state(),
             "s": scaler.init()}

        def step(tokens, targets):
            r = guard(h["p"], h["o"], h["g"], tokens, targets,
                      scaler_state=h["s"])
            h["p"], h["o"], h["g"], h["s"] = (r.params, r.opt_state,
                                              r.guard_state,
                                              r.scaler_state)
            return r

        monitored = mon.wrap(step)
        monitored(tokens, targets)
        assert reg.get("train_loss_scale").value() == 8.0
        rep = mon.report(guard=guard, scaler=scaler, scaler_state=h["s"])
        assert rep["scaler"]["loss_scale"] == 8.0
        assert rep["guard"]["steps"] == 1

    def test_plain_step_and_mfu(self):
        clock = iter([0.0, 0.5, 1.0, 1.5]).__next__
        mon = TrainingMonitor(tokens_per_step=100,
                              flops_per_token=1000.0, peak_flops=1e6,
                              clock=clock)

        monitored = mon.wrap(lambda: 2.5)   # plain step returning a loss
        assert monitored() == 2.5
        r = mon.registry
        assert r.get("train_step_time_s_last").value() == 0.5
        assert r.get("train_tokens_per_s").value() == 200.0
        # mfu = 200 tok/s * 1000 flops/tok / 1e6 peak
        assert r.get("train_mfu").value() == pytest.approx(0.2)
        assert r.get("train_loss").value() == 2.5

    def test_stream_path_opens_file(self, tmp_path):
        path = str(tmp_path / "m.jsonl")
        mon = TrainingMonitor(stream_path=path)
        mon.record(0.1)
        mon.close()
        reg, records = replay_jsonl(open(path))
        assert reg.get("train_steps_total").value() == 1
        assert any(r.get("event") == "train_step" for r in records)


# ---------------------------------------------------------------------------
# comms accounting
# ---------------------------------------------------------------------------

@pytest.mark.skipif(len(jax.devices()) < 2, reason="needs 2 devices")
class TestComms:
    def test_psum_bytes_hand_computed(self):
        mesh = jax.make_mesh((2,), ("tp",), devices=jax.devices()[:2])
        fn = jax.shard_map(lambda x: jax.lax.psum(x, "tp"),
                           mesh=mesh, in_specs=P("tp"), out_specs=P(),
                           check_vma=False)
        st = collective_stats(fn, jnp.ones((8, 16), jnp.float32))
        # per-shard operand f32[4,16]: 4*16*4 payload bytes, one op
        assert st["all_reduce"]["count"] == 1
        assert st["all_reduce"]["bytes"] == 4 * 16 * 4
        assert st["total"]["count"] == 1
        assert st["all_reduce"]["ops"][0]["group_size"] == 2

    def test_all_gather_bytes(self):
        mesh = jax.make_mesh((2,), ("tp",), devices=jax.devices()[:2])
        fn = jax.shard_map(
            lambda x: jax.lax.all_gather(x, "tp", tiled=True),
            mesh=mesh, in_specs=P("tp"), out_specs=P(), check_vma=False)
        st = collective_stats(fn, jnp.ones((8, 16), jnp.float32))
        # gathered RESULT f32[8,16] is the payload
        assert st["all_gather"]["count"] == 1
        assert st["all_gather"]["bytes"] == 8 * 16 * 4

    def test_format_and_wire(self):
        mesh = jax.make_mesh((2,), ("tp",), devices=jax.devices()[:2])
        fn = jax.shard_map(lambda x: jax.lax.psum(x, "tp"),
                           mesh=mesh, in_specs=P("tp"), out_specs=P(),
                           check_vma=False)
        st = collective_stats(fn, jnp.ones((8, 16), jnp.float32))
        table = format_stats(st)
        assert "all_reduce" in table and "total" in table
        # ring all-reduce over k=2: 2*(k-1)/k = 1.0x payload
        assert wire_bytes(st) == st["all_reduce"]["bytes"]


class TestHloParsing:
    def test_synthetic_hlo(self):
        text = """
  %ar = f32[4,16]{1,0} all-reduce(f32[4,16]{1,0} %dot), channel_id=1, replica_groups={{0,1}}
  %ag-start = (f32[4]{0}, f32[8]{0}) all-gather-start(f32[4]{0} %x), replica_groups={{0,1}}
  %ag-done = f32[8]{0} all-gather-done((f32[4]{0}, f32[8]{0}) %ag-start)
"""
        st = hlo_collective_stats(text)
        assert st["all_reduce"]["count"] == 1
        assert st["all_reduce"]["bytes"] == 4 * 16 * 4
        # async pair counts once, on the start; payload = gathered result
        assert st["all_gather"]["count"] == 1
        assert st["all_gather"]["bytes"] == 8 * 4
        assert st["total"]["count"] == 2

    def test_bf16_width(self):
        st = hlo_collective_stats(
            "%r = bf16[8,8]{1,0} all-reduce(bf16[8,8]{1,0} %a), "
            "replica_groups={{0,1,2,3}}")
        assert st["all_reduce"]["bytes"] == 8 * 8 * 2
        assert st["all_reduce"]["ops"][0]["group_size"] == 4


# ---------------------------------------------------------------------------
# serving metrics migration (satellite 1) + profiling (satellite 2)
# ---------------------------------------------------------------------------

class TestServingMetrics:
    def _clock(self):
        t = [0.0]

        def clk():
            t[0] += 0.1
            return t[0]

        return clk

    def test_terminal_states_drop_transient_state(self):
        m = ServingMetrics(clock=self._clock())
        for rid, end in (("a", "finished"), ("b", "evicted"),
                         ("c", "error"), ("d", "timeout")):
            m.request_submitted(rid)
            m.first_token(rid)
            getattr(m, f"request_{end}"
                    if end != "finished" else "request_finished")(rid)
        # the leak fix: no per-request residue after terminal states
        assert m.pending_requests == 0
        assert m._last_token == {}
        assert m.evicted == 1 and m.errors == 1 and m.timeouts == 1
        c = m.registry.get("serving_finished_total")
        assert c.value(reason="done") == 1
        assert c.value(reason="evicted") == 1
        assert c.value(reason="error") == 1
        assert c.value(reason="timeout") == 1

    def test_summary_values_unchanged(self):
        """summary() still computes exact percentiles over raw samples —
        the registry mirror must not perturb the public values."""
        m = ServingMetrics(clock=self._clock())
        m.request_submitted("r")
        m.first_token("r")               # ttft = 0.1
        m.token("r")                     # latency = 0.1
        m.token("r")
        m.step(2, 4)
        s = m.summary()
        assert s["requests"] == 1 and s["tokens"] == 3
        assert s["ttft_p50_s"] == pytest.approx(0.1)
        assert s["token_latency_p50_s"] == pytest.approx(0.1)
        assert s["slot_occupancy_mean"] == pytest.approx(0.5)
        # and the registry saw the same samples
        assert m.registry.get("serving_tokens_total").value() == 3
        assert m.registry.get("serving_ttft_seconds").count() == 1
        assert m.registry.get(
            "serving_token_latency_seconds").count() == 2

    def test_shared_registry(self):
        reg = MetricsRegistry()
        m = ServingMetrics(clock=self._clock(), registry=reg)
        m.request_submitted("r")
        assert reg.get("serving_requests_total").value() == 1


class TestProfilingSatellites:
    def test_range_pop_warns_once_on_empty_stack(self):
        profiling._POP_MISMATCH_WARNED = False
        try:
            with pytest.warns(RuntimeWarning, match="no matching"):
                profiling.range_pop()
            import warnings as _w
            with _w.catch_warnings():
                _w.simplefilter("error")     # second pop must NOT warn
                profiling.range_pop()
        finally:
            profiling._POP_MISMATCH_WARNED = False

    def test_range_depth_balanced(self):
        assert profiling.range_depth() == 0
        profiling.range_push("a")
        profiling.range_push("b")
        assert profiling.range_depth() == 2
        profiling.range_pop()
        profiling.range_pop()
        assert profiling.range_depth() == 0


# ---------------------------------------------------------------------------
# exports
# ---------------------------------------------------------------------------

def test_public_exports():
    import apex_tpu

    obs = apex_tpu.observability
    for name in ("MetricsRegistry", "Counter", "Gauge", "Histogram",
                 "replay_jsonl", "Tracer", "span",
                 "TrainingMonitor", "calibrated_peak_flops",
                 "collective_stats", "hlo_collective_stats",
                 "wire_bytes", "format_stats",
                 "CostModel", "Measurement", "fit_cost_model",
                 "load_profile", "probe_collectives",
                 "RequestRecord", "RequestTracer",
                 "BurnWindow", "RollingPercentiles",
                 "SLOMonitor", "SLOTarget"):
        assert hasattr(obs, name), name
    assert isinstance(obs.MetricsRegistry().counter("x_total"), Counter)
    assert isinstance(obs.MetricsRegistry().gauge("g"), Gauge)
    assert isinstance(obs.MetricsRegistry().histogram("h"), Histogram)


# ---------------------------------------------------------------------------
# Prometheus exporter edge cases (ISSUE 7 satellite)
# ---------------------------------------------------------------------------

class TestPrometheusEdgeCases:
    def test_empty_registry_renders_empty(self):
        assert MetricsRegistry().prometheus() == ""

    def test_label_value_escaping(self):
        reg = MetricsRegistry()
        c = reg.counter("esc_total", "esc", labelnames=("v",))
        c.inc(v='say "hi"')
        c.inc(v="back\\slash")
        c.inc(v="two\nlines")
        prom = reg.prometheus()
        assert r'esc_total{v="say \"hi\""} 1' in prom
        assert r'esc_total{v="back\\slash"} 1' in prom
        assert r'esc_total{v="two\nlines"} 1' in prom
        assert "\nlines" not in prom.replace("\\nlines", "")

    def test_no_help_omits_help_line(self):
        reg = MetricsRegistry()
        reg.gauge("bare").set(1)
        prom = reg.prometheus()
        assert "# HELP" not in prom and "# TYPE bare gauge" in prom

    def test_labeled_histogram_rendering(self):
        reg = MetricsRegistry()
        h = reg.histogram("lat_seconds", "lat", labelnames=("op",),
                          buckets=(0.25, 0.5))
        for v in (0.1, 0.3, 9.0):
            h.observe(v, op="read")
        h.observe(0.4, op="write")
        prom = reg.prometheus()
        # per-label-set cumulative buckets, le last inside the braces
        assert 'lat_seconds_bucket{op="read",le="0.25"} 1' in prom
        assert 'lat_seconds_bucket{op="read",le="0.5"} 2' in prom
        assert 'lat_seconds_bucket{op="read",le="+Inf"} 3' in prom
        assert 'lat_seconds_bucket{op="write",le="+Inf"} 1' in prom
        assert 'lat_seconds_sum{op="read"} 9.4' in prom
        assert 'lat_seconds_count{op="read"} 3' in prom
        assert 'lat_seconds_count{op="write"} 1' in prom

    def test_inf_and_int_value_formatting(self):
        reg = MetricsRegistry()
        reg.gauge("pos").set(float("inf"))
        reg.gauge("neg").set(float("-inf"))
        reg.gauge("whole").set(3.0)
        prom = reg.prometheus()
        assert "pos +Inf" in prom and "neg -Inf" in prom
        assert "whole 3\n" in prom            # 3.0 renders as 3


class TestHistogramPercentile:
    def test_interpolated_within_bucket(self):
        reg = MetricsRegistry()
        h = reg.histogram("h", buckets=(1.0, 2.0, 4.0))
        for v in (0.5, 1.5, 1.6, 3.0):
            h.observe(v)
        # rank 2 of 4 lands at the top of the (1,2] bucket's first half
        assert 0.0 < h.percentile(0.25) <= 1.0
        assert 1.0 < h.percentile(0.5) <= 2.0
        assert 2.0 < h.percentile(1.0) <= 4.0

    def test_empty_and_overflow(self):
        reg = MetricsRegistry()
        h = reg.histogram("h", buckets=(1.0, 2.0))
        assert h.percentile(0.5) == 0.0
        h.observe(100.0)                      # overflow bucket
        assert h.percentile(0.99) == 2.0      # saturates at top boundary

    def test_labeled(self):
        reg = MetricsRegistry()
        h = reg.histogram("h", labelnames=("k",), buckets=(1.0, 2.0))
        h.observe(0.5, k="a")
        h.observe(1.5, k="b")
        assert h.percentile(1.0, k="a") <= 1.0
        assert h.percentile(1.0, k="b") > 1.0
        with pytest.raises(ValueError):
            h.percentile(0.5)                 # missing label


# ---------------------------------------------------------------------------
# Tracer exception-path nesting (ISSUE 7 satellite)
# ---------------------------------------------------------------------------

class TestTracerExceptionPath:
    def test_span_closes_and_flags_on_raise(self):
        t = [0.0]

        def clk():
            t[0] += 1.0
            return t[0]

        tr = Tracer(clock=clk)
        with pytest.raises(RuntimeError, match="boom"):
            with tr.span("work"):
                raise RuntimeError("boom")
        assert tr.depth() == 0                # stack popped
        (ev,) = tr.events
        assert ev["name"] == "work" and ev["dur"] == pytest.approx(1e6)
        assert ev["args"]["error"] == "RuntimeError"
        json.loads(tr.to_json())              # still valid Chrome JSON

    def test_inner_exception_does_not_flag_outer(self):
        tr = Tracer()
        with tr.span("outer"):
            try:
                with tr.span("inner"):
                    raise ValueError("x")
            except ValueError:
                pass
        inner, outer = tr.events              # inner closes first
        assert inner["name"] == "inner"
        assert inner["args"]["error"] == "ValueError"
        assert outer["name"] == "outer"
        assert "error" not in outer.get("args", {})
        assert tr.depth() == 0

    def test_nesting_survives_exception_for_next_span(self):
        tr = Tracer()
        try:
            with tr.span("a"):
                raise KeyError("k")
        except KeyError:
            pass
        with tr.span("b"):
            pass
        names = [e["name"] for e in tr.events]
        assert names == ["a", "b"]
        assert all(e.get("args", {}).get("depth", 1) == 1
                   for e in tr.events)

    def test_async_span_event_shape(self):
        tr = Tracer(clock=lambda: 0.0)
        tr.async_span("request", 7, ts=1.0, dur=0.5, reason="eos")
        tr.async_instant("tick", 7, ts=1.2)
        b, e, n = tr.events
        assert (b["ph"], e["ph"], n["ph"]) == ("b", "e", "n")
        # async ids are namespaced by the tracer's replica tag so two
        # replicas' id counters never collide in a merged trace
        assert b["id"] == e["id"] == n["id"] == f"{tr.id_tag}/7"
        assert b["cat"] == "request" and b["ts"] == pytest.approx(1e6)
        assert e["ts"] == pytest.approx(1.5e6)
        assert b["args"] == {"reason": "eos"}
        json.loads(tr.to_json())

    def test_async_ids_unique_across_tracers(self):
        a, b = Tracer(clock=lambda: 0.0), Tracer(clock=lambda: 0.0)
        a.async_span("request", 7, ts=0.0, dur=1.0)
        b.async_span("request", 7, ts=0.0, dur=1.0)
        ids_a = {e["id"] for e in a.events}
        ids_b = {e["id"] for e in b.events}
        assert not ids_a & ids_b

    def test_flow_events(self):
        tr = Tracer(clock=lambda: 3.0)
        s = tr.flow("s", "req:1", phase="dispatch")
        t = tr.flow("t", "req:1", 4.0, phase="admit")
        f = tr.flow("f", "req:1", phase="finish")
        assert [e["ph"] for e in tr.events] == ["s", "t", "f"]
        # flow ids are NOT tag-prefixed: they must match across
        # replicas — that is how migrated fragments stitch
        assert all(e["id"] == "req:1" for e in (s, t, f))
        assert all(e["cat"] == Tracer.FLOW_CAT for e in (s, t, f))
        assert all(e["name"] == Tracer.FLOW_NAME for e in (s, t, f))
        assert t["ts"] == pytest.approx(4e6)
        assert s["ts"] == f["ts"] == pytest.approx(3e6)
        assert f["bp"] == "e"
        with pytest.raises(ValueError):
            tr.flow("x", "req:1")


# ---------------------------------------------------------------------------
# device scopes of the BERT step (jax.named_scope; PERF.md section 3)
# ---------------------------------------------------------------------------

def _bert_step_locations(n_dev):
    """The location strings of the lowered (not compiled: the compile
    cache's key leaves names out) step of the BERT recipe at its tiny
    size, on ``n_dev`` CPU devices."""
    import re

    import chip_smoke
    recipe = chip_smoke._bert_recipe()
    args = recipe.parse_args([
        "--config", "tiny", "--batch-size", str(2 * n_dev), "--seq-len",
        "16", "--vocab-size", "64", "--opt-level", "O2"])
    train_step, state, make_batch, _ = recipe.build(
        args, devices=jax.devices()[:n_dev])
    text = train_step.lower(*state, *make_batch()).as_text(debug_info=True)
    return set(re.findall(r'loc\("([^"]*)"', text))


def _under(scope, path):
    """``scope`` is a component of the path (inside a shard_map the path
    starts anew, without ``jit(step)``)."""
    return re.search(rf"(^|[/(]){re.escape(scope)}[/)]", path) is not None


@pytest.mark.parametrize("scope", ["embeddings", "attention", "mlp",
                                   "mlm_head", "optimizer"])
def test_bert_step_names_its_scopes(scope):
    paths = _bert_step_locations(1)
    hits = [p for p in paths if _under(scope, p)]
    assert hits, scope
    if scope != "optimizer":
        # forward and backward both carry the name, through the wrappers
        assert any(f"jvp({scope})" in p for p in hits)
        assert any(f"transpose(jvp({scope}))" in p for p in hits)
    assert not any(_under("ddp.reduce", p) for p in paths)


def test_bert_layer_operations_belong_to_attention_or_mlp():
    """Every operation of the encoder's layers sits under one of the two
    sublayer scopes: nothing of the forward pass is left between them."""
    paths = _bert_step_locations(1)
    forward = [p for p in paths if "jvp(" in p and "transpose(" not in p]
    assert forward
    named = ("jvp(embeddings)", "jvp(attention)", "jvp(mlp)",
             "jvp(mlm_head)")
    # all but amp's scaling of the loss, which belongs to no layer
    assert [p for p in forward if not any(n in p for n in named)] \
        == ["jit(step)/jvp()/mul"]


def test_two_device_bert_step_names_the_gradient_reduce():
    paths = _bert_step_locations(2)
    reduce_ops = [p for p in paths if _under("ddp.reduce", p)]
    assert reduce_ops and any(p.endswith("/psum") for p in reduce_ops)
    # the optimizer runs after the reduce, under its own name
    assert any(_under("optimizer", p) for p in paths)
    assert not any(_under("optimizer", p) for p in reduce_ops)


# ---------------------------------------------------------------------------
# device scopes of the plain GPT block's cache paths (PERF.md section 3)
# ---------------------------------------------------------------------------

def _plain_gpt_cache_program(name):
    """``(function, argument shapes)`` of one cache path of a tiny plain
    ``GPTModel`` with learned positions, as the serving engines jit it."""
    from apex_tpu.models.gpt import GPTConfig, GPTModel
    model = GPTModel(GPTConfig(
        vocab_size=32, hidden_size=16, num_layers=2, num_attention_heads=2,
        max_seq_len=16, rotary=False))
    params = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
    S = jax.ShapeDtypeStruct
    row, chunk = S((4,), jnp.int32), S((4, 3), jnp.int32)
    tables = S((4, 4), jnp.int32)
    pool = S((9, 2, 2, 4, 16), jnp.float32)
    quant = (S((9, 2, 2, 4, 16), jnp.int8), S((9, 2, 2, 2), jnp.float32))
    args = {
        "prefill": (S((1, 8), jnp.int32),),
        "decode_step": (row, S((4, 2, 2, 16, 2, 8), jnp.float32), row),
        "decode_step_paged": (row, pool, tables, row),
        "decode_chunk": (chunk, pool, tables, chunk, chunk, chunk),
        "decode_step_paged_quant": (row, *quant, tables, row),
        "decode_chunk_quant": (chunk, *quant, tables, chunk, chunk, chunk),
    }[name]
    return getattr(model, name), (params, *args)


@pytest.mark.parametrize("name", [
    "prefill", "decode_step", "decode_step_paged", "decode_chunk",
    "decode_step_paged_quant", "decode_chunk_quant"])
def test_plain_gpt_cache_paths_name_their_scopes(name):
    """The lowered (not compiled: the compile cache's key leaves names
    out) cache paths of the plain block carry the training forward's
    scopes, and the token's KV write one of its own inside ``attention``:
    the readers the benchmark has bill it to ``attention``, a reader that
    lists it sees it alone."""
    from benchmarks.harness import span_readers as sr
    fn, args = _plain_gpt_cache_program(name)
    text = jax.jit(fn).lower(*args).as_text(debug_info=True)
    listed = sr.SCOPES + ("lm_head", "attention.kv_write")
    # the int8 chunk inserts token by token in a loop, and a loop body's
    # paths start anew in the lowered text (not in the compiled program's)
    paths = {p for p in re.findall(r'loc\("([^"]*)"', text)
             if p.startswith((f"jit({name})/", "attention.kv_write/"))}
    by = {}
    for p in paths:
        by.setdefault(sr.innermost_scope(p, listed), []).append(p)
    want = {"embeddings", "attention", "mlp", "lm_head"}
    if name != "prefill":           # a prefill's write is the engine's
        want.add("attention.kv_write")
        for p in by["attention.kv_write"]:
            if p.startswith("jit("):
                assert "/attention/attention.kv_write/" in p
                assert sr.innermost_scope(p) == "attention"
        assert any(p.endswith("/scatter")
                   for p in by["attention.kv_write"])
    assert want <= set(by)
    # nothing of a layer is left between the scopes: what carries none
    # is the stacking of a prefill's K and V for the engine
    loose = {p.rsplit("/", 1)[1] for p in by.get(sr.UNSCOPED, [])}
    assert loose <= ({"concatenate", "broadcast_in_dim"}
                     if name == "prefill" else set())
