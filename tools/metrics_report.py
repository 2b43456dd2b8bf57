#!/usr/bin/env python
"""Render an apex_tpu JSONL metrics stream as a human-readable report.

The stream is whatever a :class:`~apex_tpu.observability.MetricsRegistry`
appended — declare records, per-mutation metric events, and free-form
records like the training monitor's per-step ``train_step`` lines.
The report replays the
stream into a fresh registry (exactly — declare records carry help text
and bucket boundaries) and prints:

* a per-metric table (counters/gauges: current value per label set;
  histograms: count / mean / sum),
* a training rollup over the ``train_step`` records (steps, mean/p50
  step time, tokens/s, loss trajectory endpoints, anomaly count),
* the tail of any other free-form records.

``--trace spans.json`` merges a :class:`~apex_tpu.observability.Tracer`
Chrome-trace file and the JSONL stream onto ONE timeline: metric
mutations become counter tracks (``ph: "C"`` — counters replayed to
running totals, gauges/histogram samples as-is), free-form records
become instants on a dedicated "metrics (JSONL)" process lane, and the
result is still a Chrome trace — one Perfetto load answers "what
happened at step N / request R".  Both producers are expected to share
a clock (the registry and tracer both take ``clock=``); when the two
time ranges are completely disjoint (different epochs), the JSONL side
is shifted min-to-min and the applied offset is recorded in the trace
metadata.

Usage:
    python tools/metrics_report.py metrics.jsonl            # report
    python tools/metrics_report.py metrics.jsonl --prom     # Prometheus
        text snapshot of the replayed registry instead
    python tools/metrics_report.py metrics.jsonl \\
        --trace spans.json --out merged.json    # merged timeline
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from apex_tpu.observability import Histogram, replay_jsonl  # noqa: E402
from apex_tpu.observability.fleetobs import align_offset  # noqa: E402


def _fmt(v):
    if isinstance(v, float):
        return f"{v:.6g}"
    return str(v)


def report(lines, out=sys.stdout):
    reg, records = replay_jsonl(lines)
    snap = reg.snapshot()
    if snap:
        out.write("== metrics ==\n")
    for name in sorted(snap):
        m = reg.get(name)
        info = snap[name]
        for key, val in sorted(info["series"].items()):
            labels = ",".join(f"{n}={v}" for n, v in
                              zip(info["labelnames"], key))
            label_s = f"{{{labels}}}" if labels else ""
            if isinstance(m, Histogram):
                mean = val["sum"] / val["count"] if val["count"] else 0.0
                out.write(f"{name}{label_s}  count={val['count']} "
                          f"mean={_fmt(mean)} sum={_fmt(val['sum'])}\n")
            else:
                out.write(f"{name}{label_s}  {_fmt(val)}\n")

    steps = [r for r in records if r.get("event") == "train_step"]
    if steps:
        times = sorted(r["step_time_s"] for r in steps
                       if "step_time_s" in r)
        losses = [r["loss"] for r in steps if "loss" in r]
        anomalies = max((r.get("anomalies", 0) for r in steps), default=0)
        out.write("\n== training ==\n")
        out.write(f"steps: {len(steps)}\n")
        if times:
            mean = sum(times) / len(times)
            out.write(f"step_time_s: mean={_fmt(mean)} "
                      f"p50={_fmt(times[len(times) // 2])} "
                      f"max={_fmt(times[-1])}\n")
            last = next((r for r in reversed(steps)
                         if "tokens_per_s" in r), None)
            if last is not None:
                out.write(f"tokens_per_s (last): "
                          f"{_fmt(last['tokens_per_s'])}\n")
        if losses:
            out.write(f"loss: first={_fmt(losses[0])} "
                      f"last={_fmt(losses[-1])}\n")
        out.write(f"anomalies: {anomalies}\n")

    other = [r for r in records if r.get("event") != "train_step"]
    if other:
        out.write("\n== events ==\n")
        for r in other[-20:]:
            kind = r.get("event", "?")
            rest = {k: v for k, v in r.items() if k not in ("event", "ts")}
            out.write(f"{kind}: {rest}\n")
    return reg


def merge_trace(trace_events, lines):
    """Merge Tracer events + JSONL metric/record events into one
    Chrome trace-event dict (see module docstring).  Returns
    ``(trace_dict, info)`` where ``info`` reports the event counts and
    any clock offset applied."""
    events = list(trace_events)
    span_ts = [e["ts"] for e in events if "ts" in e]

    metric_events = []      # (ts_s, name, labels, kind, value)
    records = []            # (ts_s, event, fields)
    for line in lines:
        line = line.strip()
        if not line:
            continue
        rec = json.loads(line)
        kind = rec.get("event")
        if kind == "declare" or "ts" not in rec:
            continue
        if kind in ("counter", "gauge", "histogram") and "name" in rec:
            metric_events.append((rec["ts"], rec["name"],
                                  rec.get("labels", {}), kind,
                                  rec["value"]))
        elif kind not in ("counter", "gauge", "histogram"):
            records.append((rec["ts"],) + (kind,
                           {k: v for k, v in rec.items()
                            if k not in ("event", "ts")}))

    jsonl_ts = [t * 1e6 for t, *_ in metric_events] \
        + [t * 1e6 for t, _, _ in records]
    # shared clock -> overlapping ranges -> no shift; disjoint ranges
    # (different epochs, e.g. perf_counter vs time.time) -> align mins
    # (align_offset is the same rule the FleetCollector applies per
    # replica stream)
    offset_us = align_offset(
        (min(span_ts), max(span_ts)) if span_ts else None,
        (min(jsonl_ts), max(jsonl_ts)) if jsonl_ts else None)

    mpid = max((e.get("pid", 0) for e in events
                if isinstance(e.get("pid"), int)), default=0) + 1
    merged = list(events)
    merged.append({"name": "process_name", "ph": "M", "pid": mpid,
                   "args": {"name": "metrics (JSONL)"}})
    counters = {}
    for ts, name, labels, kind, value in metric_events:
        label_s = ",".join(f"{k}={v}" for k, v in sorted(labels.items()))
        series = f"{name}{{{label_s}}}" if label_s else name
        if kind == "counter":      # deltas -> running total
            counters[series] = counters.get(series, 0.0) + value
            value = counters[series]
        merged.append({"name": series, "ph": "C", "pid": mpid,
                       "ts": ts * 1e6 + offset_us,
                       "args": {"value": value}})
    for ts, kind, fields in records:
        merged.append({"name": kind, "ph": "i", "s": "p", "pid": mpid,
                       "tid": 0, "ts": ts * 1e6 + offset_us,
                       "args": fields})
    info = {"span_events": len(events),
            "metric_events": len(metric_events),
            "records": len(records),
            "offset_us": offset_us}
    return ({"traceEvents": merged, "displayTimeUnit": "ms",
             "metadata": {"apex_tpu.merge_offset_us": offset_us}},
            info)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("stream", help="JSONL metrics stream file")
    ap.add_argument("--prom", action="store_true",
                    help="print a Prometheus text snapshot instead")
    ap.add_argument("--trace", metavar="SPANS_JSON", default=None,
                    help="merge this Chrome-trace file with the stream "
                         "onto one timeline")
    ap.add_argument("--out", default="merged_trace.json",
                    help="merged trace output path (with --trace)")
    args = ap.parse_args(argv)
    with open(args.stream, encoding="utf-8") as f:
        lines = f.readlines()
    if args.trace:
        with open(args.trace, encoding="utf-8") as f:
            tr = json.load(f)
        trace_events = tr["traceEvents"] if isinstance(tr, dict) else tr
        merged, info = merge_trace(trace_events, lines)
        with open(args.out, "w", encoding="utf-8") as f:
            json.dump(merged, f)
        print(f"wrote {args.out}: {info['span_events']} span events + "
              f"{info['metric_events']} metric samples + "
              f"{info['records']} records"
              + (f" (clock offset {info['offset_us']:.0f}us applied)"
                 if info["offset_us"] else ""))
    elif args.prom:
        reg, _ = replay_jsonl(lines)
        sys.stdout.write(reg.prometheus())
    else:
        report(lines)


if __name__ == "__main__":
    main()
