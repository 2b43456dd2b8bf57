"""apex_tpu.observability — unified telemetry for training + serving.

One registry, four surfaces:

* :mod:`~apex_tpu.observability.registry` — labeled
  Counter/Gauge/Histogram :class:`MetricsRegistry` with an append-only
  JSONL event stream and a Prometheus text-format snapshot;
* :mod:`~apex_tpu.observability.spans` — host-side span tracing
  (:func:`span`): a profiler ``TraceAnnotation`` on the device trace's
  clock, and Chrome trace-event JSON (Perfetto-loadable) into a
  :class:`Tracer` the caller passes;
* :mod:`~apex_tpu.observability.train_monitor` —
  :class:`TrainingMonitor`, wrapping any train step (notably
  :class:`~apex_tpu.resilience.GuardedTrainStep`) into step-time /
  tokens-s / MFU / grad-norm / loss-scale / anomaly series;
* :mod:`~apex_tpu.observability.comms` — static per-collective byte
  accounting (:func:`collective_stats`) from compiled HLO.

The MEASURED layer on top (ISSUE 7):

* :mod:`~apex_tpu.observability.costmodel` — collective microbenchmark
  probe + fitted α–β ring :class:`CostModel` (``tools/comms_probe.py``
  is the CLI; the profile JSON feeds the auto-parallel planner);
* :mod:`~apex_tpu.observability.request_trace` —
  :class:`RequestTracer`, per-request lifecycle spans
  (queue-wait/prefill/decode) in the serving engine, with TTFT/TPOT as
  derived quantities;
* :mod:`~apex_tpu.observability.slo` — :class:`SLOMonitor`, rolling
  percentiles + declarative :class:`SLOTarget`\\ s + multi-window
  burn-rate alerts.

The FLEET layer on top (ISSUE 13):

* :mod:`~apex_tpu.observability.fleetobs` — :class:`TraceContext`
  causal propagation (router-minted, engine-stamped Chrome flow
  events that stitch a request's journey across replicas),
  :class:`FleetCollector` (N-replica clock-aligned merged timelines +
  fleet-level SLO burn), :func:`check_flows` (measured trace
  continuity), and the :class:`FlightRecorder` anomaly black box.

``tools/metrics_report.py`` renders a JSONL stream into a human
summary (``--trace`` merges it with a span trace onto one timeline);
``tools/fleet_report.py`` does the N-replica version;
``docs/source/observability.md`` is the user guide.
"""

from apex_tpu.observability.anatomy import (
    MeasuredTimeline,
    attribute,
    diff_timelines,
    reconstruct,
    synthesize_events,
)
from apex_tpu.observability.registry import (
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    replay_jsonl,
)
from apex_tpu.observability.spans import Tracer, span
from apex_tpu.observability.train_monitor import (
    TrainingMonitor,
    calibrated_peak_flops,
)
from apex_tpu.observability.comms import (
    collective_stats,
    format_stats,
    hlo_collective_stats,
    wire_bytes,
)
from apex_tpu.observability.costmodel import (
    CostModel,
    Measurement,
    fit_cost_model,
    load_profile,
    probe_collectives,
)
from apex_tpu.observability.fleetobs import (
    FleetCollector,
    FlightRecorder,
    TraceContext,
    check_flows,
    emit_flow,
)
from apex_tpu.observability.request_trace import RequestRecord, RequestTracer
from apex_tpu.observability.slo import (
    BurnWindow,
    RollingPercentiles,
    SLOMonitor,
    SLOTarget,
)

__all__ = [
    "MeasuredTimeline",
    "attribute",
    "diff_timelines",
    "reconstruct",
    "synthesize_events",
    "Counter",
    "Gauge",
    "Histogram",
    "MetricsRegistry",
    "replay_jsonl",
    "Tracer",
    "span",
    "TrainingMonitor",
    "calibrated_peak_flops",
    "collective_stats",
    "format_stats",
    "hlo_collective_stats",
    "wire_bytes",
    "CostModel",
    "Measurement",
    "fit_cost_model",
    "load_profile",
    "probe_collectives",
    "FleetCollector",
    "FlightRecorder",
    "TraceContext",
    "check_flows",
    "emit_flow",
    "RequestRecord",
    "RequestTracer",
    "BurnWindow",
    "RollingPercentiles",
    "SLOMonitor",
    "SLOTarget",
]
