"""Host-side spans: one primitive, two sinks.

``jax.profiler`` traces the DEVICE; what it cannot name by itself is the
host-side orchestration around it — admission loops, sampling, checkpoint
serialization, the train loop's data stalls.  :func:`span` names those:

    with span("serving.admit.prefill"):
        logits, kv = prefill(params, tokens)

Every span opens a ``jax.profiler.TraceAnnotation``.  With no profiler
session that is a flag test; under ``jax.profiler.trace`` (or
``apex_tpu.utils.profiling.trace``) its start and end land on the
``/host:CPU`` plane of the same ``.xplane.pb`` as the device's ``XLA Ops``,
on the same clock, with ``args`` as the event's stats.  A span is ALSO
recorded as a Chrome trace event, but only into a :class:`Tracer` the
caller passed (``span(name, tracer=t)`` or ``t.span(name)``): with no
tracer nothing is built, locked or appended.

Spans nest per thread (with a tracer, a span closed out of order raises —
the same contract as ``profiling.range_push/pop``).  A span names HOST
code; to name device operations, put ``jax.named_scope`` inside the
function that is traced and compiled (``device=True`` on
:meth:`Tracer.span` does that for a span that wraps traced code).

Events use the Chrome trace-event format (``ph: "X"`` complete events,
microsecond timestamps, pid/tid) — ``Tracer.save(path)`` writes a file
that chrome://tracing and https://ui.perfetto.dev open directly.
"""

from __future__ import annotations

import itertools
import json
import os
import threading
import time
from typing import Optional

import jax

# monotone instance counter: two Tracers in ONE process (an in-process
# test fleet) must still mint distinct id tags, so pid alone is not
# enough — see `Tracer.id_tag`
_INSTANCE_SEQ = itertools.count()


class Tracer:
    """Collects spans into a Chrome trace-event list.  Thread-safe;
    ``clock`` is injectable (seconds; default ``time.perf_counter``).

    ``id_tag`` namespaces this tracer's async-event ids so traces from
    several replicas merge without (cat, id) collisions: each replica's
    id counters used to restart at 0, and Perfetto folds same-id flows
    from different files onto one row.  The default tag is
    ``"<pid hex>.<instance #>"`` — unique across processes AND across
    tracers within one process.  Flow events (:meth:`flow`) are the one
    deliberate exception: their ids must MATCH across replicas (that is
    how a migrated request's fragments stitch), so they are never
    prefixed."""

    def __init__(self, clock=time.perf_counter, *,
                 id_tag: Optional[str] = None):
        self.clock = clock
        self.id_tag = (id_tag if id_tag is not None
                       else f"{os.getpid():x}.{next(_INSTANCE_SEQ)}")
        self._lock = threading.Lock()
        self._events: list = []
        self._local = threading.local()

    # -- recording -----------------------------------------------------------

    def _stack(self) -> list:
        if not hasattr(self._local, "stack"):
            self._local.stack = []
        return self._local.stack

    def depth(self) -> int:
        """Current span nesting depth on THIS thread."""
        return len(self._stack())

    def span(self, name: str, device: bool = False, **args):
        """Time a host-side region: a profiler ``TraceAnnotation`` and a
        Chrome event in this tracer; ``args`` become the payload of both
        (more can be added inside with ``set_metadata(**args)`` on the
        value ``with`` binds).  ``device=True`` also enters
        ``jax.named_scope(name)``, for a span that wraps code being
        traced for compilation."""
        return _RecordedSpan(self, name, device, args)

    def _record(self, ev: dict) -> None:
        with self._lock:
            self._events.append(ev)

    def instant(self, name: str, **args) -> None:
        """A zero-duration marker (trace-event ``ph: "i"``) — step
        boundaries, rollbacks, admissions."""
        ev = {"name": name, "ph": "i", "cat": "host", "s": "t",
              "ts": self.clock() * 1e6,
              "pid": os.getpid(), "tid": threading.get_ident()}
        if args:
            ev["args"] = dict(args)
        with self._lock:
            self._events.append(ev)

    # -- async (per-flow) events ---------------------------------------------
    #
    # Host spans live on thread tracks; a REQUEST's lifecycle hops
    # threads and interleaves with other requests, so it gets a
    # nestable async track instead: Perfetto groups events sharing
    # (cat, id) onto one row per flow — one row per request.

    def async_span(self, name: str, id: object, ts: float, dur: float,
                   cat: str = "request", **args) -> None:
        """One closed async slice on flow ``(cat, id)``: a ``ph: "b"``
        / ``ph: "e"`` nestable pair at ``ts``..``ts + dur`` (seconds on
        this tracer's clock).  Emitted after the fact — the request
        tracer records raw timestamps on the hot path and materializes
        trace events once, at request completion."""
        ident = f"{self.id_tag}/{id}"
        pid = os.getpid()
        begin = {"name": name, "ph": "b", "cat": cat, "id": ident,
                 "ts": ts * 1e6, "pid": pid, "tid": pid}
        if args:
            begin["args"] = dict(args)
        end = {"name": name, "ph": "e", "cat": cat, "id": ident,
               "ts": (ts + dur) * 1e6, "pid": pid, "tid": pid}
        with self._lock:
            self._events.append(begin)
            self._events.append(end)

    def async_instant(self, name: str, id: object, ts: float,
                      cat: str = "request", **args) -> None:
        """A point event (``ph: "n"``) on flow ``(cat, id)`` — decode
        ticks, admission edges."""
        ev = {"name": name, "ph": "n", "cat": cat,
              "id": f"{self.id_tag}/{id}",
              "ts": ts * 1e6, "pid": os.getpid(), "tid": os.getpid()}
        if args:
            ev["args"] = dict(args)
        with self._lock:
            self._events.append(ev)

    # -- flow events (cross-replica causality) -------------------------------
    #
    # Chrome stitches flow events sharing (cat, name, id) into one
    # arrow chain across tracks — and, after a merge, across replicas.
    # Fixed cat/name ("reqflow"/"request") keep the stitch key down to
    # the id alone; the id is the fleet-wide trace id and is therefore
    # NOT namespaced by `id_tag` (matching across replicas is the
    # point).

    FLOW_CAT = "reqflow"
    FLOW_NAME = "request"

    def flow(self, ph: str, id: object, ts: Optional[float] = None,
             **args) -> dict:
        """One flow event: ``ph`` is ``"s"`` (start), ``"t"`` (step) or
        ``"f"`` (end).  ``ts`` is seconds on this tracer's clock
        (default: now).  Returns the event dict (callers stash the span
        id they put in ``args`` to parent the next hop)."""
        if ph not in ("s", "t", "f"):
            raise ValueError(f"flow ph must be s/t/f, got {ph!r}")
        pid = os.getpid()
        ev = {"name": self.FLOW_NAME, "ph": ph, "cat": self.FLOW_CAT,
              "id": str(id),
              "ts": (self.clock() if ts is None else ts) * 1e6,
              "pid": pid, "tid": pid}
        if ph == "f":
            ev["bp"] = "e"          # bind the arrow to the enclosing slice
        if args:
            ev["args"] = dict(args)
        with self._lock:
            self._events.append(ev)
        return ev

    # -- export --------------------------------------------------------------

    @property
    def events(self) -> list:
        with self._lock:
            return list(self._events)

    def clear(self) -> None:
        with self._lock:
            self._events = []

    def to_json(self) -> str:
        """Chrome trace-event JSON (the ``traceEvents`` object form)."""
        return json.dumps({"traceEvents": self.events,
                           "displayTimeUnit": "ms"})

    def save(self, path: str) -> str:
        with open(path, "w", encoding="utf-8") as f:
            f.write(self.to_json())
        return path


class _RecordedSpan:
    """The context manager of :meth:`Tracer.span`."""

    __slots__ = ("_tracer", "_name", "_args", "_scope", "_annotation",
                 "_depth", "_t0")

    def __init__(self, tracer, name, device, args):
        self._tracer, self._name, self._args = tracer, name, args
        self._scope = jax.named_scope(name) if device else None
        self._annotation = jax.profiler.TraceAnnotation(name, **args)

    def set_metadata(self, **args) -> None:
        self._annotation.set_metadata(**args)
        self._args.update(args)

    def __enter__(self):
        tr = self._tracer
        stack = tr._stack()
        stack.append(self._name)
        self._depth = len(stack)
        self._annotation.__enter__()
        if self._scope is not None:
            self._scope.__enter__()
        self._t0 = tr.clock()
        return self

    def __exit__(self, exc_type, exc, tb):
        tr, name = self._tracer, self._name
        dt = tr.clock() - self._t0
        if self._scope is not None:
            self._scope.__exit__(exc_type, exc, tb)
        self._annotation.__exit__(exc_type, exc, tb)
        popped = tr._stack().pop()
        if popped != name:            # pragma: no cover - defensive
            raise RuntimeError(
                f"span nesting violated: closing {name!r}, "
                f"top of stack is {popped!r}")
        ev = {"name": name, "ph": "X", "cat": "host",
              "ts": self._t0 * 1e6, "dur": dt * 1e6,
              "pid": os.getpid(), "tid": threading.get_ident()}
        # the span still closes (and the stack still pops) when the body
        # raises; the event records what detonated so the trace shows
        # WHERE the exception path spent its time
        if self._args or self._depth > 1 or exc_type is not None:
            ev["args"] = {**self._args, "depth": self._depth}
            if exc_type is not None:
                ev["args"]["error"] = exc_type.__name__
        tr._record(ev)
        return False


def span(name: str, *, tracer: Optional[Tracer] = None, **args):
    """``with span("serving.admit", admitted=2): ...`` — a profiler
    ``TraceAnnotation``, and a Chrome event in ``tracer`` if one is given.
    The value ``with`` binds has ``set_metadata(**args)`` either way."""
    if tracer is None:
        return jax.profiler.TraceAnnotation(name, **args)
    return tracer.span(name, **args)
