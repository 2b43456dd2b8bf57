"""From a profiler trace to numbers: device busy and idle time, time by
module and by operation, collectives exposed or hidden, idle gaps by what
the host was doing.

The arithmetic works on plain lists of ``(name, start_ns, end_ns)`` so
that it can be checked on a hand-built trace; :func:`load` fills them from
the ``.xplane.pb`` the JAX profiler writes.  On a v5e the device plane is
``/device:TPU:<n>`` with lines ``XLA Modules`` (one event per program run,
``jit_step(...)``), ``XLA Ops`` (one per operation, named by its whole HLO
line) and ``Async XLA Ops`` (a span from ``*-start`` to ``*-done``); host
threads are lines of ``/host:CPU`` and ``jax.profiler.TraceAnnotation``
spans land on the one of the thread that opened them.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re

import numpy as np

OPEN, CLOSE = "bench.window_open", "bench.window_close"
NO_SPAN = "_no_span_"


@dataclasses.dataclass
class Events:
    """Events of one line: unique ``names`` and, per event, an index into
    them with its start and end in nanoseconds."""
    names: list
    which: np.ndarray
    start: np.ndarray
    end: np.ndarray

    @classmethod
    def of(cls, events):
        ids, which = {}, []
        for name, _, _ in events:
            which.append(ids.setdefault(name, len(ids)))
        return cls(list(ids), np.asarray(which, np.int64),
                   np.asarray([e[1] for e in events], np.int64),
                   np.asarray([e[2] for e in events], np.int64))

    def matching(self, pattern, invert=False):
        """(start, end) of the events whose name matches ``pattern``."""
        rx = re.compile(pattern)
        hit = np.asarray([bool(rx.search(n)) != invert for n in self.names],
                         bool)
        keep = hit[self.which] if len(self.which) else np.zeros(0, bool)
        return self.start[keep], self.end[keep]

    def inside(self, pattern, window):
        """How many events matching ``pattern`` lie wholly in ``window``."""
        s, e = self.matching(pattern)
        return int(np.sum((s >= window[0]) & (e <= window[1])))


@dataclasses.dataclass
class DeviceTrace:
    modules: Events
    ops: Events
    async_ops: Events


@dataclasses.dataclass
class Trace:
    devices: list           # DeviceTrace per chip
    spans: list             # (name, start_ns, end_ns) host annotations
    window: tuple           # (start_ns, end_ns) of the traced window

    @property
    def window_s(self):
        return (self.window[1] - self.window[0]) / 1e9

    def mean(self, per_device):
        """A per-device reduction averaged over the chips."""
        return float(np.mean([per_device(d) for d in self.devices]))


# -- interval arithmetic -----------------------------------------------------

def union(start, end):
    """Disjoint sorted intervals covering the same points."""
    if len(start) == 0:
        return np.zeros(0, np.int64), np.zeros(0, np.int64)
    order = np.argsort(start, kind="stable")
    s, e = np.asarray(start)[order], np.asarray(end)[order]
    reach = np.maximum.accumulate(e)
    first = np.ones(len(s), bool)
    first[1:] = s[1:] > reach[:-1]
    last = np.append(first[1:], True)
    return s[first], reach[last]


def clip(start, end, window):
    s, e = np.maximum(start, window[0]), np.minimum(end, window[1])
    keep = e > s
    return s[keep], e[keep]


def covered_ns(start, end, window):
    s, e = clip(*union(start, end), window)
    return int((e - s).sum())


def gaps(start, end, window):
    """The intervals of ``window`` that ``union(start, end)`` leaves out."""
    s, e = clip(*union(start, end), window)
    gs = np.concatenate([[window[0]], e])
    ge = np.concatenate([s, [window[1]]])
    keep = ge > gs
    return gs[keep], ge[keep]


def busy_s(dev, window):
    return covered_ns(dev.ops.start, dev.ops.end, window) / 1e9


def op_s(dev, pattern, window, with_async=False):
    """Seconds in which at least one operation matching ``pattern`` ran."""
    s, e = dev.ops.matching(pattern)
    if with_async:
        a, b = dev.async_ops.matching(pattern)
        s, e = np.concatenate([s, a]), np.concatenate([e, b])
    return covered_ns(s, e, window) / 1e9


def exposed_s(dev, pattern, window):
    """Seconds in which an operation matching ``pattern`` ran, on either
    line, and no other operation did."""
    others = dev.ops.matching(pattern, invert=True)
    s, e = dev.ops.matching(pattern)
    a, b = dev.async_ops.matching(pattern)
    both = (np.concatenate([s, a, others[0]]),
            np.concatenate([e, b, others[1]]))
    # |A minus O| = |A or O| - |O|
    return (covered_ns(*both, window) - covered_ns(*others, window)) / 1e9


def idle_gaps_by_span(dev, spans, window):
    """Idle time of ``dev`` shared out over the host spans it fell in:
    ``{span name: seconds}``, the rest under ``_no_span_``."""
    gs, ge = gaps(dev.ops.start, dev.ops.end, window)
    out, total = {}, float((ge - gs).sum())
    for name, a, b in spans:
        part = float(np.clip(np.minimum(ge, b) - np.maximum(gs, a),
                             0, None).sum())
        if part:
            out[name] = out.get(name, 0.0) + part / 1e9
    out[NO_SPAN] = max(total / 1e9 - sum(out.values()), 0.0)
    return out


# -- names -------------------------------------------------------------------

_HLO = re.compile(r"^%?([\w.\-]+?)(?:\.\d+)? = ")
_SHAPE = re.compile(r"\b([a-z]+\d+|pred)\[([\d,]*)\]")
_OPCODE = re.compile(r" ([a-z][a-z0-9\-]*)\(")


def short_name(name):
    """A short stable name for an operation: its kind, the name XLA gave
    its result and the first shape it makes, without the instruction's
    number (``copy.copy_bf16_4097_24_2_8_16_64``).  A Mosaic kernel is
    ``mosaic.<name>_<shape>``."""
    m = _HLO.match(name)
    if not m:
        return re.sub(r"[^\w.\-]", "_", name)[:64]
    rest = name[m.end():]
    shape = _SHAPE.search(rest)
    op = _OPCODE.search(" " + rest)
    kind = "mosaic" if "tpu_custom_call" in name else \
        (op.group(1) if op else "op")
    dims = f"_{shape.group(1)}_{shape.group(2).replace(',', '_')}" \
        if shape else ""
    return re.sub(r"[^\w.\-]", "_", f"{kind}.{m.group(1)}{dims}")[:64]


def top_ops(trace, n=10):
    """``[[short name, seconds]]`` of the operations that took most device
    time inside the window, averaged over the chips."""
    total = {}
    for dev in trace.devices:
        s, e = dev.ops.start, dev.ops.end
        dur = np.clip(np.minimum(e, trace.window[1])
                      - np.maximum(s, trace.window[0]), 0, None)
        by_name = np.bincount(dev.ops.which, dur,
                              minlength=len(dev.ops.names))
        for name, ns in zip(dev.ops.names, by_name):
            key = short_name(name)
            total[key] = total.get(key, 0.0) + ns / 1e9 / len(trace.devices)
    return [[k, v] for k, v in sorted(total.items(),
                                      key=lambda kv: -kv[1])[:n]]


def top_gaps(trace, n=10):
    total = {}
    for dev in trace.devices:
        for k, v in idle_gaps_by_span(dev, trace.spans,
                                      trace.window).items():
            total[k] = total.get(k, 0.0) + v / len(trace.devices)
    return [[k, v] for k, v in sorted(total.items(),
                                      key=lambda kv: -kv[1])[:n] if v > 0]


# -- taking and reading the profiler's file -----------------------------------

def start(trace_dir):
    """Start the profiler (host spans and the device; no Python tracer,
    which would slow the very loop that is measured) and mark the
    window's start."""
    import jax
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.enable_hlo_proto = False
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    with jax.profiler.TraceAnnotation(OPEN):
        pass


def stop():
    import jax
    with jax.profiler.TraceAnnotation(CLOSE):
        pass
    jax.profiler.stop_trace()


def _line_events(line):
    return [(e.name, int(e.start_ns), int(e.start_ns + e.duration_ns))
            for e in line.events]


def load(trace_dir, span_names):
    """The newest ``.xplane.pb`` under ``trace_dir`` as a :class:`Trace`.
    The window runs between the benchmark's two marker annotations."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise RuntimeError(f"the profiler wrote no trace under {trace_dir}")
    devices, spans, marks = [], [], {}
    for plane in ProfileData.from_file(paths[-1]).planes:
        if plane.name.startswith("/device:TPU:"):
            lines = {line.name: _line_events(line) for line in plane.lines}
            devices.append(DeviceTrace(
                Events.of(lines.get("XLA Modules", [])),
                Events.of(lines.get("XLA Ops", [])),
                Events.of(lines.get("Async XLA Ops", []))))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in (OPEN, CLOSE):
                        marks[e.name] = int(e.start_ns)
                    elif e.name in span_names:
                        spans.append((e.name, int(e.start_ns),
                                      int(e.start_ns + e.duration_ns)))
    if OPEN not in marks or CLOSE not in marks:
        raise RuntimeError("the trace lacks the benchmark's window markers")
    return Trace(devices, sorted(spans, key=lambda s: s[1]),
                 (marks[OPEN], marks[CLOSE]))


def describe(path, head=4):
    """Planes, lines and the first events of a trace, for a reader who has
    to write a pattern against it."""
    from jax.profiler import ProfileData
    for plane in ProfileData.from_file(path).planes:
        print("PLANE", plane.name)
        for line in plane.lines:
            events = list(line.events)
            print(f"  LINE {line.name!r}: {len(events)} events")
            for e in events[:head]:
                print(f"    {e.name[:300]!r} start={e.start_ns:.0f} "
                      f"dur={e.duration_ns:.0f}")


if __name__ == "__main__":
    import sys
    describe(sys.argv[1])
