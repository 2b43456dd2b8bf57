"""Readers of what the program names itself: the host spans it opens
(``serving.*``, through ``apex_tpu.observability.span``) and the
``jax.named_scope`` paths its device operations carry.

Both come out of the one ``.xplane.pb`` of a traced run, on one clock: a
span is an event of a ``/host:CPU`` line with its ``args`` as stats; a scope
path is HLO ``op_name``, which the TPU runtime writes as the ``tf_op`` stat
of the *metadata* that the ``XLA Ops`` events of one instruction share
(``jit(step)/transpose(jvp(attention))/dot_general:``).  jax's
``ProfileData`` shows an event's own stats only, so :func:`op_paths` reads
that one map out of the raw protobuf and joins it to the events by name.
:func:`harness.trace.load` keeps neither, so :func:`load` here reads the
file a second time, once per run, when the first of these readers is asked.
The arithmetic works on plain lists, as in ``harness/trace.py``, so that it
can be checked on a hand-built trace.  A program that opens no such span
and names no such scope (every commit before PR 25) gives every reader
nothing to read: it returns None and the metric is left out of the line.

``benchmarks/__init__.py`` attaches the names in :data:`READERS` to
``harness/readers.py``, where ``run.py`` looks a metric's reader up.

    python3 -m benchmarks.harness.span_readers [.bench_trace | x.xplane.pb]

prints what a traced run left behind, for a reader who writes ``PERF.md``:
each line's first events with their stats, the host phases, the idle time
by innermost span and the device time by scope.
"""

from __future__ import annotations

import dataclasses
import functools
import glob
import os
import re

import numpy as np

from . import trace as tracing

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
SPAN_PREFIX = "serving."
TICK = "serving.step"           # the engine's thread is the one that ticks
# the scopes the program opens (PERF.md, section 3); an operation is billed
# to the innermost of these on its path, so that their shares add up
SCOPES = ("embeddings", "attention", "mlp", "mlm_head", "optimizer",
          "ddp.reduce")
UNSCOPED = "_unscoped_"
PATH_STATS = ("tf_op",)


@dataclasses.dataclass
class ProgramTrace:
    """What the program named, in nanoseconds on the trace's clock."""
    threads: dict           # host line -> [(span name, start_ns, end_ns)]
    ops: list               # per chip: [(scope path, start_ns, end_ns)]

    @property
    def engine_spans(self):
        """The spans of the thread that runs the engine's loop."""
        ticks = {k: sum(n == TICK for n, _, _ in v)
                 for k, v in self.threads.items()}
        best = max(ticks, key=ticks.get, default=None)
        return self.threads[best] if best is not None and ticks[best] else []


# -- arithmetic ----------------------------------------------------------------

def _arrays(spans, name=None):
    keep = [(s, e) for n, s, e in spans if name is None or n == name]
    return (np.asarray([s for s, _ in keep], np.int64),
            np.asarray([e for _, e in keep], np.int64))


def covered_less_ns(spans, name, minus, window):
    """Nanoseconds of ``window`` inside a span ``name`` and inside no span
    whose name is in ``minus``."""
    a = _arrays(spans, name)
    m = [_arrays(spans, n) for n in minus]
    ms = np.concatenate([x[0] for x in m] + [np.zeros(0, np.int64)])
    me = np.concatenate([x[1] for x in m] + [np.zeros(0, np.int64)])
    # |A minus M| = |A or M| - |M|
    return tracing.covered_ns(np.concatenate([a[0], ms]),
                              np.concatenate([a[1], me]), window) \
        - tracing.covered_ns(ms, me, window)


def exclusive(start, end, measure):
    """For intervals that nest properly (a span and the spans it contains,
    an operation and those it calls), each one's ``measure`` less that of
    the intervals directly inside it: what is its own.  ``measure(start,
    end)`` is additive over disjoint intervals and vectorised."""
    start, end = np.asarray(start, np.int64), np.asarray(end, np.int64)
    own = np.asarray(measure(start, end), np.float64).copy()
    whole = own.copy()
    order = np.lexsort((-end, start))       # by start, the longer first
    stack = []
    for i in order:
        while stack and end[stack[-1]] <= start[i]:
            stack.pop()
        if stack and end[i] <= end[stack[-1]]:
            own[stack[-1]] -= whole[i]
        stack.append(i)
    return np.clip(own, 0.0, None)


def _length(s, e):
    return e - s


_COMPONENT = re.compile(r"(?:^|[/(])([A-Za-z_][\w.\-]*)(?=[/)]|$)")


def components(path):
    """The scope names on an ``op_name`` path, outermost first:
    ``jit(step)/transpose(jvp(attention))/dot_general`` gives ``step,
    attention, dot_general``.  Transformations (``jvp(``, ``transpose(``,
    ``checkpoint(``, ``jit(``) wrap names and are not names themselves, and
    an argument's path (``params['layers'][3]['attention']``) names no
    scope."""
    return _COMPONENT.findall(path)


@functools.lru_cache(maxsize=None)
def innermost_scope(path, scopes=SCOPES):
    for part in reversed(components(path)):
        if part in scopes:
            return part
    return UNSCOPED


def time_by_scope(ops, window, scopes=SCOPES):
    """``{scope: ns}`` of one chip's operations inside ``window``, each
    billed to the innermost listed scope on its path (``_unscoped_`` where
    there is none), an operation that contains others for its own time
    only: the values add up to the chip's busy time."""
    if not ops:
        return {}
    s = np.clip(np.asarray([o[1] for o in ops], np.int64), *window)
    e = np.clip(np.asarray([o[2] for o in ops], np.int64), *window)
    own = exclusive(s, e, _length)
    out = {}
    for (path, _, _), ns in zip(ops, own):
        if ns:
            scope = innermost_scope(path, scopes)
            out[scope] = out.get(scope, 0.0) + float(ns)
    return out


def idle_by_innermost_span(dev, spans, window):
    """Idle time of ``dev`` inside ``window``, every nanosecond billed to
    the innermost span that covers it: ``{span name: seconds}``, the rest
    under ``_no_span_``.  (``trace.idle_gaps_by_span`` bills a nanosecond
    to every span that covers it, which is the same while spans are flat.)"""
    gs, ge = tracing.gaps(dev.ops.start, dev.ops.end, window)
    total = float((ge - gs).sum())
    before = np.concatenate([[0], np.cumsum(ge - gs)])

    def idle_until(t):
        i = np.searchsorted(gs, t, side="right")
        inside = np.where(i > 0, np.minimum(t, ge[i - 1]) - gs[i - 1], 0) \
            if len(gs) else np.zeros(len(t), np.int64)
        return before[np.maximum(i - 1, 0)] * (i > 0) + inside

    out = {}
    if spans and len(gs):
        s, e = _arrays(spans)
        own = exclusive(s, e, lambda a, b: idle_until(b) - idle_until(a))
        for (name, _, _), ns in zip(spans, own):
            if ns:
                out[name] = out.get(name, 0.0) + ns / 1e9
    out[tracing.NO_SPAN] = max(total / 1e9 - sum(out.values()), 0.0)
    return out


# -- the readers -----------------------------------------------------------------

def _program_trace(run):
    """The run's :class:`ProgramTrace`: a hand-built one under
    ``run.facts["program_trace"]``, or the profiler's file read once."""
    if "program_trace" not in run.facts:
        run.facts["program_trace"] = load(os.path.join(ROOT, ".bench_trace"))
    return run.facts["program_trace"]


def _traced(reader):
    @functools.wraps(reader)
    def wrapped(run, peak, *args, **kw):
        if run.trace is None or not run.trace.devices:
            return None
        return reader(run, peak, _program_trace(run), *args, **kw)
    return wrapped


@_traced
def span_time_share(run, peak, program, name, minus=()):
    """Percent of the traced window that the engine's thread spent inside
    a span ``name``, less the part inside spans named in ``minus``."""
    spans = program.engine_spans
    if not any(n == name for n, _, _ in spans):
        return None
    t = run.trace
    return 100.0 * covered_less_ns(spans, name, minus, t.window) \
        / (t.window[1] - t.window[0])


@_traced
def span_p50_ms(run, peak, program, name):
    """Median length of the spans ``name`` that lie wholly inside the
    traced window."""
    w = run.trace.window
    durs = [e - s for n, s, e in program.engine_spans
            if n == name and s >= w[0] and e <= w[1]]
    return float(np.median(durs)) / 1e6 if durs else None


@_traced
def scope_time_share(run, peak, program, scope):
    """Percent of the device's busy time spent in operations whose
    innermost listed scope is ``scope`` (through ``jvp(...)``,
    ``transpose(...)`` and remat wrappers), averaged over the chips."""
    shares = []
    for ops in program.ops:
        by = time_by_scope(ops, run.trace.window)
        if set(by) <= {UNSCOPED}:       # a program that names no scope
            return None
        shares.append(100.0 * by.get(scope, 0.0) / sum(by.values()))
    return float(np.mean(shares)) if shares else None


READERS = {f.__name__: f for f in (span_time_share, span_p50_ms,
                                   scope_time_share)}


# -- reading the profiler's file ---------------------------------------------------

def newest(path):
    if path.endswith(".pb"):
        return path
    paths = sorted(glob.glob(os.path.join(
        path, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise RuntimeError(f"the profiler wrote no trace under {path}")
    return paths[-1]


def _varint(buf, i):
    value = shift = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        shift += 7
        if byte < 0x80:
            return value, i


def _fields(buf):
    """``(field number, wire type, value)`` of one protobuf message: a
    varint's value, or a memoryview of a length-delimited or fixed field."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        field, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        else:
            if wire == 2:
                size, i = _varint(buf, i)
            else:                       # fixed 64 (1) or fixed 32 (5)
                size = 8 if wire == 1 else 4
            value = buf[i:i + size]
            i += size
        yield field, wire, value


def _map_entry(buf):
    """The value of a ``map<int64, Message>`` entry."""
    return next((v for f, _, v in _fields(buf) if f == 2), buf[:0])


def op_paths(path):
    """``{device plane: {event name: op_name path}}`` from the raw
    ``XSpace``: ``XPlane.event_metadata`` (field 4) holds one
    ``XEventMetadata`` per instruction with its ``name`` (2) and ``stats``
    (5); the stat whose ``XStatMetadata`` (``XPlane.stat_metadata``, 5) is
    named ``tf_op`` carries the path as ``str_value`` (5) or as a
    reference to another stat's name (``ref_value``, 7).  The lines, which
    are nearly all of the file, are stepped over."""
    with open(newest(path), "rb") as f:
        space = memoryview(f.read())
    out = {}
    for field, _, plane in _fields(space):
        if field != 1:
            continue
        name, metadata, stat_names = "", [], {}
        for f, _, v in _fields(plane):
            if f == 2:
                name = bytes(v).decode()
            elif f == 4:
                metadata.append(_map_entry(v))
            elif f == 5:
                sm = {k: x for k, _, x in _fields(_map_entry(v))}
                stat_names[sm.get(1, 0)] = bytes(sm.get(2, b"")).decode()
        if not name.startswith("/device:TPU:"):
            continue
        paths = out.setdefault(name, {})
        for md in metadata:
            event_name, tf_op = "", ""
            for f, _, v in _fields(md):
                if f == 2:
                    event_name = bytes(v).decode()
                elif f == 5:
                    st = {k: x for k, _, x in _fields(v)}
                    if stat_names.get(st.get(1)) in PATH_STATS:
                        tf_op = bytes(st[5]).decode() if 5 in st \
                            else stat_names.get(st.get(7), "")
            if tf_op:
                paths.setdefault(event_name, tf_op.rstrip(":"))
    return out


def load(path):
    """The :class:`ProgramTrace` of the newest ``.xplane.pb`` under
    ``path``."""
    from jax.profiler import ProfileData
    threads, ops = {}, []
    paths = op_paths(path)
    for plane in ProfileData.from_file(newest(path)).planes:
        if plane.name.startswith("/device:TPU:"):
            named = paths.get(plane.name, {})
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops.append([
                        (named.get(e.name, ""), int(e.start_ns),
                         int(e.start_ns + e.duration_ns))
                        for e in line.events])
        elif plane.name.startswith("/host:"):
            for i, line in enumerate(plane.lines):
                spans = [(e.name, int(e.start_ns),
                          int(e.start_ns + e.duration_ns))
                         for e in line.events
                         if e.name.startswith(SPAN_PREFIX)]
                if spans:
                    threads[(plane.name, i, line.name)] = sorted(
                        spans, key=lambda s: (s[1], -s[2]))
    return ProgramTrace(threads, ops)


# -- for a reader who writes PERF.md -------------------------------------------------

def describe(path, head=3):
    """Planes, lines and the first events of a trace with their stats and,
    for a device's operations, the ``op_name`` path of their metadata."""
    from jax.profiler import ProfileData
    paths = op_paths(path)
    for plane in ProfileData.from_file(newest(path)).planes:
        print("PLANE", plane.name)
        named = paths.get(plane.name, {})
        for line in plane.lines:
            events = list(line.events)
            print(f"  LINE {line.name!r}: {len(events)} events")
            for e in events[:head]:
                print(f"    {e.name[:120]!r} start={e.start_ns:.0f} "
                      f"dur={e.duration_ns:.0f} stats={dict(e.stats)} "
                      f"op_name={named.get(e.name)!r}")


def report(path):
    """The host phases, the idle time by innermost span and the device time
    by scope of a traced run of the benchmark."""
    trace = tracing.load(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.dirname(newest(path))))), ())
    program = load(path)
    window, spans = trace.window, program.engine_spans
    window_ns = window[1] - window[0]
    print(f"window_s={window_ns / 1e9:.4f} engine spans={len(spans)}")
    by_name = {}
    for n, s, e in spans:
        if s >= window[0] and e <= window[1]:
            by_name.setdefault(n, []).append(e - s)
    print("host spans wholly inside the window: name, count, total s, "
          "p50 ms, p90 ms, percent of window")
    for n, d in sorted(by_name.items(), key=lambda kv: -sum(kv[1])):
        print(f"  {n:28s} {len(d):5d} {sum(d) / 1e9:8.4f} "
              f"{np.percentile(d, 50) / 1e6:9.3f} "
              f"{np.percentile(d, 90) / 1e6:9.3f} "
              f"{100.0 * sum(d) / window_ns:6.2f}")
    for k, dev in enumerate(trace.devices):
        print(f"chip {k}: busy_s={tracing.busy_s(dev, window):.4f}")
        idle = idle_by_innermost_span(dev, spans, window)
        print("  idle by innermost span, s:", {
            n: round(float(v), 4) for n, v in sorted(
                idle.items(), key=lambda kv: -kv[1])})
        by = time_by_scope(program.ops[k], window) if program.ops else {}
        total = sum(by.values()) or 1.0
        print("  device time by scope, s and percent:", {
            n: (round(v / 1e9, 4), round(100 * v / total, 2))
            for n, v in sorted(by.items(), key=lambda kv: -kv[1])})
        print(f"  scopes with the rest sum to "
              f"{sum(100 * v / total for v in by.values()):.4f} percent")
        ops = program.ops[k] if program.ops else []
        largest = {}
        for (path, s, e), w in zip(ops, dev.ops.which):
            key = (innermost_scope(path), tracing.short_name(dev.ops.names[w]))
            largest[key] = largest.get(key, 0) + max(
                min(e, window[1]) - max(s, window[0]), 0)
        print("  largest operations, s: scope, short name")
        for key, ns in sorted(largest.items(), key=lambda kv: -kv[1])[:24]:
            print(f"    {ns / 1e9:8.4f} {key[0]:12s} {key[1]}")


if __name__ == "__main__":
    import sys
    target = sys.argv[1] if len(sys.argv) > 1 else \
        os.path.join(ROOT, ".bench_trace")
    describe(target)
    report(target)
