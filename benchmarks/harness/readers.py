"""The readers of per-layer metrics.  A metric's file names one of these
and gives its arguments; a reader that finds nothing to read returns None
and the metric is left out of the line."""

from __future__ import annotations

import numpy as np

from . import flops, stats, trace as tracing


def _traced(reader):
    def wrapped(run, peak, *args, **kw):
        if run.trace is None or not run.trace.devices:
            return None
        return reader(run, peak, *args, **kw)
    return wrapped


@_traced
def module_time_share(run, peak, pattern):
    """Percent of the traced window in which a module matching ``pattern``
    ran on the device."""
    t = run.trace
    return 100.0 * t.mean(lambda d: tracing.covered_ns(
        *d.modules.matching(pattern), t.window)) / 1e9 / t.window_s


@_traced
def module_time_p50_ms(run, peak, pattern):
    t = run.trace
    durs = np.concatenate([np.subtract(*d.modules.matching(pattern)[::-1])
                           for d in t.devices])
    return float(np.median(durs)) / 1e6 if len(durs) else None


@_traced
def op_time_share(run, peak, pattern, over="window", with_async=False):
    """Percent of the window (or of the device's busy time) in which an
    operation matching ``pattern`` ran."""
    t = run.trace
    base = t.window_s if over == "window" else \
        t.mean(lambda d: tracing.busy_s(d, t.window))
    return 100.0 * t.mean(lambda d: tracing.op_s(
        d, pattern, t.window, with_async)) / base


@_traced
def op_exposed_share(run, peak, pattern):
    """Percent of the window in which an operation matching ``pattern`` ran
    and no other did."""
    t = run.trace
    return 100.0 * t.mean(
        lambda d: tracing.exposed_s(d, pattern, t.window)) / t.window_s


@_traced
def idle_share(run, peak):
    t = run.trace
    return 100.0 * (1 - t.mean(lambda d: tracing.busy_s(d, t.window))
                    / t.window_s)


def memory_peak_share(run, peak):
    return 100.0 * run.facts["memory_peak_bytes"] / peak["hbm_bytes"]


def sample_mean(run, peak, sample):
    xs = run.samples.get(sample)
    return sum(xs) / len(xs) if xs else None


def sample_percentile(run, peak, sample, q, scale=1.0):
    xs = run.samples.get(sample)
    return scale * stats.percentile(xs, q) if xs else None


@_traced
def mfu(run, peak, pattern):
    """Model FLOP/s utilization over the traced window: the steps whose
    module started inside it, times the tokens of a step and the
    operations a token requires, over chips times peak."""
    t = run.trace
    steps = t.mean(lambda d: d.modules.inside(pattern, t.window))
    rate = steps * run.facts["tokens_per_step"] / t.window_s
    return 100.0 * rate * run.facts["flops_per_token"] / (
        run.facts["chips"] * peak["flops_per_s"])


def _kernel_seconds(run, pattern):
    t = run.trace
    s, e = tracing.clip(*t.devices[0].ops.matching(pattern), t.window)
    return float((e - s).sum()) / 1e9


@_traced
def train_kernel_roofline(run, peak, pattern, modules, kernel):
    """Percent of its roofline that a training kernel reached: the least
    time the chip could take for the calls the traced steps had to make
    (``calls_per_step`` from the configuration, operations and bytes from
    shapes), over the time the operations matching ``pattern`` took."""
    t = run.trace
    shapes = dict(run.facts["shapes"].get(kernel, {}))
    seconds = _kernel_seconds(run, pattern)
    if not shapes or not seconds:
        return None
    calls = shapes.pop("calls_per_step") \
        * t.devices[0].modules.inside(modules, t.window)
    ops, nbytes = getattr(flops, kernel)(**shapes)
    return flops.roofline_share(ops * calls, nbytes * calls, seconds, peak)


@_traced
def decode_kernel_roofline(run, peak, pattern, kernel):
    """The same for the paged decode kernel, whose work follows the cached
    positions the traced ticks had to read (``decode_context_tokens``)."""
    shapes = run.facts["shapes"].get(kernel)
    context = run.facts.get("decode_context_tokens")
    seconds = _kernel_seconds(run, pattern)
    if not shapes or not context or not seconds:
        return None
    ops, nbytes = getattr(flops, kernel)(
        context_tokens=context * run.facts["layers"], **shapes)
    return flops.roofline_share(ops, nbytes, seconds, peak)
