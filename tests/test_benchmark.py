"""The benchmark's own cases, collected by tier-1, and the readers of what
the program names itself (``benchmarks/harness/span_readers.py``) on
hand-built traces.

``benchmarks/tests/test_benchmark.py`` stays where the benchmark keeps it
(``python3 -m pytest benchmarks/tests``); this file loads it by path and
takes its ``test_*`` functions and fixtures, so that ``pytest tests/`` runs
the same dozen cases.
"""

import importlib.util
import json
import os

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_spec = importlib.util.spec_from_file_location(
    "benchmark_own_tests",
    os.path.join(ROOT, "benchmarks", "tests", "test_benchmark.py"))
_own = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_own)
globals().update({k: v for k, v in vars(_own).items()
                  if k.startswith("test_") or k == "man"})

from benchmarks.harness import readers, span_readers as sr  # noqa: E402
from benchmarks.harness import trace as tracing  # noqa: E402
from benchmarks.harness.job import Run  # noqa: E402


def test_the_benchmarks_own_cases_are_all_here():
    own = [k for k in vars(_own) if k.startswith("test_")]
    assert len(own) == 12
    assert all(globals()[k] is getattr(_own, k) for k in own)


# -- a hand-built serving tick --------------------------------------------------
#
#   0        10   12        40        70  75   80   90   100
#   |-------------------- serving.step (0-90) ------|
#     evict  |---------- admit (12-70) ---------|
#            |------- admit.request (12-68) ---|
#              prefill 14-20, kv_write 20-50, first_token 50-66
#                                                  dispatch 70-75
#                                                  wait 75-85, sample 85-89
#   a second step runs from 95 to 130 and is cut by the window (0-100)

TICK = [
    ("serving.step", 0, 90),
    ("serving.evict", 2, 10),
    ("serving.admit", 12, 70),
    ("serving.admit.request", 12, 68),
    ("serving.admit.prefill", 14, 20),
    ("serving.admit.kv_write", 20, 50),
    ("serving.admit.first_token", 50, 66),
    ("serving.decode.dispatch", 70, 75),
    ("serving.decode.wait", 75, 85),
    ("serving.sample", 85, 89),
    ("serving.step", 95, 130),
    ("serving.admit", 96, 130),
    ("serving.admit.request", 96, 130),
]
WINDOW = (0, 100)

# PR 37's spans laid into that tick: the dispatch in three phases that tile
# it, and the stall the two admissions put on a reply in flight (13-67 and
# 97-130, the second cut by the window)
TICK37 = TICK + [
    ("serving.decode.stalled", 13, 67),
    ("serving.decode.grow", 70, 71),
    ("serving.decode.inputs", 71, 73),
    ("serving.decode.launch", 73, 75),
    ("serving.decode.stalled", 97, 130),
]
# a tick's operations under the plain block's scopes, 90 ns busy of 100
TICK_OPS = [
    ("jit(decode_step_paged)/embeddings/gather", 0, 5),
    ("jit(decode_step_paged)/attention/dot_general", 5, 20),
    ("jit(decode_step_paged)/attention/attention.kv_write/scatter", 20, 30),
    ("jit(decode_step_paged)/attention/jit(decode_step_paged)/pallas_call",
     30, 50),
    ("jit(decode_step_paged)/mlp/dot_general", 50, 80),
    ("jit(decode_step_paged)/lm_head/dot_general", 80, 90),
]
BOTH_SERVING = ["gpt2-medium.shortreply-steady", "glm-5.2.longdoc-steady"]
# the served cells the tick-phase and stall metrics are read in, the third
# the window-attention cell
SERVING = BOTH_SERVING + ["command-a-plus-05-2026.mixedlen-steady"]
# name, unit, layer, source, cells, what the hand-built trace reads, what
# the parent's (TICK alone, operations that carry no scope) reads
PR37 = [
    ("attention_time_share.tpot", "%", "models", "device_trace",
     BOTH_SERVING[:1], 100 * 45 / 90, None),
    ("mlp_time_share.tpot", "%", "models", "device_trace",
     BOTH_SERVING[:1], 100 * 30 / 90, None),
    ("tick_dispatch_p50_ms.tpot", "ms", "serving host loop", "program_span",
     SERVING, 5e-6, 5e-6),
    ("tick_wait_p50_ms.tpot", "ms", "serving host loop", "program_span",
     SERVING, 10e-6, 10e-6),
    ("tick_grow_p50_ms.tpot", "ms", "serving host loop", "program_span",
     SERVING, 1e-6, None),
    ("tick_inputs_p50_ms.tpot", "ms", "serving host loop", "program_span",
     SERVING, 2e-6, None),
    ("tick_launch_p50_ms.tpot", "ms", "serving host loop", "program_span",
     SERVING, 2e-6, None),
    ("decode_stalled_share.tpot", "%", "serving host loop", "program_span",
     SERVING, 54 + 3, None),
    ("decode_stall_p50_ms.tpot", "ms", "serving host loop", "program_span",
     SERVING, 54e-6, None),
]
# a step that launched its tick ahead, on the ids of the tick before: the
# second step (95-130), from 96 past the window's end
TICK_AHEAD = TICK37 + [("serving.decode.ahead", 96, 130)]


def _device(ops):
    return tracing.DeviceTrace(tracing.Events.of([]), tracing.Events.of(ops),
                               tracing.Events.of([]))


def _run(spans=(), op_paths=(), ops=()):
    program = sr.ProgramTrace(
        {"engine": list(spans), "other": [("serving.sample", 0, 100)]},
        [list(op_paths)])
    trace = tracing.Trace([_device(list(ops))], [], WINDOW)
    return Run(True, 0, 0, {}, {}, {"program_trace": program}, trace=trace)


def test_span_share_nested_spans_and_minus():
    run = _run(TICK)
    # both steps, the second cut at 100: 90 + 5
    assert readers.span_time_share(run, {}, "serving.step") \
        == pytest.approx(95)
    # less the two waits, which lie inside the first step: 95 - 10 - 16
    assert readers.span_time_share(
        run, {}, "serving.step",
        minus=["serving.decode.wait", "serving.admit.first_token"]) \
        == pytest.approx(69)
    # the sample span of another thread is not the engine's
    assert readers.span_time_share(run, {}, "serving.sample") \
        == pytest.approx(4)
    # a span subtracted from itself, and one that was never opened
    assert readers.span_time_share(run, {}, "serving.sample",
                                   minus=["serving.sample"]) == 0
    assert readers.span_time_share(run, {}, "serving.prefill_chunk") is None


def test_a_span_cut_by_the_windows_edge():
    run = _run(TICK)
    # the share counts the part inside the window: 58 + 4
    assert readers.span_time_share(run, {}, "serving.admit") \
        == pytest.approx(62)
    # the median takes whole spans only: the second admission is cut
    assert readers.span_p50_ms(run, {}, "serving.admit.request") \
        == pytest.approx(56e-6)
    assert readers.span_p50_ms(run, {}, "serving.prefill_chunk") is None


def test_span_readers_find_nothing_in_a_program_without_spans():
    run = _run([])
    assert readers.span_time_share(run, {}, "serving.step") is None
    assert readers.span_p50_ms(run, {}, "serving.admit.request") is None
    assert readers.scope_time_share(run, {}, "optimizer") is None
    run.trace = None
    assert readers.span_time_share(run, {}, "serving.step") is None


@pytest.mark.parametrize("path, scope", [
    ("jit(step)/jit(main)/transpose(jvp(attention))/dot_general",
     "attention"),
    ("jit(step)/jvp(attention)/pallas_call", "attention"),
    ("jit(step)/transpose(jvp(checkpoint(mlp)))/mul", "mlp"),
    ("jit(step)/jvp(attention_mask)/select_n", sr.UNSCOPED),
    ("jit(step)/attention_mask/mlm_head_bias/add", sr.UNSCOPED),
    ("jit(step)/optimizer/jit(_where)/select_n", "optimizer"),
    ("jit(step)/shard_map/ddp.reduce/psum", "ddp.reduce"),
    ("jit(step)/jvp(mlp/attention)/add", "attention"),      # the innermost
    ("jit(step)/transpose(jvp(mlm_head))/mul", "mlm_head"),
    ("reduce_sum", sr.UNSCOPED),
    ("params['layers'][3]['attention']['qkv']['weight']", sr.UNSCOPED),
    ("", sr.UNSCOPED),
])
def test_scope_is_matched_as_a_component_through_wrappers(path, scope):
    assert sr.innermost_scope(path) == scope


OPS = [  # (scope path, start, end): a step of 100 ns with 10 ns idle
    ("jit(step)/jvp(embeddings)/gather", 0, 5),
    ("jit(step)/jvp(attention)/pallas_call", 5, 25),
    ("jit(step)/jvp(mlp)/dot_general", 25, 45),
    ("jit(step)/jvp(mlm_head)/pallas_call", 45, 55),
    ("jit(step)/transpose(jvp(mlp))/dot_general", 55, 60),
    ("jit(step)/transpose(jvp(attention))/pallas_call", 60, 70),
    ("jit(step)/jvp(attention_mask)/select_n", 70, 72),
    ("reduce_sum", 72, 75),
    # a while loop of the optimizer that holds two operations of its own
    ("jit(step)/optimizer/while", 75, 90),
    ("jit(step)/optimizer/while/body/mul", 76, 80),
    ("jit(step)/optimizer/while/body/add", 80, 88),
    # cut by the window's edge at 100
    ("jit(step)/jvp(attention)/dot_general", 95, 120),
]


def test_scope_shares_and_the_unscoped_rest_sum_to_100():
    run = _run(op_paths=OPS, ops=[("op", s, e) for _, s, e in OPS])
    share = {s: readers.scope_time_share(run, {}, s) for s in sr.SCOPES}
    busy = 95.0                     # 0-90 and 95-100
    assert share["attention"] == pytest.approx(100 * 35 / busy)
    assert share["mlp"] == pytest.approx(100 * 25 / busy)
    assert share["mlm_head"] == pytest.approx(100 * 10 / busy)
    assert share["embeddings"] == pytest.approx(100 * 5 / busy)
    # the loop is billed once: 15 ns, not 15 + 4 + 8
    assert share["optimizer"] == pytest.approx(100 * 15 / busy)
    assert share["ddp.reduce"] == 0
    by = sr.time_by_scope(OPS, WINDOW)
    assert by[sr.UNSCOPED] == pytest.approx(5)
    assert sum(share.values()) + 100 * by[sr.UNSCOPED] / busy \
        == pytest.approx(100)
    assert sum(by.values()) == pytest.approx(
        1e9 * tracing.busy_s(run.trace.devices[0], WINDOW))


def test_an_idle_gap_is_billed_to_the_innermost_span():
    # the device works 0-22, 48-76 and 84-100: idle 22-48 and 76-84
    dev = _device([("a", 0, 22), ("b", 48, 76), ("c", 84, 100)])
    idle = sr.idle_by_innermost_span(dev, TICK, WINDOW)
    # 22-48 lies under step > admit > admit.request > admit.kv_write
    assert idle["serving.admit.kv_write"] * 1e9 == pytest.approx(26)
    # 76-84 lies under step > decode.wait
    assert idle["serving.decode.wait"] * 1e9 == pytest.approx(8)
    assert set(idle) == {"serving.admit.kv_write", "serving.decode.wait",
                         tracing.NO_SPAN}
    assert idle[tracing.NO_SPAN] == pytest.approx(0)
    # the harness's own reduction bills the gap to every span over it
    flat = tracing.idle_gaps_by_span(dev, TICK, WINDOW)
    assert flat["serving.step"] * 1e9 == pytest.approx(34)
    # with flat spans the two agree, and what no span covers is named so
    spans = [("make_batch", 0, 30), ("train_step", 30, 80)]
    assert sr.idle_by_innermost_span(dev, spans, WINDOW) \
        == pytest.approx(tracing.idle_gaps_by_span(dev, spans, WINDOW))
    gap = sr.idle_by_innermost_span(
        _device([("a", 0, 50)]), [("x", 0, 60)], WINDOW)
    assert gap["x"] * 1e9 == pytest.approx(10)
    assert gap[tracing.NO_SPAN] * 1e9 == pytest.approx(40)


def test_new_metrics_resolve_to_the_new_readers():
    """Eight entries PR 25 appended to the manifest, each with its file,
    each naming a reader that ``run.py``'s ``getattr(readers, ...)`` finds;
    PR 27 appended three more behind them and PR 31 two behind those, read
    by readers the harness had; PR 35 five for its served cell, two of them
    (the scopes ``attention`` and ``mlp`` on a serving trace) the span
    readers' again; PR 36 one, a prefill's flash kernel by its name; PR 37
    nine, every one read by the span readers (``PR37`` below)."""
    man = _own.manifest.Manifest(ROOT)
    names = [m["name"] for m in man.data["per_layer"]]
    first = names.index("host_work_share.tpot")
    new = man.data["per_layer"][first:first + 8]
    assert [m["name"] for m in new] == [
        "host_work_share.tpot", "sample_host_share.tpot",
        "admit_host_share.ttft", "admit_request_p50_ms.ttft",
        "optimizer_time_share.train", "attention_time_share.train",
        "mlp_time_share.train", "mlm_head_time_share.train"]
    for entry in new:
        spec = man.metric(entry)
        assert getattr(readers, spec["reader"]) is sr.READERS[spec["reader"]]
        assert spec["source"] in ("program_span", "device_trace")
    assert names[first + 8:-5] == [
        "flash_bwd_h128_roofline.train", "allreduce_time_share.train",
        "allreduce_exposed_share.train", "grouped_dot_time_share.train",
        "flash_rows_bwd_causal_roofline.train",
        "attention_time_share.ttft", "mlp_time_share.ttft",
        "sparse_attention_time_share.ttft", "indexer_time_share.ttft",
        "grouped_dot_time_share.tpot", "masked_flash_time_share.ttft"] \
        + [case[0] for case in PR37]
    # the window-attention cell's two kernels, by their names, then the
    # trained window layers' two, then the tick launched ahead
    assert names[-5:] == ["window_flash_time_share.ttft",
                          "grouped_decode_time_share.tpot",
                          "band_flash_time_share.train",
                          "grouped_flash_bwd_roofline.train",
                          "tick_ahead_share.tpot"]
    scoped = {"attention_time_share.ttft", "mlp_time_share.ttft"} \
        | {case[0] for case in PR37}
    scoped.add("tick_ahead_share.tpot")     # a span's share
    for entry in man.data["per_layer"][first + 8:]:
        spec = man.metric(entry)
        assert (spec["reader"] in sr.READERS) == (entry["name"] in scoped)
        assert callable(getattr(readers, spec["reader"]))


def test_masked_flash_time_share_reads_the_kernel_by_its_name():
    """PR 36's entry, data alone: ``op_time_share`` over busy time of the
    custom call XLA names after the jitted function that holds it, in the
    one cell that runs it; what is left under the old shapes, another
    kernel and the parent's loop (no such call) are not counted."""
    man = _own.manifest.Manifest(ROOT)
    entry = next(m for m in man.data["per_layer"]
                 if m["name"] == "masked_flash_time_share.ttft")
    assert entry == {
        "name": "masked_flash_time_share.ttft", "unit": "%",
        "better": "lower", "source": "device_trace", "layer": "kernels",
        "moves": "ttft_p90_s", "workloads": ["glm-5.2.longdoc-steady"]}
    spec = man.metric(entry)
    assert spec["reader"] == "op_time_share" \
        and spec["args"]["over"] == "busy"
    kernel = ("%masked_flash.6 = bf16[1,16384,4096]{2,1,0:T(8,128)(2,1)} "
              "custom-call(%fusion.125, %pad_maximum_fusion.2, "
              "%convolution_bitcast_fusion.6, %get-tuple-element.540), "
              'custom_call_target="tpu_custom_call"')
    loop = ("%fusion.310 = f32[16,128]{1,0:T(8,128)} fusion("
            "f32[16,128,16384]{2,1,0} %bitcast.5), kind=kInput")
    other = ("%decode_step_paged.3 = bf16[8,16,64]{2,1,0} custom-call(), "
             'custom_call_target="tpu_custom_call"')
    read = getattr(readers, spec["reader"])
    run = _run(ops=[(kernel, 0, 30), (loop, 30, 40), (other, 40, 50),
                    (kernel.replace(".6", ".7"), 60, 80)])
    assert read(run, None, **spec["args"]) == pytest.approx(100 * 50 / 70)
    parent = _run(ops=[(loop, 0, 40), (other, 40, 50)])
    assert read(parent, None, **spec["args"]) == 0.0


@pytest.mark.parametrize("name, unit, layer, source, cells, reads, parent",
                         PR37, ids=[case[0] for case in PR37])
def test_pr37_metrics_read_the_engines_spans_and_the_plain_blocks_scopes(
        name, unit, layer, source, cells, reads, parent):
    """PR 37's nine entries, data alone: each resolves by name to a reader
    the harness had, reads from a hand-built tick what its spans or scopes
    give, and on the parent's trace (no phase inside the dispatch, no stall
    span, no scope on the plain block's cache paths) reads None, so that
    the line leaves it out; the dispatch and the wait the parent had."""
    man = _own.manifest.Manifest(ROOT)
    entry = next(m for m in man.data["per_layer"] if m["name"] == name)
    assert entry == {"name": name, "unit": unit, "better": "lower",
                     "source": source, "layer": layer,
                     "moves": "tpot_p90_s", "workloads": cells}
    spec = man.metric(entry)
    read = getattr(readers, spec["reader"])
    assert read is sr.READERS[spec["reader"]]
    ops = [("op", s, e) for _, s, e in TICK_OPS]
    got = read(_run(TICK37, op_paths=TICK_OPS, ops=ops), None, **spec["args"])
    assert got == pytest.approx(reads)
    bare = [(f"jit(decode_step_paged)/{p.rsplit('/', 1)[1]}", s, e)
            for p, s, e in TICK_OPS]
    got = read(_run(TICK, op_paths=bare, ops=ops), None, **spec["args"])
    assert got == (None if parent is None else pytest.approx(parent))
    for cell in cells:
        assert name in {m["name"] for m in man.cell(cell).per_layer}


def test_tick_ahead_share_reads_the_ahead_span_or_nothing():
    """The ahead tick's entry, data alone: ``span_time_share`` of
    ``serving.decode.ahead`` over the window, in the three serving cells;
    on a trace without the span (an engine that fetches every tick before
    it launches the next) it reads None, so that the line leaves it out."""
    man = _own.manifest.Manifest(ROOT)
    entry = next(m for m in man.data["per_layer"]
                 if m["name"] == "tick_ahead_share.tpot")
    assert entry == {"name": "tick_ahead_share.tpot", "unit": "%",
                     "better": "higher", "source": "program_span",
                     "layer": "serving host loop", "moves": "tpot_p90_s",
                     "workloads": SERVING}
    spec = man.metric(entry)
    read = getattr(readers, spec["reader"])
    assert read is sr.span_time_share
    # 96-130 cut by the window's end at 100
    assert read(_run(TICK_AHEAD), None, **spec["args"]) == pytest.approx(4)
    assert read(_run(TICK37), None, **spec["args"]) is None
    for cell in SERVING:
        assert "tick_ahead_share.tpot" in {
            m["name"] for m in man.cell(cell).per_layer}


def test_the_ticks_phases_tile_its_dispatch_and_the_write_is_billed_apart():
    """What ``PERF.md`` section 5 does with a traced run: the three phases'
    medians add up to the dispatch's, and ``time_by_scope`` with ``lm_head``
    and ``attention.kv_write`` listed beside the fixed tuple bills the
    token's scatter apart from ``attention`` and leaves nothing unscoped."""
    run = _run(TICK37, op_paths=TICK_OPS)
    p50 = {n: readers.span_p50_ms(run, None, f"serving.decode.{n}")
           for n in ("dispatch", "grow", "inputs", "launch")}
    assert p50["grow"] + p50["inputs"] + p50["launch"] \
        == pytest.approx(p50["dispatch"])
    by = sr.time_by_scope(TICK_OPS, WINDOW)
    assert by == {"embeddings": 5, "attention": 45, "mlp": 30,
                  sr.UNSCOPED: 10}
    listed = sr.time_by_scope(
        TICK_OPS, WINDOW,
        scopes=sr.SCOPES + ("lm_head", "attention.kv_write"))
    assert listed == {"embeddings": 5, "attention": 35,
                      "attention.kv_write": 10, "mlp": 30, "lm_head": 10}
