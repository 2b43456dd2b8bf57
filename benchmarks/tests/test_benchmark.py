"""The benchmark's own tests, on the CPU: the manifest and its data files,
the traffic generator, the request arithmetic and the trace reduction.

    python3 -m pytest benchmarks/tests -q

Nothing here describes a topology, starts a chip run or is marked slow.
"""

import json
import os
import re
import shutil
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks.harness import (flops, manifest, readers, stats,  # noqa: E402
                                traffic, trace as tracing)
from benchmarks.harness.job import Run  # noqa: E402


@pytest.fixture(scope="module")
def man():
    return manifest.Manifest(ROOT)


def _copy(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "benchmarks"),
                    tmp_path / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__"))
    return tmp_path


def test_manifest_names_units_and_limits(man):
    d = man.data
    assert set(d) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert 1 <= d["run_seconds"] <= 51
    metrics = d["end_to_end"] + d["per_layer"]
    for entry in metrics + d["workloads"] + d["configs"]:
        assert manifest.NAME.match(entry["name"]), entry["name"]
    names = [m["name"] for m in metrics]
    assert len(names) == len(set(names))
    for m in metrics:
        assert manifest.UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
    for m in d["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    assert any(m["name"] == "setup_s" for m in d["end_to_end"])
    for w in d["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["name"] == f"{w['config']}.{w['traffic']}"
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
    assert sum(w["chips"] == 4 for w in d["workloads"]) <= 1
    for c in d["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith("benchmarks/") and len(c["why"]) <= 200


def test_every_cell_resolves_to_its_files_by_name(man):
    e2e = {m["name"] for m in man.data["end_to_end"]}
    for w in man.data["workloads"]:
        cell = man.cell(w["name"])
        assert cell.config["name"] == w["config"]
        assert cell.traffic["job"] in ("train", "serve_open",
                                       "serve_backlog")
        reported = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in reported and len(reported) >= 2
        assert cell.per_layer
        for m in cell.per_layer:
            assert callable(getattr(readers, m["reader"]))
            assert m["moves"] in reported and m["moves"] in e2e
            assert m["name"].endswith(
                {"train_tokens_per_s": ".train", "ttft_p90_s": ".ttft",
                 "tpot_p90_s": ".tpot",
                 "served_tokens_per_s": ".served"}[m["moves"]])


def test_one_layer_one_spelling(man):
    layers = {m["layer"] for m in man.data["per_layer"]}
    assert layers <= {"entry points", "serving host loop",
                      "training wrappers and parallelism", "models",
                      "kernels", "device"}


def test_dropped_in_files_are_found_without_an_edit(tmp_path):
    root = _copy(tmp_path)
    bench = json.load(open(root / "BENCHMARK.json"))
    mix = json.load(open(root / "benchmarks/traffic/shortreply-steady.json"))
    mix["rate_rps"] = 1.5
    json.dump(mix, open(root / "benchmarks/traffic/trickle.json", "w"))
    json.dump({"unit": "s", "layer": "serving host loop",
               "moves": "ttft_p90_s", "source": "program_span",
               "reader": "sample_percentile",
               "args": {"sample": "queue_wait_s", "q": 90}},
              open(root / "benchmarks/metrics/queue_wait_p90_s.ttft.json",
                   "w"))
    bench["workloads"].append({
        "name": "gpt2-medium.trickle", "config": "gpt2-medium",
        "traffic": "trickle", "chips": 1, "why": "a fourth mix"})
    bench["per_layer"].append({
        "name": "queue_wait_p90_s.ttft", "unit": "s", "better": "lower",
        "source": "program_span", "layer": "serving host loop",
        "moves": "ttft_p90_s", "workloads": ["gpt2-medium.trickle"]})
    for m in bench["end_to_end"]:
        if m["name"] in ("ttft_p90_s", "tpot_p90_s"):
            m["workloads"].append("gpt2-medium.trickle")
    json.dump(bench, open(root / "BENCHMARK.json", "w"))
    cell = manifest.Manifest(str(root)).cell("gpt2-medium.trickle")
    assert cell.traffic["rate_rps"] == 1.5
    new = [m for m in cell.per_layer if m["name"] == "queue_wait_p90_s.ttft"]
    assert new and new[0]["reader"] == "sample_percentile"
    run = Run(True, 0, 0, {}, {"queue_wait_s": [0.1, 0.2, 0.3]}, {})
    assert getattr(readers, new[0]["reader"])(
        run, {}, **new[0]["args"]) == pytest.approx(0.28)


MIX = {"rate_rps": 4.5, "pattern_seed": 24, "lead_in_s": 8.0, "drain_s": 20.0,
       "prompt": {"lo": 16, "hi": 512, "levels": 16},
       "output": {"lo": 8, "hi": 64, "levels": 16}}


def test_every_seed_is_offered_the_same_arrivals():
    a = traffic.open_loop(MIX, 45.0)
    assert a == traffic.open_loop(MIX, 45.0)      # nothing but the mix decides
    sampled = [x for x in a if x.sampled]
    assert len(sampled) == 202                    # 20 beyond the p90
    assert len(a) == 36 + 202 + 90 and len({x.rid for x in a}) == len(a)
    assert [x.due for x in a] == sorted(x.due for x in a)
    assert all(0 <= x.due < 45.0 for x in sampled)
    # the lengths are the quantile grid, whatever the pattern's seed
    other = traffic.open_loop({**MIX, "pattern_seed": 7}, 45.0)
    pairs = lambda xs: sorted((x.prompt_len, x.new_tokens)    # noqa: E731
                              for x in xs if x.sampled)
    assert pairs(a) == pairs(other)
    assert [x.due for x in a] != [x.due for x in other]
    lengths = {x.prompt_len for x in a}
    assert lengths <= set(traffic.prompt_lengths(MIX)) and len(lengths) == 16
    assert 16 <= min(lengths) and max(lengths) <= 512
    outs = [x.new_tokens for x in sampled]
    assert 8 <= min(outs) and max(outs) <= 64 and 24 < sum(outs) / 202 < 30
    # the seed draws the tokens
    assert traffic.tokens(1, 0, 16, 50304) != traffic.tokens(2, 0, 16, 50304)


def test_backlog_passes_hold_the_same_multiset():
    mix = {"block": 64, "prompt": {"lo": 384, "hi": 960, "levels": 16},
           "output": {"lo": 8, "hi": 32, "levels": 8}}
    take = lambda seed: [next(g) for g in [traffic.backlog(mix, seed)]  # noqa
                         for _ in range(128)]
    a, b = take(1), take(2)
    key = lambda xs: sorted((x.prompt_len, x.new_tokens) for x in xs)  # noqa
    assert key(a[:64]) == key(a[64:]) == key(b[:64])
    assert [x.prompt_len for x in a] != [x.prompt_len for x in b]
    assert all(x.prompt_len + x.new_tokens < 1024 for x in a)
    assert traffic.tokens(5, 3, 9, 100) == traffic.tokens(5, 3, 9, 100)


def test_request_arithmetic_counts_from_the_due_time():
    A = traffic.Arrival
    arrivals = [A(0, 1.0, 16, 3, True),     # submitted late, by 0.5 s
                A(1, 2.0, 16, 3, True),     # fails: one token short
                A(2, 3.0, 16, 2, True),     # never finishes
                A(3, -1.0, 16, 2, False)]   # lead-in: not sampled
    out, failed = stats.request_stats(
        arrivals, submitted={0: 1.5, 1: 2.0, 2: 3.0, 3: -1.0},
        admitted={0: 1.6, 1: 2.1, 2: 3.1, 3: -0.9},
        token_times={0: [2.0, 2.2, 2.6], 1: [2.5, 2.7], 3: [0.0, 0.1]},
        finished={0: (2.6, "length"), 1: (2.7, "error"),
                  3: (0.1, "length")})
    assert out["ttft_s"] == [pytest.approx(1.0)]    # from due, not submit
    assert out["tpot_s"] == [pytest.approx(0.3)]
    assert out["queue_wait_s"] == [pytest.approx(0.6)]
    assert out["submit_late_s"] == [pytest.approx(0.5)]
    assert out["token_gap_s"] == [pytest.approx(0.2), pytest.approx(0.4)]
    assert [(f[0], f[1]) for f in failed] == [(1, "error"),
                                              (2, "unfinished")]
    assert stats.percentile([1, 2, 3, 4, 5], 90) == pytest.approx(4.6)
    assert stats.percentile([], 90) is None


def _trace():
    E = tracing.Events.of
    ops = E([("%fusion.1 = bf16[8,8]{1,0} fusion(bf16[8,8] %p)", 0, 40),
             ("%all-reduce.2 = f32[4]{0} all-reduce(f32[4] %g)", 40, 60),
             ("%k.3 = bf16[8]{0} custom-call(bf16[8] %x), "
              'custom_call_target="tpu_custom_call"', 70, 90),
             ("%fusion.4 = bf16[8,8]{1,0} fusion(bf16[8,8] %p)", 85, 95)])
    async_ops = E([("%all-reduce-start.5 = f32[4]{0} all-reduce-start()",
                    30, 66)])
    modules = E([("jit_step(1)", 0, 60), ("jit_step(1)", 70, 95),
                 ("jit_other(2)", 96, 99)])
    dev = tracing.DeviceTrace(modules, ops, async_ops)
    spans = [("train_step", 55, 68), ("wait_loss", 68, 100)]
    return tracing.Trace([dev], spans, (0, 100))


def test_trace_reduction_on_a_hand_built_trace():
    t = _trace()
    dev = t.devices[0]
    w = t.window
    assert tracing.busy_s(dev, w) * 1e9 == pytest.approx(85)   # 0-60, 70-95
    assert tracing.op_s(dev, "all-reduce", w) * 1e9 == pytest.approx(20)
    assert tracing.op_s(dev, "all-reduce", w, True) * 1e9 \
        == pytest.approx(36)                                  # 30-66
    # hidden under fusion.1 from 30 to 40; exposed 40-66
    assert tracing.exposed_s(dev, "all-reduce", w) * 1e9 == pytest.approx(26)
    assert tracing.op_s(dev, "tpu_custom_call", w) * 1e9 == pytest.approx(20)
    gaps = tracing.idle_gaps_by_span(dev, t.spans, w)
    assert gaps["train_step"] * 1e9 == pytest.approx(8)        # 60-68
    assert gaps["wait_loss"] * 1e9 == pytest.approx(7)         # 68-70, 95-100
    assert gaps[tracing.NO_SPAN] == pytest.approx(0)
    # a window cuts events at its edges
    assert tracing.busy_s(dev, (50, 80)) * 1e9 == pytest.approx(20)


def test_readers_on_the_hand_built_trace():
    t = _trace()
    run = Run(True, 2, 0, {}, {"decode_batch": [10, 20]},
              {"tokens_per_step": 100, "flops_per_token": 5e6, "chips": 1,
               "memory_peak_bytes": 4e9, "shapes": {}}, trace=t)
    peak = {"flops_per_s": 1e16, "bytes_per_s": 1e12, "hbm_bytes": 16e9}
    assert readers.idle_share(run, peak) == pytest.approx(15)
    assert readers.module_time_share(run, peak, "^jit_step") \
        == pytest.approx(85)
    assert readers.module_time_p50_ms(run, peak, "^jit_step") \
        == pytest.approx(42.5e-6)
    assert readers.op_time_share(run, peak, "tpu_custom_call", over="busy") \
        == pytest.approx(100 * 20 / 85)
    assert readers.op_exposed_share(run, peak, "all-reduce") \
        == pytest.approx(26)
    # two steps of 100 tokens in 100 ns, 5e6 operations a token
    assert readers.mfu(run, peak, "^jit_step") == pytest.approx(100.0)
    assert readers.memory_peak_share(run, peak) == pytest.approx(25)
    assert readers.sample_mean(run, peak, "decode_batch") == 15
    assert readers.sample_mean(run, peak, "absent") is None
    # the kernel of 70-90 ns made 2 calls x 2 steps, each of 1e6 operations
    # and 160 000 bytes: the bytes bound it
    run.facts["shapes"] = {"flash_bwd": {
        "batch": 1, "heads": 1, "seq": 10, "head_dim": 1000,
        "calls_per_step": 2}}
    assert readers.train_kernel_roofline(
        run, peak, "tpu_custom_call", "^jit_step", "flash_bwd") \
        == pytest.approx(100 * (640000 / 1e12) / 20e-9)
    run.facts.update(layers=2, decode_context_tokens=1000,
                     shapes={"paged_decode": {"heads": 4, "head_dim": 8}})
    # 2 x 4 x 8 x 2 bytes a position, 2000 positions, at 1e12 bytes/s
    assert readers.decode_kernel_roofline(
        run, peak, "tpu_custom_call", "paged_decode") \
        == pytest.approx(100 * (256000 / 1e12) / 20e-9)
    assert readers.decode_kernel_roofline(
        run, peak, "no such kernel", "paged_decode") is None
    run.trace = None
    assert readers.idle_share(run, peak) is None


def test_short_names_are_stable_across_instruction_numbers():
    a = tracing.short_name(
        "%copy.824 = bf16[4097,24,2,8,16,64]{5,4,3,2,1,0:T(8,128)(2,1)} "
        "copy(bf16[4097,24,2,8,16,64]{5,4,3,2,1,0} %get-tuple-element.7)")
    b = tracing.short_name(
        "%copy.9 = bf16[4097,24,2,8,16,64]{5,4,3,2,1,0:T(8,128)(2,1)} "
        "copy(bf16[4097,24,2,8,16,64]{5,4,3,2,1,0} %get-tuple-element.1)")
    assert a == b == "copy.copy_bf16_4097_24_2_8_16_64"
    k = tracing.short_name(
        "%jvp__.145 = (bf16[256,512,128]{2,1,0}, f32[256,512,1]{2,1,0}) "
        'custom-call(s32[256]{0} %c), custom_call_target="tpu_custom_call"')
    assert k == "mosaic.jvp___bf16_256_512_128"
    assert manifest.NAME.match(k) and len(tracing.short_name("x" * 99)) <= 64


def test_flop_and_roofline_arithmetic():
    n = 335_000_000
    per_token = flops.train_flops_per_token(n, 24, 1024, 512)
    assert per_token == 6 * n + 12 * 24 * 1024 * 512
    assert flops.train_flops_per_token(n, 24, 1024, 512, causal=True) \
        == 6 * n + 6 * 24 * 1024 * 512
    ops, nbytes = flops.flash_bwd(16, 16, 512, 64)
    assert ops == 10 * 16 * 16 * 512 * 512 * 64
    peak = {"flops_per_s": 197e12, "bytes_per_s": 819e9}
    share = flops.roofline_share(ops, nbytes, ops / 197e12 * 2, peak)
    assert share == pytest.approx(50.0)


def test_run_refuses_a_platform_that_is_not_a_tpu():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
         "--workload", "bert-large.pretrain-1chip", "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        env=env, capture_output=True, text=True, timeout=120)
    assert out.returncode != 0
    assert "runs on a TPU" in out.stderr
    assert not re.search(r'^\{"correct"', out.stdout, re.M)
