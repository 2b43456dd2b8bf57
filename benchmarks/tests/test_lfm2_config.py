"""The lfm2-24b-a2b configuration's own cases, on the CPU: its cut agrees
with its sizes, every width is the catalog's, its parameter count is the
issue's, the builder builds what the file says, the cell resolves by name
with its metrics, each new metric's pattern reads what it should and nothing
else, and the benchmark's copy of the plain reference is the program's.

    python3 -m pytest benchmarks/tests -q
"""

import json
import os
import re
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks.harness import manifest, readers  # noqa: E402
from benchmarks.harness import trace as tracing  # noqa: E402
from benchmarks.harness.job import Run, load_module  # noqa: E402

NAME = "lfm2-24b-a2b"
CELL = NAME + ".pretrain-1chip"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
SYMBOLS = {"conv": "C", "full_attention": "*"}


@pytest.fixture(scope="module")
def cfg():
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           NAME + ".json")) as f:
        return json.load(f)


def pattern_of(c):
    """The one-mixer pattern of the held layers: an operator symbol and a
    feed-forward symbol a published layer, the leading ones dense."""
    return "".join(
        SYMBOLS[kind] + ("D" if i < c["num_dense_layers"] else "E")
        for i, kind in enumerate(c["layer_types"]))


def parameter_count(c):
    """Parameters the share holds, from the file's numbers alone."""
    h = c["hidden_size"]
    head_dim = h // c["num_attention_heads"]
    conv = 3 * h * h + h * c["conv_L_cache"] + h * h
    kv = c["num_key_value_heads"] * head_dim
    attention = h * (h + 2 * kv) + h * h + 2 * head_dim      # and QK-norm
    dense = 3 * h * c["intermediate_size"]
    published = c["published"]["num_experts"]
    experts = (published * (h + 1)                           # router, bias
               + c["num_experts"] * 3 * h * c["moe_intermediate_size"])
    per = {"C": conv + h, "*": attention + h, "D": dense + h,
           "E": experts + h}
    return sum(per[s] for s in pattern_of(c)) + c["vocab_size"] * h + h


def test_the_cut_agrees_with_the_sizes(cfg):
    held, published = cfg["layer_types"], cfg["published"]["layer_types"]
    assert cfg["num_hidden_layers"] == len(held) == 7
    # published layer 1, then layers 2-7: a whole period of 4 and two more
    assert held == published[1:8] and cfg["num_dense_layers"] == 1
    assert published[:2] == ["conv", "conv"] \
        and cfg["published"]["num_dense_layers"] == 2
    assert published[2:6] == ["full_attention", "conv", "conv", "conv"]
    assert pattern_of(cfg) == cfg["held"]["layer_pattern"] \
        == "CD*ECECECE*ECE"
    lo, hi = cfg["held"]["experts"]
    assert hi - lo == cfg["num_experts"] == 8
    assert cfg["published"]["num_experts"] == 8 * 8          # experts 8-way
    assert cfg["published"]["vocab_size"] == 8 * cfg["vocab_size"]
    assert cfg["held"]["vocab_rows"] == [0, cfg["vocab_size_run"]]
    assert "8 chips" in cfg["deployment"] and "8-way" in cfg["deployment"]
    assert sorted(cfg["reduced"]) == sorted(cfg["published"])
    # the guide's floors: a whole period and four layers after the dense
    # ones, 8 experts, an eighth of the rows
    assert len(held) - cfg["num_dense_layers"] >= 4 + 2
    assert cfg["num_experts"] >= 8
    assert cfg["micro_batch"] in (1, 2) \
        and cfg["assumed"]["micro_batch"].startswith(str(cfg["micro_batch"]))


def test_every_width_is_the_catalogs(cfg):
    if not os.path.exists(CATALOG):
        pytest.skip("the catalog is not on this machine")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["source_url"] == cfg["source"])
    differs = {k for k, v in row["config"].items() if cfg.get(k) != v}
    assert differs == set(cfg["reduced"])
    assert not any(k.endswith(("_size", "_dim", "_rank", "per_tok"))
                   and k != "vocab_size" for k in cfg["reduced"])


def test_the_share_holds_648_million_parameters(cfg):
    assert parameter_count(cfg) == 647_819_904
    # 16 bytes a parameter as the repo trains: 10.4 GB of the chip's 16
    assert 16 * parameter_count(cfg) / 1e9 == pytest.approx(10.4, abs=0.05)


def test_the_builder_builds_what_the_file_says(cfg):
    recipe = load_module(ROOT, cfg["builder"]["file"], "lfm2_recipe")
    argv = [a.format(global_batch=cfg["micro_batch"], seed=0)
            for a in cfg["builder"]["argv"]]
    args = recipe.parse_args(argv)
    m = recipe.model_config(args)
    assert args.seq_len == cfg["seq_len"] and args.opt_level == "O2"
    assert args.batch_size == cfg["micro_batch"] and args.lr == 1e-6
    assert (m.layer_pattern, m.vocab_size, m.hidden_size) == (
        pattern_of(cfg), cfg["vocab_size_run"], cfg["hidden_size"])
    assert (m.num_attention_heads, m.num_kv_heads, m.head_dim) == (
        cfg["num_attention_heads"], cfg["num_key_value_heads"],
        cfg["hidden_size"] // cfg["num_attention_heads"])
    assert (m.n_experts, m.moe_held, m.moe_top_k, m.moe_routed_scale) == (
        cfg["published"]["num_experts"], tuple(cfg["held"]["experts"]),
        cfg["num_experts_per_tok"], cfg["routed_scaling_factor"])
    assert (m.ffn_hidden_size, m.dense_ffn_hidden_size, m.moe_shared_ffn) == (
        cfg["moe_intermediate_size"], cfg["intermediate_size"], 0)
    assert (m.short_conv_kernel, m.rope_base, m.rotary, m.qk_norm) == (
        cfg["conv_L_cache"], cfg["rope_parameters"]["rope_theta"], True, True)
    assert (m.norm, m.ffn_activation, m.bias, m.tie_head, m.moe_router,
            m.remat) == ("rmsnorm", "swiglu", cfg["conv_bias"], True,
                         "sigmoid", True)
    from apex_tpu.models import gpt, mamba2
    assert gpt._HEAD_NORM_EPS == mamba2._NORM_EPS == cfg["norm_eps"]
    assert cfg["norm_topk_prob"] and cfg["use_expert_bias"]
    # the kernel shapes the roofline metric counts are the model's
    k = cfg["kernel_shapes"]["flash_bwd"]
    assert (k["batch"], k["heads"], k["seq"], k["head_dim"], k["causal"],
            k["calls_per_step"]) == (
        cfg["micro_batch"], m.num_attention_heads, cfg["seq_len"],
        m.head_dim, True, m.layer_pattern.count("*"))
    assert cfg["flops"]["layers"] == m.layer_pattern.count("*")
    # the rehearsal builds the same pattern at the CPU's size
    tiny = recipe.model_config(recipe.parse_args(
        [a.format(global_batch=2, seed=0)
         for a in cfg["rehearsal"]["builder"]["argv"]]))
    assert tiny.layer_pattern == m.layer_pattern \
        and tiny.vocab_size == cfg["rehearsal"]["vocab_size_run"]


NEW = {"grouped_dot_time_share.train", "flash_rows_bwd_causal_roofline.train"}


def test_the_cell_resolves_by_name_with_its_metrics():
    man = manifest.Manifest(ROOT)
    c = man.cell(CELL)
    assert c.chips == 1 and c.traffic["job"] == "train"
    assert c.config["name"] == NAME
    assert {m["name"] for m in c.end_to_end} == {"train_tokens_per_s",
                                                 "setup_s"}
    names = {m["name"] for m in c.per_layer}
    assert names == NEW | {
        "step_time_p50_ms.train", "mosaic_time_share.train",
        "device_idle_share.train", "hbm_peak_share.train",
        "optimizer_time_share.train", "attention_time_share.train",
        "mlp_time_share.train"}
    assert all(callable(getattr(readers, m["reader"])) for m in c.per_layer)
    # the new metrics are this cell's alone, and the manifest's last two
    for m in man.data["per_layer"][-2:]:
        assert m["name"] in NEW and m["workloads"] == [CELL]
        assert m["layer"] == "kernels" and m["unit"] == "%"
    assert man.data["workloads"][-1]["name"] == CELL
    assert man.data["configs"][-1]["name"] == NAME
    assert sorted(man.data["configs"][-1]["reduced"]) \
        == sorted(c.config["reduced"])


def _metric(name):
    with open(os.path.join(ROOT, "benchmarks", "metrics",
                           name + ".json")) as f:
        return json.load(f)


LAY = "{2,1,0:T(8,128)(2,1)}"
TAIL = ' custom-call(s32[2]{0} %c), custom_call_target="tpu_custom_call"'
OPS = {
    "gate_up": f"%ragged-dot-none.3 = f32[8192,3072]{LAY}" + TAIL,
    "down": f"%ragged-dot-none.4 = f32[8192,2048]{LAY}" + TAIL,
    "dw": f"%ragged-dot-none.9 = f32[8,2048,3072]{LAY}" + TAIL,
    "meta": "%ragged-dot-metadata.2 = (s32[9]{0:T(128)}, s32[39]{0:T(128)}, "
            "s32[39]{0:T(128)}, s32[1]{0:T(128)})" + TAIL,
    "dq": f"%flash_rows_bwd.5 = bf16[2,8192,2048]{LAY}" + TAIL,
    "dkv": (f"%flash_rows_bwd.6 = (bf16[2,8192,2048]{LAY}, "
            f"bf16[2,8192,2048]{LAY})") + TAIL,
    "fwd": (f"%flash_rows_fwd.2 = (bf16[2,8192,2048]{LAY}, "
            "f32[64,8192,1]{2,1,0})") + TAIL,
    "bert_dq": f"%flash_rows_bwd.7 = bf16[16,512,1024]{LAY}" + TAIL,
    "h128": f"%attention.8 = bf16[32,8192,128]{LAY}" + TAIL,
    "lm_head": f"%lm_head.1 = f32[16384,1]{LAY}" + TAIL,
    "dense": "%fusion.7 = bf16[16384,23552]{1,0} fusion(%p), kind=kOutput",
    "dot": "%dot-general.2 = f32[8,8]{1,0} dot(%a, %b)",
}


def test_each_new_pattern_reads_its_kernels_and_nothing_else():
    grouped = re.compile(
        _metric("grouped_dot_time_share.train")["args"]["pattern"])
    flash = re.compile(
        _metric("flash_rows_bwd_causal_roofline.train")["args"]["pattern"])
    assert {k for k, op in OPS.items() if grouped.search(op)} \
        == {"gate_up", "down", "dw", "meta"}      # and their group layout
    assert {k for k, op in OPS.items() if flash.search(op)} == {"dq", "dkv"}


def test_the_new_readers_on_a_hand_built_trace(cfg):
    """Ten steps of 100 ms in a one-second window: the grouped products 30 ms
    a step, the two backward kernels 8 ms and 12 ms a step per attention
    layer; the shares come out of the arithmetic."""
    names, which, start, end = list(OPS.values()), [], [], []
    order = list(OPS)
    spans = {"gate_up": 12, "down": 8, "dw": 10, "dq": 16, "dkv": 24,
             "fwd": 10, "dense": 10}          # ms a step, 90 busy of 100
    for step in range(10):
        t = step * 100_000_000
        for key, ms in spans.items():
            which.append(order.index(key))
            start.append(t)
            end.append(t + ms * 1_000_000)
            t = end[-1]
    ev = lambda n, w, s, e: tracing.Events(                    # noqa: E731
        n, np.asarray(w), np.asarray(s, np.int64), np.asarray(e, np.int64))
    none = ev([], [], [], [])
    modules = ev(["jit_step(1)"], [0] * 10,
                 [i * 100_000_000 for i in range(10)],
                 [i * 100_000_000 + 95_000_000 for i in range(10)])
    trace = tracing.Trace(
        devices=[tracing.DeviceTrace(modules, ev(names, which, start, end),
                                     none)],
        spans={}, window=(0, 1_000_000_000))
    run = Run(correct=True, attempted=10, failed=0, end_to_end={},
              samples={}, trace=trace,
              facts={"shapes": cfg["kernel_shapes"]})
    peak = {"flops_per_s": 197e12, "bytes_per_s": 819e9}
    g = _metric("grouped_dot_time_share.train")
    assert getattr(readers, g["reader"])(run, peak, **g["args"]) \
        == pytest.approx(100 * 30 / 90)
    f = _metric("flash_rows_bwd_causal_roofline.train")
    k = cfg["kernel_shapes"]["flash_bwd"]
    ops = 5 * 2 * k["batch"] * 32 * 8192 * 8192 * 64 // 2
    want = 100 * (ops * k["calls_per_step"] * 10 / 197e12) / 0.4
    assert getattr(readers, f["reader"])(run, peak, **f["args"]) \
        == pytest.approx(want)
    assert want < 100


def test_the_copied_reference_is_the_programs(cfg):
    import jax
    import jax.numpy as jnp
    from apex_tpu.models import reference as ours
    recipe = load_module(ROOT, cfg["builder"]["file"], "lfm2_recipe")
    copy = load_module(ROOT, cfg["reference"], "lfm2_reference_copy")
    from apex_tpu.models.gpt import GPTModel
    m = recipe.model_config(recipe.parse_args(
        ["--config", "tiny", "--opt-level", "O0", "--seq-len", "32"]))
    params = GPTModel(m).init_params(jax.random.PRNGKey(11))
    tokens = jax.random.randint(jax.random.PRNGKey(12), (2, 32), 0, 512)
    targets = jnp.roll(tokens, -1, axis=1)
    a = copy.lfm2_reference(params, tokens, m, targets)
    b = ours.lfm2_reference(params, tokens, m, targets)
    np.testing.assert_array_equal(np.asarray(a[0]), np.asarray(b[0]))
    assert float(a[1]) == float(b[1])
    assert sorted(copy.LFM2_MIXERS) == sorted(ours.LFM2_MIXERS)
