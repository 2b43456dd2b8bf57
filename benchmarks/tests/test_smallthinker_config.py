"""The smallthinker-21ba3b-instruct configuration's own cases, on the CPU: its
cut agrees with its sizes, every width is the catalog's, its parameter count
is the cut's arithmetic, the builder builds what the file says, the cell
resolves by name with its metrics, each new metric's pattern reads what it
should and nothing else, the kernel counts are the shapes', the benchmark's
copy of the plain reference is the program's, and a rehearsal reads correct.

    python3 -m pytest benchmarks/tests -q
"""

import json
import os
import re
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks.harness import manifest, readers  # noqa: E402
from benchmarks.harness import trace as tracing  # noqa: E402
from benchmarks.harness.job import Run, load_module  # noqa: E402

NAME = "smallthinker-21ba3b-instruct"
CELL = NAME + ".pretrain-1chip"
# the catalog of architectures the other expert training cell is held to
CATALOG = load_module(ROOT, "benchmarks/tests/test_lfm2_config.py",
                      "lfm2_config_for_catalog").CATALOG
NEW = {"band_flash_time_share.train", "grouped_flash_bwd_roofline.train"}
JOINED = {"step_time_p50_ms.train", "mosaic_time_share.train",
          "device_idle_share.train", "hbm_peak_share.train",
          "optimizer_time_share.train", "attention_time_share.train",
          "mlp_time_share.train", "grouped_dot_time_share.train"}


@pytest.fixture(scope="module")
def cfg():
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           NAME + ".json")) as f:
        return json.load(f)


def parameter_count(c):
    """Parameters the share holds, from the file's numbers alone."""
    h, d = c["hidden_size"], c["head_dim"]
    q, kv = c["num_attention_heads"] * d, c["num_key_value_heads"] * d
    attention = h * (q + 2 * kv) + q * h + h
    experts = (c["published"]["moe_num_primary_experts"] * h
               + c["moe_num_primary_experts"] * 3 * h
               * c["moe_ffn_hidden_size"] + h)
    return c["num_hidden_layers"] * (attention + experts) \
        + 2 * c["vocab_size"] * h + h


def test_the_cut_agrees_with_the_sizes(cfg):
    pub = cfg["published"]
    n = cfg["num_hidden_layers"]
    assert n == 4 and pub["num_hidden_layers"] == 52
    # one whole period of both layouts, as published
    for key in ("sliding_window_layout", "rope_layout"):
        assert cfg[key] == pub[key][:n] == [0, 1, 1, 1]
        assert pub[key] == pub[key][:4] * 13
    lo, hi = cfg["held"]["experts"]
    assert hi - lo == cfg["moe_num_primary_experts"] == 16
    assert pub["moe_num_primary_experts"] == 4 * 16           # experts 4-way
    assert pub["vocab_size"] == 4 * cfg["vocab_size"] == 151936
    assert cfg["held"]["vocab_rows"] == [0, cfg["vocab_size_run"]] \
        == [0, 37984]
    assert cfg["held"]["layer_pattern"] == "*E" * n
    assert "4 chips" in cfg["deployment"] and "48 layers" in cfg["deployment"]
    assert sorted(cfg["reduced"]) == sorted(pub)
    # the guide's floors: a whole period, four layers, 8 experts, an eighth
    # of the rows; the model's own context
    assert n >= 4 and cfg["moe_num_primary_experts"] >= 8
    assert 8 * cfg["vocab_size"] >= pub["vocab_size"]
    assert cfg["seq_len"] == cfg["max_position_embeddings"] == 16384
    assert cfg["micro_batch"] == 1 \
        and cfg["assumed"]["micro_batch"].startswith("1: cut (a)")
    assert {"router_input", "initializer", "data", "optimizer",
            "precision", "head_padding", "micro_batch"} <= set(cfg["assumed"])


def test_every_width_is_the_catalogs(cfg):
    if not os.path.exists(CATALOG):
        pytest.skip("the catalog is not on this machine")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["source_url"] == cfg["source"])
    differs = {k for k, v in row["config"].items() if cfg.get(k) != v}
    assert differs == set(cfg["reduced"])
    assert {k: row["config"][k] for k in cfg["reduced"]} == cfg["published"]
    assert not any(k.endswith(("_size", "_dim", "_rank"))
                   and k != "vocab_size" for k in cfg["reduced"])


def test_the_share_holds_657_million_parameters(cfg):
    assert parameter_count(cfg) == 656_529_920
    # 16 bytes a parameter as the repo trains: 10.5 GB of the chip's 16
    assert 16 * parameter_count(cfg) / 1e9 == pytest.approx(10.50, abs=0.01)


def test_the_builder_builds_what_the_file_says(cfg):
    recipe = load_module(ROOT, cfg["builder"]["file"], "smallthinker_recipe")
    argv = [a.format(global_batch=cfg["micro_batch"], seed=0)
            for a in cfg["builder"]["argv"]]
    args = recipe.parse_args(argv)
    m = recipe.model_config(args)
    assert args.seq_len == cfg["seq_len"] and args.opt_level == "O2"
    assert args.batch_size == cfg["micro_batch"] and args.lr == 1e-6
    assert (m.layer_pattern, m.vocab_size, m.hidden_size) == (
        cfg["held"]["layer_pattern"], cfg["vocab_size_run"],
        cfg["hidden_size"])
    assert (m.num_attention_heads, m.num_kv_heads, m.head_dim) == (
        cfg["num_attention_heads"], cfg["num_key_value_heads"],
        cfg["head_dim"])
    assert (m.n_experts, m.moe_held, m.moe_top_k, m.ffn_hidden_size) == (
        cfg["published"]["moe_num_primary_experts"],
        tuple(cfg["held"]["experts"]),
        cfg["moe_num_active_primary_experts"], cfg["moe_ffn_hidden_size"])
    assert m.attention_types == tuple(
        "sliding" if s else "full" for s in cfg["sliding_window_layout"])
    assert m.rotary_layers == tuple(cfg["rope_layout"])
    assert (m.sliding_window, m.rope_base, m.norm_eps) == (
        cfg["sliding_window_size"], cfg["rope_theta"], cfg["rms_norm_eps"])
    assert (m.norm, m.ffn_activation, m.bias, m.tie_head, m.moe_router,
            m.moe_router_bias, m.moe_route_before_attention, m.remat) == (
        "rmsnorm", "reglu", False, cfg["tie_word_embeddings"],
        "softmax_sorted", False, True, True)
    assert cfg["moe_primary_router_apply_softmax"] and cfg["norm_topk_prob"]
    # the kernel shapes the roofline metric counts are the full layer's
    k = cfg["kernel_shapes"]["flash_bwd"]
    assert (k["batch"], k["heads"], k["seq"], k["head_dim"], k["causal"],
            k["calls_per_step"]) == (
        cfg["micro_batch"], m.num_attention_heads, cfg["seq_len"],
        m.head_dim, True, m.attention_types.count("full"))
    assert cfg["flops"] == {"layers": 4, "hidden": 2560, "seq": 16384,
                            "causal": True}
    # the rehearsal builds the same pattern at the CPU's size
    tiny = recipe.model_config(recipe.parse_args(
        [a.format(global_batch=1, seed=0)
         for a in cfg["rehearsal"]["builder"]["argv"]]))
    assert tiny.layer_pattern == m.layer_pattern \
        and tiny.vocab_size == cfg["rehearsal"]["vocab_size_run"]


def test_the_cell_resolves_by_name_with_its_metrics():
    man = manifest.Manifest(ROOT)
    c = man.cell(CELL)
    assert c.chips == 1 and c.traffic["job"] == "train"
    assert c.config["name"] == NAME
    assert {m["name"] for m in c.end_to_end} == {"train_tokens_per_s",
                                                 "setup_s"}
    assert {m["name"] for m in c.per_layer} == NEW | JOINED
    assert all(callable(getattr(readers, m["reader"])) for m in c.per_layer)
    mine = [m for m in man.data["per_layer"] if m["name"] in NEW]
    assert len(mine) == 2
    for m in mine:
        assert m["workloads"] == [CELL] and m["unit"] == "%"
        assert m["layer"] == "kernels" and m["source"] == "device_trace"
        assert m["moves"] == "train_tokens_per_s"
    mfu = next(m for m in man.data["per_layer"] if m["name"] == "mfu.train")
    assert CELL not in mfu["workloads"]       # its count is a dense model's
    cell = next(w for w in man.data["workloads"] if w["name"] == CELL)
    entry = next(e for e in man.data["configs"] if e["name"] == NAME)
    assert len(cell["why"]) <= 200 and len(entry["why"]) <= 200
    assert sorted(entry["reduced"]) == sorted(c.config["reduced"])


def _metric(name):
    with open(os.path.join(ROOT, "benchmarks", "metrics",
                           name + ".json")) as f:
        return json.load(f)


LAY = "{2,1,0:T(8,128)(2,1)}"
TAIL = ' custom-call(s32[2]{0} %c), custom_call_target="tpu_custom_call"'
ROWS = f"bf16[1,16384,3584]{LAY}"
STATS = "f32[1,28,16384,1]{3,2,1,0:T(8,128)}"
KV = f"bf16[1,16384,512]{LAY}"
OPS = {
    "band_fwd": f"%band_flash_fwd.6 = ({ROWS}, {STATS})" + TAIL,
    "band_dq": f"%band_flash_dq.3 = {ROWS}" + TAIL,
    "band_dkv": f"%band_flash_dkv.3 = ({KV}, {KV})" + TAIL,
    "full_fwd": f"%grouped_flash_fwd.2 = ({ROWS}, {STATS})" + TAIL,
    "full_dq": f"%grouped_flash_dq.1 = {ROWS}" + TAIL,
    "full_dkv": f"%grouped_flash_dkv.1 = ({KV}, {KV})" + TAIL,
    "window_prefill": f"%window_flash.2 = {ROWS}" + TAIL,
    "rows_bwd": f"%flash_rows_bwd.5 = bf16[2,8192,2048]{LAY}" + TAIL,
    "norm": f"%attention.8 = bf16[16416,2560]{LAY}" + TAIL,
    "dot": "%dot-general.2 = f32[8,8]{1,0} dot(%a, %b)",
}


def test_each_new_pattern_reads_its_kernels_and_nothing_else():
    band = re.compile(_metric("band_flash_time_share.train")["args"]
                      ["pattern"])
    full = re.compile(_metric("grouped_flash_bwd_roofline.train")["args"]
                      ["pattern"])
    assert {k for k, op in OPS.items() if band.search(op)} \
        == {"band_fwd", "band_dq", "band_dkv"}
    assert {k for k, op in OPS.items() if full.search(op)} \
        == {"full_dq", "full_dkv"}


def test_the_new_readers_on_a_hand_built_trace(cfg):
    """Ten steps of 100 ms in a one-second window, 90 busy: the window
    layers' three calls 30 ms a step, the full layer's dQ and dK/dV 20 and
    30 ms a step; the shares come out of the arithmetic."""
    names, which, start, end = list(OPS.values()), [], [], []
    order = list(OPS)
    spans = {"band_fwd": 8, "band_dq": 10, "band_dkv": 12, "full_fwd": 6,
             "full_dq": 20, "full_dkv": 30, "norm": 4}
    for step in range(10):
        t = step * 100_000_000
        for key, ms in spans.items():
            which.append(order.index(key))
            start.append(t)
            end.append(t + ms * 1_000_000)
            t = end[-1]
    ev = lambda n, w, s, e: tracing.Events(                    # noqa: E731
        n, np.asarray(w), np.asarray(s, np.int64), np.asarray(e, np.int64))
    modules = ev(["jit_step(1)"], [0] * 10,
                 [i * 100_000_000 for i in range(10)],
                 [i * 100_000_000 + 95_000_000 for i in range(10)])
    trace = tracing.Trace(
        devices=[tracing.DeviceTrace(modules, ev(names, which, start, end),
                                     ev([], [], [], []))],
        spans={}, window=(0, 1_000_000_000))
    run = Run(correct=True, attempted=10, failed=0, end_to_end={},
              samples={}, trace=trace,
              facts={"shapes": cfg["kernel_shapes"]})
    peak = {"flops_per_s": 197e12, "bytes_per_s": 819e9}
    b = _metric("band_flash_time_share.train")
    assert getattr(readers, b["reader"])(run, peak, **b["args"]) \
        == pytest.approx(100 * 30 / 90)
    f = _metric("grouped_flash_bwd_roofline.train")
    ops = 5 * 2 * 28 * 16384 * 16384 * 128 // 2            # 4.81 TFLOP
    assert ops / 1e12 == pytest.approx(4.81, abs=0.01)
    want = 100 * (ops * 10 / 197e12) / 0.5
    assert getattr(readers, f["reader"])(run, peak, **f["args"]) \
        == pytest.approx(want)
    assert want < 100


def test_the_kernel_counts_follow_the_band(cfg):
    counts = load_module(ROOT, f"benchmarks/configs/{NAME}.flops.py",
                         "smallthinker_flops")
    s, w = cfg["seq_len"], cfg["sliding_window_size"]
    assert counts.visible_pairs(s) == s * (s + 1) // 2 == 134_225_920
    assert counts.visible_pairs(s, w) == 58_722_304
    assert counts.visible_pairs(16, 4) == sum(min(i + 1, 4) for i in range(16))
    assert counts.visible_pairs(s, w) / counts.visible_pairs(s) \
        == pytest.approx(0.4375, abs=1e-3)
    ops, nbytes = counts.band_flash_bwd(28, 4, 128, s)
    ops_f, _ = counts.band_flash_fwd(28, 4, 128, s)
    assert ops == 2.5 * ops_f and nbytes > 0
    # the harness's causal count halves the square; the band's counts the
    # diagonal too
    from benchmarks.harness import flops
    assert flops.flash_bwd(1, 28, s, 128, causal=True)[0] \
        == pytest.approx(ops, rel=1e-4)


def test_the_copied_reference_is_the_programs(cfg):
    import jax
    import jax.numpy as jnp
    from apex_tpu.models import reference as ours
    from apex_tpu.models.gpt import GPTModel
    recipe = load_module(ROOT, cfg["builder"]["file"], "smallthinker_recipe")
    copy = load_module(ROOT, cfg["reference"], "smallthinker_reference_copy")
    m = recipe.model_config(recipe.parse_args(
        ["--config", "tiny", "--opt-level", "O0", "--seq-len", "80"]))
    params = GPTModel(m).init_params(jax.random.PRNGKey(11))
    tokens = jax.random.randint(jax.random.PRNGKey(12), (1, 80), 0, 512)
    targets = jnp.roll(tokens, -1, axis=1)
    a = copy.smallthinker_reference(params, tokens, m, targets)
    b = ours.smallthinker_reference(params, tokens, m, targets)
    np.testing.assert_array_equal(np.asarray(a[0]), np.asarray(b[0]))
    assert float(a[1]) == float(b[1])
    assert copy.smallthinker_kinds(m) == ours.smallthinker_kinds(m) == (
        ("*", None, 0), ("E",), ("*", 64, 1), ("E",), ("*", 64, 1), ("E",),
        ("*", 64, 1), ("E",))


def test_a_rehearsal_of_the_cell_reads_correct():
    """The cell's own run on the CPU at the rehearsal's tiny sizes: the
    recipe's step through the train job, first loss within 5 % of ln(512),
    no metric under a device metric's name."""
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
         "--workload", CELL, "--seed", "2147483999", "--seconds", "3",
         "--rehearsal"], capture_output=True, text=True, timeout=600,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["correct"] and line["failed"] == 0 and not line["metrics"]
    assert line["device"]["platform"] == "cpu" and line["attempted"] > 0
