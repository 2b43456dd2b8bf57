"""The nemotron-3-nano-30b-a3b configuration's own cases, on the CPU: its
cut agrees with its sizes, its parameter count is the issue's, both cells
this PR added resolve by name, and the benchmark's copy of the plain
reference is the program's.

    python3 -m pytest benchmarks/tests -q
"""

import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks.harness import manifest, readers  # noqa: E402
from benchmarks.harness.job import load_module  # noqa: E402

NAME = "nemotron-3-nano-30b-a3b"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


@pytest.fixture(scope="module")
def cfg():
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           NAME + ".json")) as f:
        return json.load(f)


def parameter_count(c):
    """Parameters the share holds, from the file's numbers alone."""
    h = c["hidden_size"]
    d_inner = c["mamba_num_heads"] * c["mamba_head_dim"]
    conv = d_inner + 2 * c["n_groups"] * c["ssm_state_size"]
    mamba = (h * (d_inner + conv + c["mamba_num_heads"])     # in_proj
             + conv * (c["conv_kernel"] + 1)                 # conv, its bias
             + 3 * c["mamba_num_heads"] + d_inner            # dt, A, D, norm
             + d_inner * h)                                  # out_proj
    qkv = (c["num_attention_heads"] + 2 * c["num_key_value_heads"]) \
        * c["head_dim"]
    attention = h * qkv + c["num_attention_heads"] * c["head_dim"] * h
    published = c["published"]["n_routed_experts"]
    experts = (published * (h + 1)                           # router, bias
               + c["n_routed_experts"] * 2 * h * c["moe_intermediate_size"]
               + 2 * h * c["moe_shared_expert_intermediate_size"])
    per = {"M": mamba + h, "*": attention + h, "E": experts + h}
    return sum(per[s] for s in c["hybrid_override_pattern"]) \
        + 2 * c["vocab_size"] * h + h


def test_the_cut_agrees_with_the_sizes(cfg):
    pattern = cfg["hybrid_override_pattern"]
    assert cfg["num_hidden_layers"] == len(pattern) == 9
    assert cfg["published"]["hybrid_override_pattern"].startswith(pattern)
    assert (pattern.count("M"), pattern.count("E"),
            pattern.count("*")) == (4, 4, 1)
    lo, hi = cfg["held"]["experts"]
    assert hi - lo == cfg["n_routed_experts"] == 8
    assert cfg["published"]["n_routed_experts"] == 16 * 8     # experts 16-way
    assert cfg["published"]["vocab_size"] == 8 * cfg["vocab_size"]
    assert cfg["held"]["vocab_rows"] == [0, cfg["vocab_size_run"]]
    assert "16 chips" in cfg["deployment"] and "16-way" in cfg["deployment"] \
        and "8-way" in cfg["deployment"]
    assert sorted(cfg["reduced"]) == sorted(cfg["published"])
    # the guide's floors: a whole period, 8 experts, an eighth of the rows
    assert cfg["n_routed_experts"] >= 8 and cfg["num_hidden_layers"] >= 9


def test_every_width_is_the_catalogs(cfg):
    if not os.path.exists(CATALOG):
        pytest.skip("the catalog is not on this machine")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["source_url"] == cfg["source"])
    differs = {k for k, v in row["config"].items() if cfg.get(k) != v}
    assert differs == set(cfg["reduced"])


def test_the_share_holds_667_million_parameters(cfg):
    assert parameter_count(cfg) == pytest.approx(667e6, rel=0.01)
    # 16 bytes a parameter as the repo trains: 10.7 GB of the chip's 16
    assert 16 * parameter_count(cfg) / 1e9 == pytest.approx(10.7, abs=0.1)


def test_the_builder_builds_what_the_file_says(cfg):
    recipe = load_module(ROOT, cfg["builder"]["file"], "nemotron_recipe")
    argv = [a.format(global_batch=cfg["micro_batch"], seed=0)
            for a in cfg["builder"]["argv"]]
    args = recipe.parse_args(argv)
    m = recipe.model_config(args)
    assert args.seq_len == cfg["seq_len"] and args.opt_level == "O2"
    assert (m.layer_pattern, m.vocab_size, m.hidden_size) == (
        cfg["hybrid_override_pattern"], cfg["vocab_size_run"],
        cfg["hidden_size"])
    assert (m.num_attention_heads, m.num_kv_heads, m.head_dim) == (
        cfg["num_attention_heads"], cfg["num_key_value_heads"],
        cfg["head_dim"])
    assert (m.n_experts, m.moe_held, m.moe_top_k, m.moe_routed_scale) == (
        cfg["published"]["n_routed_experts"], tuple(cfg["held"]["experts"]),
        cfg["num_experts_per_tok"], cfg["routed_scaling_factor"])
    assert (m.ffn_hidden_size, m.moe_shared_ffn) == (
        cfg["moe_intermediate_size"],
        cfg["moe_shared_expert_intermediate_size"])
    assert (m.mamba_num_heads, m.mamba_head_dim, m.mamba_state_size,
            m.mamba_groups, m.mamba_conv_kernel, m.mamba_chunk_size) == (
        cfg["mamba_num_heads"], cfg["mamba_head_dim"], cfg["ssm_state_size"],
        cfg["n_groups"], cfg["conv_kernel"], cfg["chunk_size"])
    from apex_tpu.models import mamba2
    assert mamba2._DT_LIMITS == (cfg["time_step_min"], cfg["time_step_max"],
                                 cfg["time_step_floor"])
    assert mamba2._NORM_EPS == cfg["norm_eps"] == cfg["layer_norm_epsilon"]
    assert (m.norm, m.ffn_activation, m.bias, m.tie_head, m.moe_router,
            m.remat) == (
        "rmsnorm", cfg["mlp_hidden_act"], cfg["use_bias"],
        cfg["tie_word_embeddings"], "sigmoid", True)
    # the kernel shapes the roofline metric counts are the model's
    k = cfg["kernel_shapes"]["flash_bwd"]
    assert (k["batch"], k["heads"], k["seq"], k["head_dim"]) == (
        cfg["micro_batch"], m.num_attention_heads, cfg["seq_len"],
        m.head_dim)


@pytest.mark.parametrize("cell,chips,metrics", [
    (NAME + ".pretrain-1chip", 1,
     {"flash_bwd_h128_roofline.train", "mlp_time_share.train",
      "attention_time_share.train", "optimizer_time_share.train",
      "hbm_peak_share.train", "step_time_p50_ms.train"}),
    ("bert-large.pretrain-dp4", 4,
     {"allreduce_time_share.train", "allreduce_exposed_share.train",
      "mfu.train", "flash_bwd_roofline.train",
      "mlm_head_time_share.train"}),
])
def test_the_new_cells_resolve_by_name(cell, chips, metrics):
    c = manifest.Manifest(ROOT).cell(cell)
    assert c.chips == chips and c.traffic["job"] == "train"
    assert {m["name"] for m in c.end_to_end} == {"train_tokens_per_s",
                                                 "setup_s"}
    names = {m["name"] for m in c.per_layer}
    assert metrics <= names
    assert all(callable(getattr(readers, m["reader"])) for m in c.per_layer)
    if chips == 1:
        assert not names & {"mfu.train", "flash_bwd_roofline.train",
                            "mlm_head_time_share.train"}


def test_the_roofline_pattern_reads_the_backward_kernels_only(cfg):
    import re
    spec = json.load(open(os.path.join(
        ROOT, "benchmarks", "metrics", "flash_bwd_h128_roofline.train.json")))
    pattern = re.compile(spec["args"]["pattern"])
    tail = ' custom-call(s32[32]{0} %c), custom_call_target="tpu_custom_call"'
    lay = "{2,1,0:T(8,128)(2,1)}"
    dq = f"%attention.7 = bf16[32,8192,128]{lay}" + tail
    dkv = (f"%attention.8 = (bf16[32,8192,128]{lay}, "
           f"bf16[32,8192,128]{lay})") + tail
    fwd = (f"%attention.6 = (bf16[32,8192,128]{lay}, "
           "f32[32,8192,1]{2,1,0})") + tail
    assert pattern.search(dq) and pattern.search(dkv)
    assert not pattern.search(fwd)
    assert not pattern.search(dq.replace("%attention", "%jvp_attention_"))


def test_the_copied_reference_is_the_programs(cfg):
    import jax
    import jax.numpy as jnp
    from apex_tpu.models import reference as ours
    recipe = load_module(ROOT, cfg["builder"]["file"], "nemotron_recipe")
    copy = load_module(ROOT, cfg["reference"], "nemotron_reference_copy")
    from apex_tpu.models.gpt import GPTModel
    m = recipe.model_config(recipe.parse_args(
        ["--config", "tiny", "--opt-level", "O0", "--seq-len", "32"]))
    params = GPTModel(m).init_params(jax.random.PRNGKey(11))
    tokens = jax.random.randint(jax.random.PRNGKey(12), (2, 32), 0, 512)
    targets = jnp.roll(tokens, -1, axis=1)
    a = copy.nemotron_h_reference(params, tokens, m, targets)
    b = ours.nemotron_h_reference(params, tokens, m, targets)
    np.testing.assert_array_equal(np.asarray(a[0]), np.asarray(b[0]))
    assert float(a[1]) == float(b[1])
