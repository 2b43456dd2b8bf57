"""The glm-5.2 configuration's own cases, on the CPU: its cut agrees with
its sizes, every number of the catalog's row is in the file or named as
reduced, its parameter count is the built model's, the mix offers the
issue's lengths, the cell resolves by name with its metrics, the reference
runs under one ``jax.jit`` at the rehearsal's size, and a rehearsal of the
whole cell reads ``correct``.

    python3 -m pytest benchmarks/tests -q
"""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks.harness import manifest, readers, traffic  # noqa: E402
from benchmarks.harness.job import load_module  # noqa: E402

NAME = "glm-5.2"
CELL = NAME + ".longdoc-steady"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NEW = {"attention_time_share.ttft", "mlp_time_share.ttft",
       "sparse_attention_time_share.ttft", "indexer_time_share.ttft",
       "grouped_dot_time_share.tpot"}
JOINED = {"submit_late_p95_ms.ttft", "queue_wait_p50_s.ttft",
          "decode_batch_mean.tpot", "token_gap_p95_s.tpot",
          "prefill_time_share.ttft", "decode_time_share.tpot",
          "kv_write_time_share.ttft", "mosaic_time_share.tpot",
          "device_idle_share.tpot", "hbm_peak_share.tpot",
          "host_work_share.tpot", "sample_host_share.tpot",
          "admit_host_share.ttft", "admit_request_p50_ms.ttft"}


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           NAME + ".json")) as f:
        return json.load(f)


def parameter_count(c):
    """Parameters the share holds, from the file's numbers alone."""
    h, heads = c["hidden_size"], c["num_attention_heads"]
    attention = (h * c["q_lora_rank"] + c["q_lora_rank"]
                 + c["q_lora_rank"] * heads * c["qk_head_dim"]
                 + h * (c["kv_lora_rank"] + c["qk_rope_head_dim"])
                 + c["kv_lora_rank"]
                 + c["kv_lora_rank"] * heads * (c["qk_nope_head_dim"]
                                                + c["v_head_dim"])
                 + heads * c["v_head_dim"] * h)
    indexer = (c["q_lora_rank"] * c["index_n_heads"] * c["index_head_dim"]
               + h * c["index_head_dim"] + 2 * c["index_head_dim"]
               + h * c["index_n_heads"])
    dense = 3 * h * c["intermediate_size"]
    expert = 3 * h * c["moe_intermediate_size"]
    published = c["published"]["n_routed_experts"]
    sparse = (published * (h + 1)                       # router and its bias
              + (c["n_routed_experts"] + c["n_shared_experts"]) * expert)
    total = 2 * c["vocab_size"] * h + h                 # untied, final norm
    for ffn, kind in zip(c["mlp_layer_types"], c["indexer_types"]):
        total += attention + h + (indexer if kind == "full" else 0)
        total += (dense if ffn == "dense" else sparse) + h
    return total


def test_the_cut_agrees_with_the_sizes(config):
    c, pub = config, config["published"]
    assert c["num_hidden_layers"] == len(c["mlp_layer_types"]) \
        == len(c["indexer_types"]) == 5
    # published layers 2-6: the last dense layer and one whole period
    assert c["mlp_layer_types"] == pub["mlp_layer_types"][2:7]
    assert c["indexer_types"] == pub["indexer_types"][2:7] \
        == ["full", "shared", "shared", "shared", "full"]
    assert pub["mlp_layer_types"][:3] == ["dense"] * 3 \
        and c["first_k_dense_replace"] == 3
    assert pub["indexer_types"][6:10] == ["full"] + ["shared"] * 3
    assert len(pub["indexer_types"]) == len(pub["mlp_layer_types"]) \
        == pub["num_hidden_layers"] == 78
    assert c["held"]["layer_pattern"] == "".join(
        "*" + ("D" if f == "dense" else "E") for f in c["mlp_layer_types"])
    lo, hi = c["held"]["experts"]
    assert hi - lo == c["n_routed_experts"] == 16
    assert pub["n_routed_experts"] == 16 * 16           # experts 16-way
    assert pub["vocab_size"] == 8 * c["vocab_size"]     # vocabulary 8-way
    assert c["held"]["vocab_rows"] == [0, c["vocab_size"]]
    assert "16 chips share each layer" in c["deployment"]
    assert sorted(c["reduced"]) == sorted(pub)
    assert c["num_nextn_predict_layers"] == 0 \
        and "multi_token_prediction" in c["assumed"]
    # the guide's floors: a whole period and four layers after the dense
    # one, at least 8 experts, at least an eighth of the vocabulary
    assert c["mlp_layer_types"].count("sparse") >= 4
    assert c["n_routed_experts"] >= 8


def test_every_number_is_the_catalogs_or_named_as_reduced(config):
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "GLM-5.2")
    assert config["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in config["reduced"]:
            assert config["published"][key] == value
            assert config[key] != value
        else:
            assert config[key] == value, key
    widths = ("hidden_size", "intermediate_size", "_dim", "_rank",
              "per_tok", "heads")
    assert not [k for k in config["reduced"] if k.endswith(widths)]
    entry = next(e for e in manifest.Manifest(ROOT).data["configs"]
                 if e["name"] == NAME)
    assert entry["reduced"] == config["reduced"]
    assert entry["source"] == config["source"]
    for key in ("indexer_storage", "indexer_conventions", "rotary",
                "initializer", "router_bias", "precision", "engine"):
        assert key in config["assumed"]


def test_the_model_group_is_the_files_numbers(config):
    c, m = config, config["model"]
    assert m["layer_pattern"] == c["held"]["layer_pattern"]
    assert m["indexer_types"] == c["indexer_types"]
    for ours, theirs in (
            ("hidden_size", "hidden_size"), ("vocab_size", "vocab_size"),
            ("num_attention_heads", "num_attention_heads"),
            ("max_seq_len", "max_position_embeddings"),
            ("ffn_hidden_size", "moe_intermediate_size"),
            ("dense_ffn_hidden_size", "intermediate_size"),
            ("moe_top_k", "num_experts_per_tok"),
            ("moe_routed_scale", "routed_scaling_factor"),
            ("kv_lora_rank", "kv_lora_rank"), ("q_lora_rank", "q_lora_rank"),
            ("qk_nope_head_dim", "qk_nope_head_dim"),
            ("qk_rope_head_dim", "qk_rope_head_dim"),
            ("v_head_dim", "v_head_dim"), ("index_topk", "index_topk"),
            ("index_n_heads", "index_n_heads"),
            ("index_head_dim", "index_head_dim")):
        assert m[ours] == c[theirs], ours
    assert m["n_experts"] == c["published"]["n_routed_experts"]
    assert m["moe_held"] == c["held"]["experts"]
    assert m["moe_shared_ffn"] == c["n_shared_experts"] \
        * c["moe_intermediate_size"]
    assert m["rope_base"] == c["rope_parameters"]["rope_theta"]
    assert not m["tie_head"] and not c["tie_word_embeddings"]
    assert c["scoring_func"] == "sigmoid" and m["moe_router"] == "sigmoid"
    assert c["qk_head_dim"] == m["qk_nope_head_dim"] + m["qk_rope_head_dim"]


def test_the_share_holds_3881_million_parameters(config):
    """The issue's arithmetic, the file's numbers and the built model's
    own count (shapes alone: no weight is made)."""
    import jax
    import jax.numpy as jnp
    from apex_tpu.models.gpt import GPTConfig, GPTModel

    kw = {k: getattr(jnp, v) if v in ("bfloat16", "float32") else v
          for k, v in config["model"].items()}
    model = GPTModel(GPTConfig(**kw))
    shapes = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
    built = sum(a.size for a in jax.tree.leaves(shapes))
    assert built == parameter_count(config) == 3_881_517_056
    assert all(a.dtype == jnp.bfloat16 for a in jax.tree.leaves(shapes)
               if a.ndim >= 2 and a.shape[0] != 256)    # router: float32
    # the cache: 5 x 576 + 2 x 128 numbers a token: 640 a latent as stored,
    # in bf16, and the index keys in float32
    assert model.cache_record() == ((5, 1, 640), (2, 1, 128, jnp.float32))
    engine = config["engine"]
    blocks = 1 + engine["max_slots"] * (kw["max_seq_len"]
                                        // engine["block_size"])
    assert blocks * engine["block_size"] * (5 * 640 * 2 + 2 * 128 * 4) \
        == 973_553_664


def test_the_mix_offers_the_issues_lengths():
    man = manifest.Manifest(ROOT)
    mix = man.cell(CELL).traffic
    assert traffic.prompt_lengths(mix) == [4871, 6889, 9742, 13777]
    assert sorted(set(traffic.grid(4, **mix["output"]).tolist())) \
        == [38, 54, 76, 108]
    assert mix["check_prompts"] == [2560, 6889, 13777] \
        and mix["check_new_tokens"] == 8
    assert min(mix["check_prompts"]) > 2048         # a selection every check
    assert (mix["pattern_seed"], mix["lead_in_s"], mix["drain_s"],
            mix["trace_slice_s"]) == (35, 8.0, 20.0, 15.0)
    a = traffic.open_loop(mix, 45.0)
    b = traffic.open_loop(mix, 45.0)
    assert [(x.due, x.prompt_len, x.new_tokens) for x in a] \
        == [(x.due, x.prompt_len, x.new_tokens) for x in b]
    sampled = [x for x in a if x.sampled]
    assert len(sampled) == round(45 * mix["rate_rps"])
    assert max(x.prompt_len + x.new_tokens for x in a) < 16384


def test_the_cell_resolves_by_name_with_its_metrics():
    man = manifest.Manifest(ROOT)
    c = man.cell(CELL)
    assert c.chips == 1 and c.traffic["job"] == "serve_open"
    assert c.config["name"] == NAME and c.config["job"] == "serve"
    assert {m["name"] for m in c.end_to_end} == {"ttft_p90_s", "tpot_p90_s",
                                                 "setup_s"}
    assert {m["name"] for m in c.per_layer} == NEW | JOINED
    assert all(callable(getattr(readers, m["reader"])) for m in c.per_layer)
    mine = [m for m in man.data["per_layer"] if m["name"] in NEW]
    assert len(mine) == 5
    for m in mine:
        assert m["workloads"] == [CELL] and m["unit"] == "%"
        assert m["moves"] == {"ttft": "ttft_p90_s", "tpot": "tpot_p90_s"}[
            m["name"].rsplit(".", 1)[1]]
    roofline = next(m for m in man.data["per_layer"]
                    if m["name"] == "paged_decode_roofline.tpot")
    assert CELL not in roofline["workloads"]    # the dense kernel's count
    cell = next(w for w in man.data["workloads"] if w["name"] == CELL)
    config = next(e for e in man.data["configs"] if e["name"] == NAME)
    assert len(cell["why"]) <= 200 and len(config["why"]) <= 200
    assert set(c.config["check"]) == {"near_tie_margin", "min_compared"}


def test_the_counts_of_the_new_kernels(config):
    counts = load_module(ROOT, "benchmarks/configs/glm-5.2.flops.py",
                         "glm_flops")
    ops, nbytes = counts.sparse_attention(64, 512, 64, selected_tokens=2048)
    assert ops == 64 * (576 + 512) * 2 * 2048 and nbytes == 1152 * 2048
    ops, nbytes = counts.indexer_scores(32, 128, context_tokens=13777)
    assert ops == 32 * 128 * 2 * 13777 and nbytes == 256 * 13777
    assert counts.topk_select(13777) == (13777, 4 * 13777)


def test_the_reference_runs_under_one_jit_at_the_rehearsals_size(config):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from apex_tpu.models.gpt import GPTConfig, GPTModel
    from benchmarks.harness import serve

    small = manifest.with_rehearsal(config)
    kw = {k: getattr(jnp, v) if v in ("bfloat16", "float32") else v
          for k, v in small["model"].items()}
    cfg = GPTConfig(**kw)
    model = GPTModel(cfg)
    params = jax.jit(model.init_params)(jax.random.PRNGKey(0))
    ref_mod = load_module(ROOT, small["reference"], "bench_ref")
    margin, least = serve.check_limits(small)
    logits, near = serve.reference_of(ref_mod, cfg, margin)
    toks = jnp.asarray(np.random.default_rng(0).integers(
        0, cfg.vocab_size, (1, 64)), jnp.int32)
    want = np.asarray(logits(params, toks))
    assert want.shape == (64, cfg.vocab_size) and want.dtype == np.float32
    assert np.asarray(near(params, toks)).shape == (64,)
    got, _ = jax.jit(model.prefill)(params, toks)
    # a router of 8 experts ties far more often than one of 256
    keep = ~np.asarray(ref_mod.near_ties(params, toks, cfg, 2e-2))[0]
    assert keep.sum() >= 8
    err = np.abs(np.asarray(got[0], np.float32) - want)[keep].max()
    assert err / np.abs(want).max() < serve.LOGIT_TOL


def test_a_rehearsal_of_the_cell_reads_correct():
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
         "--workload", CELL, "--seed", "2147483999", "--seconds", "4",
         "--rehearsal"], capture_output=True, text=True, timeout=600,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["correct"] and line["failed"] == 0 and not line["metrics"]
    assert line["device"]["platform"] == "cpu"
    notes = next(l for l in out.stdout.splitlines() if l.startswith("notes:"))
    assert "compared=" in notes and "left_out=" in notes
