"""The command-a-plus-05-2026 configuration's own cases, on the CPU: its cut
agrees with its sizes, every number of the catalog's row is in the file or
named as reduced, its parameter count is the built model's, the mix offers
its stated lengths, the cell resolves by name with its metrics, the kernels'
counts agree with a hand count, the reference runs under one ``jax.jit`` at
the rehearsal's size, and a rehearsal of the whole cell reads ``correct``.

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

NAME = "command-a-plus-05-2026"
CELL = NAME + ".mixedlen-steady"
# the catalog of architectures the other served configuration is held to
CATALOG = load_module(ROOT, "benchmarks/tests/test_glm_config.py",
                      "glm_config_for_catalog").CATALOG
NEW = {"window_flash_time_share.ttft", "grouped_decode_time_share.tpot"}
JOINED = {"submit_late_p95_ms.ttft", "queue_wait_p50_s.ttft",
          "decode_batch_mean.tpot", "token_gap_p95_s.tpot",
          "prefill_time_share.ttft", "decode_time_share.tpot",
          "kv_write_time_share.ttft", "mosaic_time_share.tpot",
          "device_idle_share.tpot", "hbm_peak_share.tpot",
          "host_work_share.tpot", "sample_host_share.tpot",
          "admit_host_share.ttft", "admit_request_p50_ms.ttft",
          "tick_dispatch_p50_ms.tpot", "tick_wait_p50_ms.tpot",
          "tick_grow_p50_ms.tpot", "tick_inputs_p50_ms.tpot",
          "tick_launch_p50_ms.tpot", "decode_stalled_share.tpot",
          "decode_stall_p50_ms.tpot", "attention_time_share.ttft",
          "mlp_time_share.ttft", "grouped_dot_time_share.tpot"}
NOT_JOINED = {"paged_decode_roofline.tpot", "masked_flash_time_share.ttft",
              "sparse_attention_time_share.ttft", "indexer_time_share.ttft",
              "attention_time_share.tpot", "mlp_time_share.tpot"}


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           NAME + ".json")) as f:
        return json.load(f)


def parameter_count(c):
    """Parameters the share holds, from the file's numbers alone: a layer's
    weight-only norm, Wq, Wk, Wv and Wo, the router (no bias), the shared
    experts side by side and the held experts; the tied embedding and the
    final norm once."""
    h, d = c["hidden_size"], c["head_dim"]
    q, kv = c["num_attention_heads"] * d, c["num_key_value_heads"] * d
    attention = h * (q + 2 * kv) + q * h
    router = c["published"]["num_experts"] * h
    shared = 3 * h * c["num_shared_experts"] * c["intermediate_size"]
    experts = c["num_experts"] * 3 * h * c["intermediate_size"]
    layer = h + attention + router + shared + experts
    return c["num_hidden_layers"] * layer + c["vocab_size"] * h + h


def test_the_cut_agrees_with_the_sizes(config):
    c, pub = config, config["published"]
    assert c["num_hidden_layers"] == len(c["layer_types"]) == 4
    # published layers 0-3: one whole period of the pattern, windows first
    assert c["layer_types"] == pub["layer_types"][:4] == [
        "sliding_attention"] * 3 + ["full_attention"]
    assert pub["layer_types"] == c["layer_types"] * 8 \
        and len(pub["layer_types"]) == pub["num_hidden_layers"] == 32
    assert c["layer_switch"] == 4 and c["first_k_dense_replace"] == 0
    assert c["order_of_interleaved_layers"] == "local_attn_first"
    assert c["held"]["layer_pattern"] == "P" * 4
    lo, hi = c["held"]["experts"]
    assert hi - lo == c["num_experts"] == 8
    assert pub["num_experts"] == 16 * 8                 # experts 16-way
    assert pub["vocab_size"] == 8 * c["vocab_size"]     # vocabulary 8-way
    assert c["held"]["vocab_rows"] == [0, c["vocab_size"]]
    assert "16 chips share each layer" in c["deployment"]
    assert sorted(c["reduced"]) == sorted(pub)
    assert c["max_position_embeddings"] == c["model"]["max_seq_len"]
    # the guide's floors: a whole period, four layers, eight experts, an
    # eighth of the vocabulary
    assert len(c["layer_types"]) % c["layer_switch"] == 0


def test_every_number_is_the_catalogs_or_named_as_reduced(config):
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == NAME)
    assert config["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in config["reduced"]:
            assert config["published"][key] == value
            assert config[key] != value
        else:
            assert config[key] == value, key
    widths = ("hidden_size", "intermediate_size", "_dim", "_rank",
              "per_tok", "heads", "sliding_window")
    assert not [k for k in config["reduced"] if k.endswith(widths)]
    entry = next(e for e in manifest.Manifest(ROOT).data["configs"]
                 if e["name"] == NAME)
    assert entry["reduced"] == config["reduced"]
    assert entry["source"] == config["source"]
    # the reading of "average", and the other one named
    assert "(R + Sh) / 2" in config["assumed"]["shared_combination"]
    assert "R + mean(shared_i)" in config["assumed"]["shared_combination"]
    for key in ("vision_tower", "initializer", "router", "precision",
                "engine", "sampling", "rotary", "window"):
        assert key in config["assumed"]


def test_the_model_group_is_the_files_numbers(config):
    c, m = config, config["model"]
    for ours, theirs in (
            ("hidden_size", "hidden_size"), ("vocab_size", "vocab_size"),
            ("num_attention_heads", "num_attention_heads"),
            ("num_kv_heads", "num_key_value_heads"), ("head_dim", "head_dim"),
            ("max_seq_len", "max_position_embeddings"),
            ("ffn_hidden_size", "intermediate_size"),
            ("moe_top_k", "num_experts_per_tok"),
            ("sliding_window", "sliding_window"),
            ("rope_base", "rope_theta")):
        assert m[ours] == c[theirs], ours
    assert m["attention_types"] == [t.split("_")[0] for t in c["layer_types"]]
    assert m["layer_pattern"] == c["held"]["layer_pattern"]
    assert m["n_experts"] == c["published"]["num_experts"]
    assert m["moe_held"] == c["held"]["experts"]
    assert m["moe_shared_ffn"] == c["num_shared_experts"] \
        * c["intermediate_size"]
    assert m["moe_shared_combine"] == c["shared_expert_combination_strategy"]
    assert c["expert_selection_fn"] == "sigmoid" == m["moe_router"]
    assert not m["moe_router_bias"] and m["moe_routed_scale"] == 1.0
    assert c["norm_topk_prob"] and c["use_parallel_block"]
    assert m["tie_head"] == c["tie_word_embeddings"] is True
    assert m["norm"] == "layernorm" and not m["norm_bias"]
    # rope_gptj, all 128 lanes, on the window layers alone: a 'P' layer
    # turns adjacent pairs where it slides, and its full layers carry no
    # position code
    assert c["position_embedding_type"] == "rope_gptj" and m["rotary"]
    assert c["rotary_pct"] == 1
    assert not c["use_qk_norm"] and not c["attention_bias"] \
        and not m["bias"]
    assert c["logit_scale"] == 1


def test_the_share_holds_3122_million_parameters(config):
    """The cut's arithmetic, the file's numbers and the built model's own
    count (shapes alone: no weight is made); the pool and the window
    storage the engine makes for it."""
    import jax
    import jax.numpy as jnp
    from apex_tpu.models.gpt import GPTConfig, GPTModel

    kw = {k: getattr(jnp, v) if v in ("bfloat16", "float32") else v
          for k, v in config["model"].items()}
    model = GPTModel(GPTConfig(**kw))
    shapes = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
    built = sum(a.size for a in jax.tree.leaves(shapes))
    assert built == parameter_count(config) == 3_122_679_808
    assert 4 * (344_461_312 + 8 * 50_331_648) + 32_768 * 4096 + 4096 \
        == built
    assert all(a.dtype == jnp.bfloat16 for a in jax.tree.leaves(shapes)
               if a.ndim >= 2 and a.shape[0] != 128)    # router: float32
    # K and V of 8 heads of 128 in bf16: 4 096 bytes a token and layer
    assert model.cache_record() == ((1, 2, 1024),)
    assert model.window_record() == (3, 2, 1024, 4096)
    engine = config["engine"]
    bs, slots = engine["block_size"], engine["max_slots"]
    full = (1 + slots * kw["max_seq_len"] // bs) * bs * 1 * 4096
    band = -(-4096 // bs) + 1
    window = (1 + slots * band) * bs * 3 * 4096
    assert band == 65 and (full, window) == (1_074_003_968, 818_675_712)


def test_the_mix_offers_its_stated_lengths():
    man = manifest.Manifest(ROOT)
    mix = man.cell(CELL).traffic
    assert traffic.prompt_lengths(mix) == [696, 1288, 2381, 4404, 8144,
                                           15060]
    assert sorted(set(traffic.grid(4, **mix["output"]).tolist())) \
        == [41, 70, 117, 197]
    # half the lengths shorter than the window, half longer
    assert sum(n < 4096 for n in traffic.prompt_lengths(mix)) == 3
    assert mix["check_prompts"] == [2381, 8144, 15060] \
        and mix["check_new_tokens"] == 8
    assert (mix["pattern_seed"], mix["lead_in_s"], mix["drain_s"],
            mix["trace_slice_s"]) == (40, 8.0, 20.0, 15.0)
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
    assert not {m["name"] for m in c.per_layer} & NOT_JOINED
    assert all(callable(getattr(readers, m["reader"])) for m in c.per_layer)
    mine = [m for m in man.data["per_layer"] if m["name"] in NEW]
    assert [m["name"] for m in man.data["per_layer"][-2:]] == [
        "window_flash_time_share.ttft", "grouped_decode_time_share.tpot"]
    for m in mine:
        spec = man.metric(m)
        assert m["workloads"] == [CELL] and m["unit"] == "%"
        assert m["layer"] == "kernels" and m["source"] == "device_trace"
        assert spec["reader"] == "op_time_share" \
            and spec["args"]["over"] == "busy"
        assert m["moves"] == {"ttft": "ttft_p90_s", "tpot": "tpot_p90_s"}[
            m["name"].rsplit(".", 1)[1]]
    cell = next(w for w in man.data["workloads"] if w["name"] == CELL)
    config = next(e for e in man.data["configs"] if e["name"] == NAME)
    assert len(cell["why"]) <= 200 and len(config["why"]) <= 200
    assert set(c.config["check"]) == {"near_tie_margin", "min_compared"}


def test_the_counts_of_the_new_kernels():
    """Against a hand count: at 8 positions and a window of 3 a causal
    sequence has 1 + 2 + 3 x 6 = 21 visible pairs (36 without the window);
    the tick's bytes are K and V of the KV heads over the positions read."""
    counts = load_module(ROOT, f"benchmarks/configs/{NAME}.flops.py",
                         "command_a_flops")
    assert counts.visible_pairs(8, 3) == 21
    assert counts.visible_pairs(8) == counts.visible_pairs(8, 8) == 36
    ops, nbytes = counts.window_flash(4, 2, 16, 8, window=3)
    assert ops == 4 * 4 * 16 * 21 and nbytes == 2 * (4 + 2) * 16 * 8 * 2
    # the prediction's arithmetic at bucket 16 384: 11.5 TFLOP for three window
    # layers, 8.8 for the full one
    assert round(3 * counts.window_flash(128, 8, 128, 16384, 4096)[0]
                 / 1e12, 1) == 11.5
    assert round(counts.window_flash(128, 8, 128, 16384)[0] / 1e12, 1) == 8.8
    ops, nbytes = counts.grouped_decode(128, 8, 128, positions=4096)
    assert ops == 4 * 128 * 128 * 4096 and nbytes == 2 * 8 * 128 * 4096 * 2


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
    margin, _ = serve.check_limits(small)
    logits, near = serve.reference_of(ref_mod, cfg, margin)
    toks = jnp.asarray(np.random.default_rng(0).integers(
        0, cfg.vocab_size, (1, 64)), jnp.int32)
    want = np.asarray(logits(params, toks))
    assert want.shape == (64, cfg.vocab_size) and want.dtype == np.float32
    assert np.asarray(near(params, toks)).shape == (64,)
    got, _ = jax.jit(model.prefill)(params, toks)
    # float32 at the rehearsal's size: every position, near ties or not
    err = np.abs(np.asarray(got[0], np.float32) - want).max()
    assert err / np.abs(want).max() < 1e-4


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
