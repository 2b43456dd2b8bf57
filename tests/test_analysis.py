"""apex_tpu.analysis: analyzer fixtures (positive + negative), the
memory estimator's accuracy gate, baseline bookkeeping, the canonical
programs vs the committed baseline, and the applied donation fixes
(inference-engine decode, guarded train step) staying bitwise-clean.
"""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from apex_tpu.analysis import (Finding, LintConfig, LintProgram, LintReport,
                               estimate_from_hlo_text, lint, lint_fn,
                               load_baseline, parse_hlo_module,
                               save_baseline, scope_of, shape_bytes)
from apex_tpu.analysis.canonical import BUILDERS, canonical_programs

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BASELINE = os.path.join(REPO, "tools", "lint_baseline.json")


def _rules(report):
    return [f.rule for f in report.findings]


# -- jaxpr-level analyzers ---------------------------------------------------


class TestDtypeRule:
    def test_bf16_upcast_matmul_trips(self):
        def step(w, x):
            return x @ w.astype(jnp.float32)        # bf16 -> f32 upcast

        rep = lint_fn(step, jnp.zeros((16, 16), jnp.bfloat16),
                      jnp.ones((4, 16), jnp.float32),
                      config=LintConfig(estimate_memory=False))
        assert "dtype/bf16-upcast-matmul" in _rules(rep)
        (f,) = [f for f in rep.findings
                if f.rule == "dtype/bf16-upcast-matmul"]
        assert f.details["source_dtype"] == "bfloat16"
        assert f.fix_hint

    def test_preferred_element_type_is_clean(self):
        def step(w, x):
            # the sanctioned AMP idiom: bf16 operands, f32 accumulate
            return jax.lax.dot_general(
                x.astype(jnp.bfloat16), w,
                dimension_numbers=(((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

        rep = lint_fn(step, jnp.zeros((16, 16), jnp.bfloat16),
                      jnp.ones((4, 16), jnp.float32),
                      config=LintConfig(estimate_memory=False))
        assert "dtype/bf16-upcast-matmul" not in _rules(rep)

    def test_f64_trips_and_is_error(self):
        with jax.enable_x64():
            def step(x):
                return x * np.float64(2.0)

            rep = lint_fn(step, jnp.ones((8,), jnp.float64),
                          config=LintConfig(estimate_memory=False))
        (f,) = [f for f in rep.findings if f.rule == "dtype/f64-op"]
        assert f.severity == "error"

    def test_f32_program_has_no_f64_finding(self):
        rep = lint_fn(lambda x: x * 2.0, jnp.ones((8,), jnp.float32),
                      config=LintConfig(estimate_memory=False))
        assert "dtype/f64-op" not in _rules(rep)


class TestDonationRule:
    def _step(self, params, opt, x):
        g = jax.tree_util.tree_map(lambda p: p * 0.9, params)
        return (jax.tree_util.tree_map(lambda a, b: a + b, params, g),
                opt, x.sum())

    def test_missing_donation_trips_per_argnum(self):
        params = {"w": jnp.zeros((64, 64)), "b": jnp.zeros((64,))}
        opt = {"m": jnp.zeros((64, 64))}
        rep = lint_fn(self._step, params, opt, jnp.ones((4, 64)),
                      config=LintConfig(estimate_memory=False))
        hits = [f for f in rep.findings if f.rule == "donation/missing"]
        assert {f.details["argnum"] for f in hits} == {0, 1}
        f0 = next(f for f in hits if f.details["argnum"] == 0)
        assert f0.details["aliasable_bytes"] >= 64 * 64 * 4
        assert f0.details["example_path"]
        assert f0.scope == "arg0"

    def test_donated_program_is_clean(self):
        params = {"w": jnp.zeros((64, 64)), "b": jnp.zeros((64,))}
        opt = {"m": jnp.zeros((64, 64))}
        rep = lint_fn(self._step, params, opt, jnp.ones((4, 64)),
                      donate_argnums=(0, 1),
                      config=LintConfig(estimate_memory=False))
        assert "donation/missing" not in _rules(rep)

    def test_tiny_aliasable_leaves_are_ignored(self):
        rep = lint_fn(lambda c: c + 1, jnp.zeros((4,), jnp.float32),
                      config=LintConfig(estimate_memory=False))
        assert "donation/missing" not in _rules(rep)


class TestHostSyncRule:
    def test_debug_print_trips(self):
        def step(x):
            jax.debug.print("loss={v}", v=x.sum())
            return x * 2

        rep = lint_fn(step, jnp.ones((8,)),
                      config=LintConfig(estimate_memory=False))
        hits = [f for f in rep.findings if f.rule == "host-sync/callback"]
        assert hits and hits[0].severity == "warning"

    def test_pure_callback_trips(self):
        def step(x):
            y = jax.pure_callback(
                lambda a: np.asarray(a) * 2.0,
                jax.ShapeDtypeStruct(x.shape, x.dtype), x)
            return y.sum()

        rep = lint_fn(step, jnp.ones((8,)),
                      config=LintConfig(estimate_memory=False))
        assert "host-sync/callback" in _rules(rep)

    def test_pure_program_is_clean(self):
        rep = lint_fn(lambda x: x * 2, jnp.ones((8,)),
                      config=LintConfig(estimate_memory=False))
        assert "host-sync/callback" not in _rules(rep)


class TestRecompileRule:
    def test_unhashable_static_is_error(self):
        from apex_tpu.analysis.jaxpr_rules import analyze_recompile
        prog = LintProgram("p", fn=lambda x, cfg: x * cfg[0],
                           args=(jnp.ones(4), [2.0]), static_argnums=(1,))
        (f,) = analyze_recompile(prog, LintConfig())
        assert f.rule == "recompile/unhashable-static"
        assert f.severity == "error"

    def test_identity_hash_static_warns(self):
        from apex_tpu.analysis.jaxpr_rules import analyze_recompile

        class Cfg:                      # no __eq__/__hash__: identity
            scale = 2.0

        prog = LintProgram("p", fn=lambda x, cfg: x * cfg.scale,
                           args=(jnp.ones(4), Cfg()), static_argnums=(1,))
        (f,) = analyze_recompile(prog, LintConfig())
        assert f.rule == "recompile/identity-static"

    def test_hashable_value_static_is_clean(self):
        from apex_tpu.analysis.jaxpr_rules import analyze_recompile
        prog = LintProgram("p", fn=lambda x, k: x * k,
                           args=(jnp.ones(4), 2.0), static_argnums=(1,))
        assert analyze_recompile(prog, LintConfig()) == []


# -- HLO-level analyzers -----------------------------------------------------


class _FakeProgram:
    """Stub carrying a pre-parsed module into the HLO analyzers."""

    def __init__(self, text):
        self._mod = parse_hlo_module(text)

    def hlo_module(self):
        return self._mod


class TestOverlapRule:
    def test_chained_psums_trip(self):
        # the pp loss pattern: psum over one axis feeding psum over the
        # other with nothing between — two serialized all-reduces
        mesh = jax.make_mesh((2, 2), ("dp", "tp"),
                             devices=jax.devices()[:4])

        def f(x):
            return jax.lax.psum(jax.lax.psum(x, "dp"), "tp")

        g = jax.shard_map(f, mesh=mesh, in_specs=P("dp"),
                          out_specs=P(), check_vma=False)
        rep = lint_fn(g, jnp.ones((8, 16)),
                      config=LintConfig(estimate_memory=False))
        hits = [f for f in rep.findings
                if f.rule == "overlap/serialized-collectives"]
        assert hits and hits[0].details["upstream_op"] == "all-reduce"

    def test_compute_between_collectives_is_clean(self):
        mesh = jax.make_mesh((4,), ("tp",), devices=jax.devices()[:4])

        def f(x):
            y = jax.lax.psum(x, "tp")
            return jax.lax.psum(jnp.tanh(y) @ jnp.ones((16, 16)), "tp")

        g = jax.shard_map(f, mesh=mesh, in_specs=P("tp"),
                          out_specs=P(), check_vma=False)
        rep = lint_fn(g, jnp.ones((8, 16)),
                      config=LintConfig(estimate_memory=False))
        assert "overlap/serialized-collectives" not in _rules(rep)


_ROUNDTRIP_HLO = """\
HloModule g, is_scheduled=true, num_partitions=4

ENTRY %main (p0: f32[64,16]) -> f32[64,16] {
  %p0 = f32[64,16]{1,0} parameter(0)
  %rs = f32[16,16]{1,0} reduce-scatter(f32[64,16]{1,0} %p0), replica_groups={{0,1,2,3}}, dimensions={0}, to_apply=%add
  %cp = f32[16,16]{1,0} copy(f32[16,16]{1,0} %rs)
  ROOT %ag = f32[64,16]{1,0} all-gather(f32[16,16]{1,0} %cp), replica_groups={{0,1,2,3}}, dimensions={0}, metadata={op_name="jit(f)/jit(main)/mlp/all_gather"}
}
"""


class TestShardingRule:
    def test_gather_roundtrip_trips(self):
        from apex_tpu.analysis.hlo_rules import analyze_sharding
        findings = analyze_sharding(_FakeProgram(_ROUNDTRIP_HLO),
                                    LintConfig())
        (f,) = [f for f in findings
                if f.rule == "sharding/gather-roundtrip"]
        assert f.details["scatter"] == "rs"
        assert f.scope == "mlp/all_gather"

    def test_large_gather_without_roundtrip_is_info(self):
        from apex_tpu.analysis.hlo_rules import analyze_sharding
        text = _ROUNDTRIP_HLO.replace("reduce-scatter", "dynamic-slice")
        findings = analyze_sharding(_FakeProgram(text),
                                    LintConfig(large_bytes=1024))
        rules = [f.rule for f in findings]
        assert "sharding/gather-roundtrip" not in rules
        (f,) = [f for f in findings if f.rule == "sharding/large-gather"]
        assert f.severity == "info"

    def test_replicated_large_trips(self):
        mesh = jax.make_mesh((8,), ("tp",), devices=jax.devices()[:8])
        w = jnp.zeros((64, 64), jnp.float32)          # 16 KiB
        x = jnp.ones((8, 64), jnp.float32)
        f = jax.jit(lambda w, x: x @ w,
                    in_shardings=(NamedSharding(mesh, P()),
                                  NamedSharding(mesh, P("tp"))),
                    out_shardings=NamedSharding(mesh, P("tp")))
        prog = LintProgram("repl", lowered=f.lower(w, x))
        cfg = LintConfig(large_bytes=4096, estimate_memory=False,
                         analyzers=("sharding",))
        rep = lint(prog, cfg)
        hits = [f for f in rep.findings
                if f.rule == "sharding/replicated-large"]
        assert hits and hits[0].details["partitions"] == 8

    def test_sharded_weight_is_clean(self):
        mesh = jax.make_mesh((8,), ("tp",), devices=jax.devices()[:8])
        w = jnp.zeros((64, 64), jnp.float32)
        x = jnp.ones((8, 64), jnp.float32)
        f = jax.jit(lambda w, x: x @ w,
                    in_shardings=(NamedSharding(mesh, P(None, "tp")),
                                  NamedSharding(mesh, P())),
                    out_shardings=NamedSharding(mesh, P(None, "tp")))
        prog = LintProgram("shrd", lowered=f.lower(w, x))
        cfg = LintConfig(large_bytes=4096, estimate_memory=False,
                         analyzers=("sharding",))
        assert "sharding/replicated-large" not in _rules(lint(prog, cfg))

    def test_single_partition_skips(self):
        from apex_tpu.analysis.hlo_rules import analyze_sharding
        text = _ROUNDTRIP_HLO.replace(", num_partitions=4", "")
        assert analyze_sharding(_FakeProgram(text), LintConfig()) == []


# -- HLO parsing + memory estimator ------------------------------------------

_SYNTH = """\
HloModule synth, is_scheduled=true, input_output_alias={ {}: (0, {}, may-alias) }, entry_computation_layout={(f32[1024]{0}, f32[1024]{0})->f32[1024]{0}}

ENTRY %main (p0: f32[1024], p1: f32[1024]) -> f32[1024] {
  %p0 = f32[1024]{0} parameter(0)
  %p1 = f32[1024]{0} parameter(1)
  %add = f32[1024]{0} add(f32[1024]{0} %p0, f32[1024]{0} %p1), metadata={op_name="jit(f)/jit(main)/layer/add"}
  %mul = f32[1024]{0} multiply(f32[1024]{0} %add, f32[1024]{0} %p1)
  ROOT %out = f32[1024]{0} add(f32[1024]{0} %mul, f32[1024]{0} %add)
}
"""

_WHILE_HLO = """\
HloModule w, is_scheduled=true

%body (bp: (f32[256], s32[])) -> (f32[256], s32[]) {
  %bp = (f32[256]{0}, s32[]) parameter(0)
  %v = f32[256]{0} get-tuple-element((f32[256]{0}, s32[]) %bp), index=0
  %i = s32[] get-tuple-element((f32[256]{0}, s32[]) %bp), index=1
  %v2 = f32[256]{0} add(f32[256]{0} %v, f32[256]{0} %v)
  %one = s32[] constant(1)
  %i2 = s32[] add(s32[] %i, s32[] %one)
  ROOT %t = (f32[256]{0}, s32[]) tuple(f32[256]{0} %v2, s32[] %i2)
}

%cond (cp: (f32[256], s32[])) -> pred[] {
  %cp = (f32[256]{0}, s32[]) parameter(0)
  %ci = s32[] get-tuple-element((f32[256]{0}, s32[]) %cp), index=1
  %n = s32[] constant(8)
  ROOT %lt = pred[] compare(s32[] %ci, s32[] %n), direction=LT
}

ENTRY %main (a: f32[256]) -> f32[256] {
  %a = f32[256]{0} parameter(0)
  %z = s32[] constant(0)
  %init = (f32[256]{0}, s32[]) tuple(f32[256]{0} %a, s32[] %z)
  %w = (f32[256]{0}, s32[]) while((f32[256]{0}, s32[]) %init), condition=%cond, body=%body
  ROOT %r = f32[256]{0} get-tuple-element((f32[256]{0}, s32[]) %w), index=0
}
"""


class TestHloParsing:
    def test_shape_bytes(self):
        assert shape_bytes("f32[128,4]") == 128 * 4 * 4
        assert shape_bytes("bf16[8]{0}") == 16
        assert shape_bytes("(f32[4], s32[2])") == 16 + 8
        assert shape_bytes("pred[]") == 1

    def test_scope_of_drops_jit_frames(self):
        assert scope_of("jit(f)/jit(main)/attn/psum") == "attn/psum"
        assert scope_of(None) == ""

    def test_synthetic_module(self):
        mod = parse_hlo_module(_SYNTH)
        assert mod.is_scheduled
        assert mod.input_output_aliases == [(0, 0)]
        e = mod.entry
        assert [p.param_number for p in e.params] == [0, 1]
        add = e.by_name()["add"]
        assert add.scope == "layer/add"
        assert add.nbytes == 4096
        assert e.root.name == "out"

    def test_while_attr_list_does_not_bleed(self):
        # `condition=%cond, body=%body` must parse as two names, not
        # one comma-slurped blob (the bug that hid every while body
        # from the estimator)
        mod = parse_hlo_module(_WHILE_HLO)
        w = mod.entry.by_name()["w"]
        assert w.called == ["cond", "body"]
        assert set(mod.computations) == {"body", "cond", "main"}


class TestMemoryEstimator:
    def test_synthetic_estimate(self):
        est = estimate_from_hlo_text(_SYNTH)
        # params (2 x 4 KiB, live throughout) + add & mul both live at
        # the mul; the ROOT writes in place over donated p0
        assert est.argument_bytes == 8192
        assert est.aliased_bytes == 4096
        assert est.peak_bytes == 8192 + 8192
        assert est.top_live[0][0] == 4096

    def test_undonated_synthetic_costs_one_more_buffer(self):
        text = _SYNTH.replace(
            "input_output_alias={ {}: (0, {}, may-alias) }, ", "")
        est = estimate_from_hlo_text(text)
        assert est.aliased_bytes == 0
        # at the ROOT: params + add + mul + the (now undonated) output
        assert est.peak_bytes == 8192 + 8192 + 4096

    def test_while_carry_counted_once(self):
        # XLA aliases a while's init, body carry and result into one
        # allocation: one 1 KiB carry + the tiny loop counter, not two
        # or three copies
        est = estimate_from_hlo_text(_WHILE_HLO)
        assert 256 * 4 <= est.peak_bytes <= 256 * 4 + 64


# -- canonical programs vs the committed baseline ----------------------------


@pytest.fixture(scope="module")
def canonical_reports():
    from apex_tpu.transformer import parallel_state
    reports = {}
    for prog in canonical_programs():
        reports[prog.name] = lint(prog)
    parallel_state.destroy_model_parallel()
    return reports


class TestCanonical:
    def test_all_six_lint(self, canonical_reports):
        assert set(canonical_reports) == set(BUILDERS)
        for rep in canonical_reports.values():
            assert isinstance(rep, LintReport)
            assert rep.analyzers            # something actually ran

    def test_committed_baseline_accepts_everything(self,
                                                   canonical_reports):
        baseline = load_baseline(BASELINE)
        for name, rep in canonical_reports.items():
            fresh = rep.new_findings(baseline.get(name, []))
            assert fresh == [], (
                f"{name}: new findings vs committed baseline: "
                f"{[f.key for f in fresh]}")

    def test_donation_clean_after_fixes(self, canonical_reports):
        # the applied fixes: decode donates the cache, the guarded step
        # donates the train state, both train steps donate params + opt
        for name, rep in canonical_reports.items():
            assert "donation/missing" not in _rules(rep), name

    def test_memory_estimates_within_1p5x_of_xla(self,
                                                 canonical_reports):
        for name, rep in canonical_reports.items():
            m = rep.memory
            assert m is not None and m.peak_bytes > 0, name
            if m.xla_ratio is None:
                continue
            assert 1 / 1.5 <= m.xla_ratio <= 1.5, (
                f"{name}: estimate {m.peak_bytes} vs XLA "
                f"{m.xla_peak_bytes} ({m.xla_ratio:.2f}x)")

    def test_reports_carry_provenance(self, canonical_reports):
        rep = canonical_reports["gpt_train_tp_sp"]
        assert any("mlp" in f.scope for f in rep.findings)


# -- findings + baseline bookkeeping -----------------------------------------


class TestBaseline:
    def _reports(self):
        f1 = Finding(rule="a/x", severity="warning", message="m",
                     scope="s1", details={"bytes": 123})
        f2 = Finding(rule="a/y", severity="error", message="m2",
                     scope="s2")
        return [LintReport(program="p", findings=[f1, f2])]

    def test_roundtrip_and_details_excluded_from_key(self, tmp_path):
        path = str(tmp_path / "b.json")
        save_baseline(path, self._reports())
        loaded = load_baseline(path)
        assert loaded == {"p": ["a/x|s1", "a/y|s2"]}
        # a size change does not churn the key
        again = Finding(rule="a/x", severity="warning", message="m",
                        scope="s1", details={"bytes": 999})
        assert again.key in loaded["p"]

    def test_new_findings_gate(self):
        (rep,) = self._reports()
        assert rep.new_findings([]) != []
        assert rep.new_findings([f.key for f in rep.findings]) == []

    def test_version_mismatch_raises(self, tmp_path):
        path = tmp_path / "b.json"
        path.write_text(json.dumps({"version": 99, "programs": {}}))
        with pytest.raises(ValueError, match="version"):
            load_baseline(str(path))

    def test_severity_validated(self):
        with pytest.raises(ValueError, match="severity"):
            Finding(rule="r", severity="fatal", message="m")


# -- the applied donation fixes stay bitwise-clean ---------------------------


def _tiny_model():
    from apex_tpu.models.gpt import GPTConfig, GPTModel
    cfg = GPTConfig(vocab_size=32, hidden_size=16, num_layers=2,
                    num_attention_heads=4, max_seq_len=16)
    model = GPTModel(cfg)
    return model, model.init_params(jax.random.PRNGKey(0))


class TestAppliedFixes:
    def test_engine_decode_donation_bitwise_vs_undonated(self):
        from apex_tpu.inference.engine import (InferenceEngine, Request,
                                               _picking)

        model, params = _tiny_model()

        def run(donate):
            eng = InferenceEngine(model, params, max_slots=2,
                                  cache_dtype=jnp.float32)
            if not donate:       # reference: the pre-fix undonated jit
                eng._decode = jax.jit(_picking(model.decode_step))
            for rid, prompt in ((1, [1, 2, 3]), (2, [4, 5])):
                eng.submit(Request(request_id=rid, prompt=prompt,
                                   max_new_tokens=6))
            return {r.request_id: r.tokens for r in eng.run()}

        assert run(True) == run(False)

    def test_engine_decode_lint_before_after(self):
        # the lint evidence that motivated the fix: without donation
        # the decode step holds the cache twice
        from apex_tpu.analysis.canonical import make_decode
        prog = make_decode(1)
        fixed = lint(prog)
        broken = lint(LintProgram("decode_undonated", fn=prog.fn,
                                  args=prog.args))
        assert "donation/missing" in _rules(broken)
        assert "donation/missing" not in _rules(fixed)
        cache_bytes = int(np.prod(prog.args[2].shape)) * 4
        assert fixed.memory.aliased_bytes >= cache_bytes
        assert broken.memory.peak_bytes > fixed.memory.peak_bytes

    def test_guard_donate_bitwise_parity(self):
        from apex_tpu.optimizers import FusedAdam
        from apex_tpu.resilience import GuardedTrainStep

        model, params = _tiny_model()
        rng = np.random.RandomState(7)
        batches = [(jnp.asarray(rng.randint(0, 32, (2, 16))),
                    jnp.asarray(rng.randint(0, 32, (2, 16))))
                   for _ in range(3)]

        def drive(donate):
            guard = GuardedTrainStep(model.loss, FusedAdam(lr=1e-3),
                                     donate=donate)
            # fresh buffers per run: the donated path consumes them
            p = jax.tree_util.tree_map(jnp.array, params)
            o = guard.optimizer.init(p)
            g = guard.init_state()
            for i, (tk, tg) in enumerate(batches):
                res = guard(p, o, g, tk, tg, step=i)
                p, o, g = res.params, res.opt_state, res.guard_state
            return p, res.loss_value

        p_don, loss_don = drive(True)
        p_ref, loss_ref = drive(False)
        assert loss_don == loss_ref
        for a, b in zip(jax.tree_util.tree_leaves(p_don),
                        jax.tree_util.tree_leaves(p_ref), strict=True):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# -- comms scope attribution (satellite) -------------------------------------


class TestCommsScope:
    def test_collective_ops_carry_scope(self):
        from apex_tpu.observability.comms import (collective_stats,
                                                  format_stats)

        mesh = jax.make_mesh((4,), ("tp",), devices=jax.devices()[:4])

        def f(x):
            with jax.named_scope("attn"):
                y = jax.lax.psum(x * 2, "tp")
            with jax.named_scope("mlp"):
                z = jax.lax.all_gather(x, "tp")
            return y, z

        g = jax.shard_map(f, mesh=mesh, in_specs=P("tp"),
                          out_specs=(P(), P("tp")), check_vma=False)
        st = collective_stats(g, jnp.ones((8, 16)))
        assert any("attn" in op["scope"]
                   for op in st["all_reduce"]["ops"])
        assert any("mlp" in op["scope"]
                   for op in st["all_gather"]["ops"])
        table = format_stats(st, by_scope=True)
        assert "attn" in table and "all_reduce" in table

    def test_synthetic_scope_parse(self):
        from apex_tpu.observability.comms import hlo_collective_stats
        line = ('  %ar = f32[64]{0} all-reduce(f32[64]{0} %x), '
                'replica_groups={{0,1}}, to_apply=%sum, '
                'metadata={op_name="jit(step)/jit(main)/layer0/psum"}')
        st = hlo_collective_stats("HloModule m\n" + line)
        (op,) = st["all_reduce"]["ops"]
        assert op["scope"] == "layer0/psum"
        assert op["bytes"] == 256
        assert op["group_size"] == 2


# -- the CLI -----------------------------------------------------------------


class TestCli:
    @pytest.mark.slow
    def test_lint_graph_json_and_gate(self, tmp_path):
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        out = subprocess.run(
            [sys.executable, os.path.join(REPO, "tools", "lint_graph.py"),
             "--programs", "decode,prefill", "--json"],
            capture_output=True, text=True, env=env, cwd=str(tmp_path),
            timeout=300)
        assert out.returncode == 0, out.stderr[-2000:]
        doc = json.loads(out.stdout)
        names = [p["program"] for p in doc["programs"]]
        assert names == ["decode", "prefill"]
        for p in doc["programs"]:
            assert p["memory"]["peak_bytes"] > 0
            assert p["elapsed_s"] < 10.0
        assert doc["new_findings"] == {}
