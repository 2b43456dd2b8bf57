"""apex_tpu.observability.costmodel: alpha-beta ring fits + profiles.

The contract under test (ISSUE 7):

* the ring primitives (``ring_hops`` / ``ring_wire_bytes``) apply the
  same factors as ``comms.wire_bytes`` — all-reduce ``2(k-1)`` hops and
  ``2(k-1)/k`` wire, gather/scatter ``k-1`` and ``(k-1)/k``, permute
  one hop at factor 1;
* the least-squares fit recovers planted (alpha, beta) coefficients
  from synthetic measurements exactly, clamps negative coefficients,
  and handles degenerate single-point curves;
* ``CostModel.predict`` falls back across dtypes (missing dtype ->
  f32 -> any curve for the op) but raises on an unknown OP;
* ``validate`` reports the worst two-sided ratio; ``holdout_split``
  never holds out a curve's endpoints;
* the profile JSON round-trips fits + measurements and refuses a
  version it doesn't understand — while a version-LESS (pre-stamp)
  profile still loads, with a warning;
* ``save`` stamps staleness metadata (``probed_at`` +
  ``n_measurements``) and ``profile_age`` / ``is_stale`` gate on it —
  a never-stamped profile is always stale;
* the incremental refit path (ROADMAP item 3): ``update`` buffers
  without fitting, ``refit`` declines below ``min_measurements`` and
  KEEPS the buffer, recovers a planted drift in its ``drift_report``,
  merges un-remeasured curves from the old model, never mutates
  ``self``, and its fits stay within the two-sided ``validate`` ratio
  on a held-out split.

The probe itself runs once, on two of the CPU mesh's devices
(``TestProbe``): that checks the plumbing from timed collectives to a
priced HLO accounting, not the coefficients, which mean something only
from a chip; every other test here is host-only math.
"""

import json

import pytest

from apex_tpu.observability.costmodel import (
    COLLECTIVE_OPS,
    HLO_KIND_TO_OP,
    PROFILE_VERSION,
    CostFit,
    CostModel,
    Measurement,
    _lstsq_fit,
    _payload_bytes,
    fit_cost_model,
    holdout_split,
    load_profile,
    probe_collectives,
    ring_hops,
    ring_wire_bytes,
    simulate_link_measurements,
)


def synthetic(op, dtype, alpha, beta, sizes, k=4):
    """Measurements lying exactly on a planted alpha-beta curve."""
    return [Measurement(op=op, dtype=dtype, group_size=k, nbytes=n,
                        time_s=alpha * ring_hops(op, k)
                        + beta * ring_wire_bytes(op, n, k))
            for n in sizes]


class TestRingPrimitives:
    def test_hops(self):
        assert ring_hops("psum", 4) == 6.0          # 2(k-1)
        assert ring_hops("all_gather", 4) == 3.0    # k-1
        assert ring_hops("psum_scatter", 8) == 7.0
        assert ring_hops("ppermute", 8) == 1.0
        with pytest.raises(ValueError):
            ring_hops("all_to_all", 4)

    def test_wire_bytes_factors(self):
        n = 1024
        assert ring_wire_bytes("psum", n, 4) == n * 2 * 3 / 4
        assert ring_wire_bytes("all_gather", n, 4) == n * 3 / 4
        assert ring_wire_bytes("psum_scatter", n, 8) == n * 7 / 8
        assert ring_wire_bytes("ppermute", n, 8) == float(n)
        with pytest.raises(ValueError):
            ring_wire_bytes("bogus", n, 2)

    def test_payload_convention(self):
        # all_gather payload is the gathered RESULT (largest shape on
        # the instruction); everything else the per-device operand
        assert _payload_bytes("all_gather", "f32", 100, 4) == 1600
        assert _payload_bytes("psum", "f32", 100, 4) == 400
        assert _payload_bytes("psum_scatter", "int8", 100, 4) == 100
        assert _payload_bytes("ppermute", "bf16", 100, 4) == 200

    def test_hlo_kind_mapping_covers_comms_kinds(self):
        assert HLO_KIND_TO_OP["all_reduce"] == "psum"
        assert HLO_KIND_TO_OP["reduce_scatter"] == "psum_scatter"
        assert set(HLO_KIND_TO_OP.values()) <= set(COLLECTIVE_OPS)


class TestFit:
    def test_recovers_planted_coefficients(self):
        alpha, beta = 5e-6, 2e-9
        ms = synthetic("psum", "f32", alpha, beta,
                       sizes=(4096, 16384, 65536, 262144))
        model = fit_cost_model(ms)
        fit = model.fits[("psum", "f32")]
        assert fit.alpha_s == pytest.approx(alpha, rel=1e-6)
        assert fit.beta_s_per_byte == pytest.approx(beta, rel=1e-6)
        assert fit.max_rel_err < 1e-9
        assert fit.n_points == 4

    def test_one_curve_per_op_dtype(self):
        ms = (synthetic("psum", "f32", 1e-6, 1e-9, (1024, 4096))
              + synthetic("psum", "int8", 1e-6, 5e-10, (1024, 4096))
              + synthetic("ppermute", "f32", 2e-6, 1e-9, (1024, 4096)))
        model = fit_cost_model(ms)
        assert set(model.fits) == {("psum", "f32"), ("psum", "int8"),
                                   ("ppermute", "f32")}

    def test_negative_beta_clamped(self):
        # times DECREASING with size is noise; beta must clamp to 0 and
        # alpha refit non-negative, never extrapolate negatively
        rows = [(2.0, 100.0, 1.0), (2.0, 1000.0, 0.5)]
        alpha, beta = _lstsq_fit(rows)
        assert beta == 0.0 and alpha >= 0.0

    def test_single_point_latency_only(self):
        alpha, beta = _lstsq_fit([(2.0, 512.0, 1e-3)])
        assert beta == 0.0 and alpha == pytest.approx(5e-4)

    def test_predict_monotone_in_size_and_group(self):
        model = fit_cost_model(
            synthetic("all_gather", "f32", 1e-6, 1e-9,
                      (4096, 65536, 1048576)))
        p1 = model.predict("all_gather", 1 << 12, 2)
        p2 = model.predict("all_gather", 1 << 16, 2)
        p3 = model.predict("all_gather", 1 << 16, 8)
        assert p1 < p2 < p3


class TestCostModel:
    def _model(self):
        return fit_cost_model(
            synthetic("psum", "f32", 1e-6, 2e-9, (4096, 65536))
            + synthetic("psum", "int8", 1e-6, 1e-9, (4096, 65536)))

    def test_dtype_fallback_chain(self):
        model = self._model()
        # exact dtype
        assert model.predict("psum", 4096, 2, dtype="int8") \
            < model.predict("psum", 4096, 2, dtype="f32")
        # un-probed dtype falls back to f32
        assert model.predict("psum", 4096, 2, dtype="bf16") \
            == model.predict("psum", 4096, 2, dtype="f32")
        # op with no f32 curve falls back to any curve for the op
        only_i8 = fit_cost_model(
            synthetic("ppermute", "int8", 1e-6, 1e-9, (4096, 65536)))
        assert only_i8.predict("ppermute", 4096, 2, dtype="bf16") > 0

    def test_unknown_op_raises(self):
        with pytest.raises(ValueError, match="unknown collective op"):
            self._model().predict("all_to_all", 4096, 2)

    def test_validate_two_sided_ratio(self):
        model = self._model()
        good = Measurement("psum", "f32", 2, 4096,
                           model.predict("psum", 4096, 2))
        slow = Measurement("psum", "f32", 2, 4096,
                           model.predict("psum", 4096, 2) * 3.0)
        report = model.validate([good, slow], tolerance=2.0)
        assert report["n"] == 2
        assert report["worst_ratio"] == pytest.approx(3.0)
        assert not report["within_tolerance"]
        # under-prediction counts the same as over-prediction
        fast = Measurement("psum", "f32", 2, 4096,
                           model.predict("psum", 4096, 2) / 3.0)
        assert model.validate([fast])["worst_ratio"] == pytest.approx(3.0)
        assert model.validate([good], tolerance=2.0)["within_tolerance"]

    def test_predict_stats(self):
        model = self._model()
        stats = {"all_reduce": {"count": 2, "bytes": 8192,
                                "ops": [{"bytes": 4096, "group_size": 2},
                                        {"bytes": 4096, "group_size": 0}]},
                 "all_gather": {"count": 0, "bytes": 0, "ops": []}}
        out = model.predict_stats(stats, group_size=4)
        assert out["all_reduce"]["modeled_as"] == "psum"
        assert out["all_reduce"]["count"] == 2
        # second op had no parsed group -> fallback group_size=4
        expect = (model.predict("psum", 4096, 2)
                  + model.predict("psum", 4096, 4))
        assert out["total_s"] == pytest.approx(expect)
        assert "all_gather" not in out       # zero-count kinds skipped


class TestHoldoutSplit:
    def _curve(self, n, op="psum", dtype="f32", k=2):
        return [Measurement(op, dtype, k, 1 << (10 + i), 1e-3 * (i + 1))
                for i in range(n)]

    def test_endpoints_never_held_out(self):
        ms = self._curve(7)
        train, held = holdout_split(ms, every=3)
        assert len(train) + len(held) == 7
        assert held                       # something was held out
        nbytes = sorted(m.nbytes for m in ms)
        held_sizes = {m.nbytes for m in held}
        assert nbytes[0] not in held_sizes
        assert nbytes[-1] not in held_sizes

    def test_tiny_curves_fully_trained(self):
        train, held = holdout_split(self._curve(2), every=3)
        assert len(train) == 2 and not held

    def test_per_curve_isolation(self):
        ms = self._curve(5) + self._curve(5, op="ppermute")
        train, held = holdout_split(ms, every=3)
        assert {m.op for m in held} == {"psum", "ppermute"}


class TestProfileJson:
    def test_round_trip(self, tmp_path):
        ms = synthetic("psum", "f32", 1e-6, 2e-9, (4096, 65536))
        model = fit_cost_model(ms, meta={"backend": "cpu"})
        path = str(tmp_path / "profile.json")
        model.save(path, measurements=ms)
        loaded, lm = load_profile(path)
        assert loaded.meta["backend"] == "cpu"
        assert set(loaded.fits) == set(model.fits)
        assert loaded.predict("psum", 12345, 4) \
            == model.predict("psum", 12345, 4)
        assert [m.to_dict() for m in lm] == [m.to_dict() for m in ms]

    def test_version_refused(self, tmp_path):
        doc = CostModel({("psum", "f32"): CostFit(1e-6, 1e-9)}).to_json()
        assert doc["version"] == PROFILE_VERSION
        doc["version"] = PROFILE_VERSION + 1
        path = tmp_path / "stale.json"
        path.write_text(json.dumps(doc))
        with pytest.raises(ValueError, match="comms_probe"):
            load_profile(str(path))

    def test_measurements_optional(self, tmp_path):
        model = fit_cost_model(
            synthetic("psum", "f32", 1e-6, 2e-9, (4096, 65536)))
        path = str(tmp_path / "bare.json")
        model.save(path)
        _, ms = load_profile(path)
        assert ms == []

    def test_versionless_profile_loads_with_warning(self, tmp_path):
        model = fit_cost_model(
            synthetic("psum", "f32", 1e-6, 2e-9, (4096, 65536)))
        doc = model.to_json()
        del doc["version"]
        path = tmp_path / "prehistoric.json"
        path.write_text(json.dumps(doc))
        with pytest.warns(UserWarning, match="no version"):
            loaded, _ = load_profile(str(path))
        assert loaded.predict("psum", 4096, 2) \
            == model.predict("psum", 4096, 2)


class TestStaleness:
    def test_save_stamps_probe_metadata(self, tmp_path):
        ms = synthetic("psum", "f32", 1e-6, 2e-9, (4096, 65536))
        model = fit_cost_model(ms)
        path = str(tmp_path / "profile.json")
        model.save(path, measurements=ms)
        loaded, _ = load_profile(path)
        assert loaded.meta["n_measurements"] == len(ms)
        t0 = loaded.meta["probed_at"]
        assert loaded.profile_age(now=t0 + 10.0) == pytest.approx(10.0)
        assert not loaded.is_stale(3600.0, now=t0 + 10.0)
        assert loaded.is_stale(3600.0, now=t0 + 7200.0)

    def test_existing_stamp_not_overwritten(self, tmp_path):
        ms = synthetic("psum", "f32", 1e-6, 2e-9, (4096, 65536))
        model = fit_cost_model(ms, meta={"probed_at": 1234.5})
        path = str(tmp_path / "profile.json")
        model.save(path, measurements=ms)
        loaded, _ = load_profile(path)
        assert loaded.meta["probed_at"] == 1234.5

    def test_never_stamped_always_stale(self):
        model = fit_cost_model(
            synthetic("psum", "f32", 1e-6, 2e-9, (4096, 65536)))
        assert model.profile_age() is None
        assert model.is_stale(1e18)     # any gate: no stamp => stale


class TestRefit:
    def _base(self):
        return fit_cost_model(
            simulate_link_measurements(1e-6, 1e-9, link_class="ici",
                                       ops=("psum",))
            + simulate_link_measurements(2e-3, 1e-9, link_class="dcn",
                                         ops=("psum",)))

    def test_update_buffers_without_fitting(self):
        model = self._base()
        before = dict(model.curves())
        n = model.update(simulate_link_measurements(
            2e-6, 2e-9, link_class="ici", ops=("psum",)))
        assert n == len(model.fresh_measurements) > 0
        assert dict(model.curves()) == before   # nothing fitted yet

    def test_too_few_declines_and_keeps_buffer(self):
        model = self._base()
        pts = simulate_link_measurements(
            2e-6, 2e-9, link_class="ici", ops=("psum",))[:3]
        model.update(pts)
        res = model.refit(min_measurements=8)
        assert not res["refitted"]
        assert "3" in res["reason"]
        assert len(model.fresh_measurements) == 3   # buffer KEPT
        # topping up past the floor succeeds and clears the buffer
        model.update(simulate_link_measurements(
            2e-6, 2e-9, link_class="ici", ops=("psum",)))
        assert model.refit(min_measurements=8)["refitted"]
        assert model.fresh_measurements == ()

    def test_recovers_planted_drift(self):
        model = self._base()
        model.update(simulate_link_measurements(
            2e-6, 2e-9, link_class="ici", ops=("psum",)))
        res = model.refit(min_measurements=8)
        assert res["refitted"]
        # everything doubled => worst |t_new/t_old - 1| == 1.0
        assert res["drift"]["max_drift"] == pytest.approx(1.0, rel=1e-3)
        assert ("psum|f32|ici" in res["drift"]["curves"])
        new = res["model"]
        assert new.predict("psum", 1 << 16, 4, link_class="ici") \
            == pytest.approx(
                2 * model.predict("psum", 1 << 16, 4, link_class="ici"),
                rel=1e-3)

    def test_unremeasured_curves_merge_and_self_unmutated(self):
        model = self._base()
        old_dcn = model.predict("psum", 1 << 16, 4, link_class="dcn")
        old_ici = model.predict("psum", 1 << 16, 4, link_class="ici")
        model.update(simulate_link_measurements(
            4e-6, 4e-9, link_class="ici", ops=("psum",)))
        new = model.refit(min_measurements=8)["model"]
        # only ici was re-measured; the dcn tier keeps the old fit
        assert new.predict("psum", 1 << 16, 4, link_class="dcn") \
            == old_dcn
        assert new.predict("psum", 1 << 16, 4, link_class="ici") \
            == pytest.approx(4 * old_ici, rel=1e-3)
        # the caller owns adoption: self never moved
        assert model.predict("psum", 1 << 16, 4, link_class="ici") \
            == old_ici

    def test_refit_stamps_staleness_metadata(self):
        model = self._base()
        model.update(simulate_link_measurements(
            2e-6, 2e-9, link_class="ici", ops=("psum",)))
        n_fresh = len(model.fresh_measurements)
        new = model.refit(min_measurements=8, now=777.0)["model"]
        assert new.meta["probed_at"] == 777.0
        assert new.meta["n_measurements"] == n_fresh
        assert not new.is_stale(10.0, now=780.0)

    def test_refit_within_validate_on_holdout(self):
        model = self._base()
        pts = simulate_link_measurements(
            3e-6, 3e-9, link_class="ici", ops=("psum",),
            sizes=(1 << 12, 1 << 14, 1 << 16, 1 << 18, 1 << 20))
        train, held = holdout_split(pts, every=3)
        assert held
        model.update(train)
        new = model.refit(min_measurements=8)["model"]
        report = new.validate(held, tolerance=2.0)
        assert report["within_tolerance"], report


class TestProbe:
    def test_probe_fit_roundtrip_prices_a_real_accounting(self, tmp_path):
        """Timed collectives -> fitted curves -> profile JSON -> the
        price of a real ``collective_stats`` dict.  The held-out ratio is
        reported, not gated: a CPU wall time under parallel test workers
        is no measurement (``validate``'s gate is held on planted curves
        in ``TestCostModel`` and ``TestRefit``)."""
        import jax
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P

        from apex_tpu.observability import collective_stats

        ops = ("psum", "all_gather", "psum_scatter", "ppermute")
        measurements = probe_collectives(
            ops=ops, dtypes=("f32", "int8"),
            sizes=(1 << 12, 1 << 14, 1 << 16, 1 << 18),
            group_sizes=(2,), iters=2, rounds=2)
        assert len(measurements) == len(ops) * 2 * 4
        assert all(m.time_s > 0 and m.group_size == 2
                   for m in measurements)
        train, held = holdout_split(measurements, every=3)
        assert held
        model = fit_cost_model(train, meta={
            "backend": jax.default_backend()})
        assert set(model.fits) == {(op, dt) for op in ops
                                   for dt in ("f32", "int8")}
        report = model.validate(held, tolerance=2.0)
        assert report["n"] == len(held) and report["worst_ratio"] >= 1.0

        path = str(tmp_path / "profile.json")
        model.save(path, measurements=measurements)
        with open(path, encoding="utf-8") as f:
            assert json.load(f)["version"] == PROFILE_VERSION
        loaded, ms = load_profile(path)
        assert len(ms) == len(measurements)
        assert set(loaded.fits) == set(model.fits)
        for op, dtype in model.fits:
            for nbytes in (1 << 13, 1 << 17):
                assert (model.predict(op, nbytes, 2, dtype=dtype)
                        == loaded.predict(op, nbytes, 2, dtype=dtype))

        mesh = jax.make_mesh((2,), ("tp",), devices=jax.devices()[:2])
        psummed = jax.shard_map(lambda x: jax.lax.psum(x, "tp"),
                                mesh=mesh, in_specs=P("tp"), out_specs=P(),
                                check_vma=False)
        priced = model.predict_stats(
            collective_stats(psummed, jnp.ones((8, 16), jnp.float32)),
            group_size=2)
        assert priced["total_s"] > 0.0
        assert priced["all_reduce"]["modeled_as"] == "psum"
