#!/usr/bin/env python
"""Probe the ring collectives and fit a machine cost profile.

Microbenchmarks ``psum`` / ``all_gather`` / ``psum_scatter`` /
``ppermute`` across message sizes, ring sizes and dtypes on the current
mesh, fits per-(op, dtype) alpha-beta ring coefficients by least
squares, validates the fit on a held-out split, and writes a VERSIONED
machine-profile JSON — the measured communication model the
auto-parallel planner (``tools/autotune.py``, ROADMAP item 1) will
consume via ``CostModel.predict`` / ``predict_stats``.

Usage:
    python tools/comms_probe.py --out profile.json
    python tools/comms_probe.py --ops psum,all_gather --dtypes f32,int8 \\
        --sizes 4096,65536,1048576 --groups 2,4 --out profile.json
    python tools/comms_probe.py --check profile.json   # re-validate a
        saved profile's fits against its own stored measurements

Two-tier (MPMD cross-pod) profiles: ``--link-class dcn`` tags the
probed measurements as the slow tier (run it on a mesh whose rings
actually cross the data-center network); ``--simulate-dcn alpha,beta``
instead synthesizes an exact dcn curve from the given per-hop latency
(seconds) and inverse bandwidth (seconds/byte) — the CPU-only CI path
for exercising the two-tier fit, e.g. ``--simulate-dcn 1e-3,1e-8``.
Both land in the same profile JSON; curves carry a ``link_class``
field and pre-link-class profiles load as ici.

On a CPU host, 8 virtual devices come from
``XLA_FLAGS=--xla_force_host_platform_device_count=8``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _csv(cast):
    return lambda s: [cast(v) for v in s.split(",") if v]


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--out", default="comms_profile.json",
                    help="machine-profile JSON path")
    ap.add_argument("--ops", type=_csv(str), default=None,
                    help="comma list from psum,all_gather,psum_scatter,"
                         "ppermute (default: all)")
    ap.add_argument("--dtypes", type=_csv(str),
                    default=["f32", "bf16", "int8"],
                    help="comma list from f32,bf16,int8")
    ap.add_argument("--sizes", type=_csv(int), default=None,
                    help="per-device local buffer bytes (default "
                         "4K..1M powers of 4)")
    ap.add_argument("--groups", type=_csv(int), default=None,
                    help="ring sizes (default: 2,4,8 where they divide "
                         "the device count)")
    ap.add_argument("--iters", type=int, default=4)
    ap.add_argument("--rounds", type=int, default=5)
    ap.add_argument("--holdout", type=int, default=3,
                    help="hold out every Nth point per curve for "
                         "validation (0: fit on everything)")
    ap.add_argument("--tolerance", type=float, default=2.0,
                    help="validation gate on held-out pred/meas ratio")
    ap.add_argument("--check", metavar="PROFILE", default=None,
                    help="skip probing; re-validate PROFILE against "
                         "its stored measurements")
    ap.add_argument("--max-age-s", type=float, default=None,
                    help="with --check: additionally gate on profile "
                         "staleness — fail when the probe stamp is "
                         "older than this many seconds or missing "
                         "entirely (never probed)")
    ap.add_argument("--link-class", default="ici",
                    help="fabric tag for the probed measurements "
                         "(ici | dcn; default ici)")
    ap.add_argument("--simulate-dcn", metavar="ALPHA,BETA", default=None,
                    help="also inject a synthetic dcn curve with the "
                         "given per-hop latency (s) and inverse "
                         "bandwidth (s/byte), e.g. 1e-3,1e-8 — the "
                         "CPU-only CI path for two-tier fits")
    ap.add_argument("-q", "--quiet", action="store_true")
    args = ap.parse_args(argv)

    simulate_dcn = None
    if args.simulate_dcn is not None:
        parts = [p for p in args.simulate_dcn.split(",") if p]
        if len(parts) != 2:
            ap.error("--simulate-dcn wants 'alpha,beta' "
                     "(seconds, seconds/byte), e.g. 1e-3,1e-8")
        simulate_dcn = (float(parts[0]), float(parts[1]))

    import jax

    from apex_tpu.observability.costmodel import (
        Measurement, fit_cost_model, holdout_split, load_profile,
        probe_collectives, simulate_link_measurements)

    if args.check:
        model, ms = load_profile(args.check)
        if not ms:
            print("profile carries no raw measurements; nothing to "
                  "re-validate", file=sys.stderr)
            return 2
        report = model.validate(ms, tolerance=args.tolerance)
        out = {k: v for k, v in report.items() if k != "rows"}
        # staleness is orthogonal to fit quality: a profile can still
        # predict its OWN stored measurements perfectly while being a
        # year out of date (drifted), or carry no stamp at all (never
        # probed on this fleet) — surface both so the autopilot's
        # max_profile_age_s gate has the same data offline
        age = model.profile_age()
        out["profile_age_s"] = age
        out["n_measurements"] = model.meta.get("n_measurements")
        if args.max_age_s is not None:
            out["stale"] = model.is_stale(args.max_age_s)
            out["max_age_s"] = args.max_age_s
        print(json.dumps(out, indent=1))
        if args.max_age_s is not None and out["stale"]:
            reason = ("no probe stamp (never probed)" if age is None
                      else f"probed {age:.0f}s ago")
            print(f"profile is stale: {reason} (gate "
                  f"{args.max_age_s:.0f}s)", file=sys.stderr)
            return 1
        return 0 if report["within_tolerance"] else 1

    from apex_tpu.observability.costmodel import COLLECTIVE_OPS

    ops = args.ops or list(COLLECTIVE_OPS)
    sizes = args.sizes or [1 << 12, 1 << 14, 1 << 16, 1 << 18, 1 << 20]
    measurements = probe_collectives(
        ops=ops, dtypes=args.dtypes, sizes=sizes,
        group_sizes=args.groups, iters=args.iters, rounds=args.rounds,
        link_class=args.link_class, verbose=not args.quiet)
    if not measurements:
        print("probe produced no measurements", file=sys.stderr)
        return 2
    if simulate_dcn is not None:
        alpha, beta = simulate_dcn
        measurements += simulate_link_measurements(
            alpha, beta, link_class="dcn", ops=ops, dtypes=["f32"],
            sizes=sizes, group_sizes=args.groups or (2, 4))

    if args.holdout:
        train, held = holdout_split(measurements, every=args.holdout)
    else:
        train, held = list(measurements), []
    model = fit_cost_model(train, meta={
        "backend": jax.default_backend(),
        "device_kind": jax.devices()[0].device_kind,
        "n_devices": len(jax.devices()),
        "iters": args.iters, "rounds": args.rounds,
    })
    model.save(args.out, measurements=measurements)

    curves = model.curves()
    print(f"wrote {args.out}: {len(curves)} fitted curves over "
          f"{len(train)} points "
          f"(link classes: {', '.join(model.link_classes)})")
    for (op, dtype, lc), fit in sorted(curves.items()):
        print(f"  {op:<13} {dtype:<5} {lc:<4} "
              f"alpha={fit.alpha_s * 1e6:8.2f}us/hop"
              f"  beta={fit.beta_s_per_byte * 1e9:8.3f}ns/B"
              f"  fit_err<={fit.max_rel_err:.2f}")
    if held:
        report = model.validate(held, tolerance=args.tolerance)
        ok = "OK" if report["within_tolerance"] else "FAIL"
        print(f"held-out validation [{ok}]: {report['n']} points, "
              f"worst ratio {report['worst_ratio']:.2f}x "
              f"(gate {args.tolerance}x)")
        return 0 if report["within_tolerance"] else 1
    return 0


if __name__ == "__main__":
    from apex_tpu.utils.platform import setup_compile_cache
    setup_compile_cache()
    sys.exit(main())
