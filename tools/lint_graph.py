#!/usr/bin/env python
"""Lint the canonical train/serve programs against the committed
baseline.

Runs the ``apex_tpu.analysis`` registry (dtype / donation / host-sync /
recompile / sharding / overlap + the peak-memory estimator) over the
six canonical programs — the GPT train step at dp, tp=2 + sequence
parallelism, pp=2; the anomaly-guarded step; serving prefill and
decode — and diffs every finding against the accepted baseline.  Any
NEW finding exits nonzero: this is the CI gate
(``tests/test_analysis.py`` drives the same rules in tier-1).

Linting is compile-only (nothing executes), so it runs anywhere —
including a 1-core CPU host with the 8-device mesh forced below.

Usage:
    python tools/lint_graph.py                        # table vs baseline
    python tools/lint_graph.py --json                 # machine-readable
    python tools/lint_graph.py --programs decode,prefill
    python tools/lint_graph.py --write-baseline       # accept findings
    python tools/lint_graph.py --baseline my.json --devices 4
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

DEFAULT_BASELINE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "lint_baseline.json")


def main() -> int:
    ap = argparse.ArgumentParser(
        description="lint the canonical programs against the baseline")
    ap.add_argument("--programs", default=None,
                    help="comma-separated subset (default: all six)")
    ap.add_argument("--json", action="store_true", dest="as_json",
                    help="emit one JSON document instead of tables")
    ap.add_argument("--table", action="store_true",
                    help="force the table view (default)")
    ap.add_argument("--baseline", default=DEFAULT_BASELINE,
                    help=f"accepted-findings file (default "
                         f"{os.path.relpath(DEFAULT_BASELINE)})")
    ap.add_argument("--no-baseline", action="store_true",
                    help="report everything; never exit nonzero")
    ap.add_argument("--write-baseline", action="store_true",
                    help="accept the current findings into --baseline")
    ap.add_argument("--devices", type=int, default=8,
                    help="forced CPU device count (default 8)")
    args = ap.parse_args()

    # environment BEFORE jax imports: the lint mesh is always host CPU,
    # with the device count the canonical programs expect
    os.environ["JAX_PLATFORMS"] = "cpu"
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            f"{flags} --xla_force_host_platform_device_count="
            f"{args.devices}").strip()
    from apex_tpu.analysis import lint, load_baseline, save_baseline
    from apex_tpu.utils.platform import setup_compile_cache

    setup_compile_cache()
    from apex_tpu.analysis.canonical import canonical_programs

    names = args.programs.split(",") if args.programs else None
    reports = [lint(p) for p in
               canonical_programs(names, n_devices=args.devices)]

    baseline = {}
    if not args.no_baseline and not args.write_baseline \
            and os.path.exists(args.baseline):
        baseline = load_baseline(args.baseline)
    new = {r.program: r.new_findings(baseline.get(r.program, []))
           for r in reports}
    n_new = sum(len(v) for v in new.values())

    if args.write_baseline:
        save_baseline(args.baseline, reports)
        print(f"wrote {args.baseline}: "
              + ", ".join(f"{r.program}={len(r.findings)}"
                          for r in reports))
        return 0

    if args.as_json:
        doc = {"programs": [r.to_dict() for r in reports],
               "baseline": args.baseline if baseline else None,
               "new_findings": {k: [f.to_dict() for f in v]
                                for k, v in new.items() if v}}
        print(json.dumps(doc, indent=2))
    else:
        for r in reports:
            print(r.format_table())
            fresh = new[r.program]
            if fresh:
                print(f"  !! {len(fresh)} NEW finding(s) not in baseline:")
                for f in fresh:
                    print(f"     {f.key}")
            print()
        total = sum(len(r.findings) for r in reports)
        print(f"{len(reports)} program(s), {total} finding(s), "
              f"{n_new} new vs baseline")

    if args.no_baseline:
        return 0
    return 1 if n_new else 0


if __name__ == "__main__":
    sys.exit(main())
