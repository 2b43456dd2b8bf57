#!/usr/bin/env python
"""Multi-chip MFU measurement (ISSUE 17): per-chip achieved FLOPs and
model-FLOPs utilization for dp x tp train steps with the fused-FFN knob
on, held against the autotune planner's own predictions.

For each plan the tool builds the planner's REAL candidate program
(``tools/autotune.build_train_step``: pipelined grad step + optimizer
over an ElasticPlan mesh), measures it with the bench timing
protocol, and reports:

* ``achieved_flops_per_chip`` — 6ND model flops (8ND under remat) over
  ``n_devices x measured_s``;
* ``mfu`` — achieved per-chip flops over the same calibrated matmul
  roofline the planner ranks with (``calibrate_matmul_flops``: a
  measured constant on THIS host, not a spec sheet, so the number is
  honest on CPU hosts too);
* ``predicted_s`` / ``gap`` — the planner's compute+comm prediction for
  the plan and its relative distance from the wall clock, i.e. the
  same predicted-vs-measured accounting ``bench.py``'s autotune leg
  tracks, evaluated at the plans the fused-FFN work actually targets.

One process per chip: ``measure`` runs in this process over
``jax.devices()`` and starts nothing.  On a chip host call it from the
one process that owns the chips (``bench.py`` does, in-process) — a
child of a process that has touched JAX cannot reach them.  An 8-device
run on virtual CPU devices is a correctness run, not an MFU.

Usage:
    python tools/mfu_multichip.py --devices 4 [--batch 8] [--out f.json]
"""

from __future__ import annotations

import argparse
import json
import sys

from _timing import time_steps  # noqa: E402 (sets sys.path)

from autotune import (_default_cost_model, DEFAULT_MODEL,  # noqa: E402
                      build_train_step, calibrate_matmul_flops,
                      predict_comm_s, predict_compute_s)


def _plans(n_devices: int):
    from apex_tpu.parallel.plan import ParallelPlan

    plans = [("dp%d_fused" % n_devices,
              ParallelPlan(dp=n_devices, fused_ffn=True))]
    if n_devices >= 4 and n_devices % 2 == 0:
        tp = 2
        dp = n_devices // tp
        plans.append((f"dp{dp}_tp{tp}_sp",
                      ParallelPlan(dp=dp, tp=tp, sequence_parallel=True)))
        plans.append((f"dp{dp}_tp{tp}_sp_fused",
                      ParallelPlan(dp=dp, tp=tp, sequence_parallel=True,
                                   fused_ffn=True)))
    return plans


def measure(n_devices: int, batch: int, *, cfg_kw=None, quiet=False):
    import jax

    def say(msg):
        if not quiet:
            print(msg, flush=True)

    cfg_kw = dict(cfg_kw or DEFAULT_MODEL)
    seq = cfg_kw["max_seq_len"]
    devices = jax.devices()[:n_devices]
    if len(devices) < n_devices:
        raise RuntimeError(f"need {n_devices} devices, have "
                           f"{len(devices)}")
    flops_per_s = calibrate_matmul_flops()
    say(f"calibrated matmul roofline: {flops_per_s / 1e9:.2f} Gflop/s "
        "per device")
    cost_model = _default_cost_model(n_devices)

    rows = {}
    for name, plan in _plans(n_devices):
        step, args, n_params = build_train_step(plan, cfg_kw, batch, seq,
                                                devices)
        compiled = jax.jit(step).lower(*args).compile()
        measured_s = time_steps(compiled, args, warmup=1, iters=4,
                                rounds=3)
        flops = 6.0 * float(n_params) * batch * seq
        if plan.remat:
            flops *= 8.0 / 6.0
        per_chip = flops / (n_devices * measured_s)
        compute_s = predict_compute_s(plan, n_params, batch, seq,
                                      flops_per_s)
        comm_s = predict_comm_s(compiled, cost_model,
                                group_size=max(plan.dp, plan.tp, plan.pp))
        predicted_s = compute_s + comm_s
        rows[name] = {
            "plan": plan.describe(),
            "measured_s": round(measured_s, 6),
            "predicted_s": round(predicted_s, 6),
            "gap": round(abs(predicted_s - measured_s) / measured_s, 4),
            "achieved_flops_per_chip": round(per_chip, 1),
            "mfu": round(per_chip / flops_per_s, 4),
        }
        say(f"  {name:<22} meas={measured_s * 1e3:8.3f} ms  "
            f"pred={predicted_s * 1e3:8.3f} ms  "
            f"mfu={rows[name]['mfu']:.4f}")
        jax.clear_caches()

    fused = {k: v for k, v in rows.items() if k.endswith("fused")}
    best = max(fused, key=lambda k: fused[k]["mfu"])
    report = {
        "n_devices": n_devices,
        "batch": batch,
        "seq": seq,
        "model": cfg_kw,
        "n_params": n_params,
        "flops_per_s_per_chip": round(flops_per_s, 1),
        "rows": rows,
        "best_fused_plan": best,
        "mfu": rows[best]["mfu"],
        "gap_max": max(r["gap"] for r in rows.values()),
    }
    if "dp%d_tp2_sp" % (n_devices // 2) in rows:
        base = rows["dp%d_tp2_sp" % (n_devices // 2)]
        tuned = rows["dp%d_tp2_sp_fused" % (n_devices // 2)]
        report["fused_speedup_dp_tp_sp"] = round(
            base["measured_s"] / tuned["measured_s"], 4)
    return report


def main(argv=None):
    ap = argparse.ArgumentParser(
        description="per-chip MFU for dp x tp fused-FFN train steps")
    ap.add_argument("--devices", type=int, default=8)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--out", default=None,
                    help="write the report JSON here (else stdout)")
    ap.add_argument("--quiet", action="store_true")
    ns = ap.parse_args(argv)
    report = measure(ns.devices, ns.batch, quiet=ns.quiet)
    text = json.dumps(report, indent=1, sort_keys=True) + "\n"
    if ns.out:
        with open(ns.out, "w") as f:
            f.write(text)
    else:
        sys.stdout.write(text)


if __name__ == "__main__":
    from apex_tpu.utils.platform import setup_compile_cache
    setup_compile_cache()
    main()
