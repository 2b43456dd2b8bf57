#!/usr/bin/env python3
"""One run of one cell of the benchmark of record.

    python3 benchmarks/run.py --workload <config>.<mix> --seed N \\
        --seconds S --trace 0|1

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics, or
with ``--trace 1`` its per-layer metrics), ``device`` and, traced,
``breakdown``.  Without a TPU holding the chips the cell asks for the run
fails and prints no result.  ``--rehearsal`` is for the CPU: tiny sizes
from the data files' ``rehearsal`` groups, platform ``cpu`` on the line and
no metric under a device metric's name.
"""

import time
T_START = time.perf_counter()

import argparse     # noqa: E402
import json         # noqa: E402
import os           # noqa: E402
import shutil       # noqa: E402
import sys          # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

JOBS = {"train": ("train", "run"), "serve_open": ("serve", "run_open"),
        "serve_backlog": ("serve", "run_backlog")}


def parse(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--seconds", type=float, default=None)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--rehearsal", action="store_true",
                   help="CPU, tiny sizes; reports no device metric")
    p.add_argument("--sweep", default=None,
                   help="open-loop cells: comma-separated rates to try, one "
                        "after another, in place of a run")
    return p.parse_args(argv)


def backend(chips, rehearsal):
    """JAX, its compile cache at a fixed place, and the cell's chips; a run
    that is not a rehearsal refuses anything but a TPU."""
    if rehearsal:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = os.environ.get("XLA_FLAGS", "") + \
            f" --xla_force_host_platform_device_count={chips}"
    import jax
    if "JAX_COMPILATION_CACHE_DIR" not in os.environ:
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(ROOT, ".jax_cache"))
    # the engine's small eager programs compile in well under a second
    # each; uncached, every run would compile them again inside set-up
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    devices = jax.devices()
    if devices[0].platform != "tpu" and not rehearsal:
        raise SystemExit(f"the benchmark runs on a TPU, JAX found "
                         f"{devices[0].platform!r}")
    if len(devices) < chips:
        raise SystemExit(f"the cell asks for {chips} chips, JAX found "
                         f"{len(devices)}")
    return devices[:chips]


def main(argv=None):
    args = parse(argv)
    from benchmarks.harness import manifest, peaks, readers
    from benchmarks.harness.job import Context
    from benchmarks.harness import trace as tracing
    man = manifest.Manifest(ROOT)
    cell = man.cell(args.workload)
    config, mix = cell.config, cell.traffic
    if args.rehearsal:
        config = manifest.with_rehearsal(config)
        mix = manifest.with_rehearsal(mix)
    seconds = args.seconds if args.seconds is not None \
        else man.data["run_seconds"]
    devices = backend(cell.chips, args.rehearsal)
    ctx = Context(root=ROOT, config=config, traffic=mix, chips=cell.chips,
                  seed=args.seed, seconds=seconds, trace=bool(args.trace),
                  devices=devices, t_start=T_START)
    ctx.lap("imports_and_backend")
    shutil.rmtree(ctx.trace_dir, ignore_errors=True)
    module, entry = JOBS[mix["job"]]
    job = __import__(f"benchmarks.harness.{module}", fromlist=[entry])
    if args.sweep:
        job.sweep(ctx, [float(r) for r in args.sweep.split(",")])
        return
    run = getattr(job, entry)(ctx)

    setup_s = sum(ctx.setup.values())
    print("notes:", "; ".join(run.notes))
    print("setup:", json.dumps({k: round(v, 3) for k, v in ctx.setup.items()}))
    # the runtime's peak_bytes_in_use counts live arrays; a program's
    # temporaries are what it reserves beside them
    stats = [d.memory_stats() or {} for d in devices]
    print("memory:", json.dumps({k: max(s.get(k, 0) for s in stats) for k in (
        "peak_bytes_in_use", "peak_bytes_reserved", "bytes_limit")}))
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": max(
                  s.get("peak_bytes_in_use", 0)
                  + s.get("peak_bytes_reserved", 0) for s in stats)}
    run.facts["memory_peak_bytes"] = device["memory_peak_bytes"]
    line = {"correct": bool(run.correct), "attempted": run.attempted,
            "failed": run.failed, "metrics": {}, "device": device}
    values = {**run.end_to_end, "setup_s": setup_s}
    if args.trace and not args.rehearsal:
        peak = peaks.peaks(device["kind"])
        t = run.trace
        device["busy_s"] = t.mean(lambda d: tracing.busy_s(d, t.window))
        device["window_s"] = t.window_s
        line["breakdown"] = {"device_ops": tracing.top_ops(t),
                             "idle_gaps": tracing.top_gaps(t)}
        for m in cell.per_layer:
            v = getattr(readers, m["reader"])(run, peak, **m.get("args", {}))
            if v is not None:
                line["metrics"][m["name"]] = {"value": v, "unit": m["unit"]}
    elif not args.rehearsal:
        for m in cell.end_to_end:
            if values.get(m["name"]) is not None:
                line["metrics"][m["name"]] = {"value": values[m["name"]],
                                              "unit": m["unit"]}
    else:
        # a CPU run says what the program counts and whether it is right;
        # its times are not the chip's and go under no metric's name
        line["rehearsal"] = {"host_values": values, "samples": {
            k: len(v) for k, v in run.samples.items()}}
    print(json.dumps(line))


if __name__ == "__main__":
    main()
