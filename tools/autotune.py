#!/usr/bin/env python
"""Measured-cost auto-parallel planner (ROADMAP item 1).

Enumerates the joint (dp, tp, pp, sequence-parallel, overlap-chunk,
virtual-stage, microbatch, remat, ZeRO, transport-dtype) space as
validated :class:`~apex_tpu.parallel.plan.ParallelPlan` candidates,
then drives each survivor through three measured gates:

1. **memory prune** — compile the candidate's ACTUAL train step
   (pipeline + optimizer, the program that would run) and reject it
   when :func:`apex_tpu.analysis.memory.estimate_peak_memory` exceeds
   the per-device HBM budget.  No closed-form activation guesses: the
   estimate walks the lowered HLO's live ranges.
2. **cost rank** — predicted step time = compute roofline (flops from
   the 6ND rule, 8ND under remat, calibrated against a matmul timed on
   THIS host, divided by the pipeline's utilization
   ``1 - bubble_fraction``) + communication from
   ``CostModel.predict_stats`` over the candidate's own optimized-HLO
   collectives, with alpha-beta coefficients fitted from ring
   microbenchmarks (``tools/comms_probe.py`` profile, or probed
   in-process when none is given).
3. **measure** — the top-K ranked candidates run for real under the
   timing protocol; the measured winner is emitted.

The emitted JSON is versioned and round-trips through
``ParallelPlan.from_dict``; hand ``load_plan(path)`` to
``HostSignals.request_replan`` and a live ``ElasticTrainer`` re-shards
onto it without a restart.

One process per chip: every candidate is built and measured in this
process over ``jax.devices()``; nothing is started.  On a chip host run
it as the one process that owns the chips (``chip_smoke.py`` calls
:func:`build_train_step` in-process) — a child of a process that has touched JAX
cannot reach them.  Measured times from virtual CPU devices rank CPU
programs, not TPU ones.

Usage:
    XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
        JAX_PLATFORMS=cpu python tools/autotune.py --devices 8 \\
        --out plan.json
    python tools/autotune.py --devices 8 --profile comms_profile.json \\
        --hbm-gb 0.5 --top-k 3 --out plan.json

``--rank-only`` stops after gate 2 (:func:`rank_plans`): enumerate,
prune and rank against the profile without measuring — the shadow
re-rank the parallelism autopilot
(:class:`apex_tpu.resilience.autopilot.ParallelismAutopilot`) runs in
the background when a REFRESHED profile drifts, leaving the live
measurement to its own K-step commit gate:

    python tools/autotune.py --devices 8 --rank-only \\
        --profile refreshed_profile.json --out reranked_plan.json

``--mpmd`` switches to the two-tier cross-pod planner: enumerate
``(pp, per-stage dp x tp, M)`` plans for ``--pods`` pod blocks, price
each under both MPMD schedules with the
:func:`apex_tpu.mpmd.schedule.simulate` event model (ICI edges from
the profile's ``ici`` fits, DCN edges from its ``dcn`` fits or an
explicit ``--dcn alpha,beta``), and emit the winning plan + schedule:

    python tools/autotune.py --devices 8 --mpmd --pods 2 \\
        --dcn 1e-3,1e-9 --out mpmd_plan.json
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
from typing import Any, List, Optional, Sequence, Tuple

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

AUTOTUNE_VERSION = 1

# tiny-GPT default workload: big enough that dp/tp/pp/microbatching all
# change the lowered program, small enough to compile dozens of
# candidates on a CPU host
DEFAULT_MODEL = dict(vocab_size=64, hidden_size=32, num_layers=4,
                     num_attention_heads=4, max_seq_len=16)


@dataclasses.dataclass
class Candidate:
    """One point of the search space and everything measured about it.

    ``status`` walks ``enumerated -> built -> ranked -> measured`` or
    dead-ends at ``rejected`` (invalid knob combination, with the
    validation error as ``reason``) / ``pruned`` (over the HBM budget)
    / ``failed`` (compile error — recorded, not fatal)."""
    plan: Any
    status: str = "enumerated"
    reason: str = ""
    peak_bytes: Optional[int] = None
    xla_peak_bytes: Optional[int] = None
    xla_ratio: Optional[float] = None
    compute_s: Optional[float] = None
    comm_s: Optional[float] = None
    predicted_s: Optional[float] = None
    measured_s: Optional[float] = None

    def to_dict(self) -> dict:
        d = {"plan": (self.plan.to_dict()
                      if hasattr(self.plan, "to_dict") else self.plan),
             "status": self.status}
        for f in ("reason", "peak_bytes", "xla_peak_bytes", "xla_ratio",
                  "compute_s", "comm_s", "predicted_s", "measured_s"):
            v = getattr(self, f)
            if v not in (None, ""):
                d[f] = v
        return d


# -- search-space enumeration -------------------------------------------------


def _divisors(n: int) -> List[int]:
    return [d for d in range(1, n + 1) if n % d == 0]


def _reject_weight_quant(cfg_kw: dict) -> None:
    """The autotuner enumerates TRAINING plans — every candidate is a
    compiled grad step (build_train_step -> pipeline_step), which int8
    decode weights cannot feed.  Reject at the door with the fix."""
    if cfg_kw.get("weight_quant") is not None:
        raise ValueError(
            f"cfg_kw['weight_quant']={cfg_kw['weight_quant']!r}: the "
            "autotune space is training plans (pipeline_step grad "
            "builds), and weight_quant is decode/prefill-only — drop it "
            "from cfg_kw here and set it on the serving GPTConfig, "
            "where the inference engine quantizes at init")


def enumerate_space(n_devices: int, *, n_layers: int, n_heads: int,
                    batch: int, seq: int, max_tp: Optional[int] = None,
                    max_pp: Optional[int] = None, zero: bool = True,
                    remat_options: Sequence[bool] = (False, True),
                    overlap_options: Sequence[int] = (0, 2),
                    ) -> List[Candidate]:
    """All candidate plans for ``n_devices``, valid and rejected alike.

    Rejections are kept (status ``rejected`` with the reason) so the
    emitted report shows WHY a corner of the space is empty — the
    engine constraints (TP-in-pipeline requires SP, interleaved needs
    ``M % pp == 0``, ZeRO layouts are global-shape-only so
    ``zero_shard > 1`` is gated to ``tp == pp == 1``) prune far more
    than the divisibility arithmetic does.
    """
    from apex_tpu.parallel.plan import ParallelPlan

    out: List[Candidate] = []
    seen = set()

    def reject(reason, **kw):
        key = ("r", tuple(sorted(kw.items())))
        if key not in seen:
            seen.add(key)
            out.append(Candidate(plan=dict(kw), status="rejected",
                                 reason=reason))

    def add(**kw):
        key = ("p", tuple(sorted(kw.items())))
        if key in seen:
            return
        seen.add(key)
        try:
            out.append(Candidate(plan=ParallelPlan(**kw)))
        except ValueError as e:
            out.append(Candidate(plan=dict(kw), status="rejected",
                                 reason=str(e)))

    for dp in _divisors(n_devices):
        for tp in _divisors(n_devices // dp):
            pp = n_devices // (dp * tp)
            if max_tp is not None and tp > max_tp:
                continue
            if max_pp is not None and pp > max_pp:
                continue
            if n_heads % tp:
                reject(f"num_attention_heads={n_heads} not divisible "
                       f"by tp={tp}", dp=dp, tp=tp, pp=pp)
                continue
            if batch % dp:
                reject(f"batch={batch} not divisible by dp={dp}",
                       dp=dp, tp=tp, pp=pp)
                continue
            if n_layers % pp:
                reject(f"num_layers={n_layers} not divisible by pp={pp}",
                       dp=dp, tp=tp, pp=pp)
                continue
            sp_options = [False]
            if tp > 1:
                # the ring engine composes TP only with SP (non-SP TP
                # cotangents are unsound under shard_map); record the
                # non-SP corner as rejected rather than silently absent
                reject("pipeline TP requires sequence parallelism "
                       "(non-SP TP grads are unsound under shard_map)",
                       dp=dp, tp=tp, pp=pp, sequence_parallel=False)
                if seq % tp:
                    reject(f"seq={seq} not divisible by tp={tp} "
                           "(SP shards the sequence axis)",
                           dp=dp, tp=tp, pp=pp, sequence_parallel=True)
                    continue
                sp_options = [True]
            m_options = [1, 2] if pp == 1 else [pp, 2 * pp]
            for sp in sp_options:
                overlaps = [0] + [c for c in overlap_options
                                  if c and sp] if sp else [0]
                for M in m_options:
                    if (batch // dp) % M:
                        reject(f"per-dp batch {batch // dp} not "
                               f"divisible by n_microbatches={M}",
                               dp=dp, tp=tp, pp=pp, n_microbatches=M)
                        continue
                    v_options = [1]
                    if pp > 1 and n_layers % (pp * 2) == 0 and M % pp == 0:
                        v_options.append(2)
                    for v in v_options:
                        if n_layers % (pp * v):
                            continue
                        for remat in remat_options:
                            for ov in overlaps:
                                zeros = [1]
                                if zero and dp > 1 and tp == 1 and pp == 1:
                                    # ZeRO bucket layouts are computed on
                                    # global shapes; only a unit tp x pp
                                    # mesh keeps local == global
                                    zeros.append(dp)
                                for z in zeros:
                                    dtypes = ([None, "bf16"] if z > 1
                                              else [None])
                                    for ad in dtypes:
                                        add(dp=dp, tp=tp, pp=pp,
                                            sequence_parallel=sp,
                                            overlap_chunks=ov,
                                            n_virtual=v,
                                            n_microbatches=M,
                                            remat=remat,
                                            allreduce_dtype=ad,
                                            zero_shard=z)
    return out


# -- candidate train-step construction ----------------------------------------


def build_train_step(plan, cfg_kw: dict, batch: int, seq: int, devices):
    """The candidate's real program: pipelined grad step + optimizer.

    Returns ``(train_step, args, n_params)``.  ``zero_shard > 1``
    candidates route the stacked per-device grads through
    ``DistributedFusedAdam.make_step`` (the reduce-scatter IS the
    gradient reduction); everything else psum-means over ``data``
    inside the region and applies ``FusedAdam`` outside it.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import PartitionSpec as P

    from apex_tpu.models.gpt import (GPTConfig, GPTModel,
                                     pack_for_shard_map, pipeline_step)
    from apex_tpu.optimizers import FusedAdam
    from apex_tpu.parallel import DistributedFusedAdam
    from apex_tpu.resilience.elastic import ElasticPlan

    eplan = ElasticPlan.build(plan, devices=devices)
    mesh = eplan.mesh
    serial = GPTModel(GPTConfig(**cfg_kw))
    params = serial.init_params(jax.random.PRNGKey(0))
    n_params = sum(int(np.prod(l.shape))
                   for l in jax.tree_util.tree_leaves(params))
    par = GPTModel(GPTConfig(plan=plan, **cfg_kw))
    tensor_axis = "model" if plan.tp > 1 else None
    packed, in_specs, local_fn, repack_fn = pack_for_shard_map(
        par, params, n_stages=plan.pp, tensor_axis=tensor_axis,
        n_virtual=plan.n_virtual)
    M = plan.n_microbatches
    mb = batch // (plan.dp * M)
    if mb < 1:
        raise ValueError(f"batch={batch} too small for dp={plan.dp} x "
                         f"M={M}")
    rng = np.random.RandomState(0)
    vocab = cfg_kw["vocab_size"]
    tokens = jnp.asarray(rng.randint(0, vocab, (batch, seq)))
    targets = jnp.asarray(rng.randint(0, vocab, (batch, seq)))
    is_spec = lambda x: isinstance(x, P)  # noqa: E731

    if plan.zero_shard > 1:
        opt = DistributedFusedAdam(lr=1e-3, plan=plan)
        opt_state = opt.make_init(mesh)(packed)
        zero_step = opt.make_step(mesh)

        def grad_step(sp_, tk_, tg_):
            tk = tk_.reshape(M, mb, seq)
            tg = tg_.reshape(M, mb, seq)
            # data_axis=None: grads stay per-device — the ZeRO step's
            # reduce-scatter is the gradient reduction
            loss, g = pipeline_step(par, local_fn(sp_), tk, tg,
                                    pipe_axis="pipe", data_axis=None,
                                    n_virtual=plan.n_virtual)
            # new unit leading axis -> P("data", ...) out_specs stack
            # the per-device grads to (world_size, *param.shape), the
            # layout make_step's reduce-scatter consumes
            g = jax.tree_util.tree_map(lambda x: x[None], repack_fn(g))
            return loss[None], g

        g_specs = jax.tree_util.tree_map(lambda s: P("data", *s),
                                         in_specs, is_leaf=is_spec)

        def train_step(packed_, opt_state_, tk_, tg_):
            loss, grads = jax.shard_map(
                grad_step, mesh=mesh,
                in_specs=(in_specs, P("data"), P("data")),
                out_specs=(P("data"), g_specs),
                check_vma=False)(packed_, tk_, tg_)
            new_p, new_s = zero_step(grads, packed_, opt_state_)
            return loss.mean(), new_p, new_s
    else:
        opt = FusedAdam(lr=1e-3)
        opt_state = opt.init(packed)

        def grad_step(sp_, tk_, tg_):
            tk = tk_.reshape(M, mb, seq)
            tg = tg_.reshape(M, mb, seq)
            loss, g = pipeline_step(par, local_fn(sp_), tk, tg,
                                    pipe_axis="pipe", data_axis="data",
                                    n_virtual=plan.n_virtual)
            return loss, repack_fn(g)

        def train_step(packed_, opt_state_, tk_, tg_):
            loss, grads = jax.shard_map(
                grad_step, mesh=mesh,
                in_specs=(in_specs, P("data"), P("data")),
                out_specs=(P(), in_specs), check_vma=False)(packed_, tk_, tg_)
            new_p, new_s = opt.step(grads, packed_, opt_state_)
            return loss, new_p, new_s

    return train_step, (packed, opt_state, tokens, targets), n_params


# -- cost prediction ----------------------------------------------------------


def calibrate_matmul_flops(n: int = 192) -> float:
    """Achievable matmul flops/s on one device of THIS host — the
    roofline's peak.  A measured constant, not a spec-sheet number, so
    candidate rankings stay meaningful on CPU hosts too."""
    import jax
    import jax.numpy as jnp

    from tools._timing import time_steps

    a = jnp.ones((n, n), jnp.float32)
    f = jax.jit(lambda x, y: x @ y)
    t = time_steps(f, (a, a), warmup=1, iters=4, rounds=3)
    return 2.0 * n ** 3 / max(t, 1e-9)


def predict_compute_s(plan, n_params: int, batch: int, seq: int,
                      flops_per_s: float) -> float:
    """6ND-rule roofline: ``6 * params * tokens`` matmul flops for
    fwd+bwd (8ND under full remat — the recomputed forward), spread
    over the plan's devices, divided by pipeline utilization."""
    from apex_tpu.transformer.pipeline_parallel.ring import bubble_fraction

    flops = 6.0 * float(n_params) * batch * seq
    if plan.remat:
        flops *= 8.0 / 6.0
    t = flops / (plan.n_devices * flops_per_s)
    if plan.pp > 1:
        util = 1.0 - bubble_fraction(plan.n_microbatches, plan.pp,
                                     plan.n_virtual)
        t /= max(util, 1e-9)
    return t


def predict_comm_s(compiled, cost_model, group_size: int) -> float:
    """Communication seconds from the candidate's OWN optimized HLO:
    every collective the compiler actually emitted, priced by the
    fitted alpha-beta ring model."""
    from apex_tpu.observability.comms import hlo_collective_stats

    stats = hlo_collective_stats(compiled.as_text())
    return cost_model.predict_stats(stats, group_size=group_size)["total_s"]


def _default_cost_model(n_devices: int):
    """Probe a minimal in-process profile when no ``--profile`` is
    given: f32-only, three sizes spanning 4K-1M and EVERY ring width
    the mesh supports — the fit extrapolates badly outside the probed
    range (in bytes and in hops alike), and the candidates' gradient
    reductions sit at the top of both."""
    from apex_tpu.observability.costmodel import (fit_cost_model,
                                                  probe_collectives)

    groups = [k for k in (2, 4, 8) if n_devices % k == 0
              and k <= n_devices]
    ms = probe_collectives(dtypes=("f32",),
                           sizes=(1 << 12, 1 << 16, 1 << 20),
                           group_sizes=groups or None, iters=2, rounds=2)
    return fit_cost_model(ms, meta={"source": "autotune-inline-probe"})


# -- two-tier MPMD planner ----------------------------------------------------


def enumerate_mpmd_space(n_devices: int, *, n_layers: int, n_heads: int,
                         batch: int, seq: int, n_pods: int,
                         max_tp: Optional[int] = None) -> List[Candidate]:
    """Cross-pod candidates: ``pp`` stages (a multiple of ``n_pods``)
    times a per-stage ``dp x tp`` mesh, each stage its own program
    (``apex_tpu.mpmd``).  Same keep-the-rejections convention as
    :func:`enumerate_space`; every valid plan carries ``n_pods``."""
    from apex_tpu.parallel.plan import ParallelPlan

    out: List[Candidate] = []
    seen = set()

    def reject(reason, **kw):
        key = ("r", tuple(sorted(kw.items())))
        if key not in seen:
            seen.add(key)
            out.append(Candidate(plan=dict(kw), status="rejected",
                                 reason=reason))

    for pp in _divisors(n_devices):
        if pp < 2 or pp % n_pods:
            continue
        if n_layers % pp:
            reject(f"num_layers={n_layers} not divisible by pp={pp}",
                   pp=pp, n_pods=n_pods)
            continue
        for dp in _divisors(n_devices // pp):
            tp = n_devices // (pp * dp)
            if max_tp is not None and tp > max_tp:
                continue
            if n_heads % tp:
                reject(f"num_attention_heads={n_heads} not divisible "
                       f"by tp={tp}", dp=dp, tp=tp, pp=pp,
                       n_pods=n_pods)
                continue
            if batch % dp:
                reject(f"batch={batch} not divisible by dp={dp}",
                       dp=dp, tp=tp, pp=pp, n_pods=n_pods)
                continue
            sp = tp > 1
            if sp and seq % tp:
                reject(f"seq={seq} not divisible by tp={tp} "
                       "(SP shards the sequence axis)",
                       dp=dp, tp=tp, pp=pp, n_pods=n_pods,
                       sequence_parallel=True)
                continue
            for M in (pp, 2 * pp):
                if (batch // dp) % M:
                    reject(f"per-dp batch {batch // dp} not divisible "
                           f"by n_microbatches={M}", dp=dp, tp=tp,
                           pp=pp, n_pods=n_pods, n_microbatches=M)
                    continue
                key = ("p", dp, tp, pp, M)
                if key in seen:
                    continue
                seen.add(key)
                try:
                    out.append(Candidate(plan=ParallelPlan(
                        dp=dp, tp=tp, pp=pp, sequence_parallel=sp,
                        n_microbatches=M, n_pods=n_pods)))
                except ValueError as e:
                    out.append(Candidate(
                        plan=dict(dp=dp, tp=tp, pp=pp, n_pods=n_pods,
                                  n_microbatches=M),
                        status="rejected", reason=str(e)))
    return out


def simulate_mpmd(plan, schedule_name: str, *, n_params: int,
                  batch: int, seq: int, hidden: int,
                  flops_per_s: float, cost_model=None,
                  dcn: Optional[Tuple[float, float]] = None) -> dict:
    """Price one cross-pod candidate with the schedule simulator.

    Stage compute comes from the 6ND roofline split over ``pp`` stage
    chunks and each stage's ``dp * tp`` devices (backward = 2x
    forward); each edge carries one microbatch's global activation
    (``batch/M * seq * hidden`` f32) priced on ITS link class —
    ``ppermute`` fits from ``cost_model``, or an explicit ``dcn``
    ``(alpha_s, beta_s_per_byte)`` override for the DCN edges.  The
    ``1f1b`` schedule runs with blocking sends (the lockstep/SPMD
    model: every hop sits on the critical path) and ``dcn_hiding``
    with asynchronous sends (the MPMD host model) — the two execution
    semantics the two engines actually have.
    """
    from apex_tpu.mpmd.schedule import (SCHEDULES, edge_link_classes,
                                        simulate)

    S, M = plan.pp, plan.n_microbatches
    tokens_per_mb = (batch // M) * seq
    stage_flops_fwd = 2.0 * (float(n_params) / S) * tokens_per_mb
    t_fwd = stage_flops_fwd / (plan.dp * plan.tp * flops_per_s)
    t_bwd = 2.0 * t_fwd
    act_bytes = (batch // M) * seq * hidden * 4
    classes = edge_link_classes(S, plan.n_pods)
    link_seconds = {}
    for e, lc in classes.items():
        if lc == "dcn" and dcn is not None:
            link_seconds[e] = dcn[0] + dcn[1] * act_bytes
        elif cost_model is not None:
            link_seconds[e] = cost_model.predict(
                "ppermute", act_bytes, 2, link_class=lc)
        else:
            link_seconds[e] = 0.0
    order = SCHEDULES[schedule_name](S, M)
    sim = simulate(order, S, M, t_fwd=t_fwd, t_bwd=t_bwd,
                   link_seconds=link_seconds, link_classes=classes,
                   blocking_sends=(schedule_name == "1f1b"))
    sim["t_fwd"] = t_fwd
    sim["t_bwd"] = t_bwd
    sim["act_bytes"] = act_bytes
    sim["link_seconds"] = {str(e): s for e, s in link_seconds.items()}
    return sim


def autotune_mpmd(n_devices: int, *, cfg_kw: Optional[dict] = None,
                  batch: int = 8, seq: Optional[int] = None,
                  n_pods: int = 2, cost_model=None,
                  dcn: Optional[Tuple[float, float]] = None,
                  max_tp: Optional[int] = None,
                  verbose: bool = True) -> dict:
    """Enumerate and rank two-tier (ICI + DCN) MPMD plans.

    Pure simulation — no per-candidate compiles: the cross-pod search
    only has to order plans by how well their schedule hides the DCN
    edges, and the simulator prices exactly that.  Every candidate is
    scored under BOTH schedules; the report's winner carries the
    schedule name to hand to :class:`~apex_tpu.mpmd.MpmdPipeline`.
    """
    import jax
    import numpy as np

    def say(msg):
        if verbose:
            print(msg, flush=True)

    cfg_kw = dict(cfg_kw or DEFAULT_MODEL)
    _reject_weight_quant(cfg_kw)
    seq = seq if seq is not None else cfg_kw["max_seq_len"]
    if cost_model is None and dcn is None:
        say("no comms profile or --dcn given; probing ici in-process")
        cost_model = _default_cost_model(n_devices)

    from apex_tpu.models.gpt import GPTConfig, GPTModel
    serial = GPTModel(GPTConfig(**cfg_kw))
    params = serial.init_params(jax.random.PRNGKey(0))
    n_params = sum(int(np.prod(l.shape))
                   for l in jax.tree_util.tree_leaves(params))
    flops_per_s = calibrate_matmul_flops()

    cands = enumerate_mpmd_space(
        n_devices, n_layers=cfg_kw["num_layers"],
        n_heads=cfg_kw["num_attention_heads"], batch=batch, seq=seq,
        n_pods=n_pods, max_tp=max_tp)
    valid = [c for c in cands if c.status == "enumerated"]
    say(f"enumerated {len(cands)} cross-pod points: {len(valid)} valid")
    if not valid:
        raise RuntimeError(
            f"no valid MPMD plan for {n_devices} devices / "
            f"{n_pods} pods — see the report's rejection reasons")

    rows = []
    for c in valid:
        for name in ("1f1b", "dcn_hiding"):
            sim = simulate_mpmd(
                c.plan, name, n_params=n_params, batch=batch, seq=seq,
                hidden=cfg_kw["hidden_size"], flops_per_s=flops_per_s,
                cost_model=cost_model, dcn=dcn)
            rows.append({"plan": c.plan.to_dict(), "schedule": name,
                         "predicted_s": sim["makespan"],
                         "bubble_fraction": sim["bubble_fraction"],
                         "dcn_hidden_fraction":
                             sim["hidden_fraction"]["dcn"]})
        c.status = "ranked"
        c.predicted_s = min(r["predicted_s"] for r in rows[-2:])
    rows.sort(key=lambda r: r["predicted_s"])
    win = rows[0]
    say(f"winner: {win['plan']} schedule={win['schedule']} "
        f"pred={win['predicted_s'] * 1e3:.3f} ms/step "
        f"bubble={win['bubble_fraction']:.3f} "
        f"dcn_hidden={win['dcn_hidden_fraction']:.3f}")
    return {
        "version": AUTOTUNE_VERSION,
        "mode": "mpmd",
        "n_devices": n_devices,
        "n_pods": n_pods,
        "model": cfg_kw,
        "batch": batch,
        "seq": seq,
        "flops_per_s": flops_per_s,
        "plan": win["plan"],
        "schedule": win["schedule"],
        "predicted_s": win["predicted_s"],
        "ranked": rows,
        "candidates": [c.to_dict() for c in cands],
    }


# -- the planner --------------------------------------------------------------


def _rank(n_devices, *, cfg_kw, batch, seq, hbm_bytes, cost_model,
          max_tp, max_pp, zero, remat_options, devices, say):
    """Shared enumerate -> compile -> memory-prune -> cost-rank pass.
    Returns ``(cands, ranked, flops_per_s, compiled_by_id)`` — the
    ranked survivors best-first plus the compiled programs keyed by
    candidate identity, so :func:`autotune` can measure the top K
    without recompiling."""
    import jax

    from apex_tpu.analysis.memory import estimate_peak_memory

    cands = enumerate_space(
        n_devices, n_layers=cfg_kw["num_layers"],
        n_heads=cfg_kw["num_attention_heads"], batch=batch, seq=seq,
        max_tp=max_tp, max_pp=max_pp, zero=zero,
        remat_options=remat_options)
    valid = [c for c in cands if c.status == "enumerated"]
    say(f"enumerated {len(cands)} points: {len(valid)} valid plans, "
        f"{len(cands) - len(valid)} rejected")
    if not valid:
        raise RuntimeError("search space is empty; every candidate was "
                           "rejected — see the report's rejection "
                           "reasons")

    flops_per_s = calibrate_matmul_flops()
    say(f"calibrated matmul roofline: {flops_per_s / 1e9:.2f} Gflop/s "
        "per device")

    compiled_by_id = {}
    for c in valid:
        plan = c.plan
        try:
            step, args, n_params = build_train_step(
                plan, cfg_kw, batch, seq, devices)
            compiled = jax.jit(step).lower(*args).compile()
        except Exception as e:  # noqa: BLE001 — a candidate that cannot
            # compile is a data point, not a crash
            c.status, c.reason = "failed", f"{type(e).__name__}: {e}"
            continue
        est = estimate_peak_memory(compiled)
        c.peak_bytes = int(est.peak_bytes)
        c.xla_peak_bytes = est.xla_peak_bytes
        c.xla_ratio = est.xla_ratio
        if est.peak_bytes > hbm_bytes:
            c.status = "pruned"
            c.reason = (f"estimated peak {est.peak_bytes} B over the "
                        f"{int(hbm_bytes)} B per-device budget")
            continue
        c.compute_s = predict_compute_s(plan, n_params, batch, seq,
                                        flops_per_s)
        c.comm_s = predict_comm_s(compiled, cost_model,
                                  group_size=max(plan.dp, plan.tp,
                                                 plan.pp))
        c.predicted_s = c.compute_s + c.comm_s
        c.status = "ranked"
        compiled_by_id[id(c)] = (compiled, args)
    ranked = sorted((c for c in valid if c.status == "ranked"),
                    key=lambda c: c.predicted_s)
    say(f"memory prune: {len(ranked)} survivors of {len(valid)} "
        f"({sum(1 for c in valid if c.status == 'pruned')} over budget, "
        f"{sum(1 for c in valid if c.status == 'failed')} failed)")
    if not ranked:
        raise RuntimeError("no candidate fits the HBM budget; raise "
                           "--hbm-gb or shrink the model")
    return cands, ranked, flops_per_s, compiled_by_id


def rank_plans(n_devices: int, *, cfg_kw: Optional[dict] = None,
               batch: int = 8, seq: Optional[int] = None,
               hbm_bytes: float = 0.5 * (1 << 30), cost_model=None,
               max_tp: Optional[int] = None,
               max_pp: Optional[int] = None, zero: bool = True,
               remat_options: Sequence[bool] = (False, True),
               devices=None, verbose: bool = True) -> dict:
    """Rank-only pass: enumerate -> compile -> prune -> rank against
    the given CostModel WITHOUT the measure phase — the background
    re-rank entry point the parallelism autopilot
    (:class:`apex_tpu.resilience.autopilot.ParallelismAutopilot`) runs
    against a REFRESHED profile: ranking costs compiles, not training
    steps, so it can shadow a live job; the winner is then proven by
    the autopilot's own K-step commit gate instead of an offline
    measurement.  Returns the same report shape as :func:`autotune`
    with ``mode="rank"`` and no ``measured_s``."""
    import jax

    def say(msg):
        if verbose:
            print(msg, flush=True)

    cfg_kw = dict(cfg_kw or DEFAULT_MODEL)
    _reject_weight_quant(cfg_kw)
    seq = seq if seq is not None else cfg_kw["max_seq_len"]
    devices = (list(devices) if devices is not None
               else jax.devices()[:n_devices])
    if len(devices) < n_devices:
        raise RuntimeError(f"need {n_devices} devices, have "
                           f"{len(devices)}")
    if cost_model is None:
        say("no comms profile given; probing a minimal one in-process")
        cost_model = _default_cost_model(n_devices)

    cands, ranked, flops_per_s, _ = _rank(
        n_devices, cfg_kw=cfg_kw, batch=batch, seq=seq,
        hbm_bytes=hbm_bytes, cost_model=cost_model, max_tp=max_tp,
        max_pp=max_pp, zero=zero, remat_options=remat_options,
        devices=devices, say=say)
    winner = ranked[0]
    say(f"winner (ranked, unmeasured): {winner.plan.describe()} "
        f"({winner.predicted_s * 1e3:.3f} ms/step predicted)")
    return {
        "version": AUTOTUNE_VERSION,
        "mode": "rank",
        "n_devices": n_devices,
        "model": cfg_kw,
        "batch": batch,
        "seq": seq,
        "hbm_bytes": int(hbm_bytes),
        "flops_per_s": flops_per_s,
        "plan": winner.plan.to_dict(),
        "predicted_s": winner.predicted_s,
        "candidates": [c.to_dict() for c in cands],
    }


def autotune(n_devices: int, *, cfg_kw: Optional[dict] = None,
             batch: int = 8, seq: Optional[int] = None,
             hbm_bytes: float = 0.5 * (1 << 30), cost_model=None,
             top_k: int = 3, max_tp: Optional[int] = None,
             max_pp: Optional[int] = None, zero: bool = True,
             remat_options: Sequence[bool] = (False, True),
             devices=None, measure_iters: int = 2,
             measure_rounds: int = 2,
             verbose: bool = True) -> dict:
    """Full prune -> rank -> measure pass; returns the report dict
    (the same structure :func:`emit_plan` writes)."""
    import jax

    from tools._timing import time_steps

    def say(msg):
        if verbose:
            print(msg, flush=True)

    cfg_kw = dict(cfg_kw or DEFAULT_MODEL)
    _reject_weight_quant(cfg_kw)
    seq = seq if seq is not None else cfg_kw["max_seq_len"]
    devices = (list(devices) if devices is not None
               else jax.devices()[:n_devices])
    if len(devices) < n_devices:
        raise RuntimeError(f"need {n_devices} devices, have "
                           f"{len(devices)}")
    if cost_model is None:
        say("no comms profile given; probing a minimal one in-process")
        cost_model = _default_cost_model(n_devices)

    cands, ranked, flops_per_s, compiled_by_id = _rank(
        n_devices, cfg_kw=cfg_kw, batch=batch, seq=seq,
        hbm_bytes=hbm_bytes, cost_model=cost_model, max_tp=max_tp,
        max_pp=max_pp, zero=zero, remat_options=remat_options,
        devices=devices, say=say)

    for c in ranked[:top_k]:
        compiled, args = compiled_by_id[id(c)]
        c.measured_s = time_steps(compiled, args, warmup=1,
                                  iters=measure_iters,
                                  rounds=measure_rounds)
        c.status = "measured"
        say(f"  measured {c.plan.describe():<55} "
            f"pred={c.predicted_s * 1e3:8.3f} ms  "
            f"meas={c.measured_s * 1e3:8.3f} ms")
    measured = sorted((c for c in ranked if c.status == "measured"),
                      key=lambda c: c.measured_s)
    winner = measured[0]
    say(f"winner: {winner.plan.describe()} "
        f"({winner.measured_s * 1e3:.3f} ms/step measured)")

    return {
        "version": AUTOTUNE_VERSION,
        "n_devices": n_devices,
        "model": cfg_kw,
        "batch": batch,
        "seq": seq,
        "hbm_bytes": int(hbm_bytes),
        "flops_per_s": flops_per_s,
        "plan": winner.plan.to_dict(),
        "predicted_s": winner.predicted_s,
        "measured_s": winner.measured_s,
        "candidates": [c.to_dict() for c in cands],
    }


# -- emit / load --------------------------------------------------------------


def emit_plan(path: str, report: dict) -> None:
    with open(path, "w") as f:
        json.dump(report, f, indent=1, sort_keys=True)
        f.write("\n")


def load_plan(path: str):
    """The winning :class:`~apex_tpu.parallel.plan.ParallelPlan` from
    an emitted report — hand it straight to
    ``HostSignals.request_replan``.  Version-checked at both layers
    (report envelope here, plan dict in ``ParallelPlan.from_dict``)."""
    from apex_tpu.parallel.plan import ParallelPlan

    with open(path) as f:
        report = json.load(f)
    v = report.get("version")
    if v != AUTOTUNE_VERSION:
        raise ValueError(
            f"autotune report version {v!r} != {AUTOTUNE_VERSION}; "
            "re-run tools/autotune.py to emit a current report")
    return ParallelPlan.from_dict(report["plan"])


# -- CLI ----------------------------------------------------------------------


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--devices", type=int, default=None,
                    help="mesh size to plan for (default: all visible)")
    ap.add_argument("--out", default="autotune_plan.json")
    ap.add_argument("--profile", default=None,
                    help="comms profile JSON from tools/comms_probe.py "
                         "(default: probe a minimal one in-process)")
    ap.add_argument("--hbm-gb", type=float, default=0.5,
                    help="per-device HBM budget for the memory prune")
    ap.add_argument("--top-k", type=int, default=3,
                    help="ranked candidates to measure for real")
    ap.add_argument("--rank-only", action="store_true",
                    help="skip the measure phase: enumerate, prune and "
                         "rank against the profile only — the shadow "
                         "re-rank the parallelism autopilot runs on a "
                         "refreshed profile (the commit gate measures "
                         "the winner live instead)")
    ap.add_argument("--batch", type=int, default=8,
                    help="global batch rows for the probe workload")
    ap.add_argument("--max-tp", type=int, default=None)
    ap.add_argument("--max-pp", type=int, default=None)
    ap.add_argument("--mpmd", action="store_true",
                    help="plan a cross-pod MPMD pipeline "
                         "(apex_tpu.mpmd) instead of a single mesh")
    ap.add_argument("--pods", type=int, default=2,
                    help="pod count for --mpmd (stages split into "
                         "this many contiguous blocks; adjacent "
                         "blocks joined by DCN)")
    ap.add_argument("--dcn", default=None, metavar="ALPHA,BETA",
                    help="price DCN edges as alpha_s,beta_s_per_byte "
                         "instead of a profile's dcn fits (e.g. "
                         "1e-3,1e-9)")
    ap.add_argument("--no-zero", action="store_true",
                    help="drop ZeRO (zero_shard > 1) candidates")
    ap.add_argument("--no-remat", action="store_true",
                    help="search remat=False only (faster compiles)")
    ap.add_argument("-q", "--quiet", action="store_true")
    args = ap.parse_args(argv)

    import jax

    n = args.devices or len(jax.devices())
    cost_model = None
    if args.profile is not None:
        from apex_tpu.observability.costmodel import load_profile
        cost_model, _ = load_profile(args.profile)

    if args.mpmd:
        dcn = None
        if args.dcn is not None:
            a, b = args.dcn.split(",")
            dcn = (float(a), float(b))
        report = autotune_mpmd(
            n, batch=args.batch, n_pods=args.pods,
            cost_model=cost_model, dcn=dcn, max_tp=args.max_tp,
            verbose=not args.quiet)
    elif args.rank_only:
        report = rank_plans(
            n, hbm_bytes=args.hbm_gb * (1 << 30), cost_model=cost_model,
            batch=args.batch, max_tp=args.max_tp,
            max_pp=args.max_pp, zero=not args.no_zero,
            remat_options=(False,) if args.no_remat else (False, True),
            verbose=not args.quiet)
    else:
        report = autotune(
            n, hbm_bytes=args.hbm_gb * (1 << 30), cost_model=cost_model,
            top_k=args.top_k, batch=args.batch, max_tp=args.max_tp,
            max_pp=args.max_pp, zero=not args.no_zero,
            remat_options=(False,) if args.no_remat else (False, True),
            verbose=not args.quiet)
    emit_plan(args.out, report)
    if not args.quiet:
        print(f"wrote {args.out}")
    return 0


if __name__ == "__main__":
    from apex_tpu.utils.platform import setup_compile_cache
    setup_compile_cache()
    sys.exit(main())
