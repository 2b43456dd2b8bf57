"""``MpmdPipeline`` — host-driven cross-pod pipeline training.

The engine owns one :class:`~apex_tpu.mpmd.stage.StageProgram` per
pipeline stage (each with its own mesh and intra-pod
:class:`~apex_tpu.parallel.plan.ParallelPlan`), executes jobs in the
order a :mod:`~apex_tpu.mpmd.schedule` produced, and moves stage
boundaries through a :class:`~apex_tpu.mpmd.channel.LocalDcnChannel`
— retrying :class:`~apex_tpu.mpmd.channel.DcnTimeout` drops in place.

Numerics contract: at ``n_pods = pp`` with homogeneous intra-pod
plans, one :meth:`loss_and_grads` is **bitwise-equal (f32)** to the
single-mesh ring engine (:func:`~apex_tpu.models.gpt.pipeline_step`
over a ``dp x pp`` mesh) — the stage programs replay the ring's exact
per-microbatch accumulation (ascending ``m``, loss cotangent seeded
``1/M``, per-data-shard partial sums pmean'd at the end), and the
channel moves bytes verbatim.  Asserted by ``tests/test_mpmd.py``
(``test_engine_loss_bitwise_vs_ring``, ``test_engine_grads_bitwise_vs_ring``).

Tied embedding across pods: the last stage ships its per-data-shard
head gradient to the first stage, which merges it into the embedding
pullback BEFORE the data pmean (the ring's summation order); the
resulting total then ships back so the last stage's embedding replica
applies the identical (elementwise) optimizer update — the two copies
stay bitwise in lockstep without an all-reduce spanning pods.

Integration: :meth:`save_checkpoint` writes per-stage
:class:`~apex_tpu.resilience.checkpoint.CheckpointManager` trees under
one stamped ``MPMD_PLAN.json`` (restore validates the cross-pod plan
and :meth:`restore_stage` re-seats a single killed stage);
``trace=True`` gives every stage a
:class:`~apex_tpu.observability.spans.Tracer` lane, threads
per-microbatch flow events (``dcn_send``/``dcn_recv``) through every
cross-pod hop, and records the structured per-op anatomy events
(``mpmd_op`` compute spans, ``mpmd_xfer`` link spans with their link
class, one ``mpmd_schedule`` marker per step) that
:mod:`apex_tpu.observability.anatomy` reconstructs into a measured
timeline — :meth:`anatomy_events` hands them over, and
``measure_ops=True`` additionally blocks on each op so the spans
measure device time, not dispatch.  :meth:`collector` returns the
:class:`~apex_tpu.observability.fleetobs.FleetCollector` whose
``continuity()`` must come back unbroken.
"""

from __future__ import annotations

import contextlib
import json
import os
from typing import Any, Dict, List, Optional

from apex_tpu.mpmd.channel import DcnTimeout, Edge, LocalDcnChannel
from apex_tpu.mpmd.schedule import SCHEDULES, edge_link_classes
from apex_tpu.mpmd.stage import StageProgram

__all__ = ["MpmdPipeline", "MPMD_PLAN_FILE"]

MPMD_PLAN_FILE = "MPMD_PLAN.json"
_PLAN_VERSION = 1


class MpmdPipeline:
    """Cross-pod MPMD pipeline over per-stage compiled programs.

    ``model_kw`` are the serial :class:`~apex_tpu.models.gpt.GPTConfig`
    kwargs of the FULL model (``num_layers`` total); ``params`` its
    serial-layout init; ``plan`` the cross-pod
    :class:`~apex_tpu.parallel.plan.ParallelPlan` (``pp`` = stage
    count, ``n_pods`` pod blocks, optional per-pod ``stage_plans``).
    """

    def __init__(self, model_kw: Dict[str, Any], params, plan, *,
                 devices=None, lr: float = 1e-3, channel=None,
                 fault_injector=None, schedule: str = "1f1b",
                 trace: bool = False, measure_ops: bool = False):
        import jax

        from apex_tpu.parallel.plan import ParallelPlan

        if plan.pp < 2:
            raise ValueError(
                f"MPMD needs pp >= 2 (got pp={plan.pp}): a one-stage "
                "pipeline has no cross-pod edges — use the single-mesh "
                "engines")
        if plan.n_virtual != 1:
            raise ValueError(
                "MPMD stages are whole programs; the interleaved "
                "schedule (n_virtual > 1) only exists inside the ring "
                "engine's scan")
        if schedule not in SCHEDULES:
            raise ValueError(f"unknown schedule {schedule!r}; "
                             f"one of {sorted(SCHEDULES)}")
        self.plan = plan
        self.n_stages = int(plan.pp)
        self.M = int(plan.n_microbatches)
        self.dp = int(plan.dp)
        self.schedule_name = schedule
        self.order = SCHEDULES[schedule](self.n_stages, self.M)
        self._edge_class = edge_link_classes(self.n_stages, plan.n_pods)
        self.channel = (channel if channel is not None
                        else LocalDcnChannel(
                            fault_injector=fault_injector))

        kw = dict(model_kw)
        n_layers = int(kw.pop("num_layers"))
        if n_layers % self.n_stages:
            raise ValueError(
                f"num_layers ({n_layers}) must divide into pp "
                f"({self.n_stages}) equal stage chunks")
        lpc = n_layers // self.n_stages
        for drop in ("tensor_parallel_size", "axis_name",
                     "sequence_parallel"):
            kw.pop(drop, None)

        per_pod = self.n_stages // plan.n_pods
        devices = list(devices) if devices is not None else jax.devices()
        self.stages: List[StageProgram] = []
        cursor = 0
        for i in range(self.n_stages):
            pod = i // per_pod
            if plan.stage_plans is not None:
                sub = plan.stage_plans[pod]
            else:
                sub = ParallelPlan(
                    dp=plan.dp, tp=plan.tp,
                    sequence_parallel=plan.sequence_parallel)
            from apex_tpu.models.gpt import GPTConfig
            cfg = GPTConfig(
                num_layers=lpc, tensor_parallel_size=sub.tp,
                axis_name="model" if sub.tp > 1 else None,
                sequence_parallel=sub.sequence_parallel, **kw)
            stage_params = {
                "embedding": params["embedding"],
                "final_layernorm": params["final_layernorm"],
                "layers": params["layers"][i * lpc:(i + 1) * lpc],
            }
            if "position_embedding" in params:
                stage_params["position_embedding"] = \
                    params["position_embedding"]
            need = sub.dp * sub.tp
            if cursor + need > len(devices):
                raise ValueError(
                    f"stage {i} needs devices [{cursor}, "
                    f"{cursor + need}) but only {len(devices)} are "
                    f"available; the cross-pod plan wants "
                    f"{plan.n_devices} in total")
            self.stages.append(StageProgram(
                cfg, stage_params, stage_index=i,
                n_stages=self.n_stages, n_microbatches=self.M,
                plan=sub, devices=devices[cursor:cursor + need],
                lr=lr))
            cursor += need

        # measure_ops implies trace: each op's result is blocked on
        # inside its span, so span durations are honest device times —
        # at the cost of serializing dispatch (an anatomy/profiling
        # mode, not the production fast path)
        self.measure_ops = bool(measure_ops)
        self.tracers = None
        if trace or self.measure_ops:
            from apex_tpu.observability.spans import Tracer
            self.tracers = [Tracer(id_tag=f"stage{i}")
                            for i in range(self.n_stages)]
        self.step_count = 0

    # -- transfers --------------------------------------------------------

    def _link_class(self, src: int, dst: int) -> str:
        if abs(src - dst) == 1:
            return self._edge_class.get(min(src, dst), "ici")
        # the tied-embedding sync between the first and last pod
        return "dcn" if self.plan.n_pods > 1 else "ici"

    def _block(self, tree):
        """Wait for every leaf (anatomy mode): span durations then
        measure the work, not just its dispatch."""
        if self.measure_ops:
            import jax
            for leaf in jax.tree_util.tree_leaves(tree):
                blocker = getattr(leaf, "block_until_ready", None)
                if blocker is not None:
                    blocker()
        return tree

    def _op_span(self, s: int, kind: str, m: int, step: int, **extra):
        """The per-op structured trace span anatomy reconstructs from
        (no-op without tracing)."""
        if self.tracers is None:
            return contextlib.nullcontext()
        return self.tracers[s].span(
            "mpmd_op", device=False, op=kind, stage=s, mb=m,
            step=step, **extra)

    def _transfer(self, src: int, dst: int, value, dst_shardings, *,
                  step: int, ctx=None, phase: str = "act"):
        from apex_tpu.observability.fleetobs import emit_flow
        edge = Edge(src, dst, self._link_class(src, dst))
        # phase is "fwd.m3" / "bwd.m5" for schedule edges and
        # "head_grad" / "embed_total" for the tied-embedding sync
        kind, _, mbs = phase.partition(".m")
        cm = contextlib.nullcontext()
        if self.tracers is not None:
            emit_flow(self.tracers[src], ctx, "dcn_send",
                      edge=f"{src}->{dst}", payload=phase)
            cm = self.tracers[src].span(
                "mpmd_xfer", device=False, src=src, dst=dst,
                kind=kind, mb=int(mbs) if mbs else -1,
                link_class=edge.link_class, step=step)
        with cm:
            out = self._block(self.channel.send_with_retry(
                value, dst_shardings, step=step, edge=edge))
        if self.tracers is not None:
            emit_flow(self.tracers[dst], ctx, "dcn_recv",
                      edge=f"{src}->{dst}", payload=phase)
        return out

    # -- tied-embedding repacking across heterogeneous tp -----------------

    def _convert_embed(self, tree, src: StageProgram,
                       dst: StageProgram, *, leading_dp: bool):
        """Re-stack a packed embedding-gradient tree from ``src``'s tp
        layout to ``dst``'s.  Pure split/concat on host, so f32 values
        round-trip bitwise; a no-op when the layouts match."""
        if src.tp == dst.tp:
            return tree
        import jax
        import numpy as np
        from apex_tpu.models.gpt import _is_sharded, _is_spec_leaf
        specs = src.model.partition_specs()["embedding"]
        off = 1 if leading_dp else 0

        def shard_dim(s):
            for d, a in enumerate(s):
                if a is not None:
                    return d
            return None

        def conv(s, a):
            if not _is_sharded(s):
                return a
            d = shard_dim(s) + off + 1   # behind the tp-stack axis
            a = np.asarray(a)
            serial = np.concatenate(
                [a[(slice(None),) * off + (r,)]
                 for r in range(a.shape[off])], axis=d - 1)
            parts = np.split(serial, dst.tp, axis=d - 1)
            return np.stack(parts, axis=off)

        return jax.tree_util.tree_map(conv, specs, tree,
                                      is_leaf=_is_spec_leaf)

    # -- one training step ------------------------------------------------

    def _place_inputs(self, tokens, targets):
        import jax
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        st0, stl = self.stages[0], self.stages[-1]
        tokens = jnp.asarray(tokens)
        targets = jnp.asarray(targets)
        rows, seq = tokens.shape
        if rows != self.dp * self.M * (rows // (self.dp * self.M)):
            raise ValueError(
                f"tokens rows ({rows}) must be dp*M*microbatch "
                f"(dp={self.dp}, M={self.M})")
        mb = rows // (self.dp * self.M)
        tokens_d = jax.device_put(tokens, st0.sharding(P("data")))
        targets_d = jax.device_put(
            targets.reshape(self.dp, self.M, mb, seq),
            stl.sharding(P("data")))
        return tokens_d, targets_d

    def loss_and_grads(self, tokens, targets, *,
                       step: Optional[int] = None):
        """Run one full schedule; returns ``(loss, per-stage grads)``
        with each stage's grads in ITS packed layout, keyed by its
        ``state_keys``."""
        from apex_tpu.observability.fleetobs import (TraceContext,
                                                     emit_flow)
        step = self.step_count if step is None else int(step)
        S, M = self.n_stages, self.M
        st0, stl = self.stages[0], self.stages[-1]
        tokens_d, targets_d = self._place_inputs(tokens, targets)

        accs = [st.fresh_acc(["layers"])["layers"]
                for st in self.stages]
        lacc = stl.fresh_acc(stl.last_keys)
        loss_acc = stl.fresh_loss_acc()
        x_all = st0.run_embed(tokens_d)
        dx0 = st0.fresh_dx0(x_all.shape, x_all.dtype)

        ctxs = {}
        if self.tracers is not None:
            ctxs = {m: TraceContext.mint(f"s{step}.m{m}")
                    for m in range(M)}
            self.tracers[0].instant(
                "mpmd_schedule", n_stages=S, n_microbatches=M,
                schedule=self.schedule_name, step=step, dp=self.dp,
                link_classes={str(e): c for e, c
                              in self._edge_class.items()},
                measured=self.measure_ops)
        stash_x: Dict[Any, Any] = {}
        stash_dy: Dict[Any, Any] = {}

        for s, kind, m in self.order:
            st = self.stages[s]
            ctx = ctxs.get(m)
            if kind == "fwd":
                if st.is_last:
                    continue       # folded into the joint backward
                # interior stages keep their input in the stash: the
                # backward recomputes the stage forward from it
                x = x_all if st.is_first else stash_x[(s, m)]
                with self._op_span(s, "fwd", m, step):
                    y = self._block(st.run_fwd(x, m))
                nxt = self.stages[s + 1]
                stash_x[(s + 1, m)] = self._transfer(
                    s, s + 1, y, nxt.act_sharding, step=step, ctx=ctx,
                    phase=f"fwd.m{m}")
            else:
                if st.is_last:
                    with self._op_span(s, "bwd", m, step,
                                       folded_fwd=True):
                        accs[s], lacc, loss_acc, dx = st.run_bwd_last(
                            targets_d, stash_x.pop((s, m)), accs[s],
                            lacc, loss_acc, m)
                        self._block(dx)
                elif st.is_first:
                    with self._op_span(s, "bwd", m, step):
                        accs[s], dx0 = st.run_bwd(
                            x_all, stash_dy.pop((s, m)), accs[s], m,
                            dx0=dx0)
                        self._block(dx0)
                    if self.tracers is not None:
                        emit_flow(self.tracers[0], ctx, "mb_done",
                                  final=True)
                    continue
                else:
                    with self._op_span(s, "bwd", m, step):
                        accs[s], dx = st.run_bwd(
                            stash_x.pop((s, m)), stash_dy.pop((s, m)),
                            accs[s], m)
                        self._block(dx)
                prv = self.stages[s - 1]
                stash_dy[(s - 1, m)] = self._transfer(
                    s, s - 1, dx, prv.act_sharding, step=step, ctx=ctx,
                    phase=f"bwd.m{m}")

        # -- tied-embedding gradient sync: last -> first -> last ------
        sync_ctx = None
        if self.tracers is not None:
            sync_ctx = TraceContext.mint(f"s{step}.sync")
        head_eg = self._transfer(
            S - 1, 0,
            self._convert_embed(lacc["embedding"], stl, st0,
                                leading_dp=True),
            st0.shardings_of(st0._acc_specs(["embedding"])["embedding"]),
            step=step, ctx=sync_ctx, phase="head_grad")
        g0 = st0.run_embed_bwd(tokens_d, dx0, head_eg)

        grads: List[Dict[str, Any]] = []
        for i, st in enumerate(self.stages):
            gi: Dict[str, Any] = {
                "layers": st.run_finish_layers(accs[i])}
            if st.is_first:
                gi.update(g0)
            if st.is_last:
                gi.update(st.run_finish_last(lacc))
                if not st.is_first:
                    gi["embedding"] = self._transfer(
                        0, S - 1,
                        self._convert_embed(g0["embedding"], st0, stl,
                                            leading_dp=False),
                        stl.shardings_of(stl.in_specs["embedding"]),
                        step=step, ctx=sync_ctx, phase="embed_total")
            grads.append(gi)
        if self.tracers is not None:
            emit_flow(self.tracers[S - 1], sync_ctx, "sync_done",
                      final=True)
        loss = stl.run_loss_final(loss_acc)
        return loss, grads

    def train_step(self, tokens, targets, *,
                   step: Optional[int] = None):
        """Full schedule + per-stage (donated) optimizer step."""
        loss, grads = self.loss_and_grads(tokens, targets, step=step)
        for st, g in zip(self.stages, grads):
            st.apply_grads(g)
        self.step_count += 1
        return loss

    # -- checkpointing ----------------------------------------------------

    def _manager(self, directory: str, i: int, keep: int = 2):
        from apex_tpu.resilience.checkpoint import CheckpointManager
        st = self.stages[i]
        return CheckpointManager(
            os.path.join(directory, f"stage_{i:02d}"), keep=keep,
            topology=st.plan.topology(), parallel_plan=st.plan)

    def save_checkpoint(self, directory: str, step: int, *,
                        keep: int = 2) -> None:
        """Per-stage checkpoint trees under one stamped cross-pod
        plan: ``directory/MPMD_PLAN.json`` + ``directory/stage_XX/``
        per stage — each stage's manifest carries ITS intra-pod plan,
        the top-level stamp the plan that binds them."""
        os.makedirs(directory, exist_ok=True)
        with open(os.path.join(directory, MPMD_PLAN_FILE), "w",
                  encoding="utf-8") as f:
            json.dump({"version": _PLAN_VERSION,
                       "n_stages": self.n_stages,
                       "plan": self.plan.to_dict()}, f, indent=1)
        for i, st in enumerate(self.stages):
            self._manager(directory, i, keep).save(
                step, {"state": st.state, "opt": st.opt_state})

    def _check_plan_stamp(self, directory: str) -> None:
        path = os.path.join(directory, MPMD_PLAN_FILE)
        with open(path, encoding="utf-8") as f:
            stamp = json.load(f)
        if stamp.get("plan") != self.plan.to_dict():
            raise ValueError(
                f"checkpoint at {directory} was saved under cross-pod "
                f"plan {stamp.get('plan')} but this engine runs "
                f"{self.plan.to_dict()}; restore onto a matching "
                "MpmdPipeline (per-stage states are packed for their "
                "stamped intra-pod layouts)")

    def restore_stage(self, i: int, directory: str, *,
                      step: Optional[int] = None,
                      _checked: bool = False) -> int:
        """Re-seat ONE stage from its checkpoint tree — the
        kill-one-stage recovery path: the surviving stages keep their
        live state, the replaced pod reloads."""
        if not _checked:
            self._check_plan_stamp(directory)
        st = self.stages[i]
        loaded, got = self._manager(directory, i).restore(
            {"state": st.state, "opt": st.opt_state}, step=step)
        st.state = loaded["state"]
        st.opt_state = loaded["opt"]
        return got

    def restore_checkpoint(self, directory: str, *,
                           step: Optional[int] = None) -> int:
        """Restore every stage from the newest (or pinned) step after
        validating the cross-pod plan stamp."""
        self._check_plan_stamp(directory)
        got = None
        for i in range(self.n_stages):
            s = self.restore_stage(i, directory, step=step,
                                   _checked=True)
            if got is not None and s != got:
                raise ValueError(
                    f"stage {i} restored step {s} but earlier stages "
                    f"restored {got}; the per-stage trees are torn — "
                    "pin step= to a step present in every stage tree")
            got = s
        self.step_count = int(got)
        return int(got)

    # -- observability ----------------------------------------------------

    def collector(self):
        """A :class:`FleetCollector` with one lane per stage (requires
        ``trace=True``)."""
        if self.tracers is None:
            raise ValueError("engine built with trace=False; pass "
                             "trace=True to collect per-stage lanes")
        from apex_tpu.observability.fleetobs import FleetCollector
        c = FleetCollector()
        for i, tr in enumerate(self.tracers):
            c.add_replica(f"stage{i}", tracer=tr)
        return c

    def anatomy_events(self) -> List[dict]:
        """Every stage tracer's events, merged (the tracers share one
        clock, so timestamps are directly comparable) — the input
        :func:`apex_tpu.observability.anatomy.reconstruct` expects.
        Requires ``trace=True``; pass ``measure_ops=True`` for span
        durations that include device time."""
        if self.tracers is None:
            raise ValueError("engine built with trace=False; pass "
                             "trace=True (or measure_ops=True) to "
                             "record per-op anatomy events")
        events: List[dict] = []
        for tr in self.tracers:
            events.extend(tr.events)
        return events
