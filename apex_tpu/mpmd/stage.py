"""``StageProgram`` — one pipeline stage as its own compiled SPMD world.

Where the ring engine compiles ALL stages into one program over one
mesh, the MPMD engine gives every stage its own mesh (an intra-pod
``dp x tp`` :class:`~apex_tpu.resilience.elastic.ElasticPlan` build),
its own packed parameters
(:func:`~apex_tpu.models.gpt.pack_for_shard_map` with ``n_stages=1``)
and its own small set of jitted ``shard_map`` programs:

* first stage: ``embed`` (token embedding for all microbatches at
  once, exactly the ring's flattened-batch embed), ``fwd``/``bwd``
  that slice microbatch ``m`` out of the stacked activations, and
  ``embed_bwd`` (the embedding pullback + tied-head gradient merge +
  data-axis pmean);
* interior stages: ``fwd`` and a recompute-``bwd`` (local ``jax.vjp``
  of the stage forward — the ring's activation-recompute discipline,
  which also sidesteps the jax 0.4.x psum-transpose bug the ring
  documents);
* last stage: a joint ``bwd`` that recomputes the stage forward AND
  the loss head under one vjp seeded ``(0, 1/M)`` — byte-for-byte the
  ring's last-stage tick.

Per-microbatch gradient accumulators keep a leading data axis
(``P("data", ...)``) so each data shard accumulates exactly what its
ring counterpart accumulates; the ``finish`` programs apply the same
``pmean`` over ``data`` the ring applies.  That is what makes a
2-stage MPMD run bitwise-equal (f32) to the ring engine — asserted by
``tests/test_mpmd.py::test_engine_grads_bitwise_vs_ring``.

Every backward program donates its accumulator arguments and the
optimizer step donates params + state, so steady-state HBM holds one
copy of each.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional

__all__ = ["StageProgram"]


def _dyn0(tree, i):
    import jax
    return jax.tree_util.tree_map(
        lambda a: jax.lax.dynamic_index_in_dim(a, i, 0, keepdims=False),
        tree)


class StageProgram:
    """One stage's parameters, mesh and compiled programs.

    ``cfg`` is this stage's :class:`~apex_tpu.models.gpt.GPTConfig`
    (``num_layers`` = layers per stage, TP/SP knobs from the intra-pod
    plan); ``stage_params`` the serial-layout dict holding this
    stage's layer chunk plus the (replicated) embedding / final-LN
    copies; ``plan`` the intra-pod :class:`ParallelPlan`
    (``pp == 1``); ``devices`` this pod's device slice.
    """

    def __init__(self, cfg, stage_params, *, stage_index: int,
                 n_stages: int, n_microbatches: int, plan, devices,
                 optimizer=None, lr: float = 1e-3):
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P

        from apex_tpu.models.gpt import GPTModel, pack_for_shard_map
        from apex_tpu.optimizers import FusedAdam
        from apex_tpu.resilience.elastic import ElasticPlan

        if cfg.n_experts > 0:
            raise ValueError(
                "MPMD v1 does not support MoE stages (expert-parallel "
                "collectives inside a stage program are untested "
                "against the cross-pod schedule); use the single-mesh "
                "ring engine for MoE models")
        if cfg.tensor_parallel_size > 1 and not cfg.sequence_parallel:
            raise ValueError(
                "MPMD stages under tensor parallelism require "
                "sequence_parallel=True — same rule as pipeline_step: "
                "the recompute backward never crosses shard_map's "
                "auto-psum, only the SP custom-VJP mappings reduce "
                "replicated-leaf grads")
        self.cfg = cfg
        self.index = int(stage_index)
        self.n_stages = int(n_stages)
        self.M = int(n_microbatches)
        self.is_first = self.index == 0
        self.is_last = self.index == self.n_stages - 1
        self.plan = plan
        self.model = GPTModel(cfg)
        self.sp = self.model._sp_enabled()
        self.dp = int(plan.dp)
        self.tp = int(plan.tp)
        self.inv_m = jnp.float32(1.0 / self.M)

        self.elastic = ElasticPlan.build(plan, devices=devices)
        self.mesh = self.elastic.mesh
        tensor_axis = "model" if self.tp > 1 else None
        (self.packed, self.in_specs, self._local_fn,
         self._repack_fn) = pack_for_shard_map(
            self.model, stage_params, n_stages=1,
            tensor_axis=tensor_axis)

        # -- the train state is only what this stage's role updates --
        self.embed_keys = (["embedding"]
                           + ([] if cfg.rotary else ["position_embedding"]))
        keys = ["layers"]
        if self.is_first:
            keys = self.embed_keys + keys
        if self.is_last:
            keys += ["final_layernorm"]
            if not self.is_first:
                keys += ["embedding"]     # tied-head replica
        self.state_keys = keys
        self.state = {k: self.packed[k] for k in keys}
        self.opt = optimizer if optimizer is not None else FusedAdam(lr=lr)
        self.opt_state = self.opt.init(self.state)

        # -- activation / accumulator placements ----------------------
        mspec = "model" if self.sp else None
        self.act_spec = P("data", None, mspec)          # (dp, mb, s, h)
        self.acts_spec = P("data", None, None, mspec)   # (dp, M, mb, s, h)
        self._P, self._NS = P, NamedSharding
        self.act_sharding = NamedSharding(self.mesh, self.act_spec)
        self.acts_sharding = NamedSharding(self.mesh, self.acts_spec)
        self.last_keys = ["final_layernorm", "embedding"]
        self._build_programs()

    # -- packing helpers (data-axis-leading accumulators) -----------------

    def sharding(self, spec):
        return self._NS(self.mesh, spec)

    def _subspecs(self, keys):
        return {k: self.in_specs[k] for k in keys}

    def _acc_specs(self, keys):
        """in_specs with a leading ``"data"`` axis on every leaf — the
        per-data-shard accumulator placement."""
        import jax
        from apex_tpu.models.gpt import _is_spec_leaf
        P = self._P
        return jax.tree_util.tree_map(
            lambda s: P(*(("data",) + tuple(s))), self._subspecs(keys),
            is_leaf=_is_spec_leaf)

    def shardings_of(self, spec_tree):
        """NamedShardings on this stage's mesh for a PartitionSpec
        pytree (e.g. a subtree of ``in_specs`` / ``_acc_specs``)."""
        import jax
        from apex_tpu.models.gpt import _is_spec_leaf
        return jax.tree_util.tree_map(
            lambda s: self._NS(self.mesh, s), spec_tree,
            is_leaf=_is_spec_leaf)

    def _local(self, tree: Dict[str, Any]):
        return self._local_fn(tree)

    def _acc_local(self, tree: Dict[str, Any]):
        import jax
        return self._local_fn(jax.tree_util.tree_map(
            lambda a: a[0], tree))

    def _acc_repack(self, tree: Dict[str, Any]):
        import jax
        return jax.tree_util.tree_map(
            lambda a: a[None], self._repack_fn(tree))

    def fresh_acc(self, keys) -> Dict[str, Any]:
        """Zeroed per-data-shard accumulator for ``keys`` — donated by
        the backward programs, so a fresh one is placed every step."""
        import jax
        import jax.numpy as jnp
        return jax.tree_util.tree_map(
            lambda leaf, spec: jax.device_put(
                jnp.zeros((self.dp,) + leaf.shape, leaf.dtype),
                self.sharding(spec)),
            {k: self.packed[k] for k in keys}, self._acc_specs(keys))

    def fresh_loss_acc(self):
        import jax
        import jax.numpy as jnp
        return jax.device_put(jnp.zeros((self.dp,), jnp.float32),
                              self.sharding(self._P("data")))

    def fresh_dx0(self, act_shape, dtype):
        """Zeroed ``(dp, M, mb, s, h)`` buffer the first stage's
        backward scatters per-microbatch input cotangents into — the
        engine-side image of the ring's ``dx0_acc``."""
        import jax
        import jax.numpy as jnp
        return jax.device_put(jnp.zeros(act_shape, dtype),
                              self.acts_sharding)

    # -- program construction ---------------------------------------------

    def _stage_fn(self):
        from apex_tpu.models.gpt import make_stage_fn
        return make_stage_fn(self.model, None)

    def _last_fn(self):
        import jax.numpy as jnp
        model = self.model

        def last_fn(lp, y, tgt, info):
            if self.sp:
                y = model._sp_gather(y)
            return jnp.mean(model.head_loss(lp, y, tgt))

        return last_fn

    def _shmap(self, body, in_specs, out_specs, donate=()):
        import jax
        fn = jax.shard_map(body, mesh=self.mesh, in_specs=in_specs,
                           out_specs=out_specs, check_vma=False)
        return jax.jit(fn, donate_argnums=tuple(donate))

    def _build_programs(self):
        import jax
        import jax.numpy as jnp
        from apex_tpu.transformer.pipeline_parallel import JobInfo

        P = self._P
        model, M = self.model, self.M
        stage_fn = self._stage_fn()
        tmap = jax.tree_util.tree_map

        def info(m):
            return JobInfo(m, jnp.int32(self.index), jnp.int32(0))

        layer_specs = self._subspecs(["layers"])["layers"]
        layer_acc_specs = self._acc_specs(["layers"])["layers"]

        if self.is_first:
            embed_specs = self._subspecs(self.embed_keys)

            def embed_fn_of(tokens):
                # the ring's flattened-batch embed: per-token lookup,
                # so one (M*mb, s) embed is bitwise the M per-mb embeds
                def embed_fn(ep):
                    x = model.embed(ep, tokens)
                    if self.sp:
                        x = model._sp_scatter(x)
                    return x.reshape((M, -1) + x.shape[1:])
                return embed_fn

            def embed_body(ep, tokens):
                x = embed_fn_of(tokens)(self._local(ep))
                return x[None]

            self._embed = self._shmap(
                embed_body, (embed_specs, P("data")), self.acts_spec)

            def fwd0_body(lp, x_all, m):
                chunk = self._local({"layers": lp})["layers"]
                xm = _dyn0(x_all[0], m)
                return stage_fn(chunk, xm, info(m))[None]

            self._fwd = self._shmap(
                fwd0_body, (layer_specs, self.acts_spec, P()),
                self.act_spec)

            def bwd0_body(lp, x_all, dy, sacc, dx0, m):
                chunk = self._local({"layers": lp})["layers"]
                xm = _dyn0(x_all[0], m)

                def f(cp, xx):
                    return stage_fn(cp, xx, info(m))

                _, pull = jax.vjp(f, chunk, xm)
                dcp, dx = pull(dy[0])
                acc = self._acc_local({"layers": sacc})["layers"]
                acc = tmap(lambda a, g: a + g, acc, dcp)
                new_dx0 = dx0[0].at[m].add(dx)
                return (self._acc_repack({"layers": acc})["layers"],
                        new_dx0[None])

            self._bwd = self._shmap(
                bwd0_body,
                (layer_specs, self.acts_spec, self.act_spec,
                 layer_acc_specs, self.acts_spec, P()),
                (layer_acc_specs, self.acts_spec), donate=(3, 4))

            emb_acc_specs = self._acc_specs(["embedding"])["embedding"]

            def embed_bwd_body(ep, tokens, dx_all, head_eg):
                p = self._local(ep)
                _, pull = jax.vjp(embed_fn_of(tokens), p)
                (eg,) = pull(dx_all[0])
                heg = self._acc_local(
                    {"embedding": head_eg})["embedding"]
                eg = dict(eg)
                # tied weight: add the head's contribution BEFORE the
                # data pmean (the ring sums then pmeans; pmean(a)+
                # pmean(b) is not bitwise pmean(a+b))
                eg["embedding"] = tmap(jnp.add, eg["embedding"], heg)
                eg = tmap(lambda g: jax.lax.pmean(g, "data"), eg)
                return self._repack_fn(eg)

            self._embed_bwd = self._shmap(
                embed_bwd_body,
                (embed_specs, P("data"), self.acts_spec, emb_acc_specs),
                embed_specs)

        elif not self.is_last:
            def fwd_body(lp, x, m):
                chunk = self._local({"layers": lp})["layers"]
                return stage_fn(chunk, x[0], info(m))[None]

            self._fwd = self._shmap(
                fwd_body, (layer_specs, self.act_spec, P()),
                self.act_spec)

            def bwd_body(lp, x, dy, sacc, m):
                chunk = self._local({"layers": lp})["layers"]

                def f(cp, xx):
                    return stage_fn(cp, xx, info(m))

                _, pull = jax.vjp(f, chunk, x[0])
                dcp, dx = pull(dy[0])
                acc = self._acc_local({"layers": sacc})["layers"]
                acc = tmap(lambda a, g: a + g, acc, dcp)
                return (self._acc_repack({"layers": acc})["layers"],
                        dx[None])

            self._bwd = self._shmap(
                bwd_body,
                (layer_specs, self.act_spec, self.act_spec,
                 layer_acc_specs, P()),
                (layer_acc_specs, self.act_spec), donate=(3,))

        if self.is_last:
            last_fn = self._last_fn()
            state_specs = self._subspecs(["layers"] + self.last_keys)
            last_acc_specs = self._acc_specs(self.last_keys)

            def bwd_last_body(sp, targets, x, sacc, lacc, loss_acc, m):
                loc = self._local(sp)
                chunk = loc["layers"]
                lp = {k: loc[k] for k in self.last_keys}
                tgt = _dyn0(targets[0], m)

                def job(cp, lpp, xx):
                    y = stage_fn(cp, xx, info(m))
                    return y, last_fn(lpp, y, tgt, info(m))

                (y_b, lm), pull = jax.vjp(job, chunk, lp, x[0])
                # the ring's last-stage seed: zero the activation
                # cotangent, seed the loss at 1/M
                dy = tmap(jnp.zeros_like, y_b)
                dcp, dlp, dx = pull((dy, self.inv_m))
                acc = self._acc_local({"layers": sacc})["layers"]
                acc = tmap(lambda a, g: a + g, acc, dcp)
                lac = self._acc_local(lacc)
                lac = tmap(lambda a, g: a + g, lac, dlp)
                return (self._acc_repack({"layers": acc})["layers"],
                        self._acc_repack(lac), loss_acc + lm, dx[None])

            self._bwd_last = self._shmap(
                bwd_last_body,
                (state_specs, P("data"), self.act_spec,
                 layer_acc_specs, last_acc_specs, P("data"), P()),
                (layer_acc_specs, last_acc_specs, P("data"),
                 self.act_spec),
                donate=(3, 4, 5))

            fln_specs = self._subspecs(["final_layernorm"])

            def finish_last_body(lacc):
                g = self._acc_local(
                    {"final_layernorm": lacc["final_layernorm"]})
                g = tmap(lambda a: jax.lax.pmean(a, "data"), g)
                return self._repack_fn(g)

            self._finish_last = self._shmap(
                finish_last_body, (last_acc_specs,), fln_specs)

            def loss_final_body(loss_acc):
                return jax.lax.pmean(loss_acc[0] * self.inv_m, "data")

            self._loss_final = self._shmap(
                loss_final_body, (P("data"),), P())

        def finish_body(sacc):
            g = self._acc_local({"layers": sacc})
            g = tmap(lambda a: jax.lax.pmean(a, "data"), g)
            return self._repack_fn(g)["layers"]

        self._finish = self._shmap(
            finish_body, (layer_acc_specs,), layer_specs)

        self._opt_step = jax.jit(
            lambda g, p, o: self.opt.step(g, p, o),
            donate_argnums=(1, 2))

    # -- execution (called by the engine in schedule order) ---------------

    def run_embed(self, tokens):
        return self._embed({k: self.state[k] for k in self.embed_keys},
                           tokens)

    def run_fwd(self, x, m):
        import jax.numpy as jnp
        if self.is_last:
            raise RuntimeError("the last stage's forward is folded "
                               "into its joint backward")
        return self._fwd(self.state["layers"], x, jnp.int32(m))

    def run_bwd(self, x, dy, sacc, m, *, dx0=None):
        import jax.numpy as jnp
        if self.is_first:
            return self._bwd(self.state["layers"], x, dy, sacc, dx0,
                             jnp.int32(m))
        return self._bwd(self.state["layers"], x, dy, sacc,
                         jnp.int32(m))

    def run_bwd_last(self, targets, x, sacc, lacc, loss_acc, m):
        import jax.numpy as jnp
        sp = {k: self.state[k] for k in ["layers"] + self.last_keys}
        return self._bwd_last(sp, targets, x, sacc, lacc, loss_acc,
                              jnp.int32(m))

    def run_embed_bwd(self, tokens, dx0, head_eg):
        return self._embed_bwd(
            {k: self.state[k] for k in self.embed_keys}, tokens, dx0,
            head_eg)

    def run_finish_layers(self, sacc):
        return self._finish(sacc)

    def run_finish_last(self, lacc):
        return self._finish_last(lacc)

    def run_loss_final(self, loss_acc):
        return self._loss_final(loss_acc)

    def apply_grads(self, grads: Dict[str, Any]) -> None:
        """One optimizer step on this stage's state (donated in
        place).  ``grads`` must cover exactly ``state_keys``."""
        g = {k: grads[k] for k in self.state_keys}
        self.state, self.opt_state = self._opt_step(
            g, self.state, self.opt_state)
