"""Expert-parallel MoE (beyond-reference; EP completes the
tp/pp/dp/sp/cp/ep axis set).  Parity: the EP=4 all_to_all dataflow must
equal the serial per-shard computation exactly, forward and gradients."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import PartitionSpec as P

from apex_tpu.transformer.expert_parallel import MoEConfig, MoEMLP


@pytest.fixture
def rng():
    return np.random.RandomState(0)


def serial_cfg(**kw):
    kw.setdefault("hidden_size", 16)
    kw.setdefault("ffn_hidden_size", 32)
    kw.setdefault("n_experts", 8)
    return MoEConfig(**kw)


class TestSerialMoE:
    def test_output_shape_and_aux(self, rng):
        m = MoEMLP(serial_cfg())
        params = m.init_params(jax.random.PRNGKey(0))
        x = jnp.asarray(rng.randn(64, 16), jnp.float32)
        out, aux = jax.jit(m)(params, x)
        assert out.shape == x.shape
        assert float(aux) > 0.0

    def test_capacity_drops_tokens(self, rng):
        # capacity 1 per expert: at most n_experts tokens survive
        m = MoEMLP(serial_cfg(capacity_factor=8.0 / 64.0))
        params = m.init_params(jax.random.PRNGKey(1))
        x = jnp.asarray(rng.randn(64, 16), jnp.float32)
        out, _ = m(params, x)
        nonzero = np.sum(np.any(np.asarray(out) != 0.0, axis=-1))
        assert nonzero <= 8

    def test_matches_dense_reference_when_uncapped(self, rng):
        """With capacity >= tokens nothing is dropped: out ==
        gate_prob * FFN_{argmax expert}(x) for every token."""
        cfg = serial_cfg(capacity_factor=float(8))   # cap = tokens
        m = MoEMLP(cfg)
        params = m.init_params(jax.random.PRNGKey(2))
        x = jnp.asarray(rng.randn(32, 16), jnp.float32)
        out, _ = jax.jit(m)(params, x)

        logits = np.asarray(x @ params["gate"])
        probs = np.asarray(jax.nn.softmax(jnp.asarray(logits), -1))
        idx = probs.argmax(-1)
        ref = np.zeros_like(np.asarray(x))
        for t in range(32):
            e = idx[t]
            h1 = np.maximum(np.asarray(x)[t] @ np.asarray(
                params["w1"])[e], 0.0)
            ref[t] = (h1 @ np.asarray(params["w2"])[e]) * probs[t, e]
        np.testing.assert_allclose(np.asarray(out), ref, rtol=2e-5,
                                   atol=2e-5)


class TestExpertParallel:
    def _setup(self, rng, ep=4, tokens_per_dev=16):
        cfg_s = serial_cfg()
        serial = MoEMLP(cfg_s)
        params = serial.init_params(jax.random.PRNGKey(3))
        x = jnp.asarray(rng.randn(ep * tokens_per_dev, 16), jnp.float32)
        cfg_p = serial_cfg(expert_parallel_size=ep, axis_name="expert")
        par = MoEMLP(cfg_p)
        nl = cfg_p.local_experts
        # shard the expert stacks over the leading axis; gate replicated
        sharded = {"gate": params["gate"],
                   "w1": params["w1"].reshape(ep, nl, *params["w1"].shape[1:]),
                   "w2": params["w2"].reshape(ep, nl, *params["w2"].shape[1:])}
        specs = {"gate": P(), "w1": P("expert"), "w2": P("expert")}
        return serial, params, par, sharded, specs, x

    def test_forward_matches_serial_shards(self, rng):
        serial, params, par, sharded, specs, x = self._setup(rng)
        mesh = jax.make_mesh((4,), ("expert",))

        def local(p, xl):
            p = dict(p, w1=p["w1"][0], w2=p["w2"][0])
            out, aux = par(p, xl)
            return out, aux[None]          # per-device aux, stacked

        out, aux = jax.jit(shard_map(
            local, mesh=mesh, in_specs=(specs, P("expert")),
            out_specs=(P("expert"), P("expert")), check_vma=False))(sharded, x)

        # serial reference: same per-shard capacity semantics
        refs, auxes = [], []
        for s in range(4):
            o, a = serial(params, x[s * 16:(s + 1) * 16])
            refs.append(np.asarray(o))
            auxes.append(float(a))
        np.testing.assert_allclose(np.asarray(out),
                                   np.concatenate(refs), rtol=2e-5,
                                   atol=2e-5)
        np.testing.assert_allclose(np.asarray(aux), np.asarray(auxes),
                                   rtol=1e-5)

    def test_grads_match_serial_shards(self, rng):
        serial, params, par, sharded, specs, x = self._setup(rng)
        mesh = jax.make_mesh((4,), ("expert",))

        def ep_loss(p, xl):
            p = dict(p, w1=p["w1"][0], w2=p["w2"][0])
            out, aux = par(p, xl)
            loss = jnp.sum(out.astype(jnp.float32) ** 2)
            return jax.lax.psum(loss, "expert") + 0.01 * jax.lax.pmean(
                aux, "expert")

        def local(p, xl):
            # expert-stack grads are PER-SHARD (sharded params -> no
            # reduction); the replicated gate's grad is auto-psummed
            return jax.grad(ep_loss)(p, xl)

        grads = jax.jit(shard_map(
            local, mesh=mesh, in_specs=(specs, P("expert")),
            out_specs=specs, check_vma=True))(sharded, x)

        def serial_loss(p):
            total = 0.0
            for s in range(4):
                out, aux = serial(p, x[s * 16:(s + 1) * 16])
                total = total + jnp.sum(out.astype(jnp.float32) ** 2) \
                    + 0.01 * aux / 4
            return total

        ref = jax.grad(serial_loss)(params)
        np.testing.assert_allclose(
            np.asarray(grads["gate"]), np.asarray(ref["gate"]),
            rtol=5e-4, atol=1e-5)
        for k in ("w1", "w2"):
            got = np.asarray(grads[k]).reshape(np.asarray(ref[k]).shape)
            np.testing.assert_allclose(got, np.asarray(ref[k]),
                                       rtol=5e-4, atol=1e-5)


class TestTopKRouting:
    """top_k=2 (GShard) routing: renormalized gates, second choices
    claim slots after all first choices."""

    def test_top2_uncapped_matches_dense(self, rng):
        cfg = serial_cfg(top_k=2, capacity_factor=float(8))
        m = MoEMLP(cfg)
        params = m.init_params(jax.random.PRNGKey(5))
        x = jnp.asarray(rng.randn(16, 16), jnp.float32)
        out, _ = jax.jit(m)(params, x)

        probs = np.asarray(jax.nn.softmax(
            jnp.asarray(np.asarray(x @ params["gate"])), -1))
        ref = np.zeros((16, 16), np.float32)
        for t in range(16):
            top2 = np.argsort(probs[t])[::-1][:2]
            norm = probs[t, top2].sum()
            for e in top2:
                h1 = np.maximum(np.asarray(x)[t] @ np.asarray(
                    params["w1"])[e], 0.0)
                ref[t] += (h1 @ np.asarray(params["w2"])[e]) \
                    * probs[t, e] / norm
        np.testing.assert_allclose(np.asarray(out), ref, rtol=2e-5,
                                   atol=2e-5)

    def test_top2_ep_matches_serial(self, rng):
        cfg_s = serial_cfg(top_k=2)
        serial = MoEMLP(cfg_s)
        params = serial.init_params(jax.random.PRNGKey(6))
        x = jnp.asarray(rng.randn(64, 16), jnp.float32)
        cfg_p = serial_cfg(top_k=2, expert_parallel_size=4,
                           axis_name="expert")
        par = MoEMLP(cfg_p)
        nl = cfg_p.local_experts
        sharded = {"gate": params["gate"],
                   "w1": params["w1"].reshape(4, nl, *params["w1"].shape[1:]),
                   "w2": params["w2"].reshape(4, nl, *params["w2"].shape[1:])}
        specs = {"gate": P(), "w1": P("expert"), "w2": P("expert")}
        mesh = jax.make_mesh((4,), ("expert",))

        def local(p, xl):
            p = dict(p, w1=p["w1"][0], w2=p["w2"][0])
            return par(p, xl)[0]

        out = jax.jit(shard_map(
            local, mesh=mesh, in_specs=(specs, P("expert")),
            out_specs=P("expert"), check_vma=False))(sharded, x)
        refs = [np.asarray(serial(params, x[s * 16:(s + 1) * 16])[0])
                for s in range(4)]
        np.testing.assert_allclose(np.asarray(out),
                                   np.concatenate(refs), rtol=2e-5,
                                   atol=2e-5)

    def test_second_choice_capacity_after_first(self, rng):
        """capacity 1: each expert serves exactly the first token that
        claims it — a second choice lands only on experts no FIRST
        choice claimed (slot ordering, checked against a reference)."""
        m = MoEMLP(serial_cfg(top_k=2,
                              capacity_factor=8.0 / (2 * 64.0)))
        params = m.init_params(jax.random.PRNGKey(7))
        x = jnp.asarray(rng.randn(64, 16), jnp.float32)
        out, _ = m(params, x)

        probs = np.asarray(jax.nn.softmax(
            jnp.asarray(np.asarray(x @ params["gate"])), -1))
        order = np.argsort(probs, axis=-1)[:, ::-1]
        first, second = order[:, 0], order[:, 1]
        # reference slot assignment: first choices in token order, then
        # second choices in token order; capacity 1 per expert
        served = {}          # expert -> (token, choice_prob_weight)
        for t in range(64):
            if first[t] not in served:
                norm = probs[t, first[t]] + probs[t, second[t]]
                served[first[t]] = (t, 0, probs[t, first[t]] / norm)
        for t in range(64):
            if second[t] not in served:
                norm = probs[t, first[t]] + probs[t, second[t]]
                served[second[t]] = (t, 1, probs[t, second[t]] / norm)
        expected = {t for (t, _c, _w) in served.values()}
        got = set(np.where(np.any(np.asarray(out) != 0.0, axis=-1))[0])
        assert got == expected, (sorted(got), sorted(expected))

    def test_invalid_topk_raises(self):
        with pytest.raises(ValueError):
            serial_cfg(top_k=0)
        with pytest.raises(ValueError):
            serial_cfg(top_k=9)


class TestSwitchGPT:
    """MoE wired into the GPT flagship (cfg.n_experts > 0)."""

    def _cfg(self, **kw):
        from apex_tpu.models.gpt import GPTConfig
        kw.setdefault("vocab_size", 32)
        kw.setdefault("hidden_size", 16)
        kw.setdefault("num_layers", 2)
        kw.setdefault("num_attention_heads", 4)
        kw.setdefault("max_seq_len", 16)
        kw.setdefault("n_experts", 4)
        return GPTConfig(**kw)

    def test_trains_and_aux_contributes(self, rng):
        from apex_tpu.models.gpt import GPTModel

        model = GPTModel(self._cfg())
        params = model.init_params(jax.random.PRNGKey(0))
        tokens = jnp.asarray(rng.randint(0, 32, (2, 16)))
        targets = jnp.asarray(rng.randint(0, 32, (2, 16)))
        loss = float(jax.jit(model.loss)(params, tokens, targets))
        assert np.isfinite(loss)

        # aux weight changes the loss (the MoE term is really in there)
        model0 = GPTModel(self._cfg(moe_aux_weight=0.0))
        loss0 = float(jax.jit(model0.loss)(params, tokens, targets))
        assert loss > loss0

        @jax.jit
        def step(params):
            l, g = jax.value_and_grad(model.loss)(params, tokens, targets)
            return l, jax.tree_util.tree_map(
                lambda p, gr: p - 0.1 * gr, params, g)

        losses = []
        for _ in range(6):
            l, params = step(params)
            losses.append(float(l))
        assert losses[-1] < losses[0], losses

    def test_gspmd_replicated_moe(self, rng):
        from jax.sharding import NamedSharding
        from apex_tpu.models.gpt import GPTModel

        model = GPTModel(self._cfg())
        params = model.init_params(jax.random.PRNGKey(1))
        tokens = jnp.asarray(rng.randint(0, 32, (2, 16)))
        ref = float(jax.jit(model.loss)(params, tokens, tokens))
        mesh = jax.make_mesh((2,), ("model",),
                             axis_types=(jax.sharding.AxisType.Auto,))
        specs = model.partition_specs()
        sharded = jax.tree_util.tree_map(
            lambda x, s: jax.device_put(x, NamedSharding(mesh, s)),
            params, specs, is_leaf=lambda x: isinstance(x, P))
        got = float(jax.jit(model.loss)(sharded, tokens, tokens))
        np.testing.assert_allclose(got, ref, rtol=1e-5)

    def test_moe_tp_divisibility_validated(self):
        with pytest.raises(ValueError,
                           match="MoE ffn_hidden_size must be divisible"):
            self._cfg(ffn_hidden_size=30, tensor_parallel_size=4,
                      axis_name="model")

    def test_ep_sharded_switch_gpt(self, rng):
        """GPT with experts sharded over an expert axis: tokens are
        per-device shards (the EP group doubles as DP), loss pmeans."""
        from apex_tpu.models.gpt import GPTModel

        ep = 4
        serial = GPTModel(self._cfg())
        params = serial.init_params(jax.random.PRNGKey(3))
        tokens = jnp.asarray(rng.randint(0, 32, (ep * 2, 16)))
        targets = jnp.asarray(rng.randint(0, 32, (ep * 2, 16)))
        # serial golden: per-shard losses averaged (same per-shard MoE
        # capacity semantics)
        refs = [float(jax.jit(serial.loss)(
            params, tokens[s * 2:(s + 1) * 2], targets[s * 2:(s + 1) * 2]))
            for s in range(ep)]

        par = GPTModel(self._cfg(expert_axis="expert",
                                 expert_parallel_size=ep))
        nl = 1
        def shard_moe(path, x):
            ks = jax.tree_util.keystr(path)
            if "mlp" in ks and ("w1" in ks or "w2" in ks):
                return x.reshape(ep, nl, *x.shape[1:])
            return x
        sharded = jax.tree_util.tree_map_with_path(shard_moe, params)
        def spec_moe(path, x):
            ks = jax.tree_util.keystr(path)
            if "mlp" in ks and ("w1" in ks or "w2" in ks):
                return P("expert")
            return P()
        specs = jax.tree_util.tree_map_with_path(spec_moe, params)
        mesh = jax.make_mesh((ep,), ("expert",))

        def local(p, tk, tg):
            def fix(path, x):
                ks = jax.tree_util.keystr(path)
                if "mlp" in ks and ("w1" in ks or "w2" in ks):
                    return x[0]
                return x
            p = jax.tree_util.tree_map_with_path(fix, p)
            return jax.lax.pmean(par.loss(p, tk, tg), "expert")

        loss = float(jax.jit(shard_map(
            local, mesh=mesh,
            in_specs=(specs, P("expert"), P("expert")),
            out_specs=P(), check_vma=False))(sharded, tokens, targets))
        np.testing.assert_allclose(loss, np.mean(refs), rtol=1e-5)


class TestMoETensorParallel:
    """MoE x TP: each expert's FFN dim Column/Row-sharded over the
    tensor axis must match the serial full-width expert exactly."""

    def test_moe_tp_fwd_and_grads_match_serial(self, rng):
        serial = MoEMLP(serial_cfg(n_experts=4))
        params = serial.init_params(jax.random.PRNGKey(0))
        x = jnp.asarray(rng.randn(32, 16), jnp.float32)

        def serial_loss(p):
            out, aux = serial(p, x)
            return jnp.sum(out.astype(jnp.float32) ** 2) + 0.01 * aux

        ref_loss = float(jax.jit(serial_loss)(params))
        ref_g = jax.jit(jax.grad(serial_loss))(params)

        tpn = 2
        par = MoEMLP(serial_cfg(n_experts=4, tensor_parallel_size=tpn,
                                tensor_axis="model"))
        fl = par.cfg.local_ffn
        sharded = {
            "gate": params["gate"],
            "w1": jnp.stack([params["w1"][:, :, r * fl:(r + 1) * fl]
                             for r in range(tpn)]),
            "w2": jnp.stack([params["w2"][:, r * fl:(r + 1) * fl, :]
                             for r in range(tpn)])}
        specs = {"gate": P(), "w1": P("model"), "w2": P("model")}
        mesh = jax.make_mesh((tpn,), ("model",))

        def grad_fn(p):
            def local_loss(p):
                p = dict(p, w1=p["w1"][0], w2=p["w2"][0])
                out, aux = par(p, x)
                return jnp.sum(out.astype(jnp.float32) ** 2) + 0.01 * aux
            return jax.value_and_grad(local_loss)(p)

        loss, g = jax.jit(shard_map(
            grad_fn, mesh=mesh, in_specs=(specs,),
            out_specs=(P(), specs), check_vma=False))(sharded)
        np.testing.assert_allclose(float(loss), ref_loss, rtol=1e-5)
        np.testing.assert_allclose(np.asarray(g["gate"]),
                                   np.asarray(ref_g["gate"]),
                                   rtol=5e-4, atol=1e-5)
        for k, sl in (("w1", lambda a, r: a[:, :, r * fl:(r + 1) * fl]),
                      ("w2", lambda a, r: a[:, r * fl:(r + 1) * fl, :])):
            ref_sh = np.stack([sl(np.asarray(ref_g[k]), r)
                               for r in range(tpn)])
            np.testing.assert_allclose(np.asarray(g[k]), ref_sh,
                                       rtol=5e-4, atol=1e-5)


def _per_microbatch_golden(model, params, tokens, targets, mb):
    """Serial golden for sharded-batch MoE runs: mean of per-microbatch
    losses (MoE capacity is a per-dispatch-group statistic, so each
    device-microbatch is computed independently)."""
    n = tokens.shape[0] // mb

    def loss(p):
        losses = [model.loss(p, tokens[i * mb:(i + 1) * mb],
                             targets[i * mb:(i + 1) * mb])
                  for i in range(n)]
        return jnp.mean(jnp.stack(losses))

    return loss


def _assert_grad_tree_allclose(grads, ref):
    for (path, g), (_, r) in zip(
            jax.tree_util.tree_flatten_with_path(grads)[0],
            jax.tree_util.tree_flatten_with_path(ref)[0], strict=True):
        np.testing.assert_allclose(
            np.asarray(g), np.asarray(r), rtol=5e-4, atol=1e-5,
            err_msg=jax.tree_util.keystr(path))


def test_vary_params_needs_vma_tracking():
    """Without vma tracking there is no pcast transpose to carry the
    dense-grad reduction: refuse, do not return partial grads."""
    from apex_tpu.transformer.expert_parallel import vary_params_over_axis

    mesh = jax.make_mesh((2,), ("expert",))
    with pytest.raises(ValueError, match="check_vma=True"):
        jax.jit(shard_map(
            lambda p: vary_params_over_axis(p, "expert"), mesh=mesh,
            in_specs=P(), out_specs=P(), check_vma=False))(jnp.ones((4,)))


class TestMoEComposition:
    """The round-4 axis-product lanes: MoE composes with TP and with the
    SPMD pipeline (and all three at once) with exact loss+grad parity
    against the per-microbatch serial golden."""

    def _models(self, n_experts=2, num_layers=2, **par_kw):
        from apex_tpu.models.gpt import GPTConfig, GPTModel

        kw = dict(vocab_size=32, hidden_size=16, num_layers=num_layers,
                  num_attention_heads=4, max_seq_len=16,
                  n_experts=n_experts)
        return GPTModel(GPTConfig(**kw)), GPTModel(GPTConfig(**kw,
                                                             **par_kw))

    def test_ep_tp_switch_gpt_grad_parity(self, rng):
        from apex_tpu.models.gpt import pack_for_shard_map
        from apex_tpu.transformer.expert_parallel import (
            vary_params_over_axis)

        ep, tpn = 2, 2
        serial, par = self._models(
            n_experts=4, tensor_parallel_size=tpn, axis_name="model",
            expert_axis="expert", expert_parallel_size=ep)
        params = serial.init_params(jax.random.PRNGKey(0))
        tokens = jnp.asarray(rng.randint(0, 32, (ep * 2, 16)))
        targets = jnp.asarray(rng.randint(0, 32, (ep * 2, 16)))
        golden = _per_microbatch_golden(serial, params, tokens, targets, 2)
        ref_loss = float(jax.jit(golden)(params))
        ref_g = jax.jit(jax.grad(golden))(params)

        packed, in_specs, local_fn, repack_fn = pack_for_shard_map(
            par, params, tensor_axis="model", expert_axis="expert")
        mesh = jax.make_mesh((ep, tpn), ("expert", "model"))

        def grad_fn(sp, tk, tg):
            def loss_fn(p):
                p = vary_params_over_axis(p, "expert")
                return jax.lax.pmean(par.loss(p, tk, tg), "expert")
            loss, g = jax.value_and_grad(loss_fn)(local_fn(sp))
            return loss, repack_fn(g)

        loss, grads = jax.jit(shard_map(
            grad_fn, mesh=mesh,
            in_specs=(in_specs, P("expert"), P("expert")),
            out_specs=(P(), in_specs),
            check_vma=True))(packed, tokens, targets)
        np.testing.assert_allclose(float(loss), ref_loss, rtol=1e-5)
        ref_packed, _, _, _ = pack_for_shard_map(
            par, ref_g, tensor_axis="model", expert_axis="expert")
        _assert_grad_tree_allclose(grads, ref_packed)

    def _pipeline_case(self, rng, tpn, pp, ep, dp):
        from apex_tpu.models.gpt import pack_for_shard_map, pipeline_step

        Mb, mb, seq = 2, 2, 16
        tensor_axis = "model" if tpn > 1 else None
        serial, par = self._models(
            tensor_parallel_size=tpn, axis_name=tensor_axis,
            expert_axis="expert", expert_parallel_size=ep)
        params = serial.init_params(jax.random.PRNGKey(0))
        nshard = dp * ep * Mb
        tokens = jnp.asarray(rng.randint(0, 32, (nshard * mb, seq)))
        targets = jnp.asarray(rng.randint(0, 32, (nshard * mb, seq)))
        golden = _per_microbatch_golden(serial, params, tokens, targets,
                                        mb)
        ref_loss = float(jax.jit(golden)(params))
        ref_g = jax.jit(jax.grad(golden))(params)

        packed, in_specs, local_fn, repack_fn = pack_for_shard_map(
            par, params, n_stages=pp, tensor_axis=tensor_axis,
            expert_axis="expert")
        axes, sizes = [], []
        if dp > 1:
            axes.append("data"); sizes.append(dp)
        if tpn > 1:
            axes.append("model"); sizes.append(tpn)
        axes += ["pipe", "expert"]; sizes += [pp, ep]
        mesh = jax.make_mesh(tuple(sizes), tuple(axes))
        batch_axes = (("data", "expert") if dp > 1 else ("expert",))

        def grad_step(sp, tk, tg):
            tk = tk.reshape(Mb, mb, seq)
            tg = tg.reshape(Mb, mb, seq)
            loss, g = pipeline_step(
                par, local_fn(sp), tk, tg, pipe_axis="pipe",
                data_axis="data" if dp > 1 else None)
            return loss, repack_fn(g)

        loss, grads = jax.jit(shard_map(
            grad_step, mesh=mesh,
            in_specs=(in_specs, P(batch_axes), P(batch_axes)),
            out_specs=(P(), in_specs),
            check_vma=False))(packed, tokens, targets)
        np.testing.assert_allclose(float(loss), ref_loss, rtol=1e-5)
        ref_packed, _, _, _ = pack_for_shard_map(
            par, ref_g, n_stages=pp, tensor_axis=tensor_axis,
            expert_axis="expert")
        _assert_grad_tree_allclose(grads, ref_packed)

    def test_dp_pp_ep_pipeline_grad_parity(self, rng):
        self._pipeline_case(rng, tpn=1, pp=2, ep=2, dp=2)

    def test_tp_pipeline_without_sp_rejected(self):
        """The ring engine requires sequence_parallel for TP (the SP
        custom-VJP mappings reduce replicated-leaf grads inside the local
        vjp), and SP does not compose with MoE — so TP x PP x MoE is an
        explicit ValueError, not a silently-wrong grad."""
        from apex_tpu.models.gpt import pipeline_step

        _, par = self._models(tensor_parallel_size=2, axis_name="model",
                              expert_axis="expert",
                              expert_parallel_size=2)
        params = par.init_params(jax.random.PRNGKey(0))
        tk = jnp.zeros((2, 2, 16), jnp.int32)
        with pytest.raises(ValueError, match="sequence_parallel"):
            pipeline_step(par, params, tk, tk, pipe_axis="pipe")


class TestSwitchGPTGradParity:
    """The EP training wiring used by examples/moe/train_switch_gpt.py:
    local-loss grads + explicit reductions must equal the serial
    per-shard golden exactly (dense = mean of shard grads, expert =
    sum/ep routed to the owner by the all_to_all transpose)."""

    def test_ep_grads_match_serial(self, rng):
        from apex_tpu.models.gpt import GPTConfig, GPTModel

        ep = 4
        kw = dict(vocab_size=32, hidden_size=16, num_layers=1,
                  num_attention_heads=4, max_seq_len=16, n_experts=4)
        serial = GPTModel(GPTConfig(**kw))
        params = serial.init_params(jax.random.PRNGKey(0))
        tokens = jnp.asarray(rng.randint(0, 32, (ep * 2, 16)))
        targets = jnp.asarray(rng.randint(0, 32, (ep * 2, 16)))

        # serial golden: mean over per-shard losses (same per-shard MoE
        # capacity semantics as the EP run)
        def serial_loss(p):
            losses = [serial.loss(p, tokens[s * 2:(s + 1) * 2],
                                  targets[s * 2:(s + 1) * 2])
                      for s in range(ep)]
            return jnp.mean(jnp.stack(losses))

        ref = jax.jit(jax.grad(serial_loss))(params)

        par = GPTModel(GPTConfig(expert_axis="expert",
                                 expert_parallel_size=ep, **kw))

        from apex_tpu.transformer.expert_parallel import (
            is_gpt_expert_leaf as is_expert, localize_expert_params,
            reduce_moe_grads)

        sharded = jax.tree_util.tree_map_with_path(
            lambda p, x: x.reshape(ep, 1, *x.shape[1:])
            if is_expert(p) else x, params)
        specs = jax.tree_util.tree_map_with_path(
            lambda p, x: P("expert") if is_expert(p) else P(), params)
        mesh = jax.make_mesh((ep,), ("expert",))

        def grad_fn(p, tk, tg):
            local = localize_expert_params(p)
            loss, grads = jax.value_and_grad(par.loss)(local, tk, tg)
            grads = reduce_moe_grads(grads, "expert")
            return jax.lax.pmean(loss, "expert"), grads

        loss, grads = jax.jit(shard_map(
            grad_fn, mesh=mesh,
            in_specs=(specs, P("expert"), P("expert")),
            out_specs=(P(), specs), check_vma=False))(
                sharded, tokens, targets)
        np.testing.assert_allclose(
            float(loss), float(jax.jit(serial_loss)(params)), rtol=1e-5)

        ref_shaped = jax.tree_util.tree_map_with_path(
            lambda p, x: x.reshape(ep, 1, *x.shape[1:])
            if is_expert(p) else x, ref)
        for (path, g), (_, r) in zip(
                jax.tree_util.tree_flatten_with_path(grads)[0],
                jax.tree_util.tree_flatten_with_path(ref_shaped)[0],
                strict=True):
            np.testing.assert_allclose(
                np.asarray(g), np.asarray(r), rtol=5e-4, atol=1e-5,
                err_msg=jax.tree_util.keystr(path))
