"""Fused optimizer tests vs unfused references.

Apex pattern (``tests/L0/run_optimizers/test_fused_optimizer.py``): run the
fused optimizer and a plain reference implementation step-by-step on the
same inputs and compare parameters at each step.
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from apex_tpu.optimizers import (FusedAdam, FusedSGD, FusedLAMB,
                                 FusedNovoGrad, FusedAdagrad)


def _packed(cls, **kw):
    """Construct with the packed multi_tensor engine.

    The ctor opt-in was removed after two bench rounds measured the
    packed single-chip step at 0.49-0.53x optax (``bucketed=True`` on a
    plain optimizer now raises); the engine survives only as the
    ZeRO/distributed optimizers' sharding unit.  The kernel tests below
    still pin it directly — by attribute, the same way the distributed
    mixin selects it."""
    opt = cls(**kw)
    opt.bucketed = True
    return opt


def make_params(rng, dtype=np.float32):
    return {
        "dense": {"kernel": jnp.asarray(rng.randn(17, 31).astype(dtype)),
                  "bias": jnp.asarray(rng.randn(31).astype(dtype))},
        "ln": {"scale": jnp.asarray(rng.rand(17).astype(dtype) + 0.5)},
    }


def make_grads(rng, params, scale=1.0):
    return jax.tree_util.tree_map(
        lambda p: jnp.asarray(
            rng.randn(*p.shape).astype(np.float32) * scale).astype(p.dtype),
        params)


def tree_allclose(a, b, rtol=1e-5, atol=1e-6):
    for x, y in zip(jax.tree_util.tree_leaves(a),
                    jax.tree_util.tree_leaves(b)):
        np.testing.assert_allclose(np.asarray(x, np.float32),
                                   np.asarray(y, np.float32),
                                   rtol=rtol, atol=atol)


class TestFusedAdam:
    def test_matches_optax_adamw(self, rng):
        lr, wd = 1e-2, 0.05
        params = make_params(rng)
        opt = _packed(FusedAdam, lr=lr, weight_decay=wd,
                        adam_w_mode=True)
        state = opt.init(params)
        ref = optax.adamw(lr, b1=0.9, b2=0.999, eps=1e-8, weight_decay=wd)
        ref_params = params
        ref_state = ref.init(params)
        step = jax.jit(opt.step)
        for i in range(5):
            grads = make_grads(rng, params)
            params, state = step(grads, params, state)
            upd, ref_state = ref.update(grads, ref_state, ref_params)
            ref_params = optax.apply_updates(ref_params, upd)
            tree_allclose(params, ref_params, rtol=2e-5, atol=1e-6)

    def test_classic_adam_l2_mode(self, rng):
        # adam_w_mode=False folds decay into grads = optax.adam on g + wd*p
        lr, wd = 1e-2, 0.1
        params = make_params(rng)
        opt = _packed(FusedAdam, lr=lr, weight_decay=wd,
                        adam_w_mode=False)
        state = opt.init(params)
        ref = optax.adam(lr, b1=0.9, b2=0.999, eps=1e-8)
        ref_params, ref_state = params, ref.init(params)
        for i in range(3):
            grads = make_grads(rng, params)
            params, state = opt.step(grads, params, state)
            l2g = jax.tree_util.tree_map(lambda g, p: g + wd * p, grads,
                                         ref_params)
            upd, ref_state = ref.update(l2g, ref_state)
            ref_params = optax.apply_updates(ref_params, upd)
            tree_allclose(params, ref_params, rtol=2e-5, atol=1e-6)

    def test_noop_skips_step_and_count(self, rng):
        params = make_params(rng)
        opt = _packed(FusedAdam, lr=0.1)
        state = opt.init(params)
        grads = make_grads(rng, params)
        p1, s1 = opt.step(grads, params, state, noop_flag=1)
        tree_allclose(p1, params)
        assert int(s1["step"]) == 0
        p2, s2 = opt.step(grads, params, state, noop_flag=0)
        assert int(s2["step"]) == 1
        with np.testing.assert_raises(AssertionError):
            tree_allclose(p2, params)

    def test_grad_scale_fused_unscaling(self, rng):
        params = make_params(rng)
        opt = _packed(FusedAdam, lr=1e-2)
        state = opt.init(params)
        grads = make_grads(rng, params)
        scaled = jax.tree_util.tree_map(lambda g: g * 128.0, grads)
        p_a, _ = opt.step(grads, params, state)
        p_b, _ = opt.step(scaled, params, state, grad_scale=1.0 / 128.0)
        tree_allclose(p_a, p_b, rtol=1e-5)

    def test_master_weights_bf16(self, rng):
        params = make_params(rng, dtype=np.float32)
        bf16_params = jax.tree_util.tree_map(
            lambda p: p.astype(jnp.bfloat16), params)
        opt = _packed(FusedAdam, lr=1e-3, master_weights=True)
        state = opt.init(bf16_params)
        # master copies exist for the bf16 bucket
        assert any("master" in b for b in state["buckets"].values())
        grads = make_grads(rng, bf16_params)
        p1, s1 = opt.step(grads, bf16_params, state)
        assert all(p.dtype == jnp.bfloat16
                   for p in jax.tree_util.tree_leaves(p1))
        # 100 tiny steps: master accumulates beyond bf16 resolution
        fp32_opt = _packed(FusedAdam, lr=1e-3)
        fp32_state = fp32_opt.init(params)
        fp32_p = params
        for _ in range(3):
            p1, s1 = opt.step(grads, p1, s1)
            fp32_p, fp32_state = fp32_opt.step(
                jax.tree_util.tree_map(lambda g: g.astype(jnp.float32),
                                       grads), fp32_p, fp32_state)
        tree_allclose(p1, fp32_p, rtol=2e-2, atol=2e-2)

    def test_param_groups_no_decay(self, rng):
        params = make_params(rng)
        no_decay = lambda path: "no_decay" if ("bias" in path or
                                               "scale" in path) else "default"
        opt = _packed(FusedAdam, lr=1e-2, weight_decay=0.5,
                        param_group_fn=no_decay,
                        param_groups={"no_decay": {"weight_decay": 0.0}})
        state = opt.init(params)
        zero_grads = jax.tree_util.tree_map(jnp.zeros_like, params)
        p1, _ = opt.step(zero_grads, params, state)
        # decayed: kernel moved; un-decayed: bias/scale unchanged
        assert not np.allclose(p1["dense"]["kernel"],
                               params["dense"]["kernel"])
        np.testing.assert_allclose(p1["dense"]["bias"],
                                   params["dense"]["bias"], atol=1e-7)
        np.testing.assert_allclose(p1["ln"]["scale"], params["ln"]["scale"],
                                   atol=1e-7)

    def test_amsgrad_raises(self):
        with pytest.raises(RuntimeError):
            _packed(FusedAdam, amsgrad=True)

    def test_as_optax(self, rng):
        params = make_params(rng)
        tx = _packed(FusedAdam, lr=1e-2).as_optax()
        state = tx.init(params)
        grads = make_grads(rng, params)
        upd, state = tx.update(grads, state, params)
        new_p = optax.apply_updates(params, upd)
        ref_p, _ = _packed(FusedAdam, lr=1e-2).step(
            grads, params, _packed(FusedAdam, lr=1e-2).init(params))
        tree_allclose(new_p, ref_p, rtol=1e-5)


class TestFusedSGD:
    def test_matches_optax_sgd_momentum(self, rng):
        lr, mu = 0.1, 0.9
        params = make_params(rng)
        opt = _packed(FusedSGD, lr=lr, momentum=mu)
        state = opt.init(params)
        ref = optax.sgd(lr, momentum=mu, nesterov=False)
        ref_params, ref_state = params, ref.init(params)
        for _ in range(4):
            grads = make_grads(rng, params)
            params, state = opt.step(grads, params, state)
            upd, ref_state = ref.update(grads, ref_state)
            ref_params = optax.apply_updates(ref_params, upd)
            tree_allclose(params, ref_params, rtol=1e-5)

    def test_nesterov(self, rng):
        lr, mu = 0.05, 0.9
        params = make_params(rng)
        opt = _packed(FusedSGD, lr=lr, momentum=mu, nesterov=True)
        state = opt.init(params)
        ref = optax.sgd(lr, momentum=mu, nesterov=True)
        ref_params, ref_state = params, ref.init(params)
        for _ in range(4):
            grads = make_grads(rng, params)
            params, state = opt.step(grads, params, state)
            upd, ref_state = ref.update(grads, ref_state)
            ref_params = optax.apply_updates(ref_params, upd)
            tree_allclose(params, ref_params, rtol=1e-5)

    def test_weight_decay(self, rng):
        params = make_params(rng)
        opt = _packed(FusedSGD, lr=0.1, weight_decay=0.01)
        state = opt.init(params)
        grads = make_grads(rng, params)
        p1, _ = opt.step(grads, params, state)
        ref = jax.tree_util.tree_map(
            lambda p, g: p - 0.1 * (g + 0.01 * p), params, grads)
        tree_allclose(p1, ref, rtol=1e-5)


def _lamb_reference(params, grads, m, v, step, lr, b1, b2, eps, wd,
                    max_grad_norm=1.0):
    """Plain numpy LAMB (adamw mode, grad averaging, bias correction)."""
    leaves_p = jax.tree_util.tree_leaves(params)
    leaves_g = jax.tree_util.tree_leaves(grads)
    gnorm = np.sqrt(sum(float(np.sum(np.asarray(g) ** 2))
                        for g in leaves_g))
    clip = max_grad_norm / gnorm if gnorm > max_grad_norm else 1.0
    new_p, new_m, new_v = [], [], []
    for p, g, mi, vi in zip(leaves_p, leaves_g, m, v):
        p, g = np.asarray(p, np.float64), np.asarray(g, np.float64) * clip
        mi = b1 * mi + (1 - b1) * g
        vi = b2 * vi + (1 - b2) * g * g
        u = (mi / (1 - b1 ** step)) / \
            (np.sqrt(vi / (1 - b2 ** step)) + eps) + wd * p
        pn, un = np.linalg.norm(p), np.linalg.norm(u)
        ratio = pn / un if (pn > 0 and un > 0) else 1.0
        new_p.append(p - lr * ratio * u)
        new_m.append(mi)
        new_v.append(vi)
    return new_p, new_m, new_v


class TestFusedLAMB:
    def test_matches_reference(self, rng):
        lr, wd = 1e-2, 0.01
        params = make_params(rng)
        opt = _packed(FusedLAMB, lr=lr, weight_decay=wd)
        state = opt.init(params)
        leaves = jax.tree_util.tree_leaves(params)
        ref_p = [np.asarray(p, np.float64) for p in leaves]
        ref_m = [np.zeros_like(p) for p in ref_p]
        ref_v = [np.zeros_like(p) for p in ref_p]
        for t in range(1, 4):
            grads = make_grads(rng, params)
            params, state = opt.step(grads, params, state)
            ref_p, ref_m, ref_v = _lamb_reference(
                jax.tree_util.tree_unflatten(
                    jax.tree_util.tree_structure(params), ref_p),
                grads, ref_m, ref_v, t, lr, 0.9, 0.999, 1e-6, wd)
            for a, b in zip(jax.tree_util.tree_leaves(params), ref_p):
                np.testing.assert_allclose(np.asarray(a), b, rtol=3e-4,
                                           atol=1e-6)

    def test_grad_clipping_engages(self, rng):
        params = make_params(rng)
        opt = _packed(FusedLAMB, lr=1e-2, max_grad_norm=0.5)
        state = opt.init(params)
        big_grads = make_grads(rng, params, scale=100.0)
        p1, _ = opt.step(big_grads, params, state)
        # params should move a bounded amount despite huge grads
        for a, b in zip(jax.tree_util.tree_leaves(p1),
                        jax.tree_util.tree_leaves(params)):
            assert float(jnp.max(jnp.abs(a - b))) < 1.0


class TestFusedMixedPrecisionLamb:
    """apex ``fused_mixed_precision_lamb.py``: LAMB over low-precision
    model params with fp32 master copies (the BERT O2 recipe optimizer)."""

    def test_master_copy_exists_and_tracks_fp32_lamb(self, rng):
        from apex_tpu.optimizers import FusedLAMB, FusedMixedPrecisionLamb

        params = make_params(rng, dtype=np.float32)
        bf16_params = jax.tree_util.tree_map(
            lambda p: p.astype(jnp.bfloat16), params)
        opt = FusedMixedPrecisionLamb(lr=1e-2,
                                      reduced_precision_dtype=jnp.bfloat16)
        state = opt.init(bf16_params)
        assert any("master" in b for b in state["buckets"].values())

        ref_opt = _packed(FusedLAMB, lr=1e-2)
        ref_state = ref_opt.init(params)
        grads = make_grads(rng, bf16_params)
        f32_grads = jax.tree_util.tree_map(
            lambda g: g.astype(jnp.float32), grads)
        p, s, rp, rs = bf16_params, state, params, ref_state
        for _ in range(3):
            p, s = opt.step(grads, p, s)
            rp, rs = ref_opt.step(f32_grads, rp, rs)
        assert all(x.dtype == jnp.bfloat16
                   for x in jax.tree_util.tree_leaves(p))
        tree_allclose(p, rp, rtol=2e-2, atol=2e-2)

    def test_noop_flag_freezes_master(self, rng):
        from apex_tpu.optimizers import FusedMixedPrecisionLamb

        params = make_params(rng, dtype=np.float32)
        bf16_params = jax.tree_util.tree_map(
            lambda p: p.astype(jnp.bfloat16), params)
        opt = FusedMixedPrecisionLamb(lr=1e-2)
        state = opt.init(bf16_params)
        grads = make_grads(rng, bf16_params)
        p1, s1 = opt.step(grads, bf16_params, state,
                          noop_flag=jnp.ones((), jnp.int32))
        for a, b in zip(jax.tree_util.tree_leaves(p1),
                        jax.tree_util.tree_leaves(bf16_params)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        assert int(s1["step"]) == 0


class TestFusedNovoGradAdagrad:
    def test_novograd_first_step(self, rng):
        params = make_params(rng)
        opt = _packed(FusedNovoGrad, lr=0.1, bias_correction=False,
                            grad_averaging=False, weight_decay=0.0)
        state = opt.init(params)
        grads = make_grads(rng, params)
        p1, s1 = opt.step(grads, params, state)
        # step 1: v = ||g||² per tensor, m = g/||g||, p -= lr*m
        for (a, p, g) in zip(jax.tree_util.tree_leaves(p1),
                             jax.tree_util.tree_leaves(params),
                             jax.tree_util.tree_leaves(grads)):
            gn = float(jnp.linalg.norm(g))
            ref = np.asarray(p) - 0.1 * np.asarray(g) / (gn + 1e-8)
            np.testing.assert_allclose(np.asarray(a), ref, rtol=1e-4,
                                       atol=1e-6)

    def test_adagrad_matches_optax(self, rng):
        params = make_params(rng)
        opt = _packed(FusedAdagrad, lr=0.1, eps=1e-10)
        state = opt.init(params)
        ref = optax.adagrad(0.1, initial_accumulator_value=0.0, eps=1e-10)
        ref_params, ref_state = params, ref.init(params)
        for _ in range(3):
            grads = make_grads(rng, params)
            params, state = opt.step(grads, params, state)
            upd, ref_state = ref.update(grads, ref_state)
            ref_params = optax.apply_updates(ref_params, upd)
            tree_allclose(params, ref_params, rtol=1e-4, atol=1e-6)


class TestMasterParams:
    """apex amp.master_params: extract the fp32 master copies."""

    def test_masters_match_fp32_trajectory(self, rng):
        from apex_tpu import amp

        params = make_params(rng, dtype=np.float32)
        bf16 = jax.tree_util.tree_map(
            lambda p: p.astype(jnp.bfloat16), params)
        opt = _packed(FusedAdam, lr=1e-3, master_weights=True)
        state = opt.init(bf16)
        grads = make_grads(rng, bf16)
        p, s = opt.step(grads, bf16, state)
        masters = amp.master_params(opt, p, s)
        for m, mp in zip(jax.tree_util.tree_leaves(masters),
                         jax.tree_util.tree_leaves(p)):
            assert m.dtype == jnp.float32
            # model params are the bf16 round-trip of the masters
            np.testing.assert_array_equal(
                np.asarray(m.astype(jnp.bfloat16)), np.asarray(mp))

    def test_fp32_params_pass_through(self, rng):
        from apex_tpu import amp

        params = make_params(rng, dtype=np.float32)
        opt = _packed(FusedAdam, lr=1e-3)
        state = opt.init(params)
        masters = amp.master_params(opt, params, state)
        for m, p in zip(jax.tree_util.tree_leaves(masters),
                        jax.tree_util.tree_leaves(params)):
            np.testing.assert_array_equal(np.asarray(m), np.asarray(p))


class TestPerLeafLayout:
    """bucketed=False: the per-leaf layout must walk the SAME trajectory
    as the packed engine (identical _*_math single-source updates), for
    every optimizer family, including masters, noop and param groups."""

    OPTS = [
        (FusedAdam, dict(lr=1e-2, weight_decay=0.05)),
        (FusedAdam, dict(lr=1e-2, weight_decay=0.1, adam_w_mode=False,
                         bias_correction=False)),
        (FusedSGD, dict(lr=1e-2, momentum=0.9, weight_decay=0.01)),
        (FusedLAMB, dict(lr=1e-2, weight_decay=0.01)),
        (FusedLAMB, dict(lr=1e-2, use_nvlamb=True, grad_averaging=False)),
        (FusedNovoGrad, dict(lr=1e-2, weight_decay=0.01)),
        (FusedAdagrad, dict(lr=1e-2, weight_decay=0.01)),
        (FusedAdagrad, dict(lr=1e-2, weight_decay=0.01,
                            adagrad_w_mode=True)),
    ]

    @pytest.mark.parametrize("cls,kw", OPTS,
                             ids=lambda o: getattr(o, "__name__", None))
    def test_matches_packed_trajectory(self, rng, cls, kw):
        params = make_params(rng)
        packed = _packed(cls, **kw)
        leaf = cls(bucketed=False, **kw)
        ps, ss = params, packed.init(params)
        pl_, sl = params, leaf.init(params)
        pstep, lstep = jax.jit(packed.step), jax.jit(leaf.step)
        for _ in range(4):
            grads = make_grads(rng, params)
            ps, ss = pstep(grads, ps, ss)
            pl_, sl = lstep(grads, pl_, sl)
            tree_allclose(ps, pl_, rtol=1e-6, atol=1e-7)
        assert int(sl["step"]) == 4

    def test_master_weights_and_noop(self, rng):
        params32 = make_params(rng)
        bf16 = jax.tree_util.tree_map(
            lambda p: p.astype(jnp.bfloat16), params32)
        packed = _packed(FusedLAMB, lr=1e-2, master_weights=True)
        leaf = FusedLAMB(lr=1e-2, master_weights=True, bucketed=False)
        ps, ss = bf16, packed.init(bf16)
        pl_, sl = bf16, leaf.init(bf16)
        for i in range(3):
            grads = make_grads(rng, bf16)
            noop = jnp.asarray(1 if i == 1 else 0)  # skip the middle step
            ps, ss = packed.step(grads, ps, ss, noop_flag=noop)
            pl_, sl = leaf.step(grads, pl_, sl, noop_flag=noop)
            tree_allclose(ps, pl_, rtol=1e-6, atol=1e-7)
        assert int(ss["step"]) == int(sl["step"]) == 2
        # per-leaf masters are leaf-shaped fp32
        from apex_tpu import amp
        m = amp.master_params(leaf, pl_, sl)
        for lm, lp in zip(jax.tree_util.tree_leaves(m),
                          jax.tree_util.tree_leaves(pl_)):
            assert lm.dtype == jnp.float32 and lm.shape == lp.shape

    def test_param_groups(self, rng):
        params = make_params(rng)
        group_fn = lambda path: ("no_decay" if "bias" in path or "scale"
                                 in path else "default")
        kw = dict(lr=1e-2, weight_decay=0.1, param_group_fn=group_fn,
                  param_groups={"no_decay": {"weight_decay": 0.0}})
        packed = _packed(FusedAdam, **kw)
        leaf = FusedAdam(bucketed=False, **kw)
        ps, ss = params, packed.init(params)
        pl_, sl = params, leaf.init(params)
        for _ in range(3):
            grads = make_grads(rng, params)
            ps, ss = packed.step(grads, ps, ss)
            pl_, sl = leaf.step(grads, pl_, sl)
        tree_allclose(ps, pl_, rtol=1e-6, atol=1e-7)

    def test_zero_requires_bucketed(self):
        from apex_tpu.contrib.optimizers import DistributedFusedAdam
        with pytest.raises(ValueError, match="bucketed"):
            DistributedFusedAdam(lr=1e-3, world_size=2, axis_name="data",
                                 bucketed=False)

    def test_default_layout_per_leaf_and_packed_raises(self):
        """Layouts: plain optimizers are per-leaf-only (the packed
        engine pays a pack and unpack per step; ROADMAP D1); the
        ZeRO subclasses keep bucketed (their sharding unit); an explicit
        packed request on a plain optimizer is rejected outright."""
        from apex_tpu.contrib.optimizers import DistributedFusedAdam
        assert FusedAdam(lr=1e-3).bucketed is False
        assert DistributedFusedAdam(lr=1e-3, world_size=2,
                                    axis_name="data").bucketed is True
        with pytest.raises(ValueError, match="per-leaf"):
            FusedAdam(lr=1e-3, bucketed=True)

    def test_grad_scale_parity(self, rng):
        """amp's fused unscaling (grad_scale=1/loss_scale) must walk the
        same trajectory in both layouts AND match stepping on pre-divided
        grads — LAMB is the interesting case because grad_scale also
        enters the global-norm clip (the third arm catches a shared-code
        bug that drops/double-applies grad_scale in both layouts)."""
        params = make_params(rng)
        packed = _packed(FusedLAMB, lr=1e-2)
        leaf = FusedLAMB(lr=1e-2, bucketed=False)
        unscaled = FusedLAMB(lr=1e-2, bucketed=False)
        ps, ss = params, packed.init(params)
        pl_, sl = params, leaf.init(params)
        pu, su = params, unscaled.init(params)
        for _ in range(3):
            grads = make_grads(rng, params, scale=128.0)  # "scaled" grads
            pre = jax.tree_util.tree_map(lambda g: g / 128.0, grads)
            ps, ss = packed.step(grads, ps, ss, grad_scale=1 / 128.0)
            pl_, sl = leaf.step(grads, pl_, sl, grad_scale=1 / 128.0)
            pu, su = unscaled.step(pre, pu, su)
            tree_allclose(ps, pl_, rtol=1e-6, atol=1e-7)
            tree_allclose(pl_, pu, rtol=1e-5, atol=1e-7)
