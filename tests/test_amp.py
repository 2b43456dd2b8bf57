"""amp engine tests (apex ``tests/L0/run_amp`` analogue).

Covers: O1 autocast primitive classification (basic casts + promotion),
dynamic loss scaler dynamics, checkpoint round-trip, and the minimum
end-to-end slice from SURVEY §7 — a 2-layer MLP trained to convergence with
``amp.initialize`` + FusedAdam + loss scaling under one jit.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu import amp
from apex_tpu.optimizers import FusedAdam, FusedSGD


class TestLegacyAmpSurface:
    """apex ``amp.py``/``opt.py``/``rnn_compat.py`` (the pre-initialize
    API, VERDICT r3 missing item 7)."""

    def test_casting_decorators(self):
        @amp.half_function
        def mm(a, b):
            return a @ b

        @amp.float_function
        def ex(x):
            return x * 2

        @amp.promote_function
        def add(a, b):
            return a + b

        a = jnp.ones((4, 4), jnp.float32)
        assert mm(a, a).dtype == jnp.bfloat16
        assert ex(jnp.ones((2,), jnp.bfloat16)).dtype == jnp.float32
        out = add(jnp.ones((2,), jnp.bfloat16), jnp.ones((2,), jnp.float32))
        assert out.dtype == jnp.float32

    def test_register_patches_and_restores(self):
        import types
        fake = types.SimpleNamespace(f=lambda x: x)
        amp.register_half_function(fake, "f")
        handle = amp.init(loss_scale=128.0)
        try:
            assert fake.f(jnp.ones((2,), jnp.float32)).dtype == jnp.bfloat16
        finally:
            handle._deactivate()
        assert fake.f(jnp.ones((2,), jnp.float32)).dtype == jnp.float32

    def test_init_disabled_noop(self):
        handle = amp.init(enabled=False)
        assert not handle.is_active
        with handle.scale_loss(jnp.float32(2.0)) as scaled:
            assert float(scaled) == 2.0

    def test_handle_scale_loss_and_optim_wrapper(self):
        handle = amp.init(loss_scale=64.0)
        try:
            with handle.scale_loss(jnp.float32(3.0)) as scaled:
                assert float(scaled) == 3.0 * 64.0
            opt = FusedAdam(lr=1e-2)
            params = {"w": jnp.ones((8, 8), jnp.float32)}
            state = opt.init(params)
            wrapper = handle.wrap_optimizer(opt)
            grads = {"w": jnp.full((8, 8), 0.5 * 64.0)}  # scaled grads
            new_p, _ = wrapper.step(grads, params, state)
            # unscaled inside: matches a plain step on UNscaled grads
            ref_p, _ = opt.step({"w": jnp.full((8, 8), 0.5)}, params,
                                opt.init(params))
            np.testing.assert_allclose(new_p["w"], ref_p["w"], rtol=1e-6)
        finally:
            handle._deactivate()

    def test_rnn_compat_surface(self):
        from apex_tpu.amp import legacy
        assert legacy.has_old_rnns is False
        legacy.whitelist_rnn_cells()       # validated no-op


class TestAutocastO1:
    def test_matmul_runs_half(self):
        # apex test_basic_casts: whitelist ops produce half outputs
        def f(a, b):
            return a @ b

        fa = amp.autocast(f, compute_dtype=jnp.bfloat16)
        a = jnp.ones((16, 16), jnp.float32)
        out = fa(a, a)
        assert out.dtype == jnp.bfloat16

    def test_blacklist_runs_fp32(self):
        def f(x):
            return jnp.exp(x)

        fa = amp.autocast(f, compute_dtype=jnp.bfloat16)
        out = fa(jnp.ones((8, 8), jnp.bfloat16))
        assert out.dtype == jnp.float32

    def test_promotion_widest(self):
        # apex test_promotion: mixed-dtype add promotes to the wider type
        def f(a, b):
            return a + b

        fa = amp.autocast(f)
        out = fa(jnp.ones((4,), jnp.bfloat16), jnp.ones((4,), jnp.float32))
        assert out.dtype == jnp.float32

    def test_grad_through_autocast(self):
        def loss_fn(w, x):
            h = x @ w                     # bf16 matmul under O1
            return jnp.sum(jax.nn.softmax(h.astype(jnp.float32)))

        fa = amp.autocast(loss_fn)
        w = jnp.ones((8, 8), jnp.float32) * 0.1
        x = jnp.ones((2, 8), jnp.float32)
        g = jax.grad(lambda w: fa(w, x))(w)
        assert g.dtype == jnp.float32
        assert np.all(np.isfinite(np.asarray(g)))

    def test_scan_body_autocast_hlo(self):
        """VERDICT r3 item 4: O1 must descend into scan bodies — the only
        dots in this model live inside a ``lax.scan``, so a bf16
        dot_general in the lowered HLO proves the interior was cast
        (apex ``amp/wrap.py`` semantics apply inside loops)."""
        w = jnp.full((3, 16, 16), 0.1, jnp.float32)

        def model(w, x):
            def body(h, wi):
                return jnp.tanh(h @ wi), ()
            h, _ = jax.lax.scan(body, x, w)
            return jnp.sum(h)

        fa = amp.autocast(model, compute_dtype=jnp.bfloat16)
        x = jnp.ones((4, 16), jnp.float32)
        hlo = jax.jit(fa).lower(w, x).as_text()
        dots = [l for l in hlo.splitlines() if "dot_general" in l]
        assert dots, "model lost its dots"
        assert any("bf16" in l for l in dots), (
            "no bf16 dot in the scanned body:\n" + "\n".join(dots))
        # numerics still track fp32
        ref = float(model(w, x))
        out = float(fa(w, x))
        assert abs(out - ref) < 1e-2 * max(abs(ref), 1.0)

    def test_while_and_cond_bodies_autocast(self):
        w = jnp.full((16, 16), 0.1, jnp.float32)

        def model(w, x):
            def body(c):
                h, i = c
                return jnp.tanh(h @ w), i + 1
            h, _ = jax.lax.while_loop(lambda c: c[1] < 3, body, (x, 0))
            return jnp.sum(jax.lax.cond(jnp.sum(h) > 0,
                                        lambda y: y @ w, lambda y: y, h))

        fa = amp.autocast(model, compute_dtype=jnp.bfloat16)
        x = jnp.ones((4, 16), jnp.float32)
        hlo = jax.jit(fa).lower(w, x).as_text()
        assert "bf16" in hlo
        ref, out = float(model(w, x)), float(fa(w, x))
        assert abs(out - ref) < 1e-2 * max(abs(ref), 1.0)
        # grad composes through the autocast cond (while_loop is not
        # reverse-differentiable in JAX with or without autocast)
        def cond_only(w, x):
            return jnp.sum(jax.lax.cond(jnp.sum(x) > 0,
                                        lambda y: y @ w, lambda y: y, x))
        fc = amp.autocast(cond_only, compute_dtype=jnp.bfloat16)
        g = jax.grad(lambda w: fc(w, x))(w)
        assert g.dtype == jnp.float32
        assert np.all(np.isfinite(np.asarray(g)))

    def test_rnn_under_o1(self):
        """The RNN tier is scan cells — under O1 it must (a) run, (b) emit
        half-precision dots, (c) track the fp32 trajectory."""
        from apex_tpu.RNN import LSTM

        import warnings
        with warnings.catch_warnings():
            warnings.simplefilter("ignore", DeprecationWarning)
            m = LSTM(16, 32)
        params = m.init_params(jax.random.PRNGKey(0))
        x = jnp.asarray(np.random.RandomState(3).randn(8, 2, 16),
                        jnp.float32)

        def run(params, x):
            out, _ = m.apply(params, x)
            return jnp.sum(out)

        fa = amp.autocast(run, compute_dtype=jnp.bfloat16)
        hlo = jax.jit(fa).lower(params, x).as_text()
        dots = [l for l in hlo.splitlines() if "dot_general" in l]
        assert any("bf16" in l for l in dots), "LSTM cell dots stayed fp32"
        ref, out = float(run(params, x)), float(fa(params, x))
        assert abs(out - ref) < 5e-2 * max(abs(ref), 1.0)
        g = jax.grad(lambda p: fa(p, x))(params)
        assert all(np.all(np.isfinite(np.asarray(l)))
                   for l in jax.tree_util.tree_leaves(g))

    def test_autocast_inside_shard_map(self):
        """O1 x DDP composition: autocast the per-device function, wrap
        in shard_map — collectives pass through, grads compose, and the
        interior dots run bf16."""
        from jax.sharding import PartitionSpec as P

        from jax import shard_map

        mesh = jax.make_mesh((jax.device_count(),), ("data",))
        w = jnp.full((16, 16), 0.1, jnp.float32)
        x = jnp.ones((jax.device_count() * 2, 16), jnp.float32)

        def loss(w, x):
            h = jnp.tanh(x @ w)
            return jax.lax.pmean(jnp.sum(h), "data")

        ac = amp.autocast(loss, compute_dtype=jnp.bfloat16)
        sm = shard_map(ac, mesh=mesh, in_specs=(P(), P("data")),
                       out_specs=P(), check_vma=False)
        ref = float(jax.jit(shard_map(
            loss, mesh=mesh, in_specs=(P(), P("data")),
            out_specs=P(), check_vma=False))(w, x))
        out = float(jax.jit(sm)(w, x))
        assert abs(out - ref) < 1e-2 * max(abs(ref), 1.0)
        hlo = jax.jit(sm).lower(w, x).as_text()
        assert any("bf16" in l for l in hlo.splitlines()
                   if "dot_general" in l), "dot stayed fp32 in the region"
        def grad_of(fn):
            return jax.jit(shard_map(
                lambda w, x: jax.grad(lambda w: fn(w, x))(w), mesh=mesh,
                in_specs=(P(), P("data")), out_specs=P(),
                check_vma=False))(w, x)

        g = grad_of(ac)
        assert g.dtype == jnp.float32
        # the composition claim is numeric: autocast grads must track the
        # un-autocast shard_map grads (same pmean transpose/psum wiring)
        np.testing.assert_allclose(np.asarray(g),
                                   np.asarray(grad_of(loss)),
                                   rtol=2e-2, atol=2e-2)

    def test_composite_network_numerics(self):
        # autocast output should approximate the f32 reference
        def net(params, x):
            h = jnp.tanh(x @ params["w1"])
            return jnp.sum(jax.nn.log_softmax(h @ params["w2"]))

        rng = np.random.RandomState(0)
        params = {"w1": jnp.asarray(rng.randn(16, 32).astype(np.float32)),
                  "w2": jnp.asarray(rng.randn(32, 8).astype(np.float32))}
        x = jnp.asarray(rng.randn(4, 16).astype(np.float32))
        ref = net(params, x)
        out = amp.autocast(net)(params, x)
        np.testing.assert_allclose(float(out), float(ref), rtol=2e-2)

    def test_jit_compose(self):
        def f(a, b):
            return a @ b

        fa = jax.jit(amp.autocast(f))
        out = fa(jnp.ones((8, 8)), jnp.ones((8, 8)))
        assert out.dtype == jnp.bfloat16


class TestAutocastPallasComposition:
    """Round-2 regression: ``jax.grad(amp.autocast(loss))`` over the
    library's own Pallas custom_vjp ops must work — the interpreter keeps
    custom-derivative calls opaque so the VJP rule survives (on TPU the
    inlined body would be a bare ``pallas_call`` with no autodiff)."""

    @pytest.fixture(autouse=True)
    def _force_pallas(self):
        from apex_tpu.utils import set_force_pallas
        set_force_pallas(True)
        yield
        set_force_pallas(None)

    def test_grad_autocast_fused_layer_norm(self, rng):
        from apex_tpu.normalization import FusedLayerNorm

        ln = FusedLayerNorm(32)
        params = {"ln": ln.init_params(),
                  "w": jnp.asarray(rng.randn(32, 32).astype(np.float32))}
        x = jnp.asarray(rng.randn(4, 32).astype(np.float32))

        def loss(params, x):
            h = x @ params["w"]
            return jnp.sum(ln(params["ln"], h) ** 2)

        fa = amp.autocast(loss)
        g = jax.grad(fa)(params, x)
        ref = jax.grad(loss)(params, x)
        for leaf, rleaf in zip(jax.tree_util.tree_leaves(g),
                               jax.tree_util.tree_leaves(ref)):
            assert np.all(np.isfinite(np.asarray(leaf, np.float32)))
            np.testing.assert_allclose(np.asarray(leaf, np.float32),
                                       np.asarray(rleaf, np.float32),
                                       rtol=5e-2, atol=5e-2)

    def test_grad_autocast_flash_attention(self, rng):
        from apex_tpu.ops.flash_attention import flash_attention

        q = jnp.asarray(rng.randn(1, 2, 128, 64).astype(np.float32))

        def loss(q):
            return jnp.sum(flash_attention(q, q, q, causal=True))

        g = jax.grad(amp.autocast(loss))(q)
        assert np.all(np.isfinite(np.asarray(g)))

    def test_jit_grad_autocast_pallas(self, rng):
        from apex_tpu.ops.layer_norm import fused_rms_norm_affine

        w = jnp.ones((64,), jnp.float32)
        x = jnp.asarray(rng.randn(8, 64).astype(np.float32))

        def loss(x, w):
            return jnp.sum(fused_rms_norm_affine(x, w) ** 2)

        g = jax.jit(jax.grad(amp.autocast(loss)))(x, w)
        assert np.all(np.isfinite(np.asarray(g)))

    def test_matmul_still_autocasts_around_pallas(self, rng):
        """The whitelist cast must still fire for ops OUTSIDE the opaque
        custom call (matmul output bf16), while the Pallas op keeps its
        traced dtype."""
        from apex_tpu.normalization import FusedLayerNorm

        ln = FusedLayerNorm(16)
        lp = ln.init_params()

        def f(x, w):
            return ln(lp, x @ w)

        fa = amp.autocast(f)
        out = fa(jnp.ones((4, 16)), jnp.ones((16, 16)))
        # LN was traced at f32 (inputs restored at the opaque boundary)
        assert out.dtype == jnp.float32


class TestLossScaler:
    def test_dynamic_halves_on_overflow(self):
        s = amp.LossScaler("dynamic", init_scale=2.0 ** 8)
        st = s.init()
        st2 = s.update(st, jnp.asarray(1.0))
        assert float(st2.loss_scale) == 2.0 ** 7
        assert int(st2.unskipped) == 0
        assert int(st2.overflows) == 1

    def test_dynamic_grows_after_window(self):
        s = amp.LossScaler("dynamic", init_scale=4.0, scale_window=3)
        st = s.init()
        for _ in range(3):
            st = s.update(st, jnp.asarray(0.0))
        assert float(st.loss_scale) == 8.0
        assert int(st.unskipped) == 0

    def test_static_never_changes(self):
        s = amp.LossScaler(128.0)
        st = s.init()
        st = s.update(st, jnp.asarray(1.0))
        assert float(st.loss_scale) == 128.0

    def test_found_inf(self):
        g = {"a": jnp.ones((4,)), "b": jnp.asarray([1.0, np.inf])}
        assert float(amp.LossScaler.found_inf(g)) == 1.0
        g = {"a": jnp.ones((4,)), "b": jnp.asarray([1.0, 2.0])}
        assert float(amp.LossScaler.found_inf(g)) == 0.0

    def test_checkpoint_roundtrip(self):
        # apex tests/L0/run_amp/test_checkpointing.py: amp state_dict survives
        s = amp.LossScaler("dynamic", init_scale=2.0 ** 10)
        st = s.update(s.init(), jnp.asarray(1.0))
        d = s.state_dict(st)
        st2 = s.load_state_dict(d)
        assert float(st2.loss_scale) == float(st.loss_scale)
        assert int(st2.unskipped) == int(st.unskipped)


class TestEndToEndSlice:
    """SURVEY §7 minimum slice: amp.initialize + FusedAdam + scale_loss,
    2-layer MLP on synthetic data, trained to convergence under one jit."""

    @pytest.mark.parametrize("opt_level", ["O0", "O1", "O2", "O3"])
    def test_mlp_converges(self, opt_level, rng):
        def apply_fn(params, x):
            h = jax.nn.relu(x @ params["w1"] + params["b1"])
            return h @ params["w2"] + params["b2"]

        params = {
            "w1": jnp.asarray(rng.randn(8, 32).astype(np.float32) * 0.3),
            "b1": jnp.zeros((32,), jnp.float32),
            "w2": jnp.asarray(rng.randn(32, 4).astype(np.float32) * 0.3),
            "b2": jnp.zeros((4,), jnp.float32),
        }
        w_true = rng.randn(8, 4).astype(np.float32)
        x = rng.randn(256, 8).astype(np.float32)
        y = np.argmax(x @ w_true, axis=1)
        x, y = jnp.asarray(x), jnp.asarray(y)

        optimizer = FusedAdam(lr=5e-3)
        state = amp.initialize(apply_fn, optimizer, opt_level=opt_level,
                               half_dtype=jnp.bfloat16)
        params = state.cast_params(params)
        opt_state = optimizer.init(params)
        scaler_state = state.scaler.init()

        def loss_fn(params, x, y, scaler_state):
            (x,) = state.cast_inputs(x)
            logits = state.apply_fn(params, x).astype(jnp.float32)
            loss = -jnp.mean(jax.nn.log_softmax(logits)[jnp.arange(len(y)),
                                                        y])
            return amp.scale_loss(loss, scaler_state), loss

        @jax.jit
        def train_step(params, opt_state, scaler_state, x, y):
            grads, loss = jax.grad(loss_fn, has_aux=True)(
                params, x, y, scaler_state)
            params, opt_state, scaler_state, _ = amp.unscale_step(
                optimizer, grads, params, opt_state, state.scaler,
                scaler_state)
            return params, opt_state, scaler_state, loss

        losses = []
        for i in range(150):
            params, opt_state, scaler_state, loss = train_step(
                params, opt_state, scaler_state, x, y)
            losses.append(float(loss))
        assert losses[-1] < losses[0] * 0.5, (opt_level, losses[::30])
        # O2: params stayed half precision except none (no norm layers)
        if opt_level in ("O2", "O3"):
            assert params["w1"].dtype == jnp.bfloat16

    def test_overflow_skip_then_recover(self, rng):
        """Inject an inf gradient; the step must be skipped and the scale
        halved (apex dynamic loss scaling semantics)."""
        params = {"w": jnp.ones((16, 16), jnp.float32)}
        optimizer = FusedAdam(lr=0.1)
        opt_state = optimizer.init(params)
        scaler = amp.LossScaler("dynamic", init_scale=2.0 ** 8)
        sstate = scaler.init()
        bad_grads = {"w": jnp.full((16, 16), np.inf, jnp.float32)}
        p1, o1, s1, finf = amp.unscale_step(
            optimizer, bad_grads, params, opt_state, scaler, sstate)
        assert float(finf) == 1.0
        np.testing.assert_array_equal(np.asarray(p1["w"]),
                                      np.asarray(params["w"]))
        assert float(s1.loss_scale) == 2.0 ** 7
        assert int(o1["step"]) == 0
        good = {"w": jnp.ones((16, 16), jnp.float32)}
        p2, o2, s2, finf2 = amp.unscale_step(
            optimizer, good, p1, o1, scaler, s1)
        assert float(finf2) == 0.0
        assert int(o2["step"]) == 1
        assert not np.allclose(np.asarray(p2["w"]), np.asarray(p1["w"]))
