"""ResNet + BERT model families (reference: apex wires its CNN pieces
into torchvision ResNet in ``examples/imagenet/main_amp.py`` and its
BERT-era kernels into MLPerf BERT; serial-golden + parallel-parity
testing mirrors ``tests/test_gpt.py``)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import PartitionSpec as P

from apex_tpu.models.bert import BertConfig, BertModel
from apex_tpu.models.resnet import ResNet, ResNetConfig


@pytest.fixture
def rng():
    return np.random.RandomState(0)


def tiny_resnet(**kw):
    kw.setdefault("depths", (1, 1))
    kw.setdefault("width", 8)
    kw.setdefault("num_classes", 5)
    return ResNet(ResNetConfig(**kw))


def tiny_bert(**kw):
    kw.setdefault("vocab_size", 64)
    kw.setdefault("hidden_size", 32)
    kw.setdefault("num_layers", 2)
    kw.setdefault("num_attention_heads", 4)
    kw.setdefault("max_seq_len", 16)
    return BertModel(BertConfig(**kw))


class TestResNet:
    def test_shapes_and_state_threading(self, rng):
        model = tiny_resnet()
        params = model.init_params(jax.random.PRNGKey(0))
        state = model.init_state()
        x = jnp.asarray(rng.randn(2, 32, 32, 3), jnp.float32)
        logits, new_state = jax.jit(
            lambda p, s, x: model.apply(p, s, x, training=True))(
                params, state, x)
        assert logits.shape == (2, 5)
        # training mode must advance BN running stats
        old = state["stem"].num_batches_tracked
        assert int(new_state["stem"].num_batches_tracked) == int(old) + 1
        assert not np.allclose(np.asarray(new_state["stem"].running_mean),
                               np.asarray(state["stem"].running_mean))

    def test_eval_uses_running_stats(self, rng):
        model = tiny_resnet()
        params = model.init_params(jax.random.PRNGKey(0))
        state = model.init_state()
        x = jnp.asarray(rng.randn(2, 32, 32, 3), jnp.float32)
        y1, s1 = model.apply(params, state, x, training=False)
        y2, s2 = model.apply(params, state, x, training=False)
        np.testing.assert_array_equal(np.asarray(y1), np.asarray(y2))
        # eval mode leaves state untouched
        np.testing.assert_array_equal(
            np.asarray(s1["stem"].running_mean),
            np.asarray(state["stem"].running_mean))

    def test_loss_decreases(self, rng):
        model = tiny_resnet()
        params = model.init_params(jax.random.PRNGKey(1))
        state = model.init_state()
        x = jnp.asarray(rng.randn(4, 32, 32, 3), jnp.float32)
        y = jnp.asarray(rng.randint(0, 5, (4,)))

        @jax.jit
        def step(params, state):
            (loss, new_state), grads = jax.value_and_grad(
                model.loss, has_aux=True)(params, state, x, y)
            params = jax.tree_util.tree_map(
                lambda p, g: p - 0.05 * g, params, grads)
            return params, new_state, loss

        losses = []
        for _ in range(5):
            params, state, loss = step(params, state)
            losses.append(float(loss))
        assert losses[-1] < losses[0]

    def test_syncbn_matches_serial_big_batch(self, rng):
        """DP over 4 devices with axis_name BN == serial big-batch BN."""
        model_p = tiny_resnet(axis_name="data")
        model_s = tiny_resnet()
        params = model_p.init_params(jax.random.PRNGKey(0))
        state = model_p.init_state()
        x = jnp.asarray(rng.randn(4, 16, 16, 3), jnp.float32)
        y_ref, _ = jax.jit(
            lambda p, s, x: model_s.apply(p, s, x, training=True))(
                params, state, x)

        mesh = jax.make_mesh((4,), ("data",))
        y_par = jax.jit(shard_map(
            lambda p, s, x: model_p.apply(p, s, x, training=True)[0],
            mesh=mesh, in_specs=(P(), P(), P("data")),
            out_specs=P("data"), check_vma=False))(params, state, x)
        np.testing.assert_allclose(np.asarray(y_ref), np.asarray(y_par),
                                   rtol=2e-4, atol=2e-4)


class TestResNetAmp:
    def test_o1_autocast_tracks_f32(self, rng):
        """amp O1 over the conv/BN family: the autocast interpreter must
        reclassify convs to half while keeping BN stats math in f32, and
        outputs must track the f32 run within bf16 tolerance."""
        from apex_tpu import amp

        model = tiny_resnet()
        params = model.init_params(jax.random.PRNGKey(0))
        state = model.init_state()
        x = jnp.asarray(rng.randn(2, 32, 32, 3), jnp.float32)

        def fwd(params, state, x):
            return model.apply(params, state, x, training=True)

        ref, _ = jax.jit(fwd)(params, state, x)
        auto = amp.autocast(fwd)
        got, new_state = jax.jit(auto)(params, state, x)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=5e-2, atol=5e-2)
        # the cast really happened: half-precision numerics differ
        # bitwise from the pure-f32 run (a no-op autocast would be exact)
        assert not np.array_equal(np.asarray(got), np.asarray(ref))
        # grads flow through the autocast interpreter
        def loss(params):
            logits, _ = auto(params, state, x)
            return jnp.sum(logits.astype(jnp.float32) ** 2)

        g = jax.jit(jax.grad(loss))(params)
        assert all(bool(jnp.all(jnp.isfinite(l)))
                   for l in jax.tree_util.tree_leaves(g))


class TestBert:
    def test_mlm_loss_masks_correctly(self, rng):
        model = tiny_bert()
        params = model.init_params(jax.random.PRNGKey(0))
        tokens = jnp.asarray(rng.randint(0, 64, (2, 16)))
        labels_none = jnp.full((2, 16), -1)
        labels_all = tokens

        # no masked positions: guarded denominator, finite zero-ish loss
        l_none = float(jax.jit(model.loss)(params, tokens, labels_none))
        assert np.isfinite(l_none) and l_none == 0.0

        l_all = float(jax.jit(model.loss)(params, tokens, labels_all))
        # manual reference: mean full-vocab xent over all positions
        hidden = model.apply(params, tokens)
        logits = model.mlm_logits(params, hidden)
        logp = jax.nn.log_softmax(logits, axis=-1)
        ref = -np.mean(np.take_along_axis(
            np.asarray(logp), np.asarray(tokens)[..., None], -1))
        np.testing.assert_allclose(l_all, ref, rtol=1e-5)

    def test_partial_mask_equals_subset_mean(self, rng):
        model = tiny_bert()
        params = model.init_params(jax.random.PRNGKey(1))
        tokens = jnp.asarray(rng.randint(0, 64, (2, 16)))
        mask = rng.rand(2, 16) < 0.3
        labels = jnp.asarray(np.where(mask, np.asarray(tokens), -1))
        loss = float(jax.jit(model.loss)(params, tokens, labels))

        hidden = model.apply(params, tokens)
        logp = jax.nn.log_softmax(model.mlm_logits(params, hidden), -1)
        per = -np.take_along_axis(np.asarray(logp),
                                  np.asarray(tokens)[..., None], -1)[..., 0]
        ref = per[mask].mean()
        np.testing.assert_allclose(loss, ref, rtol=1e-5)

    def test_nsp_head(self, rng):
        model = tiny_bert()
        params = model.init_params(jax.random.PRNGKey(2))
        tokens = jnp.asarray(rng.randint(0, 64, (2, 16)))
        labels = jnp.full((2, 16), -1).at[:, 3].set(5)
        nsp = jnp.asarray([0, 1])
        l0 = float(model.loss(params, tokens, labels))
        l1 = float(model.loss(params, tokens, labels, nsp_labels=nsp))
        assert l1 > l0          # adds a positive xent term

    def test_seqlens_padding_ignored(self, rng):
        """Positions past seqlen must not affect earlier outputs."""
        model = tiny_bert()
        params = model.init_params(jax.random.PRNGKey(3))
        tokens = jnp.asarray(rng.randint(0, 64, (2, 16)))
        seqlens = jnp.asarray([8, 8])
        h1 = model.apply(params, tokens, seqlens=seqlens)
        corrupted = tokens.at[:, 8:].set(7)
        h2 = model.apply(params, corrupted, seqlens=seqlens)
        np.testing.assert_allclose(np.asarray(h1[:, :8]),
                                   np.asarray(h2[:, :8]),
                                   rtol=2e-5, atol=2e-5)

    def test_gspmd_tp2_parity(self, rng):
        """Idiomatic TPU path: jit the serial form with partition_specs
        over a 2-device model axis (tests/test_gpt.py GSPMD pattern)."""
        from jax.sharding import NamedSharding

        serial = tiny_bert()
        params = serial.init_params(jax.random.PRNGKey(4))
        tokens = jnp.asarray(rng.randint(0, 64, (2, 16)))
        mask = rng.rand(2, 16) < 0.3
        labels = jnp.asarray(np.where(mask, np.asarray(tokens), -1))
        ref = float(jax.jit(serial.loss)(params, tokens, labels))

        mesh = jax.make_mesh((2,), ("model",),
                             axis_types=(jax.sharding.AxisType.Auto,))
        specs = serial.partition_specs()
        sharded = jax.tree_util.tree_map(
            lambda x, s: jax.device_put(x, NamedSharding(mesh, s)),
            params, specs, is_leaf=lambda x: isinstance(x, P))
        got = float(jax.jit(serial.loss)(sharded, tokens, labels))
        np.testing.assert_allclose(got, ref, rtol=1e-5)
