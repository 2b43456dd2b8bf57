"""EQuARX-style block-quantized collectives (utils/compressed_allreduce)
on the fake 8-device CPU mesh, plus the byte-capped bucket splitter the
distributed optimizers use (apex ``message_size`` semantics)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from apex_tpu.multi_tensor_apply import bucketing as B
from apex_tpu.utils import compressed_allreduce as CA

N = 8


@pytest.fixture
def mesh():
    return jax.make_mesh((N,), ("data",))


class TestQuantizeInt8:
    def test_roundtrip_error_bound(self, rng):
        x = jnp.asarray(rng.randn(64, 128).astype(np.float32))
        q, s = CA.quantize_int8(x)
        assert q.dtype == jnp.int8 and s.shape == (64, 1)
        err = np.abs(np.asarray(CA.dequantize_int8(q, s)) - np.asarray(x))
        # symmetric rounding: error ≤ scale/2 = blockmax/254 per element
        bound = np.max(np.abs(np.asarray(x)), axis=1, keepdims=True) / 254
        assert np.all(err <= bound + 1e-7)

    def test_zero_block_exact(self):
        q, s = CA.quantize_int8(jnp.zeros((4, 128)))
        np.testing.assert_array_equal(np.asarray(q), 0)
        np.testing.assert_array_equal(np.asarray(s), 1.0)
        np.testing.assert_array_equal(
            np.asarray(CA.dequantize_int8(q, s)), 0.0)

    def test_extremes_saturate_cleanly(self):
        x = jnp.concatenate([jnp.full((1, 64), 3.0),
                             jnp.full((1, 64), -3.0)], axis=1)
        q, s = CA.quantize_int8(x)
        out = np.asarray(CA.dequantize_int8(q, s))
        np.testing.assert_allclose(out, np.asarray(x), rtol=1e-6)

    def test_bad_mode_rejected(self):
        with pytest.raises(ValueError, match="allreduce_dtype"):
            CA.check_mode("fp8")


def _run(mesh, body, x, out_specs=P()):
    return jax.jit(jax.shard_map(body, mesh=mesh,
                                 in_specs=(P("data"),),
                                 out_specs=out_specs, check_vma=False))(x)


class TestReduceScatter:
    def test_f32_bitwise_matches_psum_scatter(self, rng, mesh):
        x = jnp.asarray(rng.randn(N, 16, 128).astype(np.float32))

        def exact(v):
            return jax.lax.psum_scatter(v[0], "data", scatter_dimension=0,
                                        tiled=True)

        def ours(v):
            return CA.reduce_scatter(v[0], "data", N, "f32")

        np.testing.assert_array_equal(
            np.asarray(_run(mesh, exact, x, P("data"))),
            np.asarray(_run(mesh, ours, x, P("data"))))

    @pytest.mark.parametrize("mode,tol", [("bf16", 1e-2), ("int8", 1e-2)])
    def test_quantized_close(self, rng, mesh, mode, tol):
        x = jnp.asarray(rng.randn(N, 16, 128).astype(np.float32))

        def body(v):
            s = CA.reduce_scatter(v[0], "data", N, mode)
            return CA.all_gather_rows(s, "data", mode)

        out = np.asarray(_run(mesh, body, x))
        ref = np.sum(np.asarray(x), axis=0)
        err = np.max(np.abs(out - ref)) / np.max(np.abs(ref))
        assert err < tol, err

    def test_indivisible_rows_raise(self, mesh):
        opts = dict(mesh=mesh, in_specs=(P("data"),), out_specs=P("data"))

        def body(v):
            return CA.reduce_scatter(v[0], "data", N, "int8")

        with pytest.raises(ValueError, match="divisible"):
            jax.jit(jax.shard_map(body, **opts, check_vma=False))(
                jnp.zeros((N, 12, 128)))  # 12 % 8 != 0

    def test_pad_rows(self):
        x = jnp.ones((12, 128))
        y = CA.pad_rows(x, N)
        assert y.shape == (16, 128)
        np.testing.assert_array_equal(np.asarray(y[12:]), 0.0)
        assert CA.pad_rows(y, N) is y


class TestPsumCompressed:
    @pytest.mark.parametrize("shape", [(33, 7), (128,), (1,)])
    def test_arbitrary_shapes(self, rng, mesh, shape):
        x = jnp.asarray(rng.randn(N, *shape).astype(np.float32))

        def body(v):
            return CA.psum_compressed(v[0], "data", N, "int8")

        out = np.asarray(_run(mesh, body, x))
        ref = np.sum(np.asarray(x), axis=0)
        scale = max(np.max(np.abs(ref)), 1e-6)
        assert np.max(np.abs(out - ref)) / scale < 2e-2
        assert out.shape == tuple(shape)

    def test_f32_is_plain_psum(self, rng, mesh):
        x = jnp.asarray(rng.randn(N, 9, 5).astype(np.float32))

        def body(v):
            return CA.psum_compressed(v[0], "data", N, None)

        def ref_body(v):
            return jax.lax.psum(v[0], "data")

        np.testing.assert_array_equal(np.asarray(_run(mesh, body, x)),
                                      np.asarray(_run(mesh, ref_body, x)))

    def test_tree_skips_int_leaves(self, mesh):
        tree = {"g": jnp.ones((N, 4, 128)),
                "count": jnp.ones((N,), jnp.int32)}

        def body(v):
            v = jax.tree_util.tree_map(lambda x: x[0], v)
            return CA.psum_tree_compressed(v, "data", N, "int8")

        out = jax.jit(jax.shard_map(
            body, mesh=mesh,
            in_specs=({"g": P("data"), "count": P("data")},),
            out_specs=P(), check_vma=False))(tree)
        assert out["count"].dtype == jnp.int32
        assert int(out["count"]) == N          # exact integer psum
        np.testing.assert_allclose(np.asarray(out["g"]), 8.0, rtol=1e-6)


class TestSplitByMessageSize:
    def test_bytes_are_dtype_aware(self):
        # four 128-element tensors: f32 = 512 B each, bf16 = 256 B each.
        # A 1 KiB cap holds 2 f32 tensors per bucket but 4 bf16 ones.
        shapes = [(128,)] * 4
        assert B.split_by_message_size(shapes, jnp.float32, 1024) == \
            [[0, 1], [2, 3]]
        assert B.split_by_message_size(shapes, jnp.bfloat16, 1024) == \
            [[0, 1, 2, 3]]

    def test_padded_footprint_counts(self):
        # a 1-element tensor still ships a full LANE-padded row (512 B f32)
        assert B.split_by_message_size([(1,), (1,)], jnp.float32, 512) == \
            [[0], [1]]

    def test_oversize_tensor_gets_own_group(self):
        shapes = [(64,), (1024,), (64,)]
        groups = B.split_by_message_size(shapes, jnp.float32, 1024)
        assert groups == [[0], [1], [2]]     # 4 KiB tensor > 1 KiB cap

    def test_nonpositive_cap_rejected(self):
        with pytest.raises(ValueError, match="message_size"):
            B.split_by_message_size([(4,)], jnp.float32, 0)
