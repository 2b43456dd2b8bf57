"""Native host runtime + gpu_direct_storage (reference:
``apex/contrib/csrc/gpu_direct_storage``, ``csrc/flatten_unflatten.cpp``).

The native .so is compiled on demand by ``apex_tpu.utils.native``; every
API must also work with the library disabled (pure-Python fallback), so
each test runs both paths.
"""

import importlib

import numpy as np
import pytest

from apex_tpu.utils import native


@pytest.fixture(params=["native", "fallback"])
def native_mode(request, monkeypatch):
    if request.param == "native":
        if native.lib() is None:
            pytest.skip("native host runtime unavailable (no g++?)")
    else:
        monkeypatch.setattr(native, "_lib", None)
        monkeypatch.setattr(native, "_tried", True)
    return request.param


class TestPack:
    def test_roundtrip_mixed_dtypes(self, native_mode):
        rng = np.random.RandomState(0)
        arrs = [rng.randn(17, 3).astype(np.float32),
                rng.randint(0, 100, (5,)).astype(np.int64),
                rng.randn(2, 2, 2).astype(np.float16),
                np.asarray(3.0, np.float64)]
        buf = native.pack(arrs)
        assert buf.dtype == np.uint8
        assert buf.size == sum(a.nbytes for a in arrs)
        outs = [np.empty_like(a) for a in arrs]
        native.unpack(buf, outs)
        for a, o in zip(arrs, outs):
            np.testing.assert_array_equal(a, o)

    def test_matches_concatenate(self, native_mode):
        rng = np.random.RandomState(1)
        arrs = [rng.randn(n).astype(np.float32) for n in (1, 1000, 77)]
        buf = native.pack(arrs)
        ref = np.concatenate([a.view(np.uint8).reshape(-1) for a in arrs])
        np.testing.assert_array_equal(buf, ref)

    def test_large_multithreaded(self, native_mode):
        rng = np.random.RandomState(2)
        arrs = [rng.randn(300_000).astype(np.float32) for _ in range(4)]
        buf = native.pack(arrs)  # >1 MiB: native path goes threaded
        outs = [np.empty_like(a) for a in arrs]
        native.unpack(buf, outs)
        for a, o in zip(arrs, outs):
            np.testing.assert_array_equal(a, o)

    def test_empty_list(self, native_mode):
        assert native.pack([]).size == 0


class TestFileIO:
    def test_roundtrip(self, native_mode, tmp_path):
        rng = np.random.RandomState(3)
        data = rng.randint(0, 256, (123457,)).astype(np.uint8)
        p = str(tmp_path / "blob.bin")
        native.file_write(p, data)
        out = native.file_read(p)
        np.testing.assert_array_equal(data, out)

    def test_large_parallel(self, native_mode, tmp_path):
        data = np.arange(9 << 20, dtype=np.uint8)  # >8 MiB: threaded
        p = str(tmp_path / "big.bin")
        native.file_write(p, data, threads=4)
        out = native.file_read(p, threads=4)
        np.testing.assert_array_equal(data, out)


class TestGDS:
    def _gds(self):
        return importlib.import_module(
            "apex_tpu.contrib.gpu_direct_storage")

    def test_numpy_roundtrip(self, native_mode, tmp_path):
        gds = self._gds()
        rng = np.random.RandomState(4)
        a = rng.randn(33, 7).astype(np.float32)
        p = str(tmp_path / "t.apxt")
        gds.save(p, a)
        out = gds.load(p)
        assert out.dtype == a.dtype and out.shape == a.shape
        np.testing.assert_array_equal(a, out)

    def test_pytree_roundtrip(self, native_mode, tmp_path):
        gds = self._gds()
        rng = np.random.RandomState(5)
        tree = {"w": rng.randn(8, 8).astype(np.float32),
                "stats": [rng.randn(3).astype(np.float64),
                          np.asarray(7, np.int32)]}
        p = str(tmp_path / "tree.apxt")
        gds.save(p, tree)
        out = gds.load(p, tree_like=tree)
        assert set(out) == {"w", "stats"}
        np.testing.assert_array_equal(out["w"], tree["w"])
        np.testing.assert_array_equal(out["stats"][0], tree["stats"][0])
        np.testing.assert_array_equal(out["stats"][1], tree["stats"][1])

    def test_overwrite_pytree_with_array(self, native_mode, tmp_path):
        """save(array) over a pytree checkpoint must clear the sidecar so
        load() dispatches on the new format."""
        gds = self._gds()
        p = str(tmp_path / "ck.apxt")
        gds.save(p, {"w": np.arange(4.0)})
        a = np.arange(10.0).reshape(2, 5)
        gds.save(p, a)
        out = gds.load(p)
        assert out.shape == (2, 5)
        np.testing.assert_array_equal(out, a)

    def test_jax_array(self, native_mode, tmp_path):
        import jax.numpy as jnp
        gds = self._gds()
        a = jnp.arange(16.0).reshape(4, 4)
        p = str(tmp_path / "jx.apxt")
        gds.save(p, a)
        np.testing.assert_array_equal(gds.load(p), np.asarray(a))


def test_gds_scalar_leaves_roundtrip(tmp_path):
    """0-d leaves must round-trip as 0-d: np.ascontiguousarray promotes
    scalars to 1-d, which used to corrupt optimizer step counters and
    scaler state in checkpoints (caught by the resume recipe)."""
    import jax.numpy as jnp

    from apex_tpu.contrib import gpu_direct_storage as gds

    obj = {"a": jnp.zeros((3, 4)), "step": jnp.int32(7),
           "scale": jnp.float32(2.5)}
    path = str(tmp_path / "scalars.bin")
    gds.save(path, obj)
    back = gds.load(path, tree_like=obj)
    assert np.asarray(back["step"]).shape == ()
    assert np.asarray(back["scale"]).shape == ()
    assert int(back["step"]) == 7 and float(back["scale"]) == 2.5
    np.testing.assert_array_equal(np.asarray(back["a"]),
                                  np.asarray(obj["a"]))


def test_artifact_name_is_tied_to_the_source(tmp_path, monkeypatch):
    """The loader only looks for the library built from THIS source: the
    name carries the source's hash, so a stale ``.so`` left in
    ``apex_tpu/csrc`` by an older ``host_runtime.cpp`` is never loaded."""
    import hashlib

    with open(native._SRC, "rb") as f:
        digest = hashlib.sha256(f.read()).hexdigest()[:16]
    assert native._built_path().endswith(
        f"libapex_host_runtime.{digest}.so")
    src = tmp_path / "host_runtime.cpp"
    src.write_text("// a different source\n")
    monkeypatch.setattr(native, "_SRC", str(src))
    assert digest not in native._built_path()

