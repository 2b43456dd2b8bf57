"""ctypes loader for the native host runtime (``apex_tpu/csrc``).

The reference builds its host-side machinery as C++ extensions (apex_C,
gpu_direct_storage, …) flag-gated in setup.py.  Here the library is a
plain C-ABI shared object: ``pip install`` with ``APEX_TPU_CPP_EXT=1``
builds it, and as a developer convenience this loader will also compile
it on first use with g++ into the package directory.  Every caller must
tolerate ``lib() is None`` (pure-Python fallback) — the native path is a
host-side performance feature, never a correctness requirement.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import threading

import numpy as np

_SRC = os.path.join(os.path.dirname(os.path.dirname(__file__)), "csrc",
                    "host_runtime.cpp")


def _built_path() -> str | None:
    """The one artifact this source builds: the source's hash is in the
    file name (setup.py uses the same rule), so a library left behind by
    an older ``host_runtime.cpp`` is never loaded."""
    try:
        with open(_SRC, "rb") as f:
            digest = hashlib.sha256(f.read()).hexdigest()[:16]
    except OSError:
        return None
    return os.path.join(os.path.dirname(_SRC),
                        f"libapex_host_runtime.{digest}.so")

_lock = threading.Lock()
_lib = None
_tried = False


def _configure(lib) -> bool:
    try:
        lib.apex_version.restype = ctypes.c_int
        if lib.apex_version() != 1:
            return False
        lib.apex_pack.restype = ctypes.c_int
        lib.apex_pack.argtypes = [ctypes.POINTER(ctypes.c_void_p),
                                  ctypes.POINTER(ctypes.c_size_t),
                                  ctypes.c_int, ctypes.c_void_p]
        lib.apex_unpack.restype = ctypes.c_int
        lib.apex_unpack.argtypes = [ctypes.c_void_p,
                                    ctypes.POINTER(ctypes.c_void_p),
                                    ctypes.POINTER(ctypes.c_size_t),
                                    ctypes.c_int]
        lib.apex_file_write.restype = ctypes.c_int
        lib.apex_file_write.argtypes = [ctypes.c_char_p, ctypes.c_void_p,
                                        ctypes.c_size_t, ctypes.c_int]
        lib.apex_file_read.restype = ctypes.c_int
        lib.apex_file_read.argtypes = [ctypes.c_char_p, ctypes.c_void_p,
                                       ctypes.c_size_t, ctypes.c_int]
        return True
    except AttributeError:
        return False


def _try_load(path):
    try:
        lib = ctypes.CDLL(path)
    except OSError:
        return None
    return lib if _configure(lib) else None


def _compile(built: str) -> bool:
    tmp = f"{built}.{os.getpid()}.tmp"     # rename: no half-written load
    try:
        subprocess.run(
            ["g++", "-O3", "-shared", "-fPIC", "-std=c++17", "-pthread",
             _SRC, "-o", tmp],
            check=True, capture_output=True, timeout=120)
        os.replace(tmp, built)
        return True
    except (OSError, subprocess.SubprocessError):
        return False


def lib():
    """The loaded native library, or None (use the Python fallback)."""
    global _lib, _tried
    if _lib is not None or _tried:
        return _lib
    with _lock:
        if _lib is not None or _tried:
            return _lib
        if os.environ.get("APEX_TPU_NO_NATIVE"):
            _tried = True
            return None
        built = _built_path()
        # built from THIS source already (pip build or an earlier
        # on-demand compile), else compile on demand (developer path)
        if built is not None and (os.path.exists(built) or _compile(built)):
            _lib = _try_load(built)
        _tried = True
        return _lib


def _as_1d_bytes(a: np.ndarray) -> np.ndarray:
    a = np.ascontiguousarray(a)
    return a.view(np.uint8).reshape(-1)


def pack(arrays, out: np.ndarray | None = None) -> np.ndarray:
    """Gather a list of numpy arrays into one contiguous uint8 buffer.

    Native path releases the GIL and memcpys with all host cores; the
    fallback is np.concatenate.  This is the host-side stage of bucket
    packing (device-side packing stays inside jit — see
    ``multi_tensor_apply.bucketing``).
    """
    views = [_as_1d_bytes(a) for a in arrays]
    total = int(sum(v.size for v in views))
    if out is None:
        out = np.empty((total,), np.uint8)
    else:
        assert out.dtype == np.uint8 and out.size == total
    L = lib()
    if L is None:
        off = 0
        for v in views:
            out[off:off + v.size] = v
            off += v.size
        return out
    n = len(views)
    srcs = (ctypes.c_void_p * n)(*[v.ctypes.data for v in views])
    sizes = (ctypes.c_size_t * n)(*[v.size for v in views])
    rc = L.apex_pack(srcs, sizes, n, out.ctypes.data)
    if rc != 0:
        raise OSError(-rc, f"apex_pack failed: {rc}")
    return out


def unpack(buf: np.ndarray, arrays) -> None:
    """Scatter a contiguous uint8 buffer back into the given arrays."""
    views = [_as_1d_bytes(a) for a in arrays]
    # _as_1d_bytes may copy non-contiguous inputs; require contiguous so
    # the scatter lands in the caller's memory
    for a, v in zip(arrays, views):
        if a.__array_interface__["data"][0] != \
                v.__array_interface__["data"][0]:
            raise ValueError("unpack needs contiguous destination arrays")
    buf = np.ascontiguousarray(buf).view(np.uint8).reshape(-1)
    L = lib()
    if L is None:
        off = 0
        for v in views:
            v[:] = buf[off:off + v.size]
            off += v.size
        return
    n = len(views)
    dsts = (ctypes.c_void_p * n)(*[v.ctypes.data for v in views])
    sizes = (ctypes.c_size_t * n)(*[v.size for v in views])
    rc = L.apex_unpack(buf.ctypes.data, dsts, sizes, n)
    if rc != 0:
        raise OSError(-rc, f"apex_unpack failed: {rc}")


def file_write(path: str, buf: np.ndarray, threads: int = 4) -> None:
    """Write a contiguous buffer to ``path`` (parallel pwrite natively)."""
    v = _as_1d_bytes(buf)
    L = lib()
    if L is None:
        with open(path, "wb") as f:
            f.write(v.tobytes())
        return
    rc = L.apex_file_write(path.encode(), v.ctypes.data, v.size,
                           int(threads))
    if rc != 0:
        raise OSError(-rc, f"apex_file_write({path}) failed")


def file_read(path: str, nbytes: int | None = None,
              threads: int = 4) -> np.ndarray:
    """Read ``path`` into a fresh uint8 buffer (parallel pread natively)."""
    size = os.path.getsize(path) if nbytes is None else int(nbytes)
    out = np.empty((size,), np.uint8)
    L = lib()
    if L is None:
        with open(path, "rb") as f:
            data = f.read(size)
        out[:] = np.frombuffer(data, np.uint8)
        return out
    rc = L.apex_file_read(path.encode(), out.ctypes.data, size,
                          int(threads))
    if rc != 0:
        raise OSError(-rc, f"apex_file_read({path}) failed")
    return out
