"""Backend/platform helpers.

Apex gates its native kernels at build time (setup.py feature flags) and each
Python wrapper raises ImportError when its extension is missing.  On TPU the
equivalent gate is *runtime*: Pallas kernels run on the TPU backend, and every
op carries a pure-jnp fallback with identical semantics for CPU/GPU (used by
the unit-test suite running on a fake 8-device CPU mesh).
"""

from __future__ import annotations

import os

import jax

_FORCE_PALLAS: bool | None = None

_REPO_ROOT = os.path.abspath(
    os.path.join(os.path.dirname(__file__), os.pardir, os.pardir))


def default_compile_cache_dir() -> str:
    """Where the compile cache lives when the environment names none:
    ``JAX_COMPILATION_CACHE_DIR`` if set, else ``<checkout>/.jax_cache``.
    The path is part of the cache key, so it never carries a temporary
    name, a pid or a time."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") \
        or os.path.join(_REPO_ROOT, ".jax_cache")


def setup_compile_cache() -> str:
    """Point JAX's persistent compilation cache at
    :func:`default_compile_cache_dir`.  When ``JAX_COMPILATION_CACHE_DIR``
    is in the environment nothing is touched (JAX reads it itself).
    Call before the first compile; returns the directory in use."""
    path = default_compile_cache_dir()
    if "JAX_COMPILATION_CACHE_DIR" not in os.environ:
        jax.config.update("jax_compilation_cache_dir", path)
    return path


def is_tpu_backend() -> bool:
    """True when the default JAX backend is a TPU.  A backend that fails
    to initialise (e.g. a chip held by another process) raises here — it
    must not turn into a quiet CPU run."""
    return jax.default_backend() == "tpu"


def set_force_pallas(value: bool | None) -> None:
    """Force Pallas kernels on (interpret mode off-TPU) / off, or None=auto."""
    global _FORCE_PALLAS
    _FORCE_PALLAS = value


def use_pallas() -> bool:
    """Whether fused ops should lower to Pallas kernels.

    Auto policy: Pallas on TPU, jnp fallback elsewhere.  Override with
    :func:`set_force_pallas` or ``APEX_TPU_FORCE_PALLAS=1/0``.
    """
    if _FORCE_PALLAS is not None:
        return _FORCE_PALLAS
    env = os.environ.get("APEX_TPU_FORCE_PALLAS")
    if env is not None:
        return env not in ("0", "false", "False")
    return is_tpu_backend()


def interpret_mode() -> bool:
    """Pallas ``interpret=`` flag: interpret when not actually on TPU."""
    return not is_tpu_backend()
