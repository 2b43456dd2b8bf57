"""apex-tpu build (reference: apex ``setup.py``, ~900 lines of flag-gated
CUDA extension builds — ``--cpp_ext --cuda_ext --fmha --bnp ...``).

The TPU rebuild needs none of that for device code: every kernel is
JAX/Pallas, shipped as Python.  The one native artifact is the host
runtime (``apex_tpu/csrc/host_runtime.cpp`` — threaded buffer packing and
parallel file IO used by bucketing and gpu_direct_storage).  Mirroring the
reference's gating, it is built when ``APEX_TPU_CPP_EXT=1`` (or the
``--cpp_ext`` global option) is set and skipped otherwise; at runtime
``apex_tpu.utils.native`` also compiles it on demand and always has a
pure-Python fallback, so a wheel without it is functional.
"""

import hashlib
import os
import subprocess
import sys

from setuptools import setup
from setuptools.command.build_py import build_py


def _want_cpp_ext() -> bool:
    if os.environ.get("APEX_TPU_CPP_EXT") == "1":
        return True
    if "--cpp_ext" in sys.argv:
        sys.argv.remove("--cpp_ext")
        return True
    return False


class BuildWithNative(build_py):
    def run(self):
        if _want_cpp_ext():
            src = os.path.join("apex_tpu", "csrc", "host_runtime.cpp")
            # the source's hash names the artifact, as the loader
            # (apex_tpu/utils/native.py::_built_path) expects
            with open(src, "rb") as f:
                digest = hashlib.sha256(f.read()).hexdigest()[:16]
            out = os.path.join("apex_tpu", "csrc",
                               f"libapex_host_runtime.{digest}.so")
            print(f"building native host runtime: {src} -> {out}")
            subprocess.run(
                ["g++", "-O3", "-shared", "-fPIC", "-std=c++17",
                 "-pthread", src, "-o", out],
                check=True)
        super().run()


setup(
    cmdclass={"build_py": BuildWithNative},
    package_data={"apex_tpu": ["csrc/*.cpp", "csrc/*.so"]},
)
