"""``benchmarks/tests/test_serving_check.py`` (what serving's ``correct``
holds a configuration to: PR 33's cases) collected by path, so that ``pytest
tests/`` runs it; the file stays where the benchmark keeps it (``python3 -m
pytest benchmarks/tests``)."""

import importlib.util
import os

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_spec = importlib.util.spec_from_file_location(
    "serving_check_tests",
    os.path.join(ROOT, "benchmarks", "tests", "test_serving_check.py"))
_own = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_own)
globals().update({k: v for k, v in vars(_own).items()
                  if k.startswith("test_")})
