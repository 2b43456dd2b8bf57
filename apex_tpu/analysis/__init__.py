"""apex_tpu.analysis — static analysis of jitted programs.

A linter over the artifacts jit already produces (closed jaxpr,
optimized scheduled HLO, the compiled object): dtype-promotion leaks,
missing buffer donation, host-sync hazards, recompile hazards, sharding
lint, collective-overlap audit, plus a liveness-based peak-memory
estimator cross-checked against ``compiled.memory_analysis()``.

Compile-only: nothing is ever executed.  ``tools/lint_graph.py`` runs
the registry over every canonical train/serve program against a
committed baseline; ``tests/test_analysis.py`` carries the same check
in tier-1.
"""

from apex_tpu.analysis.findings import (BASELINE_VERSION, Finding,
                                        LintReport, load_baseline,
                                        save_baseline)
from apex_tpu.analysis.hlo import (HloModule, Instruction, parse_hlo_module,
                                   scope_of, shape_bytes)
from apex_tpu.analysis.linter import ANALYZERS, LintConfig, lint, lint_fn
from apex_tpu.analysis.memory import (MemoryEstimate, estimate_from_hlo_text,
                                      estimate_peak_memory, xla_peak_bytes)
from apex_tpu.analysis.program import LintProgram

__all__ = [
    "ANALYZERS", "BASELINE_VERSION", "Finding", "HloModule", "Instruction",
    "LintConfig", "LintProgram", "LintReport", "MemoryEstimate",
    "estimate_from_hlo_text", "estimate_peak_memory", "lint", "lint_fn",
    "load_baseline", "parse_hlo_module", "save_baseline", "scope_of",
    "shape_bytes", "xla_peak_bytes",
]
