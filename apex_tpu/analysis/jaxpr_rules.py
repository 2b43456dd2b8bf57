"""Jaxpr-level analyzers: dtype promotion, donation, host sync,
recompilation.

These run on the CLOSED JAXPR (pre-XLA), where the op stream still
carries user-level structure: primitive names, ``named_scope``
provenance on every eqn (``eqn.source_info.name_stack``) and the
argument pytree paths.  Each analyzer is a pure function
``(LintProgram, LintConfig) -> [Finding]`` registered with the linter.

Rule ids (catalog in ``docs/source/analysis.md``):

* ``dtype/bf16-upcast-matmul`` — a matmul executing in f32 whose
  operand was upcast from bf16/f16: in an amp/bf16 path this silently
  runs the MXU at the f32 rate (~1/8th) and doubles operand traffic.
* ``dtype/f64-op`` — any f64/c128 op: unintended x64 promotion
  (catastrophic on TPU — f64 is emulated).
* ``donation/missing`` — an input leaf that is shape/dtype-aliasable
  with an output but not donated: params + opt state held twice (the
  double-HBM hazard donation exists to prevent).
* ``host-sync/callback`` — callbacks/debug prints reachable from the
  step fn: each one is a device->host round trip per step.
* ``recompile/unhashable-static`` / ``recompile/identity-static`` —
  static args that cannot hash (jit raises) or hash by object identity
  (every fresh instance silently retraces).
"""

from __future__ import annotations

import numpy as np
from jax.extend import core as jex_core

from apex_tpu.analysis.findings import Finding

# dataflow the dtype walk may cross while tracking "the same value"
_TRANSPARENT = frozenset({
    "transpose", "reshape", "broadcast_in_dim", "squeeze", "copy",
    "slice", "rev"})
_MATMUL = frozenset({"dot_general", "conv_general_dilated"})
_SMALL_FLOATS = ("bfloat16", "float16")

_CALLBACK_PRIMS = frozenset({
    "pure_callback", "io_callback", "debug_callback", "debug_print",
    "callback", "infeed", "outfeed", "host_callback_call"})


def _all_jaxprs(closed_jaxpr):
    """Yield the top jaxpr and every sub-jaxpr (scan/cond/remat/pjit
    bodies), depth-first."""
    import jax
    seen = []

    def walk(jaxpr):
        seen.append(jaxpr)
        for eqn in jaxpr.eqns:
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(closed_jaxpr.jaxpr)
    return seen


def _scope(eqn) -> str:
    try:
        return str(eqn.source_info.name_stack)
    except Exception:
        return ""


def _dtype_of(var):
    aval = getattr(var, "aval", None)
    return getattr(aval, "dtype", None)


def analyze_dtype_promotion(program, config):
    """bf16->f32 upcasts feeding f32 matmuls, and any f64 op."""
    findings = []
    f64_count = 0
    f64_first = None
    upcast_hits = []
    for jaxpr in _all_jaxprs(program.closed_jaxpr()):
        # vars produced by a small-float -> f32 convert in this jaxpr
        upcast_vars = {}
        producers = {}
        for eqn in jaxpr.eqns:
            for v in eqn.outvars:
                producers[v] = eqn
            if eqn.primitive.name == "convert_element_type":
                src = _dtype_of(eqn.invars[0])
                dst = _dtype_of(eqn.outvars[0])
                if (src is not None and dst is not None
                        and str(src) in _SMALL_FLOATS
                        and str(dst) == "float32"):
                    upcast_vars[eqn.outvars[0]] = str(src)
        for eqn in jaxpr.eqns:
            for v in eqn.outvars:
                dt = _dtype_of(v)
                if dt is not None and str(dt) in ("float64", "complex128"):
                    f64_count += 1
                    if f64_first is None:
                        f64_first = (eqn.primitive.name, _scope(eqn))
            if eqn.primitive.name not in _MATMUL:
                continue
            out_dt = _dtype_of(eqn.outvars[0])
            if out_dt is None or str(out_dt) != "float32":
                continue
            for invar in eqn.invars:
                # walk back through transparent ops to the origin
                v = invar
                for _ in range(32):
                    if isinstance(v, jex_core.Literal):
                        break          # inline constant: no producer
                    if v in upcast_vars:
                        upcast_hits.append(
                            (upcast_vars[v], eqn.primitive.name,
                             _scope(eqn)))
                        break
                    p = producers.get(v)
                    if p is None or p.primitive.name not in _TRANSPARENT:
                        break
                    v = p.invars[0]
    if upcast_hits:
        src, prim, scope = upcast_hits[0]
        findings.append(Finding(
            rule="dtype/bf16-upcast-matmul", severity="warning",
            message=(f"{len(upcast_hits)} matmul(s) execute in f32 on "
                     f"operands upcast from {src} (first: {prim} at "
                     f"{scope or '<top>'}) — the MXU runs f32 at ~1/8 "
                     "the bf16 rate and operand traffic doubles"),
            scope=scope, op=prim,
            fix_hint=("keep the matmul operands in the compute dtype and "
                      "accumulate in f32 via preferred_element_type, as "
                      "ops.lm_head does"),
            details={"count": len(upcast_hits), "source_dtype": src}))
    if f64_count:
        prim, scope = f64_first
        findings.append(Finding(
            rule="dtype/f64-op", severity="error",
            message=(f"{f64_count} op(s) compute in f64/c128 (first: "
                     f"{prim} at {scope or '<top>'}) — unintended x64 "
                     "promotion; TPUs emulate f64 at ~1/100 rate"),
            scope=scope, op=prim,
            fix_hint=("keep jax_enable_x64 off, or cast the offending "
                      "input to f32 at the boundary"),
            details={"count": f64_count}))
    return findings


def analyze_donation(program, config):
    """Input leaves aliasable with outputs but not donated."""
    import jax
    jaxpr = program.closed_jaxpr()
    leaves = program.arg_leaves()
    invars = jaxpr.jaxpr.invars
    if len(invars) != len(leaves):
        return []                      # closure-captured consts etc.
    out_avals = [getattr(v, "aval", None) for v in jaxpr.jaxpr.outvars]

    def sig(aval):
        shape = getattr(aval, "shape", None)
        dtype = getattr(aval, "dtype", None)
        return (None if shape is None else tuple(shape), str(dtype))

    out_pool = {}
    for aval in out_avals:
        s = sig(aval)
        out_pool[s] = out_pool.get(s, 0) + 1
    donated = set(program.donate_argnums)
    # donated inputs claim their matching outputs first
    for argnum, path, leaf in leaves:
        if argnum in donated:
            s = sig(getattr(leaf, "aval", None) or _np_aval(leaf))
            if out_pool.get(s, 0) > 0:
                out_pool[s] -= 1
    # remaining matches against non-donated inputs, grouped per argnum
    per_arg = {}
    for argnum, path, leaf in leaves:
        if argnum in donated:
            continue
        aval = getattr(leaf, "aval", None) or _np_aval(leaf)
        s = sig(aval)
        if s[0] is None or out_pool.get(s, 0) <= 0:
            continue
        out_pool[s] -= 1
        nbytes = int(np.prod(s[0], dtype=np.int64) *
                     np.dtype(s[1]).itemsize) if s[0] is not None else 0
        ex_bytes, ex_count, ex_path = per_arg.get(argnum, (0, 0, path))
        per_arg[argnum] = (ex_bytes + nbytes, ex_count + 1, ex_path)
    findings = []
    for argnum, (nbytes, count, path) in sorted(per_arg.items()):
        if nbytes < config.donation_min_bytes:
            continue
        findings.append(Finding(
            rule="donation/missing", severity="warning",
            message=(f"arg {argnum} has {count} leaf(s) totalling "
                     f"{nbytes:,} B whose shape/dtype matches an output "
                     f"but is not donated (first leaf {path!r}) — both "
                     "copies are live across the step (double-HBM "
                     "hazard)"),
            scope=f"arg{argnum}", op="",
            fix_hint=(f"add {argnum} to donate_argnums (and stop reading "
                      "the input buffer after the call)"),
            details={"argnum": argnum, "aliasable_bytes": nbytes,
                     "leaves": count, "example_path": path}))
    return findings


def _np_aval(leaf):
    class _A:
        def __init__(self, x):
            x = np.asarray(x)
            self.shape, self.dtype = x.shape, x.dtype
    return _A(leaf)


def analyze_host_sync(program, config):
    """Callbacks / debug prints / infeed-outfeed inside the program."""
    hits = []
    for jaxpr in _all_jaxprs(program.closed_jaxpr()):
        for eqn in jaxpr.eqns:
            name = eqn.primitive.name
            if name in _CALLBACK_PRIMS or name.endswith("_callback"):
                hits.append((name, _scope(eqn)))
    findings = []
    seen = set()
    for name, scope in hits:
        key = (name, scope)
        if key in seen:
            continue
        seen.add(key)
        findings.append(Finding(
            rule="host-sync/callback", severity="warning",
            message=(f"`{name}` reachable from the step fn at "
                     f"{scope or '<top>'} — a device->host round trip "
                     "per step (the class of sync PR 5 cut 2->1 by "
                     "hand)"),
            scope=scope or name, op=name,
            fix_hint=("move the readback out of the step (batch it with "
                      "the telemetry vector) or gate it behind a debug "
                      "flag"),
            details={"primitive": name}))
    return findings


def analyze_recompile(program, config):
    """Static args that cannot hash or hash by identity."""
    findings = []
    for i in program.static_argnums:
        if i >= len(program.args):
            continue
        v = program.args[i]
        try:
            hash(v)
        except TypeError:
            findings.append(Finding(
                rule="recompile/unhashable-static", severity="error",
                message=(f"static arg {i} ({type(v).__name__}) is "
                         "unhashable — jit raises at call time"),
                scope=f"arg{i}", op=type(v).__name__,
                fix_hint=("pass it as a hashable (tuple / frozen "
                          "dataclass) or make it a traced arg"),
                details={"argnum": i, "type": type(v).__name__}))
            continue
        t = type(v)
        if (t.__hash__ is object.__hash__
                and getattr(t, "__eq__", None) is object.__eq__):
            findings.append(Finding(
                rule="recompile/identity-static", severity="warning",
                message=(f"static arg {i} ({t.__name__}) hashes by "
                         "object identity — every fresh instance "
                         "silently retraces and recompiles"),
                scope=f"arg{i}", op=t.__name__,
                fix_hint=("pass a module-level singleton, or give the "
                          "type __eq__/__hash__ over its contents"),
                details={"argnum": i, "type": t.__name__}))
    return findings
