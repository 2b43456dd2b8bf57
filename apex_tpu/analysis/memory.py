"""Peak-memory / liveness estimation from scheduled HLO.

The feasibility term the auto-parallel planner needs (AMP, GSPMD — both
prune candidate plans by predicted per-device memory before measuring
anything): given a compiled program's HLO text, estimate the per-device
peak bytes and name the top live-set contributors.

Method — classic linear-scan liveness over the SCHEDULED instruction
order (``is_scheduled=true``: the text order is the execution order):

* every non-view instruction defines a buffer of its result bytes, live
  from its position to its last use (the root's buffers to the end);
* ``parameter``/``get-tuple-element``/``tuple``/``bitcast`` are views —
  no new bytes, but they keep their source buffers alive;
* entry parameters are caller-owned: live for the whole program;
* donated inputs (``input_output_alias``) zero out the aliased OUTPUT
  buffers — the update writes in place, which is exactly the
  double-HBM hazard the donation lint rule is about;
* ``while``/``call``/``conditional`` recurse: the callee's internal
  peak is added at the call site (its parameters alias the caller's
  operands, so only genuinely new bytes count).

Fusion internals are invisible (their temps are register/scratch-sized
by construction), constants count at their position.  The estimate is
validated against ``compiled.memory_analysis()`` to within 1.5x in the
test suite (``tests/test_analysis.py::TestCanonical``).
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Tuple

from apex_tpu.analysis.hlo import (CALL_OPS, Computation, HloModule,
                                   VIEW_OPS, parse_hlo_module)


@dataclasses.dataclass
class MemoryEstimate:
    """Per-device peak-memory estimate for one compiled program."""
    peak_bytes: int
    argument_bytes: int
    output_bytes: int
    aliased_bytes: int            # output bytes served by donated inputs
    temp_peak_bytes: int          # peak - (args + outputs - aliased)
    top_live: List[Tuple[int, str, str]]   # (bytes, instr, scope) at peak
    xla_peak_bytes: Optional[int] = None   # from compiled.memory_analysis()
    xla_ratio: Optional[float] = None      # estimate / xla, when available

    def to_dict(self) -> dict:
        return {
            "peak_bytes": self.peak_bytes,
            "argument_bytes": self.argument_bytes,
            "output_bytes": self.output_bytes,
            "aliased_bytes": self.aliased_bytes,
            "temp_peak_bytes": self.temp_peak_bytes,
            "top_live": [{"bytes": b, "instruction": n, "scope": s}
                         for b, n, s in self.top_live],
            "xla_peak_bytes": self.xla_peak_bytes,
            "xla_ratio": (None if self.xla_ratio is None
                          else round(self.xla_ratio, 3)),
        }

    def format_summary(self) -> str:
        lines = [f"peak ~{_fmt(self.peak_bytes)} "
                 f"(args {_fmt(self.argument_bytes)}, "
                 f"outputs {_fmt(self.output_bytes)}"
                 + (f" [{_fmt(self.aliased_bytes)} donated-in-place]"
                    if self.aliased_bytes else "")
                 + f", temps {_fmt(self.temp_peak_bytes)})"]
        if self.xla_peak_bytes is not None:
            lines[0] += (f"  vs XLA {_fmt(self.xla_peak_bytes)} "
                         f"({self.xla_ratio:.2f}x)")
        for b, name, scope in self.top_live[:10]:
            lines.append(f"  live@peak {_fmt(b):>10}  {name}"
                         + (f"  [{scope}]" if scope else ""))
        return "\n".join(lines)


def _fmt(n: int) -> str:
    for unit in ("B", "KiB", "MiB", "GiB"):
        if abs(n) < 1024 or unit == "GiB":
            return (f"{n:.0f}{unit}" if unit == "B"
                    else f"{n / 1.0:.1f}{unit}")
        n /= 1024
    return f"{n}B"


def _storage_map(comp: Computation) -> Dict[str, frozenset]:
    """Map each instruction name to the set of DEFINING buffer names its
    value lives in (views forward their operands' storage).

    ``while`` forwards too: XLA's in-place loop execution aliases the
    init operand, the body parameter, the body root and the while result
    into ONE allocation, so a while defines no new bytes — its carry is
    whatever buffers built the init (and a chained scan, e.g. the 1F1B
    forward stash feeding the backward loop, shares a single carry
    allocation instead of double-counting)."""
    storage: Dict[str, frozenset] = {}
    by_name = comp.by_name()
    for ins in comp.instructions:
        if (ins.opcode in VIEW_OPS and ins.opcode != "parameter") \
                or ins.opcode == "while":
            s: frozenset = frozenset()
            for op in ins.operands:
                s |= storage.get(op, frozenset())
            storage[ins.name] = s
        else:
            storage[ins.name] = frozenset({ins.name})
    return storage


def _comp_peak(module: HloModule, comp: Computation,
               memo: Dict[Tuple[str, bool], int], *, entry: bool = False,
               zero_root: bool = False,
               aliased_outputs: frozenset = frozenset()
               ) -> Tuple[int, int, List[Tuple[int, str, str]]]:
    """(peak_bytes, output_bytes, top_live_at_peak) for one computation.

    Non-entry computations exclude their parameters (they alias caller
    buffers).  ``aliased_outputs`` (entry only) holds root tuple indices
    whose buffers are donated inputs — counted as zero new bytes.
    ``zero_root`` (while bodies) zeroes ALL root buffers: the next carry
    is written in place over the current one (XLA's in-place loop
    execution — dynamic-update-slice on the carry does not allocate), so
    only genuinely transient per-iteration temps count; the carry itself
    is the caller's ``while`` result.
    """
    instrs = comp.instructions
    if not instrs:
        return 0, 0, []
    storage = _storage_map(comp)
    by_name = comp.by_name()

    # buffer sizes: defining instructions only; views/params define none
    size: Dict[str, int] = {}
    def_pos: Dict[str, int] = {}
    for ins in instrs:
        if ins.is_param:
            if entry:
                size[ins.name] = ins.nbytes
                def_pos[ins.name] = 0
            continue
        if ins.opcode in VIEW_OPS or ins.opcode == "while":
            continue
        size[ins.name] = ins.nbytes
        def_pos[ins.name] = ins.index

    # root storage: the output buffers (live to the end)
    root = comp.root
    root_bufs = set(storage.get(root.name, frozenset()))
    if zero_root:
        for b in root_bufs:
            if b in size and not by_name[b].is_param:
                size[b] = 0
    if entry and aliased_outputs:
        # donated outputs write in place: zero those element buffers
        # (tuple roots alias per element; a non-tuple root is output 0)
        if root.opcode == "tuple":
            donated_ops = [root.operands[k] for k in aliased_outputs
                           if k < len(root.operands)]
        else:
            donated_ops = [root.name] if 0 in aliased_outputs else []
        for opnd in donated_ops:
            for b in storage.get(opnd, frozenset()):
                if b in size and not by_name[b].is_param:
                    size[b] = 0

    last_ref: Dict[str, int] = {b: p for b, p in def_pos.items()}
    for ins in instrs:
        for op in ins.operands:
            for b in storage.get(op, frozenset()):
                if b in last_ref:
                    last_ref[b] = max(last_ref[b], ins.index)
    end = len(instrs) - 1
    for b in root_bufs:
        if b in last_ref:
            last_ref[b] = end
    if entry:
        for ins in instrs:
            if ins.is_param:
                last_ref[ins.name] = end       # caller-owned

    # call-site transient: callee internal peak, live only at that index
    callee_extra: Dict[int, int] = {}
    for ins in instrs:
        if ins.opcode in CALL_OPS:
            zr = ins.opcode == "while"
            extra = 0
            for cname in ins.called:
                sub = module.computations.get(cname)
                if sub is None:
                    continue
                key = (cname, zr)
                if key not in memo:
                    memo[key] = 0              # cycle guard
                    memo[key] = _comp_peak(module, sub, memo,
                                           zero_root=zr)[0]
                extra = max(extra, memo[key])
            if extra:
                callee_extra[ins.index] = extra

    # sweep: +size at def, -size after last ref
    delta = [0] * (len(instrs) + 1)
    for b, sz in size.items():
        if sz <= 0:
            continue
        delta[def_pos[b]] += sz
        delta[last_ref[b] + 1] -= sz
    live = 0
    peak = 0
    peak_pos = 0
    for i in range(len(instrs)):
        live += delta[i]
        total = live + callee_extra.get(i, 0)
        if total > peak:
            peak, peak_pos = total, i

    # top live buffers at the peak position
    top = [(sz, b, by_name[b].scope) for b, sz in size.items()
           if sz > 0 and def_pos[b] <= peak_pos <= last_ref[b]]
    if peak_pos in callee_extra:
        top.append((callee_extra[peak_pos],
                    f"<{instrs[peak_pos].opcode} body "
                    f"{instrs[peak_pos].name}>",
                    instrs[peak_pos].scope))
    top.sort(key=lambda t: -t[0])

    out_bytes = sum(size.get(b, 0) for b in root_bufs)
    return peak, out_bytes, top[:10]


def estimate_from_hlo_text(text: str) -> MemoryEstimate:
    """Estimate per-device peak bytes from optimized HLO text."""
    module = parse_hlo_module(text)
    comp = module.entry
    aliases = module.input_output_aliases
    aliased_out = frozenset(o for o, _ in aliases)
    alias_params = {p for _, p in aliases}
    arg_bytes = sum(p.nbytes for p in comp.params)
    aliased_bytes = sum(p.nbytes for p in comp.params
                        if p.param_number in alias_params)
    memo: Dict[Tuple[str, bool], int] = {}
    peak, out_bytes, top = _comp_peak(module, comp, memo, entry=True,
                                      aliased_outputs=aliased_out)
    return MemoryEstimate(
        peak_bytes=peak,
        argument_bytes=arg_bytes,
        output_bytes=out_bytes + aliased_bytes,
        aliased_bytes=aliased_bytes,
        temp_peak_bytes=max(0, peak - arg_bytes - out_bytes),
        top_live=top)


def xla_peak_bytes(compiled) -> Optional[int]:
    """Comparable peak from ``compiled.memory_analysis()``:
    args + outputs + temps - aliased (donated outputs reuse argument
    memory).  ``None`` when the backend doesn't report, or reports all
    zeros (some backends stub the call out)."""
    try:
        ma = compiled.memory_analysis()
    except Exception:
        return None
    if ma is None:
        return None
    try:
        total = (ma.argument_size_in_bytes + ma.output_size_in_bytes
                 + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
    except AttributeError:
        return None
    return total if total > 0 else None


def estimate_peak_memory(compiled) -> MemoryEstimate:
    """Estimate from a jax ``Compiled`` object, with the XLA
    cross-check attached when the backend reports one."""
    est = estimate_from_hlo_text(compiled.as_text())
    xla = xla_peak_bytes(compiled)
    if xla:
        est.xla_peak_bytes = xla
        est.xla_ratio = est.peak_bytes / xla
    return est
