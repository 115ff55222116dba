"""Trip-count-aware HLO cost analysis (the §Roofline 'profiler').

XLA's `compiled.cost_analysis()` counts while-loop bodies ONCE (verified
empirically: a scanned 8-layer stack reports 1/8 the flops of the unrolled
stack). Since this framework scans over layers / microbatches / KV chunks,
all roofline terms here are derived by parsing `compiled.as_text()` directly:

- FLOPs: every `dot` (and dot-fusions) → 2 · |result| · contracted-size,
  multiplied by the trip counts of every enclosing while loop. Elementwise
  flops are ignored (dots dominate ≥95% on these models; stated in
  EXPERIMENTS.md §Roofline).
- Collective bytes: operand bytes of all-reduce / all-gather / reduce-scatter
  / all-to-all / collective-permute, trip-multiplied.
- HBM traffic estimate: *write-once model* — every materialized buffer
  (instruction result) counts its bytes once, ×2 for the paired read;
  dynamic-update-slice counts only the updated region; fusion internals are
  VMEM-resident. Trip-multiplied. Biases relative to a real TPU lowering are
  documented in EXPERIMENTS.md §Roofline (CPU upcasts bf16 math to f32 and
  stacks scan intermediates for backward, both inflating this estimate), so
  the dominant-bottleneck call also consults the analytic model in
  benchmarks/roofline.py; this estimate is still the right *relative* signal
  between two lowerings of the same cell, which is what §Perf iterates on.

Trip counts: a while's condition region compares the induction variable
against an integer constant; we take the largest integer constant found in
the condition region (incl. called computations). Every loop this framework
emits (lax.scan) has this form.

Shapes come from a global name→type symbol table built from instruction
definitions and computation signatures, so operand sizes resolve across
regions. Post-SPMD HLO is the per-device program: all numbers are per-chip.

The HLO-text parsing layer (shape/instruction/computation grammar, the
name→type symbol table) lives in `repro.analysis.ir` and is shared with the
serving-contract static analyzer (`repro.analysis`); this module keeps only
the roofline-specific cost model
(trip counts, dot/conv flops, the write-once byte model).
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict

# Shared HLO grammar — re-exported so existing consumers (tests, notebooks)
# keep importing them from here.
from repro.analysis.ir import (Computation, Instr, nbytes as _nbytes,  # noqa: F401
                               operand_names as _operand_names,
                               parse_hlo, parse_shapes as _parse_shapes,
                               symbol_table as _symbol_table,
                               CALLS_RE as _CALLS_RE)

_CONST_RE = re.compile(r"constant\((\d+)\)")

COLLECTIVE_OPS = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                  "collective-permute")


def _dot_flops(ins: Instr, table) -> float:
    res = _parse_shapes(ins.result_type)
    if not res:
        return 0.0
    _, rshape = res[0]
    out_elems = 1
    for d in rshape:
        out_elems *= d
    m = re.search(r"lhs_contracting_dims=\{([0-9,]*)\}", ins.rest)
    ops = _operand_names(ins.rest)
    contracted = 1
    if m and ops:
        lhs_type = table.get(ops[0], "")
        shapes = _parse_shapes(lhs_type)
        if shapes:
            _, lshape = shapes[0]
            for idx in (int(i) for i in m.group(1).split(",") if i):
                if idx < len(lshape):
                    contracted *= lshape[idx]
    return 2.0 * out_elems * contracted


def _conv_flops(ins: Instr, table) -> float:
    # flops ≈ 2 * |out| * (kernel spatial * in_features) — derive from window.
    res = _parse_shapes(ins.result_type)
    if not res:
        return 0.0
    _, rshape = res[0]
    out_elems = 1
    for d in rshape:
        out_elems *= d
    ops = _operand_names(ins.rest)
    k_elems = 1
    if len(ops) >= 2:
        kshapes = _parse_shapes(table.get(ops[1], ""))
        if kshapes:
            _, kshape = kshapes[0]
            for d in kshape:
                k_elems *= d
            # divide by output-feature dim (counted in out_elems)
            if kshape:
                k_elems //= max(kshape[-1], 1)
    return 2.0 * out_elems * k_elems


_SKIP_BYTES_OPS = {
    "parameter", "constant", "get-tuple-element", "tuple", "bitcast",
    "bitcast-convert", "reshape", "after-all", "partition-id", "replica-id",
}


@dataclasses.dataclass
class Cost:
    flops: float = 0.0
    bytes: float = 0.0
    collective_bytes: float = 0.0
    collective_breakdown: dict = dataclasses.field(default_factory=dict)

    def __iadd__(self, o):
        self.flops += o.flops
        self.bytes += o.bytes
        self.collective_bytes += o.collective_bytes
        for k, v in o.collective_breakdown.items():
            self.collective_breakdown[k] = self.collective_breakdown.get(k, 0.0) + v
        return self

    def scaled(self, f):
        return Cost(self.flops * f, self.bytes * f, self.collective_bytes * f,
                    {k: v * f for k, v in self.collective_breakdown.items()})


def _trip_count(cond_name, comps) -> int:
    """Trip count of a while from its condition region.

    lax.scan lowers to `compare(induction_var, constant(N), LT)` — the
    constant may be a direct compare operand or threaded through a fusion.
    We locate the ROOT of the condition region, resolve its constant
    operands (following one fusion hop), and take the max. Falls back to
    the max constant anywhere in the region."""
    comp = comps.get(cond_name)
    if comp is None:
        return 1

    local_defs = {ins.name: ins for ins in comp.instrs}

    def const_of(name):
        ins = local_defs.get(name)
        if ins is None:
            return None
        m = _CONST_RE.search(ins.line)
        return int(m.group(1)) if m else None

    candidates = []
    root = None
    for ins in comp.instrs:
        if ins.line.strip().startswith("ROOT"):
            root = ins
    if root is not None:
        frontier = [root]
        for hop in range(2):
            nxt = []
            for ins in frontier:
                for o in _operand_names(ins.rest):
                    c = const_of(o)
                    if c is not None:
                        candidates.append(c)
                    elif o in local_defs and local_defs[o].op in ("fusion", "compare", "call"):
                        nxt.append(local_defs[o])
            frontier = nxt
    if candidates:
        return max(candidates)
    best = 1
    for ins in comp.instrs:
        for c in _CONST_RE.findall(ins.line):
            best = max(best, int(c))
    return best


def analyze(text: str) -> Cost:
    comps = parse_hlo(text)
    table = _symbol_table(comps)
    memo: Dict[str, Cost] = {}

    entry = None
    for name in comps:
        if ".entry" in name or name.endswith("main") or "main" in name:
            entry = name
            break
    if entry is None:  # fall back: computation not referenced by any other
        called = set()
        for c in comps.values():
            for ins in c.instrs:
                called.update(_CALLS_RE.findall(ins.rest))
        candidates = [n for n in comps if n not in called]
        entry = candidates[0] if candidates else next(iter(comps))

    def comp_cost(name) -> Cost:
        if name in memo:
            return memo[name]
        memo[name] = Cost()  # cycle guard
        total = Cost()
        comp = comps.get(name)
        if comp is None:
            return total
        for ins in comp.instrs:
            total += instr_cost(ins)
        memo[name] = total
        return total

    def instr_cost(ins: Instr) -> Cost:
        c = Cost()
        op = ins.op
        if op == "while":
            m_body = re.search(r"body=%?([\w\.\-]+)", ins.rest)
            m_cond = re.search(r"condition=%?([\w\.\-]+)", ins.rest)
            trips = _trip_count(m_cond.group(1), comps) if m_cond else 1
            inner = comp_cost(m_body.group(1)) if m_body else Cost()
            return inner.scaled(trips)
        if op == "conditional":
            inner = Cost()
            for callee in _CALLS_RE.findall(ins.rest):
                inner += comp_cost(callee)
            return inner
        if op in ("call", "fusion", "custom-call"):
            for callee in _CALLS_RE.findall(ins.rest):
                inner = comp_cost(callee)
                if op == "fusion":
                    # Fusion internals are register/VMEM-resident: take the
                    # compute and collectives, not the per-op byte counts.
                    c += Cost(flops=inner.flops,
                              collective_bytes=inner.collective_bytes,
                              collective_breakdown=dict(inner.collective_breakdown))
                else:
                    c += inner
            c.bytes += 2 * _nbytes(ins.result_type)   # write-once model
            return c
        if op == "dot":
            c.flops += _dot_flops(ins, table)
            c.bytes += 2 * _nbytes(ins.result_type)
            return c
        if op == "convolution":
            c.flops += _conv_flops(ins, table)
            c.bytes += 2 * _nbytes(ins.result_type)
            return c
        if any(op.startswith(col) for col in COLLECTIVE_OPS):
            opbytes = sum(_nbytes(table.get(o, "")) for o in _operand_names(ins.rest))
            if opbytes == 0:
                opbytes = _nbytes(ins.result_type)
            c.collective_bytes += opbytes
            kind = next(col for col in COLLECTIVE_OPS if op.startswith(col))
            c.collective_breakdown[kind] = c.collective_breakdown.get(kind, 0.0) + opbytes
            c.bytes += opbytes + _nbytes(ins.result_type)
            return c
        if op in _SKIP_BYTES_OPS:
            return c
        if op in ("dynamic-update-slice", "scatter"):
            # Reads+writes only the update region (buffer aliased in place).
            ops = _operand_names(ins.rest)
            upd = _nbytes(table.get(ops[1], "")) if len(ops) > 1 else 0
            c.bytes += 2 * upd
            return c
        # generic op (copy, reduce, select, dynamic-slice, gather, ...):
        # write-once — count the materialized result, ×2 for the paired read.
        c.bytes += 2 * _nbytes(ins.result_type)
        return c

    return comp_cost(entry)
