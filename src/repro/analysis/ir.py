"""Shared IR inspection utilities: HLO-text parsing + jaxpr walking.

Two consumers (kept deliberately in one place — ISSUE 6 satellite):

- `repro.launch.hlo_analysis` — the trip-count-aware roofline profiler
  parses post-compile HLO text through `parse_hlo`/`symbol_table`.
- `repro.analysis.jaxpr_audit` — the serving-contract audit walks jaxprs
  (`iter_eqns`) and lowered StableHLO (donation aliasing).
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict, Iterator, List, Optional, Tuple

import jax.extend.core as jax_core

# ---------------------------------------------------------------------------
# HLO text parsing (shapes, instructions, computations)
# ---------------------------------------------------------------------------

DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16, "e4m3": 1, "e5m2": 1, "f8e4m3fn": 1, "f8e5m2": 1,
}

_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
_INSTR_RE = re.compile(r"^\s*(?:ROOT\s+)?%([\w\.\-]+)\s*=\s*(.+?)\s+([\w\-]+)\((.*)$")
_COMP_HDR_RE = re.compile(r"^(?:ENTRY\s+)?%?([\w\.\-]+)\s*\((.*?)\)\s*->")
CALLS_RE = re.compile(r"(?:calls|condition|body|to_apply)=%?([\w\.\-]+)")


def parse_shapes(type_str) -> List[Tuple[str, Tuple[int, ...]]]:
    out = []
    for dt, dims in _SHAPE_RE.findall(type_str):
        if dt not in DTYPE_BYTES:
            continue
        shape = tuple(int(d) for d in dims.split(",") if d) if dims else ()
        out.append((dt, shape))
    return out


def nbytes(type_str) -> int:
    total = 0
    for dt, shape in parse_shapes(type_str):
        n = 1
        for d in shape:
            n *= d
        total += n * DTYPE_BYTES[dt]
    return total


@dataclasses.dataclass
class Instr:
    name: str
    result_type: str
    op: str
    rest: str       # raw tail of the line (operands + attrs)
    line: str


@dataclasses.dataclass
class Computation:
    name: str
    instrs: List[Instr]
    param_types: Dict[str, str]


def parse_hlo(text: str) -> Dict[str, Computation]:
    comps: Dict[str, Computation] = {}
    cur: Optional[Computation] = None
    for line in text.splitlines():
        hdr = _COMP_HDR_RE.match(line.strip()) if "{" in line and "->" in line else None
        if hdr and not line.strip().startswith("%constant"):
            params = {}
            for p in hdr.group(2).split(","):
                p = p.strip()
                if ":" in p:
                    pname, ptype = p.split(":", 1)
                    params[pname.strip().lstrip("%")] = ptype.strip()
            cur = Computation(hdr.group(1), [], params)
            comps[cur.name] = cur
            continue
        m = _INSTR_RE.match(line)
        if m and cur is not None:
            cur.instrs.append(Instr(m.group(1), m.group(2), m.group(3),
                                    m.group(4), line))
    return comps


def symbol_table(comps) -> Dict[str, str]:
    """Global name → type-string table across all computations."""
    table = {}
    for c in comps.values():
        for name, t in c.param_types.items():
            table[name] = t
        for ins in c.instrs:
            table[ins.name] = ins.result_type
    return table


def operand_names(rest: str) -> List[str]:
    """The leading %refs before the closing paren of an HLO op call."""
    depth = 0
    token = ""
    for ch in rest:
        if ch == "(":
            depth += 1
        if ch == ")":
            if depth == 0:
                break
            depth -= 1
        token += ch
    return re.findall(r"%([\w\.\-]+)", token)


# ---------------------------------------------------------------------------
# jaxpr walking
# ---------------------------------------------------------------------------

def subjaxprs(eqn) -> Iterator:
    """All jaxprs appearing in an eqn's params (scan/while/cond/pjit/...)."""
    for val in eqn.params.values():
        vals = val if isinstance(val, (list, tuple)) else (val,)
        for v in vals:
            if isinstance(v, jax_core.ClosedJaxpr):
                yield v.jaxpr
            elif isinstance(v, jax_core.Jaxpr):
                yield v


def iter_eqns(jaxpr, path="") -> Iterator[Tuple[object, str]]:
    """DFS over every eqn of a jaxpr and all nested sub-jaxprs.

    Yields (eqn, path) where path is the '/'-joined chain of enclosing
    higher-order primitives (e.g. "scan/pjit"). Accepts a Jaxpr or
    ClosedJaxpr.
    """
    if isinstance(jaxpr, jax_core.ClosedJaxpr):
        jaxpr = jaxpr.jaxpr
    for eqn in jaxpr.eqns:
        yield eqn, path
        sub_path = f"{path}/{eqn.primitive.name}" if path else eqn.primitive.name
        for sub in subjaxprs(eqn):
            yield from iter_eqns(sub, sub_path)


def eqn_source(eqn) -> str:
    """Best-effort 'file.py:line' of the user frame that emitted an eqn."""
    try:
        from jax._src import source_info_util
        frame = source_info_util.user_frame(eqn.source_info)
        if frame is None:
            return "?"
        fname = frame.file_name
        for marker in ("/src/", "/site-packages/"):
            if marker in fname:
                fname = fname.split(marker)[-1]
        return f"{fname}:{frame.start_line}"
    except Exception:  # pragma: no cover - source info is advisory
        return "?"


def aval_nbytes(aval) -> int:
    """Byte size of a ShapedArray-like aval (0 for abstract tokens)."""
    dtype = getattr(aval, "dtype", None)
    shape = getattr(aval, "shape", None)
    if dtype is None or shape is None:
        return 0
    n = 1
    for d in shape:
        n *= int(d)
    return n * dtype.itemsize
