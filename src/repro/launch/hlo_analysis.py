"""Post-SPMD HLO analysis for the roofline.

``compiled.cost_analysis()`` does NOT scale ``while`` bodies by trip count
(verified empirically: a 10-iteration scan of a matmul reports the FLOPs of
one matmul), so this module re-derives the three roofline inputs directly
from ``compiled.as_text()``:

* dot FLOPs        — every ``dot`` op: 2 × |result| × |contracted dims|,
                     multiplied through the while-loop nest using the
                     ``known_trip_count`` backend_config XLA attaches to
                     scan-derived loops;
* HBM bytes        — fusion-boundary traffic model: for each materializing
                     instruction, bytes = |result| + Σ|operands| (slicing ops
                     counted as 2×|result|; in-place dynamic-update-slice as
                     2×|update|), trip-scaled;
* collective bytes — result sizes of all-gather / all-reduce /
                     reduce-scatter / all-to-all / collective-permute
                     (+ their async -start forms), trip-scaled, per type.

All numbers are PER DEVICE (the HLO is the post-partitioning module).
"""
from __future__ import annotations

import json
import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
    "c64": 8, "c128": 16, "f8e4m3fn": 1, "f8e5m2": 1, "s4": 1, "u4": 1,
    "token": 0, "opaque": 0,
}

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_INSTR_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%([\w.\-]+)\s*=\s*(.*?)\s+([\w\-]+)\((.*)$")
_COMP_RE = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*\(.*\)\s*->.*{\s*$")
_TRIP_RE = re.compile(r'"known_trip_count":\{"n":"(\d+)"\}')
_CALLED_SINGLE_RE = re.compile(
    r"(?:body|condition|calls|to_apply)=%([\w.\-]+)")
_CALLED_MULTI_RE = re.compile(
    r"(?:calls|branch_computations)=\{([^}]*)\}")

COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute", "ragged-all-to-all")
_META_OPS = {"parameter", "constant", "get-tuple-element", "tuple", "bitcast",
             "after-all", "partition-id", "replica-id", "iota", "custom-call"}


def _shape_bytes(type_str: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(type_str):
        if dt not in DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * DTYPE_BYTES[dt]
    return total


def _shape_dims(type_str: str) -> List[int]:
    m = _SHAPE_RE.search(type_str)
    if not m:
        return []
    dims = m.group(2)
    return [int(d) for d in dims.split(",")] if dims else []


@dataclass
class Instr:
    name: str
    opcode: str
    result_type: str
    result_bytes: int
    operands: List[str]
    rest: str               # attrs after the operand list

    @property
    def trip_count(self) -> Optional[int]:
        m = _TRIP_RE.search(self.rest)
        return int(m.group(1)) if m else None

    def called(self) -> List[str]:
        out = []
        for m in _CALLED_SINGLE_RE.finditer(self.rest):
            out.append(m.group(1))
        for m in _CALLED_MULTI_RE.finditer(self.rest):
            for nm in m.group(1).split(","):
                nm = nm.strip().lstrip("%")
                if nm and nm not in out:
                    out.append(nm)
        return out


@dataclass
class Computation:
    name: str
    instrs: List[Instr] = field(default_factory=list)


def parse_hlo(text: str) -> Tuple[Dict[str, Computation], Optional[str]]:
    comps: Dict[str, Computation] = {}
    entry = None
    cur: Optional[Computation] = None
    for line in text.splitlines():
        mc = _COMP_RE.match(line)
        if mc and ("->" in line):
            cur = Computation(mc.group(1))
            comps[cur.name] = cur
            if line.lstrip().startswith("ENTRY"):
                entry = cur.name
            continue
        if line.strip() == "}":
            cur = None
            continue
        if cur is None:
            continue
        mi = _INSTR_RE.match(line)
        if not mi:
            continue
        name, rtype, opcode, tail = mi.groups()
        # split operand list from attrs: first unmatched ')' closes operands
        depth = 1
        idx = 0
        for idx, ch in enumerate(tail):
            if ch == "(":
                depth += 1
            elif ch == ")":
                depth -= 1
                if depth == 0:
                    break
        opnds_str, rest = tail[:idx], tail[idx + 1:]
        operands = [t.strip().lstrip("%") for t in re.findall(
            r"%[\w.\-]+", opnds_str)]
        cur.instrs.append(Instr(name, opcode, rtype, _shape_bytes(rtype),
                                operands, rest))
    return comps, entry


@dataclass
class HloStats:
    dot_flops: float = 0.0
    hbm_bytes: float = 0.0
    collective_bytes: Dict[str, float] = field(default_factory=dict)
    collective_count: Dict[str, int] = field(default_factory=dict)

    @property
    def total_collective_bytes(self) -> float:
        return sum(self.collective_bytes.values())


def _instr_bytes(ins: Instr, sizes: Dict[str, int]) -> float:
    op = ins.opcode
    if op in _META_OPS:
        return 0.0
    if op in ("dynamic-slice", "gather", "slice"):
        return 2.0 * ins.result_bytes
    if op == "dynamic-update-slice":
        upd = sizes.get(ins.operands[1], 0) if len(ins.operands) > 1 else 0
        return 2.0 * upd
    if op == "scatter":
        upd = sizes.get(ins.operands[-1], ins.result_bytes)
        return 2.0 * upd
    if op == "while":  # accounted via recursion
        return 0.0
    if op in ("call", "conditional", "fusion") and op != "fusion":
        return 0.0
    total = float(ins.result_bytes)
    for o in ins.operands:
        total += sizes.get(o, 0)
    return total


def _dot_flops(ins: Instr, sizes_dims: Dict[str, List[int]]) -> float:
    res_dims = _shape_dims(ins.result_type)
    n_res = 1
    for d in res_dims:
        n_res *= d
    m = re.search(r"lhs_contracting_dims=\{([\d,]*)\}", ins.rest)
    contract = 1
    if m and ins.operands:
        lhs_dims = sizes_dims.get(ins.operands[0], [])
        for di in m.group(1).split(","):
            if di and int(di) < len(lhs_dims):
                contract *= lhs_dims[int(di)]
    return 2.0 * n_res * contract


def analyze_hlo(text: str) -> HloStats:
    comps, entry = parse_hlo(text)
    if entry is None:
        return HloStats()
    sizes = {i.name: i.result_bytes
             for c in comps.values() for i in c.instrs}
    dims = {i.name: _shape_dims(i.result_type)
            for c in comps.values() for i in c.instrs}
    stats = HloStats()
    seen_fusion_comps = set()

    def walk(comp_name: str, mult: float) -> None:
        comp = comps.get(comp_name)
        if comp is None:
            return
        for ins in comp.instrs:
            if ins.opcode == "while":
                tc = ins.trip_count or 1
                for callee in ins.called():
                    walk(callee, mult * tc)
                continue
            if ins.opcode in ("call", "conditional", "async-start"):
                for callee in ins.called():
                    walk(callee, mult)
                continue
            base = ins.opcode.replace("-start", "")
            if base in COLLECTIVES:
                stats.collective_bytes[base] = (
                    stats.collective_bytes.get(base, 0.0)
                    + mult * ins.result_bytes)
                stats.collective_count[base] = (
                    stats.collective_count.get(base, 0) + int(mult))
            if ins.opcode == "dot":
                stats.dot_flops += mult * _dot_flops(ins, dims)
            if ins.opcode == "fusion":
                # dots inside fusions still count (rare on TPU path)
                for callee in ins.called():
                    fc = comps.get(callee)
                    if fc is None:
                        continue
                    for fi in fc.instrs:
                        if fi.opcode == "dot":
                            stats.dot_flops += mult * _dot_flops(fi, dims)
            stats.hbm_bytes += mult * _instr_bytes(ins, sizes)
    walk(entry, 1.0)
    return stats
