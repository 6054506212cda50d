"""While-aware HLO cost model (text-based).

``compiled.cost_analysis()`` counts every while (scan) body ONCE, ignoring
trip counts (verified in tests/test_hlocost.py) -- a 60-80x undercount for
scanned layer stacks.  This module parses the optimized HLO text, walks the
call graph (entry -> fusions/calls/conditionals/whiles), multiplies while
bodies by their PARSED trip counts, and accumulates:

  * flops            dot ops: 2 * prod(result dims) * contracted size
  * hbm_bytes        an explicit HBM-traffic model: dot operands/outputs,
                     dynamic-(update-)slice and gather/scatter traffic,
                     entry parameters + root outputs.  Elementwise temps
                     are EXCLUDED (VMEM-resident after TPU fusion) -- this
                     is the roofline memory term, not op-level bytes.
  * collectives      ring-model bytes (analysis.py), scaled by enclosing
                     while trip products, ICI/DCN classified.

Trip counts come from the while condition computation: scan lowers to
``compare(iv, constant(N))`` -- we take the max s32 constant compared
against in the condition.  Unparseable conditions fall back to trip=1 with
a warning flag.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Optional

import numpy as np

from repro.roofline.analysis import (_DTYPE_BYTES, _GROUPS_IOTA_RE,
                                     _GROUPS_LIST_RE, _parse_groups)

# %name = type opcode(operands...), attrs
_OP_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%([\w.\-]+)\s*=\s*((?:\([^)]*\)|[a-z0-9]+\[[^\]]*\](?:\{[^}]*\})?))\s+"
    r"([\w\-]+)\(([^)]*(?:\([^)]*\)[^)]*)*)\)(.*)$")

_COMP_HDR_RE = re.compile(
    r"^\s*(ENTRY\s+)?%?([\w.\-]+)\s*\(.*\)\s*->\s*.+\{\s*$")
_TRIP_RE = re.compile(r'"known_trip_count":\{"n":"(\d+)"\}')
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([\d,]*)\]")
_CALL_ATTR_RE = re.compile(r"(?:calls|body|condition|to_apply|branch_computations)="
                           r"\{?%?([\w.\-]+(?:,\s*%?[\w.\-]+)*)\}?")
_CONST_RE = re.compile(r"constant\((\d+)\)")


def _type_bytes(t: str) -> int:
    total = 0
    for m in _SHAPE_RE.finditer(t):
        dt, dims = m.group(1), m.group(2)
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def _shape_dims(t: str) -> list[int]:
    m = _SHAPE_RE.search(t)
    if not m or not m.group(2):
        return []
    return [int(d) for d in m.group(2).split(",") if d]


@dataclasses.dataclass
class Op:
    name: str
    type: str
    opcode: str
    operands: list
    attrs: str


@dataclasses.dataclass
class Computation:
    name: str
    is_entry: bool
    ops: list
    types: dict          # op name -> type string


@dataclasses.dataclass
class CollectiveRecord:
    kind: str
    result_bytes: int
    group_size: int
    bytes_per_device: float
    crosses_pod: bool
    multiplier: float


@dataclasses.dataclass
class HloCost:
    flops: float = 0.0
    hbm_bytes: float = 0.0
    ici_bytes: float = 0.0
    dcn_bytes: float = 0.0
    collectives: list = dataclasses.field(default_factory=list)
    unparsed_trip_whiles: int = 0
    hbm_by_kind: dict = dataclasses.field(default_factory=dict)

    def _add_hbm(self, kind: str, nbytes: float):
        self.hbm_bytes += nbytes
        self.hbm_by_kind[kind] = self.hbm_by_kind.get(kind, 0.0) + nbytes


def parse_module(text: str) -> dict[str, Computation]:
    comps: dict[str, Computation] = {}
    cur: Optional[Computation] = None
    for line in text.splitlines():
        hdr = _COMP_HDR_RE.match(line)
        if hdr and ("->" in line) and line.rstrip().endswith("{"):
            cur = Computation(hdr.group(2), bool(hdr.group(1)), [], {})
            comps[cur.name] = cur
            continue
        if cur is None:
            continue
        if line.strip() == "}":
            cur = None
            continue
        m = _OP_RE.match(line)
        if not m:
            continue
        name, typ, opcode, operands, attrs = m.groups()
        if "%" in operands:
            # typed operand form: "f32[128,128]{1,0} %name, ..." -- layout
            # braces contain commas, so split-on-comma corrupts names; the
            # %-prefixed identifiers are unambiguous.
            ops = re.findall(r"%([\w.\-]+)", operands)
        else:
            ops = [o.strip().lstrip("%") for o in operands.split(",")]
            ops = [o.split(" ")[-1].lstrip("%") for o in ops if o]
        op = Op(name, typ, opcode, ops, attrs)
        cur.ops.append(op)
        cur.types[name] = typ
    return comps


def _dot_flops(op: Op, comp: Computation) -> float:
    out_dims = _shape_dims(op.type)
    out_n = float(np.prod(out_dims)) if out_dims else 1.0
    # contracted size from lhs type and lhs_contracting_dims
    lhs_t = comp.types.get(op.operands[0], "") if op.operands else ""
    lhs_dims = _shape_dims(lhs_t)
    m = re.search(r"lhs_contracting_dims=\{([\d,]*)\}", op.attrs)
    k = 1.0
    if m and lhs_dims:
        for d in m.group(1).split(","):
            if d and int(d) < len(lhs_dims):
                k *= lhs_dims[int(d)]
    return 2.0 * out_n * k


_CHASE_1OP = {"convert", "copy", "reshape", "transpose", "bitcast",
              "broadcast", "negate"}


def _source_bytes(comp: Computation, name: str, depth: int = 8) -> int:
    """HBM bytes of a dot operand, chasing through the elementwise chain a
    fusing compiler would absorb (convert/reshape/... and dequant
    multiplies), so an int8 weight consumed via ``convert*scale`` is costed
    at int8 bytes -- the fused-decompression CABA contract."""
    cur = name
    best = _type_bytes(comp.types.get(cur, ""))
    ops_by_name = getattr(comp, "_by_name", None)
    if ops_by_name is None:
        ops_by_name = {o.name: o for o in comp.ops}
        comp._by_name = ops_by_name
    for _ in range(depth):
        op = ops_by_name.get(cur)
        if op is None:
            break
        if op.opcode in _CHASE_1OP and op.operands:
            cur = op.operands[0]
        elif op.opcode in ("multiply", "divide", "add", "subtract") \
                and len(op.operands) >= 2:
            # dequant-style: follow the larger operand (the payload)
            a, b = op.operands[0], op.operands[1]
            ba = _type_bytes(comp.types.get(a, ""))
            bb = _type_bytes(comp.types.get(b, ""))
            cur = a if ba >= bb else b
        else:
            break
        nb = _type_bytes(comp.types.get(cur, ""))
        if nb:
            best = min(best, nb)
    return best


def _while_trip(while_op: Op, cond: Optional[Computation]) -> Optional[int]:
    """XLA annotates scheduled whiles with known_trip_count; fall back to
    the max integer constant in the condition computation."""
    m = _TRIP_RE.search(while_op.attrs or "")
    if m:
        return int(m.group(1))
    if cond is None:
        return None
    consts = []
    for op in cond.ops:
        mm = re.search(r"constant\((\d+)\)", (op.attrs or "") + op.type)
        if mm:
            consts.append(int(mm.group(1)))
    return max(consts) if consts else None


_HBM_OPCODES = {"dynamic-slice", "dynamic-update-slice", "gather", "scatter"}
_COLL_KINDS = {"all-gather": "all-gather", "all-gather-start": "all-gather",
               "all-reduce": "all-reduce", "all-reduce-start": "all-reduce",
               "reduce-scatter": "reduce-scatter",
               "all-to-all": "all-to-all",
               "collective-permute": "collective-permute",
               "collective-permute-start": "collective-permute"}


def _walk(comp: Computation, comps: dict, mult: float, cost: HloCost,
          devices_per_pod: int, n_devices: int, seen_stack: tuple):
    if comp.name in seen_stack:          # recursion guard
        return
    for op in comp.ops:
        if op.opcode == "dot":
            cost.flops += mult * _dot_flops(op, comp)
            # dot traffic: operands + output (weights/activations stream),
            # operands costed at their pre-dequant source bytes
            ob = sum(_source_bytes(comp, o) for o in op.operands)
            cost._add_hbm("dot", mult * (ob + _type_bytes(op.type)))
        elif op.opcode == "convolution":
            out_n = float(np.prod(_shape_dims(op.type)))
            lhs = _shape_dims(comp.types.get(op.operands[0], ""))
            k = float(np.prod(lhs[1:])) if lhs else 1.0
            cost.flops += mult * 2.0 * out_n * min(k, 1e6)
        elif op.opcode == "dynamic-update-slice":
            # in-place update (donated buffers): traffic = the slice written
            # (+ read-modify of the same bytes), NOT the whole buffer
            upd_t = comp.types.get(op.operands[1], "") if len(op.operands) > 1 else ""
            cost._add_hbm(op.opcode, mult * 2 * _type_bytes(upd_t))
        elif op.opcode == "scatter":
            upd_t = comp.types.get(op.operands[-1], "") if op.operands else ""
            cost._add_hbm(op.opcode, mult * 2 * _type_bytes(upd_t))
        elif op.opcode in _HBM_OPCODES:
            cost._add_hbm(op.opcode, mult * _type_bytes(op.type))
        elif op.opcode in _COLL_KINDS:
            kind = _COLL_KINDS[op.opcode]
            rb = _type_bytes(op.type)
            groups = _parse_groups(op.attrs)
            g = int(groups.shape[1]) if groups is not None else n_devices
            if g > 1 and rb > 0:
                if kind == "all-gather":
                    per_dev = rb * (g - 1) / g
                elif kind == "reduce-scatter":
                    per_dev = rb * (g - 1)
                elif kind == "all-reduce":
                    per_dev = 2.0 * rb * (g - 1) / g
                elif kind == "all-to-all":
                    per_dev = rb * (g - 1) / g
                else:
                    per_dev = float(rb)
                crosses = False
                if devices_per_pod and groups is not None:
                    pods = groups // devices_per_pod
                    crosses = bool((pods != pods[:, :1]).any())
                cost.collectives.append(CollectiveRecord(
                    kind, rb, g, per_dev, crosses, mult))
                if crosses:
                    cost.dcn_bytes += mult * per_dev
                else:
                    cost.ici_bytes += mult * per_dev
        # ---- nested computations ----
        callees = []
        trip = 1.0
        if op.opcode == "while":
            mm = re.search(r"body=%?([\w.\-]+)", op.attrs)
            mc = re.search(r"condition=%?([\w.\-]+)", op.attrs)
            if mm:
                cond = comps.get(mc.group(1)) if mc else None
                t = _while_trip(op, cond)
                if t is None:
                    cost.unparsed_trip_whiles += 1
                    t = 1
                callees = [mm.group(1)]
                trip = float(max(t, 1))
        elif op.opcode in ("fusion", "call", "map", "reduce", "reduce-window",
                           "sort", "scatter", "select-and-scatter",
                           "conditional"):
            mm = _CALL_ATTR_RE.search(op.attrs)
            if mm:
                callees = [c.strip().lstrip("%")
                           for c in mm.group(1).split(",")]
        for cal in callees:
            if cal in comps:
                _walk(comps[cal], comps, mult * trip, cost,
                      devices_per_pod, n_devices,
                      seen_stack + (comp.name,))


def analyze_text(text: str, *, n_devices: int,
                 devices_per_pod: int = 0,
                 entry_io_bytes: bool = True) -> HloCost:
    comps = parse_module(text)
    entry = next((c for c in comps.values() if c.is_entry), None)
    if entry is None and comps:
        entry = max(comps.values(), key=lambda c: len(c.ops))
    cost = HloCost()
    if entry is None:
        return cost
    _walk(entry, comps, 1.0, cost, devices_per_pod, n_devices, ())
    if entry_io_bytes:
        for op in entry.ops:
            if op.opcode == "parameter":
                cost._add_hbm("entry_param", _type_bytes(op.type))
    return cost
