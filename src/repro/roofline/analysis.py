"""Roofline decomposition of a compiled step (DESIGN.md 9).

Three per-device time terms from the AOT-compiled artifact:

    compute    = HLO_FLOPs / peak_FLOP/s
    memory     = HLO_bytes / HBM_bw
    collective = ici_bytes / ICI_bw  +  dcn_bytes / DCN_bw

FLOPs/bytes come from ``compiled.cost_analysis()`` (the partitioned,
per-device module).  Collective bytes are NOT in cost_analysis: we parse
the optimized HLO text, sum ring-model bytes per device for every
all-gather / all-reduce / reduce-scatter / all-to-all / collective-permute,
and classify each op's traffic as ICI (intra-pod) or DCN (crosses the
``pod`` axis) from its replica groups.

Ring model (g = group size, R = result bytes, per device):
    all-gather       (g-1)/g * R        (R = full gathered result)
    reduce-scatter   (g-1)   * R        (R = the shard)
    all-reduce       2 (g-1)/g * R
    all-to-all       (g-1)/g * R
    collective-permute  R
"""
from __future__ import annotations

import dataclasses
import json
import re
from typing import Optional

import numpy as np

from repro.roofline.peaks import MODEL_TARGET

# the modeled chip's published peaks (roofline/peaks.py)
PEAK_FLOPS = MODEL_TARGET.bf16_flops
HBM_BW = MODEL_TARGET.hbm_bw
ICI_BW = MODEL_TARGET.ici_link_bw
DCN_BW = 25e9                # bytes/s per chip across pods (assumed)

_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1,
    "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1, "f8e4m3": 1,
    "f8e4m3b11fnuz": 1, "f8e5m2fnuz": 1, "f8e4m3fnuz": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16,
}

_COLL_RE = re.compile(
    r"=\s*(?:\()?([a-z0-9\[\],{}\s]*?)\)?\s*"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\(")

_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([\d,]*)\]")

_GROUPS_LIST_RE = re.compile(r"replica_groups=\{\{([^}]*(?:\},\{[^}]*)*)\}\}")
_GROUPS_IOTA_RE = re.compile(
    r"replica_groups=\[(\d+),(\d+)\]<=\[([\d,]+)\](?:T\(([\d,]+)\))?")


def _shape_bytes(type_str: str) -> int:
    """Sum bytes over (possibly tuple) result type like 'f32[8,128]'."""
    total = 0
    for m in _SHAPE_RE.finditer(type_str):
        dt, dims = m.group(1), m.group(2)
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def _parse_groups(line: str) -> Optional[np.ndarray]:
    """-> int array [n_groups, group_size] or None."""
    m = _GROUPS_IOTA_RE.search(line)
    if m:
        n_g, g_sz = int(m.group(1)), int(m.group(2))
        dims = [int(d) for d in m.group(3).split(",")]
        arr = np.arange(int(np.prod(dims))).reshape(dims)
        if m.group(4):
            perm = [int(p) for p in m.group(4).split(",")]
            arr = arr.transpose(perm)
        return arr.reshape(n_g, g_sz)
    m = _GROUPS_LIST_RE.search(line)
    if m:
        groups = [[int(x) for x in grp.split(",") if x.strip()]
                  for grp in m.group(1).split("},{")]
        return np.asarray(groups)
    return None


@dataclasses.dataclass
class CollectiveOp:
    kind: str
    result_bytes: int
    group_size: int
    bytes_per_device: float
    crosses_pod: bool


@dataclasses.dataclass
class RooflineReport:
    arch: str
    shape: str
    mesh: str
    n_devices: int
    flops_per_device: float
    bytes_per_device: float
    ici_bytes_per_device: float
    dcn_bytes_per_device: float
    collectives: list
    model_flops: float
    memory_per_device: dict

    # -- derived terms -------------------------------------------------------
    @property
    def compute_s(self) -> float:
        return self.flops_per_device / PEAK_FLOPS

    @property
    def memory_s(self) -> float:
        return self.bytes_per_device / HBM_BW

    @property
    def collective_s(self) -> float:
        return (self.ici_bytes_per_device / ICI_BW
                + self.dcn_bytes_per_device / DCN_BW)

    @property
    def bottleneck(self) -> str:
        t = {"compute": self.compute_s, "memory": self.memory_s,
             "collective": self.collective_s}
        return max(t, key=t.get)

    @property
    def step_time_s(self) -> float:
        """Perfect-overlap lower bound."""
        return max(self.compute_s, self.memory_s, self.collective_s)

    @property
    def roofline_fraction(self) -> float:
        """Dominant term / serial sum: 1.0 = single hard roof, lower means
        time is split across roofs (overlap opportunity)."""
        s = self.compute_s + self.memory_s + self.collective_s
        return self.step_time_s / s if s else 0.0

    @property
    def useful_flops_fraction(self) -> float:
        """MODEL_FLOPS / (HLO flops x devices): remat/redundancy waste."""
        total = self.flops_per_device * self.n_devices
        return self.model_flops / total if total else 0.0

    def collective_breakdown(self, top: int = 12) -> list:
        """Aggregate collective traffic by (kind, group size, result MB)."""
        agg: dict = {}
        for c in self.collectives:
            mult = c.get("multiplier", 1.0) if isinstance(c, dict) else 1.0
            d = c if isinstance(c, dict) else dataclasses.asdict(c)
            key = (d["kind"], d["group_size"],
                   round(d["result_bytes"] / 1e6, 2))
            e = agg.setdefault(key, [0.0, 0])
            e[0] += d["bytes_per_device"] * mult
            e[1] += 1
        rows = [{"kind": k[0], "group": k[1], "result_MB": k[2],
                 "total_GB_per_dev": v[0] / 1e9, "sites": v[1]}
                for k, v in agg.items()]
        rows.sort(key=lambda r: -r["total_GB_per_dev"])
        return rows[:top]

    def summary(self) -> dict:
        return {
            "arch": self.arch, "shape": self.shape, "mesh": self.mesh,
            "devices": self.n_devices,
            "compute_s": self.compute_s, "memory_s": self.memory_s,
            "collective_s": self.collective_s,
            "ici_GB": self.ici_bytes_per_device / 1e9,
            "dcn_GB": self.dcn_bytes_per_device / 1e9,
            "bottleneck": self.bottleneck,
            "step_time_s": self.step_time_s,
            "roofline_fraction": self.roofline_fraction,
            "model_flops": self.model_flops,
            "hlo_flops_per_dev": self.flops_per_device,
            "hlo_bytes_per_dev": self.bytes_per_device,
            "useful_flops_fraction": self.useful_flops_fraction,
            "memory_analysis": self.memory_per_device,
            "collective_breakdown": self.collective_breakdown(),
        }


def parse_collectives(hlo_text: str, n_devices: int,
                      devices_per_pod: Optional[int] = None
                      ) -> list[CollectiveOp]:
    """Scan optimized HLO for collectives; bytes via the ring model."""
    ops = []
    for line in hlo_text.splitlines():
        m = _COLL_RE.search(line)
        if not m or "-done" in line.split("=")[0]:
            continue
        result_type, kind = m.group(1), m.group(2)
        rb = _shape_bytes(result_type)
        if rb == 0:
            continue
        groups = _parse_groups(line)
        g = int(groups.shape[1]) if groups is not None else n_devices
        if g <= 1:
            continue
        if kind == "all-gather":
            per_dev = rb * (g - 1) / g
        elif kind == "reduce-scatter":
            per_dev = rb * (g - 1)
        elif kind == "all-reduce":
            per_dev = 2.0 * rb * (g - 1) / g
        elif kind == "all-to-all":
            per_dev = rb * (g - 1) / g
        else:                      # collective-permute
            per_dev = float(rb)
        crosses = False
        if devices_per_pod and groups is not None:
            pods = groups // devices_per_pod
            crosses = bool((pods != pods[:, :1]).any())
        ops.append(CollectiveOp(kind, rb, g, per_dev, crosses))
    return ops


def analyze(compiled, *, arch: str, shape: str, mesh_desc: str,
            n_devices: int, devices_per_pod: Optional[int] = None,
            model_flops: float = 0.0) -> RooflineReport:
    """Roofline report from a jax AOT-compiled step.

    FLOPs/bytes/collectives come from the while-aware HLO cost model
    (roofline/hlocost.py): ``compiled.cost_analysis()`` counts scan bodies
    once (60-80x undercount on deep stacks, see tests/test_hlocost.py), so
    raw numbers are recorded for reference but the terms use the corrected
    walk.  The memory term is an explicit HBM-traffic model (matmul
    operand/result streams + cache slice traffic + entry I/O).
    """
    from repro.roofline import hlocost
    hlo = compiled.as_text()
    hc = hlocost.analyze_text(hlo, n_devices=n_devices,
                              devices_per_pod=devices_per_pod or 0)
    try:
        cost = compiled.cost_analysis()
        raw_flops = float(cost.get("flops", 0.0))
        raw_bytes = float(cost.get("bytes accessed", 0.0))
    except Exception:
        raw_flops = raw_bytes = 0.0
    try:
        ma = compiled.memory_analysis()
        mem = {k: int(getattr(ma, k)) for k in
               ("argument_size_in_bytes", "output_size_in_bytes",
                "temp_size_in_bytes", "generated_code_size_in_bytes")
               if hasattr(ma, k)}
    except Exception:
        mem = {}
    mem["raw_cost_analysis_flops"] = raw_flops
    mem["raw_cost_analysis_bytes"] = raw_bytes
    mem["unparsed_trip_whiles"] = hc.unparsed_trip_whiles
    mem["hbm_by_kind_GB"] = {k: round(v / 1e9, 3)
                             for k, v in sorted(hc.hbm_by_kind.items(),
                                                key=lambda kv: -kv[1])}
    return RooflineReport(
        arch=arch, shape=shape, mesh=mesh_desc, n_devices=n_devices,
        flops_per_device=hc.flops, bytes_per_device=hc.hbm_bytes,
        ici_bytes_per_device=hc.ici_bytes, dcn_bytes_per_device=hc.dcn_bytes,
        collectives=[dataclasses.asdict(c) for c in hc.collectives[:200]],
        model_flops=model_flops, memory_per_device=mem)


def model_flops_estimate(arch, shape) -> float:
    """6*N*D for training, 2*N_active*D for serving (per the assignment)."""
    n_active = arch.active_param_count()
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * n_active * tokens
    if shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * n_active * tokens
    # decode: one token per row
    return 2.0 * n_active * shape.global_batch
