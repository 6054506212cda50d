"""Reduction of a ``jax.profiler`` trace to the numbers the metrics read.

The trace is the ``.xplane.pb`` that ``jax.profiler.stop_trace`` writes.
Device planes (``/device:TPU:<n>``) carry the programs that ran (line
``XLA Modules``) and their operations (line ``XLA Ops``); host planes
carry the benchmark's own ``TraceAnnotation`` spans (``bench.*``) on the
profiler's clock. The window is the stretch the benchmark's spans cover.

- busy time: the union of operation intervals inside the window, averaged
  over the device planes;
- time by program and by operation name: summed durations;
- idle gaps: the stretches of the window in which no operation ran, each
  labelled by the innermost host event open at its midpoint.
"""
from __future__ import annotations

import dataclasses
import pathlib
from collections import defaultdict

SPAN_PREFIX = "bench."
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


@dataclasses.dataclass
class Reduced:
    window_ns: tuple            # (start, end) on the profiler's clock
    n_devices: int
    busy_ns: float              # per device, averaged
    modules: dict               # program name -> [count, total ns]
    ops: dict                   # operation name -> [count, total ns]
    op_desc: dict               # operation name -> name + its string stats
    gaps: list                  # [(label, ns)], longest first

    @property
    def window_s(self) -> float:
        return (self.window_ns[1] - self.window_ns[0]) * 1e-9

    @property
    def busy_s(self) -> float:
        return self.busy_ns * 1e-9

    def module_time_s(self, token: str) -> tuple[int, float]:
        """(executions, seconds) of programs whose name holds ``token``,
        summed over devices."""
        n = t = 0
        for name, (c, ns) in self.modules.items():
            if token in name:
                n, t = n + c, t + ns
        return n, t * 1e-9

    def op_time_s(self, token: str) -> tuple[int, float]:
        """(calls, seconds) of operations whose name or string stats hold
        ``token``, summed over devices."""
        n = t = 0
        for name, (c, ns) in self.ops.items():
            if token in self.op_desc.get(name, name):
                n, t = n + c, t + ns
        return n, t * 1e-9

    def top_ops(self, k: int = 10) -> list:
        tot = sorted(((ns * 1e-9, name) for name, (_, ns) in
                      self.ops.items()), reverse=True)[:k]
        return [[name, s] for s, name in tot]


def load(path) -> object:
    """ProfileData of a trace file, or of the newest one under a
    directory."""
    from jax.profiler import ProfileData
    p = pathlib.Path(path)
    if p.is_dir():
        files = sorted(p.rglob("*.xplane.pb"), key=lambda f: f.stat().st_mtime)
        if not files:
            raise FileNotFoundError(f"no .xplane.pb under {p}")
        p = files[-1]
    return ProfileData.from_file(str(p))


def _union(intervals) -> list:
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clip(intervals, lo, hi) -> list:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def _desc(ev) -> str:
    parts = [ev.name]
    for k, v in ev.stats:
        if isinstance(v, str):
            parts.append(f"{k}={v}")
    return " ".join(parts)


def reduce(pd, span_prefix: str = SPAN_PREFIX, n_gaps: int = 10) -> Reduced:
    host_events = []      # (start, end, name, is_span)
    spans = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                s, e = ev.start_ns, ev.start_ns + ev.duration_ns
                span = ev.name.startswith(span_prefix)
                host_events.append((s, e, ev.name, span))
                if span:
                    spans.append((s, e))
    if not spans:
        raise ValueError(f"trace holds no host span named {span_prefix}*")
    lo, hi = min(s for s, _ in spans), max(e for _, e in spans)

    modules = defaultdict(lambda: [0, 0.0])
    ops = defaultdict(lambda: [0, 0.0])
    op_desc = {}
    busy, gaps, n_dev = 0.0, [], 0
    for plane in pd.planes:
        if not plane.name.startswith("/device:TPU:"):
            continue
        lines = {line.name: line for line in plane.lines}
        if OPS_LINE not in lines:
            continue
        n_dev += 1
        intervals = []
        for ev in lines[OPS_LINE].events:
            s, e = ev.start_ns, ev.start_ns + ev.duration_ns
            if e <= lo or s >= hi:
                continue
            intervals.append((s, e))
            ops[ev.name][0] += 1
            ops[ev.name][1] += min(e, hi) - max(s, lo)
            if ev.name not in op_desc:
                op_desc[ev.name] = _desc(ev)
        if MODULES_LINE in lines:
            for ev in lines[MODULES_LINE].events:
                s, e = ev.start_ns, ev.start_ns + ev.duration_ns
                if e <= lo or s >= hi:
                    continue
                modules[ev.name][0] += 1
                modules[ev.name][1] += min(e, hi) - max(s, lo)
        merged = _union(_clip(intervals, lo, hi))
        busy += sum(e - s for s, e in merged)
        if n_dev == 1:        # gaps are labelled on the first device
            prev = lo
            for s, e in merged + [[hi, hi]]:
                if s > prev:
                    gaps.append((prev, s))
                prev = max(prev, e)
    if not n_dev:
        raise ValueError("trace holds no TPU device plane with XLA Ops")
    gaps.sort(key=lambda g: g[1] - g[0], reverse=True)
    labelled = [[_label(host_events, (s + e) / 2), (e - s) * 1e-9]
                for s, e in gaps[:n_gaps]]
    return Reduced((lo, hi), n_dev, busy / n_dev, dict(modules), dict(ops),
                   op_desc, labelled)


def _label(host_events, t) -> str:
    """The benchmark span open at ``t`` and the innermost host event
    inside it, e.g. ``bench.step > PjitFunction(prefill_fn)``."""
    span, inner = None, None
    for s, e, name, is_span in host_events:
        if not s <= t <= e:
            continue
        if is_span:
            if span is None or e - s < span[1] - span[0]:
                span = (s, e, name)
        elif inner is None or e - s < inner[1] - inner[0]:
            inner = (s, e, name)
    parts = [p[2] for p in (span, inner) if p is not None]
    return " > ".join(parts) if parts else "no host event"


# -- small copies of a trace (test fixtures) ------------------------------------

def _short(name: str) -> str:
    """An op's id, and its custom-call target where it has one."""
    head = name.split(" = ")[0][:120]
    if 'custom_call_target="' in name:
        tgt = name.split('custom_call_target="')[1].split('"')[0]
        head += f' custom_call_target="{tgt}"'
    return head


def extract(pd, span_ms: float = 400.0, min_host_ns: float = 20_000,
            span_prefix: str = SPAN_PREFIX) -> dict:
    """The first ``span_ms`` of a trace's window as plain data: device
    ops and programs, and host events of at least ``min_host_ns``, with
    names cut to their ids. ``from_extract`` reads it back."""
    spans = [ev.start_ns for plane in pd.planes
             if plane.name.startswith("/host:") for line in plane.lines
             for ev in line.events if ev.name.startswith(span_prefix)]
    lo = min(spans)
    hi = lo + span_ms * 1e6
    planes = []
    for plane in pd.planes:
        host = plane.name.startswith("/host:")
        if not (host or plane.name.startswith("/device:TPU:")):
            continue
        lines = []
        for line in plane.lines:
            if not host and line.name not in (OPS_LINE, MODULES_LINE):
                continue
            evs = [[ev.name if host else _short(ev.name), ev.start_ns,
                    ev.duration_ns] for ev in line.events
                   if lo <= ev.start_ns < hi
                   and (not host or ev.duration_ns >= min_host_ns
                        or ev.name.startswith(span_prefix))]
            if evs:
                lines.append({"name": line.name, "events": evs})
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def from_extract(data: dict):
    """A ProfileData-like object over ``extract``'s data."""
    from types import SimpleNamespace as NS
    return NS(planes=[NS(name=p["name"], lines=[
        NS(name=ln["name"], events=[
            NS(name=n, start_ns=s, duration_ns=d, stats=[])
            for n, s, d in ln["events"]]) for ln in p["lines"]])
        for p in data["planes"]])
