"""Per-layer metric readers, one file each: ``bench/metrics/<metric>.py``.

A reader defines ``read(ctx) -> float | None``. ``ctx`` holds the reduced
trace (``trace``), the host's samples of the traced stretch (``steps``,
``prefill_tokens``), the requests (``recs``), generator lateness
(``gen_lag_ms``), tier samples (``tier_samples``, ``cold_moves``), the
configuration, mix, peaks, and the family's FLOP and byte counts
(``counts``). A reader that finds nothing to read returns None, and the
metric is left out of the line.
"""
from __future__ import annotations

import importlib.util
import pathlib

HERE = pathlib.Path(__file__).resolve().parent


def reader(name: str, root: pathlib.Path | None = None):
    path = (root / "bench" / "metrics" if root else HERE) / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no reader for metric {name!r} at {path}")
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def metrics_for(manifest: dict, cell: str) -> list[dict]:
    """The per-layer metrics a cell reports: those that list it, and those
    without a list whose moved metric the cell reports."""
    e2e = {m["name"]: m for m in manifest["end_to_end"]}

    def reports(metric):
        cells = e2e[metric].get("workloads")
        return cells is None or cell in cells

    out = []
    for m in manifest["per_layer"]:
        cells = m.get("workloads")
        if (cell in cells) if cells is not None else reports(m["moves"]):
            out.append(m)
    return out


def read_all(cell: dict, ctx: dict, root: pathlib.Path) -> dict:
    from bench.harness import manifest
    out = {}
    for m in metrics_for(manifest(root), cell["name"]):
        v = reader(m["name"], root)(ctx)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out
