"""Shared pieces of the benchmark's tests: paths and a tiny cell.

The tiny cell has the structure of the real ones (a qwen2-style decoder,
the paged engine with hot and warm tiers, open-loop traffic) at sizes a
CPU run holds, so the harness can be driven end to end without a chip.
"""
from __future__ import annotations

import json
import pathlib
import shutil

ROOT = pathlib.Path(__file__).resolve().parents[2]

TINY_CONFIG = {
    "name": "tiny", "arch": "qwen2-7b", "family": "dense_gqa",
    "source": "test-only configuration",
    "config": {"hidden_act": "silu", "hidden_size": 64,
               "intermediate_size": 128, "num_attention_heads": 4,
               "num_key_value_heads": 2, "num_hidden_layers": 2,
               "rms_norm_eps": 1e-06, "rope_theta": 1000000.0,
               "use_sliding_window": False, "tie_word_embeddings": False,
               "vocab_size": 256},
    "reduced": [],
    "serving": {"attn_backend": "pallas_int8", "page_size": 16,
                "hbm_budget_bytes": 1 << 20, "hot_fraction": 0.5,
                "enable_warm": True, "enable_cold": False},
}
TINY_MIX = {
    "loop": "open", "arrivals": {"process": "poisson", "rate_per_s": 3.0},
    "prompt": {"dist": "lognormal", "median": 20, "sigma": 0.5, "min": 4,
               "max": 40},
    "output": {"dist": "uniform", "min": 4, "max": 8},
    "serve": {"lanes": 2, "max_len": 64}, "window": {"start": "immediate"},
    "check": {"sample_tokens": 12, "max_requests": 2},
}
TINY_CELL = "tiny.chat"
#: set from the tiny cell's own readings (tests/bench/test_bench_gate.py)
TINY_LIMITS = {"max_gap": 0.05}


#: a cell large enough that a control one precision step below bf16
#: shows: readings on four seeds (CPU) were a widest gap of at most 0.0142
#: for the program, 0.0148-0.0401 for int8 weights and 0.102-0.169 for
#: fp8 weights
MID_CELL = "mid.chat"
MID_CONFIG = dict(TINY_CONFIG, name="mid", config=dict(
    TINY_CONFIG["config"], hidden_size=256, intermediate_size=768,
    num_attention_heads=8, num_hidden_layers=4, vocab_size=8192),
    serving=dict(TINY_CONFIG["serving"], hbm_budget_bytes=8 << 20))
MID_MIX = dict(TINY_MIX, output={"dist": "uniform", "min": 24, "max": 40},
               prompt=dict(TINY_MIX["prompt"], max=40),
               serve={"lanes": 2, "max_len": 96},
               check={"sample_tokens": 80, "max_requests": 3})
MID_LIMITS = {"max_gap": 0.05}

#: a closed loop whose KV outgrows a six-page hot tier: lanes are
#: preempted and their pages demoted to the warm tier and back
LD_CELL = "tiny.longdoc"
LD_CONFIG = dict(TINY_CONFIG, name="tiny-ld", serving=dict(
    TINY_CONFIG["serving"], hbm_budget_bytes=52000, enable_cold=True))
LD_MIX = {"loop": "closed", "clients": 5, "queue_per_client": 6,
          "prompt": {"dist": "uniform", "min": 24, "max": 48},
          "output": {"dist": "uniform", "min": 8, "max": 16},
          "serve": {"lanes": 3, "max_len": 64},
          "window": {"start": "after_preemption"},
          "check": {"sample_tokens": 30, "max_requests": 2}}

CELLS = {TINY_CELL: ("tiny", TINY_CONFIG, TINY_MIX, TINY_LIMITS),
         MID_CELL: ("mid", MID_CONFIG, MID_MIX, MID_LIMITS),
         LD_CELL: ("tiny-ld", LD_CONFIG, LD_MIX, TINY_LIMITS)}


def make_tiny_root(root: pathlib.Path):
    """Lay out a checkout holding the benchmark and the test cells."""
    shutil.copytree(ROOT / "bench", root / "bench", dirs_exist_ok=True,
                    ignore=shutil.ignore_patterns("__pycache__"))
    cells = []
    for cell, (name, conf, mix, limits) in CELLS.items():
        (root / "bench" / "configs" / f"{name}.json").write_text(
            json.dumps(conf))
        (root / "bench" / "traffic" / f"{name}-chat.json").write_text(
            json.dumps(mix))
        (root / "bench" / "limits" / f"{cell}.json").write_text(
            json.dumps(limits))
        cells.append({"name": cell, "config": name,
                      "traffic": f"{name}-chat", "chips": 1, "why": "test"})
    man = json.loads((ROOT / "BENCHMARK.json").read_text())
    man["workloads"] = cells
    names = [c["name"] for c in cells]
    man["end_to_end"] = [dict(m, workloads=names) if "workloads" in m
                         else m for m in man["end_to_end"]]
    man["per_layer"] = [dict(m, workloads=names) for m in man["per_layer"]]
    (root / "BENCHMARK.json").write_text(json.dumps(man))
    return root
