"""Every ``BENCHMARK.json`` entry resolves to its files, and the file
keeps to the shape the benchmark's readers rely on."""
from __future__ import annotations

import json
import re

import pytest

from bench import metrics as M
from bench.traffic import gen
from bench_fixtures import ROOT

MAN = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
TOP_KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}


def test_top_level_keys_and_paths():
    assert set(MAN) == TOP_KEYS
    for p in MAN["paths"]:
        assert (ROOT / p).is_dir() and not p.startswith("/") and ".." not in p
    assert 1 <= MAN["run_seconds"] <= 51
    assert len(json.dumps(MAN)) < 64 * 1024


def test_command_names_only_files_under_paths():
    cmd = MAN["command"]
    assert cmd[:3] == ["python3", "-m", "bench.run"]
    mod = cmd[2].replace(".", "/") + ".py"
    assert any(mod.startswith(p + "/") for p in MAN["paths"])
    assert (ROOT / mod).is_file()


@pytest.mark.parametrize("conf", MAN["configs"], ids=lambda c: c["name"])
def test_config_files(conf):
    assert set(conf) == {"name", "source", "file", "reduced", "why"}
    assert NAME.match(conf["name"])
    path = ROOT / conf["file"]
    assert path.is_file()
    assert any(conf["file"].startswith(p + "/") for p in MAN["paths"])
    data = json.loads(path.read_text())
    assert data["name"] == conf["name"]
    assert data["reduced"] == conf["reduced"]
    # every reduced key is stated with its published value
    assert set(data["published"]) >= set(conf["reduced"])
    for key in conf["reduced"]:
        assert NAME.match(key)
        assert not key.endswith(("_dim", "_rank", "_size"))
        assert data["config"][key] != data["published"][key]
    assert 1 <= len(conf["why"]) <= 200
    assert any(w["config"] == conf["name"] for w in MAN["workloads"])


@pytest.mark.parametrize("cell", MAN["workloads"], ids=lambda c: c["name"])
def test_cells_resolve(cell):
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    assert NAME.match(cell["name"]) and NAME.match(cell["traffic"])
    assert cell["chips"] in (1, 4)
    assert 1 <= len(cell["why"]) <= 200 and "\n" not in cell["why"]
    assert cell["config"] in {c["name"] for c in MAN["configs"]}
    mix = gen.load_mix(cell["traffic"])
    assert mix["loop"] in ("open", "closed")
    lim = json.loads((ROOT / "bench" / "limits" / f"{cell['name']}.json")
                     .read_text())
    assert lim["max_gap"] > 0
    # every cell reports setup_s, another end-to-end metric and a
    # per-layer metric
    e2e = [m["name"] for m in MAN["end_to_end"]
           if cell["name"] in m.get("workloads", [cell["name"]])]
    assert "setup_s" in e2e and len(e2e) >= 2
    assert M.metrics_for(MAN, cell["name"])


def test_cells_are_unique_pairs():
    pairs = [(c["config"], c["traffic"]) for c in MAN["workloads"]]
    assert len(pairs) == len(set(pairs))
    names = [c["name"] for c in MAN["workloads"]]
    assert len(names) == len(set(names))


def test_end_to_end_metrics():
    names = {m["name"] for m in MAN["end_to_end"]}
    assert "setup_s" in names
    cells = {c["name"] for c in MAN["workloads"]}
    for m in MAN["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
        assert set(m.get("workloads", cells)) <= cells


@pytest.mark.parametrize("metric", MAN["per_layer"], ids=lambda m: m["name"])
def test_per_layer_metrics_have_readers(metric):
    assert set(metric) <= {"name", "unit", "better", "source", "layer",
                           "moves", "workloads"}
    assert NAME.match(metric["name"]) and UNIT.match(metric["unit"])
    assert metric["source"] in ("device_trace", "program_span",
                                "program_counter", "host_clock")
    assert callable(M.reader(metric["name"]))
    moved = {m["name"]: m for m in MAN["end_to_end"]}[metric["moves"]]
    cells = {c["name"] for c in MAN["workloads"]}
    for cell in metric.get("workloads", cells):
        assert cell in cells
        assert cell in moved.get("workloads", cells)
    assert 1 <= len(metric["layer"]) <= 200


def test_layer_names_are_spelled_alike():
    layers = {m["layer"] for m in MAN["per_layer"]}
    assert all(layer == layer.strip() for layer in layers)
    assert len({layer.lower() for layer in layers}) == len(layers)
