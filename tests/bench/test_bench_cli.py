"""The command the benchmark is run by: no chip, or no program, means a
non-zero exit and no result line."""
from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys

from bench_fixtures import ROOT

MAN = json.loads((ROOT / "BENCHMARK.json").read_text())


def _run(cwd, *extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    cmd = [sys.executable] + MAN["command"][1:] + [
        "--workload", MAN["workloads"][0]["name"], "--seed", "3",
        "--seconds", "1", "--trace", "0", *extra]
    return subprocess.run(cmd, cwd=cwd, env=env, capture_output=True,
                          text=True, timeout=300)


def test_without_a_tpu_the_run_fails_and_prints_nothing():
    p = _run(ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "needs a TPU" in p.stderr


def test_benchmark_files_alone_do_not_run(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    for p in MAN["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
