"""Readings behind a cell's correctness limit, many seeds in one process.

    python -m bench.calibrate --workload <cell> --seconds <s> --seeds 1,2,3

For every seed: one run of the cell as the benchmark makes it, with the
control (the reference at a precision below the configuration's, int8 by
default) in the program's place at the check. On one sample of served
tokens it reads the program's gaps under the float32 reference (the
lower reading) and the control's (the upper reading): the widest gap,
the mean gap and the positions that differ, and the run's verdict, which
judges the control and has to read ``correct: false``. Prints one JSON
line per seed. The limit in ``bench/limits/<cell>.json`` is set from
these readings.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse      # noqa: E402
import json          # noqa: E402
import sys           # noqa: E402

from bench.run import ROOT  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control", default="int8", choices=("int8", "fp8"))
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT / "src"))
    from bench import harness
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        out = harness.run_cell(args.workload, seed, args.seconds, False,
                               t_start=t0, control=args.control)
        print(json.dumps({
            "seed": seed, "control": args.control,
            "control_correct": out["correct"],
            "attempted": out["attempted"], "failed": out["failed"],
            "check": {k: v["value"] for k, v in out["check"].items()},
            "readings": out["diagnostics"]["readings"],
            "setup_s": out["metrics"]["setup_s"]["value"],
            "run_s": time.perf_counter() - t0}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
