"""Run one benchmark cell once and print its result line.

    python -m bench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up (weights from the seed, engine, compile cache, warm-up of the
cell's own shapes, and a mix's pre-roll of its own traffic) is timed as
``setup_s``; then the window runs for ``--seconds``, and the drain waits
for every answer due in it. With ``--trace 0`` the result's metrics are
the cell's end-to-end metrics; with ``--trace 1`` its per-layer metrics,
read from a profiler trace of the window's last seconds. The last line of
standard output is one JSON object; the numbers that decide ``correct``
are printed beside their limits as the last lines of standard error and
under the result's last key, ``check``. Without a TPU (or with fewer
chips than the cell asks for) the run exits non-zero and prints no
result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse      # noqa: E402
import json          # noqa: E402
import pathlib       # noqa: E402
import sys           # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", default=None,
                    help="copy the raw profiler trace to this directory")
    args = ap.parse_args(argv)

    src = ROOT / "src"
    if not (src / "repro").is_dir():
        print(f"bench: the program is not in this checkout ({src})",
              file=sys.stderr)
        return 2
    sys.path.insert(0, str(src))
    from bench import harness
    try:
        out = harness.run_cell(args.workload, args.seed, args.seconds,
                               bool(args.trace), t_start=T_START,
                               trace_dir=args.keep_trace)
    except harness.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    for name, c in out["check"].items():
        print(f"check {name}: {c['value']} {c['rule']} {c['limit']}",
              file=sys.stderr)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
