"""Benchmark harness entry: one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--only fig8,fig13] [--smoke]

    # tier-1 tests + smoke benchmarks (incl. the serving_micro attention-
    # backend matrix) as ONE command:
    PYTHONPATH=src python -m benchmarks.run --smoke --with-tier1

    # persist the serving perf trajectory (tokens/s, tick percentiles,
    # capacity ratios, prefill compile counts) for cross-PR comparison:
    PYTHONPATH=src python -m benchmarks.run --only serving_micro --json

    # perf-trend gate: rerun serving_micro and fail on a >20% tokens/s
    # regression vs the committed record (CI runs this; --smoke must
    # match the record's smoke flag or the gate refuses to compare).
    # Cross-machine by default: a uniform speed shift vs the record's
    # box is normalized out; --compare-absolute for same-machine A/B.
    PYTHONPATH=src python -m benchmarks.run --smoke --compare \
        BENCH_serving.json

    # Chrome trace-event JSON of one tiered serving scenario (Perfetto)
    PYTHONPATH=src python -m benchmarks.run --trace out.json

Each module prints its table and asserts its paper-validation bounds; a
failed validation fails the run (EXPERIMENTS.md SS Paper-validation is
generated from this output).  ``--smoke`` forwards a reduced workload to
the modules that support it (CI mode); serving_micro's smoke run includes
the per-backend (gather/pallas/pallas_int8) decode matrix.  ``--json``
writes ``BENCH_serving.json`` at the repo root from serving_micro's
returned record (CI uploads it as an artifact).
"""
from __future__ import annotations

import argparse
import inspect
import json
import pathlib
import subprocess
import sys
import time
import traceback

BENCH_JSON = pathlib.Path(__file__).resolve().parents[1] / \
    "BENCH_serving.json"


def _jsonable(x):
    """Coerce benchmark records (numpy scalars, tuples-as-keys already
    stringified upstream) into plain JSON types; drop what will not fit."""
    if isinstance(x, dict):
        return {str(k): _jsonable(v) for k, v in x.items()}
    if isinstance(x, (list, tuple)):
        return [_jsonable(v) for v in x]
    if isinstance(x, bool) or x is None or isinstance(x, (int, float, str)):
        return x
    if hasattr(x, "item"):                       # numpy scalar
        return x.item()
    return str(x)

def _collect_tps(rec, prefix=""):
    """Flatten a serving record to {scenario_path: tokens_per_s}."""
    out = {}
    if isinstance(rec, dict):
        for k, v in rec.items():
            if isinstance(v, dict):
                if "tokens_per_s" in v:
                    out[f"{prefix}{k}"] = float(v["tokens_per_s"])
                out.update(_collect_tps(v, f"{prefix}{k}/"))
    return out


def _compare_serving(result, base, baseline_path, smoke, threshold=0.20,
                     absolute=False):
    """Perf-trend gate: fail on a >threshold tokens/s regression in any
    scenario present in both the fresh run and the committed record.

    ``base`` is the baseline record LOADED BEFORE the benchmarks ran:
    --json rewrites BENCH_serving.json mid-run, and comparing against the
    rewritten file would self-compare and gate nothing.

    By default the comparison is MACHINE-NORMALIZED: the committed record
    comes from whatever box the last PR ran on, CI runs on another, and a
    uniform speed difference is not a regression.  The geometric mean of
    per-scenario new/old ratios estimates that fleet-wide shift; a
    scenario regresses when it loses >threshold RELATIVE to the shift --
    i.e. slowed down more than the workload as a whole did.  A real
    code-level slowdown is never uniform across hot-only / tiered /
    backend scenarios (they stress different paths), so it still trips
    the per-scenario gate.  ``absolute=True`` (--compare-absolute) gates
    raw tokens/s instead -- the right mode for a same-machine A/B.
    """
    if bool(base.get("smoke")) != bool(smoke):
        raise SystemExit(
            f"--compare: baseline {baseline_path} was recorded with "
            f"smoke={base.get('smoke')} but this run has smoke={smoke}; "
            f"workloads differ, refusing to compare")
    new = _collect_tps(_jsonable(result))
    old = _collect_tps(base)
    shared = sorted(k for k in set(new) & set(old)
                    if old[k] > 0 and new[k] > 0)
    if not shared:
        raise SystemExit("--compare: no shared tokens/s scenarios between "
                         "the run and the baseline record")
    fresh = sorted(set(new) - set(old))
    if fresh:
        # a scenario landing with its first record has no baseline yet:
        # warn (so a typo'd rename is visible) but never fail on it
        print(f"\n--compare: {len(fresh)} scenario(s) absent from "
              f"{baseline_path} (new this run, not gated): {fresh}")
    stale = sorted(set(old) - set(new))
    if stale:
        # the record can also be NEWER than the checkout (a baseline
        # committed by a later PR, compared on an older branch): those
        # scenarios have nothing to gate against -- warn, never crash
        print(f"\n--compare: {len(stale)} scenario(s) only in "
              f"{baseline_path} (stale or from a newer schema, not "
              f"gated): {stale}")
    import math
    shift = 1.0 if absolute else math.exp(
        sum(math.log(new[k] / old[k]) for k in shared) / len(shared))
    regressions = []
    mode = "absolute" if absolute else \
        f"machine-normalized, fleet shift {shift:.2f}x"
    print(f"\nperf trend vs {baseline_path} "
          f"(gate: >{threshold:.0%} tokens/s regression, {mode}):")
    for k in shared:
        o, n = old[k] * shift, new[k]
        delta = (n - o) / o
        bad = n < (1.0 - threshold) * o
        print(f"  {'REGRESSED' if bad else 'ok':>9}  {k:40s} "
              f"{o:9.1f} -> {n:9.1f} tok/s ({delta:+.1%})")
        if bad:
            regressions.append((k, o, n))
    return regressions


MODULES = [
    ("fig2", "benchmarks.fig2_bottleneck"),
    ("fig8", "benchmarks.fig8_performance"),
    ("fig9", "benchmarks.fig9_bandwidth"),
    ("fig12", "benchmarks.fig12_algorithms"),
    ("fig13", "benchmarks.fig13_ratio"),
    ("fig14", "benchmarks.fig14_bw_sensitivity"),
    ("fig10", "benchmarks.fig10_energy"),
    ("kernel_micro", "benchmarks.kernel_micro"),
    ("serving_micro", "benchmarks.serving_micro"),
]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated subset, e.g. fig8,fig13")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced workloads (fast CI check)")
    ap.add_argument("--seed", type=int, default=0,
                    help="RNG seed threaded through every benchmark "
                         "stream that supports it (request prompts, "
                         "session traces) -- one seed, bit-reproducible "
                         "workloads")
    ap.add_argument("--with-tier1", action="store_true",
                    help="run the tier-1 pytest suite before the benchmarks")
    ap.add_argument("--json", action="store_true",
                    help="write BENCH_serving.json (serving perf record)")
    ap.add_argument("--compare", metavar="BASELINE_JSON", default=None,
                    help="perf-trend gate: fail on >20%% tokens/s "
                         "regression vs a committed BENCH_serving.json "
                         "(machine-normalized: a uniform speed shift vs "
                         "the record's box is factored out)")
    ap.add_argument("--compare-absolute", action="store_true",
                    help="gate raw tokens/s instead of normalizing out "
                         "the fleet-wide shift (same-machine A/B)")
    ap.add_argument("--trace", metavar="OUT_JSON", default=None,
                    help="write a Chrome trace-event JSON of one tiered "
                         "serving scenario and exit (view in Perfetto)")
    ap.add_argument("--strict-transfers", action="store_true",
                    help="run serving benchmarks with the tick transfer "
                         "guard armed (jax.transfer_guard('disallow') "
                         "around the jitted dispatch): an implicit host "
                         "sync in the decode loop fails the run")
    args = ap.parse_args()
    only = set(args.only.split(",")) if args.only else None

    if args.compare and only and "serving_micro" not in only:
        raise SystemExit("--compare needs serving_micro in the run "
                         "(drop --only or include serving_micro)")
    baseline = None
    if args.compare:
        # load NOW: --json may rewrite this very file during the run
        baseline = json.loads(pathlib.Path(args.compare).read_text())

    failures = []
    serving_result = None
    if args.with_tier1:
        print(f"{'=' * 72}\nRUNNING tier-1 (pytest)\n{'=' * 72}")
        repo_root = pathlib.Path(__file__).resolve().parents[1]
        rc = subprocess.run([sys.executable, "-m", "pytest"],
                            cwd=repo_root).returncode
        if rc != 0:
            failures.append(("tier1", f"pytest exit {rc}"))
    # only after pytest has exited: a chip belongs to one process, and this
    # is the first time this process touches JAX
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    if args.trace:
        from benchmarks import serving_micro
        serving_micro.run_trace(args.trace, smoke=True)
        return
    for name, modname in MODULES:
        if only and name not in only:
            continue
        print(f"\n{'=' * 72}\nRUNNING {name} ({modname})\n{'=' * 72}")
        t0 = time.time()
        try:
            mod = __import__(modname, fromlist=["main"])
            params = inspect.signature(mod.main).parameters
            kwargs = {}
            if args.smoke and "smoke" in params:
                kwargs["smoke"] = True
            if "seed" in params:
                kwargs["seed"] = args.seed
            if args.strict_transfers and "strict_transfers" in params:
                kwargs["strict_transfers"] = True
            result = mod.main(**kwargs)
            print(f"[{name}] done in {time.time() - t0:.1f}s")
            if name == "serving_micro":
                serving_result = result
            if args.json and name == "serving_micro" and result:
                record = {"smoke": bool(args.smoke), **_jsonable(result)}
                BENCH_JSON.write_text(json.dumps(record, indent=2,
                                                 sort_keys=True) + "\n")
                print(f"[{name}] wrote {BENCH_JSON}")
        except Exception as e:
            traceback.print_exc()
            failures.append((name, str(e)))
    if args.compare and serving_result is not None:
        regs = _compare_serving(serving_result, baseline, args.compare,
                                args.smoke,
                                absolute=args.compare_absolute)
        if regs:
            failures.append(("perf-trend",
                             f"{len(regs)} scenario(s) regressed >20% "
                             f"tokens/s: {[k for k, _, _ in regs]}"))
    elif args.compare:
        failures.append(("perf-trend", "serving_micro produced no record "
                         "to compare"))
    print(f"\n{'=' * 72}")
    if failures:
        print(f"{len(failures)} benchmark(s) FAILED: "
              f"{[n for n, _ in failures]}")
        sys.exit(1)
    print("ALL BENCHMARKS PASSED")


if __name__ == "__main__":
    main()
