"""Knee sweep of an open-loop cell: one engine, a window per rate.

    python -m bench.sweep --workload <cell> --seed <n> --seconds <s> \
        --rates 2,4,6,8

Used once, when a cell is defined, to find the highest rate the system
sustains; the cell then runs at a fixed rate written in its mix file.
Set-up is the benchmark's own (``harness.set_up``, ``harness.warm_up``);
each rate is one window of the cell's loop, pre-roll and drain included,
fed by a fresh driver. Prints one JSON line per rate: the end-to-end
metrics, the requests offered and lost, how many were unfinished at the
close, the requests waiting for a lane (queued, or prefilled and
parked) at the open and at the close, and how long the drain took. The
engine prefills a request as it arrives, whether or not a lane is free,
so overload shows as requests waiting for a lane and in the tokens per
second, not in the time to first token. A rate whose drain leaves requests
in the engine ends the sweep.
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
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT / "src"))
    from bench import harness as H
    from bench.traffic import gen

    s = H.set_up(args.workload, args.seed)
    rates = [float(r) for r in args.rates.split(",")]
    plans = [gen.plan(s.mix, args.seed + k, args.seconds, s.vocab, r)
             for k, r in enumerate(rates)]
    H.warm_up(s.drv, plans, s.mix["serve"]["max_len"])
    print(json.dumps({"device": s.device, "setup_s":
                      time.perf_counter() - T_START}), flush=True)
    drv = s.drv
    for rate, plan in zip(rates, plans):
        drv = H.Driver(s.eng, args.seed, first_rid=drv.next_rid)
        comp = H._CompileCounter()
        res = H._open_loop(drv, plan, s.mix, args.seconds, False,
                           time.perf_counter(), comp, None)
        waiting = sum(1 for r in res["recs"] if not r.times
                      or r.times[-1] > res["close"])
        m, attempted, failed, _ = H._end_to_end(res, args.seconds)
        print(json.dumps({"rate_per_s": rate, "attempted": attempted,
                          "failed": failed, "unfinished_at_close": waiting,
                          "waiting_for_lane": res["waiting"],
                          "offered_tokens_per_s": sum(
                              p.max_new for p in plan) / args.seconds,
                          "drain_s": res["drained_at"] - res["close"],
                          "compiles_in_window": comp.n,
                          "metrics": {k: v["value"] for k, v in m.items()
                                      if k != "setup_s"}}), flush=True)
        if drv.live:
            break
    return 0


if __name__ == "__main__":
    sys.exit(main())
