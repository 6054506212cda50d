"""End-to-end serving driver (batched requests, continuous batching).

    PYTHONPATH=src python -m repro.launch.serve --arch qwen2-7b --reduced \
        --requests 16 --slots 4 --max-new 12 --kv-mode int8

    # paged, tiered KV cache (repro.cache): --slots becomes decode lanes,
    # residency is bounded by the HBM budget instead of the slot count
    PYTHONPATH=src python -m repro.launch.serve --arch qwen2-7b --reduced \
        --requests 16 --slots 4 --paged --hbm-budget-mb 1

    # attention backend for the paged decode step (kernels/decode_attn/
    # ops.py registry): gather (jnp), pallas (bf16 kernel), pallas_int8
    # (tiered kernel, in-VMEM warm dequant)
    ... --paged --attn-backend pallas_int8

Engine construction goes through ``ServeConfig.build()`` (repro.serving.
config): the CLI's flat flags fold into the config's nested ``AssistSpec``
(repro.assist), and ``EngineBase.from_config`` picks the dense or paged
engine -- one construction path for both.
"""
from __future__ import annotations

import argparse
import atexit
import os
import signal
import time

import numpy as np

import dataclasses

from repro.kernels.decode_attn.ops import attn_backend_names
from repro.launch.compile_cache import enable_compile_cache
from repro.configs.base import DEFAULT_EOS_ID
from repro.obs import Observability, ObsSpec
from repro.obs.export import SnapshotWriter, serve_metrics
from repro.obs.metrics import REGISTRY
from repro.serving.config import ServeConfig
from repro.serving.engine import Request


def build_engine(scfg: ServeConfig):
    """(engine, model, params) for a ServeConfig.

    Thin alias of :meth:`ServeConfig.build`, kept for callers of the
    pre-assist API.
    """
    return scfg.build()


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--max-new", type=int, default=12)
    ap.add_argument("--kv-mode", default="bf16", choices=("bf16", "int8"))
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--eos-id", type=int, default=DEFAULT_EOS_ID,
                    help="end-of-sequence token id (stops a request)")
    ap.add_argument("--paged", action="store_true",
                    help="use the paged, tiered KV cache (repro.cache)")
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--hbm-budget-mb", type=float, default=64.0)
    ap.add_argument("--attn-backend", default="gather",
                    choices=attn_backend_names(),
                    help="paged decode attention backend")
    ap.add_argument("--max-cold-pages", type=int, default=None,
                    help="cap on cold (host-offloaded) page ids; default "
                         "derives from the host budget / HBM pools")
    # cross-request prefix reuse (paged engine; DESIGN.md 14)
    ap.add_argument("--prefix-reuse", action="store_true",
                    help="radix-tree prefix store at admission: shared "
                         "prompt prefixes map read-only pages into new "
                         "requests (COW on divergence), skipping prefill "
                         "on a full hit")
    ap.add_argument("--prefix-max-nodes", type=int, default=512,
                    help="prefix-store node budget (one held page per "
                         "node; LRU leaves evicted past it)")
    ap.add_argument("--prefix-min-pages", type=int, default=1,
                    help="shortest shareable prefix, in full pages")
    # multi-turn sessions (repro.sessions, DESIGN.md 15; paged engine)
    # dest avoids the ServeConfig.sessions field (a SessionSpec): the
    # vars(args)-to-fields filter below must not plant this int there
    ap.add_argument("--sessions", dest="n_sessions", type=int,
                    default=None, metavar="N",
                    help="serve N multi-turn sessions from the seeded "
                         "load generator instead of one-shot requests: "
                         "conversations park between turns and resume "
                         "without re-prefilling history")
    ap.add_argument("--no-session-park", dest="session_park",
                    action="store_false",
                    help="stateless baseline: drop pages between turns "
                         "and re-prefill the full history each turn")
    ap.add_argument("--session-resume", default="auto",
                    choices=("auto", "replay", "reprefill"),
                    help="resume policy for parked sessions (auto = the "
                         "promotion-cost vs re-prefill rule)")
    ap.add_argument("--session-turns", type=float, default=3.0,
                    help="mean turns per generated session")
    # observability (repro.obs, DESIGN.md 13)
    ap.add_argument("--no-obs", action="store_true",
                    help="disable all telemetry (counters, probe, trace): "
                         "the overhead-free hot path")
    ap.add_argument("--metrics-port", type=int, default=None,
                    help="serve Prometheus text on this port at /metrics "
                         "(0 = ephemeral; omit to not serve)")
    ap.add_argument("--snapshot-json", default=None,
                    help="write a periodic JSON metrics snapshot here")
    ap.add_argument("--snapshot-every", type=float, default=10.0,
                    help="snapshot period in seconds")
    ap.add_argument("--trace", default=None,
                    help="write a Chrome trace-event JSON (Perfetto) of "
                         "the run here")
    # resilience (repro.serving.resilience, DESIGN.md 17)
    ap.add_argument("--max-queue", dest="max_queue", type=int, default=None,
                    help="bounded admission queue: above this depth the "
                         "lowest-SLO-class submission is shed with error "
                         "status (interactive sheds last)")
    ap.add_argument("--harvest-timeout", dest="harvest_timeout_s",
                    type=float, default=None, metavar="S",
                    help="surface a hung harvest device_get as a watchdog "
                         "trip after S seconds instead of a silent hang")
    ap.add_argument("--session-store", default=None, metavar="PATH",
                    help="durable session snapshot: restored at startup "
                         "if present, written on SIGTERM/exit after a "
                         "graceful drain (paged engine only)")
    ap.add_argument("--strict-transfers", action="store_true",
                    help="wrap the jitted tick dispatch in "
                         "jax.transfer_guard('disallow'): any implicit "
                         "host<->device transfer in the decode loop "
                         "raises instead of silently syncing")
    args = ap.parse_args(argv)
    fields = {f.name for f in dataclasses.fields(ServeConfig)}
    spec = ObsSpec.off() if args.no_obs else ObsSpec(
        trace=args.trace is not None)
    if args.strict_transfers:
        # composes with --no-obs: the guard is independent of telemetry
        spec = dataclasses.replace(spec, strict_transfers=True)
    scfg = ServeConfig(obs=spec, **{k: v for k, v in vars(args).items()
                                    if k in fields and k != "obs"})

    # the serving entrypoint exports through the PROCESS-GLOBAL registry
    # (library consumers get private ones); /metrics and the snapshot
    # writer read it concurrently with the engine loop
    obs = Observability(spec, registry=None if args.no_obs else REGISTRY)
    srv = writer = None
    if args.metrics_port is not None and not args.no_obs:
        srv = serve_metrics(args.metrics_port)
        print(f"/metrics on http://127.0.0.1:{srv.server_address[1]}/metrics")
    if args.snapshot_json and not args.no_obs:
        writer = SnapshotWriter(args.snapshot_json,
                                every_s=args.snapshot_every).start()

    eng, model, _ = scfg.build(obs=obs)
    cfg = model.cfg

    # crash-safe serving (DESIGN.md 17): restore parked sessions from the
    # durable store, and drain gracefully on SIGTERM/exit -- stop
    # admission, finish in-flight ticks, persist, snapshot metrics
    store_path = args.session_store if scfg.assist.paged else None
    if store_path and os.path.exists(store_path):
        eng.restore(store_path)
        print(f"restored {len(eng._parked_sessions)} parked session(s) "
              f"from {store_path}")
    _drained = []

    def _drain(signum=None, frame=None):
        if _drained:
            return
        _drained.append(True)
        eng.queue.clear()                      # stop admission
        eng.run()                              # finish in-flight ticks
        if store_path:
            from repro.serving.resilience import SnapshotError
            try:
                eng.persist(store_path)
                print(f"sessions persisted -> {store_path}")
            except SnapshotError as e:
                print(f"persist skipped: {e}")
        if writer is not None:
            writer.stop()                      # final metrics snapshot
        if signum is not None:
            raise SystemExit(128 + signum)

    signal.signal(signal.SIGTERM, _drain)
    atexit.register(_drain)
    rng = np.random.default_rng(scfg.seed)
    t0 = time.time()
    if args.n_sessions is not None:
        # trace-driven multi-turn serving (repro.sessions): parked turns
        # keep their pages; goodput is accounted per SLO class
        if not scfg.assist.paged:
            raise SystemExit("--sessions needs --paged (the session "
                             "layer parks pages, not slots)")
        import dataclasses as _dc
        from repro.sessions import SessionManager, make_trace
        sspec = _dc.replace(scfg.session_spec(),
                            resume_policy=args.session_resume)
        traces = make_trace(n_sessions=args.n_sessions, seed=scfg.seed,
                            vocab_size=cfg.vocab_size,
                            page_size=scfg.page_size,
                            max_len=scfg.max_len,
                            mean_turns=args.session_turns,
                            max_new=scfg.max_new)
        mgr = SessionManager(eng, sspec, traces)
        rep = mgr.run()
        dt = time.time() - t0
        n_tok = eng.tokens_generated
        print(f"\n{rep['sessions']} sessions / {rep['turns']} turns, "
              f"{n_tok} tokens in {dt:.1f}s ({n_tok / max(dt, 1e-9):.1f} "
              f"tok/s); resumes: {rep['resumes_replay']} replay / "
              f"{rep['resumes_reprefill']} re-prefill, "
              f"{rep['replayed_tokens']} tokens replayed")
        for cls_name, c in rep["per_class"].items():
            gp = (f"{c['goodput_frac']:.2f}"
                  if c["goodput_frac"] is not None else "n/a")
            print(f"  {cls_name:12s} turns={c['turns']:3d} "
                  f"ok={c['turns_ok']:3d} viol={c['slo_violations']:3d} "
                  f"goodput={gp} p95={c['p95_latency_ticks']} ticks "
                  f"(budget {c['budget_ticks']})")
        done = eng.finished
    else:
        for rid in range(scfg.requests):
            plen = int(rng.integers(4, scfg.max_len - scfg.max_new - 1))
            eng.submit(Request(rid=rid,
                               prompt=list(rng.integers(2, cfg.vocab_size,
                                                        plen)),
                               max_new=scfg.max_new))
        done = eng.run()
    dt = time.time() - t0
    n_tok = sum(len(r.out) for r in done)
    for r in sorted(done, key=lambda r: r.rid)[:8]:
        print(f"req {r.rid:3d}: prompt={len(r.prompt):3d} tok "
              f"-> {r.out[:8]}{'...' if len(r.out) > 8 else ''}")
    aspec = scfg.assist
    mode = (f"paged/{aspec.attn_backend}" if aspec.paged
            else f"kv={aspec.kv}")
    print(f"\n{len(done)} requests, {n_tok} tokens in {dt:.1f}s "
          f"({n_tok/dt:.1f} tok/s, {mode})")
    stats = eng.stats()
    if "dispatch_p50_ms" in stats:
        print(f"tick dispatch p50/p95/p99 ms: "
              f"{stats['dispatch_p50_ms']:.3f}/"
              f"{stats['dispatch_p95_ms']:.3f}/"
              f"{stats['dispatch_p99_ms']:.3f}  "
              f"exec p50/p95/p99 ms: "
              f"{stats.get('exec_p50_ms', 0.0):.3f}/"
              f"{stats.get('exec_p95_ms', 0.0):.3f}/"
              f"{stats.get('exec_p99_ms', 0.0):.3f} "
              f"({stats.get('exec_samples', 0)} fenced samples)")
    if aspec.paged:
        print(f"cache stats: {stats}")
    if args.trace and eng.obs.tracer is not None:
        eng.obs.tracer.write(args.trace)
        print(f"chrome trace -> {args.trace}")
    if writer is not None:
        writer.stop()
        print(f"metrics snapshot -> {args.snapshot_json}")
    if srv is not None:
        srv.shutdown()
    return done


if __name__ == "__main__":
    enable_compile_cache()
    main()
