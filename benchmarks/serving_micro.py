"""Serving microbenchmark: resident-token capacity, tokens/s and decode
latency percentiles across tier configurations of the paged KV cache
(repro.cache), plus tokens/s per ATTENTION BACKEND (kernels/decode_attn/
ops.py registry).

Engines are constructed through ``ServeConfig.build()`` with a nested
``AssistSpec`` (repro.assist) -- the same unified path serve.py and the
examples use -- so the benchmark exercises the production construction
API, not private constructors.

Under ONE fixed HBM budget, three engines admit the same request stream:

  hot-only        bf16 pages, no demotion (a dense-quality paged cache)
  hot+warm        LRU demotion to int8 pages (the CABA KV site)
  hot+warm+cold   plus delta+BDI/FPC-packed host offload with WaSP prefetch

Validation target (the subsystem's acceptance bar): the tiered configs hold
>= 2x the resident tokens of hot-only under the same HBM budget, while
every admitted request still completes.

The backend section decodes the same stream through each registered
attention backend (gather / pallas / pallas_int8), hot-only and with the
int8 warm tier forced into play, and reports tokens/s so the Pallas path's
cost/benefit is MEASURED -- on CPU the kernels run in interpret mode, so
absolute numbers only bound relative behavior until the TPU re-measure
(ROADMAP).

Per-tick decode latency is recorded over the measured window and reported
as DISPATCH p50/p95/p99 (ms) -- what the host loop pays per tick under
the async decode loop (PR 5), NOT how long the tick computes.  The
engine's execution probe (repro.obs.probe) fences every Nth tick with
``block_until_ready`` and reports EXEC p50/p95/p99 alongside; by
construction exec >= dispatch per fenced sample.  Both surfaces appear in
the tables and the JSON record; window tokens/s stays ground truth.

``main(smoke=True)`` shrinks the workload for CI (benchmarks/run.py
--smoke).
"""
from __future__ import annotations

import time

import numpy as np
import jax

from repro.analysis.runtime import assert_compile_bound
from repro.assist import AssistSpec
from repro.cache import PageGeometry, TierConfig
from repro.configs import ARCHS, reduced
from repro.kernels.decode_attn.ops import attn_backend_names
from repro.models.model import build_model, n_prompt_buckets
from repro.models.transformer import stack_plan
from repro.obs import Observability, ObsSpec
from repro.serving.config import ServeConfig
from repro.serving.engine import Request
from repro.serving.paged_engine import PagedEngine
from benchmarks.common import print_table

PAGE = 16
ARCH = "qwen2-7b"

#: set by main(strict_transfers=True) (benchmarks/run.py
#: --strict-transfers): every engine built below then arms the tick
#: transfer guard, so an implicit host sync in the decode loop fails the
#: benchmark instead of silently slowing it
STRICT_TRANSFERS = False


def _obs_spec():
    """The ObsSpec every scenario engine is built with (None = the
    ServeConfig default: counters + probe on, guard off)."""
    return ObsSpec(strict_transfers=True) if STRICT_TRANSFERS else None


def _assist_specs(hbm_budget: int):
    base = dict(paged=True, page_size=PAGE, hbm_budget_bytes=hbm_budget)
    return {
        "hot-only": AssistSpec(**base, enable_warm=False, enable_cold=False),
        "hot+warm": AssistSpec(**base, hot_fraction=0.5, enable_warm=True,
                               enable_cold=False),
        "hot+warm+cold": AssistSpec(**base, hot_fraction=0.5,
                                    enable_warm=True, enable_cold=True,
                                    host_budget_bytes=hbm_budget),
    }


def _build(model, params, spec: AssistSpec, lanes: int, max_len: int):
    scfg = ServeConfig(arch=ARCH, reduced=True, slots=lanes,
                       max_len=max_len, eos_id=0, assist=spec,
                       obs=_obs_spec())
    eng, _, _ = scfg.build(model, params)
    return eng


def _tick_window(eng, ticks: int):
    """(tokens/s, per-tick latencies[s]) over a fixed tick window.

    The engine loop is ASYNC (dispatch returns before the tick executes),
    so the window is bracketed by ``eng.sync()``: the open sync drains
    pending work out of the window, the close sync charges every
    dispatched tick's EXECUTION to the window.  Per-tick latencies time
    dispatch for all but the last tick, which absorbs the drain -- the
    window total (and so tokens/s) is always true wall time.
    """
    def _produced():
        # harvested tokens + the lagged in-flight tokens that will really
        # be appended (junk post-EOS rows excluded): true production
        return eng.tokens_generated + eng.pending_decode_tokens()

    eng.sync()
    t0 = time.time()
    tok0 = _produced()
    lats = []
    for i in range(ticks):
        t1 = time.time()
        if not eng.step():
            break
        if i == ticks - 1:
            eng.sync()                 # final tick: time execution too
        lats.append(time.time() - t1)
    eng.sync()
    dt = time.time() - t0
    tps = (_produced() - tok0) / max(dt, 1e-9)
    return tps, lats


def _pcts(lats) -> dict:
    """dispatch p50/p95/p99 tick latency in ms (zeros if nothing measured).

    These time the HOST side of the async loop (dispatch cost); the
    matching execution-true numbers are the engine probe's ``exec_p*``
    keys (repro.obs.probe), surfaced via ``eng.stats()``.
    """
    if not lats:
        return {"dispatch_p50_ms": 0.0, "dispatch_p95_ms": 0.0,
                "dispatch_p99_ms": 0.0}
    ms = np.asarray(lats) * 1e3
    return {f"dispatch_p{p}_ms": float(np.percentile(ms, p))
            for p in (50, 95, 99)}


def _exec_pcts(stats: dict) -> dict:
    """The probe's exec percentiles out of ``eng.stats()`` (zeros if the
    probe is off or never fenced)."""
    return {k: float(stats.get(k, 0.0))
            for k in ("exec_p50_ms", "exec_p95_ms", "exec_p99_ms",
                      "exec_samples")}


def run(smoke: bool = False, seed: int = 0):
    cfg = reduced(ARCHS[ARCH])
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    plan = stack_plan(cfg)
    geom = PageGeometry(len(plan.pattern), plan.n_scan, cfg.n_kv_heads,
                        PAGE, cfg.head_dim)

    budget_pages = 12 if smoke else 24        # hot-equivalent pages of HBM
    hbm_budget = budget_pages * geom.hot_page_bytes
    n_req = 24 if smoke else 64
    max_new = 4 if smoke else 8
    ticks = 6 if smoke else 24
    lanes = 2
    max_len = 48

    results = {}
    rows = []
    for name, spec in _assist_specs(hbm_budget).items():
        rng = np.random.default_rng(seed)
        eng = _build(model, params, spec, lanes, max_len)
        for rid in range(n_req):
            plen = int(rng.integers(18, 33))
            eng.submit(Request(rid=rid,
                               prompt=list(rng.integers(2, cfg.vocab_size,
                                                        plen)),
                               max_new=max_new))
        # one tick admits everything the budget allows (capacity probe) ...
        eng.step()
        capacity = eng.resident_tokens()
        # ... then measure decode throughput + latency over a tick window
        tps, lats = _tick_window(eng, ticks)
        eng.run(max_ticks=5000)               # drain: everything completes
        s = eng.stats()
        pct = _pcts(lats)
        ex = _exec_pcts(s)
        # window-measured dispatch pct wins over the probe's whole-run
        # dispatch numbers; exec_* comes from the probe (only source)
        results[name] = {"capacity": capacity, "tokens_per_s": tps,
                         "finished": len(eng.finished), **s, **pct, **ex}
        rows.append([name, eng.store.hot_pages, eng.store.warm_pages,
                     capacity, round(tps, 1),
                     round(pct["dispatch_p50_ms"], 1),
                     round(pct["dispatch_p99_ms"], 1),
                     round(ex["exec_p50_ms"], 1),
                     round(ex["exec_p99_ms"], 1),
                     len(eng.finished), s["store"]["demote_warm"],
                     s["store"]["demote_cold"],
                     s["policy"]["prefetch_hits"]])
        eng.pool.check()
        # retrace sentinel: the whole mixed-length stream must fit the
        # bucketed prefill compile bound (DESIGN.md 16)
        assert_compile_bound(f"tiers/{name}", eng.prefill_compiles(),
                             n_prompt_buckets(max_len, PAGE))
    print_table(
        f"serving_micro: fixed HBM budget = {hbm_budget // 1024} KiB "
        f"({budget_pages} bf16 pages), {n_req} requests",
        ["tier config", "hot_pg", "warm_pg", "resident_tok", "tok/s",
         "disp_p50", "disp_p99", "exec_p50", "exec_p99", "done",
         "dem_warm", "dem_cold", "pf_hit"], rows)
    return results


def run_backends(smoke: bool = False, seed: int = 0):
    """Per-backend tokens/s + latency, hot-only and with the warm tier in
    play.

    Every backend decodes the same greedy stream; hot-only outputs must
    agree token-for-token across backends (the equivalence bar the test
    matrix enforces -- re-checked here on live traffic).
    """
    cfg = reduced(ARCHS[ARCH])
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    plan = stack_plan(cfg)
    geom = PageGeometry(len(plan.pattern), plan.n_scan, cfg.n_kv_heads,
                        PAGE, cfg.head_dim)

    n_req = 4 if smoke else 8
    max_new = 4 if smoke else 8
    ticks = 6 if smoke else 16
    tiers = {
        # budget sized to the stream: an over-large budget allocates an
        # over-large hot pool, and pool size dominates CPU gather time
        "hot-only": dict(hbm_budget_bytes=24 * geom.hot_page_bytes,
                         enable_warm=False, enable_cold=False),
        # tight hot tier so parked requests actually demote to int8 pages
        "int8-warm": dict(hbm_budget_bytes=10 * geom.hot_page_bytes,
                          hot_fraction=0.5, enable_warm=True,
                          enable_cold=False),
    }
    results = {}
    rows = []
    outputs = {}
    for tier_name, tier_kw in tiers.items():
        for backend in attn_backend_names():
            rng = np.random.default_rng(seed)
            spec = AssistSpec(paged=True, page_size=PAGE,
                              attn_backend=backend,
                              use_roofline_trigger=False, **tier_kw)
            eng = _build(model, params, spec, lanes=2, max_len=48)
            for rid in range(n_req):
                eng.submit(Request(rid=rid,
                                   prompt=list(rng.integers(
                                       2, cfg.vocab_size,
                                       int(rng.integers(10, 25)))),
                                   max_new=max_new))
            eng.step()                       # admit + first decode (compile)
            tps, lats = _tick_window(eng, ticks)
            done = eng.run(max_ticks=2000)
            pct = _pcts(lats)
            ex = _exec_pcts(eng.stats())
            outputs[(tier_name, backend)] = {r.rid: tuple(r.out)
                                             for r in done}
            results[(tier_name, backend)] = {"tokens_per_s": tps,
                                             "finished": len(done),
                                             **pct, **ex}
            rows.append([tier_name, backend, round(tps, 1),
                         round(pct["dispatch_p50_ms"], 1),
                         round(pct["dispatch_p99_ms"], 1),
                         round(ex["exec_p50_ms"], 1), len(done)])
            eng.pool.check()
    print_table("serving_micro backends: tokens/s per attention backend "
                "(CPU interpret mode)",
                ["tier", "backend", "tok/s", "disp_p50", "disp_p99",
                 "exec_p50", "done"], rows)
    return results, outputs


def run_host_overhead(smoke: bool = False, seed: int = 0):
    """The host-overhead A/B (ISSUE 5 tentpole): mixed-length prompts --
    the retrace killer -- served once by the pre-PR loop (``host_sync``:
    exact-length prefill retracing per distinct prompt length, blocking
    per-tick readback, full block-table rebuild, single-page movers) and
    once by the host-sync-free loop (bucketed prefill, fused on-device
    sampling, lagged harvest, dirty-row updates, batched movers).

    Reports end-to-end tokens/s, decode-tick p50/p95/p99 and the prefill
    compile count per mode.  Acceptance bar: >= 1.5x end-to-end tokens/s
    (recompile elimination dominates) and the bucketed path compiles at
    most ``n_prompt_buckets`` prefill variants.
    """
    from repro.models.model import n_prompt_buckets
    from repro.models.transformer import paged_geometry
    cfg = reduced(ARCHS[ARCH])
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    max_len, lanes = 128, 2
    n_req = 14 if smoke else 20
    max_new = 4 if smoke else 6
    # >= 12 distinct prompt lengths spanning several buckets
    lens = [9 + 7 * (i % 14) for i in range(n_req)]
    assert len(set(lens)) >= min(12, n_req)
    # budget sized to the stream (run_backends note: an over-large budget
    # allocates an over-large hot pool, and pool size dominates CPU gather
    # time); later requests admit as earlier ones retire
    geom = paged_geometry(cfg, PAGE)
    tier = TierConfig(page_size=PAGE,
                      hbm_budget_bytes=40 * geom.hot_page_bytes,
                      enable_warm=False, enable_cold=False)

    results = {}
    rows = []
    for mode, host_sync in (("host-sync", True), ("async", False)):
        rng = np.random.default_rng(seed)
        # the host-sync arm keeps the guard OFF: its loop syncs on purpose
        # (the A/B baseline), and the guard would fail it by design
        obs = Observability(_obs_spec()) \
            if STRICT_TRANSFERS and not host_sync else None
        eng = PagedEngine(model, params, lanes=lanes, max_len=max_len,
                          tier=tier, eos_id=0, use_roofline_trigger=False,
                          host_sync=host_sync, obs=obs)
        for rid, plen in enumerate(lens):
            eng.submit(Request(rid=rid,
                               prompt=list(rng.integers(2, cfg.vocab_size,
                                                        plen)),
                               max_new=max_new))
        eng.sync()
        t0 = time.time()
        lats = []
        while (eng.queue or eng.resident or eng._inflight is not None
               or eng._pending_first):
            t1 = time.time()
            if not eng.step():
                break
            lats.append(time.time() - t1)
        eng.sync()
        dt = time.time() - t0
        pct = _pcts(lats)
        ex = _exec_pcts(eng.stats())
        compiles = eng.prefill_compiles()
        tps = eng.tokens_generated / max(dt, 1e-9)
        results[mode] = {"tokens_per_s": tps, "wall_s": dt,
                         "prefill_compiles": compiles,
                         "finished": len(eng.finished), **pct, **ex}
        rows.append([mode, round(tps, 1), round(dt, 2), compiles,
                     round(pct["dispatch_p50_ms"], 1),
                     round(pct["dispatch_p99_ms"], 1),
                     round(ex["exec_p50_ms"], 1),
                     round(ex["exec_p99_ms"], 1), len(eng.finished)])
        eng.pool.check()
    print_table(
        f"serving_micro host overhead: {n_req} requests, "
        f"{len(set(lens))} distinct prompt lengths, max_len={max_len}",
        ["decode loop", "tok/s", "wall_s", "prefill_jits", "disp_p50",
         "disp_p99", "exec_p50", "exec_p99", "done"], rows)
    # the execution probe's bar on the ASYNC loop: a fenced tick can never
    # finish before its own dispatch returns -- exec >= dispatch holds
    # per fenced tick, and so at p50 over the PAIRED samples (the
    # aggregate dispatch_* percentiles cover every tick, fenced or not,
    # so comparing those two sample sets directly could cross)
    pairs = eng.obs.probe.fenced_pairs()  # eng = the async-mode engine
    if pairs:
        assert all(e >= d for d, e in pairs), pairs
        d50 = float(np.percentile([d for d, _ in pairs], 50)) * 1e3
        e50 = float(np.percentile([e for _, e in pairs], 50)) * 1e3
        assert e50 >= d50, (d50, e50)
        results["async"]["exec_p50_over_dispatch_p50_fenced"] = \
            e50 / max(d50, 1e-9)
        results["async"]["probe_ok"] = True
    speedup = (results["async"]["tokens_per_s"]
               / max(results["host-sync"]["tokens_per_s"], 1e-9))
    results["speedup"] = speedup
    results["n_buckets"] = n_prompt_buckets(max_len, PAGE)
    assert results["async"]["finished"] == results["host-sync"]["finished"]
    # retrace sentinel: the async path compiles at most one prefill per
    # bucket (>= 12 distinct prompt lengths above map into n_buckets)
    assert_compile_bound("host_overhead/async",
                         results["async"]["prefill_compiles"],
                         results["n_buckets"])
    return results


def run_local_window(smoke: bool = False, seed: int = 0):
    """A local-attention-window model end-to-end through the paged path
    (per-layer capability dispatch: attn + attn_local segments)."""
    import dataclasses
    cfg = dataclasses.replace(reduced(ARCHS[ARCH]), name="qwen2-local",
                              n_layers=4,
                              block_pattern=("attn", "attn_local"), window=8)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    plan = stack_plan(cfg)
    geom = PageGeometry(len(plan.pattern), plan.n_scan, cfg.n_kv_heads,
                        PAGE, cfg.head_dim)
    spec = AssistSpec(paged=True, page_size=PAGE,
                      hbm_budget_bytes=16 * geom.hot_page_bytes,
                      enable_warm=False, enable_cold=False,
                      attn_backend="pallas_int8",
                      use_roofline_trigger=False)
    n_req = 3 if smoke else 6
    rng = np.random.default_rng(seed)
    eng = _build(model, params, spec, lanes=2, max_len=48)
    for rid in range(n_req):
        eng.submit(Request(rid=rid,
                           prompt=list(rng.integers(2, cfg.vocab_size,
                                                    int(rng.integers(10, 25)))),
                           max_new=4 if smoke else 6))
    done = eng.run(max_ticks=2000)
    eng.pool.check()
    assert len(done) == n_req, (len(done), n_req)
    print(f"[serving_micro] local-window PASS: {n_req} requests decoded "
          f"through the paged path (attn+attn_local, pallas_int8 backend)")
    return done


def _capacity_run(arch: str, spec: AssistSpec, lanes: int, max_len: int,
                  n_req: int, model, params, cfg, seed: int = 0):
    """Admit a stream and probe resident-token capacity + completion."""
    rng = np.random.default_rng(seed)
    eng = _build_arch(arch, model, params, spec, lanes, max_len)
    lens = []
    for rid in range(n_req):
        plen = int(rng.integers(18, 33))
        lens.append(plen)
        eng.submit(Request(rid=rid,
                           prompt=list(rng.integers(2, cfg.vocab_size, plen)),
                           max_new=4))
    eng.step()                          # one tick admits all the budget allows
    capacity = eng.resident_tokens()
    done = eng.run(max_ticks=3000)
    eng.pool.check()
    return capacity, len(done), float(np.mean(lens))


def _build_arch(arch, model, params, spec, lanes, max_len):
    scfg = ServeConfig(arch=arch, reduced=True, slots=lanes,
                       max_len=max_len, assist=spec, obs=_obs_spec())
    eng, _, _ = scfg.build(model, params)
    return eng


def run_page_kinds(smoke: bool = False, seed: int = 0):
    """Resident-token capacity for the NEW page kinds (ISSUE 4): one MLA
    config (latent pages) and one hybrid (SSM state parking), tiered vs
    the bf16 DENSE-SLAB baseline under the same HBM budget.

    The dense-slab baseline is the dense engine's storage model: every
    admitted request owns a full ``[max_len]`` bf16 slab (plus its f32
    recurrence state for hybrids) regardless of its actual length --
    capacity = floor(budget / slab_bytes) * mean resident length.  The
    tiered paged engine must hold >= 2x that (MLA: the acceptance bar).
    """
    from repro.models.transformer import paged_geometry
    max_len, lanes = 48, 2
    # the stream must OVERSUBSCRIBE the budget, or capacity saturates at
    # the stream size and the ratio measures nothing
    n_req = 28 if smoke else 56
    rows, results = [], {}
    for arch_id, label in (("deepseek-v2-lite-16b", "mla-latent"),
                           ("zamba2-1.2b", "hybrid-state")):
        cfg = reduced(ARCHS[arch_id])
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        geom = paged_geometry(cfg, PAGE)
        per_tok = geom.hot_page_bytes / PAGE
        budget_pages = 8 if smoke else 16
        budget = int(budget_pages * max_len * per_tok
                     + 6 * geom.state_hot_bytes)
        spec = AssistSpec(paged=True, page_size=PAGE,
                          hbm_budget_bytes=budget, hot_fraction=0.5,
                          enable_warm=True, enable_cold=True,
                          host_budget_bytes=budget,
                          use_roofline_trigger=False)
        capacity, finished, mean_len = _capacity_run(
            arch_id, spec, lanes, max_len, n_req, model, params, cfg,
            seed=seed)
        slab_bytes = max_len * per_tok + geom.state_hot_bytes
        dense_slots = int(budget // slab_bytes)
        dense_capacity = dense_slots * mean_len
        ratio = capacity / max(dense_capacity, 1.0)
        results[label] = {"capacity": capacity,
                          "dense_slab_capacity": dense_capacity,
                          "ratio": ratio, "finished": finished}
        rows.append([label, cfg.name, budget // 1024, capacity,
                     round(dense_capacity), round(ratio, 2), finished])
    print_table(
        "serving_micro page kinds: tiered resident-token capacity vs bf16 "
        "dense slabs (same HBM budget)",
        ["page kind", "arch", "budget_KiB", "resident_tok",
         "dense_slab_tok", "ratio", "done"], rows)
    return results


def run_prefix_reuse(smoke: bool = False, seed: int = 0):
    """Zipfian shared-prompt workload through the radix prefix store
    (ISSUE 7): a few popular prompt headers, Zipf-weighted, each request
    a header plus a short unique tail (sometimes no tail at all -- the
    full-prefill-skip case).  The same stream and HBM budget run with
    ``prefix_reuse`` off and on; the store must buy >= 1.5x the resident
    LOGICAL tokens (shared pages count once physically, once per reader
    logically) and a nonzero prefill-skip rate, with every request still
    completing and the pool conserving at drain.
    """
    cfg = reduced(ARCHS[ARCH])
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    plan = stack_plan(cfg)
    geom = PageGeometry(len(plan.pattern), plan.n_scan, cfg.n_kv_heads,
                        PAGE, cfg.head_dim)
    budget = (16 if smoke else 24) * geom.hot_page_bytes
    n_req = 20 if smoke else 40
    rng = np.random.default_rng(seed)
    # Zipf-popular headers: 3 full pages each, so a reused header costs
    # 3 shared page refs instead of 3 fresh pages
    headers = [list(rng.integers(2, cfg.vocab_size, 3 * PAGE))
               for _ in range(2 if smoke else 3)]
    weight = np.array([1 / (r + 1) ** 1.1 for r in range(len(headers))])
    weight /= weight.sum()
    prompts = []
    for rid in range(n_req):
        h = headers[int(rng.choice(len(headers), p=weight))]
        tail = int(rng.integers(0, 9))      # 0 => exact header: full skip
        prompts.append(h + list(rng.integers(2, cfg.vocab_size, tail)))

    results, rows = {}, []
    for label, enabled in (("disabled", False), ("enabled", True)):
        spec = AssistSpec(paged=True, page_size=PAGE,
                          hbm_budget_bytes=budget,
                          enable_warm=False, enable_cold=False,
                          use_roofline_trigger=False,
                          prefix_reuse=enabled, prefix_min_pages=1)
        eng = _build(model, params, spec, lanes=4, max_len=96)
        for rid, p in enumerate(prompts):
            eng.submit(Request(rid=rid, prompt=p, max_new=4))
        eng.step()                      # one tick admits all the budget allows
        capacity = eng.resident_tokens()
        done = eng.run(max_ticks=3000)
        pstats = eng.stats()["prefix"] or {}
        if enabled:
            eng.drop_prefix_cache()
        eng.pool.check()
        skips = pstats.get("prefill_skips", 0)
        results[label] = {
            "capacity": capacity,
            "peak_resident_tokens": eng.peak_resident_tokens,
            "finished": len(done),
            "prefill_skips": skips,
            "skip_rate": skips / n_req,
            "skipped_tokens": pstats.get("skipped_tokens", 0),
            "shared_pages": pstats.get("shared_pages", 0),
            "cow_pages": eng.pool.stats.cow,
        }
        rows.append([label, capacity, eng.peak_resident_tokens, skips,
                     pstats.get("shared_pages", 0), eng.pool.stats.cow,
                     len(done)])
    ratio = (results["enabled"]["peak_resident_tokens"]
             / max(results["disabled"]["peak_resident_tokens"], 1))
    results["capacity_ratio"] = ratio
    print_table(
        "serving_micro prefix reuse: Zipf shared-prompt stream, one HBM "
        "budget, prefix store off vs on",
        ["prefix store", "tok@1st tick", "peak_resident_tok",
         "prefill_skips", "shared_pages", "cow_pages", "done"], rows)
    print(f"  logical resident-token capacity ratio: {ratio:.2f}x")
    return results


def run_sessions(smoke: bool = False, seed: int = 0):
    """Multi-turn sessions under trace-driven load (ISSUE 8 tentpole):
    the SAME deterministic trace (repro.sessions.loadgen -- seeded
    arrivals, Zipfian shared headers, heavy-tailed turn gaps) served in
    two modes over one tiered budget:

      park       conversations park between turns (pages pushed down the
                 tier ladder in one batched episode, predictively
                 re-promoted before the next turn) and resume WITHOUT
                 re-prefilling history -- only unseen tokens replay
                 through the decode step
      reprefill  the stateless baseline: every turn re-prefills the full
                 accumulated history

    Reports GOODPUT UNDER SLO per latency class (turns whose last token
    lands within the class budget of the turn becoming ready), not just
    tokens/s.  Asserts the resume-without-reprefill bar: >= 1 session
    resumes by replay, and park mode prefills strictly fewer prompt
    tokens than the baseline.
    """
    from repro.sessions import SessionManager, SessionSpec, make_trace
    from repro.sessions.spec import SLOClass
    cfg = reduced(ARCHS[ARCH])
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    plan = stack_plan(cfg)
    geom = PageGeometry(len(plan.pattern), plan.n_scan, cfg.n_kv_heads,
                        PAGE, cfg.head_dim)
    budget = (16 if smoke else 24) * geom.hot_page_bytes
    max_len, lanes = 96, 2
    n_sessions = 4 if smoke else 10
    traces = make_trace(n_sessions=n_sessions, seed=seed,
                        vocab_size=cfg.vocab_size, page_size=PAGE,
                        max_len=max_len, mean_turns=2.5,
                        turn_tokens=(6, 14), max_new=4 if smoke else 6,
                        n_prefixes=2, arrival_rate=0.5,
                        gap_mean=3.0, gap_cap=10 if smoke else 20)
    n_turns = sum(len(t.turns) for t in traces)
    # wide-but-real budgets for the toy CPU model: interactive turns must
    # land an order of magnitude faster than batch is allowed to
    classes = (SLOClass("interactive", priority=0, turn_budget_ticks=40),
               SLOClass("batch", priority=1, turn_budget_ticks=400))
    aspec = AssistSpec(paged=True, page_size=PAGE, hbm_budget_bytes=budget,
                       hot_fraction=0.5, enable_warm=True, enable_cold=True,
                       host_budget_bytes=budget, use_roofline_trigger=False)
    results, rows = {}, []
    for mode, park in (("park", True), ("reprefill", False)):
        # "replay" pins the resume decision so the asserted bar measures
        # the mechanism; the "auto" cost rule is exercised in tests
        sspec = SessionSpec(park=park, resume_policy="replay",
                            classes=classes)
        scfg = ServeConfig(arch=ARCH, reduced=True, slots=lanes,
                           max_len=max_len, eos_id=0, assist=aspec,
                           sessions=sspec, obs=_obs_spec())
        eng, _, _ = scfg.build(model, params)
        mgr = SessionManager(eng, scfg.session_spec(), traces)
        eng.sync()
        t0 = time.time()
        rep = mgr.run(max_ticks=800 if smoke else 3000)
        eng.sync()
        dt = time.time() - t0
        assert mgr.done(), f"{mode}: sessions did not finish " \
            f"({[s.state for s in mgr.sessions]})"
        eng.pool.check()
        rep["tokens_per_s"] = eng.tokens_generated / max(dt, 1e-9)
        results[mode] = rep
        for cname, c in rep["per_class"].items():
            rows.append([mode, cname, c["turns"], c["turns_ok"],
                         c["slo_violations"], c["budget_ticks"],
                         c["p95_latency_ticks"],
                         rep["resumes_replay"], rep["resumes_reprefill"],
                         rep["replayed_tokens"],
                         rep["prefilled_prompt_tokens"]])
    print_table(
        f"serving_micro sessions: {n_sessions} sessions / {n_turns} turns, "
        f"trace seed={seed}, park-and-resume vs stateless re-prefill",
        ["mode", "class", "turns", "ok", "viol", "budget_tk", "p95_tk",
         "res_replay", "res_reprefill", "replayed_tok", "prefilled_tok"],
        rows)
    # acceptance bars (ISSUE 8): >= 1 session resumed WITHOUT re-prefill,
    # park mode prefilled strictly fewer prompt tokens than the stateless
    # baseline, and both modes completed every turn of every session
    park_r, base_r = results["park"], results["reprefill"]
    assert park_r["resumes_replay"] >= 1, park_r
    assert park_r["replayed_tokens"] > 0, park_r
    assert park_r["resumes_reprefill"] == 0, park_r
    assert base_r["resumes_replay"] == 0, base_r
    assert park_r["prefilled_prompt_tokens"] \
        < base_r["prefilled_prompt_tokens"], (park_r, base_r)
    for mode, rep in results.items():
        turns_done = sum(c["turns"] for c in rep["per_class"].values())
        assert turns_done == n_turns, (mode, turns_done, n_turns)
    print(f"[serving_micro] sessions PASS: {park_r['resumes_replay']} "
          f"replay resumes (0 re-prefills) in park mode; prompt tokens "
          f"prefilled {base_r['prefilled_prompt_tokens']} -> "
          f"{park_r['prefilled_prompt_tokens']}; goodput "
          + ", ".join(f"{c}={rep['goodput_frac']:.2f}"
                      if (rep := park_r['per_class'][c])['turns'] else
                      f"{c}=n/a"
                      for c in park_r['per_class']))
    return results


def run_trace(log_dir: str, smoke: bool = True, seed: int = 0):
    """Decode one tiered scenario under ``jax.profiler.trace(log_dir)``:
    the engine's ``engine.*`` spans and the device's programs land on one
    timeline (a Perfetto trace beside the ``.xplane.pb``; open it at
    https://ui.perfetto.dev).

    Returns the number of ``engine.step`` spans the trace holds, one per
    tick (benchmarks/run.py --trace).
    """
    from repro.obs.trace import read_spans
    cfg = reduced(ARCHS[ARCH])
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    plan = stack_plan(cfg)
    geom = PageGeometry(len(plan.pattern), plan.n_scan, cfg.n_kv_heads,
                        PAGE, cfg.head_dim)
    spec = AssistSpec(paged=True, page_size=PAGE,
                      hbm_budget_bytes=12 * geom.hot_page_bytes,
                      hot_fraction=0.5, enable_warm=True, enable_cold=False,
                      use_roofline_trigger=False)
    scfg = ServeConfig(arch=ARCH, reduced=True, slots=2, max_len=48,
                       eos_id=0, assist=spec, obs=_obs_spec())
    eng, _, _ = scfg.build(model, params)
    rng = np.random.default_rng(seed)
    n_req = 6 if smoke else 16
    for rid in range(n_req):
        eng.submit(Request(rid=rid,
                           prompt=list(rng.integers(2, cfg.vocab_size,
                                                    int(rng.integers(18, 33)))),
                           max_new=4 if smoke else 8))
    with jax.profiler.trace(log_dir, create_perfetto_trace=True):
        eng.run(max_ticks=2000)
    n_steps = sum(e[0] == "engine.step" for e in read_spans(log_dir))
    if n_steps != eng.tick_no:
        raise AssertionError(f"{n_steps} engine.step spans for "
                             f"{eng.tick_no} ticks")
    print(f"[serving_micro] trace PASS: {n_steps} engine.step spans -> "
          f"{log_dir}")
    return n_steps


def run_chaos(smoke: bool = False, seed: int = 0):
    """Seeded fault storm + kill/restore (ISSUE 10, DESIGN.md 17).

    Leg 1 -- fault storm.  One tiered engine with a bounded admission
    queue serves a class-tagged burst while a ``FaultSpec`` storm window
    injects mover dispatch failures (bounded retry + backoff -- the
    sleeps inflate tick latency past the watchdog threshold, tripping
    the degraded plan), cold-page corruption (checksum quarantine),
    allocator exhaustion (admission retried) and NaN logits (quarantine).
    A fault-free twin decodes the same stream; the invariants:

      * zero cross-request corruption: every request that finishes
        WITHOUT an error status is token-identical to the twin's;
      * sheds are exclusively the lowest SLO class (interactive last);
      * goodput floor: healthy completions stay above a fraction of the
        submitted burst despite the storm;
      * hysteresis: the watchdog trips during the storm AND recovers
        after it (both visible in counters, gauge back to 0).

    Leg 2 -- kill and restore.  A parked multi-turn session is persisted
    (atomic snapshot), "killed" (a fresh engine is built), restored, and
    resumed; its second turn must be token-identical to an engine that
    was never killed.  Page-kind coverage (mla_latent / state_slab) for
    the same round trip lives in tests/test_resilience.py.
    """
    import os
    import tempfile
    from repro.serving.resilience import FaultInjector, FaultSpec

    cfg = reduced(ARCHS[ARCH])
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    plan = stack_plan(cfg)
    geom = PageGeometry(len(plan.pattern), plan.n_scan, cfg.n_kv_heads,
                        PAGE, cfg.head_dim)
    # GENEROUS budget: the active burst never demotes, so a healthy
    # request's output is scheduling-independent (hot pages are bf16 and
    # exact; only the int8 warm edge is lossy, and only explicitly
    # parked sessions cross it -- identically in both engines)
    budget = 48 * geom.hot_page_bytes
    max_len, lanes = 48, 2
    n_req = 12 if smoke else 16
    max_queue = n_req // 2
    max_new = 4 if smoke else 6
    n_sess = 3
    aspec = AssistSpec(paged=True, page_size=PAGE, hbm_budget_bytes=budget,
                       hot_fraction=0.5, enable_warm=True, enable_cold=True,
                       host_budget_bytes=budget, use_roofline_trigger=False)

    rng = np.random.default_rng(seed)
    sess_prompts = [[int(t) for t in rng.integers(2, cfg.vocab_size, 24)]
                    for _ in range(n_sess)]
    sess_turn2 = [[int(t) for t in rng.integers(2, cfg.vocab_size, 6)]
                  for _ in range(n_sess)]
    stream = []
    for rid in range(n_req):
        cls = "interactive" if rid % 4 == 0 else "batch"
        plen = int(rng.integers(18, 33))
        stream.append((rid, [int(t) for t in
                             rng.integers(2, cfg.vocab_size, plen)], cls))

    def _drain(e):
        # run() can break early on a tick where every lane is empty AND
        # the storm blocks the one admission it tried -- keep driving
        for _ in range(50):
            e.run(max_ticks=3000)
            if not (e.queue or e.resident or e._inflight is not None
                    or e._pending_first):
                break

    def _setup(e):
        """Identical pre-storm history for chaos engine and twin: park
        ``n_sess`` sessions to the cold tier (the checksum targets),
        then submit the class-tagged burst (intake sheds are decided
        here, deterministically) and admit it fully."""
        hist, hlen = {}, {}
        for k in range(n_sess):
            srid = 1000 + k
            r = Request(rid=srid, prompt=sess_prompts[k], max_new=max_new)
            e.submit(r)
            e.park_on_retire(srid)
            _drain(e)
            hist[srid] = list(sess_prompts[k]) + list(r.out)
            hlen[srid] = e.parked_session_len(srid)
            e.park_session_pages(srid)
        for rid, prompt, cls in stream:
            e.submit(Request(rid=rid, prompt=prompt, max_new=max_new,
                             cls=cls))
        for _ in range(3):          # admit every survivor pre-storm
            e.step()
        return hist, hlen

    def _resume(e, srid, hist, hlen, k):
        r2 = Request(rid=srid, prompt=hist + sess_turn2[k],
                     max_new=max_new)
        e.resume_session(r2, hist[hlen:] + sess_turn2[k])
        _drain(e)
        return r2

    scfg = ServeConfig(arch=ARCH, reduced=True, slots=lanes,
                       max_len=max_len, eos_id=0, assist=aspec,
                       max_queue=max_queue, obs=_obs_spec())

    # fault-free twin: the expected outputs of every healthy request
    twin, _, _ = scfg.build(model, params)
    t_hist, t_hlen = _setup(twin)
    _drain(twin)
    twin_out = {r.rid: tuple(r.out) for r in twin.finished
                if r.error is None}
    twin_shed = {r.rid for r in twin.finished if r.error == "shed"}
    twin_sess = {srid: tuple(_resume(twin, srid, t_hist[srid],
                                     t_hlen[srid], k).out)
                 for k, srid in enumerate(sorted(t_hist))}
    twin.pool.check()

    # chaos engine: identical setup, then a 7-tick storm window opens
    eng, _, _ = scfg.build(model, params)
    hist, hlen = _setup(eng)
    assert hlen == t_hlen
    t0 = eng.tick_no
    # backoff_base_s must make one storm tick's retry sleeps
    # (base * (1+2+4) = 7*base) exceed the watchdog's 10 s latency
    # threshold, or the storm never trips the degraded plan
    eng.fault = FaultInjector(
        FaultSpec(seed=seed, mover_fail_rate=1.0, corrupt_rate=0.5,
                  alloc_fail_rate=0.5, nan_rate=0.2, max_retries=3,
                  backoff_base_s=1.6, from_tick=t0, until_tick=t0 + 7),
        metrics=eng.obs.metrics)
    _drain(eng)
    # recovery tail: idle ticks are cheap and feed the watchdog
    for _ in range(16):
        eng.step()
    # resume the parked sessions: corrupted cold pages are DETECTED here
    # (checksum on promotion) and quarantined; clean sessions must match
    sess_reqs = {srid: _resume(eng, srid, hist[srid], hlen[srid], k)
                 for k, srid in enumerate(sorted(hist))}
    eng.pool.check()

    gv = eng.obs.metrics.get_value
    done = {r.rid: r for r in eng.finished if 0 <= r.rid < n_req}
    healthy = {rid: r for rid, r in done.items() if r.error is None}
    shed = [r for r in done.values() if r.error == "shed"]
    quar = ([r for r in done.values() if r.error in ("checksum", "nan")]
            + [r for r in sess_reqs.values() if r.error is not None])
    assert len(done) == n_req, (len(done), n_req)
    assert {r.rid for r in shed} == twin_shed, "shed set diverged"
    for rid, r in healthy.items():
        assert tuple(r.out) == twin_out[rid], \
            f"rid {rid}: healthy output changed under the fault storm"
    for srid, r in sess_reqs.items():
        if r.error is None:
            assert tuple(r.out) == twin_sess[srid], \
                f"session {srid}: healthy resume changed under the storm"
    assert shed and all(r.cls == "batch" for r in shed), \
        f"shed set not exclusively the lowest SLO class: " \
        f"{[(r.rid, r.cls) for r in shed]}"
    floor = 0.25
    goodput = len(healthy) / n_req
    assert goodput >= floor, (goodput, floor)
    trips = gv("engine_watchdog_trips_total", reason="latency") or 0
    recovers = gv("engine_watchdog_recoveries_total") or 0
    injected = sum(gv("engine_faults_injected_total", site=s) or 0
                   for s in ("mover", "cold_payload", "alloc", "nan"))
    assert injected > 0, "storm injected nothing"
    assert trips >= 1, "watchdog never tripped under the storm"
    assert recovers >= 1, "watchdog never recovered after the storm"
    assert (gv("engine_degraded") or 0) == 0, "still degraded at drain"
    assert len(quar) >= 1, "no quarantine despite corrupt/nan injection"

    # -- leg 2: kill between ticks, restore, resume ---------------------
    def _session_engine():
        e, _, _ = ServeConfig(arch=ARCH, reduced=True, slots=lanes,
                              max_len=96, eos_id=0, assist=aspec,
                              obs=_obs_spec()).build(model, params)
        return e
    t1 = [int(t) for t in rng.integers(2, cfg.vocab_size, 20)]
    t2 = [int(t) for t in rng.integers(2, cfg.vocab_size, 6)]

    def _first_turn(e):
        r = Request(rid=0, prompt=t1, max_new=4)
        e.submit(r)
        e.park_on_retire(0)
        e.run(max_ticks=2000)
        # park to COLD on both sides: persist parks hot pages down the
        # ladder anyway (the durable payload is the int8-lossy cold
        # representation), so the uninterrupted baseline must pay the
        # same quantization for token identity to be well-defined
        e.park_session_pages(0)
        return t1 + r.out, e.parked_session_len(0)

    live = _session_engine()
    hist, hlen = _first_turn(live)

    killed = _session_engine()
    hist_k, _ = _first_turn(killed)
    assert hist_k == hist
    path = os.path.join(tempfile.mkdtemp(prefix="chaos_store_"), "snap")
    killed.persist(path)            # ... process dies here ...
    restored = _session_engine()    # fresh process, same config
    restored.restore(path)
    assert restored.parked_session_len(0) == hlen

    outs = []
    for e in (live, restored):
        r2 = Request(rid=0, prompt=hist + t2, max_new=4)
        e.resume_session(r2, hist[hlen:] + t2)
        e.run(max_ticks=2000)
        outs.append(tuple(r2.out))
        e.pool.check()
    assert outs[0] == outs[1], \
        "restored session diverged from the uninterrupted one"

    print_table(
        f"serving_micro chaos: {n_req} requests, storm ticks "
        f"{t0}..{t0 + 6}, max_queue={max_queue}",
        ["healthy", "shed", "quarantined", "goodput", "injected",
         "trips", "recoveries"],
        [[len(healthy), len(shed), len(quar), round(goodput, 2),
          int(injected), int(trips), int(recovers)]])
    print(f"[serving_micro] chaos PASS: {len(healthy)} healthy outputs "
          f"identical under the storm, {len(shed)} shed (all batch), "
          f"{len(quar)} quarantined, watchdog tripped and recovered; "
          f"kill+restore resume token-identical")
    return {"healthy": len(healthy), "shed": len(shed),
            "quarantined": len(quar), "goodput": goodput,
            "faults_injected": int(injected), "watchdog_trips": int(trips),
            "watchdog_recoveries": int(recovers),
            "restore_token_identical": True}


def main(smoke: bool = False, seed: int = 0,
         strict_transfers: bool = False):
    global STRICT_TRANSFERS
    STRICT_TRANSFERS = bool(strict_transfers)
    if STRICT_TRANSFERS:
        print("[serving_micro] strict transfers ON: tick dispatches run "
              "under jax.transfer_guard('disallow')")
    res = run(smoke=smoke, seed=seed)
    hot = res["hot-only"]["capacity"]
    warm = res["hot+warm"]["capacity"]
    cold = res["hot+warm+cold"]["capacity"]
    # capacity bar: tiers buy >= 2x resident tokens for the same HBM
    assert warm > hot, (hot, warm)
    assert cold >= 2 * hot, (hot, cold)
    # correctness bar: nothing is rejected or lost in any config
    finished = {r["finished"] for r in res.values()}
    assert len(finished) == 1, "configs finished different request counts"
    print(f"\n[serving_micro] PASS: capacity {hot} -> {warm} (warm) -> "
          f"{cold} (cold) resident tokens under one HBM budget "
          f"({cold / hot:.2f}x >= 2x)")

    overhead = run_host_overhead(smoke=smoke, seed=seed)
    # acceptance bar (ISSUE 5): the host-sync-free loop beats the pre-PR
    # loop >= 1.5x end-to-end on the mixed-length stream (recompile
    # elimination dominates) with the bucketed compile count bounded
    assert overhead["speedup"] >= 1.5, overhead
    print(f"[serving_micro] host overhead PASS: "
          f"{overhead['speedup']:.2f}x >= 1.5x tokens/s over the pre-PR "
          f"loop; prefill compiles "
          f"{overhead['host-sync']['prefill_compiles']} -> "
          f"{overhead['async']['prefill_compiles']} "
          f"(<= {overhead['n_buckets']} buckets)")

    bres, bouts = run_backends(smoke=smoke, seed=seed)
    backends = attn_backend_names()
    # equivalence bar on live traffic: hot-only greedy outputs identical
    ref = bouts[("hot-only", backends[0])]
    for be in backends[1:]:
        assert bouts[("hot-only", be)] == ref, \
            f"hot-only outputs diverge: {backends[0]} vs {be}"
    # warm mode: all backends complete the same request set
    done = {bres[("int8-warm", be)]["finished"] for be in backends}
    assert len(done) == 1, f"warm-mode finished counts diverge: {done}"
    print(f"[serving_micro] backends PASS: {', '.join(backends)} "
          f"token-identical hot-only, all complete with int8 warm")
    run_local_window(smoke=smoke, seed=seed)
    kinds = run_page_kinds(smoke=smoke, seed=seed)
    # acceptance bar (ISSUE 4): the tiered MLA config holds >= 2x the
    # resident tokens of bf16 dense slabs under the same HBM budget, and
    # every admitted request completes for both new page kinds
    mla = kinds["mla-latent"]
    assert mla["ratio"] >= 2.0, mla
    for label, r in kinds.items():
        assert r["finished"] > 0, (label, r)
    print(f"[serving_micro] page kinds PASS: MLA latent pages hold "
          f"{mla['ratio']:.2f}x >= 2x the dense-slab resident tokens; "
          f"hybrid state parking ratio "
          f"{kinds['hybrid-state']['ratio']:.2f}x")
    prefix = run_prefix_reuse(smoke=smoke, seed=seed)
    # acceptance bar (ISSUE 7): the prefix store buys >= 1.5x resident
    # logical tokens on the Zipf shared-prompt stream with a nonzero
    # prefill-skip rate, and every request completes in both configs
    assert prefix["capacity_ratio"] >= 1.5, prefix
    assert prefix["enabled"]["prefill_skips"] > 0, prefix
    assert prefix["enabled"]["finished"] == \
        prefix["disabled"]["finished"], prefix
    print(f"[serving_micro] prefix reuse PASS: "
          f"{prefix['capacity_ratio']:.2f}x >= 1.5x resident tokens, "
          f"{prefix['enabled']['prefill_skips']} prefill skips "
          f"({100 * prefix['enabled']['skip_rate']:.0f}% of admissions)")
    sessions = run_sessions(smoke=smoke, seed=seed)
    # one JSON-able record per section: benchmarks/run.py --json persists
    # this as BENCH_serving.json (the cross-PR perf trajectory)
    return {"tiers": res,
            "host_overhead": overhead,
            "backends": {f"{t}/{b}": v for (t, b), v in bres.items()},
            "page_kinds": kinds,
            "prefix_reuse": prefix,
            "sessions": sessions}


if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("scenario", nargs="?", default="all",
                    choices=["all", "run_chaos"],
                    help="'all' runs the full benchmark record; "
                         "'run_chaos' runs only the fault-storm + "
                         "kill/restore scenario (CI chaos smoke)")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--strict-transfers", action="store_true")
    a = ap.parse_args()
    if a.scenario == "run_chaos":
        STRICT_TRANSFERS = a.strict_transfers
        run_chaos(smoke=a.smoke, seed=a.seed)
    else:
        main(smoke=a.smoke, seed=a.seed, strict_transfers=a.strict_transfers)
