"""repro.obs: the telemetry spine (DESIGN.md 13).

Covers, in order: the registry substrate and its export formats; the
null-object disabled mode (overhead-free hot path); the execution-true
tick probe; counter CONSERVATION on a live tiered engine (flow-balance
invariants the registry must satisfy if the increments are placed right);
token identity with observability on vs off; and the engine's spans on
the profiler's clock with their per-phase counters and request stamps.
"""
import time

import numpy as np
import jax
import pytest

from repro.configs import ARCHS, reduced
from repro.models.model import build_model
from repro.obs import (MetricsRegistry, NULL_METRIC, NULL_REGISTRY, PHASES,
                       ObsSpec, Observability, TickProbe, log_buckets)
from repro.obs.export import prometheus_text, serve_metrics, snapshot
from repro.obs.trace import read_spans
from repro.serving.config import ServeConfig
from repro.serving.engine import Request


# -- registry substrate ------------------------------------------------------

def test_registry_basics():
    m = MetricsRegistry()
    c = m.counter("requests_total", "reqs", route="a")
    c.inc()
    c.inc(3)
    # same (name, labels) -> same handle (shared series)
    assert m.counter("requests_total", route="a") is c
    assert m.get_value("requests_total", route="a") == 4
    assert m.get_value("requests_total", route="b") is None
    g = m.gauge("depth")
    g.set(7)
    g.dec(2)
    g.set_max(3)          # below current value: no-op
    assert m.get_value("depth") == 5
    h = m.histogram("lat_seconds", buckets=log_buckets(1e-3, 1.0))
    for v in (0.002, 0.02, 0.2, 5.0):
        h.observe(v)
    assert h.count == 4 and h.value == 4
    assert h.cumulative()[-1] == (float("inf"), 4)

    with pytest.raises(ValueError):
        m.gauge("requests_total")          # type clash on one name
    with pytest.raises(ValueError):
        m.counter("bad name")
    with pytest.raises(ValueError):
        m.counter("ok", **{"bad-label": 1})
    with pytest.raises(TypeError):
        c.set_max(9)                        # counters only increment


def test_prometheus_text_and_snapshot():
    m = MetricsRegistry()
    m.counter("tokens_total", "tokens out", engine="paged").inc(11)
    m.gauge("lanes_active").set(2)
    h = m.histogram("tick_seconds", buckets=(0.001, 0.01))
    h.observe(0.0005)
    h.observe(0.5)
    text = prometheus_text(m)
    assert '# TYPE tokens_total counter' in text
    assert 'tokens_total{engine="paged"} 11' in text
    assert "lanes_active 2" in text
    # histogram: cumulative buckets, +Inf, _sum/_count
    assert 'tick_seconds_bucket{le="0.001"} 1' in text
    assert 'tick_seconds_bucket{le="+Inf"} 2' in text
    assert "tick_seconds_count 2" in text
    snap = snapshot(m)
    assert snap["tokens_total"]["engine=paged"] == 11
    assert snap["tick_seconds"][""]["count"] == 2


def test_null_registry_is_inert():
    assert not NULL_REGISTRY.enabled
    c = NULL_REGISTRY.counter("x_total")
    assert c is NULL_METRIC
    assert c is NULL_REGISTRY.gauge("y") is NULL_REGISTRY.histogram("z")
    c.inc()
    c.observe(1.0)
    c.set(5)
    assert c.value == 0
    assert NULL_REGISTRY.families() == []
    assert NULL_REGISTRY.get_value("x_total") is None
    assert prometheus_text(NULL_REGISTRY) == ""


def test_metrics_endpoint():
    m = MetricsRegistry()
    m.counter("up_total").inc()
    srv = serve_metrics(0, registry=m)       # ephemeral port
    try:
        import urllib.request
        port = srv.server_address[1]
        body = urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics", timeout=5).read().decode()
        assert "up_total 1" in body
    finally:
        srv.shutdown()


# -- probe -------------------------------------------------------------------

def test_tick_probe_semantics():
    p = TickProbe(sample_every=4, window=16)
    assert p.percentiles()["dispatch_p50_ms"] == 0.0   # empty -> zeros
    for tick in range(8):
        p.record_dispatch(0.001)
        if p.should_fence(tick):
            p.record_exec(0.003)
    s = p.percentiles()
    assert s["exec_samples"] == 2                      # ticks 0 and 4
    assert s["exec_p50_ms"] >= s["dispatch_p50_ms"]
    assert s["dispatch_p50_ms"] == pytest.approx(1.0)
    assert s["exec_p50_ms"] == pytest.approx(3.0)
    # sample_every=0 disables fencing entirely
    p0 = TickProbe(sample_every=0)
    assert not any(p0.should_fence(t) for t in range(10))


# -- engine integration ------------------------------------------------------

@pytest.fixture(scope="module")
def served_model():
    cfg = reduced(ARCHS["qwen2-7b"])
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def _tiered_scfg(obs: ObsSpec, budget_pages: int = 12):
    """A paged config tight enough to exercise demote/promote/prefetch."""
    from repro.assist import AssistSpec
    from repro.cache import PageGeometry
    from repro.models.transformer import stack_plan
    cfg = reduced(ARCHS["qwen2-7b"])
    plan = stack_plan(cfg)
    geom = PageGeometry(len(plan.pattern), plan.n_scan, cfg.n_kv_heads,
                        16, cfg.head_dim)
    budget = budget_pages * geom.hot_page_bytes
    spec = AssistSpec(paged=True, page_size=16, hbm_budget_bytes=budget,
                      hot_fraction=0.5, enable_warm=True, enable_cold=True,
                      host_budget_bytes=budget)
    return ServeConfig(arch="qwen2-7b", reduced=True, slots=2, max_len=48,
                       eos_id=0, assist=spec, obs=obs)


def _run_stream(scfg, model, params, n_req=12, max_new=4, obs=None):
    eng, _, _ = scfg.build(model, params, obs=obs)
    rng = np.random.default_rng(0)
    for rid in range(n_req):
        eng.submit(Request(rid=rid,
                           prompt=list(rng.integers(2, 400,
                                                    int(rng.integers(18, 33)))),
                           max_new=max_new))
    done = eng.run(max_ticks=3000)
    eng.pool.check()
    return eng, done


@pytest.fixture(scope="module")
def tiered_run(served_model):
    """One oversubscribed tiered stream, shared by the counter tests."""
    cfg, model, params = served_model
    return _run_stream(_tiered_scfg(ObsSpec()), model, params, n_req=24)


def test_counter_conservation_tiered(tiered_run):
    """Flow balance on a live oversubscribed stream: every page that
    enters a tier leaves it or is still there; every prefetch issue
    resolves to exactly one outcome; the batched mover never carries more
    pages than dispatches x MOVER_BATCH."""
    from repro.cache.tiers import MOVER_BATCH
    eng, done = tiered_run
    assert len(done) == 24
    m = eng.obs.metrics

    def tot(name, **labels):
        return sum(m.get_value(name, cls=c, **labels) or 0
                   for c in ("kv", "state"))

    # warm tier: in = demote(hot->warm) + promote(cold->warm);
    # out = demote(warm->cold) + promote(warm->hot) + released@warm;
    # difference = pages still resident in warm
    warm_now = sum(len(s) for s in eng.store._warm_ids.values())
    assert (tot("cache_pages_demoted_total", to="warm")
            + tot("cache_pages_promoted_total", to="warm")) == \
        (tot("cache_pages_demoted_total", to="cold")
         + tot("cache_pages_promoted_total", to="hot")
         + tot("cache_pages_released_total", tier="warm") + warm_now)
    # cold tier: in = demote(warm->cold); out = promote(cold->warm) +
    # released@cold; difference = still-cold pages
    assert tot("cache_pages_demoted_total", to="cold") == \
        (tot("cache_pages_promoted_total", to="warm")
         + tot("cache_pages_released_total", tier="cold")
         + len(eng.store.cold))
    # the flow actually moved pages (else the invariants are vacuous)
    assert tot("cache_pages_demoted_total", to="warm") > 0
    assert tot("cache_pages_demoted_total", to="cold") > 0

    # pool: every allocated page was freed (stream fully drained)
    assert m.get_value("pool_pages_allocated_total") == \
        m.get_value("pool_pages_freed_total")
    assert m.get_value("pool_pages_in_use") == 0

    # prefetch: issued pages resolve to exactly one outcome
    gv = m.get_value
    issued = gv("prefetch_pages_total", outcome="issued") or 0
    resolved = sum(gv("prefetch_pages_total", outcome=o) or 0
                   for o in ("hit", "late", "wasted"))
    outstanding = len(eng.policy.prefetch._outstanding)
    assert issued == resolved + outstanding
    assert issued > 0

    # batched mover: pages carried per dispatch bounded by the batch size
    disp = gv("cache_mover_dispatches_total", kind="mover") or 0
    moved = gv("cache_mover_pages_total", kind="mover") or 0
    assert disp > 0 and moved > 0
    assert moved <= disp * MOVER_BATCH
    # the batch-occupancy histogram saw every mover dispatch
    h = m.histogram("cache_mover_batch_pages")
    assert h.count == disp and h.sum == moved

    # prefill bucket histogram: one observation per admission
    hb = m.histogram("engine_prefill_bucket_tokens")
    assert hb.count == (gv("engine_admissions_total") or 0) > 0

    # legacy dict views stay consistent with the registry
    s = eng.stats()
    assert s["store"]["demote_warm"] == tot("cache_pages_demoted_total",
                                            to="warm")
    assert s["policy"]["prefetch_hits"] == (gv("prefetch_pages_total",
                                               outcome="hit") or 0)


def test_controller_decisions_counted(tiered_run):
    eng, _ = tiered_run
    m = eng.obs.metrics
    decisions = sum(v for (name, typ, _, children) in m.families()
                    if name == "assist_decisions_total"
                    for _, metric in children for v in [metric.value])
    assert decisions > 0


def test_obs_disabled_is_overhead_free(served_model, monkeypatch):
    """ObsSpec.off(): no fence syncs from the probe, null metrics
    everywhere, and stats() still answers (with the probe keys absent)."""
    import repro.serving.paged_engine as pe
    cfg, model, params = served_model
    scfg = _tiered_scfg(ObsSpec.off())
    eng, _, _ = scfg.build(model, params)
    assert eng.obs.probe is None and eng.obs.tracer is None
    assert not eng.obs.metrics.enabled
    assert eng.store.metrics is eng.obs.metrics     # one registry threaded

    fences = []
    real = pe.jax.block_until_ready
    monkeypatch.setattr(pe.jax, "block_until_ready",
                        lambda x: (fences.append(1), real(x))[1])
    rng = np.random.default_rng(0)
    for rid in range(4):
        eng.submit(Request(rid=rid, prompt=list(rng.integers(2, 400, 12)),
                           max_new=3))
    for _ in range(6):
        eng.step()
    assert fences == []                 # the probe is the only step() fence
    s = eng.stats()
    assert "dispatch_p50_ms" not in s and "exec_p50_ms" not in s
    eng.run(max_ticks=2000)


def test_obs_enabled_fences_and_exec_dominates(served_model):
    """sample_every=1 fences every tick: exec >= dispatch per sample, so
    the percentiles order too (the serving_micro assertion, pinned here
    at tier-1 speed)."""
    cfg, model, params = served_model
    scfg = _tiered_scfg(ObsSpec(exec_sample_every=1))
    eng, done = _run_stream(scfg, model, params, n_req=6)
    s = eng.stats()
    assert s["exec_samples"] > 0
    assert s["exec_p50_ms"] >= s["dispatch_p50_ms"]
    assert s["exec_p95_ms"] >= s["dispatch_p95_ms"]
    # registry histograms saw the same samples
    m = eng.obs.metrics
    assert m.histogram("engine_tick_exec_seconds").count == \
        s["exec_samples"]


def _profiled(log_dir):
    """A profiler session that records host annotations but no Python
    frames (the CPU traces stay small)."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    return jax.profiler.trace(str(log_dir), profiler_options=opts)


def test_token_identity_obs_on_off(served_model, tmp_path):
    """Telemetry must be a pure observer: identical greedy streams with
    counters+probe on, everything off, and a profiler recording the
    engine's spans."""
    cfg, model, params = served_model
    outs = {}
    for key, spec in (("on", ObsSpec()), ("off", ObsSpec.off()),
                      ("trace", ObsSpec())):
        if key == "trace":
            with _profiled(tmp_path):
                eng, done = _run_stream(_tiered_scfg(spec), model, params,
                                        n_req=8, max_new=4)
            assert read_spans(tmp_path)
        else:
            eng, done = _run_stream(_tiered_scfg(spec), model, params,
                                    n_req=8, max_new=4)
        outs[key] = {r.rid: tuple(r.out) for r in done}
    assert outs["on"] == outs["off"] == outs["trace"]


# -- spans, phase counters, request stamps -------------------------------------

def _inside(ev, outer):
    return outer[1] <= ev[1] and ev[2] <= outer[2]


def test_engine_trace_spans(served_model, tmp_path):
    """Under a profiler session the engine's spans land on the host
    plane: one ``engine.step`` per tick, one ``engine.admit`` and one
    nested ``engine.prefill`` per prefilled request with ``rid`` /
    ``bucket`` stats, ``engine.harvest_wait`` nested in ``engine.harvest``
    in ``engine.step``, and a retire instant per request."""
    cfg, model, params = served_model
    eng, _, _ = _tiered_scfg(ObsSpec()).build(model, params)
    rng = np.random.default_rng(0)
    for rid in range(6):
        eng.submit(Request(rid=rid,
                           prompt=list(rng.integers(2, 400,
                                                    int(rng.integers(18, 33)))),
                           max_new=4))
    t0 = eng.tick_no
    with _profiled(tmp_path):
        done = eng.run(max_ticks=3000)
    assert len(done) == 6
    evs = read_spans(tmp_path)
    by = {}
    for ev in evs:
        by.setdefault(ev[0], []).append(ev)
    steps = by["engine.step"]
    assert len(steps) == eng.tick_no - t0
    assert [e[3]["tick"] for e in steps] == list(range(t0 + 1,
                                                       eng.tick_no + 1))
    admits, prefills = by["engine.admit"], by["engine.prefill"]
    assert sorted(e[3]["rid"] for e in admits) == list(range(6))
    assert sorted(e[3]["rid"] for e in prefills) == list(range(6))
    for pf in prefills:
        assert pf[3]["bucket"] % 16 == 0 and pf[3]["bucket"] >= 18
        (adm,) = [a for a in admits if a[3]["rid"] == pf[3]["rid"]]
        assert _inside(pf, adm)
        assert any(_inside(adm, st) for st in steps)
    for hw in by["engine.harvest_wait"]:
        assert any(_inside(hw, h) for h in by["engine.harvest"])
        assert any(_inside(hw, st) for st in steps)
    for name in ("engine.lanes", "engine.movers", "engine.dispatch",
                 "engine.harvest"):
        assert by[name] and all(any(_inside(e, st) for st in steps)
                                for e in by[name])
    assert sorted(e[3]["rid"] for e in by["engine.retire"]) == list(range(6))


def test_phase_counters_sum_within_step_total(served_model):
    """Nested phases count once, in the innermost span: the phases sum to
    at most the host time of the steps, and to nearly all of it."""
    cfg, model, params = served_model
    eng, _, _ = _tiered_scfg(ObsSpec()).build(model, params)
    rng = np.random.default_rng(1)
    for rid in range(8):
        eng.submit(Request(rid=rid,
                           prompt=list(rng.integers(2, 400,
                                                    int(rng.integers(18, 33)))),
                           max_new=4))
    wall = 0.0
    while (eng.queue or eng.resident or eng._inflight is not None
           or eng._pending_first):
        t = time.perf_counter()
        eng.step()
        wall += time.perf_counter() - t
    m = eng.obs.metrics
    per = {p: m.get_value("engine_phase_seconds_total", phase=p)
           for p in PHASES}
    assert all(v > 0 for v in per.values()), per
    assert 0.9 * wall <= sum(per.values()) <= wall


def test_phase_counters_untouched_when_obs_off(served_model, tmp_path):
    """ObsSpec.off(): no tracer, every span the shared no-op, no
    ``engine.*`` event even while a profiler records, and no counter."""
    from repro.obs.trace import no_span
    cfg, model, params = served_model
    with _profiled(tmp_path):
        eng, done = _run_stream(_tiered_scfg(ObsSpec.off()), model, params,
                                n_req=4, max_new=3)
    assert len(done) == 4
    assert eng.obs.tracer is None and eng._span is no_span
    assert eng.obs.metrics.get_value("engine_phase_seconds_total",
                                     phase="step") is None
    assert read_spans(tmp_path) == []


def test_request_timestamps_ordered(served_model):
    """``t_submit <= t_admit <= t_first`` for every finished request,
    the full-prefix-skip path included; the dense engine leaves them
    unset."""
    from repro.cache import TierConfig
    from repro.serving.paged_engine import PagedEngine
    cfg, model, params = served_model
    rng = np.random.default_rng(2)
    base = [int(t) for t in rng.integers(2, 400, 48)]
    eng = PagedEngine(model, params, lanes=2, max_len=96,
                      tier=TierConfig(page_size=16, hbm_budget_bytes=1 << 30,
                                      enable_warm=False, enable_cold=False),
                      use_roofline_trigger=False, prefix_reuse=True)
    eng.submit(Request(rid=0, prompt=base + [5, 6, 7], max_new=4))
    eng.run()
    eng.submit(Request(rid=1, prompt=base[:32], max_new=4))  # full skip
    eng.submit(Request(rid=2, prompt=base[:35] + [401] * 9, max_new=4))
    done = eng.run()
    assert eng.stats()["prefix"]["prefill_skips"] == 1
    assert {r.rid for r in done} == {0, 1, 2}
    for r in done:
        assert r.out and r.error is None
        assert r.t_submit <= r.t_admit <= r.t_first, r

    dense = ServeConfig(arch="qwen2-7b", reduced=True, slots=2, max_len=48)
    deng, _, _ = dense.build(model, params)
    deng.submit(Request(rid=0, prompt=base[:20], max_new=3))
    (r,) = deng.run()
    assert r.out and r.t_submit is r.t_admit is r.t_first is None


def test_serving_micro_trace_smoke(tmp_path):
    """The benchmarks/run.py --trace path end to end: one tiered scenario
    under a profiler session, one ``engine.step`` per tick."""
    from benchmarks.serving_micro import run_trace
    n = run_trace(str(tmp_path), smoke=True)
    assert n > 0
    assert sum(e[0] == "engine.step" for e in read_spans(tmp_path)) == n


def test_paged_attn_pages_counted(served_model):
    """``paged_attn_pages_total{kind}``, from the host's length mirror at
    each decode dispatch: ``read`` adds ceil(len / ps) for every live lane
    (len counts this tick's token), ``read + skipped`` every entry of
    every lane's table."""
    from repro.cache import TierConfig
    from repro.serving.paged_engine import PagedEngine
    cfg, model, params = served_model
    ps, lanes, max_len = 16, 3, 64
    eng = PagedEngine(model, params, lanes=lanes, max_len=max_len,
                      tier=TierConfig(page_size=ps, hbm_budget_bytes=1 << 30,
                                      enable_warm=False, enable_cold=False),
                      use_roofline_trigger=False)
    seen = []
    decode = eng._decode

    def spy(params, pools, tokens, bt, lengths, *rest):
        seen.append(np.asarray(lengths).copy())
        return decode(params, pools, tokens, bt, lengths, *rest)

    spy._cache_size = decode._cache_size        # the compile count probe
    eng._decode = spy
    rng = np.random.default_rng(3)
    # prompts that put lengths on page boundaries (15 + 1 = 16 keys, 31 + 1)
    for rid, (plen, new) in enumerate([(15, 3), (31, 6), (5, 2), (40, 4)]):
        eng.submit(Request(rid=rid,
                           prompt=[int(t) for t in rng.integers(2, 400, plen)],
                           max_new=new))
    assert len(eng.run()) == 4
    m = eng.obs.metrics
    read = m.get_value("paged_attn_pages_total", kind="read")
    skipped = m.get_value("paged_attn_pages_total", kind="skipped")
    want = sum(-(-(int(n) + 1) // ps) for ls in seen for n in ls if n > 0)
    assert read == want > 0
    assert read + skipped == len(seen) * lanes * (max_len // ps)
    assert any((ls == 0).any() for ls in seen)      # an idle lane was seen
