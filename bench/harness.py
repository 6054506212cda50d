"""One run of one cell: set-up, measured window, drain, check.

The served path is the program's own: ``ServeConfig.build()`` builds a
``PagedEngine``, and the window drives ``PagedEngine.submit`` and
``PagedEngine.step``. The harness only offers requests, watches the
tokens each request has received after every step, and times them on the
host's clock. Each request is timed from its scheduled arrival.
"""
from __future__ import annotations

import dataclasses
import gc
import itertools
import json
import pathlib
import shutil
import tempfile
import time

import numpy as np

from bench import flops as F
from bench.traffic import gen

ROOT = pathlib.Path(__file__).resolve().parents[1]
#: the drain after the window's close ends when every request has its
#: answer, or at the latest once the longest answer the mix asks for could
#: have been decoded DRAIN_STEPS_PER_TOKEN times over at the window's
#: median step time, plus DRAIN_SLACK_S for admission and prefill
DRAIN_STEPS_PER_TOKEN = 1.5
DRAIN_SLACK_S = 30.0
#: longest ramp of a closed loop before its window may open
RAMP_S = 150.0


class NoChip(RuntimeError):
    """JAX sees no accelerator of the kind, or not as many as, the cell
    asks for."""


# -- files found by name ------------------------------------------------------

def manifest(root: pathlib.Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def load_cell(name: str, root: pathlib.Path = ROOT):
    """(cell, configuration, mix, limits) of one ``workloads`` entry."""
    m = manifest(root)
    cells = {c["name"]: c for c in m["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; known: {sorted(cells)}")
    cell = cells[name]
    conf = json.loads((root / "bench" / "configs"
                       / f"{cell['config']}.json").read_text())
    mix = gen.load_mix(cell["traffic"], root / "bench" / "traffic")
    lim_path = root / "bench" / "limits" / f"{name}.json"
    limits = json.loads(lim_path.read_text()) if lim_path.is_file() else {}
    return cell, conf, mix, limits


def device_info(chips: int, require_tpu: bool = True) -> dict:
    import jax
    devs = jax.devices()
    d = devs[0]
    if require_tpu and d.platform != "tpu":
        raise NoChip(f"needs a TPU; JAX found platform {d.platform!r}")
    if len(devs) < chips:
        raise NoChip(f"needs {chips} chips; JAX found {len(devs)}")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}


# -- the system under test ----------------------------------------------------

def arch_config(conf: dict):
    """The program's ArchConfig for a configuration file: the registered
    architecture with every size the file states."""
    import dataclasses as dc
    from repro.configs import get_arch
    c = conf["config"]
    act = {"silu": "silu", "gelu_pytorch_tanh": "gelu"}[c["hidden_act"]]
    norm = "layernorm" if c.get("norm_type") == "layer_norm" else "rmsnorm"
    return dc.replace(
        get_arch(conf["arch"]), n_layers=c["num_hidden_layers"],
        d_model=c["hidden_size"], n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"], d_ff=c["intermediate_size"],
        vocab_size=c["vocab_size"], rope_theta=float(c["rope_theta"]),
        tie_embeddings=bool(c["tie_word_embeddings"]), act=act, norm=norm,
        qkv_bias=True)


def build_engine(conf: dict, mix: dict, params, model, seed: int):
    from repro.assist import AssistSpec
    from repro.obs import ObsSpec
    from repro.serving.config import ServeConfig
    spec = AssistSpec(paged=True, **conf["serving"])
    scfg = ServeConfig(
        arch=model.cfg.name, slots=mix["serve"]["lanes"],
        max_len=mix["serve"]["max_len"], seed=seed, assist=spec,
        # every request runs to its budget: the work is fixed by the mix
        eos_id=-1,
        # counters on (the tier and preemption readers use them); the
        # execution probe's block_until_ready fences stay out of the window
        obs=ObsSpec(counters=True, trace=False, exec_probe=False))
    eng, _, _ = scfg.build(model=model, params=params)
    return eng


# -- per-request record -------------------------------------------------------

@dataclasses.dataclass
class Rec:
    planned: gen.Planned
    req: object                   # the engine's Request
    due: float                    # scheduled arrival (host clock)
    submitted: float = 0.0
    admitted: float | None = None
    times: list = dataclasses.field(default_factory=list)

    @property
    def plen(self) -> int:
        return len(self.planned.prompt)


class Driver:
    """Offers the plan to the engine and records what comes back."""

    def __init__(self, eng, seed: int, first_rid: int = 0):
        self.eng = eng
        self.live: list[Rec] = []
        self.by_rid: dict[int, Rec] = {}
        self.done: list[Rec] = []
        self.next_rid = first_rid
        self.sample_steps = False
        self.steps: list = []     # traced stretch: (ctxs, warm share)
        self.step_s: list = []    # wall time of every step
        self.step_at: list = []   # and when it began

    def submit(self, planned: gen.Planned, due: float) -> Rec:
        import jax
        from repro.serving.engine import Request
        req = Request(rid=self.next_rid, prompt=planned.prompt.tolist(),
                      max_new=planned.max_new)
        self.next_rid += 1
        rec = Rec(planned, req, due)
        with jax.profiler.TraceAnnotation("bench.submit"):
            rec.submitted = time.perf_counter()
            self.eng.submit(req)
        self.live.append(rec)
        self.by_rid[req.rid] = rec
        return rec

    def step(self) -> None:
        import jax
        t = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.step"):
            self.eng.step()
        now = time.perf_counter()
        self.step_s.append(now - t)
        self.step_at.append(t)
        queued = {id(r) for r in self.eng.queue}
        still = []
        for rec in self.live:
            n = len(rec.req.out)
            if n > len(rec.times):
                rec.times.extend([now] * (n - len(rec.times)))
            if rec.admitted is None and id(rec.req) not in queued:
                rec.admitted = now
            if rec.req.done:
                self.done.append(rec)
            else:
                still.append(rec)
        self.live = still
        if self.sample_steps:
            self._sample(now)

    def _sample(self, now: float) -> None:
        ctxs = []
        for rid in self.eng.lanes:
            rec = self.by_rid.get(rid) if rid is not None else None
            if rec is not None:
                ctxs.append(rec.plen + len(rec.req.out) + 1)
        t = self.eng.store.tier_counts()
        resident = t["hot"] + t["warm"] + t["cold"]
        self.steps.append((now, ctxs, t["warm"] / max(t["hot"] + t["warm"], 1),
                           t["warm"] / max(resident, 1)))

    def counter(self, name: str, **labels) -> float:
        return self.eng.obs.metrics.get_value(name, **labels) or 0


# -- the run ------------------------------------------------------------------

@dataclasses.dataclass
class Setup:
    """What set-up builds for a cell: its files, the device, the weights
    drawn from the seed, and the engine with the driver that feeds it."""
    cell: dict
    conf: dict
    mix: dict
    limits: dict
    device: dict
    vocab: int
    params: object
    eng: object
    drv: Driver


def set_up(name: str, seed: int, *, root: pathlib.Path = ROOT,
           require_tpu: bool = True, compile_cache: bool = True) -> Setup:
    """Everything before the warm-up: the look for the chip, the compile
    cache, the weights drawn on the device from ``seed``, the engine."""
    cell, conf, mix, limits = load_cell(name, root)
    device = device_info(cell["chips"], require_tpu)
    import jax
    if compile_cache:
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        from repro.launch.compile_cache import enable_compile_cache
        enable_compile_cache()
    from repro.models.model import build_model
    from bench import weights as W

    cfg = arch_config(conf)
    model = build_model(cfg)
    params = W.make_params(conf["config"], seed)
    W.check_layout(params, jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    eng = build_engine(conf, mix, params, model, seed)
    return Setup(cell, conf, mix, limits, device, cfg.vocab_size, params,
                 eng, Driver(eng, seed))


def warm_up(drv: Driver, plans: list, max_len: int) -> None:
    """One short request per prefill bucket the plans use, and the decode
    step: every program the window drives is built before it opens."""
    from repro.models.model import prompt_bucket
    ps = drv.eng.pool.page_size
    buckets = sorted({prompt_bucket(len(p.prompt), max_len, ps)
                      for plan in plans for p in plan})
    for b in buckets:
        # b - 2 prompt tokens fall in bucket b and leave room for 2 more
        warm = gen.Planned(None, np.resize(plans[0][0].prompt, b - 2), 2)
        drv.submit(warm, time.perf_counter())
    while drv.live:
        drv.step()
    drv.eng.sync()
    drv.done.clear()


def run_cell(name: str, seed: int, seconds: float, trace: bool, *,
             t_start: float, root: pathlib.Path = ROOT,
             require_tpu: bool = True, trace_dir: str | None = None,
             control: str | None = None, fault=None,
             compile_cache: bool = True) -> dict:
    """Run one cell once and return the result line's object.

    ``fault`` (tests only) is called with the engine before the window.
    ``control`` names a precision (``int8``, ``fp8``): the check then
    judges the tokens that the reference at that precision puts first,
    in the program's place, and reads the program's beside them."""
    s = set_up(name, seed, root=root, require_tpu=require_tpu,
               compile_cache=compile_cache)
    cell, conf, mix, limits, device = (s.cell, s.conf, s.mix, s.limits,
                                       s.device)
    import jax
    plan = gen.plan(mix, seed, seconds, s.vocab)
    warm_up(s.drv, [plan], mix["serve"]["max_len"])
    if fault is not None:
        fault(s.eng)

    compiles = _CompileCounter()
    res = (_open_loop if mix["loop"] == "open" else _closed_loop)(
        s.drv, plan, mix, seconds, trace, t_start, compiles, trace_dir)
    mem = (jax.devices()[0].memory_stats() or {}).get("peak_bytes_in_use")
    device["memory_peak_bytes"] = int(mem) if mem is not None else 0
    metrics_e2e, attempted, failed, answers = _end_to_end(res, seconds)
    end = res.get("drained_at", res["close"])
    extra = {"compiles_in_window": compiles.n,
             "drain_s": end - res["close"],
             # requests waiting for a lane at the window's open and close
             "waiting": res.get("waiting"),
             "stats": {k: v for k, v in s.eng.stats().items()
                       if k in ("preemptions", "admissions", "quarantines",
                                "tiers", "store", "tokens_generated")},
             # where time to first token went, for the slowest three:
             # [due (s into the window), submitted after due, admitted
             # after submitted, first token after admitted (ms), prompt]
             "slowest_first_tokens": [
                 [round(r.due - res["t0"], 3),
                  round((r.submitted - r.due) * 1e3, 1),
                  round(((r.admitted or r.submitted) - r.submitted) * 1e3, 1),
                  round((r.times[0] - (r.admitted or r.submitted)) * 1e3, 1),
                  r.plen]
                 for r in sorted((r for r in res["recs"] if r.times),
                                 key=lambda r: r.due - r.times[0])[:3]],
             # the longest steps of the window: [began (s into it), ms]
             "longest_steps": _longest_steps(res.get("steps", ([], [])),
                                             res["t0"], res["close"]),
             "gc_pauses_ms": res.get("gc_ms"),
             # requests that failed: id, prompt and asked tokens, tokens
             # received, the engine's error (None: never finished), and
             # the seconds from its last token to the drain's end (large:
             # it had stopped; near a step: it was still decoding)
             "failed": [[r.req.rid, r.plen, r.planned.max_new,
                         len(r.req.out), r.req.error,
                         round(end - r.times[-1], 3) if r.times else None]
                        for r in res["recs"]
                        if not r.req.done or r.req.error is not None]}

    per_layer = None
    if trace:
        from bench import metrics as M
        ctx = dict(res["ctx"], conf=conf, mix=mix,
                   peaks=_peaks(device, require_tpu),
                   shapes=F.shapes(conf["config"]),
                   page_size=s.eng.pool.page_size, window_s=res["window_s"])
        per_layer = M.read_all(cell, ctx, root)
        device["busy_s"] = res["ctx"]["trace"].busy_s
        device["window_s"] = res["ctx"]["trace"].window_s

    # the check runs after the program's state is freed
    params = s.params
    del s
    gc.collect()
    check, readings = _check(conf, mix, limits, params, answers, seed,
                             failed, control)
    correct = all(_holds(v) for v in check.values())
    if readings is not None:
        extra["readings"] = readings

    out = {"correct": bool(correct), "attempted": attempted,
           "failed": failed,
           "metrics": per_layer if trace else metrics_e2e,
           "device": device}
    if trace:
        out["breakdown"] = {"device_ops": ctx["trace"].top_ops(10),
                            "idle_gaps": ctx["trace"].gaps[:10]}
    out["diagnostics"] = extra
    out["check"] = check
    return out


def _peaks(device, require_tpu):
    from bench.peaks import PEAKS, peaks_for
    if not require_tpu and device["platform"] != "tpu":
        return PEAKS["TPU v5 lite"]       # CPU rehearsal: shapes only
    return peaks_for(device["platform"], device["kind"])


class _CompileCounter:
    """Counts XLA compilations while the window is open."""

    def __init__(self):
        self.n = 0
        self.on = False
        import jax
        jax.monitoring.register_event_duration_secs_listener(self._hear)

    def _hear(self, event: str, duration: float, **_):
        if self.on and event in self.EVENTS:
            self.n += 1

    #: a program compiled, or loaded from the persistent cache
    EVENTS = ("/jax/core/compile/backend_compile_duration",
              "/jax/compilation_cache/cache_retrieval_time_sec")


# -- the loops ----------------------------------------------------------------

def _trace_len(seconds: float) -> float:
    """Length of the traced stretch, which ends as the window closes."""
    return min(5.0, 0.4 * seconds)


def _schedule(plan, mix, seconds):
    """``(offset from the window's open, request, in the window)`` in
    order of arrival, and the pre-roll's length.

    A window that opens ``after_preroll`` is preceded by the last
    ``preroll_s`` seconds of its own plan, so that it opens on the load
    it closes on: the requests in flight at the open are those in flight
    at the close. The pre-roll's requests are served like any other and
    are neither metered nor checked."""
    win = mix["window"]
    lead = 0.0
    pre = []
    if win["start"] == "after_preroll":
        lead = min(float(win["preroll_s"]), seconds)
        pre = [(p.arrival_s - seconds, p, False) for p in plan
               if p.arrival_s >= seconds - lead]
    elif win["start"] != "immediate":
        raise ValueError(f"an open loop's window cannot open "
                         f"{win['start']!r}")
    return pre + [(p.arrival_s, p, True) for p in plan], lead


def _longest_steps(steps, t0, close, k=3):
    at, dur = steps
    inside = [(a - t0, d * 1e3) for a, d in zip(at, dur) if t0 <= a < close]
    return [[round(a, 3), round(d, 1)]
            for a, d in sorted(inside, key=lambda x: -x[1])[:k]]


class _GCWatch:
    """Garbage-collector pauses while on: [count, total ms, longest ms]."""

    def __init__(self):
        import gc as _gc
        self.on, self.t, self.ms = False, 0.0, []
        _gc.callbacks.append(self._hear)

    def _hear(self, phase, info):
        if not self.on:
            return
        if phase == "start":
            self.t = time.perf_counter()
        elif self.t:
            self.ms.append((time.perf_counter() - self.t) * 1e3)
            self.t = 0.0

    def close(self):
        import gc as _gc
        self.on = False
        _gc.callbacks.remove(self._hear)
        return [len(self.ms), round(sum(self.ms), 1),
                round(max(self.ms, default=0.0), 1)]


def _median_step(drv: Driver, since: int) -> float:
    xs = drv.step_s[since:]
    return float(np.median(xs)) if xs else 1.0


def _drain(drv: Driver, mix: dict, step_s: float) -> float:
    """Step until every request has its whole answer. A request still
    unfinished once the longest answer the mix asks for could have been
    decoded ``DRAIN_STEPS_PER_TOKEN`` times over is lost. Returns the
    time the drain ended."""
    limit = (time.perf_counter() + DRAIN_SLACK_S + DRAIN_STEPS_PER_TOKEN
             * int(mix["output"]["max"]) * step_s)
    while drv.live and time.perf_counter() < limit:
        drv.step()
    drv.eng.sync()
    return time.perf_counter()


def _open_loop(drv, plan, mix, seconds, trace, t_start, compiles,
               trace_dir):
    import jax
    sched, lead = _schedule(plan, mix, seconds)
    tracer = _Tracer(trace_dir) if trace else None
    drv.eng.sync()
    k0 = len(drv.step_s)
    t0 = time.perf_counter() + lead       # the window opens after the lead
    setup_s = t0 - t_start
    close = t0 + seconds
    t_trace = close - _trace_len(seconds)
    recs, served, i, n = [], [], 0, len(sched)
    waiting_at_open = None
    gcw = _GCWatch()
    while True:
        now = time.perf_counter()
        if now >= close:
            break
        if waiting_at_open is None and now >= t0:
            waiting_at_open = _waiting(drv)
            gcw.on = True
        compiles.on = now >= t0
        while i < n and t0 + sched[i][0] <= now:
            off, planned, metered = sched[i]
            rec = drv.submit(planned, t0 + off)
            served.append(rec)
            if metered:
                recs.append(rec)
            i += 1
        if tracer is not None:
            tracer.tick(drv, now, t_trace)
        if drv.live:
            drv.step()
        else:
            nxt = t0 + sched[i][0] if i < n else close
            with jax.profiler.TraceAnnotation("bench.wait"):
                time.sleep(max(0.0, min(nxt, close) - time.perf_counter()))
    # requests due before the close that the last step held back are
    # offered now, late; they count as the window's
    while i < n and t0 + sched[i][0] < close:
        off, planned, metered = sched[i]
        rec = drv.submit(planned, t0 + off)
        served.append(rec)
        if metered:
            recs.append(rec)
        i += 1
    compiles.on = False
    gc_ms = gcw.close()
    if tracer is not None:
        tracer.stop(drv)
    waiting_at_close = _waiting(drv)
    # drain: every request due in the window gets its whole answer
    drained_at = _drain(drv, mix, _median_step(drv, k0))
    return {"recs": recs, "served": served, "t0": t0, "close": close,
            "setup_s": setup_s, "window_s": seconds, "loop": "open",
            "drained_at": drained_at, "gc_ms": gc_ms,
            "steps": (drv.step_at[k0:], drv.step_s[k0:]),
            "waiting": [waiting_at_open or 0, waiting_at_close],
            "ctx": tracer.ctx(drv, recs) if tracer else {"recs": recs}}


def _waiting(drv: Driver) -> int:
    """Requests the engine holds that no lane decodes: queued, or
    prefilled and parked until a lane frees."""
    return len(drv.eng.queue) + len(drv.eng.parked)


def _closed_loop(drv, plan, mix, seconds, trace, t_start, compiles,
                 trace_dir):
    tracer = _Tracer(trace_dir) if trace else None
    clients = int(mix["clients"])
    # the plan's requests in order, again from the start if the run
    # outlasts them: the same sizes, in the same order, for every seed
    pool = itertools.cycle(plan)
    recs = []

    def top_up():
        while len(drv.live) < clients:
            recs.append(drv.submit(next(pool), time.perf_counter()))

    ramp_end = time.perf_counter() + RAMP_S
    while True:
        top_up()
        drv.step()
        if mix["window"]["start"] == "immediate":
            break
        # the KV has outgrown the hot tier once a lane was preempted
        if drv.counter("engine_preemptions_total") > 0:
            break
        if time.perf_counter() > ramp_end:
            raise RuntimeError("the closed loop saw no preemption within "
                               f"{RAMP_S:.0f} s of ramp")
    st = drv.eng.store.stats
    moves0 = st["demote_cold"] + st["promote_warm"]
    done0 = len(drv.done)
    t0 = time.perf_counter()
    setup_s = t0 - t_start
    close = t0 + seconds
    t_trace = close - _trace_len(seconds)
    compiles.on = True
    tiers = []
    while True:
        now = time.perf_counter()
        if now >= close:
            break
        if tracer is not None:
            tracer.tick(drv, now, t_trace)
        top_up()
        drv.step()
        t = drv.eng.store.tier_counts()
        tiers.append(t)
    compiles.on = False
    if tracer is not None:
        tracer.stop(drv)
    drv.eng.sync()
    st = drv.eng.store.stats
    ctx = tracer.ctx(drv, recs) if tracer else {"recs": recs}
    ctx["tier_samples"] = tiers
    ctx["cold_moves"] = st["demote_cold"] + st["promote_warm"] - moves0
    return {"recs": recs, "t0": t0, "close": close, "setup_s": setup_s,
            "window_s": seconds, "loop": "closed",
            "finished_in_window": drv.done[done0:], "ctx": ctx}


class _Tracer:
    """Profiles the last stretch of the window. Inside the window it only
    starts the profiler; the profiler stops once the window has closed,
    and the trace is reduced after the drain."""

    def __init__(self, keep_dir: str | None):
        self.keep_dir = keep_dir
        self.dir = tempfile.mkdtemp(prefix="bench-trace-")
        self.state = "before"
        self.t = (0.0, 0.0)

    def tick(self, drv, now, start):
        import jax
        if self.state == "before" and now >= start:
            jax.profiler.start_trace(self.dir)
            self.state, self.t = "on", (time.perf_counter(), 0.0)
            drv.sample_steps = True

    def stop(self, drv):
        import jax
        if self.state != "on":
            return
        drv.sample_steps = False
        self.t = (self.t[0], time.perf_counter())
        jax.profiler.stop_trace()
        self.state = "done"

    def ctx(self, drv, recs) -> dict:
        from bench import trace_reduce
        if self.state != "done":
            raise RuntimeError("the window closed before the traced "
                               "stretch began; run longer")
        if self.keep_dir:
            shutil.copytree(self.dir, self.keep_dir, dirs_exist_ok=True)
        try:
            reduced = trace_reduce.reduce(trace_reduce.load(self.dir))
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)
        lo, hi = self.t
        return {"recs": recs, "trace": reduced,
                "steps": drv.steps,
                "prefill_tokens": [r.plen for r in recs
                                   if r.admitted is not None
                                   and lo <= r.admitted <= hi]}


# -- end-to-end metrics --------------------------------------------------------

def _pct(xs, q):
    return float(np.percentile(np.asarray(xs, np.float64), q))


def _end_to_end(res: dict, seconds: float):
    """Metrics of the window, requests attempted and failed, and the
    answers the check may sample from."""
    recs, t0, close = res["recs"], res["t0"], res["close"]
    # tokens and gaps of every request served, a pre-roll's included;
    # times to first token of the requests due in the window
    served = res.get("served", recs)
    tokens = sum(1 for r in served for t in r.times if t0 <= t <= close)
    m = {"output_tokens_per_s": {"value": tokens / seconds,
                                 "unit": "tokens/s"},
         "setup_s": {"value": res["setup_s"], "unit": "s"}}
    if res["loop"] == "open":
        attempted = len(recs)
        ok = [r for r in recs if r.req.done and r.req.error is None]
        failed = attempted - len(ok)
        ttft = [(r.times[0] - r.due) * 1e3 for r in recs if r.times]
        gaps = [(b - a) * 1e3 for r in served
                for a, b in zip(r.times, r.times[1:]) if t0 <= b <= close]
        if ttft:
            m["ttft_p95_ms"] = {"value": _pct(ttft, 95), "unit": "ms"}
        if gaps:
            m["itl_p50_ms"] = {"value": _pct(gaps, 50), "unit": "ms"}
            m["itl_p95_ms"] = {"value": _pct(gaps, 95), "unit": "ms"}
        answers = ok
    else:
        ok = [r for r in res["finished_in_window"] if r.req.error is None]
        attempted = len(res["finished_in_window"])
        failed = attempted - len(ok)
        answers = ok
    lags = [(r.submitted - r.due) * 1e3 for r in recs]
    res["ctx"]["gen_lag_ms"] = lags
    return m, attempted, failed, answers


# -- the check -----------------------------------------------------------------

def _sample(answers, seed: int, want_tokens: int, max_requests: int):
    """The longest answer, then others drawn from the seed, until the
    sample holds ``want_tokens`` served tokens."""
    if not answers:
        return []
    order = sorted(answers, key=lambda r: (-len(r.req.out), -r.plen))
    first, rest = order[0], order[1:]
    rng = np.random.default_rng(seed ^ 0x5EED)
    rest = [rest[i] for i in rng.permutation(len(rest))]
    out, n = [first], len(first.req.out)
    for r in rest:
        if n >= want_tokens or len(out) >= max_requests:
            break
        out.append(r)
        n += len(r.req.out)
    return out


def _check(conf, mix, limits, params, answers, seed, failed, control):
    """Compare the served tokens of a sample with the float32 reference:
    the widest gap by which a served token's logit lies below the
    reference's best. Every answer must also be whole.

    With ``control`` (a precision below the configuration's, ``int8`` or
    ``fp8``) the reference at that precision stands in the program's
    place: at the same positions, the tokens it puts first are compared,
    and the program's tokens are read beside them."""
    import jax.numpy as jnp
    from bench import reference as R
    chk = mix.get("check", {})
    sample = _sample(answers, seed, int(chk.get("sample_tokens", 300)),
                     int(chk.get("max_requests", 8)))
    S = int(mix["serve"]["max_len"])
    kinds = ("program",) + ((control,) if control else ())
    gaps = {k: [] for k in kinds}
    short = sum(1 for r in answers if len(r.req.out) != r.planned.max_new)
    for r in sample:
        out = list(r.req.out)
        full = np.zeros(S, np.int32)
        seq = np.concatenate([r.planned.prompt,
                              np.asarray(out[:-1], np.int32)])
        full[:len(seq)] = seq
        pos = np.arange(r.plen - 1, r.plen - 1 + len(out))
        cand = np.zeros((S, len(kinds)), np.int32)
        cand[pos, 0] = out
        if control:
            _, am = R.gaps(conf["config"], params, jnp.asarray(full),
                           jnp.asarray(cand[:, :1]), quantize=control)
            cand[pos, 1] = np.asarray(am)[pos]
        g, _ = R.gaps(conf["config"], params, jnp.asarray(full),
                      jnp.asarray(cand))
        g = np.asarray(g)[pos]
        for j, k in enumerate(kinds):
            gaps[k].extend(g[:, j].tolist())
    judged = gaps[control or "program"]
    check = {"max_gap": {"value": max(judged, default=0.0),
                         "limit": limits.get("max_gap"), "rule": "<="},
             "lost": {"value": failed, "limit": 0, "rule": "<="},
             "short_answers": {"value": short, "limit": 0, "rule": "<="},
             "sampled_tokens": {"value": len(judged), "limit": 1,
                                "rule": ">="}}
    readings = {k: {"max_gap": max(v, default=0.0),
                    "mean_gap": float(np.mean(v)) if v else 0.0,
                    "flips": int(sum(x > 0 for x in v))}
                for k, v in gaps.items()} if control else None
    return check, readings


def _holds(c: dict) -> bool:
    """Whether one compared number keeps to its limit (no limit: never)."""
    if c["limit"] is None:
        return False
    v, lim = c["value"], c["limit"]
    return v <= lim if c["rule"] == "<=" else v >= lim
