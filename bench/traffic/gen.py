"""The one traffic generator: a mix file in, a request plan out.

A mix (``bench/traffic/<mix>.json``) is data only:

    loop       "open" (requests arrive on a schedule, whatever the server
               does) or "closed" (``clients`` requests in flight; each
               finished one is replaced at once)
    arrivals   open loop: {"process": "poisson", "rate_per_s": r} or
               {"process": "onoff", "rate_per_s": r, "burst_factor": b,
               "period_s": p, "on_share": f} (bursts at b times the rate in
               the first f of every period, the mean rate unchanged)
    prompt,    {"dist": "lognormal", "median", "sigma", "min", "max"} or
    output     {"dist": "uniform", "min", "max"} (lengths in tokens)
    prefix     optional {"n_prefixes", "zipf_a", "tokens"}: every prompt
               opens with one of n shared prefixes drawn Zipfian
    serve      {"lanes", "max_len"}: the engine the mix is served by
    window     {"start": "immediate" | "after_preroll" | "after_preemption"}
               after_preroll (open loop) adds "preroll_s": the last
               preroll_s seconds of the plan are served first, so the
               window opens on the load it closes on
    check      {"sample_tokens", "max_requests"}: the correctness sample

The work does not depend on the seed: lengths are the stratified
quantiles of their distribution and arrival gaps the stratified quantiles
of the exponential, so every seed serves the same multiset of sizes and
gaps. The seed only shuffles their order and draws the token ids.
Distributions follow ``repro.sessions.loadgen`` (Poisson arrivals,
Zipfian shared prefixes), timed here in wall seconds instead of ticks.
"""
from __future__ import annotations

import dataclasses
import json
import math
import pathlib
import statistics

import numpy as np

HERE = pathlib.Path(__file__).resolve().parent
#: lowest token id drawn (0 is the engines' end-of-sequence id)
FIRST_TOKEN = 2


@dataclasses.dataclass
class Planned:
    """One request of the plan: when it is due (seconds from the window's
    start; None in a closed loop) and what it asks."""
    arrival_s: float | None
    prompt: np.ndarray          # int32 token ids
    max_new: int


def load_mix(name: str, root: pathlib.Path = HERE) -> dict:
    path = root / f"{name}.json"
    if not path.is_file():
        raise FileNotFoundError(f"no traffic mix {name!r} at {path}")
    return json.loads(path.read_text())


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lengths(spec: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    """``n`` lengths: the stratified quantiles of ``spec``, shuffled."""
    lo, hi = int(spec["min"]), int(spec["max"])
    u = _quantiles(n)
    if spec["dist"] == "lognormal":
        z = np.array([statistics.NormalDist().inv_cdf(x) for x in u])
        vals = np.exp(math.log(spec["median"]) + spec["sigma"] * z)
    elif spec["dist"] == "uniform":
        vals = lo + (hi - lo) * u
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    vals = np.clip(np.rint(vals), lo, hi).astype(np.int64)
    return rng.permutation(vals)


def arrival_times(spec: dict, n: int, seconds: float,
                  rng: np.random.Generator) -> np.ndarray:
    """Arrival offsets of ``n`` requests in ``[0, seconds)``.

    Poisson: the gaps are the stratified quantiles of the exponential,
    shuffled and scaled so that they fill the window. On/off: the same
    gaps, with time warped so that the first ``on_share`` of every period
    receives ``burst_factor`` times the mean rate."""
    gaps = rng.permutation(-np.log1p(-_quantiles(n)))
    t = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    t = t * (seconds / gaps.sum())
    if spec["process"] == "poisson":
        return t
    if spec["process"] != "onoff":
        raise ValueError(f"unknown arrival process {spec['process']!r}")
    period, share = float(spec["period_s"]), float(spec["on_share"])
    burst = float(spec["burst_factor"])
    on_mass = min(share * burst, 1.0)     # share of arrivals in the bursts
    out = []
    for x in t:
        k, frac = divmod(x / period, 1.0)
        if frac < on_mass:
            pos = frac / on_mass * share
        else:
            pos = share + (frac - on_mass) / max(1.0 - on_mass, 1e-9) \
                * (1.0 - share)
        out.append((k + pos) * period)
    return np.asarray(out)


def n_open(mix: dict, seconds: float) -> int:
    return max(1, int(round(mix["arrivals"]["rate_per_s"] * seconds)))


def plan(mix: dict, seed: int, seconds: float, vocab: int,
         rate_per_s: float | None = None) -> list[Planned]:
    """The requests of one run. Open loop: every request due in the
    window, in arrival order. Closed loop: a queue long enough for the
    clients to draw from for the whole window."""
    rng = np.random.default_rng(seed)
    mix = dict(mix)
    if rate_per_s is not None:
        mix["arrivals"] = dict(mix["arrivals"], rate_per_s=rate_per_s)
    if mix["loop"] == "open":
        n = n_open(mix, seconds)
        arrivals = arrival_times(mix["arrivals"], n, seconds, rng)
    elif mix["loop"] == "closed":
        n = int(mix["clients"]) * int(mix.get("queue_per_client", 4))
        arrivals = [None] * n
    else:
        raise ValueError(f"unknown loop {mix['loop']!r}")
    plens = lengths(mix["prompt"], n, rng)
    outs = lengths(mix["output"], n, rng)
    prefixes = None
    if "prefix" in mix:
        p = mix["prefix"]
        prefixes = [rng.integers(FIRST_TOKEN, vocab, int(p["tokens"]),
                                 dtype=np.int32)
                    for _ in range(int(p["n_prefixes"]))]
        ranks = np.arange(1, len(prefixes) + 1, dtype=np.float64)
        zipf = ranks ** -float(p["zipf_a"])
        zipf /= zipf.sum()
    reqs = []
    for a, pl, mo in zip(arrivals, plens, outs):
        toks = rng.integers(FIRST_TOKEN, vocab, int(pl), dtype=np.int32)
        if prefixes is not None:
            pre = prefixes[rng.choice(len(prefixes), p=zipf)]
            k = min(len(pre), len(toks) - 1)
            toks[:k] = pre[:k]
        reqs.append(Planned(None if a is None else float(a), toks, int(mo)))
    return reqs
