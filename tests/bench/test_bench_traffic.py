"""The traffic generator: deterministic from the seed, the same work for
every seed, and distributions that match each mix file."""
from __future__ import annotations

import math
import statistics

import numpy as np
import pytest

from bench.traffic import gen

MIXES = ["chat", "code", "longdoc-tiered"]


def _plan(mix_name, seed, seconds=30.0, vocab=1000):
    return gen.plan(gen.load_mix(mix_name), seed, seconds, vocab)


@pytest.mark.parametrize("mix", MIXES)
def test_same_seed_same_plan(mix):
    a, b = _plan(mix, 2**33 + 5), _plan(mix, 2**33 + 5)
    assert len(a) == len(b)
    for x, y in zip(a, b):
        assert x.arrival_s == y.arrival_s and x.max_new == y.max_new
        np.testing.assert_array_equal(x.prompt, y.prompt)


@pytest.mark.parametrize("mix", MIXES)
def test_every_seed_serves_the_same_work(mix):
    a, b = _plan(mix, 1), _plan(mix, 2)
    assert sorted(len(p.prompt) for p in a) == \
        sorted(len(p.prompt) for p in b)
    assert sorted(p.max_new for p in a) == sorted(p.max_new for p in b)
    assert [len(p.prompt) for p in a] != [len(p.prompt) for p in b]
    if a[0].arrival_s is not None:
        # the gaps are one shuffled multiset; the window holds all but
        # the gap after the last arrival
        ga = set(np.round(np.diff([p.arrival_s for p in a]), 9))
        gb = set(np.round(np.diff([p.arrival_s for p in b]), 9))
        assert len(ga ^ gb) <= 2


@pytest.mark.parametrize("mix", MIXES)
def test_lengths_match_the_mix(mix):
    spec = gen.load_mix(mix)
    plan = _plan(mix, 7, seconds=50.0)
    for key, got in (("prompt", [len(p.prompt) for p in plan]),
                     ("output", [p.max_new for p in plan])):
        d = spec[key]
        assert min(got) >= d["min"] and max(got) <= d["max"]
        med = statistics.median(got)
        want = (d["median"] if d["dist"] == "lognormal"
                else (d["min"] + d["max"]) / 2)
        assert abs(med - want) <= 0.05 * want + 1
        if d["dist"] == "lognormal":
            # the stratified draw's spread in log space is the mix's sigma
            # (clipping only narrows it)
            logs = np.log(got)
            q1, q3 = np.percentile(logs, [25, 75])
            assert (q3 - q1) <= 2 * 0.6745 * d["sigma"] * 1.05
    assert all(len(p.prompt) + p.max_new <= spec["serve"]["max_len"]
               for p in plan)


@pytest.mark.parametrize("mix", ["chat", "code"])
def test_open_loop_arrivals_fill_the_window_at_the_rate(mix):
    spec = gen.load_mix(mix)
    seconds = 40.0
    plan = _plan(mix, 11, seconds)
    rate = spec["arrivals"]["rate_per_s"]
    assert len(plan) == round(rate * seconds)
    t = np.array([p.arrival_s for p in plan])
    assert t[0] == 0.0 and np.all(np.diff(t) > 0) and t[-1] < seconds
    gaps = np.diff(t)
    # exponential gaps: the coefficient of variation is about 1
    assert 0.8 < gaps.std() / gaps.mean() < 1.2


def test_token_ids_avoid_the_end_of_sequence_id():
    plan = _plan("chat", 3, vocab=50)
    toks = np.concatenate([p.prompt for p in plan])
    assert toks.min() >= gen.FIRST_TOKEN and toks.max() < 50


def test_onoff_bursts_keep_the_mean_rate():
    spec = {"process": "onoff", "rate_per_s": 4.0, "burst_factor": 4.0,
            "period_s": 10.0, "on_share": 0.2}
    t = gen.arrival_times(spec, 400, 100.0, np.random.default_rng(0))
    assert len(t) == 400 and t.max() < 100.0
    phase = (t % 10.0) / 10.0
    # 80% of the arrivals land in the first 20% of each period
    assert 0.75 < np.mean(phase < 0.2) < 0.85


def test_shared_prefixes_are_zipfian():
    mix = dict(gen.load_mix("chat"),
               prefix={"n_prefixes": 4, "zipf_a": 1.5, "tokens": 16})
    plan = gen.plan(mix, 5, 60.0, 1000)
    heads = [tuple(p.prompt[:16]) for p in plan if len(p.prompt) > 16]
    counts = sorted((heads.count(h) for h in set(heads)), reverse=True)
    assert len(counts) <= 4 and counts[0] > counts[-1]
    assert math.isclose(sum(counts), len(heads))


@pytest.mark.parametrize("seconds", [40.0, 10.0])
def test_preroll_serves_the_end_of_the_plan_before_the_window(seconds):
    """A window that opens after a pre-roll is preceded by the last
    ``preroll_s`` seconds of its own plan (all of it, where the window is
    shorter), unmetered; the window itself holds the whole plan."""
    from bench import harness
    mix = gen.load_mix("chat")
    assert mix["window"]["start"] == "after_preroll"
    plan = gen.plan(mix, 2**31 + 11, seconds, 1000)
    sched, lead = harness._schedule(plan, mix, seconds)
    assert lead == min(float(mix["window"]["preroll_s"]), seconds)
    pre = [(t, p) for t, p, metered in sched if not metered]
    win = [(t, p) for t, p, metered in sched if metered]
    assert [p for _, p in win] == plan
    assert [t for t, _ in win] == [p.arrival_s for p in plan]
    assert [p for _, p in pre] == [p for p in plan
                                   if p.arrival_s >= seconds - lead]
    assert pre and all(-lead <= t < 0 for t, _ in pre)
    times = [t for t, _, _ in sched]
    assert times == sorted(times)
