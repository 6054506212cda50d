"""The trace reduction: busy and idle time as a union of device
intervals, time by program and by operation name, idle gaps labelled by
the host event open in them."""
from __future__ import annotations

import gzip
import json
from types import SimpleNamespace as NS

import pytest

from bench import layer_metrics as LM
from bench import trace_reduce as T
from bench_fixtures import ROOT

#: the first 400 ms of a traced qwen2-7b.chat window on one TPU v5e
#: (bench.trace_reduce.extract of the profiler's .xplane.pb)
CHIP_TRACE = ROOT / "tests" / "bench" / "data" / "chat_trace_extract.json.gz"


def _ev(name, start, dur, **stats):
    return NS(name=name, start_ns=start, duration_ns=dur,
              stats=list(stats.items()))


def _pd(host, ops, modules):
    return NS(planes=[
        NS(name="/host:CPU", lines=[NS(name="python", events=host)]),
        NS(name="/device:TPU:0", lines=[
            NS(name=T.MODULES_LINE, events=modules),
            NS(name=T.OPS_LINE, events=ops)]),
    ])


def test_union_merges_overlaps():
    assert T._union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [[0, 3], [5, 8]]
    assert T._clip([(0, 10), (20, 30)], 5, 25) == [(5, 10), (20, 25)]


def test_reduce_by_hand():
    host = [_ev("bench.step", 0, 100), _ev("bench.step", 100, 100),
            _ev("PjitFunction(prefill_fn)", 110, 30)]
    ops = [_ev("fusion.1", 10, 20), _ev("fusion.1", 20, 20),   # overlap
           _ev("kernel.7", 60, 30, long_name="pallas _tiered_kernel"),
           _ev("fusion.2", 150, 40), _ev("late", 250, 10)]     # outside
    modules = [_ev("jit_step_fn(1)", 10, 80),
               _ev("jit_prefill_fn(2)", 150, 40)]
    r = T.reduce(_pd(host, ops, modules))
    assert r.window_ns == (0, 200)
    # busy: [10, 40) + [60, 90) + [150, 190)
    assert r.busy_ns == 100
    assert r.module_time_s("step_fn") == (1, pytest.approx(80e-9))
    assert r.module_time_s("prefill_fn") == (1, pytest.approx(40e-9))
    assert r.op_time_s("_tiered_kernel") == (1, pytest.approx(30e-9))
    assert r.op_time_s("fusion") == (3, pytest.approx(80e-9))
    # idle gaps: [90, 150) is the longest, its midpoint inside the
    # prefill dispatch of the second step
    assert r.gaps[0][0] == "bench.step > PjitFunction(prefill_fn)"
    assert r.gaps[0][1] == pytest.approx(60e-9)
    assert [g[1] for g in r.gaps] == pytest.approx(
        [60e-9, 20e-9, 10e-9, 10e-9])
    # per-name totals add durations (fusion.1 ran twice)
    assert sorted(s for _, s in r.top_ops(2)) == pytest.approx([40e-9, 40e-9])


def test_reduce_refuses_a_trace_without_spans_or_devices():
    with pytest.raises(ValueError):
        T.reduce(_pd([], [_ev("x", 0, 1)], []))
    pd = _pd([_ev("bench.step", 0, 10)], [], [])
    pd.planes = pd.planes[:1]
    with pytest.raises(ValueError):
        T.reduce(pd)


@pytest.fixture(scope="module")
def chip_trace():
    with gzip.open(CHIP_TRACE) as f:
        data = json.load(f)
    return data, T.reduce(T.from_extract(data))


def test_chip_trace_busy_time_is_the_union_of_ops(chip_trace):
    data, r = chip_trace
    dev = [p for p in data["planes"] if p["name"] == "/device:TPU:0"][0]
    ops = [ln for ln in dev["lines"] if ln["name"] == T.OPS_LINE][0]
    lo, hi = r.window_ns
    # an independent sweep over the op intervals, clipped to the window
    edges = sorted((max(s, lo), min(s + d, hi)) for _, s, d in
                   ops["events"] if s + d > lo and s < hi)
    busy, end = 0, lo
    for s, e in edges:
        if e > end:
            busy += e - max(s, end)
            end = e
    assert r.busy_ns == busy
    assert r.window_s == pytest.approx(0.526015534)
    assert r.busy_s == pytest.approx(0.473376236)
    assert sum(ns for _, ns in r.gaps) <= (r.window_s - r.busy_s) + 1e-12
    assert all(label.startswith("bench.") for label, _ in r.gaps)


def test_chip_trace_time_by_program_and_kernel(chip_trace):
    _, r = chip_trace
    assert r.module_time_s(LM.DECODE) == (3, pytest.approx(0.370968861))
    assert r.module_time_s(LM.PREFILL) == (1, pytest.approx(0.114363149))
    n, s = r.op_time_s(LM.ATTN_KERNEL)
    # the paged-attention kernel runs once per layer (14) of each decode
    # step; the window cuts the first step short
    assert 2 * 14 < n <= 3 * 14 and s == pytest.approx(0.27415122)
    assert s < r.module_time_s(LM.DECODE)[1]
