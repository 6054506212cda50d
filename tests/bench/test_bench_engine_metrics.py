"""The readers of what the engine measures itself: its request stamps
(``admit_wait_p95_ms``, ``first_token_lag_p95_ms``) and its named
decode-attention kernel (``attn_kernel_ms``); and the trace readers that
came before them, pinned on the recorded chip trace."""
from __future__ import annotations

import gzip
import json
from types import SimpleNamespace as NS

import numpy as np
import pytest

from bench import families
from bench import layer_metrics as LM
from bench import metrics as M
from bench import trace_reduce as T
from bench.peaks import PEAKS
from bench_fixtures import ROOT

CHIP_TRACE = ROOT / "tests" / "bench" / "data" / "chat_trace_extract.json.gz"

KERNEL_OP = ('%paged_decode_attn_tiered.3 = bf16[32,4,7,128] custom-call('
             '%p.1), custom_call_target="tpu_custom_call"')
#: a layout copy of the kernel's result: the kernel's name is only in
#: its metadata
COPY_OP = ('%copy.11 = bf16[32,4,7,128] copy(%paged_decode_attn_tiered.3), '
           'metadata={op_name="jit(step_fn)/paged_decode_attn_tiered/'
           'pallas_call"}')


def _synthetic_trace():
    """Two traced decode steps, each a ``bench.step`` holding the
    engine's spans; on the device, 14 kernel calls of 3 ms, each followed
    by a 1 ms layout copy, and one fusion of 20 ms."""
    host, ops, modules = [], [], []
    for k in range(2):
        t0 = k * 200_000_000
        host += [["bench.step", t0, 150_000_000],
                 ["engine.step", t0 + 1_000, 149_000_000],
                 ["engine.lanes", t0 + 2_000, 3_000_000],
                 ["engine.dispatch", t0 + 6_000_000, 2_000_000],
                 ["engine.harvest", t0 + 9_000_000, 140_000_000],
                 ["engine.harvest_wait", t0 + 9_500_000, 139_000_000]]
        modules.append([f"jit_step_fn({k})", t0 + 8_000_000, 130_000_000])
        t = t0 + 8_000_000
        for _ in range(14):
            ops.append([KERNEL_OP, t, 3_000_000])
            ops.append([COPY_OP, t + 3_000_000, 1_000_000])
            t += 4_000_000
        ops.append(["%fusion.7 = bf16[32,3584] fusion(%a)", t, 20_000_000])
    return T.from_extract({"planes": [
        {"name": "/host:CPU", "lines": [{"name": "python", "events": host}]},
        {"name": "/device:TPU:0", "lines": [
            {"name": T.MODULES_LINE, "events": modules},
            {"name": T.OPS_LINE, "events": ops}]}]})


def test_attn_kernel_ms_reads_the_named_kernel():
    r = T.reduce(_synthetic_trace())
    assert r.module_time_s(LM.DECODE)[0] == 2
    ms = M.reader("attn_kernel_ms")({"trace": r})
    # 14 calls of 3 ms per step; the copies (1 ms each) are not counted
    assert ms == pytest.approx(42.0)
    # the kernel's own name and its call target find the same time
    n, s = r.op_time_s(LM.ATTN_KERNEL)
    assert n == 28 and ms * 2 == pytest.approx(s * 1e3)


def test_attn_kernel_ms_reads_nothing_without_the_name(chip_trace):
    # the recorded trace predates the kernel's name: its kernel op is
    # ``closed_call.12``
    assert M.reader("attn_kernel_ms")({"trace": chip_trace}) is None
    assert M.reader("attn_kernel_ms")({}) is None


def _recs(stamps):
    return [NS(req=NS(**s)) for s in stamps]


STAMPS = [dict(t_submit=10.0 + i, t_admit=10.0 + i + 0.002 * (i % 5),
               t_first=10.0 + i + 0.002 * (i % 5) + 0.15 + 0.01 * i)
          for i in range(30)]


@pytest.mark.parametrize("metric, a, b", [
    ("admit_wait_p95_ms", "t_submit", "t_admit"),
    ("first_token_lag_p95_ms", "t_admit", "t_first"),
])
def test_request_stamp_readers(metric, a, b):
    read = M.reader(metric)
    want = np.percentile([(s[b] - s[a]) * 1e3 for s in STAMPS], 95)
    assert read({"recs": _recs(STAMPS)}) == pytest.approx(want)
    # a request not yet admitted (or without its first token) is left
    # out, not read as zero
    late = dict(STAMPS[0], t_admit=None, t_first=None)
    assert read({"recs": _recs(STAMPS + [late])}) == pytest.approx(want)
    # an engine that stamps nothing reads nothing
    assert read({"recs": [NS(req=NS(rid=1))]}) is None
    assert read({}) is None


@pytest.fixture(scope="module")
def chip_trace():
    with gzip.open(CHIP_TRACE) as f:
        return T.reduce(T.from_extract(json.load(f)))


#: the values the trace readers gave on the recorded chip trace, with
#: three fixed decode-step samples and one prefill of 577 tokens
PINNED = {"decode_step_ms": 123.65628700000002,
          "prefill_share": 24.159038900296636,
          "prefill_ms_per_ktok": 198.2030311958406,
          "device_idle_share": 10.007175567556526,
          "paged_attn_roofline": 1.4130799034459138,
          "step_mfu": 5.538846816884488}


@pytest.mark.parametrize("metric", sorted(PINNED))
def test_trace_readers_read_as_before(chip_trace, metric):
    conf = json.loads((ROOT / "bench" / "configs" / "qwen2-7b.json")
                      .read_text())
    steps = [(0.1 * k, [600 + k + 37 * i for i in range(lanes)], 0.0, 0.0)
             for k, lanes in enumerate((32, 32, 30))]
    ctx = {"trace": chip_trace, "steps": steps, "prefill_tokens": [577],
           "counts": families.load(conf).counts(conf["config"]),
           "page_size": 16, "peaks": PEAKS["TPU v5 lite"]}
    assert M.reader(metric)(ctx) == pytest.approx(PINNED[metric], rel=1e-12)
