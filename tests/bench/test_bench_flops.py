"""The benchmark's FLOP and byte counts against hand arithmetic, at the
shapes of both served configurations."""
from __future__ import annotations

import json

import pytest

from bench import flops as F
from bench.peaks import PEAKS, peaks_for
from bench_fixtures import ROOT


def shapes_of(name):
    conf = json.loads((ROOT / "bench" / "configs" / f"{name}.json")
                      .read_text())
    return F.shapes(conf["config"])


@pytest.mark.parametrize("name, layer_params, per_token", [
    # qwen2-7b, 14 layers: attention 2*3584^2 + 2*3584*512 = 29,360,128;
    # SwiGLU 3*3584*18944 = 203,685,888; head 3584*152064 = 544,997,376
    ("qwen2-7b", 233_046_016, 2 * (14 * 233_046_016 + 544_997_376)),
    # starcoder2-3b, 30 layers: attention 2*3072^2 + 2*3072*256 =
    # 20,447,232; GELU MLP 2*3072*12288 = 75,497,472; tied head 3072*49152
    ("starcoder2-3b", 95_944_704, 2 * (30 * 95_944_704 + 150_994_944)),
])
def test_model_flops_per_token(name, layer_params, per_token):
    s = shapes_of(name)
    assert s.layer_matmul_params == layer_params
    assert F.matmul_flops_per_token(s) == per_token


@pytest.mark.parametrize("name, per_key", [
    ("qwen2-7b", 4 * 14 * 28 * 128),
    ("starcoder2-3b", 4 * 30 * 24 * 128),
])
def test_attention_and_prefill_flops(name, per_key):
    s = shapes_of(name)
    assert F.attn_flops(s, 1000) == per_key * 1000
    mm = F.matmul_flops_per_token(s)
    assert F.decode_token_flops(s, 7) == mm + per_key * 7
    # a 3-token causal prefill: its queries see 1 + 2 + 3 keys
    assert F.prefill_flops(s, 3) == 3 * mm + per_key * 6


@pytest.mark.parametrize("name, hot, warm", [
    # K and V, 4 KV heads, 16 tokens: bf16 rows of 128 * 2 bytes; int8
    # rows of 128 bytes plus one f32 scale
    ("qwen2-7b", 2 * 4 * 16 * 256, 2 * 4 * 16 * 132),
    ("starcoder2-3b", 2 * 2 * 16 * 256, 2 * 2 * 16 * 132),
])
def test_kv_page_bytes(name, hot, warm):
    s = shapes_of(name)
    assert F.kv_page_bytes(s, 16, warm=False) == hot
    assert F.kv_page_bytes(s, 16, warm=True) == warm


def test_paged_attn_cost_by_hand():
    s = shapes_of("qwen2-7b")
    flops, nbytes = F.paged_attn_cost(s, [16, 17], 16)
    assert flops == 4 * 28 * 128 * 33
    # 1 + 2 hot pages, and a bf16 query and output row per lane
    assert nbytes == 3 * 32_768 + 2 * 28 * 128 * 2 * 2
    _, warm_bytes = F.paged_attn_cost(s, [16, 17], 16, warm_share=1.0)
    assert warm_bytes == 3 * 16_896 + 2 * 28 * 128 * 2 * 2


def test_least_time_takes_the_larger_bound():
    p = PEAKS["TPU v5 lite"]
    assert F.least_time(197e12, 0.0, p) == pytest.approx(1.0)
    assert F.least_time(0.0, 819e9 * 2, p) == pytest.approx(2.0)
    assert F.least_time(197e12, 819e9 * 2, p) == pytest.approx(2.0)


def test_peaks_refuse_unknown_devices():
    assert peaks_for("tpu", "TPU v5 lite")["hbm_bw"] == 819e9
    with pytest.raises(KeyError):
        peaks_for("tpu", "TPU v99")
    with pytest.raises(KeyError):
        peaks_for("cpu", "cpu")
