"""What makes each left-out cell what it is, read from its mix and
configuration files, and which per-layer metrics a result line carries
in a closed loop and in an open one."""
from __future__ import annotations

import gzip
import json
import time

import pytest

from bench import harness
from bench import trace_reduce as T
from bench.traffic import gen
from bench_fixtures import LD_CELL, ROOT, TINY_CELL, make_tiny_root

SEED = 2**33 + 101
SECONDS = 51.0


def _plan(config, traffic):
    conf = json.loads((ROOT / "bench" / "configs" / f"{config}.json")
                      .read_text())
    mix = gen.load_mix(traffic)
    return conf, mix, gen.plan(mix, SEED, SECONDS,
                               conf["config"]["vocab_size"])


def test_code_cell_is_prompt_heavy():
    """Code completion: a long file context in, a short completion out.
    Prompt tokens outweigh answer tokens at least ten to one."""
    _, mix, plan = _plan("starcoder2-3b", "code")
    assert mix["loop"] == "open"
    prompt = sum(len(p.prompt) for p in plan)
    answer = sum(p.max_new for p in plan)
    assert prompt >= 10 * answer


def _hot_tokens(conf) -> int:
    """Tokens the hot tier holds: its share of the page budget over the
    bytes of one bf16 page (K and V of every layer and KV head)."""
    c, s = conf["config"], conf["serving"]
    dh = c["hidden_size"] // c["num_attention_heads"]
    page = (c["num_hidden_layers"] * 2 * c["num_key_value_heads"]
            * s["page_size"] * dh * 2)
    return int(s["hbm_budget_bytes"] * s["hot_fraction"]) // page \
        * s["page_size"]


def test_longdoc_cell_outgrows_the_hot_tier():
    """At the closed loop's full occupancy (one request per client, each
    holding at least its prompt) the resident tokens exceed what the hot
    tier holds, so KV has to sit in the warm tier."""
    conf, mix, plan = _plan("qwen2-7b", "longdoc-tiered")
    assert mix["loop"] == "closed"
    hot = _hot_tokens(conf)
    assert hot == 56160          # as qwen2-7b.json's "memory" states
    resident = sum(len(p.prompt) for p in plan[:int(mix["clients"])])
    assert resident > hot
    assert int(mix["clients"]) > mix["serve"]["lanes"]


#: the real cell the tiny open-loop cell stands for in the test checkout
STANDS_FOR = {"qwen2-7b.chat": TINY_CELL}
#: the closed loop's metrics: (name, unit, better, source, layer)
CLOSED = [("decode_step_ms.batch", "ms", "lower", "device_trace",
           "model step"),
          ("paged_attn_roofline.batch", "%", "higher", "device_trace",
           "kernels"),
          ("step_mfu.batch", "%", "higher", "device_trace", "whole step"),
          ("device_idle_share.batch", "%", "lower", "device_trace",
           "device"),
          ("warm_resident_share", "%", "higher", "program_counter", "cache"),
          ("cold_moves_per_s", "pages/s", "lower", "program_counter",
           "cache")]
CLOSED_ONLY = {c[0] for c in CLOSED}


@pytest.fixture(scope="module")
def routed_root(tmp_path_factory):
    """The tiny checkout, with ``BENCHMARK.json``'s lists of cells as the
    real file has them, the chat cell's name replaced by its tiny
    stand-in, and the closed loop's metrics listed for the tiny closed
    loop alone."""
    root = make_tiny_root(tmp_path_factory.mktemp("routed"))
    real = json.loads((ROOT / "BENCHMARK.json").read_text())
    man = json.loads((root / "BENCHMARK.json").read_text())

    def route(m):
        if "workloads" not in m:
            return m
        return dict(m, workloads=[STANDS_FOR[c] for c in m["workloads"]
                                  if c in STANDS_FOR])
    man["end_to_end"] = [route(m) for m in real["end_to_end"]]
    man["per_layer"] = [route(m) for m in real["per_layer"]] + [
        {"name": n, "unit": u, "better": b, "source": src, "layer": layer,
         "moves": "output_tokens_per_s", "workloads": [LD_CELL]}
        for n, u, b, src, layer in CLOSED]
    (root / "BENCHMARK.json").write_text(json.dumps(man))
    return root


@pytest.fixture
def recorded_trace(monkeypatch):
    """The profiler's trace of a CPU run has no TPU plane: the reduction
    reads the recorded chip trace in its place."""
    with gzip.open(ROOT / "tests" / "bench" / "data"
                   / "chat_trace_extract.json.gz") as f:
        data = json.load(f)
    monkeypatch.setattr(T, "load", lambda _dir: T.from_extract(data))


@pytest.mark.parametrize("cell, closed", [(LD_CELL, True),
                                          (TINY_CELL, False)])
def test_traced_line_carries_the_cache_metrics_in_a_closed_loop(
        routed_root, recorded_trace, cell, closed):
    out = harness.run_cell(cell, 2**31 + 7, 2.0, True,
                           t_start=time.perf_counter(), root=routed_root,
                           require_tpu=False, compile_cache=False)
    got = set(out["metrics"])
    if closed:
        assert CLOSED_ONLY <= got
        assert out["metrics"]["warm_resident_share"]["value"] > 0
        assert "itl_p50_ms" not in got and "decode_step_ms" not in got
    else:
        assert not CLOSED_ONLY & got
        assert {"decode_step_ms", "prefill_share", "step_mfu"} <= got
    assert out["device"]["busy_s"] > 0
