"""Serving: kv-cache quantization, continuous-batching engine."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.configs import ARCHS, reduced
from repro.models.model import build_model
from repro.serving.engine import Engine, Request
from repro.serving.kv_cache import (dequantize, init_kv_int8, kv_bytes,
                                    quantize_token, update_kv_int8)


def test_quantize_roundtrip_bound(rng):
    x = jnp.asarray(rng.standard_normal((2, 4, 8, 64)), jnp.float32)
    q, s = quantize_token(x)
    back = dequantize(q, s)
    bound = np.abs(np.asarray(x)).max() / 127 + 1e-6
    assert np.abs(np.asarray(back) - np.asarray(x)).max() <= bound * 1.01


def test_kv_int8_update(rng):
    st = init_kv_int8(2, 4, 16, 8)
    k_new = jnp.asarray(rng.standard_normal((2, 4, 1, 8)), jnp.float32)
    v_new = jnp.asarray(rng.standard_normal((2, 4, 1, 8)), jnp.float32)
    slot = jnp.asarray([3, 5], jnp.int32)
    st2 = update_kv_int8(st, k_new, v_new, slot)
    back = dequantize(st2["k8"], st2["ks"])
    for b, sl in enumerate([3, 5]):
        np.testing.assert_allclose(np.asarray(back)[b, :, sl],
                                   np.asarray(k_new)[b, :, 0], atol=0.03)
    assert kv_bytes(st2) == kv_bytes(st)


@pytest.fixture(scope="module")
def served_model():
    cfg = reduced(ARCHS["qwen2-7b"])
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


@pytest.mark.parametrize("kv_mode", ["bf16", "int8"])
def test_engine_completes_all(served_model, kv_mode, rng):
    cfg, model, params = served_model
    eng = Engine(model, params, batch_slots=3, max_len=48, kv_mode=kv_mode,
                 eos_id=0)
    for rid in range(5):
        eng.submit(Request(rid=rid,
                           prompt=list(rng.integers(2, 400, 6 + rid)),
                           max_new=4))
    done = eng.run()
    assert len(done) == 5
    assert all(1 <= len(r.out) <= 4 for r in done)


def test_engine_batch_independence(served_model, rng):
    """Same prompt in different slots/batches -> identical greedy output."""
    cfg, model, params = served_model
    p = list(rng.integers(2, 400, 9))
    eng = Engine(model, params, batch_slots=2, max_len=48, eos_id=0)
    eng.submit(Request(rid=0, prompt=p, max_new=5))
    eng.submit(Request(rid=1, prompt=p, max_new=5))
    a, b = eng.run()
    assert a.out == b.out

    eng2 = Engine(model, params, batch_slots=1, max_len=48, eos_id=0)
    eng2.submit(Request(rid=2, prompt=p, max_new=5))
    (c,) = eng2.run()
    assert c.out == a.out


def test_engine_continuous_batching(served_model, rng):
    """More requests than slots: later requests reuse freed slots."""
    cfg, model, params = served_model
    eng = Engine(model, params, batch_slots=2, max_len=48, eos_id=0)
    for rid in range(6):
        eng.submit(Request(rid=rid, prompt=list(rng.integers(2, 400, 5)),
                           max_new=3))
    done = eng.run()
    assert sorted(r.rid for r in done) == list(range(6))


# -- eos_id default unification (ISSUE 4 bugfix) -----------------------------

def test_eos_default_single_constant():
    """Every layer's eos default is THE constant -- no more silent
    0-vs-1 divergence between construction paths."""
    import inspect
    from repro.configs.base import DEFAULT_EOS_ID
    from repro.data.pipeline import DataConfig
    from repro.serving.config import ServeConfig
    from repro.serving.paged_engine import PagedEngine
    assert ServeConfig.__dataclass_fields__["eos_id"].default \
        == DEFAULT_EOS_ID
    assert DataConfig.__dataclass_fields__["eos_id"].default \
        == DEFAULT_EOS_ID
    assert inspect.signature(Engine.__init__).parameters["eos_id"].default \
        == DEFAULT_EOS_ID
    assert inspect.signature(
        PagedEngine.__init__).parameters["eos_id"].default == DEFAULT_EOS_ID


def test_interpret_and_cold_cap_reach_paged_engine(served_model,
                                                   monkeypatch):
    """Pallas interpret mode comes from the platform, never from a
    config field: a ServeConfig.build() engine on a TPU runs compiled
    kernels.  ``max_cold_pages`` threads through ServeConfig/AssistSpec
    into EngineBase.from_config."""
    import inspect
    from repro.assist import AssistSpec
    from repro.kernels import pallas_interpret
    from repro.serving.config import ServeConfig
    from repro.serving.paged_engine import PagedEngine
    cfg, model, params = served_model
    assert "interpret" not in ServeConfig.__dataclass_fields__
    assert "interpret" not in AssistSpec.__dataclass_fields__
    assert "interpret" not in inspect.signature(PagedEngine).parameters
    assert pallas_interpret() is (jax.default_backend() != "tpu")
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert pallas_interpret() is False
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    assert pallas_interpret() is True
    assert pallas_interpret(False) is False     # compile tests only
    monkeypatch.undo()
    spec = AssistSpec(paged=True, enable_warm=True, enable_cold=True,
                      max_cold_pages=5, use_roofline_trigger=False)
    scfg = ServeConfig(arch="qwen2-7b", reduced=True, slots=2, max_len=48,
                       assist=spec)
    eng, _, _ = scfg.build(model, params)
    # the cap reached the pool sizing: page-id space = hot + warm + cap
    assert eng.pool.num_pages == (eng.store.hot_pages
                                  + eng.store.warm_pages + 5)
    # flat-alias spelling folds into the spec identically
    flat = ServeConfig(arch="qwen2-7b", reduced=True, paged=True,
                       max_cold_pages=5)
    assert flat.assist.max_cold_pages == 5


def test_direct_and_config_construction_decode_identically(served_model, rng):
    """Regression: Engine(...) with default eos_id vs ServeConfig.build()
    (which threads ServeConfig.eos_id) must stop on the same token and
    produce identical greedy outputs."""
    from repro.serving.config import ServeConfig
    cfg, model, params = served_model
    prompts = [list(rng.integers(2, 400, 7 + i)) for i in range(3)]

    direct = Engine(model, params, batch_slots=2, max_len=48)  # default eos
    for i, p in enumerate(prompts):
        direct.submit(Request(rid=i, prompt=p, max_new=5))
    want = {r.rid: r.out for r in direct.run()}

    scfg = ServeConfig(arch="qwen2-7b", reduced=True, slots=2, max_len=48)
    built, _, _ = scfg.build(model, params)
    assert built.eos_id == direct.eos_id
    for i, p in enumerate(prompts):
        built.submit(Request(rid=i, prompt=p, max_new=5))
    got = {r.rid: r.out for r in built.run()}
    assert got == want
