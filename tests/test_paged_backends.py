"""Attention-backend equivalence matrix for the paged decode path.

Backends (kernels/decode_attn/ops.py registry): ``gather`` (jnp),
``pallas`` (bf16 paged kernel), ``pallas_int8`` (tiered kernel, in-VMEM
warm dequant).  Models: uniform GQA stack, local-attention windows, and a
non-uniform head/tail stack (MoE first_dense head + tail layer) -- the
per-layer capability dispatch coverage.

Bars:
  * hot-only: every backend is TOKEN-IDENTICAL to the dense engine
  * int8 warm tier in play: backends agree with EACH OTHER (int8 is lossy
    vs dense, but the representation -- and so the tokens -- must not
    depend on which backend reads it)
"""
import dataclasses

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.cache import TierConfig
from repro.configs import ARCHS, reduced
from repro.configs.base import MoEConfig
from repro.kernels.decode_attn import ops as attn_ops
from repro.kernels.decode_attn.ops import attn_backend_names
from repro.models import transformer as T
from repro.models.model import build_model
from repro.serving.engine import Engine, Request
from repro.serving.paged_engine import PagedEngine

BACKENDS = ("gather", "pallas", "pallas_int8")

HOT_ONLY = TierConfig(page_size=16, hbm_budget_bytes=1 << 30,
                      enable_warm=False, enable_cold=False)


def _model_cfg(kind: str):
    base = reduced(ARCHS["qwen2-7b"])
    if kind == "uniform":
        return base
    if kind == "local":
        return dataclasses.replace(base, name="qwen2-local", n_layers=4,
                                   block_pattern=("attn", "attn_local"),
                                   window=8)
    if kind == "headtail":
        # MoE first_dense -> one unstacked head layer; n_layers % pattern
        # -> one unstacked tail layer; scan covers the middle
        return dataclasses.replace(
            base, name="qwen2-headtail", n_layers=6,
            block_pattern=("attn", "attn_local"), window=8,
            moe=MoEConfig(n_routed=4, n_shared=1, top_k=2, d_expert=32,
                          first_dense=1))
    raise ValueError(kind)


@pytest.fixture(scope="module", params=["uniform", "local", "headtail"])
def served(request):
    cfg = _model_cfg(request.param)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    prompts = [list(rng.integers(2, 400, 6 + i)) for i in range(3)]
    dense = Engine(model, params, batch_slots=3, max_len=48, eos_id=0)
    for i, p in enumerate(prompts):
        dense.submit(Request(rid=i, prompt=p, max_new=4))
    want = {r.rid: r.out for r in dense.run()}
    return cfg, model, params, prompts, want


def _run_paged(model, params, prompts, tier, backend, lanes=3):
    eng = PagedEngine(model, params, lanes=lanes, max_len=48, tier=tier,
                      eos_id=0, use_roofline_trigger=False, backend=backend)
    for i, p in enumerate(prompts):
        eng.submit(Request(rid=i, prompt=p, max_new=4))
    got = {r.rid: r.out for r in eng.run()}
    eng.pool.check()
    return got, eng


@pytest.mark.parametrize("backend", BACKENDS)
def test_hot_only_token_identical_to_dense(served, backend):
    cfg, model, params, prompts, want = served
    got, _ = _run_paged(model, params, prompts, HOT_ONLY, backend)
    assert got == want, f"{cfg.name}/{backend} diverged from dense"


def test_int8_warm_backends_agree(served):
    """Tight hot tier forces parked pages down to int8; every backend must
    read the same warm representation to the same tokens."""
    cfg, model, params, _, want = served
    plan = T.stack_plan(cfg)
    from repro.cache import PageGeometry
    geom = PageGeometry(len(plan.pattern), plan.n_scan, cfg.n_kv_heads, 16,
                        cfg.head_dim,
                        seg_stacks=tuple(s.n_stack
                                         for s in T.paged_segments(cfg)))
    # two-page prompts + a 5-hot-page tier: the lane and one parked
    # request fit hot, admitting the third forces the parked one's pages
    # down to int8 warm (admit-then-demote, not serialization)
    rng = np.random.default_rng(1)
    prompts = [list(rng.integers(2, 400, 20 + 2 * i)) for i in range(3)]
    tier = TierConfig(page_size=16,
                      hbm_budget_bytes=10 * geom.hot_page_bytes,
                      hot_fraction=0.5, enable_warm=True, enable_cold=False)
    outs = {}
    demoted = {}
    for backend in BACKENDS:
        got, eng = _run_paged(model, params, prompts, tier, backend, lanes=1)
        outs[backend] = got
        demoted[backend] = eng.stats()["store"]["demote_warm"]
        assert sorted(got) == [0, 1, 2], f"{backend}: lost requests"
    assert outs["pallas"] == outs["gather"], cfg.name
    assert outs["pallas_int8"] == outs["gather"], cfg.name
    # the test only means something if the warm tier was actually read
    assert all(d > 0 for d in demoted.values()), demoted


def test_registry_names_and_unknown():
    from repro.kernels.decode_attn import ops
    assert set(BACKENDS) <= set(attn_backend_names())
    with pytest.raises(KeyError, match="registered"):
        ops.get_attn_backend("nope")


def test_per_layer_capability_dispatch():
    """Unsupported layers are reported per layer, not as a whole-model
    boolean.  Since the page-kind generalization (MLA latent pages,
    SSM/RWKV state slabs, weight-shared attention) every decoder layer
    kind is covered -- the audio encoder is the only remaining
    unsupported stack, and a hypothetical future kind is still tagged at
    its exact position."""
    for name, cfg in ARCHS.items():
        r = reduced(cfg)
        bad = T.paged_unsupported_layers(r)
        assert T.paged_decode_supported(r) == (not bad)
        if cfg.frontend == "audio":
            assert bad == ["*:audio-encoder"], (name, bad)
        else:
            assert bad == [], (name, bad)
    future = dataclasses.replace(reduced(ARCHS["qwen2-7b"]), name="future",
                                 block_pattern=("attn", "future_kind"))
    assert T.paged_unsupported_layers(future) == ["pattern[1]:future_kind"]


def test_paged_segments_layout():
    cfg = _model_cfg("headtail")
    segs = T.paged_segments(cfg)
    assert [(s.name, s.kind, s.n_stack) for s in segs] == [
        ("head_0", "attn_dense", 1),
        ("pat_0", "attn", 2), ("pat_1", "attn_local", 2),
        ("tail_0", "attn", 1)]


def test_tiered_kernel_matches_gather_backend(rng):
    """Unit-level: the mixed hot/warm Pallas kernel against the gather
    backend on a random encoded table, global and windowed."""
    from repro.kernels.decode_attn import ops
    B, H, G, D, ps, NP = 2, 4, 2, 32, 3, 3
    hot_n, warm_n = 5, 4
    q = jnp.asarray(rng.standard_normal((B, H, D)), jnp.bfloat16)
    pools = {
        "kh": jnp.asarray(rng.standard_normal((1 + hot_n, G, ps, D)),
                          jnp.bfloat16),
        "vh": jnp.asarray(rng.standard_normal((1 + hot_n, G, ps, D)),
                          jnp.bfloat16),
        "k8": jnp.asarray(rng.integers(-127, 128, (1 + warm_n, G, ps, D)),
                          jnp.int8),
        "v8": jnp.asarray(rng.integers(-127, 128, (1 + warm_n, G, ps, D)),
                          jnp.int8),
        "ks": jnp.asarray(rng.uniform(0.005, 0.02, (1 + warm_n, G, ps)),
                          jnp.float32),
        "vs": jnp.asarray(rng.uniform(0.005, 0.02, (1 + warm_n, G, ps)),
                          jnp.float32),
    }
    # encoded table: mix of hot (>0), warm (<0), trash (0) entries
    bt = jnp.asarray([[1, -2, 3], [-1, 2, 0]], jnp.int32)
    lengths = jnp.asarray([NP * ps, 2 * ps - 1], jnp.int32)
    for window in (0, 5):
        ref = ops.attn_backend_gather(q, pools, bt, lengths, window=window)
        out = ops.attn_backend_pallas_int8(q, pools, bt, lengths,
                                           window=window)
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(ref, np.float32), atol=2e-2)
        out2 = ops.attn_backend_pallas(q, pools, bt, lengths, window=window)
        np.testing.assert_allclose(np.asarray(out2, np.float32),
                                   np.asarray(ref, np.float32), atol=2e-2)


# -- the tiered kernel's iteration space -------------------------------------
#
# One jitted kernel and one gather per (geometry, window), shared by the
# cases below, so each shape compiles once.  Page size 16 and 20-page
# tables give two 16-page blocks, the second partial.  The trash slot of
# every pool holds NaN: the kernel must never fetch it, and must select,
# not multiply, wherever a stale row could reach the sums.

_GEOMS = {"qwen2-7b": (4, 7, 128), "starcoder2-3b": (2, 12, 128)}
_PS, _NP, _LANES = 16, 20, 3
_tiered_jit = jax.jit(attn_ops.attn_backend_pallas_int8,
                      static_argnames=("window", "has_warm"))
_gather_jit = jax.jit(attn_ops.attn_backend_gather,
                      static_argnames=("window", "has_warm"))

# name: (geometry, window, tiers of the live pages, lane lengths, what the
# entries past each length hold[, lanes whose whole table is trash])
_KERNEL_CASES = {
    "all_hot": ("qwen2-7b", 0, "hot", (320, 100, 17), "trash"),
    "all_warm": ("qwen2-7b", 0, "warm", (320, 100, 17), "trash"),
    "mixed_block_boundary": ("qwen2-7b", 0, "mixed", (300, 256, 33),
                             "trash"),
    "page_boundaries": ("qwen2-7b", 0, "mixed", (32, 16, 48), "trash"),
    # lane 2 as the engine dispatches an idle lane: this tick's token
    # (length 1) over an all-trash table
    "idle_lanes": ("qwen2-7b", 0, "mixed", (0, 200, 1), "trash", (2,)),
    "pages_past_length": ("qwen2-7b", 0, "mixed", (100, 5, 250), "pages"),
    "starcoder_hot": ("starcoder2-3b", 0, "hot", (320, 257, 1), "trash"),
    "starcoder_mixed": ("starcoder2-3b", 0, "mixed", (320, 200, 64),
                        "pages"),
    "window_past_block0": ("qwen2-7b", 40, "mixed", (300, 320, 30),
                           "trash"),
    "window_hot": ("qwen2-7b", 40, "hot", (290, 64, 256), "trash"),
}


def _kernel_case(name):
    geom, window, tiers, lengths, past, *trash = _KERNEL_CASES[name]
    G, group, D = _GEOMS[geom]
    rng = np.random.default_rng(sum(map(ord, name)))
    n = _LANES * _NP
    shp, sc = (1 + n, G, _PS, D), (1 + n, G, _PS)
    pools = {
        "kh": rng.standard_normal(shp).astype(np.float32),
        "vh": rng.standard_normal(shp).astype(np.float32),
        "k8": rng.integers(-127, 128, shp).astype(np.float32),
        "v8": rng.integers(-127, 128, shp).astype(np.float32),
        "ks": rng.uniform(0.005, 0.02, sc).astype(np.float32),
        "vs": rng.uniform(0.005, 0.02, sc).astype(np.float32),
    }
    for a in ("kh", "vh", "ks", "vs"):
        pools[a][0] = np.nan                     # the trash slot
    pools = {k: jnp.asarray(v, {"kh": jnp.bfloat16, "vh": jnp.bfloat16,
                                "k8": jnp.int8, "v8": jnp.int8}.get(
                                    k, jnp.float32))
             for k, v in pools.items()}
    slots = rng.permutation(np.arange(1, n + 1)).reshape(_LANES, _NP)
    warm = {"hot": np.zeros((_LANES, _NP), bool),
            "warm": np.ones((_LANES, _NP), bool),
            "mixed": rng.random((_LANES, _NP)) < 0.5}[tiers]
    bt = np.where(warm, -slots, slots)
    live = np.arange(_NP)[None, :] < -(-np.asarray(lengths)[:, None] // _PS)
    if past == "trash":
        bt = np.where(live, bt, 0)
    for lane in trash[0] if trash else ():
        bt[lane] = 0
    q = jnp.asarray(rng.standard_normal((_LANES, G * group, D)),
                    jnp.bfloat16)
    return (q, pools, jnp.asarray(bt, jnp.int32),
            jnp.asarray(lengths, jnp.int32), window)


@pytest.mark.parametrize("case", list(_KERNEL_CASES))
def test_tiered_kernel_iteration_space(case):
    """The tiered kernel, which fetches each live page from its own tier
    in double-buffered blocks, against the gather backend: all-hot,
    all-warm and mixed blocks, trash or stale pages past the length,
    idle lanes, lengths on page and block boundaries and at the full
    table, a table that is not a whole number of blocks, and a window
    whose first live block is past block 0."""
    q, pools, bt, lengths, window = _kernel_case(case)
    out = np.asarray(_tiered_jit(q, pools, bt, lengths, window=window),
                     np.float32)
    ref = np.asarray(_gather_jit(q, pools, bt, lengths, window=window),
                     np.float32)
    assert np.isfinite(out).all()
    # a lane with no key or no fetched page reads zeros (the gather
    # backend attends to the trash slot there)
    empty = (np.asarray(lengths) == 0) | (np.asarray(bt) == 0).all(axis=1)
    assert not out[empty].any()
    np.testing.assert_allclose(out[~empty], ref[~empty], atol=2e-2)
