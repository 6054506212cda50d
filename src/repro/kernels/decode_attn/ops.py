"""jit'd wrappers for compressed-KV flash-decode."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.decode_attn import decode_attn as da
from repro.kernels.decode_attn import ref as da_ref

quantize_kv = da_ref.quantize_kv


@functools.partial(jax.jit, static_argnames=("bs",))
def decode_attn_q8(q, k8, ks, v8, vs, lengths, *, bs: int = 128):
    """Flash-decode over int8 KV (CABA compressed-KV site)."""
    return da.decode_attn(q, k8, ks, v8, vs, lengths, bs=bs)


@functools.partial(jax.jit, static_argnames=("bs",))
def decode_attn_raw(q, k, v, lengths, *, bs: int = 128):
    """Uncompressed-KV baseline with the identical flash schedule."""
    B, G, S, _ = k.shape
    dummy = jnp.ones((B, G, S), jnp.float32)
    return da.decode_attn(q, k, dummy, v, dummy, lengths, bs=bs)


@jax.jit
def paged_decode_attn_q8(q, k_pool, ks_pool, v_pool, vs_pool, block_table,
                         lengths):
    """Flash-decode gathering int8 KV pages through a block table
    (repro.cache warm tier; in-VMEM dequant after each page DMA)."""
    from repro.kernels.decode_attn import paged as pg
    return pg.paged_decode_attn(q, k_pool, ks_pool, v_pool, vs_pool,
                                block_table, lengths)


@jax.jit
def paged_decode_attn_raw(q, k_pool, v_pool, block_table, lengths):
    """bf16-page baseline with the identical paged schedule."""
    from repro.kernels.decode_attn import paged as pg
    P, G, ps, _ = k_pool.shape
    dummy = jnp.ones((P, G, ps), jnp.float32)
    return pg.paged_decode_attn(q, k_pool, dummy, v_pool, dummy,
                                block_table, lengths)


# ---------------------------------------------------------------------------
# attention-backend registry (paged decode)
# ---------------------------------------------------------------------------
#
# A backend computes one layer's paged decode attention over the tiered
# pools.  Uniform signature:
#
#   backend(q, pools_j, bt, lengths, *, window=0, has_warm=True) -> out
#
#   q        bf16[B, H, dh]        this tick's queries (post-rope)
#   pools_j  one layer's tier pools: kh/vh bf16[1+hot, G, ps, dh],
#            k8/v8 int8[1+warm, G, ps, dh], ks/vs f32[1+warm, G, ps]
#   bt       int32[B, maxp]        ENCODED locations (>0 hot slot, <0 warm
#                                  slot -loc, 0 trash -- repro.cache tiers)
#   lengths  int32[B]              valid tokens INCLUDING this tick's write
#   window   static; >0 masks attention to the last `window` positions
#   has_warm static; False promises bt >= 0 so the int8 tier compiles out
#
# The engine picks a backend by name (ServeConfig.attn_backend /
# PagedEngine(backend=...)); models/transformer.py threads the choice into
# every attention layer.  All backends are numerically interchangeable:
# gather is the jnp baseline, pallas runs the bf16 kernel (warm pages paid
# for by a dense dequant materialization per step), pallas_int8 reads warm
# pages as int8 and dequantizes in VMEM right after the DMA (the CABA
# fused-decompression path).

ATTN_BACKENDS: dict = {}


def register_attn_backend(name: str):
    def deco(fn):
        ATTN_BACKENDS[name] = fn
        return fn
    return deco


def get_attn_backend(name: str):
    try:
        return ATTN_BACKENDS[name]
    except KeyError:
        raise KeyError(f"unknown attention backend {name!r}; "
                       f"registered: {attn_backend_names()}") from None


def attn_backend_names() -> tuple:
    return tuple(sorted(ATTN_BACKENDS))


def _pool_valid(bt, lengths, ps: int, window: int):
    """bool[B, maxp*ps] position validity for a paged request."""
    maxp = bt.shape[1]
    pos = jnp.arange(maxp * ps)[None, :]
    valid = pos < lengths[:, None]
    if window:
        valid &= pos >= lengths[:, None] - window
    return valid


NEG_INF = -1e30


def masked_decode_attn(q, k, v, valid):
    """q: [B,H,dh]; k/v: [B,G,S,dh] (any float dtype); valid: bool[B,S]
    -> [B,H,dh].

    Plain (non-online) f32 softmax.  This is THE reference decode
    attention: the dense engine's cache path
    (models/transformer.py::_masked_decode_attn) delegates here, so the
    gather backend is bit-identical to it by construction -- the
    equivalence oracle for the whole backend matrix.
    """
    B, H, dh = q.shape
    G = k.shape[1]
    group = H // G
    qf = (q.astype(jnp.float32) * dh ** -0.5).reshape(B, G, group, dh)
    logits = jnp.einsum("bghd,bgsd->bghs", qf, k.astype(jnp.float32))
    logits = jnp.where(valid[:, None, None, :], logits, NEG_INF)
    m = jnp.max(logits, axis=-1, keepdims=True)
    pr = jnp.exp(logits - m)
    # select, don't rely on the zero weight: invalid rows may hold non-finite
    # garbage (paged gathers read the shared trash slot) and 0 * NaN = NaN
    vf = jnp.where(valid[:, None, :, None], v.astype(jnp.float32), 0.0)
    out = jnp.einsum("bghs,bgsd->bghd", pr, vf)
    out = out / jnp.sum(pr, axis=-1)[..., None]
    return out.reshape(B, H, v.shape[-1]).astype(q.dtype)


_masked_attn = masked_decode_attn      # registry-internal alias


@register_attn_backend("gather")
def attn_backend_gather(q, pools_j, bt, lengths, *, window: int = 0,
                        has_warm: bool = True):
    """jnp baseline: gather both tiers into a dense f32 cache, then mask."""
    kh, vh = pools_j["kh"], pools_j["vh"]
    B = q.shape[0]
    G, ps = kh.shape[1], kh.shape[2]
    maxp = bt.shape[1]
    is_warm = bt < 0
    hot_idx = jnp.where(bt > 0, bt, 0)
    warm_idx = jnp.where(is_warm, -bt, 0)
    sel = is_warm[:, :, None, None, None]

    def gathered(hot_pool, q8_pool, sc_pool):
        hot = hot_pool[hot_idx].astype(jnp.float32)   # [B, maxp, G, ps, dh]
        if has_warm:
            warm = (q8_pool[warm_idx].astype(jnp.float32)
                    * sc_pool[warm_idx][..., None])
            hot = jnp.where(sel, warm, hot)
        return hot.transpose(0, 2, 1, 3, 4).reshape(
            B, G, maxp * ps, hot_pool.shape[-1])

    k = gathered(kh, pools_j["k8"], pools_j["ks"])
    v = gathered(vh, pools_j["v8"], pools_j["vs"])
    return _masked_attn(q, k, v, _pool_valid(bt, lengths, ps, window))


@register_attn_backend("pallas")
def attn_backend_pallas(q, pools_j, bt, lengths, *, window: int = 0,
                        has_warm: bool = True):
    """The bf16 paged Pallas kernel (paged.py).  Warm pages must first be
    dequantized into a dense pool appended after the hot slots -- the
    materialization cost pallas_int8 exists to avoid."""
    from repro.kernels.decode_attn import paged as pg
    if has_warm:
        # f32 concat keeps warm-page numerics identical to the gather
        # backend (dequant stays exact); this whole materialization is the
        # per-step cost pallas_int8 avoids
        kw = pools_j["k8"].astype(jnp.float32) * pools_j["ks"][..., None]
        vw = pools_j["v8"].astype(jnp.float32) * pools_j["vs"][..., None]
        k_pool = jnp.concatenate([pools_j["kh"].astype(jnp.float32), kw],
                                 axis=0)
        v_pool = jnp.concatenate([pools_j["vh"].astype(jnp.float32), vw],
                                 axis=0)
        n_hot = pools_j["kh"].shape[0]
        bt = jnp.where(bt < 0, n_hot - bt, bt)        # warm slot w -> n_hot+w
    else:
        # hot-only: feed the bf16 pools straight through (the kernel casts
        # tiles to f32 in VMEM, which is exact for bf16)
        k_pool, v_pool = pools_j["kh"], pools_j["vh"]
    P, G, ps, _ = k_pool.shape
    dummy = jnp.ones((P, G, ps), jnp.float32)
    return pg.paged_decode_attn(q, k_pool, dummy, v_pool, dummy, bt, lengths,
                                out_dtype=q.dtype, window=window)


@register_attn_backend("pallas_int8")
def attn_backend_pallas_int8(q, pools_j, bt, lengths, *, window: int = 0,
                             has_warm: bool = True):
    """Tiered Pallas kernel: each lane's live pages stream from their own
    tier, hot pages as bf16, warm pages as int8 dequantized in VMEM right
    after the DMA (fused decompression)."""
    del has_warm        # the kernel finds a hot-only table from its signs
    from repro.kernels.decode_attn import paged as pg
    return pg.paged_decode_attn_tiered(
        q, pools_j["kh"], pools_j["vh"], pools_j["k8"], pools_j["ks"],
        pools_j["v8"], pools_j["vs"], bt, lengths, out_dtype=q.dtype,
        window=window)


# ---------------------------------------------------------------------------
# latent-page backends (absorbed-form MLA decode over paged latents)
# ---------------------------------------------------------------------------
#
# MLA's absorbed decode attends directly against the per-token LATENT
# (kv_lora_rank floats) plus the shared single-head rope key
# (rope_head_dim floats) -- pages carry those two planes (kh = latent,
# vh = rope key, ONE head) instead of per-head K/V.  A latent backend's
# signature mirrors the GQA one but takes the two query factors the
# absorbed form produces:
#
#   backend(q_lat, q_rope, pools_j, bt, lengths, *, scale,
#           has_warm=True) -> o_lat f32[B, H, lora]
#
# The caller (models/mla.py::mla_paged_decode) folds W_uk into q_lat
# before and W_uv into o_lat after, so the backend is pure cache math.
# Only ``gather`` is implemented; the Pallas kernels raise
# NotImplementedError until the TPU bring-up pass (ROADMAP).

LATENT_ATTN_BACKENDS: dict = {}


def register_latent_backend(name: str):
    def deco(fn):
        LATENT_ATTN_BACKENDS[name] = fn
        return fn
    return deco


def get_latent_backend(name: str):
    try:
        return LATENT_ATTN_BACKENDS[name]
    except KeyError:
        if name in ATTN_BACKENDS:
            raise NotImplementedError(
                f"attention backend {name!r} has no MLA latent-page path "
                f"yet (Pallas latent kernel pending the TPU pass; see "
                f"ROADMAP); use backend='gather' for MLA models") from None
        raise KeyError(f"unknown attention backend {name!r}; "
                       f"registered: {attn_backend_names()}") from None


def latent_backend_names() -> tuple:
    return tuple(sorted(LATENT_ATTN_BACKENDS))


def masked_latent_decode_attn(q_lat, q_rope, c, r, valid, scale):
    """Absorbed-MLA decode attention over a dense latent cache.

    q_lat: f32[B,H,lora] (W_uk already folded in); q_rope: f32[B,H,dr];
    c: [B,S,lora]; r: [B,S,dr]; valid: bool[B,S] -> o_lat f32[B,H,lora].

    This is THE reference latent attention: the dense engine's MLA decode
    (models/mla.py::mla_decode) delegates here, so the latent gather
    backend is bit-identical to it by construction -- the equivalence
    oracle for MLA paged decode.
    """
    logits = (jnp.einsum("bhr,bsr->bhs", q_lat, c.astype(jnp.float32))
              + jnp.einsum("bhr,bsr->bhs", q_rope,
                           r.astype(jnp.float32))) * scale
    logits = jnp.where(valid[:, None, :], logits, NEG_INF)
    w = jax.nn.softmax(logits, axis=-1)
    # select, don't rely on the zero weight: invalid rows may hold non-finite
    # garbage (paged gathers read the shared trash slot) and 0 * NaN = NaN
    cf = jnp.where(valid[:, :, None], c.astype(jnp.float32), 0.0)
    return jnp.einsum("bhs,bsr->bhr", w, cf)


@register_latent_backend("gather")
def latent_backend_gather(q_lat, q_rope, pools_j, bt, lengths, *,
                          scale: float, has_warm: bool = True):
    """jnp baseline: gather both tiers into dense latent/rope caches, then
    run the reference absorbed attention."""
    ch, rh = pools_j["kh"], pools_j["vh"]     # [1+hot, 1, ps, lora/dr]
    B = q_lat.shape[0]
    ps = ch.shape[2]
    maxp = bt.shape[1]
    is_warm = bt < 0
    hot_idx = jnp.where(bt > 0, bt, 0)
    warm_idx = jnp.where(is_warm, -bt, 0)
    sel = is_warm[:, :, None, None, None]

    def gathered(hot_pool, q8_pool, sc_pool):
        hot = hot_pool[hot_idx].astype(jnp.float32)   # [B, maxp, 1, ps, w]
        if has_warm:
            warm = (q8_pool[warm_idx].astype(jnp.float32)
                    * sc_pool[warm_idx][..., None])
            hot = jnp.where(sel, warm, hot)
        return hot.reshape(B, maxp * ps, hot_pool.shape[-1])

    c = gathered(ch, pools_j["k8"], pools_j["ks"])
    r = gathered(rh, pools_j["v8"], pools_j["vs"])
    return masked_latent_decode_attn(q_lat, q_rope, c, r,
                                     _pool_valid(bt, lengths, ps, 0), scale)
