"""Paged flash-decode Pallas kernel: KV gathered through a block table.

Same online-softmax schedule as decode_attn.py, but the KV cache is a pool
of fixed-size pages ``[P, G, ps, D]`` (the repro.cache warm tier) instead of
a dense ``[B, G, S, D]`` slab.  The grid's S axis walks a request's *block
table* (int32[B, n_pages], scalar-prefetched), so each KV tile's DMA source
is ``pool[bt[b, s]]`` -- the address indirection the block table buys, with
the int8 dequant still fused right after the HBM->VMEM move (the blocking
high-priority decompression warp of the paper).

Unmapped table entries must point at a valid (e.g. trash) page; the length
mask removes their contribution exactly as in the dense kernel.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import jax.experimental.pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import pallas_interpret

NEG_INF = -1e30


def _valid(pos, len_b, window):
    """Positions a decode query attends to: before ``len_b`` and, for
    local attention (``window > 0``), within the last ``window``."""
    valid = pos < len_b
    if window:
        valid &= pos >= len_b - window
    return valid


def _flash_step(s, np_, ps, window, len_b, q_ref, k, v, o_ref, m_s, l_s,
                acc_s):
    """One page's online-softmax accumulation, shared by every paged kernel
    (they differ only in how the [ps, D] K/V tiles are produced)."""
    @pl.when(s == 0)
    def _init():
        m_s[...] = jnp.full_like(m_s, NEG_INF)
        l_s[...] = jnp.zeros_like(l_s)
        acc_s[...] = jnp.zeros_like(acc_s)

    D = q_ref.shape[3]
    q = q_ref[0, 0].astype(jnp.float32)                   # [group, D]
    logits = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * (D ** -0.5)  # [group, ps]
    valid = _valid(s * ps + jax.lax.broadcasted_iota(jnp.int32, (1, ps), 1),
                   len_b, window)
    logits = jnp.where(valid, logits, NEG_INF)

    m_prev = m_s[...]
    m_new = jnp.maximum(m_prev, jnp.max(logits, axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(logits - m_new)
    p = jnp.where(valid, p, 0.0)
    # select, don't rely on the zero weight: invalid rows may hold
    # non-finite garbage (trash-slot pages) and 0 * NaN = NaN.  The row
    # mask comes from its own iota: Mosaic cannot reshape the (1, ps) mask
    # into (ps, 1)
    v = jnp.where(_valid(s * ps + jax.lax.broadcasted_iota(
        jnp.int32, (ps, 1), 0), len_b, window), v, 0.0)
    l_s[...] = l_s[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
    acc_s[...] = acc_s[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_s[...] = m_new

    @pl.when(s == np_ - 1)
    def _done():
        denom = jnp.maximum(l_s[...], 1e-30)
        o_ref[0, 0] = (acc_s[...] / denom).astype(o_ref.dtype)


def _dequant(x8_ref, sc_ref):
    """f32 [ps, D] tile of an int8 page times its per-token scales.  The
    scale block holds every KV head of the page ([1, G, ps]: a (1, ps)
    block breaks the TPU's (8, 128) tiling rule), so the kernel picks its
    own head's row."""
    g = pl.program_id(1)
    return x8_ref[0, 0].astype(jnp.float32) * sc_ref[0, g][:, None]


def _paged_kernel(len_ref, bt_ref, q_ref, k8_ref, ks_ref, v8_ref, vs_ref,
                  o_ref, m_s, l_s, acc_s, *, np_: int, ps: int,
                  quantized: bool, window: int):
    b = pl.program_id(0)
    s = pl.program_id(2)
    if quantized:
        k = _dequant(k8_ref, ks_ref)
        v = _dequant(v8_ref, vs_ref)
    else:
        k = k8_ref[0, 0].astype(jnp.float32)              # [ps, D]
        v = v8_ref[0, 0].astype(jnp.float32)
    _flash_step(s, np_, ps, window, len_ref[b], q_ref, k, v, o_ref, m_s,
                l_s, acc_s)


def paged_decode_attn(q, k_pool, ks_pool, v_pool, vs_pool, block_table,
                      lengths, *, out_dtype=jnp.bfloat16, window: int = 0,
                      interpret: bool | None = None):
    """q: [B, H, D]; pools: int8/bf16[P, G, ps, D] (+ f32[P, G, ps] scales,
    ignored unless int8); block_table: int32[B, n_pages] pool slots;
    lengths: int32[B] -> [B, H, D].  ``window > 0`` masks to the last
    ``window`` positions (local attention)."""
    B, H, D = q.shape
    P, G, ps, _ = k_pool.shape
    group = H // G
    np_ = block_table.shape[1]
    quantized = (k_pool.dtype == jnp.int8)
    q4 = q.reshape(B, G, group, D)
    kernel = functools.partial(_paged_kernel, np_=np_, ps=ps,
                               quantized=quantized, window=window)
    # the KV tile for grid step (b, g, s) is page block_table[b, s]
    pool_map = lambda b, g, s, L, BT: (BT[b, s], g, 0, 0)
    scale_map = lambda b, g, s, L, BT: (BT[b, s], 0, 0)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, G, np_),
            in_specs=[
                pl.BlockSpec((1, 1, group, D),
                             lambda b, g, s, L, BT: (b, g, 0, 0),
                             memory_space=pltpu.VMEM),
                pl.BlockSpec((1, 1, ps, D), pool_map,
                             memory_space=pltpu.VMEM),
                pl.BlockSpec((1, G, ps), scale_map,
                             memory_space=pltpu.VMEM),
                pl.BlockSpec((1, 1, ps, D), pool_map,
                             memory_space=pltpu.VMEM),
                pl.BlockSpec((1, G, ps), scale_map,
                             memory_space=pltpu.VMEM),
            ],
            out_specs=pl.BlockSpec((1, 1, group, D),
                                   lambda b, g, s, L, BT: (b, g, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((group, 1), jnp.float32),
                pltpu.VMEM((group, 1), jnp.float32),
                pltpu.VMEM((group, D), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, G, group, D), out_dtype),
        interpret=pallas_interpret(interpret),
    )(lengths, block_table, q4, k_pool, ks_pool, v_pool, vs_pool)
    return out.reshape(B, H, D)


# -- tiered kernel: hot bf16 + warm int8 through one encoded table -----------
#
# Block-table entries use the repro.cache encoded-location convention:
# loc > 0 hot slot, loc < 0 warm slot -loc, loc == 0 trash.  Each grid step
# DMAs BOTH candidate tiles (hot slot max(loc,0), warm slot max(-loc,0)) and
# selects in VMEM, dequantizing the warm tile right after the move -- the
# CABA fused-decompression contract without materializing a dense bf16 copy
# of the warm tier (which is what the plain bf16 kernel must do).

def _tiered_kernel(len_ref, bt_ref, q_ref, kh_ref, k8_ref, ks_ref, vh_ref,
                   v8_ref, vs_ref, o_ref, m_s, l_s, acc_s, *, np_: int,
                   ps: int, window: int):
    b = pl.program_id(0)
    s = pl.program_id(2)
    is_warm = bt_ref[b, s] < 0
    k = jnp.where(is_warm, _dequant(k8_ref, ks_ref),
                  kh_ref[0, 0].astype(jnp.float32))       # [ps, D]
    v = jnp.where(is_warm, _dequant(v8_ref, vs_ref),
                  vh_ref[0, 0].astype(jnp.float32))
    _flash_step(s, np_, ps, window, len_ref[b], q_ref, k, v, o_ref, m_s,
                l_s, acc_s)


def paged_decode_attn_tiered(q, kh_pool, vh_pool, k8_pool, ks_pool, v8_pool,
                             vs_pool, block_table, lengths, *,
                             out_dtype=jnp.bfloat16, window: int = 0,
                             interpret: bool | None = None):
    """Mixed hot/warm paged flash-decode through an ENCODED block table.

    q: [B, H, D]; hot pools bf16[P_hot, G, ps, D]; warm pools
    int8[P_warm, G, ps, D] + f32[P_warm, G, ps] scales; block_table:
    int32[B, n_pages] encoded locations (>0 hot, <0 warm, 0 trash);
    lengths: int32[B] valid-token counts -> [B, H, D]."""
    B, H, D = q.shape
    _, G, ps, _ = kh_pool.shape
    group = H // G
    np_ = block_table.shape[1]
    q4 = q.reshape(B, G, group, D)
    kernel = functools.partial(_tiered_kernel, np_=np_, ps=ps, window=window)
    hot_map = lambda b, g, s, L, BT: (jnp.maximum(BT[b, s], 0), g, 0, 0)
    warm_map = lambda b, g, s, L, BT: (jnp.maximum(-BT[b, s], 0), g, 0, 0)
    wscale_map = lambda b, g, s, L, BT: (jnp.maximum(-BT[b, s], 0), 0, 0)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, G, np_),
            in_specs=[
                pl.BlockSpec((1, 1, group, D),
                             lambda b, g, s, L, BT: (b, g, 0, 0),
                             memory_space=pltpu.VMEM),
                pl.BlockSpec((1, 1, ps, D), hot_map,
                             memory_space=pltpu.VMEM),
                pl.BlockSpec((1, 1, ps, D), warm_map,
                             memory_space=pltpu.VMEM),
                pl.BlockSpec((1, G, ps), wscale_map,
                             memory_space=pltpu.VMEM),
                pl.BlockSpec((1, 1, ps, D), hot_map,
                             memory_space=pltpu.VMEM),
                pl.BlockSpec((1, 1, ps, D), warm_map,
                             memory_space=pltpu.VMEM),
                pl.BlockSpec((1, G, ps), wscale_map,
                             memory_space=pltpu.VMEM),
            ],
            out_specs=pl.BlockSpec((1, 1, group, D),
                                   lambda b, g, s, L, BT: (b, g, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((group, 1), jnp.float32),
                pltpu.VMEM((group, 1), jnp.float32),
                pltpu.VMEM((group, D), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, G, group, D), out_dtype),
        interpret=pallas_interpret(interpret),
    )(lengths, block_table, q4, kh_pool, k8_pool, ks_pool, vh_pool, v8_pool,
      vs_pool)
    return out.reshape(B, H, D)


# -- gather-based oracle -----------------------------------------------------

def gather_pool(pool, block_table):
    """pool [P, G, ps, D] + table [B, NP] -> dense [B, G, NP*ps, D]."""
    B, NP = block_table.shape
    _, G, ps, D = pool.shape
    g = pool[block_table]                       # [B, NP, G, ps, D]
    return g.transpose(0, 2, 1, 3, 4).reshape(B, G, NP * ps, D)


def gather_scales(scales, block_table):
    """scales [P, G, ps] + table [B, NP] -> [B, G, NP*ps]."""
    B, NP = block_table.shape
    _, G, ps = scales.shape
    g = scales[block_table]                     # [B, NP, G, ps]
    return g.transpose(0, 2, 1, 3).reshape(B, G, NP * ps)


def paged_decode_attn_ref(q, k_pool, ks_pool, v_pool, vs_pool, block_table,
                          lengths, out_dtype=jnp.bfloat16):
    """Oracle: gather the table into a dense cache, then dense reference."""
    from repro.kernels.decode_attn import ref as da_ref
    k = gather_pool(k_pool, block_table)
    v = gather_pool(v_pool, block_table)
    if k_pool.dtype == jnp.int8:
        ks = gather_scales(ks_pool, block_table)
        vs = gather_scales(vs_pool, block_table)
        return da_ref.decode_attn_ref(q, k, ks, v, vs, lengths, out_dtype)
    return da_ref.decode_attn_raw_ref(q, k, v, lengths, out_dtype)
