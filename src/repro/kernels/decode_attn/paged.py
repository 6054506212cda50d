"""Paged flash-decode Pallas kernels: KV read through a block table.

Same online-softmax schedule as decode_attn.py, but the KV cache is a pool
of fixed-size pages ``[P, G, ps, D]`` (the repro.cache tiers) instead of a
dense ``[B, G, S, D]`` slab.

* ``paged_decode_attn`` (backend ``pallas``): one pool, the grid
  ``(B, G, n_pages)`` walks a request's *block table* (int32[B, n_pages],
  scalar-prefetched), so each KV tile's DMA source is ``pool[bt[b, s]]``.
  Unmapped entries must point at a valid (e.g. trash) page; the length
  mask removes their contribution.
* ``paged_decode_attn_tiered`` (backend ``pallas_int8``): hot bf16 and
  warm int8 pools through one encoded table, on the grid ``(B,)``.  Each
  lane walks only its live pages, in double-buffered blocks of pages
  copied by hand, each page fetched from the one tier it lives in, with
  the int8 dequant fused right after the HBM->VMEM move (the blocking
  high-priority decompression warp of the paper).
"""
from __future__ import annotations

import functools
import math

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
        name="paged_decode_attn",
    )(lengths, block_table, q4, k_pool, ks_pool, v_pool, vs_pool)
    return out.reshape(B, H, D)


# -- tiered kernel: hot bf16 + warm int8 through one encoded table -----------
#
# Block-table entries use the repro.cache encoded-location convention:
# loc > 0 hot slot, loc < 0 warm slot -loc, loc == 0 trash.  One grid step
# serves one lane with all its KV heads.  The pools stay in HBM and the
# kernel walks only the lane's live pages -- from the first page the window
# reaches to ceil(len / ps) -- in blocks of ``ppb`` pages, double-buffered:
# while one block is attended, the next block's pages are in flight.  Each
# page is one DMA per tensor of all G heads, from the one tier it lives in
# (a hot bf16 page or a warm int8 page); trash entries and pages outside
# the live range are never fetched.  A block of hot pages is attended
# straight from its DMA buffers.  A block holding a warm or a trash page is
# staged in f32 first, and its warm keys are dequantized in VMEM right
# after the move -- the CABA fused-decompression contract without a dense
# bf16 copy of the warm tier (which the plain bf16 kernel needs).
#
# The warm scales reach the kernel as key-major rows, f32[B, G, keys]:
# Mosaic DMAs only slices whose last dim is a multiple of 128, which a
# [G, ps] scale page is not.  The rows are gathered only when the table
# holds a warm entry, and a block DMAs its rows only when it holds a warm
# page.  A scale multiplies a key's logit and its softmax weight, which is
# the same as multiplying its K and V rows.

#: keys per block: enough work per step to hide the next block's DMAs
_BLOCK_TOKENS = 256
#: VMEM the kernel's page buffers may take
_VMEM_BUDGET = 8 << 20
#: Mosaic's lane tile: a DMA'd slice's last dim is a multiple of it, so a
#: block's scale rows start at one
_LANE_TILE = 128


def _pages_per_block(ps: int, G: int, D: int, n_pages: int) -> int:
    """Pages per block: ``_BLOCK_TOKENS`` keys within ``_VMEM_BUDGET`` (two
    buffers of hot bf16 and warm int8 K and V pages, the f32 staging of a
    mixed block, scale rows), a multiple of 128 keys unless the table is
    one block, and never more than the table."""
    per_page = G * ps * (20 * D + 32)
    ppb = max(1, min(_BLOCK_TOKENS // ps, _VMEM_BUDGET // per_page))
    step = _LANE_TILE // math.gcd(ps, _LANE_TILE)
    return min(n_pages, max(step, ppb // step * step))


def _scale_rows(ks_pool, vs_pool, bt, width: int):
    """Each lane's warm K and V scales, key-major: f32[B, G, width], the
    key at position t in column t.  Hot and trash keys read whatever slot
    0 holds; the kernel never uses them."""
    B, NP = bt.shape
    _, G, ps = ks_pool.shape

    def gather():
        idx = jnp.maximum(-bt, 0)
        return tuple(
            jnp.pad(sc[idx].transpose(0, 2, 1, 3).reshape(B, G, NP * ps),
                    ((0, 0), (0, 0), (0, width - NP * ps)))
            for sc in (ks_pool, vs_pool))

    def none():
        return (jnp.zeros((B, G, width), jnp.float32),) * 2

    return jax.lax.cond(jnp.any(bt < 0), gather, none)


def _tiered_kernel(len_ref, bt_ref, q_ref, kh_hbm, vh_hbm, k8_hbm, v8_hbm,
                   ksr_hbm, vsr_hbm, o_ref, kh_buf, vh_buf, k8_buf, v8_buf,
                   ks_buf, vs_buf, k_st, v_st, m_s, l_s, acc_s, sem, *,
                   np_: int, ps: int, ppb: int, window: int):
    b = pl.program_id(0)
    G, D = q_ref.shape[1], q_ref.shape[3]
    T = ppb * ps
    len_b = len_ref[b]
    hi_page = jax.lax.div(len_b + ps - 1, ps)
    lo_page = (jax.lax.div(jnp.maximum(len_b - window, 0), ps) if window
               else 0)
    first = jax.lax.div(lo_page, ppb)
    last = jax.lax.div(hi_page + ppb - 1, ppb)

    def pages(blk, visit, init):
        """Fold ``visit(j, loc, live, carry)`` over the pages of a block:
        ``loc`` is page j's encoded location, 0 outside the live range,
        so such a page is never fetched."""
        def page_j(j, carry):
            p = blk * ppb + j
            live = (p >= lo_page) & (p < hi_page)
            e = jnp.where(live, bt_ref[b, jnp.minimum(p, np_ - 1)], 0)
            return visit(j, e, live, carry)
        # unrolled when lowered: the body is traced once (tracing is part
        # of every engine's set-up) and runs without loop overhead
        return jax.lax.fori_loop(0, ppb, page_j, init, unroll=True)

    def dma(blk, slot, op):
        """Start or wait for a block's copies, each page from its tier."""
        def page(j, e, live, n_warm):
            h, w = jnp.maximum(e, 0), jnp.maximum(-e, 0)
            for cond, pairs in (
                    (e > 0, ((kh_hbm.at[h], kh_buf), (vh_hbm.at[h], vh_buf))),
                    (e < 0, ((k8_hbm.at[w], k8_buf), (v8_hbm.at[w], v8_buf)))):
                @pl.when(cond)
                def _():
                    for src, dst in pairs:
                        getattr(pltpu.make_async_copy(
                            src, dst.at[slot, j], sem.at[slot]), op)()
            return n_warm + (e < 0).astype(jnp.int32)

        @pl.when(pages(blk, page, 0) > 0)
        def _():
            keys = pl.ds(pl.multiple_of(blk * T, T), ks_buf.shape[-1])
            for src, dst in ((ksr_hbm, ks_buf), (vsr_hbm, vs_buf)):
                getattr(pltpu.make_async_copy(
                    src.at[b, :, keys], dst.at[slot], sem.at[slot]), op)()

    def attend(blk, k_of, v_of, page_ok=None, scales=None):
        """Online-softmax accumulation of one block for every head;
        ``k_of(g)`` / ``v_of(g)`` give the block's [ppb, ps, D] tiles,
        ``scales(g)`` each key's K and V scale rows ([1, T])."""
        pos = blk * T + jax.lax.broadcasted_iota(jnp.int32, (1, T), 1)
        valid = _valid(pos, len_b, window)
        if page_ok is not None:
            valid &= page_ok
        # the value rows' mask from its own iota: Mosaic cannot reshape
        # the (1, T) mask into (T, 1)
        row_ok = _valid(blk * T + jax.lax.broadcasted_iota(
            jnp.int32, (T, 1), 0), len_b, window)
        for g in range(G):
            q = q_ref[0, g].astype(jnp.float32)           # [group, D]
            k = k_of(g).astype(jnp.float32).reshape(T, D)
            logits = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)       # [group, T]
            if scales is not None:
                k_sc, v_sc = scales(g)
                logits = logits * k_sc
            logits = jnp.where(valid, logits * (D ** -0.5), NEG_INF)
            m_prev = m_s[g]
            m_new = jnp.maximum(m_prev,
                                jnp.max(logits, axis=-1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.where(valid, jnp.exp(logits - m_new), 0.0)
            # select, don't rely on the zero weight: rows outside the
            # window or the length may hold non-finite garbage and
            # 0 * NaN = NaN
            v = jnp.where(row_ok,
                          v_of(g).astype(jnp.float32).reshape(T, D), 0.0)
            l_s[g] = l_s[g] * alpha + jnp.sum(p, axis=-1, keepdims=True)
            pv = p * v_sc if scales is not None else p
            acc_s[g] = acc_s[g] * alpha + jax.lax.dot_general(
                pv, v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_s[g] = m_new

    def mixed(blk, slot):
        """A block holding warm or trash pages: stage every page in f32
        (hot cast, warm int8 cast, unfetched zero), mask the unfetched
        keys, scale the warm ones."""
        row = jax.lax.broadcasted_iota(jnp.int32, (1, T), 1)

        def stage(j, e, live, masks):
            for st, hot, q8 in ((k_st, kh_buf, k8_buf),
                                (v_st, vh_buf, v8_buf)):
                @pl.when(e > 0)
                def _():
                    st[j] = hot[slot, j].astype(jnp.float32)

                @pl.when(e < 0)
                def _():
                    st[j] = q8[slot, j].astype(jnp.float32)

                @pl.when(e == 0)
                def _():
                    st[j] = jnp.zeros(st.shape[1:], jnp.float32)
            in_page = ((row >= j * ps) & (row < (j + 1) * ps)).astype(
                jnp.int32)
            fetched, warm = masks
            return (fetched | in_page * (e != 0).astype(jnp.int32),
                    warm | in_page * (e < 0).astype(jnp.int32))

        zero = jnp.zeros((1, T), jnp.int32)
        fetched, warm = pages(blk, stage, (zero, zero))

        def scales(g):
            # select: the rows of non-warm keys may be stale or garbage
            return tuple(jnp.where(warm > 0, buf[slot, pl.ds(g, 1), :T], 1.0)
                         for buf in (ks_buf, vs_buf))

        attend(blk, lambda g: k_st[:, g], lambda g: v_st[:, g], fetched > 0,
               scales)

    def block(blk, carry):
        slot = jax.lax.rem(blk - first, 2)

        @pl.when(blk + 1 < last)
        def _():
            dma(blk + 1, 1 - slot, "start")

        dma(blk, slot, "wait")
        # a block whose live pages are all hot reads its DMA buffers as
        # they are (the positional mask covers the pages outside the live
        # range); one with no fetched page adds nothing
        n_other, n_fetched = pages(
            blk, lambda j, e, live, n: (
                n[0] + (live & (e <= 0)).astype(jnp.int32),
                n[1] + (e != 0).astype(jnp.int32)), (0, 0))

        @pl.when(n_other == 0)
        def _():
            attend(blk, lambda g: kh_buf[slot, :, g],
                   lambda g: vh_buf[slot, :, g])

        @pl.when((n_other > 0) & (n_fetched > 0))
        def _():
            mixed(blk, slot)
        return carry

    m_s[...] = jnp.full_like(m_s, NEG_INF)
    l_s[...] = jnp.zeros_like(l_s)
    acc_s[...] = jnp.zeros_like(acc_s)

    @pl.when(first < last)
    def _():
        dma(first, 0, "start")

    jax.lax.fori_loop(first, last, block, 0)
    for g in range(G):
        o_ref[0, g] = (acc_s[g] / jnp.maximum(l_s[g], 1e-30)
                       ).astype(o_ref.dtype)


def paged_decode_attn_tiered(q, kh_pool, vh_pool, k8_pool, ks_pool, v8_pool,
                             vs_pool, block_table, lengths, *,
                             out_dtype=jnp.bfloat16, window: int = 0,
                             interpret: bool | None = None):
    """Mixed hot/warm paged flash-decode through an ENCODED block table.

    q: [B, H, D]; hot pools bf16[P_hot, G, ps, D]; warm pools
    int8[P_warm, G, ps, D] + f32[P_warm, G, ps] scales; block_table:
    int32[B, n_pages] encoded locations (>0 hot, <0 warm, 0 trash);
    lengths: int32[B] valid-token counts -> [B, H, D].  A lane of length
    0, or whose live pages are all trash, reads zeros."""
    B, H, D = q.shape
    _, G, ps, _ = kh_pool.shape
    group = H // G
    np_ = block_table.shape[1]
    ppb = _pages_per_block(ps, G, D, np_)
    T = ppb * ps
    row_w = -(-T // _LANE_TILE) * _LANE_TILE   # a block's scale-row DMA
    n_blocks = -(-np_ // ppb)
    ks_rows, vs_rows = _scale_rows(ks_pool, vs_pool, block_table,
                                   (n_blocks - 1) * T + row_w)
    q4 = q.reshape(B, G, group, D)
    kernel = functools.partial(_tiered_kernel, np_=np_, ps=ps, ppb=ppb,
                               window=window)
    lane = pl.BlockSpec((1, G, group, D), lambda b, L, BT: (b, 0, 0, 0),
                        memory_space=pltpu.VMEM)
    hbm = pl.BlockSpec(memory_space=pltpu.HBM)

    def page_bufs(pool):
        return pltpu.VMEM((2, ppb) + pool.shape[1:], pool.dtype)

    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B,),
            in_specs=[lane] + [hbm] * 6,
            out_specs=lane,
            scratch_shapes=[
                page_bufs(kh_pool), page_bufs(vh_pool), page_bufs(k8_pool),
                page_bufs(v8_pool),
                pltpu.VMEM((2, G, row_w), jnp.float32),
                pltpu.VMEM((2, G, row_w), jnp.float32),
                pltpu.VMEM((ppb, G, ps, D), jnp.float32),
                pltpu.VMEM((ppb, G, ps, D), jnp.float32),
                pltpu.VMEM((G, group, 1), jnp.float32),
                pltpu.VMEM((G, group, 1), jnp.float32),
                pltpu.VMEM((G, group, D), jnp.float32),
                pltpu.SemaphoreType.DMA((2,)),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, G, group, D), out_dtype),
        interpret=pallas_interpret(interpret),
        name="paged_decode_attn_tiered",
    )(lengths, block_table, q4, kh_pool, vh_pool, k8_pool, v8_pool, ks_rows,
      vs_rows)
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
