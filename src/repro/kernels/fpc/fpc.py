"""FPC segment-parallel decompression as a Pallas kernel (paper Alg. 3).

Variable-rate: the per-block payload offset table (compress-time prefix sum)
is scalar-prefetched; per-segment offsets are an in-kernel cumsum of the
pattern-size lookup.  Each of the 16 segments decodes via an 8-way
``lax.switch`` over the pattern subroutines -- the AWS-subroutine-per-
encoding structure again.
"""
from __future__ import annotations

import functools

import numpy as np
import jax
import jax.numpy as jnp
import jax.experimental.pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import pallas_interpret

from repro.assist.schemes.fpc import PATTERNS, SEG_WORDS, SEG_BYTES

_SEG_SIZES = np.array([int(p[2] * SEG_WORDS) for p in PATTERNS], np.int32)


def _sext(v, bits: int):
    full = (1 << bits) - 1
    half = 1 << (bits - 1)
    return ((v & full) ^ half) - half


def _decode_seg(payload, pat: int):
    """payload: int32[SEG_BYTES] (over-fetched); -> int32[SEG_WORDS] words."""
    p = payload
    if pat == 0:
        return jnp.zeros((SEG_WORDS,), jnp.int32)
    if pat == 1:
        nib = jnp.stack([p[:SEG_WORDS // 2] & 0xF,
                         (p[:SEG_WORDS // 2] >> 4) & 0xF], -1).reshape(-1)
        return _sext(nib, 4)
    if pat == 2:
        return _sext(p[:SEG_WORDS], 8)
    if pat == 3:
        h = p[0:2 * SEG_WORDS:2] | (p[1:2 * SEG_WORDS:2] << 8)
        return _sext(h, 16)
    if pat == 4:
        h = p[0:2 * SEG_WORDS:2] | (p[1:2 * SEG_WORDS:2] << 8)
        return h << 16
    if pat == 5:
        lo = _sext(p[0:2 * SEG_WORDS:2], 8) & 0xFFFF
        hi = _sext(p[1:2 * SEG_WORDS:2], 8) & 0xFFFF
        return lo | (hi << 16)
    if pat == 6:
        b = p[:SEG_WORDS]
        return b | (b << 8) | (b << 16) | (b << 24)
    if pat == 7:
        q = p[:4 * SEG_WORDS]
        return q[0::4] | (q[1::4] << 8) | (q[2::4] << 16) | (q[3::4] << 24)
    raise ValueError(pat)


def _fpc_kernel(off_ref, stream_ref, seg_enc_ref, out_ref, scratch, sem, *,
                block_bytes: int):
    i = pl.program_id(0)
    off = off_ref[i]
    cp = pltpu.make_async_copy(
        stream_ref.at[pl.ds(off, scratch.shape[0])], scratch, sem)
    cp.start()
    cp.wait()
    rec = scratch[...].astype(jnp.int32)
    nseg = block_bytes // SEG_BYTES
    segs = seg_enc_ref[0, :].astype(jnp.int32)            # [nseg]
    sizes = jnp.zeros_like(segs)                          # select-chain lookup
    for p, *_ in PATTERNS:                                # (no captured consts)
        sizes = jnp.where(segs == p, jnp.int32(int(_SEG_SIZES[p])), sizes)
    seg_off = jnp.cumsum(sizes) - sizes                   # exclusive scan
    words = []
    for s in range(nseg):                                 # unrolled segments
        payload = jax.lax.dynamic_slice(rec, (seg_off[s],), (SEG_BYTES,))
        branches = [functools.partial(_decode_seg, payload, p)
                    for p, *_ in PATTERNS]
        words.append(jax.lax.switch(segs[s], branches))
    w = jnp.concatenate(words)                            # [W] int32 words
    b = [(w >> (8 * k)) & 0xFF for k in range(4)]
    out_ref[0, :] = jnp.stack(b, -1).reshape(block_bytes).astype(jnp.uint8)


def decompress_pallas(stream, offsets, seg_enc, *, block_bytes: int = 512,
                      interpret: bool | None = None):
    """stream u8[S]; offsets i32[nb]; seg_enc u8[nb, nseg] -> u8[nb, B]."""
    nb, nseg = seg_enc.shape
    kernel = functools.partial(_fpc_kernel, block_bytes=block_bytes)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(nb,),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec((1, nseg), lambda i, off: (i, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((1, block_bytes), lambda i, off: (i, 0)),
        scratch_shapes=[pltpu.VMEM((block_bytes + SEG_BYTES,), jnp.uint8),
                        pltpu.SemaphoreType.DMA],
    )
    return pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((nb, block_bytes), jnp.uint8),
        interpret=pallas_interpret(interpret),
    )(offsets, stream, seg_enc)
