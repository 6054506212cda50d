"""BDI assist-warp subroutines as Pallas TPU kernels.

One kernel instance per encoding, mirroring the paper's AWS which stores "a
separate subroutine for each possible BDI encoding" (5.1.2).  The kernel body
is the paper's Algorithm 1: load deltas, masked vector-add to the base, store
the uncompressed line -- executed across 8x128 VPU lanes instead of 32 SIMT
lanes.

Tiling: BN blocks per grid step along the block axis.  For a 512 B block and
bf16 words the natural tile is deltas (BN, 256) u8 / out (BN, 256) u16 --
lane-dim multiples of 128, VMEM-resident.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import jax.experimental.pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import pallas_interpret

ENC_PARAMS = {"b2d1": (2, 1), "b4d1": (4, 1), "b4d2": (4, 2)}


def _sext_i32(v, d_bytes: int):
    """Sign-extend low d bytes held in an int32 carrier (VPU-friendly)."""
    bits = 8 * d_bytes
    half = 1 << (bits - 1)
    full = (1 << bits) - 1
    return ((v & full) ^ half) - half


def _unpack_mask(mask_u8, W: int):
    """uint8[bn, W/8] -> bool[bn, W] little-bit-endian (matches pack_bits)."""
    m = mask_u8.astype(jnp.int32)
    bits = (m[:, :, None] >> jax.lax.broadcasted_iota(jnp.int32, (1, 1, 8), 2)) & 1
    return bits.reshape(mask_u8.shape[0], W) == 1


def _decompress_kernel(base_ref, mask_ref, deltas_ref, out_ref, *,
                       enc: str, block_bytes: int):
    wb, db = ENC_PARAMS[enc]
    W = block_bytes // wb
    bn = deltas_ref.shape[0]
    base = base_ref[...].astype(jnp.int32)                 # [bn, 1]
    use_base = _unpack_mask(mask_ref[...], W)              # [bn, W]
    if db == 1:
        d = _sext_i32(deltas_ref[...].astype(jnp.int32), 1)
    else:  # db == 2: interleaved little-endian byte pairs
        raw = deltas_ref[...].astype(jnp.int32).reshape(bn, W, 2)
        d = _sext_i32(raw[..., 0] | (raw[..., 1] << 8), 2)
    v = jnp.where(use_base, base + d, d)                   # Alg. 1 line 2
    if wb == 2:
        out_ref[...] = (v & 0xFFFF).astype(jnp.uint16)
    else:
        out_ref[...] = v.astype(jnp.uint32)


def _compress_kernel(blocks_ref, base_ref, mask_ref, deltas_ref, ok_ref, *,
                     enc: str, block_bytes: int):
    """Paper Alg. 2 for one fixed encoding: test, mask, store deltas."""
    wb, db = ENC_PARAMS[enc]
    W = block_bytes // wb
    bn = blocks_ref.shape[0]
    w = blocks_ref[...].astype(jnp.int32)                  # [bn, W] words
    base = w[:, :1]
    delta = w - base
    bits = 8 * db
    half = 1 << (bits - 1)
    # words are carried as unsigned wb-byte ints in int32: range checks are
    # exact in int32 for wb<=2; for wb==4 we emulate uint32 wraparound
    if wb == 4:
        du = delta.astype(jnp.uint32)
        from_base = (du + jnp.uint32(half)) < jnp.uint32(1 << bits)
        wu = w.astype(jnp.uint32)
        from_zero = (wu + jnp.uint32(half)) < jnp.uint32(1 << bits)
    else:
        from_base = (delta + half >= 0) & (delta + half < (1 << bits))
        from_zero = (w + half >= 0) & (w + half < (1 << bits))
    ok = jnp.all(from_base | from_zero, axis=-1)           # global predicate
    sel = jnp.where(from_base, delta, w)
    base_ref[...] = base.astype(jnp.uint32)
    ok_ref[...] = ok[:, None].astype(jnp.uint8)
    # pack mask bits little-bit-endian
    mb = from_base.reshape(bn, W // 8, 8).astype(jnp.int32)
    weights = (1 << jax.lax.broadcasted_iota(jnp.int32, (1, 1, 8), 2))
    mask_ref[...] = jnp.sum(mb * weights, axis=-1).astype(jnp.uint8)
    if db == 1:
        deltas_ref[...] = (sel & 0xFF).astype(jnp.uint8)
    else:
        lo = (sel & 0xFF).astype(jnp.uint8)
        hi = ((sel >> 8) & 0xFF).astype(jnp.uint8)
        deltas_ref[...] = jnp.stack([lo, hi], axis=-1).reshape(bn, W * db)


def decompress_pallas(base, mask, deltas, *, enc: str, block_bytes: int = 512,
                      bn: int | None = None, interpret: bool | None = None):
    """base u32[nb,1], mask u8[nb,W/8], deltas u8[nb,W*d] -> words."""
    wb, db = ENC_PARAMS[enc]
    W = block_bytes // wb
    nb = base.shape[0]
    if bn is None:
        bn = next(b for b in (8, 4, 2, 1) if nb % b == 0)
    assert nb % bn == 0, (nb, bn)
    out_dtype = jnp.uint16 if wb == 2 else jnp.uint32
    kernel = functools.partial(_decompress_kernel, enc=enc,
                               block_bytes=block_bytes)
    return pl.pallas_call(
        kernel,
        grid=(nb // bn,),
        in_specs=[
            pl.BlockSpec((bn, 1), lambda i: (i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((bn, W // 8), lambda i: (i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((bn, W * db), lambda i: (i, 0), memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((bn, W), lambda i: (i, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((nb, W), out_dtype),
        interpret=pallas_interpret(interpret),
    )(base, mask, deltas)


def compress_pallas(words, *, enc: str, block_bytes: int = 512,
                    bn: int | None = None, interpret: bool | None = None):
    """words u16/u32[nb, W] -> (base, mask, deltas, ok) kernel layout."""
    wb, db = ENC_PARAMS[enc]
    W = block_bytes // wb
    nb = words.shape[0]
    if bn is None:
        bn = next(b for b in (8, 4, 2, 1) if nb % b == 0)
    assert nb % bn == 0
    kernel = functools.partial(_compress_kernel, enc=enc,
                               block_bytes=block_bytes)
    return pl.pallas_call(
        kernel,
        grid=(nb // bn,),
        in_specs=[pl.BlockSpec((bn, W), lambda i: (i, 0),
                               memory_space=pltpu.VMEM)],
        out_specs=[
            pl.BlockSpec((bn, 1), lambda i: (i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((bn, W // 8), lambda i: (i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((bn, W * db), lambda i: (i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((bn, 1), lambda i: (i, 0), memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((nb, 1), jnp.uint32),
            jax.ShapeDtypeStruct((nb, W // 8), jnp.uint8),
            jax.ShapeDtypeStruct((nb, W * db), jnp.uint8),
            jax.ShapeDtypeStruct((nb, 1), jnp.uint8),
        ],
        interpret=pallas_interpret(interpret),
    )(words)


# ---------------------------------------------------------------------------
# Variable-rate decode: per-block encodings via scalar-prefetch offsets.
# TPU stand-in for the paper's coalescing/address-generation reuse (5.1.3):
# the offset table drives a dynamic DMA of each compressed record.
# ---------------------------------------------------------------------------

def _packed_kernel(off_ref, enc_ref, stream_ref, out_ref, scratch, sem, *,
                   block_bytes: int):
    i = pl.program_id(0)
    off = off_ref[i]
    max_rec = scratch.shape[0]
    cp = pltpu.make_async_copy(stream_ref.at[pl.ds(off, max_rec)], scratch, sem)
    cp.start()
    cp.wait()
    rec = scratch[...].astype(jnp.int32)   # [max_rec] bytes (enc byte first)
    B = block_bytes

    def dec_zeros():
        return jnp.zeros((B,), jnp.int32)

    def dec_rep8():
        return jnp.tile(rec[1:9], B // 8)

    def dec_raw():
        return rec[1:1 + B]

    def dec_bd(wb, db):
        W = B // wb
        mask_bytes = W // 8
        base = jnp.int32(0)
        for k in range(wb if wb <= 4 else 4):
            base = base | (rec[1 + k] << (8 * k))
        mb = rec[1 + wb:1 + wb + mask_bytes]
        bits = (mb[:, None] >> jax.lax.broadcasted_iota(jnp.int32, (1, 8), 1)) & 1
        use_base = bits.reshape(W) == 1
        draw = rec[1 + wb + mask_bytes:1 + wb + mask_bytes + W * db]
        if db == 1:
            d = _sext_i32(draw, 1)
        elif db == 2:
            p = draw.reshape(W, 2)
            d = _sext_i32(p[:, 0] | (p[:, 1] << 8), 2)
        else:
            p = draw.reshape(W, 4)
            d = p[:, 0] | (p[:, 1] << 8) | (p[:, 2] << 16) | (p[:, 3] << 24)
        v = jnp.where(use_base, base + d, d)
        if wb == 2:
            v = v & 0xFFFF
            b0, b1 = v & 0xFF, (v >> 8) & 0xFF
            return jnp.stack([b0, b1], -1).reshape(B)
        b = [(v >> (8 * k)) & 0xFF for k in range(4)]
        return jnp.stack(b, -1).reshape(B)

    # branch per encoding id (paper: AWS subroutine select by SR.ID).
    # 8-byte-word encodings are excluded from the kernel path at compress
    # time (ops.py passes allowed=KERNEL_ENCODINGS); their slots fall back to
    # raw and are never taken.
    branches = [
        dec_zeros,                                    # 0 zeros
        dec_rep8,                                     # 1 rep8
        dec_raw,                                      # 2 b8d1 (never emitted)
        dec_raw,                                      # 3 b8d2 (never emitted)
        dec_raw,                                      # 4 b8d4 (never emitted)
        lambda: dec_bd(4, 1),                         # 5 b4d1
        lambda: dec_bd(4, 2),                         # 6 b4d2
        lambda: dec_bd(2, 1),                         # 7 b2d1
        dec_raw,                                      # 8 raw
    ]
    out = jax.lax.switch(enc_ref[i], branches)
    out_ref[0, :] = out.astype(jnp.uint8)


def decompress_packed_pallas(stream, offsets, enc, *, block_bytes: int = 512,
                             interpret: bool | None = None):
    """Variable-rate BDI decode (4-byte-word subset + specials + raw).

    stream: uint8[S]; offsets: int32[nb]; enc: uint8[nb] ->
    uint8[nb, block_bytes].
    """
    nb = offsets.shape[0]
    max_rec = 1 + block_bytes
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(nb,),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((1, block_bytes), lambda i, off, enc: (i, 0)),
        scratch_shapes=[pltpu.VMEM((max_rec,), jnp.uint8),
                        pltpu.SemaphoreType.DMA],
    )
    kernel = functools.partial(_packed_kernel, block_bytes=block_bytes)
    return pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((nb, block_bytes), jnp.uint8),
        interpret=pallas_interpret(interpret),
    )(offsets, enc.astype(jnp.int32), stream)
