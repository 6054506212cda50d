"""Fused decompress-then-matmul Pallas kernels: the heart of CABA-on-TPU.

The paper's high-priority decompression warp runs BEFORE the parent warp's
load completes (5.2.1: the load that triggered decompression is buffered
until the assist warp finishes).  The TPU equivalent is structural: the
matmul kernel DMAs the COMPRESSED weight tile HBM->VMEM, decompresses it in
VREGs, and feeds the MXU -- so HBM only ever moves compressed bytes, and the
decompression cost lands on otherwise-idle VPU cycles of a memory-bound op.

Two weight formats:
  q8  : block-scaled int8 (fixed-rate; the production path)    ~2x bf16 bytes
  bdi : b2d1 on bf16 bit patterns (paper-faithful lossless)    ~1.8x where it fits

Grid: (M/bm, N/bn, K/bk), K innermost for accumulation in VMEM scratch.
bn % 256 == 0 so N-tiles cover whole compression blocks; bk multiples of the
q8 K-group so one scale row covers the tile.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import jax.experimental.pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import pallas_interpret


# ---------------------------------------------------------------------------
# q8: block-scaled int8 weights
# ---------------------------------------------------------------------------

def _matmul_q8_kernel(x_ref, w8_ref, scale_ref, o_ref, acc, *, out_dtype,
                      nk: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)

    x = x_ref[...].astype(jnp.float32)                  # [bm, bk]
    w8 = w8_ref[...].astype(jnp.float32)                # [bk, bn]
    s = scale_ref[...].astype(jnp.float32)              # [1, bn]
    # scale is constant along the k-tile (bk == GK), so it factors out of the
    # dot: (x @ (w8 * s)) == (x @ w8) * s -- one MXU pass + one VPU scale.
    acc[...] += jnp.dot(x, w8, preferred_element_type=jnp.float32) * s

    @pl.when(k == nk - 1)
    def _done():
        o_ref[...] = acc[...].astype(out_dtype)


def matmul_q8(x, w8, scale, *, gk: int = 256, bm: int = 128, bn: int = 256,
              out_dtype=jnp.bfloat16, interpret: bool | None = None):
    """y = x @ dequant(w8, scale).  x: [M, K] f32/bf16; w8: int8[K, N];
    scale: f32[K/gk, N].  bk is pinned to gk so scales factor per tile."""
    M, K = x.shape
    _, N = w8.shape
    bk = gk
    assert M % bm == 0 and N % bn == 0 and K % bk == 0
    nk = K // bk
    kernel = functools.partial(_matmul_q8_kernel, out_dtype=out_dtype, nk=nk)
    return pl.pallas_call(
        kernel,
        grid=(M // bm, N // bn, nk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, bn), lambda i, j, k: (k, j),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((M, N), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=pallas_interpret(interpret),
    )(x, w8, scale)


# ---------------------------------------------------------------------------
# bdi: lossless b2d1 weights (paper-faithful fused decompression)
# ---------------------------------------------------------------------------

def _matmul_bdi_kernel(x_ref, base_ref, mask_ref, deltas_ref, o_ref, acc, *,
                       out_dtype, nk: int, bn: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)

    bk = deltas_ref.shape[0]
    nblk = bn // 256
    # --- BDI decompression (paper Alg. 1) on the weight tile, in VREGs ---
    d = deltas_ref[...].astype(jnp.int32)
    d = ((d & 0xFF) ^ 0x80) - 0x80                       # sign-extend int8
    d = d.reshape(bk, nblk, 256)
    m = mask_ref[...].astype(jnp.int32).reshape(bk, nblk, 32)
    bits = (m[..., None] >> jax.lax.broadcasted_iota(jnp.int32, (1, 1, 1, 8), 3)) & 1
    use_base = bits.reshape(bk, nblk, 256) == 1
    b = base_ref[...].astype(jnp.int32).reshape(bk, nblk, 1)
    v = (jnp.where(use_base, b + d, d) & 0xFFFF).astype(jnp.uint16)
    w = jax.lax.bitcast_convert_type(v.reshape(bk, bn), jnp.bfloat16)
    # --- MXU pass over the reconstructed tile ---
    x = x_ref[...].astype(jnp.float32)
    acc[...] += jnp.dot(x, w.astype(jnp.float32),
                        preferred_element_type=jnp.float32)

    @pl.when(k == nk - 1)
    def _done():
        o_ref[...] = acc[...].astype(out_dtype)


def matmul_bdi(x, base, mask, deltas, *, bm: int = 128, bn: int = 256,
               bk: int = 128, out_dtype=jnp.bfloat16, interpret: bool | None = None):
    """y = x @ bdi_decompress(base, mask, deltas).

    x: [M, K]; base: u32[K, N/256]; mask: u8[K, N/32]; deltas: u8[K, N].
    """
    M, K = x.shape
    _, N = deltas.shape
    assert M % bm == 0 and N % bn == 0 and K % bk == 0 and bn % 256 == 0
    nk = K // bk
    kernel = functools.partial(_matmul_bdi_kernel, out_dtype=out_dtype,
                               nk=nk, bn=bn)
    nblk = bn // 256
    return pl.pallas_call(
        kernel,
        grid=(M // bm, N // bn, nk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((bk, nblk), lambda i, j, k: (k, j),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((bk, bn // 8), lambda i, j, k: (k, j),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((M, N), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=pallas_interpret(interpret),
    )(x, base, mask, deltas)
