"""C-Pack parallel decompression as a Pallas kernel (paper Alg. 5).

The paper's fixed compressed word size is what makes this kernel trivially
parallel: every word is 4-bit code + 1-byte payload at a static offset.  The
dictionary gather is realized as a 4-way masked select chain (TPU has no
cheap VREG gather; NDICT=4 makes selects cheaper than a gather -- this is
the same argument the paper uses for limiting the dictionary to 4 entries).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import jax.experimental.pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import pallas_interpret

from repro.assist.schemes.cpack import (NDICT, CODE_ZERO, CODE_FULL0,
                                      CODE_PART0, CODE_ZEXT)


def _decompress_kernel(ok_ref, dict_ref, codes_ref, payload_ref, raw_ref,
                       out_ref, *, block_bytes: int):
    bn = ok_ref.shape[0]
    W = block_bytes // 4
    nib = codes_ref[...].astype(jnp.int32)
    codes = jnp.stack([nib & 0xF, (nib >> 4) & 0xF], axis=-1).reshape(bn, W)
    pay = payload_ref[...].astype(jnp.int32)             # [bn, W]
    d = dict_ref[...].astype(jnp.uint32)                 # [bn, 4]
    w = jnp.zeros((bn, W), jnp.uint32)
    for k in range(NDICT):                               # select chain
        dk = d[:, k:k + 1]
        w = jnp.where(codes == CODE_FULL0 + k, dk, w)
        w = jnp.where(codes == CODE_PART0 + k,
                      (dk & jnp.uint32(0xFFFFFF00)) | pay.astype(jnp.uint32), w)
    w = jnp.where(codes == CODE_ZEXT, pay.astype(jnp.uint32), w)
    # words -> bytes
    b = jax.lax.bitcast_convert_type(w, jnp.uint8).reshape(bn, block_bytes)
    ok = ok_ref[...] != 0                                # [bn, 1]
    out_ref[...] = jnp.where(ok, b, raw_ref[...])


def decompress_pallas(ok, dict_, codes, payload, raw, *, block_bytes: int = 512,
                      bn: int | None = None, interpret: bool | None = None):
    nb = ok.shape[0]
    W = block_bytes // 4
    if bn is None:  # largest power-of-two tile that divides nb
        bn = next(b for b in (8, 4, 2, 1) if nb % b == 0)
    assert nb % bn == 0
    kernel = functools.partial(_decompress_kernel, block_bytes=block_bytes)
    return pl.pallas_call(
        kernel,
        grid=(nb // bn,),
        in_specs=[
            pl.BlockSpec((bn, 1), lambda i: (i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((bn, NDICT), lambda i: (i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((bn, W // 2), lambda i: (i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((bn, W), lambda i: (i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((bn, block_bytes), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((bn, block_bytes), lambda i: (i, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((nb, block_bytes), jnp.uint8),
        interpret=pallas_interpret(interpret),
    )(ok, dict_, codes, payload, raw)
