"""jit'd public wrappers for the BDI Pallas kernels."""
from __future__ import annotations

import functools

import numpy as np
import jax
import jax.numpy as jnp

from repro.assist import bytesops as bo
from repro.assist.schemes import bdi as bdi_scheme
from repro.kernels.bdi import bdi as bdi_kernel
from repro.kernels.bdi import ref as bdi_ref

# encoding ids the variable-rate kernel supports (no 8-byte words: 64-bit
# carries are not worth emulating on the VPU for float tensors; DESIGN.md 2)
KERNEL_ENCODINGS = tuple(
    bdi_scheme.ENC_BY_NAME[n][0]
    for n in ("zeros", "rep8", "b4d1", "b4d2", "b2d1"))


def compress_for_kernel(x, enc: str, block_bytes: int = 512):
    """Host-side: tensor -> kernel-native SoA layout (see kernels/bdi/ref.py)."""
    return bdi_ref.layout_from_uniform(x, enc, block_bytes)


@functools.partial(jax.jit, static_argnames=("enc", "block_bytes", "shape",
                                             "dtype"))
def decompress(base, mask, deltas, *, enc: str, block_bytes: int,
               shape: tuple, dtype: str):
    """Kernel-accelerated uniform-encoding decompression -> tensor."""
    words = bdi_kernel.decompress_pallas(
        base, mask, deltas, enc=enc, block_bytes=block_bytes)
    wb, _ = bdi_kernel.ENC_PARAMS[enc]
    blocks = bo.block_from_words(
        words if wb != 8 else words, wb, block_bytes)
    flat = blocks.reshape(-1)
    n = int(np.prod(shape)) * jnp.dtype(dtype).itemsize
    return bo.from_bytes(flat[:n], dtype, shape)


@functools.partial(jax.jit, static_argnames=("enc", "block_bytes"))
def compress(words, *, enc: str, block_bytes: int = 512):
    """Kernel-accelerated fixed-encoding compression (low-priority warp)."""
    return bdi_kernel.compress_pallas(words, enc=enc,
                                      block_bytes=block_bytes)


def compress_packed_for_kernel(x, block_bytes: int = 512):
    """Host-side variable-rate compression restricted to kernel encodings."""
    return bdi_scheme.compress_packed(x, block_bytes=block_bytes,
                                      allowed=KERNEL_ENCODINGS)


@functools.partial(jax.jit, static_argnames=("block_bytes", "shape", "dtype"))
def decompress_packed(stream, offsets, enc, *, block_bytes: int, shape: tuple,
                      dtype: str):
    """Variable-rate kernel decode of a BDIPacked stream -> tensor."""
    blocks = bdi_kernel.decompress_packed_pallas(
        stream, offsets, enc, block_bytes=block_bytes)
    flat = blocks.reshape(-1)
    n = int(np.prod(shape)) * jnp.dtype(dtype).itemsize
    return bo.from_bytes(flat[:n], dtype, shape)
