"""jit'd wrapper for the FPC decompress kernel."""
from __future__ import annotations

import functools

import numpy as np
import jax
import jax.numpy as jnp

from repro.assist import bytesops as bo
from repro.assist.schemes.fpc import FPCPacked, compress
from repro.kernels.fpc import fpc as fpc_kernel


@functools.partial(jax.jit, static_argnames=("block_bytes", "shape", "dtype"))
def _decompress(stream, offsets, seg_enc, *, block_bytes, shape, dtype):
    blocks = fpc_kernel.decompress_pallas(
        stream, offsets, seg_enc, block_bytes=block_bytes)
    flat = blocks.reshape(-1)
    n = int(np.prod(shape)) * jnp.dtype(dtype).itemsize
    return bo.from_bytes(flat[:n], dtype, shape)


def decompress(c: FPCPacked):
    return _decompress(c.stream, c.offsets, c.seg_enc,
                       block_bytes=c.block_bytes, shape=c.shape,
                       dtype=c.dtype_name)
