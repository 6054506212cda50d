"""jit'd wrapper for the C-Pack decompress kernel."""
from __future__ import annotations

import functools

import numpy as np
import jax
import jax.numpy as jnp

from repro.assist import bytesops as bo
from repro.assist.schemes.cpack import CPacked, compress
from repro.kernels.cpack import cpack as cpack_kernel


@functools.partial(jax.jit, static_argnames=("block_bytes", "shape", "dtype"))
def _decompress(ok_u8, dict_, codes, payload, raw, *, block_bytes, shape,
                dtype):
    blocks = cpack_kernel.decompress_pallas(
        ok_u8, dict_, codes, payload, raw, block_bytes=block_bytes)
    flat = blocks.reshape(-1)
    n = int(np.prod(shape)) * jnp.dtype(dtype).itemsize
    return bo.from_bytes(flat[:n], dtype, shape)


def decompress(c: CPacked):
    return _decompress(c.ok[:, None].astype(jnp.uint8), c.dict_, c.codes,
                       c.payload, c.raw, block_bytes=c.block_bytes,
                       shape=c.shape, dtype=c.dtype_name)
