"""jit'd wrappers for the fused compressed-weight matmuls."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.fused_matmul import fused_matmul as fm
from repro.kernels.fused_matmul import ref as fm_ref


@functools.partial(jax.jit, static_argnames=("gk", "bm", "bn"))
def matmul_q8(x, w8, scale, *, gk: int = 256, bm: int = 128, bn: int = 256):
    return fm.matmul_q8(x, w8, scale, gk=gk, bm=bm, bn=bn)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk"))
def matmul_bdi(x, base, mask, deltas, *, bm: int = 128, bn: int = 256,
               bk: int = 128):
    return fm.matmul_bdi(x, base, mask, deltas, bm=bm, bn=bn, bk=bk)


# layout builders (host-side, the paper's 5.3.1 initial setup)
make_q8_layout = fm_ref.make_q8_layout
make_bdi_b2d1_layout = fm_ref.make_bdi_b2d1_layout
