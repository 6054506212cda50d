"""Flash-decode Pallas kernel over int8-compressed KV cache.

Grid (B, G, S/bs): online-softmax accumulation over KV tiles; the int8 KV
tile is dequantized in VREGs right after the HBM->VMEM DMA (the blocking
"high-priority decompression warp" of the paper, fused structurally).

Scratch per (B, G): m [group, 1] running max, l [group, 1] running sum,
acc [group, D] weighted values.  Written to out on the last S tile.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import jax.experimental.pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import pallas_interpret

NEG_INF = -1e30


def _decode_kernel(len_ref, q_ref, k8_ref, ks_ref, v8_ref, vs_ref, o_ref,
                   m_s, l_s, acc_s, *, ns: int, bs: int, quantized: bool):
    b = pl.program_id(0)
    s = pl.program_id(2)

    @pl.when(s == 0)
    def _init():
        m_s[...] = jnp.full_like(m_s, NEG_INF)
        l_s[...] = jnp.zeros_like(l_s)
        acc_s[...] = jnp.zeros_like(acc_s)

    group, D = q_ref.shape[2], q_ref.shape[3]
    q = q_ref[0, 0].astype(jnp.float32)                   # [group, D]
    if quantized:
        k = k8_ref[0, 0].astype(jnp.float32) * ks_ref[0, 0][:, None]
        v = v8_ref[0, 0].astype(jnp.float32) * vs_ref[0, 0][:, None]
    else:
        k = k8_ref[0, 0].astype(jnp.float32)              # [bs, D]
        v = v8_ref[0, 0].astype(jnp.float32)
    logits = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * (D ** -0.5)  # [group, bs]
    # length mask (cache may be partially filled)
    pos = s * bs + jax.lax.broadcasted_iota(jnp.int32, (1, bs), 1)
    valid = pos < len_ref[b]
    logits = jnp.where(valid, logits, NEG_INF)

    m_prev = m_s[...]                                     # [group, 1]
    m_new = jnp.maximum(m_prev, jnp.max(logits, axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(logits - m_new)                           # [group, bs]
    p = jnp.where(valid, p, 0.0)
    # select, don't rely on the zero weight: invalid rows may hold
    # non-finite garbage and 0 * NaN = NaN
    v = jnp.where(valid.reshape(bs, 1), v, 0.0)
    l_s[...] = l_s[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
    acc_s[...] = acc_s[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_s[...] = m_new

    @pl.when(s == ns - 1)
    def _done():
        denom = jnp.maximum(l_s[...], 1e-30)
        o_ref[0, 0] = (acc_s[...] / denom).astype(o_ref.dtype)


def decode_attn(q, k, ks, v, vs, lengths, *, bs: int = 128,
                out_dtype=jnp.bfloat16, interpret: bool | None = None):
    """q: [B, H, D]; k/v: int8 or bf16 [B, G, S, D]; ks/vs: f32[B, G, S]
    (ignored when k is not int8); lengths: int32[B] -> [B, H, D]."""
    B, H, D = q.shape
    _, G, S, _ = k.shape
    group = H // G
    assert S % bs == 0
    ns = S // bs
    quantized = (k.dtype == jnp.int8)
    q4 = q.reshape(B, G, group, D)
    kernel = functools.partial(_decode_kernel, ns=ns, bs=bs,
                               quantized=quantized)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, G, ns),
            in_specs=[
                pl.BlockSpec((1, 1, group, D), lambda b, g, s, L: (b, g, 0, 0),
                             memory_space=pltpu.VMEM),
                pl.BlockSpec((1, 1, bs, D), lambda b, g, s, L: (b, g, s, 0),
                             memory_space=pltpu.VMEM),
                pl.BlockSpec((1, 1, bs), lambda b, g, s, L: (b, g, s),
                             memory_space=pltpu.VMEM),
                pl.BlockSpec((1, 1, bs, D), lambda b, g, s, L: (b, g, s, 0),
                             memory_space=pltpu.VMEM),
                pl.BlockSpec((1, 1, bs), lambda b, g, s, L: (b, g, s),
                             memory_space=pltpu.VMEM),
            ],
            out_specs=pl.BlockSpec((1, 1, group, D),
                                   lambda b, g, s, L: (b, g, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((group, 1), jnp.float32),
                pltpu.VMEM((group, 1), jnp.float32),
                pltpu.VMEM((group, D), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, G, group, D), out_dtype),
        interpret=pallas_interpret(interpret),
    )(lengths, q4, k, ks, v, vs)
    return out.reshape(B, H, D)
