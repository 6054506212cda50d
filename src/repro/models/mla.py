"""DeepSeek-V2 Multi-head Latent Attention (MLA).

MLA caches a single low-rank LATENT per token (kv_lora_rank + rope_head_dim
floats) instead of per-head K/V -- the model architecture itself is a KV
compressor.  This is the paper-synergy arch of the assignment (DESIGN.md 5):
CABA's KV-compression site stacks int8 block scaling ON TOP of the latent,
compounding the two ratios.

Two execution forms, numerically identical (tested):
* EXPANDED (train/prefill): latent -> per-head K/V via ``wkv_b``, then
  standard chunked flash attention.  Compute-optimal when every token is new.
* ABSORBED (decode): fold ``w_uk`` into the query and ``w_uv`` into the
  output so attention runs directly against the latent cache -- the cache
  read per step is O(S * (kv_lora + rope_dim)) instead of O(S * H * dh).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models.layers import (_dense_init, apply_rope, chunked_attention,
                                 NEG_INF)
from repro.launch.sharding import shard
from repro.models.quantized import getw


def _rms(x, scale, eps=1e-6):
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps) * scale).astype(x.dtype)


def mla_init(rng, cfg: ArchConfig):
    m = cfg.mla
    D, H = cfg.d_model, cfg.n_heads
    qd = m.nope_head_dim + m.rope_head_dim
    ks = jax.random.split(rng, 6)
    p = {
        "wkv_a": _dense_init(ks[0], (D, m.kv_lora_rank + m.rope_head_dim)),
        "kv_norm": jnp.ones((m.kv_lora_rank,), jnp.float32),
        "wkv_b": _dense_init(ks[1], (m.kv_lora_rank,
                                     H * (m.nope_head_dim + m.v_head_dim))),
        "wo": _dense_init(ks[2], (H * m.v_head_dim, D)),
    }
    if m.q_lora_rank:
        p["wq_a"] = _dense_init(ks[3], (D, m.q_lora_rank))
        p["q_norm"] = jnp.ones((m.q_lora_rank,), jnp.float32)
        p["wq_b"] = _dense_init(ks[4], (m.q_lora_rank, H * qd))
    else:
        p["wq"] = _dense_init(ks[5], (D, H * qd))
    return p


def _queries(cfg: ArchConfig, p, x, positions):
    """-> q_nope [B,S,H,dn], q_rope [B,S,H,dr] (rope applied)."""
    m = cfg.mla
    B, S, _ = x.shape
    H = cfg.n_heads
    qd = m.nope_head_dim + m.rope_head_dim
    if m.q_lora_rank:
        cq = _rms(jnp.einsum("bsd,dr->bsr", x, getw(p, "wq_a")), p["q_norm"])
        q = jnp.einsum("bsr,rf->bsf", cq, getw(p, "wq_b"))
    else:
        q = jnp.einsum("bsd,df->bsf", x, getw(p, "wq"))
    q = q.reshape(B, S, H, qd)
    q_nope = q[..., :m.nope_head_dim]
    q_rope = apply_rope(q[..., m.nope_head_dim:], positions, cfg.rope_theta)
    return q_nope, q_rope


def _latent(cfg: ArchConfig, p, x, positions):
    """-> c_kv [B,S,lora] (normalized), k_rope [B,S,dr] (rope applied)."""
    m = cfg.mla
    kv_a = jnp.einsum("bsd,dr->bsr", x, getw(p, "wkv_a"))
    c_kv = _rms(kv_a[..., :m.kv_lora_rank], p["kv_norm"])
    k_rope = kv_a[..., m.kv_lora_rank:]
    # shared single-head rope key: add a head axis for apply_rope, drop after
    k_rope = apply_rope(k_rope[..., None, :], positions, cfg.rope_theta)[..., 0, :]
    return c_kv, k_rope


def mla_apply(cfg: ArchConfig, p, x, *, positions=None):
    """Expanded-form forward (train/prefill).

    Returns (out [B,S,D], cache (c_kv [B,S,lora], k_rope [B,S,dr])).
    """
    m = cfg.mla
    B, S, D = x.shape
    H = cfg.n_heads
    if positions is None:
        positions = jnp.arange(S)[None, :]
    q_nope, q_rope = _queries(cfg, p, x, positions)
    c_kv, k_rope = _latent(cfg, p, x, positions)
    kv = jnp.einsum("bsr,rf->bsf", c_kv, getw(p, "wkv_b"))
    kv = kv.reshape(B, S, H, m.nope_head_dim + m.v_head_dim)
    k_nope, v = kv[..., :m.nope_head_dim], kv[..., m.nope_head_dim:]
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope[:, :, None, :],
                                  (B, S, H, m.rope_head_dim))], axis=-1)
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    q = shard(q.transpose(0, 2, 1, 3), "batch", "model", None, None)
    k = shard(k.transpose(0, 2, 1, 3), "batch", "model", None, None)
    v = shard(v.transpose(0, 2, 1, 3), "batch", "model", None, None)
    scale = (m.nope_head_dim + m.rope_head_dim) ** -0.5
    out = chunked_attention(q, k, v, causal=cfg.causal, scale=scale)
    out = out.transpose(0, 2, 1, 3).reshape(B, S, H * m.v_head_dim)
    return jnp.einsum("bsf,fd->bsd", out, getw(p, "wo")), (c_kv, k_rope)


def _absorb_mats(cfg: ArchConfig, p):
    """wkv_b split into the two absorbable factors.
    w_uk: [lora, H, dn]; w_uv: [lora, H, dv]."""
    m = cfg.mla
    H = cfg.n_heads
    w = getw(p, "wkv_b").reshape(m.kv_lora_rank, H, m.nope_head_dim + m.v_head_dim)
    return w[..., :m.nope_head_dim], w[..., m.nope_head_dim:]


def mla_decode(cfg: ArchConfig, p, x, state, pos):
    """Absorbed-form single-token decode.

    x: [B,1,D]; state: {"c","r"} (bf16 latent cache) or {"c8","cs","r"}
    (int8-compressed latent, the CABA KV site stacked on MLA's own
    compression); pos: int32[B] current lengths.
    Returns (out [B,1,D], new_state).
    """
    m = cfg.mla
    B = x.shape[0]
    H = cfg.n_heads
    uniform = (pos.ndim == 0)                # scalar: production decode path
    pos_rows = jnp.broadcast_to(pos, (B,)) if uniform else pos
    q_nope, q_rope = _queries(cfg, p, x, pos_rows[:, None])  # [B,1,H,*]
    c_new, r_new = _latent(cfg, p, x, pos_rows[:, None])     # [B,1,lora/dr]
    w_uk, w_uv = _absorb_mats(cfg, p)
    # fold W_uk into the query: q_lat [B,H,lora]
    q_lat = jnp.einsum("bhn,rhn->bhr", q_nope[:, 0].astype(jnp.float32),
                       w_uk.astype(jnp.float32))

    if uniform:
        def upd3(c, n):
            return jax.lax.dynamic_update_slice(
                c, n.astype(c.dtype), (0, pos, 0))

        def upd2(c, n):
            return jax.lax.dynamic_update_slice(
                c, n.astype(c.dtype), (0, pos))
    else:
        def upd3(c, n):
            return jax.vmap(lambda cb, nb, pb: jax.lax.dynamic_update_slice(
                cb, nb.astype(cb.dtype), (pb, 0)))(c, n, pos)

        def upd2(c, n):
            return jax.vmap(lambda cb, nb, pb: jax.lax.dynamic_update_slice(
                cb, nb.astype(cb.dtype), (pb,)))(c, n, pos)

    compressed = "c8" in state
    cache_r = upd3(state["r"], r_new)
    scale = (m.nope_head_dim + m.rope_head_dim) ** -0.5
    if compressed:
        from repro.serving.kv_cache import quantize_token
        c8_new, cs_new = quantize_token(c_new)               # [B,1,lora]/[B,1]
        c8 = upd3(state["c8"], c8_new)
        cs = upd2(state["cs"], cs_new)
        state = dict(state, c8=c8, cs=cs, r=cache_r)
        Smax = c8.shape[1]
        valid = jnp.arange(Smax)[None, :] <= pos_rows[:, None]  # incl. new
        # scales factor out of the latent contractions: int8 bytes in HBM
        lat_logits = jnp.einsum("bhr,bsr->bhs", q_lat,
                                c8.astype(jnp.float32)) * cs[:, None, :]
        logits = (lat_logits
                  + jnp.einsum("bhr,bsr->bhs",
                               q_rope[:, 0].astype(jnp.float32),
                               cache_r.astype(jnp.float32))) * scale
        logits = jnp.where(valid[:, None, :], logits, NEG_INF)
        w = jax.nn.softmax(logits, axis=-1)
        o_lat = jnp.einsum("bhs,bsr->bhr", w * state["cs"][:, None, :],
                           state["c8"].astype(jnp.float32))
    else:
        from repro.kernels.decode_attn.ops import masked_latent_decode_attn
        cache_c = upd3(state["c"], c_new)
        state = dict(state, c=cache_c, r=cache_r)
        Smax = cache_c.shape[1]
        valid = jnp.arange(Smax)[None, :] <= pos_rows[:, None]  # incl. new
        o_lat = masked_latent_decode_attn(
            q_lat, q_rope[:, 0].astype(jnp.float32), cache_c, cache_r,
            valid, scale)
    # fold W_uv into the output
    o = jnp.einsum("bhr,rhv->bhv", o_lat, w_uv.astype(jnp.float32))
    out = jnp.einsum("bf,fd->bd", o.reshape(B, H * m.v_head_dim).astype(x.dtype),
                     getw(p, "wo"))
    return out[:, None], state


def mla_init_cache(cfg: ArchConfig, batch: int, max_len: int, dtype=jnp.bfloat16):
    m = cfg.mla
    return (jnp.zeros((batch, max_len, m.kv_lora_rank), dtype),
            jnp.zeros((batch, max_len, m.rope_head_dim), dtype))


def mla_paged_decode(cfg: ArchConfig, p, x, pools_j, bt, lengths, *,
                     has_warm: bool = True, backend: str = "gather"):
    """Absorbed-form decode over LATENT PAGES (the "mla_latent" page kind).

    x: [B,1,D]; pools_j: one layer's tiered latent pools (kh = latent
    c [1+hot, 1, ps, lora], vh = rope key r [1+hot, 1, ps, dr], plus the
    int8 warm planes); bt: int32[B, max_pages] encoded locations;
    lengths: int32[B].  The write page (lengths // ps) must be hot.
    Numerically identical to :func:`mla_decode` over a dense cache when
    every page is hot (shared reference attention, see
    kernels/decode_attn/ops.py::masked_latent_decode_attn).
    """
    from repro.kernels.decode_attn import ops as attn_ops
    m = cfg.mla
    B = x.shape[0]
    H = cfg.n_heads
    ch, rh = pools_j["kh"], pools_j["vh"]
    ps = ch.shape[2]
    q_nope, q_rope = _queries(cfg, p, x, lengths[:, None])   # [B,1,H,*]
    c_new, r_new = _latent(cfg, p, x, lengths[:, None])      # [B,1,lora/dr]
    w_uk, w_uv = _absorb_mats(cfg, p)
    q_lat = jnp.einsum("bhn,rhn->bhr", q_nope[:, 0].astype(jnp.float32),
                       w_uk.astype(jnp.float32))
    # append the new token's latent into its (hot) page
    wp, offs = lengths // ps, lengths % ps
    locs_w = jnp.take_along_axis(bt, wp[:, None], axis=1)[:, 0]
    ch = ch.at[locs_w, 0, offs].set(c_new[:, 0, :].astype(ch.dtype))
    rh = rh.at[locs_w, 0, offs].set(r_new[:, 0, :].astype(rh.dtype))
    pools_j = dict(pools_j, kh=ch, vh=rh)
    scale = (m.nope_head_dim + m.rope_head_dim) ** -0.5
    o_lat = attn_ops.get_latent_backend(backend)(
        q_lat, q_rope[:, 0].astype(jnp.float32), pools_j, bt, lengths + 1,
        scale=scale, has_warm=has_warm)
    o = jnp.einsum("bhr,rhv->bhv", o_lat, w_uv.astype(jnp.float32))
    out = jnp.einsum("bf,fd->bd",
                     o.reshape(B, H * m.v_head_dim).astype(x.dtype),
                     getw(p, "wo"))
    return out[:, None], pools_j
