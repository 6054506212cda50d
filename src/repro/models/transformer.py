"""Generic decoder/encoder stack over heterogeneous block patterns.

A model is [head layers] + [scan over the repeating ``block_pattern``] +
[tail layers].  The scanned segment stacks each pattern-position's params
with a leading ``n_scan`` axis and runs ``lax.scan`` so tracing/compile time
is O(pattern), not O(n_layers) -- required for the 60-80 layer dry-runs.

Block kinds:
  attn        GQA or MLA attention + FFN (MoE if cfg.moe, else dense MLP)
  attn_local  same with windowed attention
  attn_dense  attention + dense MLP even in MoE archs (DeepSeek first_dense)
  shared_attn Zamba2: one attention+MLP block whose WEIGHTS are shared by
              every invocation (params live once at stack level)
  mamba2      Mamba2 SSD token mixer (residual inside block here)
  rwkv6       RWKV6 time+channel mix (residual inside)

Decode caches (per attention layer):
  full    k/v (or MLA c/r) sized [*, max_len, *]; validity = position < len
  window  rolling buffer of ``window`` slots + stored absolute positions
"""
from __future__ import annotations

import dataclasses
from functools import partial

import numpy as np
import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models import layers as L
from repro.models import mla as MLA
from repro.models import moe as MOE
from repro.models import ssm as SSM
from repro.serving import kv_cache as KV
from repro.models import quantized as Q
from repro.launch.sharding import shard

NEG_INF = L.NEG_INF


# ---------------------------------------------------------------------------
# stack structure
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class StackPlan:
    head: tuple        # unstacked leading layer kinds
    pattern: tuple     # scanned repeating kinds
    n_scan: int
    tail: tuple        # unstacked trailing kinds
    has_shared: bool


def stack_plan(cfg: ArchConfig) -> StackPlan:
    head = ()
    if cfg.moe is not None and cfg.moe.first_dense:
        head = ("attn_dense",) * cfg.moe.first_dense
    remaining = cfg.n_layers - len(head)
    pat = cfg.block_pattern
    n_scan = remaining // len(pat)
    tail = tuple(pat[: remaining % len(pat)])
    return StackPlan(head, pat, n_scan, tail,
                     has_shared="shared_attn" in pat or "shared_attn" in tail)


def _is_attn(kind: str) -> bool:
    return kind in ("attn", "attn_local", "attn_dense", "shared_attn")


# ---------------------------------------------------------------------------
# single-block init / apply
# ---------------------------------------------------------------------------

def block_init(rng, cfg: ArchConfig, kind: str):
    if kind == "mamba2":
        return {"norm": L.norm_init(cfg), "mix": SSM.mamba2_init(rng, cfg)}
    if kind == "rwkv6":
        return SSM.rwkv6_init(rng, cfg)
    assert _is_attn(kind), kind
    k1, k2 = jax.random.split(rng)
    attn = (MLA.mla_init(k1, cfg) if cfg.mla is not None
            else L.gqa_init(k1, cfg))
    use_moe = cfg.moe is not None and kind not in ("attn_dense", "shared_attn")
    ffn = MOE.moe_init(k2, cfg) if use_moe else L.mlp_init(k2, cfg)
    return {"norm1": L.norm_init(cfg), "attn": attn,
            "norm2": L.norm_init(cfg), "ffn": ffn}


def _ffn_apply(cfg, kind, p, x, *, moe_dropless: bool = False):
    use_moe = cfg.moe is not None and kind not in ("attn_dense", "shared_attn")
    if use_moe:
        return MOE.moe_apply(cfg, p["ffn"], x, dropless=moe_dropless)
    return L.mlp_apply(cfg, p["ffn"], x), jnp.float32(0.0)


def block_apply_seq(cfg: ArchConfig, kind: str, p, x, *, positions=None,
                    state=None, want_state: bool, moe_dropless: bool = False,
                    true_len=None):
    """Full-sequence forward for one block.

    Returns (x_out, aux_loss, new_state_or_None).  ``state=None`` starts
    fresh (train); a state pytree continues it (chunked prefill).
    ``true_len`` (int32[B], optional) marks right-padding (bucketed
    prefill): attention layers need nothing (causal masking already keeps
    pads out of real positions) but recurrence layers must carry their
    state through pads untouched.
    """
    B, S, D = x.shape
    if kind == "mamba2":
        h = L.norm_apply(cfg, p["norm"], x)
        out, st = SSM.mamba2_apply(cfg, p["mix"], h, state,
                                   true_len=true_len)
        return x + out, jnp.float32(0.0), (st if want_state else None)
    if kind == "rwkv6":
        out, st = SSM.rwkv6_apply(cfg, p, x, state, true_len=true_len)
        return out, jnp.float32(0.0), (st if want_state else None)
    assert _is_attn(kind)
    local = kind == "attn_local" or (kind == "shared_attn" and cfg.window > 0)
    h = L.norm_apply(cfg, p["norm1"], x)
    if cfg.mla is not None:
        out, (c_kv, k_rope) = MLA.mla_apply(cfg, p["attn"], h,
                                            positions=positions)
        st = {"c": c_kv, "r": k_rope} if want_state else None
    else:
        out, (k, v) = L.gqa_apply(cfg, p["attn"], h, local=local,
                                  positions=positions)
        st = {"k": k, "v": v} if want_state else None
    x = x + out
    h = L.norm_apply(cfg, p["norm2"], x)
    out, aux = _ffn_apply(cfg, kind, p, h, moe_dropless=moe_dropless)
    return x + out, aux, st


def block_apply_decode(cfg: ArchConfig, kind: str, p, x, state, pos):
    """One-token decode for one block.  x: [B,1,D]; pos: int32[B] lengths."""
    if kind == "mamba2":
        h = L.norm_apply(cfg, p["norm"], x)
        out, st = SSM.mamba2_decode(cfg, p["mix"], h, state)
        return x + out, st
    if kind == "rwkv6":
        return SSM.rwkv6_apply(cfg, p, x, state)
    assert _is_attn(kind)
    local = kind == "attn_local" or (kind == "shared_attn" and cfg.window > 0)
    h = L.norm_apply(cfg, p["norm1"], x)
    if cfg.mla is not None:
        out, state = MLA.mla_decode(cfg, p["attn"], h, state, pos)
    else:
        out, state = _gqa_cached_decode(cfg, p["attn"], h, state, pos,
                                        local=local)
    x = x + out
    h = L.norm_apply(cfg, p["norm2"], x)
    out, _ = _ffn_apply(cfg, kind, p, h, moe_dropless=True)
    return x + out, state


def _gqa_cached_decode(cfg, p, x, state, pos, *, local: bool):
    """GQA decode against a full or rolling-window cache (bf16 or int8).

    ``pos`` is int32[B] (per-row lengths: continuous-batching engine) or a
    scalar (uniform position: the production decode path).  The scalar form
    writes the cache with one plain dynamic_update_slice, which GSPMD
    shards cleanly; the vmapped per-row write forces cache replication
    ("involuntary full remat") and is kept only for the engine (SS Perf).
    """
    B = x.shape[0]
    uniform = (pos.ndim == 0)
    pos_rows = jnp.broadcast_to(pos, (B,)) if uniform else pos
    compressed = "k8" in state
    W = (state["k8"] if compressed else state["k"]).shape[2]
    q, k_new, v_new = L.gqa_qkv(cfg, p, x, pos_rows[:, None])
    slot = pos % W

    if uniform:
        def upd(c, n):
            return jax.lax.dynamic_update_slice(
                c, n.astype(c.dtype), (0, 0, slot, 0))
    else:
        def upd(c, n):
            return jax.vmap(lambda cb, nb, sb: jax.lax.dynamic_update_slice(
                cb, nb.astype(cb.dtype), (0, sb, 0)))(c, n, slot)

    if compressed:
        if uniform:
            k8, ks = KV.quantize_token(k_new)
            v8, vs = KV.quantize_token(v_new)
            state = dict(state,
                         k8=upd(state["k8"], k8),
                         ks=jax.lax.dynamic_update_slice(
                             state["ks"], ks.astype(state["ks"].dtype),
                             (0, 0, slot)),
                         v8=upd(state["v8"], v8),
                         vs=jax.lax.dynamic_update_slice(
                             state["vs"], vs.astype(state["vs"].dtype),
                             (0, 0, slot)))
        else:
            state = dict(state,
                         **KV.update_kv_int8(state, k_new, v_new, slot))
    else:
        state = dict(state, k=upd(state["k"], k_new),
                     v=upd(state["v"], v_new))
    if "pos_arr" in state:                    # rolling window cache
        if uniform:
            pos_arr = jax.lax.dynamic_update_slice(
                state["pos_arr"],
                jnp.broadcast_to(pos, (B, 1)).astype(jnp.int32),
                (0, slot))
        else:
            pos_arr = jax.vmap(lambda pa, sb, pb: pa.at[sb].set(pb))(
                state["pos_arr"], slot, pos)
        valid = (pos_arr <= pos_rows[:, None]) & (pos_arr >= 0)
        if local and cfg.window:
            valid &= pos_arr > (pos_rows[:, None] - cfg.window)
        state = dict(state, pos_arr=pos_arr)
    else:
        s_idx = jnp.arange(W)
        valid = s_idx[None, :] <= pos_rows[:, None]
        if local and cfg.window:
            valid &= s_idx[None, :] > (pos_rows[:, None] - cfg.window)
    if compressed:
        out = _masked_decode_attn_q8(q, state["k8"], state["ks"],
                                     state["v8"], state["vs"], valid)
    else:
        out = _masked_decode_attn(q, state["k"], state["v"], valid)
    out = out.transpose(0, 2, 1, 3).reshape(B, 1, -1)
    return jnp.einsum("bsf,fd->bsd", out, Q.getw(p, "wo")), state


def _masked_decode_attn(q, k, v, valid):
    """q: [B,H,1,dh]; k/v: [B,G,W,dh]; valid: bool[B,W].

    Delegates to the shared reference attention
    (kernels/decode_attn/ops.py::masked_decode_attn) -- one implementation
    keeps the dense engine and the gather backend bit-identical.
    """
    from repro.kernels.decode_attn.ops import masked_decode_attn
    return masked_decode_attn(q[:, :, 0], k, v, valid)[:, :, None, :]


def _masked_decode_attn_q8(q, k8, ks, v8, vs, valid):
    """int8-cache decode attention; scales factor out of the contractions
    (kv_cache.py) so HLO reads int8 bytes -- the CABA KV site."""
    B, H, _, dh = q.shape
    G, W = k8.shape[1], k8.shape[2]
    group = H // G
    qf = (q.astype(jnp.float32) * dh ** -0.5).reshape(B, G, group, dh)
    logits = jnp.einsum("bghd,bgsd->bghs", qf, k8.astype(jnp.float32))
    logits = logits * ks[:, :, None, :]
    logits = jnp.where(valid[:, None, None, :], logits, NEG_INF)
    m = jnp.max(logits, axis=-1, keepdims=True)
    pr = jnp.exp(logits - m)
    out = jnp.einsum("bghs,bgsd->bghd", pr * vs[:, :, None, :],
                     v8.astype(jnp.float32))
    out = out / jnp.sum(pr, axis=-1)[..., None]
    return out.reshape(B, H, 1, v8.shape[-1]).astype(q.dtype)


# ---------------------------------------------------------------------------
# decode-state construction
# ---------------------------------------------------------------------------

def block_init_state(cfg: ArchConfig, kind: str, batch: int, max_len: int,
                     kv_dtype=jnp.bfloat16, kv_mode: str = "bf16"):
    if kind == "mamba2":
        return SSM.mamba2_init_state(cfg, batch)
    if kind == "rwkv6":
        return SSM.rwkv6_init_state(cfg, batch)
    assert _is_attn(kind), kind
    if cfg.mla is not None:
        m = cfg.mla
        if kv_mode == "int8":
            return KV.init_latent_int8(batch, max_len, m.kv_lora_rank,
                                       m.rope_head_dim, kv_dtype)
        c, r = MLA.mla_init_cache(cfg, batch, max_len, kv_dtype)
        return {"c": c, "r": r}
    G, dh = cfg.n_kv_heads, cfg.head_dim
    local = kind == "attn_local" or (kind == "shared_attn" and cfg.window > 0)
    W = cfg.window if (local and cfg.window and cfg.window < max_len) \
        else max_len
    if kv_mode == "int8":
        st = KV.init_kv_int8(batch, G, W, dh)
    else:
        st = {"k": jnp.zeros((batch, G, W, dh), kv_dtype),
              "v": jnp.zeros((batch, G, W, dh), kv_dtype)}
    if W < max_len:
        st["pos_arr"] = jnp.full((batch, W), -1, jnp.int32)
    return st


# ---------------------------------------------------------------------------
# full stack
# ---------------------------------------------------------------------------

def stack_init(rng, cfg: ArchConfig):
    plan = stack_plan(cfg)
    D, V = cfg.d_model, cfg.vocab_size
    params: dict = {"final_norm": L.norm_init(cfg)}
    k_embed, k_head, k_scan, k_tail, k_shared, k_unembed = \
        jax.random.split(rng, 6)
    if cfg.frontend != "audio":
        params["embed"] = (jax.random.normal(k_embed, (V, D), jnp.float32)
                           * 0.02).astype(jnp.bfloat16)
    if not cfg.tie_embeddings:
        params["unembed"] = L._dense_init(k_unembed, (D, V))
    if plan.head:
        params["head_layers"] = [
            block_init(jax.random.fold_in(k_head, i), cfg, kind)
            for i, kind in enumerate(plan.head)]
    if plan.n_scan:
        def one(i):
            kp = jax.random.fold_in(k_scan, i)
            return tuple(
                {} if kind == "shared_attn"
                else block_init(jax.random.fold_in(kp, j), cfg, kind)
                for j, kind in enumerate(plan.pattern))
        # vmapped over the block index, each weight is drawn straight into
        # its stacked [n_scan, ...] array: stacking per-block dicts would
        # hold every layer's weights twice at peak
        params["scan"] = jax.vmap(one)(jnp.arange(plan.n_scan))
    if plan.tail:
        params["tail_layers"] = [
            {} if kind == "shared_attn"
            else block_init(jax.random.fold_in(k_tail, i), cfg, kind)
            for i, kind in enumerate(plan.tail)]
    if plan.has_shared:
        params["shared"] = block_init(k_shared, cfg, "shared_attn")
    return params


def _embed_input(cfg: ArchConfig, params, batch):
    """-> x [B, S, D] from tokens / frames / patches+tokens."""
    if cfg.frontend == "audio":
        return batch["frames"].astype(jnp.bfloat16)
    tok = batch["tokens"]
    x = jnp.take(params["embed"], tok, axis=0)
    if cfg.frontend == "vision" and "patches" in batch:
        x = jnp.concatenate([batch["patches"].astype(x.dtype), x], axis=1)
    return x


def _logits(cfg: ArchConfig, params, x):
    x = L.norm_apply(cfg, params["final_norm"], x)
    w = (params["embed"].T if cfg.tie_embeddings else params["unembed"])
    logits = jnp.einsum("bsd,dv->bsv", x, w)
    return shard(logits.astype(jnp.float32), "batch", None, "model")


def stack_apply_seq(cfg: ArchConfig, params, batch, *, want_state: bool,
                    remat: bool = True, kv_dtype=jnp.bfloat16,
                    max_len: int | None = None, moe_dropless: bool = False,
                    kv_mode: str = "bf16", paged_layout: bool = False):
    """Full-sequence forward (train / prefill).

    Returns (logits f32[B,S,V], aux_loss, state_or_None).  When
    ``want_state``, caches are allocated at ``max_len`` (>= S) so decode can
    continue in place.
    """
    plan = stack_plan(cfg)
    x = _embed_input(cfg, params, batch)
    B, S, D = x.shape
    x = shard(x, "batch", None, None)
    positions = batch.get("positions")
    if positions is None:
        positions = jnp.arange(S)[None, :]
    # bucketed prefill: tokens beyond true_len are right-padding.  Causal
    # attention keeps them out of real positions for free; recurrence
    # layers get the mask so their state ends exactly at true_len.
    true_len = batch.get("true_len")
    max_len = max_len or S
    shared_p = params.get("shared")
    from repro.launch.sharding import match_vma
    aux_total = match_vma(jnp.float32(0.0), x)
    states: dict = {}

    def run_block(kind, p, x, st_in):
        p = p if kind != "shared_attn" else shared_p
        return block_apply_seq(cfg, kind, p, x, positions=positions,
                               state=st_in, want_state=want_state,
                               moe_dropless=moe_dropless, true_len=true_len)

    # head layers
    for i, kind in enumerate(plan.head):
        x, aux, st = run_block(kind, params["head_layers"][i], x, None)
        aux_total += aux
        if want_state:
            states[f"head_{i}"] = _pad_seq_state(cfg, kind, st, S, max_len,
                                                 kv_dtype, kv_mode,
                                                 paged_layout, true_len)

    # scanned segment
    if plan.n_scan:
        def body(carry, layer_p):
            x, aux = carry
            sts = []
            for j, kind in enumerate(plan.pattern):
                x, a, st = run_block(kind, layer_p[j], x, None)
                aux += a
                sts.append(_pad_seq_state(cfg, kind, st, S, max_len,
                                          kv_dtype, kv_mode, paged_layout,
                                          true_len)
                           if want_state else 0)
            x = shard(x, "batch", None, None)
            return (x, aux), tuple(sts)

        body_fn = jax.checkpoint(body) if remat else body
        (x, aux_total), scan_states = jax.lax.scan(
            body_fn, (x, aux_total), params["scan"])
        if want_state:
            states["scan"] = scan_states

    # tail layers
    for i, kind in enumerate(plan.tail):
        x, aux, st = run_block(kind, params.get("tail_layers", [{}] * 8)[i],
                               x, None)
        aux_total += aux
        if want_state:
            states[f"tail_{i}"] = _pad_seq_state(cfg, kind, st, S, max_len,
                                                 kv_dtype, kv_mode,
                                                 paged_layout, true_len)

    logits = _logits(cfg, params, x)
    if want_state:
        states["len"] = (jnp.broadcast_to(true_len, (B,)).astype(jnp.int32)
                         if true_len is not None
                         else jnp.full((B,), S, jnp.int32))
        return logits, aux_total, states
    return logits, aux_total, None


def _pad_seq_state(cfg, kind, st, S: int, max_len: int,
                   kv_dtype=jnp.bfloat16, kv_mode: str = "bf16",
                   paged_layout: bool = False, true_len=None):
    """Turn a full-seq block state into a decode cache of size max_len.

    ``paged_layout`` keeps local-attention layers at FULL positional layout
    (no rolling-window compaction): the paged engine scatters prefill KV
    into absolute-position pages and masks the window at attention time.
    ``true_len`` (int32[B], optional) marks bucketed-prefill padding: the
    rolling-window compaction then keeps the window trailing the last REAL
    token (pad KV beyond it is garbage that decode validity masks away).
    """
    if st is None:
        return None
    if kind in ("mamba2", "rwkv6"):
        return st
    pad = max_len - S
    if cfg.mla is not None:
        r = jnp.pad(st["r"].astype(kv_dtype), ((0, 0), (0, pad), (0, 0)))
        if kv_mode == "int8":
            c8, cs = KV.quantize_token(st["c"])
            c8 = jnp.pad(c8, ((0, 0), (0, pad), (0, 0)))
            cs = jnp.pad(cs, ((0, 0), (0, pad)), constant_values=1.0)
            return {"c8": c8, "cs": cs, "r": r}
        c = jnp.pad(st["c"].astype(kv_dtype), ((0, 0), (0, pad), (0, 0)))
        return {"c": c, "r": r}
    local = kind == "attn_local" or (kind == "shared_attn" and cfg.window > 0)
    k, v = st["k"], st["v"]
    if local and cfg.window and cfg.window < max_len and not paged_layout:
        W = cfg.window
        B, G = k.shape[0], k.shape[1]
        last = k.shape[2]
        if true_len is None:
            # keep the last `window` keys, placed at their rolling slots
            take = min(W, last)
            ks_, vs_ = k[:, :, -take:], v[:, :, -take:]
            pos = jnp.arange(last - take, last)
            slots = pos % W
            kw = jnp.zeros((B, G, W, k.shape[-1]),
                           k.dtype).at[:, :, slots].set(ks_)
            vw = jnp.zeros((B, G, W, v.shape[-1]),
                           v.dtype).at[:, :, slots].set(vs_)
            pos_arr = jnp.full((B, W), -1, jnp.int32).at[:, slots].set(pos)
        else:
            # window [true_len - W, true_len): for each rolling slot s the
            # unique in-window position with pos % W == s, gathered per
            # row (positions < 0 are marked invalid)
            tl = jnp.broadcast_to(true_len, (B,)).astype(jnp.int32)
            base = tl[:, None] - W                          # [B, 1]
            slots = jnp.arange(W)[None, :]
            pos = base + (slots - base) % W                 # [B, W]
            valid = pos >= 0
            cpos = jnp.clip(pos, 0, last - 1)
            kw = jnp.take_along_axis(k, cpos[:, None, :, None], axis=2)
            vw = jnp.take_along_axis(v, cpos[:, None, :, None], axis=2)
            pos_arr = jnp.where(valid, pos, -1).astype(jnp.int32)
        k, v, extra = kw, vw, {"pos_arr": pos_arr}
    else:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
        extra = {}
    if kv_mode == "int8":
        k8, ks = KV.quantize_token(k)
        v8, vs = KV.quantize_token(v)
        return {"k8": k8, "ks": ks, "v8": v8, "vs": vs, **extra}
    return {"k": k.astype(kv_dtype), "v": v.astype(kv_dtype), **extra}


def stack_init_state(cfg: ArchConfig, batch: int, max_len: int,
                     kv_dtype=jnp.bfloat16, kv_mode: str = "bf16",
                     uniform_pos: bool = False):
    """Fresh decode state for a batch (dry-run decode cells start here).

    ``uniform_pos=True`` stores a SCALAR position (all rows aligned): the
    production decode path whose cache writes shard cleanly (SS Perf).
    The [B]-lengths form serves the continuous-batching engine."""
    plan = stack_plan(cfg)
    states: dict = {"len": (jnp.zeros((), jnp.int32) if uniform_pos
                            else jnp.zeros((batch,), jnp.int32))}
    for i, kind in enumerate(plan.head):
        states[f"head_{i}"] = block_init_state(cfg, kind, batch, max_len,
                                               kv_dtype, kv_mode)
    if plan.n_scan:
        def stack_n(st):
            return jax.tree.map(
                lambda a: jnp.broadcast_to(a[None], (plan.n_scan,) + a.shape),
                st)
        states["scan"] = tuple(
            stack_n(block_init_state(cfg, kind, batch, max_len, kv_dtype,
                                     kv_mode))
            for kind in plan.pattern)
    for i, kind in enumerate(plan.tail):
        states[f"tail_{i}"] = block_init_state(cfg, kind, batch, max_len,
                                               kv_dtype, kv_mode)
    return states


def stack_decode_step(cfg: ArchConfig, params, state, tokens):
    """One decode step.  tokens: int32[B, 1] -> (logits [B,1,V], state')."""
    plan = stack_plan(cfg)
    pos = state["len"]
    if cfg.frontend == "audio":
        raise ValueError("encoder-only arch has no decode step")
    x = jnp.take(params["embed"], tokens, axis=0)
    x = shard(x, "batch", None, None)
    shared_p = params.get("shared")
    new_state: dict = {}

    for i, kind in enumerate(plan.head):
        p = params["head_layers"][i] if kind != "shared_attn" else shared_p
        x, st = block_apply_decode(cfg, kind, p, x, state[f"head_{i}"], pos)
        new_state[f"head_{i}"] = st

    if plan.n_scan:
        def body(x, inp):
            layer_p, layer_st = inp
            sts = []
            for j, kind in enumerate(plan.pattern):
                p = layer_p[j] if kind != "shared_attn" else shared_p
                x, st = block_apply_decode(cfg, kind, p, x, layer_st[j], pos)
                sts.append(st)
            return x, tuple(sts)

        x, scan_states = jax.lax.scan(body, x,
                                      (params["scan"], state["scan"]))
        new_state["scan"] = scan_states

    for i, kind in enumerate(plan.tail):
        p = params.get("tail_layers", [{}] * 8)[i] \
            if kind != "shared_attn" else shared_p
        x, st = block_apply_decode(cfg, kind, p, x, state[f"tail_{i}"], pos)
        new_state[f"tail_{i}"] = st

    new_state["len"] = pos + 1
    return _logits(cfg, params, x), new_state


# ---------------------------------------------------------------------------
# paged decode (repro.cache block-table path)
# ---------------------------------------------------------------------------
#
# The KV cache is a pool of fixed-size pages instead of a dense [B, max_len]
# slab; each request's pages are named by an int32 block table whose entries
# encode the page's tier (tiers.py): loc > 0 hot slot, loc < 0 warm slot
# -loc (int8, dequantized by the attention backend -- the CABA KV site),
# loc == 0 the reserved trash page (masked by the length mask).  With every
# page hot the math below is bit-identical to _gqa_cached_decode over a
# dense cache of the same max_len, which is the paged engine's drop-in
# guarantee.
#
# Coverage is dispatched PER LAYER, not per model: each layer kind maps to
# a PAGE KIND (repro.assist.page_kinds) -- per-head attention KV
# (global-GQA / local-window-GQA / weight-shared), the absorbed-MLA
# latent, or a fixed-size SSM/RWKV state slab -- and the stack is walked
# as SEGMENTS: unstacked head layers, the scanned pattern, unstacked tail
# layers, each segment owning one entry of the tiered pool tuple.  The
# attention math itself is a pluggable backend (kernels/decode_attn/ops.py
# registry: gather / pallas / pallas_int8; latent pages have their own
# backend table, gather-only until the TPU pass).

#: attention layer kinds the paged path can decode (value: uses cfg.window)
PAGED_ATTN_KINDS = {"attn": False, "attn_dense": False, "attn_local": True,
                    "shared_attn": True}
#: recurrence layer kinds parked as non-growing state slabs
PAGED_STATE_KINDS = ("mamba2", "rwkv6")


@dataclasses.dataclass(frozen=True)
class PagedSegment:
    """One pool-owning slice of the stack: a head/tail layer (n_stack=1) or
    one scanned pattern position (n_stack=n_scan)."""
    name: str          # "head_0" | "pat_1" | "tail_0" (state dict keys)
    kind: str          # layer kind (attn / attn_local / mamba2 / ...)
    n_stack: int
    page_kind: str = "attn_kv"     # repro.assist.page_kinds name


def _layer_page_kind(cfg: ArchConfig, kind: str) -> str:
    if kind in PAGED_STATE_KINDS:
        return "state_slab"
    if _is_attn(kind) and cfg.mla is not None:
        return "mla_latent"
    return "attn_kv"


def paged_layer_window(cfg: ArchConfig, kind: str) -> int:
    """Static attention window for one layer kind (0 = global)."""
    return cfg.window if PAGED_ATTN_KINDS.get(kind, False) else 0


def paged_unsupported_layers(cfg: ArchConfig) -> list:
    """Layers the paged decode path cannot serve, as "position:kind" tags.

    Per-layer capability dispatch: a model is paged-decodable iff this is
    empty; the engine surfaces the exact offending layers otherwise.
    Since the page-kind generalization (MLA latent pages, SSM/RWKV state
    parking, weight-shared attention) every decoder layer kind is
    covered; only encoder-only stacks remain out."""
    if cfg.frontend == "audio":
        return ["*:audio-encoder"]
    supported = set(PAGED_ATTN_KINDS) | set(PAGED_STATE_KINDS)
    plan = stack_plan(cfg)
    bad = []
    for i, kind in enumerate(plan.head):
        if kind not in supported:
            bad.append(f"head[{i}]:{kind}")
    for j, kind in enumerate(plan.pattern):
        if kind not in supported:
            bad.append(f"pattern[{j}]:{kind}")
    for i, kind in enumerate(plan.tail):
        if kind not in supported:
            bad.append(f"tail[{i}]:{kind}")
    return bad


def paged_decode_supported(cfg: ArchConfig) -> bool:
    return not paged_unsupported_layers(cfg)


def paged_segments(cfg: ArchConfig) -> tuple:
    """Pool-tuple layout for a paged-decodable model (head, pattern, tail)."""
    plan = stack_plan(cfg)

    def seg(name, kind, n_stack):
        return PagedSegment(name, kind, n_stack, _layer_page_kind(cfg, kind))

    segs = [seg(f"head_{i}", kind, 1) for i, kind in enumerate(plan.head)]
    if plan.n_scan:
        segs += [seg(f"pat_{j}", kind, plan.n_scan)
                 for j, kind in enumerate(plan.pattern)]
    segs += [seg(f"tail_{i}", kind, 1) for i, kind in enumerate(plan.tail)]
    return tuple(segs)


def paged_geometry(cfg: ArchConfig, page_size: int):
    """Per-segment :class:`repro.cache.tiers.SegmentGeometry` tuple wrapped
    in a PageGeometry -- the single source of page shapes for the engine
    and the tiered store."""
    from repro.cache.tiers import PageGeometry, SegmentGeometry
    plan = stack_plan(cfg)
    geoms = []
    for s in paged_segments(cfg):
        if s.page_kind == "state_slab":
            rows, width = SSM.state_slab_rows(cfg, s.kind)
            geoms.append(SegmentGeometry("state_slab", s.n_stack, 1, rows,
                                         width))
        elif s.page_kind == "mla_latent":
            m = cfg.mla
            geoms.append(SegmentGeometry("mla_latent", s.n_stack, 1,
                                         page_size, m.kv_lora_rank,
                                         m.rope_head_dim))
        else:
            geoms.append(SegmentGeometry("attn_kv", s.n_stack,
                                         cfg.n_kv_heads, page_size,
                                         cfg.head_dim, cfg.head_dim))
    return PageGeometry(n_pat=len(plan.pattern), n_scan=plan.n_scan,
                        n_kv_heads=cfg.n_kv_heads, page_size=page_size,
                        head_dim=cfg.head_dim, segments=tuple(geoms))


def _gqa_paged_decode(cfg, p, x, pools_j, bt, lengths, *, has_warm: bool,
                      backend: str = "gather", window: int = 0):
    """One layer's paged GQA decode.

    x: [B, 1, D]; pools_j: one layer's slice of a tiers pool dict
    (kh/vh [P_hot, G, ps, dh], k8/v8 [P_warm, G, ps, dh], ks/vs
    [P_warm, G, ps]); bt: int32[B, max_pages] encoded locations;
    lengths: int32[B].  The write page (lengths // ps) must be hot.
    ``has_warm=False`` (static) promises bt has no warm entries and
    compiles the int8 gather out entirely.  ``backend`` names a registered
    attention backend (kernels/decode_attn/ops.py).
    """
    from repro.kernels.decode_attn import ops as attn_ops
    B = x.shape[0]
    kh, vh = pools_j["kh"], pools_j["vh"]
    ps = kh.shape[2]
    q, k_new, v_new = L.gqa_qkv(cfg, p, x, lengths[:, None])
    # append the new token into its (hot) page
    wp, offs = lengths // ps, lengths % ps
    locs_w = jnp.take_along_axis(bt, wp[:, None], axis=1)[:, 0]
    kh = kh.at[locs_w, :, offs].set(k_new[:, :, 0, :].astype(kh.dtype))
    vh = vh.at[locs_w, :, offs].set(v_new[:, :, 0, :].astype(vh.dtype))
    pools_j = dict(pools_j, kh=kh, vh=vh)
    out = attn_ops.get_attn_backend(backend)(
        q[:, :, 0], pools_j, bt, lengths + 1, window=window,
        has_warm=has_warm)                                # [B, H, dh]
    out = out.reshape(B, 1, -1)
    return jnp.einsum("bsf,fd->bsd", out, Q.getw(p, "wo")), pools_j


def _state_paged_decode(cfg: ArchConfig, kind: str, p, x, pools_j,
                        state_slots, lengths):
    """One recurrence layer's decode against its parked state slab.

    pools_j: one segment's state pools (sh f32[1+hot_state, 1, rows,
    width] after the stack peel); state_slots: int32[B] hot slot per lane
    (0 = trash for idle lanes).  The slab round-trips the dense engine's
    state pytree bit-exactly (f32 superset dtype), so hot-only paged
    decode stays token-identical.
    """
    B = x.shape[0]
    sh = pools_j["sh"]
    W = SSM.state_width(cfg, kind)
    flat = sh[state_slots].reshape(B, -1)[:, :W]
    st = SSM.unflatten_state(cfg, kind, flat)
    x, st_new = block_apply_decode(cfg, kind, p, x, st, lengths)
    flat_new = SSM.flatten_state(cfg, kind, st_new)
    pad = sh.shape[-2] * sh.shape[-1] - W
    flat_new = jnp.pad(flat_new, ((0, 0), (0, pad)))
    sh = sh.at[state_slots].set(
        flat_new.reshape(B, *sh.shape[1:]).astype(sh.dtype))
    return x, dict(pools_j, sh=sh)


#: hot planes each page kind writes per tick (scan ys carry ONLY these)
_HOT_PLANES = ("kh", "vh", "sh")


def block_apply_paged_decode(cfg: ArchConfig, kind: str, p, x, pools_j,
                             bt, lengths, *, state_slots=None,
                             has_warm: bool = True,
                             backend: str = "gather"):
    """One layer's paged decode, dispatched on the layer's page kind:
    attention layers gather token pages (per-head KV or MLA latent);
    mamba2/rwkv6 layers read/write their state slab in place."""
    if kind in PAGED_STATE_KINDS:
        return _state_paged_decode(cfg, kind, p, x, pools_j, state_slots,
                                   lengths)
    assert kind in PAGED_ATTN_KINDS, \
        f"paged decode does not support {kind!r}"
    h = L.norm_apply(cfg, p["norm1"], x)
    if cfg.mla is not None:
        out, pools_j = MLA.mla_paged_decode(cfg, p["attn"], h, pools_j, bt,
                                            lengths, has_warm=has_warm,
                                            backend=backend)
    else:
        out, pools_j = _gqa_paged_decode(
            cfg, p["attn"], h, pools_j, bt, lengths, has_warm=has_warm,
            backend=backend, window=paged_layer_window(cfg, kind))
    x = x + out
    h = L.norm_apply(cfg, p["norm2"], x)
    out, _ = _ffn_apply(cfg, kind, p, h, moe_dropless=True)
    return x + out, pools_j


def stack_paged_decode_step(cfg: ArchConfig, params, pools, tokens, bt,
                            lengths, state_slots=None, *,
                            has_warm: bool = True,
                            backend: str = "gather"):
    """One paged decode step over the full stack (head + scan + tail).

    pools: tuple of tier pool dicts, one per :func:`paged_segments` entry
    (leading axis = segment n_stack); tokens: int32[B, 1]; bt:
    int32[B, max_pages]; lengths: int32[B]; state_slots: int32[B] hot
    state-slab slot per lane (required iff the stack has mamba2/rwkv6
    layers; 0 = trash).  Returns (logits, pools').
    """
    plan = stack_plan(cfg)
    bad = paged_unsupported_layers(cfg)
    if bad:
        raise ValueError(f"{cfg.name}: paged decode unsupported for layers "
                         f"{bad}")
    has_state = any(k in PAGED_STATE_KINDS
                    for k in plan.head + plan.pattern + plan.tail)
    if has_state and state_slots is None:
        raise ValueError(f"{cfg.name}: stack has recurrence layers; paged "
                         f"decode needs state_slots")
    x = jnp.take(params["embed"], tokens, axis=0)
    x = shard(x, "batch", None, None)
    shared_p = params.get("shared")
    new_pools = list(pools)
    idx = 0

    def hot_of(pj):
        return {k: pj[k] for k in _HOT_PLANES if k in pj}

    def run_unstacked(kind, layer_p, x, seg_idx):
        p = layer_p if kind != "shared_attn" else shared_p
        pj = jax.tree.map(lambda a: a[0], pools[seg_idx])
        x, pj = block_apply_paged_decode(cfg, kind, p, x, pj, bt,
                                         lengths, state_slots=state_slots,
                                         has_warm=has_warm,
                                         backend=backend)
        new_pools[seg_idx] = dict(pools[seg_idx],
                                  **{k: v[None]
                                     for k, v in hot_of(pj).items()})
        return x

    for i, kind in enumerate(plan.head):
        x = run_unstacked(kind, params["head_layers"][i], x, idx)
        idx += 1

    if plan.n_scan:
        npat = len(plan.pattern)
        scan_pools = tuple(pools[idx + j] for j in range(npat))

        # only the hot planes are written per tick; returning the warm
        # planes through the scan ys would re-materialize the whole int8
        # tier every step, so the ys carry kh/vh/sh and the rest passes
        # through untouched
        def body(x, inp):
            layer_p, layer_pools = inp
            hot_updates = []
            for j, kind in enumerate(plan.pattern):
                p = layer_p[j] if kind != "shared_attn" else shared_p
                x, pj = block_apply_paged_decode(
                    cfg, kind, p, x, layer_pools[j], bt, lengths,
                    state_slots=state_slots, has_warm=has_warm,
                    backend=backend)
                hot_updates.append(hot_of(pj))
            return x, tuple(hot_updates)

        x, hot = jax.lax.scan(body, x, (params["scan"], scan_pools))
        for j in range(npat):
            new_pools[idx + j] = dict(pools[idx + j], **hot[j])
        idx += npat

    for i, kind in enumerate(plan.tail):
        x = run_unstacked(kind, params["tail_layers"][i], x, idx)
        idx += 1

    return _logits(cfg, params, x), tuple(new_pools)
