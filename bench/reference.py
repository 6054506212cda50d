"""Plain float32 reference of the served decoders (dense GQA).

A straightforward full forward pass over one whole sequence: no kernels,
no cache, no batching, no padding masks beyond causality. It imports
nothing of the program. It follows the published architecture through
the configuration's own keys:

- RMSNorm (``rms_norm_eps``) or LayerNorm (``norm_type: layer_norm``,
  ``norm_epsilon``), each with its learned scale (and bias);
- grouped-query attention with q/k/v biases, rotary embeddings in the
  half-split convention (``rope_theta``), causal softmax;
- a SwiGLU MLP (``hidden_act: silu``) or a GELU MLP (``gelu_pytorch_tanh``),
  with ``use_bias`` biases when the configuration has them;
- a separate output head, or the embedding (``tie_word_embeddings``);
- a sliding window (``sliding_window``) unless ``use_sliding_window`` is
  false.

Matmuls run at ``highest`` precision, so the float32 math is float32 on a
TPU too. ``quantize`` gives a control, the step below the bf16 weights
the configuration states: every weight matrix (and the embedding) rounded
to ``int8`` or to ``fp8`` (e4m3), with one scale per output channel.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

F32 = jnp.float32


def _eps(config: dict) -> float:
    if config.get("norm_type") == "layer_norm":
        return float(config["norm_epsilon"])
    return float(config["rms_norm_eps"])


def _norm(config, p, x):
    eps = _eps(config)
    if config.get("norm_type") == "layer_norm":
        mu = x.mean(-1, keepdims=True)
        var = ((x - mu) ** 2).mean(-1, keepdims=True)
        return (x - mu) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * p["scale"]


def _rope(x, theta: float):
    """x: [S, n, d] at positions 0..S-1."""
    S, _, d = x.shape
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=F32) / d)
    ang = jnp.arange(S, dtype=F32)[:, None] * inv          # [S, d/2]
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def lower(w, fmt):
    """``w`` rounded to ``fmt`` ("int8" or "fp8") with one scale per
    output channel (the last axis), back in float32; ``None`` keeps it."""
    w = w.astype(F32)
    if fmt is None:
        return w
    absmax = jnp.max(jnp.abs(w), axis=-2, keepdims=True)
    if fmt == "int8":
        scale = jnp.where(absmax == 0, 1.0, absmax / 127.0)
        return jnp.clip(jnp.round(w / scale), -127, 127) * scale
    if fmt == "fp8":
        scale = jnp.where(absmax == 0, 1.0, absmax / 448.0)
        return (w / scale).astype(jnp.float8_e4m3fn).astype(F32) * scale
    raise ValueError(f"unknown control format {fmt!r}")


def _layer(config, quantize, x, p):
    S, D = x.shape
    H, G = config["num_attention_heads"], config["num_key_value_heads"]
    dh = config.get("head_dim") or D // H

    def w(a):
        return lower(a, quantize)
    a, f = p["attn"], p["ffn"]
    h = _norm(config, p["norm1"], x)
    q = (h @ w(a["wq"]) + a["bq"]).reshape(S, H, dh)
    k = (h @ w(a["wk"]) + a["bk"]).reshape(S, G, dh)
    v = (h @ w(a["wv"]) + a["bv"]).reshape(S, G, dh)
    q, k = _rope(q, config["rope_theta"]), _rope(k, config["rope_theta"])
    k = jnp.repeat(k, H // G, axis=1)
    v = jnp.repeat(v, H // G, axis=1)
    s = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(F32(dh))
    qi, ki = jnp.arange(S)[:, None], jnp.arange(S)[None, :]
    seen = ki <= qi
    window = config.get("sliding_window")
    if window and config.get("use_sliding_window", True):
        seen &= ki > qi - window
    s = jnp.where(seen[None], s, -jnp.inf)
    o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v)
    o = o.reshape(S, H * dh) @ w(a["wo"])
    if config.get("use_bias"):
        o = o + a["bo"]
    x = x + o
    h = _norm(config, p["norm2"], x)
    up = h @ w(f["wi"])
    if config.get("use_bias"):
        up = up + f["bi"]
    if config["hidden_act"] == "silu":
        up = jax.nn.silu(up) * (h @ w(f["wg"]))
    else:
        up = jax.nn.gelu(up, approximate=True)
    down = up @ w(f["wo"])
    if config.get("use_bias"):
        down = down + f["bo"]
    return x + down, None


def logits(config: dict, params, tokens, quantize: str | None = None):
    """f32[S, V] next-token logits at every position of ``tokens``."""
    with jax.default_matmul_precision("highest"):
        emb = params["embed"]
        # rows are scaled one by one, so the gathered rows alone suffice
        x = lower(emb[tokens].T, quantize).T
        layer = functools.partial(_layer, config, quantize)
        x, _ = jax.lax.scan(layer, x, params["scan"][0])
        x = _norm(config, params["final_norm"], x)
        if config["tie_word_embeddings"]:
            head = lower(emb.T, quantize)
        else:
            head = lower(params["unembed"], quantize)
        return x @ head


@functools.partial(jax.jit, static_argnums=(0, 4))
def _gaps(config, params, tokens, cand, quantize):
    lg = logits(dict(config), params, tokens, quantize)
    at = jnp.take_along_axis(lg, cand, axis=-1)
    return lg.max(-1)[:, None] - at, jnp.argmax(lg, -1).astype(jnp.int32)


class _Frozen(dict):
    def __hash__(self):
        return hash(repr(sorted(self.items())))


def gaps(config: dict, params, tokens, cand, quantize: str | None = None):
    """For ``tokens`` [S] and candidates ``cand`` [S, C]: how far each
    candidate's logit lies below the best logit at its position [S, C],
    and the argmax [S], from the (control when ``quantize``) forward.
    Callers pad to one length, so one program serves every sequence."""
    return _gaps(_Frozen(config), params, tokens, cand, quantize)
