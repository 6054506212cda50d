"""Random weights drawn from ``--seed``, on the device, in the type served.

The benchmark makes the weights itself, so that the plain reference reads
nothing the program made. The tree follows the layout the serving engine
takes (``embed``, ``unembed``, ``final_norm`` and one scanned block with a
leading layer axis); ``check_layout`` compares it with the layout the
engine's own initialiser would build.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench.flops import shapes


def seed_key(seed: int):
    """A PRNG key for any whole-number seed, also one over 32 bits."""
    seed = int(seed)
    key = jax.random.PRNGKey(0)
    for word in (seed & 0xFFFFFFFF, (seed >> 32) & 0xFFFFFFFF, seed >> 64):
        key = jax.random.fold_in(key, np.uint32(word & 0xFFFFFFFF))
    return key


def _norm(key, config, lead=()):
    D = config["hidden_size"]
    k1, k2 = jax.random.split(key)
    p = {"scale": 1.0 + 0.1 * jax.random.normal(k1, lead + (D,),
                                                jnp.float32)}
    if config.get("norm_type") == "layer_norm":
        p["bias"] = 0.05 * jax.random.normal(k2, lead + (D,), jnp.float32)
    return p


def _matrix(key, shape):
    """bf16 weights with unit variance per output under a unit input."""
    fan_in = shape[-2]
    return (jax.random.normal(key, shape, jnp.bfloat16)
            * jnp.bfloat16(1.0 / np.sqrt(fan_in)))


def _draw(key, config):
    s = shapes(config)
    L, D, H, G, dh, F, V = (s.layers, s.d_model, s.heads, s.kv_heads,
                            s.head_dim, s.d_ff, s.vocab)
    ks = iter(jax.random.split(key, 16))
    attn = {"wq": _matrix(next(ks), (L, D, H * dh)),
            "wk": _matrix(next(ks), (L, D, G * dh)),
            "wv": _matrix(next(ks), (L, D, G * dh)),
            "wo": _matrix(next(ks), (L, H * dh, D)),
            "bq": 0.1 * jax.random.normal(next(ks), (L, H * dh)),
            "bk": 0.1 * jax.random.normal(next(ks), (L, G * dh)),
            "bv": 0.1 * jax.random.normal(next(ks), (L, G * dh))}
    ffn = {"wi": _matrix(next(ks), (L, D, F)),
           "wo": _matrix(next(ks), (L, F, D))}
    if s.gated:
        ffn["wg"] = _matrix(next(ks), (L, D, F))
    block = {"norm1": _norm(next(ks), config, (L,)), "attn": attn,
             "norm2": _norm(next(ks), config, (L,)), "ffn": ffn}
    params = {"final_norm": _norm(next(ks), config),
              "embed": jax.random.normal(next(ks), (V, D), jnp.bfloat16),
              "scan": (block,)}
    if not config["tie_word_embeddings"]:
        params["unembed"] = _matrix(next(ks), (D, V))
    return params


def make_params(config: dict, seed: int):
    """Every weight, drawn on the default device in one jitted call."""
    return jax.jit(_draw, static_argnums=1)(seed_key(seed),
                                            _Frozen(config))


class _Frozen(dict):
    """A hashable view of a configuration (a static jit argument)."""

    def __hash__(self):
        return hash(repr(sorted(self.items())))


def check_layout(params, expected) -> None:
    """Raise unless ``params`` has the tree, shapes and dtypes of
    ``expected`` (e.g. ``jax.eval_shape`` of the engine's initialiser)."""
    got = jax.tree_util.tree_flatten_with_path(params)[0]
    want = jax.tree_util.tree_flatten_with_path(expected)[0]
    g = {jax.tree_util.keystr(p): (a.shape, a.dtype) for p, a in got}
    w = {jax.tree_util.keystr(p): (a.shape, a.dtype) for p, a in want}
    if g != w:
        diff = sorted(set(g.items()) ^ set(w.items()))
        raise ValueError(f"weight layout differs from the engine's: {diff}")
