"""Dense grouped-query decoders (Qwen2, StarCoder2): the family over the
benchmark's dense code, ``bench/weights.py``, ``bench/reference.py`` and
``bench/flops.py``."""
from __future__ import annotations

import dataclasses
import functools
import types

from bench import flops as F
from bench import reference, weights


def arch(conf: dict):
    """The program's ArchConfig for a configuration file: the registered
    architecture with every size the file states."""
    from repro.configs import get_arch
    c = conf["config"]
    act = {"silu": "silu", "gelu_pytorch_tanh": "gelu"}[c["hidden_act"]]
    norm = "layernorm" if c.get("norm_type") == "layer_norm" else "rmsnorm"
    return dataclasses.replace(
        get_arch(conf["arch"]), n_layers=c["num_hidden_layers"],
        d_model=c["hidden_size"], n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"], d_ff=c["intermediate_size"],
        vocab_size=c["vocab_size"], rope_theta=float(c["rope_theta"]),
        tie_embeddings=bool(c["tie_word_embeddings"]), act=act, norm=norm,
        qkv_bias=True)


def make_params(config: dict, seed: int):
    """Every weight, drawn on the device in one jitted call from the seed
    (``bench.weights.make_params``)."""
    return weights.make_params(config, seed)


def gaps(config: dict, params, tokens, cand, quantize: str | None = None):
    """The float32 dense GQA reference's gaps and argmax
    (``bench.reference.gaps``)."""
    return reference.gaps(config, params, tokens, cand, quantize)


def counts(config: dict):
    """FLOPs and bytes from the shapes (``bench.flops``); a decode step's
    attention cost is one layer's paged call times the layers."""
    s = F.shapes(config)

    def attn_step_cost(ctxs, page_size, warm_share=0.0):
        fl, nbytes = F.paged_attn_cost(s, ctxs, page_size, warm_share)
        return s.layers * fl, s.layers * nbytes
    return types.SimpleNamespace(
        prefill_flops=functools.partial(F.prefill_flops, s),
        decode_token_flops=functools.partial(F.decode_token_flops, s),
        attn_step_cost=attn_step_cost)
