"""build_model: the public model API consumed by train/serve/dryrun.

``build_model(cfg)`` returns pure functions over explicit params/state
pytrees -- no framework object state -- so every entry point jits/lowers
cleanly with ShapeDtypeStructs (the multi-pod dry-run path).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable

import numpy as np
import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig, ShapeConfig
from repro.models import transformer as T


@dataclasses.dataclass(frozen=True)
class ModelFns:
    cfg: ArchConfig
    init: Callable            # rng -> params
    fwd_train: Callable       # (params, batch) -> (logits, aux)
    loss: Callable            # (params, batch) -> (loss, metrics)
    prefill: Callable         # (params, batch, max_len) -> (logits, state)
    decode_step: Callable     # (params, state, tokens) -> (logits, state)
    init_state: Callable      # (batch, max_len) -> state
    # (params, pools, tokens, block_table, lengths, state_slots)
    #   -> (logits, pools)
    paged_decode_step: Callable = None


def build_model(cfg: ArchConfig, *, remat: bool = True) -> ModelFns:
    def init(rng):
        return T.stack_init(rng, cfg)

    def fwd_train(params, batch):
        logits, aux, _ = T.stack_apply_seq(cfg, params, batch,
                                           want_state=False, remat=remat)
        return logits, aux

    def loss(params, batch):
        logits, aux = fwd_train(params, batch)
        if cfg.frontend == "audio":
            # encoder masked-prediction stub: per-position CE
            labels = batch["labels"]
            lp = jax.nn.log_softmax(logits, axis=-1)
            nll = -jnp.take_along_axis(lp, labels[..., None], axis=-1)[..., 0]
            ce = jnp.mean(nll)
        else:
            labels = batch.get("labels", batch["tokens"])
            n_prefix = logits.shape[1] - labels.shape[1]   # vlm patch prefix
            lg = logits[:, n_prefix:]
            lp = jax.nn.log_softmax(lg[:, :-1], axis=-1)
            tgt = labels[:, 1:]
            nll = -jnp.take_along_axis(lp, tgt[..., None], axis=-1)[..., 0]
            ce = jnp.mean(nll)
        aux_w = 0.003 if cfg.moe is not None else 0.0
        total = ce + aux_w * aux
        return total, {"ce": ce, "aux": aux}

    def prefill(params, batch, max_len: int, *, moe_dropless: bool = False,
                kv_mode: str = "bf16", paged_layout: bool = False):
        # ``batch`` may carry "true_len" (int32[B]): tokens beyond it are
        # right-padding from prompt-length bucketing (see prompt_bucket);
        # logits/state at real positions match the unpadded run and the
        # recurrence state ends exactly at true_len
        logits, _, state = T.stack_apply_seq(cfg, params, batch,
                                             want_state=True, remat=False,
                                             max_len=max_len,
                                             moe_dropless=moe_dropless,
                                             kv_mode=kv_mode,
                                             paged_layout=paged_layout)
        return logits, state

    def decode_step(params, state, tokens):
        return T.stack_decode_step(cfg, params, state, tokens)

    def paged_decode_step(params, pools, tokens, block_table, lengths,
                          state_slots=None, *, has_warm: bool = True,
                          backend: str = "gather"):
        return T.stack_paged_decode_step(cfg, params, pools, tokens,
                                         block_table, lengths, state_slots,
                                         has_warm=has_warm, backend=backend)

    def init_state(batch: int, max_len: int, kv_dtype=jnp.bfloat16,
                   kv_mode: str = "bf16", uniform_pos: bool = False):
        return T.stack_init_state(cfg, batch, max_len, kv_dtype, kv_mode,
                                  uniform_pos)

    return ModelFns(cfg, init, fwd_train, loss, prefill, decode_step,
                    init_state, paged_decode_step)


# ---------------------------------------------------------------------------
# prompt-length bucketing (retrace control for serving prefill)
# ---------------------------------------------------------------------------

def prompt_bucket(plen: int, max_len: int, quantum: int = 16) -> int:
    """Padded prefill length for a ``plen``-token prompt.

    Buckets are ``quantum * 2**k`` capped at ``max_len``, so every possible
    prompt length maps onto at most ``log2(max_len / quantum) + 1`` distinct
    jit shapes -- the engines pad prompts up to the bucket (and mask via
    batch["true_len"]) instead of retracing prefill per prompt length.
    """
    if plen > max_len:
        raise ValueError(f"prompt length {plen} exceeds max_len {max_len}")
    b = quantum
    while b < plen:
        b *= 2
    return min(b, max_len)


def n_prompt_buckets(max_len: int, quantum: int = 16) -> int:
    """How many distinct bucket shapes ``prompt_bucket`` can emit."""
    return len({prompt_bucket(p, max_len, quantum)
                for p in range(1, max_len + 1)})


# ---------------------------------------------------------------------------
# input specs (ShapeDtypeStruct stand-ins; no allocation)
# ---------------------------------------------------------------------------

def input_specs(cfg: ArchConfig, shape: ShapeConfig) -> dict:
    """Training/prefill batch spec for one (arch x shape) cell.

    [audio]/[vlm] archs get precomputed frame/patch embeddings per the
    assignment (the modality frontend is a stub).
    """
    B, S = shape.global_batch, shape.seq_len
    f32, i32 = jnp.float32, jnp.int32
    if cfg.frontend == "audio":
        return {"frames": jax.ShapeDtypeStruct((B, S, cfg.d_model),
                                               jnp.bfloat16),
                "labels": jax.ShapeDtypeStruct((B, S), i32)}
    if cfg.frontend == "vision":
        P = cfg.n_patches
        return {"tokens": jax.ShapeDtypeStruct((B, S - P), i32),
                "patches": jax.ShapeDtypeStruct((B, P, cfg.d_model),
                                                jnp.bfloat16),
                "labels": jax.ShapeDtypeStruct((B, S - P), i32)}
    return {"tokens": jax.ShapeDtypeStruct((B, S), i32),
            "labels": jax.ShapeDtypeStruct((B, S), i32)}


def decode_token_specs(cfg: ArchConfig, shape: ShapeConfig):
    B = shape.global_batch
    return jax.ShapeDtypeStruct((B, 1), jnp.int32)


def make_batch(cfg: ArchConfig, shape_or_specs, rng: np.random.Generator):
    """Concrete random batch matching input_specs (smoke tests/examples)."""
    if isinstance(shape_or_specs, ShapeConfig):
        specs = input_specs(cfg, shape_or_specs)
    else:
        specs = shape_or_specs
    out = {}
    for k, s in specs.items():
        if np.issubdtype(s.dtype, np.integer):
            hi = cfg.vocab_size if k in ("tokens", "labels") else 2
            out[k] = jnp.asarray(rng.integers(0, hi, s.shape), s.dtype)
        else:
            out[k] = jnp.asarray(rng.standard_normal(s.shape) * 0.02, s.dtype)
    return out
