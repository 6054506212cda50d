"""Operations and bytes of the served model, computed from its shapes.

The counts follow the algorithm, not an implementation: a later change
to the program cannot change what a token or a kernel call is charged.
All counts take a configuration's published keys (``config`` of a file
under ``bench/configs``).
"""
from __future__ import annotations

import dataclasses
import math

BF16 = 2
INT8 = 1
F32 = 4


@dataclasses.dataclass(frozen=True)
class Shapes:
    layers: int
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    gated: bool          # SwiGLU-style MLP (three matrices) or two

    @property
    def layer_matmul_params(self) -> int:
        D, H, G, dh, F = (self.d_model, self.heads, self.kv_heads,
                          self.head_dim, self.d_ff)
        attn = D * H * dh + 2 * D * G * dh + H * dh * D
        mlp = (3 if self.gated else 2) * D * F
        return attn + mlp


def shapes(config: dict) -> Shapes:
    D, H = config["hidden_size"], config["num_attention_heads"]
    return Shapes(layers=config["num_hidden_layers"], d_model=D, heads=H,
                  kv_heads=config["num_key_value_heads"],
                  head_dim=config.get("head_dim") or D // H,
                  d_ff=config["intermediate_size"],
                  vocab=config["vocab_size"],
                  gated=config["hidden_act"] == "silu")


def matmul_flops_per_token(s: Shapes) -> float:
    """Weight matmuls of one token through every layer and the output
    head (2 FLOPs per multiply-add; the embedding lookup is free)."""
    return 2.0 * (s.layers * s.layer_matmul_params + s.d_model * s.vocab)


def attn_flops(s: Shapes, ctx: int) -> float:
    """Scores and weighted values of one query over ``ctx`` keys, every
    layer: QK^T and PV, each 2 * heads * head_dim per key."""
    return 4.0 * s.layers * s.heads * s.head_dim * ctx


def decode_token_flops(s: Shapes, ctx: int) -> float:
    """One decoded token whose query sees ``ctx`` keys (itself included)."""
    return matmul_flops_per_token(s) + attn_flops(s, ctx)


def prefill_flops(s: Shapes, n: int) -> float:
    """A causal prefill of ``n`` true (unpadded) prompt tokens."""
    return n * matmul_flops_per_token(s) + attn_flops(s, n * (n + 1) // 2)


def kv_page_bytes(s: Shapes, page_size: int, warm: bool) -> int:
    """Bytes of one layer's K and V page: bf16 when hot; int8 with one
    float32 scale per token and head when warm."""
    per_row = s.head_dim * INT8 + F32 if warm else s.head_dim * BF16
    return 2 * s.kv_heads * page_size * per_row


def paged_attn_cost(s: Shapes, ctxs, page_size: int,
                    warm_share: float = 0.0) -> tuple[float, float]:
    """(FLOPs, bytes) of one layer's paged decode-attention call.

    ``ctxs`` holds each decoding lane's key count (its cached tokens plus
    the new one).  Bytes are the pages that hold those keys, at their
    stored precision (``warm_share`` of them int8 + scales, the rest
    bf16), plus each lane's bf16 query and output rows."""
    flops = sum(4.0 * s.heads * s.head_dim * c for c in ctxs)
    hot = kv_page_bytes(s, page_size, warm=False)
    warm = kv_page_bytes(s, page_size, warm=True)
    per_page = (1.0 - warm_share) * hot + warm_share * warm
    pages = sum(math.ceil(c / page_size) for c in ctxs)
    qo = 2 * s.heads * s.head_dim * BF16 * len(ctxs)
    return flops, pages * per_page + qo


def least_time(flops: float, nbytes: float, peaks: dict) -> float:
    """The roofline's floor: the larger of compute and memory time."""
    return max(flops / peaks["bf16_flops"], nbytes / peaks["hbm_bw"])
