"""Arithmetic shared by the per-layer readers in ``bench/metrics``.

Program names in the trace: the decode step is the jitted ``step_fn`` and
a prefill the jitted ``prefill_fn`` of the paged engine. The paged
decode-attention kernel (``_tiered_kernel``: hot bf16 and warm int8 pages
through one table) carries no name of its own in the trace: it is the one
Mosaic custom call the engine runs, found by its call target.
"""
from __future__ import annotations

import numpy as np

from bench import flops as F

DECODE = "step_fn"
PREFILL = "prefill_fn"
ATTN_KERNEL = 'custom_call_target="tpu_custom_call"'


def idle_share(ctx):
    tr = ctx.get("trace")
    if tr is None or tr.window_s <= 0:
        return None
    return (1.0 - tr.busy_s / tr.window_s) * 100.0


def decode_step_ms(ctx):
    tr = ctx.get("trace")
    n, s = tr.module_time_s(DECODE) if tr else (0, 0.0)
    return s / n * 1e3 if n else None


def prefill_share(ctx):
    tr = ctx.get("trace")
    if tr is None or tr.busy_s <= 0:
        return None
    return tr.module_time_s(PREFILL)[1] / tr.busy_s * 100.0


def prefill_ms_per_ktok(ctx):
    tr = ctx.get("trace")
    toks = sum(ctx.get("prefill_tokens", []))
    n, s = tr.module_time_s(PREFILL) if tr else (0, 0.0)
    if not n or not toks:
        return None
    return s * 1e3 / (toks / 1000.0)


def paged_attn_roofline(ctx):
    """Least time over kernel time, summed over the traced decode steps:
    the least time of a step is every layer's paged-attention call at the
    lanes' contexts, bounded by the larger of FLOPs and page bytes."""
    tr = ctx.get("trace")
    steps = ctx.get("steps") or []
    n_k, t_k = tr.op_time_s(ATTN_KERNEL) if tr else (0, 0.0)
    if not n_k or not steps:
        return None
    s, ps, peaks = ctx["shapes"], ctx["page_size"], ctx["peaks"]
    n_dec = tr.module_time_s(DECODE)[0]
    least = []
    for _, ctxs, warm_of_lanes, _ in steps:
        if ctxs:
            fl, by = F.paged_attn_cost(s, ctxs, ps, warm_of_lanes)
            least.append(s.layers * F.least_time(fl, by, peaks))
    if not least or not n_dec:
        return None
    # the traced decode programs, each charged the mean step's least time
    return float(np.mean(least)) * n_dec / t_k * 100.0


def step_mfu(ctx):
    """Model FLOPs of every token processed in the traced stretch (true
    prompt tokens and decoded tokens) over busy time at the bf16 peak."""
    tr = ctx.get("trace")
    if tr is None or tr.busy_s <= 0:
        return None
    s = ctx["shapes"]
    fl = sum(F.prefill_flops(s, n) for n in ctx.get("prefill_tokens", []))
    for _, ctxs, _, _ in ctx.get("steps") or []:
        fl += sum(F.decode_token_flops(s, c) for c in ctxs)
    if fl <= 0:
        return None
    return fl / (tr.busy_s * ctx["peaks"]["bf16_flops"]) * 100.0
