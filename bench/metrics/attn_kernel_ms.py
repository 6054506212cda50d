"""attn_kernel_ms: device time of the paged decode-attention kernel per
traced decode step, in ms: operations whose own name (the HLO
instruction's, before `` = ``) holds the kernel's name
(``paged_decode_attn``, which ``paged_decode_attn_tiered`` holds too),
over the traced ``step_fn`` executions. Layout copies of the kernel's
operands and result carry that name only in their metadata and are not
counted. A program whose kernel has no such name reads nothing."""
from bench.layer_metrics import DECODE

KERNEL = "paged_decode_attn"


def read(ctx):
    tr = ctx.get("trace")
    if tr is None:
        return None
    ns = sum(t for name, (_, t) in tr.ops.items()
             if KERNEL in name.split(" = ")[0])
    n_dec = tr.module_time_s(DECODE)[0]
    return ns * 1e-6 / n_dec if ns and n_dec else None
