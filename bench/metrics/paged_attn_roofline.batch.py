"""paged_attn_roofline.batch: see ``bench.layer_metrics.paged_attn_roofline``."""
from bench.layer_metrics import paged_attn_roofline


def read(ctx):
    return paged_attn_roofline(ctx)
