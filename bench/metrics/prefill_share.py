"""prefill_share: see ``bench.layer_metrics.prefill_share``."""
from bench.layer_metrics import prefill_share


def read(ctx):
    return prefill_share(ctx)
