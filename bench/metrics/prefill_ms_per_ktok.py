"""prefill_ms_per_ktok: see ``bench.layer_metrics.prefill_ms_per_ktok``."""
from bench.layer_metrics import prefill_ms_per_ktok


def read(ctx):
    return prefill_ms_per_ktok(ctx)
