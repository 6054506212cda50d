"""decode_step_ms.batch: see ``bench.layer_metrics.decode_step_ms``."""
from bench.layer_metrics import decode_step_ms


def read(ctx):
    return decode_step_ms(ctx)
