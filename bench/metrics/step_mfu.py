"""step_mfu: see ``bench.layer_metrics.step_mfu``."""
from bench.layer_metrics import step_mfu


def read(ctx):
    return step_mfu(ctx)
