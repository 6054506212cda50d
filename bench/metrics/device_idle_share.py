"""device_idle_share: see ``bench.layer_metrics.idle_share``."""
from bench.layer_metrics import idle_share


def read(ctx):
    return idle_share(ctx)
