"""gen_lag_p99_ms: how late the load generator offered requests, 99th
percentile over every request of the window, in ms after its scheduled
arrival (host clock). A late generator would pass for a fast server."""
import numpy as np


def read(ctx):
    lags = ctx.get("gen_lag_ms") or []
    return float(np.percentile(lags, 99)) if lags else None
