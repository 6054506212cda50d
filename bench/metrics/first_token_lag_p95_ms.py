"""first_token_lag_p95_ms: from a request's admission to its first token
reaching ``Request.out``, 95th percentile over the window's requests, in
ms: the prefill, and the step whose harvest lands the first token. Read
from the ``t_admit`` / ``t_first`` stamps that ``PagedEngine`` puts on
each ``Request`` (host clock); an engine without them reads nothing."""
import numpy as np


def read(ctx):
    lags = [(r.req.t_first - r.req.t_admit) * 1e3
            for r in ctx.get("recs") or []
            if getattr(r.req, "t_first", None) is not None
            and getattr(r.req, "t_admit", None) is not None]
    return float(np.percentile(lags, 95)) if lags else None
