"""admit_wait_p95_ms: how long requests waited between the engine's
``submit`` and their admission (prefill dispatched, or a full prefix
match taken), 95th percentile over the window's requests, in ms. Read
from the ``t_submit`` / ``t_admit`` stamps that ``PagedEngine`` puts on
each ``Request`` (host clock); an engine without them reads nothing."""
import numpy as np


def read(ctx):
    waits = [(r.req.t_admit - r.req.t_submit) * 1e3
             for r in ctx.get("recs") or []
             if getattr(r.req, "t_admit", None) is not None
             and getattr(r.req, "t_submit", None) is not None]
    return float(np.percentile(waits, 95)) if waits else None
