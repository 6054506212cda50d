"""warm_resident_share: time-average share of the resident KV pages that
sit in the int8 warm tier, sampled from the tier counts after every step
of the window (%)."""


def read(ctx):
    shares = []
    for t in ctx.get("tier_samples") or []:
        resident = t["hot"] + t["warm"] + t["cold"]
        if resident:
            shares.append(t["warm"] / resident)
    return 100.0 * sum(shares) / len(shares) if shares else None
