"""cold_moves_per_s: pages packed to the host cold tier plus pages
unpacked back to warm, per second of the window (store counters)."""


def read(ctx):
    moves = ctx.get("cold_moves")
    if moves is None:
        return None
    return moves / ctx["window_s"]
