"""Device milliseconds per round inside collective operations (union of
their intervals, mean over the chips)."""
LAYER = "collectives"
UNIT, SOURCE, MOVES = "ms", "device_trace", "rounds_per_s"


def read(ctx):
    trace, n = ctx["trace"], ctx["traced_rounds"]
    if trace is None or not n or trace["collective_s"] <= 0:
        return None
    return 1e3 * trace["collective_s"] / n
