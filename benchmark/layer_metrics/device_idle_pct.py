"""Share of the traced window in which no operation ran on the device
(mean over the chips)."""
LAYER = "device"
UNIT, SOURCE, MOVES = "%", "device_trace", "rounds_per_s"


def read(ctx):
    trace = ctx["trace"]
    if trace is None or trace["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
