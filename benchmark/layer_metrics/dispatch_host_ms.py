"""Mean host milliseconds per unit that the calling thread itself computes
inside `RoundEngine.dispatch` and `post_unit` (thread CPU time), over the
units of the timed window. Not the wall clock: the runtime blocks the host
inside a dispatch until the device has caught up (4479 ms of a 4485 ms unit
of ten chained CNN rounds), which is the device's time, not the engine's. And a
mean, not a median: the thread clock of the chip's machine ticks in 10 ms."""
LAYER = "engine"
UNIT, SOURCE, MOVES = "ms", "program_span", "rounds_per_s"


def read(ctx):
    spans = ctx["spans"]
    d = spans.durations("dispatch", "window", cpu=True)
    p = spans.durations("post_unit", "window", cpu=True)
    if not d or len(d) != len(p):
        return None
    return 1e3 * (sum(d) + sum(p)) / len(d)
