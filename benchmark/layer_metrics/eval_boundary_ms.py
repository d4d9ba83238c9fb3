"""Device milliseconds of the two eval programs (`jit(eval_fn)`) per eval
boundary of the traced part. The boundary's finite check is left out: it is
microseconds, and XLA's cache serves it the benchmark's own probe program, so
the trace files it under that name."""
LAYER = "eval"
UNIT, SOURCE, MOVES = "ms", "device_trace", "rounds_per_s"
PROGRAM = "jit(eval_fn)"


def read(ctx):
    trace, n = ctx["trace"], ctx["traced_boundaries"]
    if trace is None or not n or PROGRAM not in trace["by_program_s"]:
        return None
    return 1e3 * trace["by_program_s"][PROGRAM] / n
