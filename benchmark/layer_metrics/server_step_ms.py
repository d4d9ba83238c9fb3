"""Device milliseconds per round under the scope `aggregate_rlr`. XLA fuses
part of the vote and the average into operations it files under
neighbouring scopes, so this is the step's own operations, not all of its
traffic (PERF.md section 7)."""
LAYER = "server step"
UNIT, SOURCE, MOVES = "ms", "device_trace", "rounds_per_s"


def read(ctx):
    trace, n = ctx["trace"], ctx["traced_rounds"]
    if trace is None or not n or "aggregate_rlr" not in trace["by_scope_s"]:
        return None
    return 1e3 * trace["by_scope_s"]["aggregate_rlr"] / n
