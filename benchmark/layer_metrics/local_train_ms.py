"""Device milliseconds per round under the scopes `local_train` and
`sample_gather` (self time of the operations, mean over the chips)."""
LAYER = "local training"
UNIT, SOURCE, MOVES = "ms", "device_trace", "rounds_per_s"


def read(ctx):
    trace, n = ctx["trace"], ctx["traced_rounds"]
    if trace is None or not n or "local_train" not in trace["by_scope_s"]:
        return None
    scopes = trace["by_scope_s"]
    return 1e3 * (scopes["local_train"] + scopes.get("sample_gather", 0.0)) / n
