"""Device milliseconds a round under the scope `moe_experts`: the held experts' three grouped products, forward, backward and recompute (self time, innermost scope: benchmark/trace/inner_scopes.py)."""
from benchmark.trace import inner_scopes

LAYER = "sparse experts"
UNIT, SOURCE, MOVES = "ms", "device_trace", "rounds_per_s"


def read(ctx):
    return inner_scopes.model_scope_ms_per_round(ctx, "moe_experts")
