"""Device milliseconds a round under the scope `moe_router`: gate product, sigmoid, top-k, the sort by expert, dispatch and combine, forward, backward and recompute."""
from benchmark.trace import inner_scopes

LAYER = "router"
UNIT, SOURCE, MOVES = "ms", "device_trace", "rounds_per_s"


def read(ctx):
    return inner_scopes.model_scope_ms_per_round(ctx, "moe_router")
