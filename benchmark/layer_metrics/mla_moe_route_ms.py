"""Device milliseconds a round under the scope `moe_router` of the latent-attention model: gate product over 256 experts, sigmoid, top-8, the sort by expert, dispatch and combine, forward, backward and recompute (the scope `moe_route_ms` reads, through this cell's own entry)."""
from benchmark.layer_metrics.mla_attention_ms import scope_ms_per_round

LAYER = "router"
UNIT, SOURCE, MOVES = "ms", "device_trace", "rounds_per_s"


def read(ctx):
    return scope_ms_per_round(ctx, "moe_router")
