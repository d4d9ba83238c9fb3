"""Device milliseconds a round under the scope `moe_router` of the window/full-attention model: gate product over 256 experts, sigmoid, top-8, the sort by expert, dispatch into 16384 rows and combine, forward, backward and recompute (as `mla_moe_route_ms` reads it: the shared sparse code plants the scope, through this cell's own entry)."""
from benchmark.layer_metrics.mla_moe_route_ms import read  # noqa: F401

LAYER = "router"
UNIT, SOURCE, MOVES = "ms", "device_trace", "rounds_per_s"
