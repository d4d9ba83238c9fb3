"""Device milliseconds a round under the scope `moe_experts` of the window/full-attention model: the 16 held routed experts' casts and three grouped products against [2048, 512] matrices, forward, backward and recompute (as `mla_moe_experts_ms` reads it, through this cell's own entry)."""
from benchmark.layer_metrics.mla_moe_experts_ms import read  # noqa: F401

LAYER = "sparse experts"
UNIT, SOURCE, MOVES = "ms", "device_trace", "rounds_per_s"
