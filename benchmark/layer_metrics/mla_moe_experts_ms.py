"""Device milliseconds a round under the scope `moe_experts` of the latent-attention model: the held routed experts' casts and three grouped products, forward, backward and recompute (the scope `moe_experts_ms` reads, through this cell's own entry)."""
from benchmark.layer_metrics.mla_attention_ms import scope_ms_per_round

LAYER = "sparse experts"
UNIT, SOURCE, MOVES = "ms", "device_trace", "rounds_per_s"


def read(ctx):
    return scope_ms_per_round(ctx, "moe_experts")
