"""Device milliseconds a round under the scope `mtp`, whatever lies inside it: the multi-token-prediction module's embedding of the next token, its two norms and projection, its block (latent attention, router, shared expert) and its pass through the head, forward, backward and recompute. Its block's grouped products carry no path (inner_scopes.OP_SCOPES) and are not in it: they read under `mla_moe_experts_ms`."""
from benchmark.layer_metrics.mla_attention_ms import scope_ms_per_round

LAYER = "multi-token prediction"
UNIT, SOURCE, MOVES = "ms", "device_trace", "rounds_per_s"


def read(ctx):
    return scope_ms_per_round(ctx, "mtp", ("mtp",))
