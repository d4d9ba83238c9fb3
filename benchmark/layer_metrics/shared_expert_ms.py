"""Device milliseconds a round under the scope `shared_expert`: the gated feed-forward every token passes beside its routed experts, in every sparse block (the MTP module's too), forward, backward and recompute."""
from benchmark.layer_metrics.mla_attention_ms import scope_ms_per_round

LAYER = "shared expert"
UNIT, SOURCE, MOVES = "ms", "device_trace", "rounds_per_s"


def read(ctx):
    return scope_ms_per_round(ctx, "shared_expert")
