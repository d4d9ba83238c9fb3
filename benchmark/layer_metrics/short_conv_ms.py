"""Device milliseconds a round under the scope `short_conv`: the gated short convolution's two projections, its gates and its three taps, forward, backward and recompute."""
from benchmark.trace import inner_scopes

LAYER = "short convolution"
UNIT, SOURCE, MOVES = "ms", "device_trace", "rounds_per_s"


def read(ctx):
    return inner_scopes.model_scope_ms_per_round(ctx, "short_conv")
