"""Device milliseconds a round under the scope `attn_core` inside `window_attention`: the attention core of the sliding-window layers alone (on the chip the splash kernel under a band of `sliding_window` keys, dq and dkv in two kernels), forward, recompute and backward, in the round program (benchmark/trace/scope_pairs.py). What is left of `attn_core_ms` is the full layers' core."""
from benchmark.layer_metrics.attn_core_ms import CORE, scope_ms_by_layer

LAYER = "attention core"
UNIT, SOURCE, MOVES = "ms", "device_trace", "rounds_per_s"


def read(ctx):
    return (scope_ms_by_layer(ctx, CORE) or {}).get("window_attention")
