"""Device milliseconds a round under the scope `global_attention`: the full-attention layers of the window/full-attention model (two of the five held, 48 query heads), each around its products, partial rotary embedding under YaRN, the blockwise causal core and the output gate; forward, backward and recompute."""
from benchmark.layer_metrics.swa_window_attention_ms import swa_scope_ms

LAYER = "global attention"
UNIT, SOURCE, MOVES = "ms", "device_trace", "rounds_per_s"


def read(ctx):
    return swa_scope_ms(ctx, "global_attention")
