"""Device milliseconds a round under the scope `gated_attention`: the full-attention layer of the linear/full-attention model (one of the four held), around its q-and-gate, k, v and output products, the zero-centred q / k norms, partial rotary (`attn_rope`), the causal core at heads of 256 (`attn_core`) and the element-wise sigmoid gate; forward, backward and recompute, the eval programs left out."""
from benchmark.layer_metrics.gdn_layers_ms import GDN_SCOPES
from benchmark.layer_metrics.mla_attention_ms import scope_ms_per_round

LAYER = "gated attention"
UNIT, SOURCE, MOVES = "ms", "device_trace", "rounds_per_s"


def read(ctx):
    return scope_ms_per_round(ctx, "gated_attention", GDN_SCOPES)
