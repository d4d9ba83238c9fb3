"""Device milliseconds a round under the scope `gdn`: the Gated DeltaNet layers of the linear/full-attention model (three of the four held), each around its q / k / v / z and b / a products, the causal convolution, the l2 norms, the chunked delta rule (`gdn_core`, inside it), the gated norm and the output product; forward, backward and recompute (self time, innermost scope: benchmark/trace/inner_scopes.py), the eval programs left out."""
from benchmark.layer_metrics.mla_attention_ms import scope_ms_per_round

LAYER = "linear attention"
UNIT, SOURCE, MOVES = "ms", "device_trace", "rounds_per_s"
# the scopes models/gdn_moe.py and the shared sparse code plant inside
# `local_train`, none inside another but `moe_experts` under `moe_router`
# and the cores (`gdn_core`, `attn_core`, `attn_rope`) under their layers'
GDN_SCOPES = ("gdn", "gated_attention", "moe_router", "moe_experts",
              "shared_expert", "lm_head")


def read(ctx):
    return scope_ms_per_round(ctx, "gdn", GDN_SCOPES)
