"""Device milliseconds a round under the scope `gdn_core`: the chunked gated delta rule of every Gated DeltaNet layer (`gdn_moe.chunked_delta_rule`: the decays, K K^T, the float32 unit triangular solve, the intra-chunk products and the scan over 64 chunks that carries the float32 state), forward, its recompute and backward, the eval programs left out; a part of `gdn_layers_ms`."""
from benchmark.layer_metrics.gdn_layers_ms import GDN_SCOPES
from benchmark.layer_metrics.mla_attention_ms import scope_ms_per_round

LAYER = "linear attention core"
UNIT, SOURCE, MOVES = "ms", "device_trace", "rounds_per_s"


def read(ctx):
    return scope_ms_per_round(ctx, "gdn_core", GDN_SCOPES + ("gdn_core",))
