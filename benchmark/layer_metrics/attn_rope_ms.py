"""Device milliseconds a round under the scope `attn_rope`: each attention layer's rotary step, in float32 (LFM2: rotate-half of q and k after their norms; Laguna: rotate-half of 64 or 48 query heads and 8 key heads, partial under YaRN, and the casts back; JoyAI: the rotary pairs of `q_pe` and of the one shared `k_pe`, its broadcast to every head and the two concatenations that assemble q and k), forward, recompute and backward, in the round program (self time, innermost of `attn_core` / `attn_rope`: benchmark/trace/scope_pairs.py)."""
from benchmark.layer_metrics.attn_core_ms import ROPE, scope_ms_by_layer

LAYER = "rotary embedding"
UNIT, SOURCE, MOVES = "ms", "device_trace", "rounds_per_s"


def read(ctx):
    by_layer = scope_ms_by_layer(ctx, ROPE)
    return sum(by_layer.values()) if by_layer else None
