"""Share of the chip's bf16 peak that the sliding-window layers' attention
core reaches: 3 x `attn_core_flops{kind=window}` (the band's own keys,
`sum_r min(r + 1, window)` a sequence, whatever tiles the kernel visits) x
the steps of a round, over `window_core_ms` x chips x the peak. Half of
each window tile of the kernel is masked, and the count does not hold it:
the yardstick of a window tile that follows the band."""
from benchmark.layer_metrics.attn_core_ms import CORE, scope_ms_by_layer
from benchmark.layer_metrics.attn_core_mxu_pct import (core_flops_a_round,
                                                       share)

LAYER = "attention core"
UNIT, SOURCE, MOVES = "%", "device_trace", "rounds_per_s"


def read(ctx):
    ms = (scope_ms_by_layer(ctx, CORE) or {}).get("window_attention")
    flops = core_flops_a_round(ctx).get("window")
    if not ms or not flops:
        return None
    return share(ctx, ms, flops)
