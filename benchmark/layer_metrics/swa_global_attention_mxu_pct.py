"""Share of the chip's bf16 peak that the full-attention layers reach: 3 x
`reference/<model>.full_attention_flops` of a round's tokens (projections,
gate, and the causal half of scores and values) over the device seconds a
round spends under the scope `global_attention` times the peak. The seconds
hold the recompute, rotary embedding, softmax and the masked half of every
diagonal square, and the count does not: it reads low, never over."""
from benchmark.layer_metrics.swa_window_attention_ms import attention_mxu_pct

LAYER = "global attention"
UNIT, SOURCE, MOVES = "%", "device_trace", "rounds_per_s"


def read(ctx):
    return attention_mxu_pct(ctx, "global_attention", "full_attention_flops")
