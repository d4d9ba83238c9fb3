"""Share of the chip's bf16 peak that the sliding-window layers reach:
forward + backward operations of a round's tokens through every held
`sliding_attention` layer (3 x `reference/<model>.window_attention_flops`:
projections, gate, and scores and values ON THE BAND, `sum_r min(r + 1,
window)` keys a query, from widths and the sequence length alone) over the
device seconds a round spends under the scope `window_attention` times the
peak (`peaks.json`). The count is the band's own, whatever squares the
program forms, so skipping squares cannot push it over 100%; the seconds
hold the recompute, rotary embedding, softmax and the masked parts of the
squares at the band's edges, and the count does not: it reads low."""
from benchmark.layer_metrics.swa_window_attention_ms import attention_mxu_pct

LAYER = "window attention"
UNIT, SOURCE, MOVES = "%", "device_trace", "rounds_per_s"


def read(ctx):
    return attention_mxu_pct(ctx, "window_attention",
                             "window_attention_flops")
