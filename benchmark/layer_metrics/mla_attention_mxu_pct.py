"""Share of the chip's bf16 peak that latent attention reaches: forward +
backward operations of a round's tokens through every block's latent
attention (3 x `reference/<model>.mla_attention_flops`: projections and the
causal half of scores and values, from widths and the sequence length
alone) over the device seconds a round spends under the scope
`mla_attention` times the peak (`peaks.json`). The seconds hold the
recompute, the norms, rotary embedding, softmax and the masked half of
every diagonal block, and the count does not, so it reads low, never over."""
from benchmark import registry
from benchmark.layer_metrics.mla_attention_ms import scope_ms_per_round

LAYER = "latent attention"
UNIT, SOURCE, MOVES = "%", "device_trace", "rounds_per_s"


def read(ctx):
    ms = scope_ms_per_round(ctx, "mla_attention")
    cell = ctx["cell"]
    ref = registry.load_module(cell.search_dirs, "reference",
                               cell.config["reference"])
    if not ms or not hasattr(ref, "mla_attention_flops"):
        return None
    flops = 3.0 * ref.mla_attention_flops(cell.config["examples_per_round"],
                                          ref.dims_of(cell.config))
    peak = ctx["flops"].peaks(ctx["device"]["kind"])["bf16_tflops"] * 1e12
    return 100.0 * flops / (ms * 1e-3 * ctx["chips"] * peak)
