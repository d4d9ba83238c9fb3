"""Share of the chip's bf16 peak that the held routed experts' products
reach in the latent-attention model, computed as `moe_experts_mxu_pct`
computes it: the program's counter `moe_pairs_held` of the traced rounds
times `reference/<model>.moe_expert_flops` over the device seconds a round
spends under the scope `moe_experts` times the peak. The seconds hold the
recompute and the casts and the count does not: it reads low, never over."""
from benchmark import registry
from benchmark.layer_metrics.mla_attention_ms import scope_ms_per_round
from benchmark.layer_metrics.moe_experts_mxu_pct import traced_counts

LAYER = "sparse experts"
UNIT, SOURCE, MOVES = "%", "device_trace", "rounds_per_s"


def read(ctx):
    ms = scope_ms_per_round(ctx, "moe_experts")
    pairs = traced_counts(ctx, "moe_pairs_held")
    cell = ctx["cell"]
    ref = registry.load_module(cell.search_dirs, "reference",
                               cell.config["reference"])
    if not ms or not pairs or not hasattr(ref, "moe_expert_flops"):
        return None
    flops = ref.moe_expert_flops(sum(pairs) / len(pairs),
                                 ref.dims_of(cell.config))
    peak = ctx["flops"].peaks(ctx["device"]["kind"])["bf16_tflops"] * 1e12
    return 100.0 * flops / (ms * 1e-3 * ctx["chips"] * peak)
