"""Device milliseconds a round under the scope `window_attention`: the sliding-window layers of the window/full-attention model (three of the five held), each around its q, k, v, gate and output products, rotary embedding over the whole head and the blockwise core that reads three key blocks a query block; forward, backward and recompute (self time, innermost scope: benchmark/trace/inner_scopes.py)."""
from benchmark import registry
from benchmark.layer_metrics.mla_attention_ms import scope_ms_per_round

LAYER = "window attention"
UNIT, SOURCE, MOVES = "ms", "device_trace", "rounds_per_s"
# the two scopes models/swa_moe.py plants inside `local_train`, one a layer
# kind, neither inside another (the sparse code's scopes are read by the
# readers that read them for the latent-attention cell)
SWA_SCOPES = ("window_attention", "global_attention")


def swa_scope_ms(ctx, scope):
    """`scope_ms_per_round` by this model's attention scopes; None where the
    program plants neither."""
    return scope_ms_per_round(ctx, scope, SWA_SCOPES)


def attention_mxu_pct(ctx, scope, flops_of):
    """Share of the chip's bf16 peak under an attention scope: 3 x the
    reference's `flops_of` (forward operations of that layer kind for a
    round's tokens: projections, gate, and scores and values over the keys
    the mask keeps) over the scope's device seconds a round times the peak.
    None where the scope or the reference's function is absent."""
    ms = swa_scope_ms(ctx, scope)
    cell = ctx["cell"]
    ref = registry.load_module(cell.search_dirs, "reference",
                               cell.config["reference"])
    if not ms or not hasattr(ref, flops_of):
        return None
    flops = 3.0 * getattr(ref, flops_of)(cell.config["examples_per_round"],
                                         ref.dims_of(cell.config))
    peak = ctx["flops"].peaks(ctx["device"]["kind"])["bf16_tflops"] * 1e12
    return 100.0 * flops / (ms * 1e-3 * ctx["chips"] * peak)


def read(ctx):
    return swa_scope_ms(ctx, "window_attention")
