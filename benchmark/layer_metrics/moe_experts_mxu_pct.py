"""Share of the chip's bf16 peak that the held experts' products reach:
forward + backward operations of the (token, expert) pairs a traced round
computed here (the program's counter `moe_pairs_held`, times 3 x 6 x hidden
x expert width: `reference/<model>.moe_expert_flops`, a function of pairs and
widths only, whatever computes the products) over the device seconds a round
spends under the scope `moe_experts` times the peak (`peaks.json`). The
seconds hold the recompute and the count does not, so it reads low, never
over."""
from benchmark import program_view, registry
from benchmark.trace import inner_scopes

LAYER = "sparse experts"
UNIT, SOURCE, MOVES = "%", "device_trace", "rounds_per_s"


def traced_counts(ctx, name):
    """The values the program counted under `name` for the traced rounds:
    those counted after the traced part began."""
    tr = program_view.tracer()
    bounds = (program_view.phase_bounds(ctx, "traced")
              if tr is not None else None)
    if bounds is None:
        return []
    early = len([1 for n, _v, _l in tr.counted(before=bounds[0])
                 if n == name])
    return [v for n, v, _l in tr.counted() if n == name][early:]


def read(ctx):
    ms = inner_scopes.model_scope_ms_per_round(ctx, "moe_experts")
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
