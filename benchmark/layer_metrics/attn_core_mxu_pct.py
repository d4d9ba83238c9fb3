"""Share of the chip's bf16 peak that the attention core reaches: 3 x the
program's counter `attn_core_flops{kind}` (counted once at build by each
model's `build_counters`: the forward operations of `q k^T` and `p v` over
the keys the mask keeps, for one step's tokens, in every attention layer and
the MTP module's block) x the steps of a round, over `attn_core_ms` x chips
x the peak (`peaks.json`). The seconds hold the recompute, the backward's
second pass over the scores and the masked parts of the tiles at the
diagonal and the band's edges, and the count does not: it reads low, never
over. Prints one `[bench] attn_core {...}` line: the milliseconds and
operations a round by layer kind, the squares of scores the core forms of
those a sequence has (`attn_squares_computed` / `attn_squares`, and under a
window `attn_window_squares_computed` / `attn_window_squares`), and the
layers by the path their core takes (`attn_path`)."""
import json

from benchmark import program_view
from benchmark.layer_metrics.attn_core_ms import CORE, scope_ms_by_layer

LAYER = "attention core"
UNIT, SOURCE, MOVES = "%", "device_trace", "rounds_per_s"
SQUARES = ("attn_squares_computed", "attn_squares",
           "attn_window_squares_computed", "attn_window_squares")


def steps_a_round(cfg):
    """Client steps a round: clients x local epochs x batches an epoch."""
    return (cfg.agents_per_round * cfg.local_ep
            * (cfg.synth_train_size // cfg.num_agents // cfg.bs))


def core_flops_a_round(ctx):
    """{layer kind: forward operations of the core a round} from the
    program's counter; {} where it counted none (a program older than the
    counter)."""
    tr = program_view.tracer()
    out = {}
    for name, n, labels in (tr.counted() if tr is not None else ()):
        if name == "attn_core_flops":
            kind = labels.get("kind", "")
            out[kind] = out.get(kind, 0.0) + n * steps_a_round(ctx["cfg"])
    return out


def share(ctx, ms, flops):
    """100 x 3 x `flops` over `ms` x chips x the bf16 peak."""
    peak = ctx["flops"].peaks(ctx["device"]["kind"])["bf16_tflops"] * 1e12
    return 100.0 * 3.0 * flops / (ms * 1e-3 * ctx["chips"] * peak)


def by_kind(by_layer):
    """Core milliseconds by layer kind: the window layers' scope, and every
    other layer's as `full`."""
    window = by_layer.get("window_attention", 0.0)
    return {"full": sum(by_layer.values()) - window, "window": window}


def read(ctx):
    by_layer = scope_ms_by_layer(ctx, CORE)
    flops = core_flops_a_round(ctx)
    if not by_layer or not flops:
        return None
    tr = program_view.tracer()
    counted = tr.counted() if tr is not None else []
    print("[bench] attn_core " + json.dumps({
        "ms": by_kind(by_layer), "flops": flops,
        "squares": {n: v for n, v, _l in counted if n in SQUARES},
        "path": {lab.get("path"): v for n, v, lab in counted
                 if n == "attn_path"}}), flush=True)
    return share(ctx, sum(by_layer.values()), sum(flops.values()))
