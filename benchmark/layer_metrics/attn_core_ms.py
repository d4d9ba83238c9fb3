"""Device milliseconds a round under the scope `attn_core`: the attention core of every attention layer a client's step runs (`token_ops.causal_attention`, both lowerings: on the chip the splash kernel's forward, recompute and backward custom calls with the scaling of q and the head-major transposes around them), the MTP module's block included, in the round program (self time, innermost of `attn_core` / `attn_rope`: benchmark/trace/scope_pairs.py)."""
import os

from benchmark.trace import inner_scopes, reduce, scope_pairs

LAYER = "attention core"
UNIT, SOURCE, MOVES = "ms", "device_trace", "rounds_per_s"
CORE, ROPE = "attn_core", "attn_rope"
# the scopes the token models open around a whole attention layer, each
# around both of the above
LAYER_SCOPES = ("attention", "mla_attention", "window_attention",
                "global_attention")


def trace_path(ctx):
    """The traced part's `.xplane.pb`, or None where there is none or no
    traced round."""
    if ctx["trace"] is None or not ctx["traced_rounds"]:
        return None
    return reduce.find_xplane(
        os.path.join(os.path.dirname(ctx["cfg"].log_dir), "trace"))


def scope_ms_by_layer(ctx, scope):
    """{layer scope around it: device ms a traced round} under `scope`
    (CORE or ROPE) in the round program; None where there is no trace or
    no operation under `scope` (a program that plants none)."""
    path = trace_path(ctx)
    table = (scope_pairs.pair_seconds(path, (CORE, ROPE), LAYER_SCOPES,
                                      inner_scopes.EVAL_PROGRAMS)
             if path else None)
    got = {outer: 1e3 * secs / ctx["traced_rounds"]
           for (outer, inner), secs in (table or {}).items()
           if inner == scope}
    return got if sum(got.values()) > 0 else None


def read(ctx):
    by_layer = scope_ms_by_layer(ctx, CORE)
    return sum(by_layer.values()) if by_layer else None
