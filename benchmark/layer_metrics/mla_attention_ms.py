"""Device milliseconds a round under the scope `mla_attention`: latent attention in every block a client's step runs (the MTP module's too): the low-rank projections, their norms, rotary embedding, blockwise causal scores and values, forward, backward and recompute (self time, innermost scope: benchmark/trace/inner_scopes.py)."""
import os

from benchmark.trace import inner_scopes, reduce

LAYER = "latent attention"
UNIT, SOURCE, MOVES = "ms", "device_trace", "rounds_per_s"
# the scopes models/mla_moe.py and the shared sparse code plant inside
# `local_train`, none inside another but `moe_experts` under `moe_router`
MLA_SCOPES = ("mla_attention", "shared_expert", "moe_router", "moe_experts",
              "dense_ffn", "lm_head")


def scope_ms_per_round(ctx, scope, names=MLA_SCOPES):
    """Device milliseconds a traced round spends under `scope`, the innermost
    of `names` on an operation's path, in the round program (the eval
    programs left out); None where there is no trace, no traced round, or no
    operation under the scope (a program that plants none)."""
    if ctx["trace"] is None or not ctx["traced_rounds"]:
        return None
    path = reduce.find_xplane(
        os.path.join(os.path.dirname(ctx["cfg"].log_dir), "trace"))
    table = (inner_scopes.self_seconds(path, names, inner_scopes.EVAL_PROGRAMS)
             if path else None)
    if not table or not table.get(scope):
        return None
    return 1e3 * table[scope] / ctx["traced_rounds"]


def read(ctx):
    return scope_ms_per_round(ctx, "mla_attention")
