"""Device milliseconds a round of the layout copies around the attention core: operations with no path of their own (`hlo_category` `data formatting`, or named `copy...` but for the asynchronous `copy-start` / `copy-done`) whose neighbour on the device lies under `attn_core` (the next operation with a path, whose operand it makes, or the last one before it, whose result it takes: benchmark/trace/scope_pairs.py), in the round program. On the chip they are the head-major copies XLA puts around the kernel's custom calls, which no scope holds."""
from benchmark.layer_metrics.attn_core_ms import CORE, trace_path
from benchmark.trace import inner_scopes, scope_pairs

LAYER = "attention layout copies"
UNIT, SOURCE, MOVES = "ms", "device_trace", "rounds_per_s"


def read(ctx):
    path = trace_path(ctx)
    copies = (scope_pairs.beside(path, CORE, inner_scopes.EVAL_PROGRAMS)
              if path else None)
    if copies is None:
        return None
    return 1e3 * sum(copies.values()) / ctx["traced_rounds"]
