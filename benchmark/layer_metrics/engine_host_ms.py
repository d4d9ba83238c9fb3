"""Mean host milliseconds per unit of the timed window inside
`RoundEngine.dispatch` and `post_unit`, from the program's own spans: the
wall time of `engine/dispatch` + `engine/post_unit` outside the leaf that
enters the runtime (`round/dispatch`, whose wall time is the device's
wherever the runtime makes the host wait), plus that leaf's own CPU time.
The twin, from inside, of `dispatch_host_ms`, which is thread CPU time on a
clock of 10 ms ticks around the same two calls. Prints the parts, and the
same sum for `engine/eval_boundary`, as `[bench] engine_host {...}`."""
import json

from benchmark import program_view

LAYER = "engine"
UNIT, SOURCE, MOVES = "ms", "program_span", "rounds_per_s"
OUTER = ("engine/dispatch", "engine/post_unit")


def read(ctx):
    tr = program_view.tracer()
    units = program_view.window_units(ctx, tr) if tr is not None else {}
    if not units:
        return None
    unit_rows = [program_view.host_ms(spans, OUTER,
                                      program_view.DISPATCH_LEAVES)
                 for spans in units.values()]
    eval_rows = [program_view.host_ms(spans, ("engine/eval_boundary",),
                                      program_view.EVAL_LEAVES)
                 for spans in units.values()
                 if any(s.name == "engine/eval_boundary" for s in spans)]
    unit_mean = program_view.mean_table(unit_rows)
    print("[bench] engine_host " + json.dumps({
        "units": len(unit_rows), "dispatch_and_post_unit": unit_mean,
        "eval_boundaries": len(eval_rows),
        "eval_boundary": program_view.mean_table(eval_rows)}), flush=True)
    return unit_mean["host_ms"]
