"""Share of the traced part's idle device time that lies under no leaf span
of the program: each idle gap of each device goes to the innermost program
span that covers most of it (`benchmark/trace/program_spans.py`), and a gap
under no span, or in the self time of a span that has children, is host
time nobody has named. The harness keeps the profile at
`<dirname(cfg.log_dir)>/trace` until the readers have run. Prints the table
as `[bench] idle_by_program_span {...}`."""
import json
import os

from benchmark import program_view
from benchmark.trace import program_spans, reduce

LAYER = "device"
UNIT, SOURCE, MOVES = "%", "device_trace", "rounds_per_s"


def read(ctx):
    tr = program_view.tracer()
    if tr is None or ctx["trace"] is None:
        return None
    path = reduce.find_xplane(
        os.path.join(os.path.dirname(ctx["cfg"].log_dir), "trace"))
    if path is None:
        return None
    table = program_spans.idle_by_program_span(
        path, {s.name for s in tr.records()})
    if table is None or table["idle_s"] <= 0:
        return None
    print("[bench] idle_by_program_span " + json.dumps(table), flush=True)
    return 100.0 * table["outside_leaves_s"] / table["idle_s"]
