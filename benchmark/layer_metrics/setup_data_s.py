"""Seconds of set-up inside `RoundEngine.__init__` that go to the data:
the program's own spans `setup/data` (load or generate from the seed,
partition, poison, poisoned validation set) and `setup/place` (dataset
stacks and parameters onto the device or the mesh; host time: a transfer
may run on after the span). Prints the whole set-up table of the run,
`[bench] setup_spans {...}`, on the way."""
import json

from benchmark import program_view

LAYER = "compile persistence and data"
UNIT, SOURCE, MOVES = "s", "program_span", "setup_s"
NAMES = ("setup/data", "setup/place")


def read(ctx):
    tr = program_view.tracer()
    cut = program_view.before_window(ctx, tr) if tr is not None else None
    if cut is None:
        return None
    table = program_view.setup_table(ctx, tr)
    if table is not None:
        print("[bench] setup_spans " + json.dumps(table), flush=True)
    own = [s for s in cut[0] if s.name in NAMES]
    return sum(program_view.seconds(s) for s in own) if own else None
