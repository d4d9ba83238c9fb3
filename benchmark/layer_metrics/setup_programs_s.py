"""Seconds of set-up in which a program was acquired, from the program's
own spans: every `setup/acquire/<family>` (the executable bank: load, or
compile and bank) and every `xla/acquire` outside them (the backend
compiled, or fetched from XLA's persistent cache: every sharded family,
anything eager, anything that compiles late) that ended before the window's
first stamp. So the programs a four-chip cell loads at its first dispatch,
inside the harness's warm-up, count."""
from benchmark import program_view

LAYER = "compile persistence and data"
UNIT, SOURCE, MOVES = "s", "program_span", "setup_s"


def read(ctx):
    tr = program_view.tracer()
    cut = program_view.before_window(ctx, tr) if tr is not None else None
    if cut is None:
        return None
    return sum(program_view.seconds(s)
               for s in program_view.acquisitions(cut[0]))
