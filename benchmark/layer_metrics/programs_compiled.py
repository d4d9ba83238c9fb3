"""Programs compiled before the window's first stamp: the program's counter
`programs{family, source}` where `source` is `compiled` (the others are
`bank_hit` and `xla_cache_hit`). 0 on a warm run; every one on a cell's
first run from an empty `.compile_cache/`."""
from benchmark import program_view

LAYER = "compile persistence and data"
UNIT, SOURCE, MOVES = "programs", "program_counter", "setup_s"


def read(ctx):
    tr = program_view.tracer()
    bounds = (program_view.phase_bounds(ctx, "window")
              if tr is not None else None)
    if bounds is None:
        return None
    return sum(n for name, n, labels in tr.counted(before=bounds[0])
               if name == "programs" and labels.get("source") == "compiled")
