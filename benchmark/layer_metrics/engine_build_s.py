"""Seconds in `RoundEngine.__init__`: data generation from the seed,
program acquisition (utils/compile_cache.py), placement."""
LAYER = "compile persistence and data"
UNIT, SOURCE, MOVES = "s", "program_span", "setup_s"


def read(ctx):
    spans = ctx["spans"].durations("engine_build")
    return spans[0] if spans else None
