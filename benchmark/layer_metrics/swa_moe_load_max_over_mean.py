"""Imbalance of the routed load in the window/full-attention model: the largest over the mean number of (token, expert) pairs a held expert of one sparse layer computed in a round, as `moe_load_max_over_mean` reads it from the counters `moe_load_max` and `moe_load_mean` of the traced rounds (16 of 256 experts held, no routing bias: 256 rows a step in expectation)."""
from benchmark.layer_metrics.moe_load_max_over_mean import read  # noqa: F401

LAYER = "router"
UNIT, SOURCE, MOVES = "ratio", "program_counter", "rounds_per_s"
