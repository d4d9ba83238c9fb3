"""Imbalance of the routed load: the largest over the mean number of
(token, expert) pairs a held expert of one sparse layer computed in a round
(summed over clients and steps), from the program's counters
`moe_load_max` and `moe_load_mean` of the traced rounds. 1.0 is even
routing; the grouped products pad each expert's rows to whole tiles, so a
skewed load costs time the pair count does not show."""
from benchmark.layer_metrics.moe_experts_mxu_pct import traced_counts

LAYER = "router"
UNIT, SOURCE, MOVES = "ratio", "program_counter", "rounds_per_s"


def read(ctx):
    top = traced_counts(ctx, "moe_load_max")
    mean = traced_counts(ctx, "moe_load_mean")
    if not top or not mean or sum(mean) <= 0:
        return None
    return sum(top) / sum(mean)
