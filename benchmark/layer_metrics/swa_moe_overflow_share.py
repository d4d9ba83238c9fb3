"""Share of the traced rounds' sparse-layer forwards whose held pairs numbered more than the sorted buffer's first pass takes (16384 of a step's 65536 rows), so that the second pass ran: the program's counter `moe_overflow_steps` over (sparse blocks a step runs) x (clients x local epochs x batches an epoch) x rounds, as `mla_moe_overflow_share` reads it (this model has no MTP module: its reference's `dims_of` gives no `mtp_depth`)."""
from benchmark.layer_metrics.mla_moe_overflow_share import read  # noqa: F401

LAYER = "router"
UNIT, SOURCE, MOVES = "%", "program_counter", "rounds_per_s"
