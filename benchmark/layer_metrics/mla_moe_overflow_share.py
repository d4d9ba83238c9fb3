"""Share of the traced rounds' sparse-layer forwards whose held pairs
numbered more than the sorted buffer's first pass takes, so that the second
pass ran: the program's counter `moe_overflow_steps` over (sparse blocks a
step runs, the MTP modules' included, as the reference's `dims_of` counts
them) x (clients x local epochs x batches an epoch) x rounds."""
from benchmark import registry
from benchmark.layer_metrics.moe_experts_mxu_pct import traced_counts

LAYER = "router"
UNIT, SOURCE, MOVES = "%", "program_counter", "rounds_per_s"


def read(ctx):
    over = traced_counts(ctx, "moe_overflow_steps")
    if not over:
        return None
    cell, cfg = ctx["cell"], ctx["cfg"]
    ref = registry.load_module(cell.search_dirs, "reference",
                               cell.config["reference"])
    dims = ref.dims_of(cell.config)
    blocks = (sum(1 for layer in dims["layers"] if layer[-1])
              + int(dims.get("mtp_depth", 0)))
    steps = (cfg.agents_per_round * cfg.local_ep
             * (cfg.synth_train_size // cfg.num_agents // cfg.bs))
    if not blocks * steps:
        return None
    return 100.0 * sum(over) / (blocks * steps * len(over))
