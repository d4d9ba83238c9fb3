"""Share of its roofline that the chunked delta rule reaches: the least time
the chip could take for the core's work in a round, the larger of its
operations over the bf16 peak and its bytes over the HBM bandwidth
(`peaks.json`), over `gdn_core_ms` x chips. Operations and bytes are 3 x
the reference's forward counts (`gdn_core_flops`, `gdn_core_bytes`: the
chunked form's products at chunk 64, and q, k, v, g, beta and the output
read or written once) over a round's tokens and every held linear layer,
whatever chunk or lowering the program takes. The seconds hold the
recompute of the core and the scan's steps, and the count does not: it
reads low, never over. Prints one `[bench] gdn_core {...}` line with both
bounds."""
import json

from benchmark import registry
from benchmark.layer_metrics import gdn_core_ms

LAYER = "linear attention core"
UNIT, SOURCE, MOVES = "%", "device_trace", "rounds_per_s"


def read(ctx):
    ms = gdn_core_ms.read(ctx)
    cell = ctx["cell"]
    ref = registry.load_module(cell.search_dirs, "reference",
                               cell.config["reference"])
    if not ms or not hasattr(ref, "gdn_core_flops"):
        return None
    tokens = cell.config["examples_per_round"]
    ops = 3.0 * ref.gdn_core_flops(tokens, cell.config)
    moved = 3.0 * ref.gdn_core_bytes(tokens, cell.config)
    peak = ctx["flops"].peaks(ctx["device"]["kind"])
    bound = {"flops_s": ops / (peak["bf16_tflops"] * 1e12),
             "bytes_s": moved / (peak["hbm_gbytes_per_s"] * 1e9)}
    print("[bench] gdn_core " + json.dumps(
        {"ms": ms, "ops": ops, "bytes": moved, **bound}), flush=True)
    return 100.0 * max(bound.values()) / (ms * 1e-3 * ctx["chips"])
