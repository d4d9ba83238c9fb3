"""Device milliseconds a round under the scope `shared_expert` of the window/full-attention model: the 512-wide gated feed-forward every token passes beside its routed experts, in every sparse block, forward, backward and recompute (as `shared_expert_ms` reads it, through this cell's own entry)."""
from benchmark.layer_metrics.shared_expert_ms import read  # noqa: F401

LAYER = "shared expert"
UNIT, SOURCE, MOVES = "ms", "device_trace", "rounds_per_s"
