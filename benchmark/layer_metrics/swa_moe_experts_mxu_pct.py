"""Share of the chip's bf16 peak that the held routed experts' products reach in the window/full-attention model, as `mla_moe_experts_mxu_pct` computes it: the program's counter `moe_pairs_held` of the traced rounds times this configuration's `reference/<model>.moe_expert_flops` (18 x 2048 x 512 a pair) over the device seconds a round spends under the scope `moe_experts` times the peak. The seconds hold the recompute and the casts and the count does not: it reads low, never over."""
from benchmark.layer_metrics.mla_moe_experts_mxu_pct import read  # noqa: F401

LAYER = "sparse experts"
UNIT, SOURCE, MOVES = "%", "device_trace", "rounds_per_s"
