"""Model utilisation: forward + backward operations of one round, from
shapes (recompute not counted), times the run's rounds per second, over the
chips' bf16 peak."""
LAYER = "local training"
UNIT, SOURCE, MOVES = "%", "program_counter", "rounds_per_s"


def read(ctx):
    config = ctx["cell"].config
    peak = ctx["flops"].peaks(ctx["device"]["kind"])["bf16_tflops"] * 1e12
    per_round = ctx["flops"].round_train_flops(
        ctx["forward_flops"], config["examples_per_round"])
    return 100.0 * per_round * ctx["rounds_per_s"] / (ctx["chips"] * peak)
