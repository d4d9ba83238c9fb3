"""A benchmark root of its own at a tiny size, for the CPU tests: the same
harness, found through a BENCHMARK.json in a temporary directory whose
`paths` add files to the ones benchmark/ has."""

import json
import os

TINY_FLAGS = ["--data=synthetic", "--num_agents=4", "--local_ep=1", "--bs=16",
              "--num_corrupt=1", "--poison_frac=0.5",
              "--robustLR_threshold=3", "--synth_train_size=128",
              "--synth_val_size=64", "--eval_bs=64"]
TINY_CONFIG = {
    "name": "tiny-cnn", "source": "tests", "flags": TINY_FLAGS,
    "reference": "cnn_mnist", "image_shape": [8, 8, 1], "n_classes": 10,
    "agents": 4, "examples_per_round": 128,
    "normalise": {"mean": [0.5], "std": [0.5]},
    # the program's stand-in pattern on 8x8 synthetic images: a 3x3 corner
    "backdoor": {"pattern": "corner", "base_class": 5, "target_class": 7,
                 "value": 255, "strokes": [{"rows": [0, 2], "cols": [0, 2]}]},
    # XLA:CPU at the highest precision against the same arithmetic
    "check": {"loss_rtol": 1e-4, "loss_atol": 1e-6, "acc_images": 0.5},
    "reduced": [], "assumed": {},
}
REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def repo_benchmark():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def make_root(tmp, extra_flags=(), cells=(("tiny-cnn.round-eval", "round-eval"),
                                           ("tiny-cnn.pairs", "pairs")),
              per_layer=None, config=None):
    """Writes <tmp>/BENCHMARK.json, <tmp>/ext/configs/tiny-cnn.json and the
    mix <tmp>/ext/traffic/pairs.json (two rounds per dispatch: the repo's
    ten take XLA:CPU a quarter of a minute to compile) and returns the path
    of the first. Metrics are the repo's own, less `mfu_pct`, whose reader
    rightly refuses a device that has no published peak, and with
    `round_p90_ms` in the first cell."""
    tmp = str(tmp)
    os.makedirs(os.path.join(tmp, "ext", "configs"), exist_ok=True)
    os.makedirs(os.path.join(tmp, "ext", "traffic"), exist_ok=True)
    with open(os.path.join(tmp, "ext", "traffic", "pairs.json"), "w") as f:
        json.dump({"flags": ["--chain=2", "--snap=2"], "trace_units": 2,
                   "why": "two rounds per dispatch"}, f)
    cfg = dict(config or TINY_CONFIG)
    cfg["flags"] = list(cfg["flags"]) + list(extra_flags)
    with open(os.path.join(tmp, "ext", "configs", "tiny-cnn.json"), "w") as f:
        json.dump(cfg, f)
    bench = repo_benchmark()
    names = [n for n, _t in cells]
    bench["paths"] = ["ext"]
    bench["configs"] = [{"name": "tiny-cnn", "source": "tests",
                         "file": "ext/configs/tiny-cnn.json", "reduced": [],
                         "why": "tiny"}]
    bench["workloads"] = [{"name": n, "config": "tiny-cnn", "traffic": t,
                           "chips": 1, "why": "tiny"} for n, t in cells]
    bench["per_layer"] = [m for m in bench["per_layer"]
                          if m["name"] != "mfu_pct"]
    # the tail the harness computes for a cell with units enough to have
    # one: no cell of the repo has (PERF.md section 7), the first here does
    bench["end_to_end"].append(
        {"name": "round_p90_ms", "unit": "ms", "better": "lower",
         "bound": 0.01, "source": "host_clock", "workloads": names[:1]})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            m["workloads"] = names[:1]
    if per_layer:
        bench["per_layer"] += per_layer
    path = os.path.join(tmp, "BENCHMARK.json")
    with open(path, "w") as f:
        json.dump(bench, f)
    return path
