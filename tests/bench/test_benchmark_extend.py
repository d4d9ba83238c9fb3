"""A later PR adds a configuration, a traffic mix, a per-layer metric and a
cell as new files and one entry each, and edits no file that is there
(benchmark/README.md). Shown here from a temporary directory."""

import json
import os

from benchmark import harness, registry

from tiny_root import TINY_CONFIG, make_root

READER = '''"""Host milliseconds per unit blocked on the stamp."""
LAYER = "engine"
UNIT, SOURCE, MOVES = "ms", "program_span", "rounds_per_s"


def read(ctx):
    waits = ctx["spans"].durations("wait", "window")
    return 1e3 * sum(waits) / len(waits) if waits else None
'''


def test_one_of_each_from_outside(tmp_path):
    root = make_root(tmp_path)
    ext = tmp_path / "ext"
    # a configuration: its own file of sizes, naming its plain reference
    config = dict(TINY_CONFIG, name="tiny-cnn-six",
                  flags=[f.replace("--num_agents=4", "--num_agents=6")
                         for f in TINY_CONFIG["flags"]], agents=6)
    (ext / "configs" / "tiny-cnn-six.json").write_text(json.dumps(config))
    # a traffic mix: a data file of parameters for the one generator
    (ext / "traffic" / "every-third.json").write_text(json.dumps(
        {"flags": ["--snap=3"], "trace_units": 3,
         "why": "an eval boundary after every third round"}))
    # a per-layer metric: a small reader of its own
    os.makedirs(ext / "layer_metrics")
    (ext / "layer_metrics" / "stamp_wait_ms.py").write_text(READER)
    # and one entry each
    bench = json.loads(open(root).read())
    bench["configs"].append({"name": "tiny-cnn-six", "source": "tests",
                             "file": "ext/configs/tiny-cnn-six.json",
                             "reduced": [], "why": "six agents"})
    bench["workloads"].append({"name": "tiny-cnn-six.every-third",
                               "config": "tiny-cnn-six",
                               "traffic": "every-third", "chips": 1,
                               "why": "new cell"})
    bench["per_layer"].append({"name": "stamp_wait_ms", "unit": "ms",
                               "better": "lower", "source": "program_span",
                               "layer": "engine", "moves": "rounds_per_s",
                               "workloads": ["tiny-cnn-six.every-third"]})
    open(root, "w").write(json.dumps(bench))

    cell = registry.resolve(registry.load_benchmark(root),
                            "tiny-cnn-six.every-third")
    assert "--snap=3" in cell.flags and "--num_agents=6" in cell.flags
    lines = []
    result = harness.run_cell("tiny-cnn-six.every-third", 21, 0.0, True,
                              platform="cpu", bench_path=root,
                              say=lines.append)
    assert result["correct"] is True
    assert result["metrics"]["stamp_wait_ms"]["unit"] == "ms"
    assert result["metrics"]["stamp_wait_ms"]["value"] >= 0
    c3 = json.loads(next(ln for ln in lines if ln.startswith("[bench] C3")
                         ).split(" ", 2)[2])
    # warm-up runs to the first boundary (round 3); then 3 + 3 rounds
    assert c3["eval_boundaries"] == c3["eval_rows"] == 3
    # the cells that were there do not report the new metric
    old = registry.resolve(registry.load_benchmark(root),
                           "tiny-cnn.round-eval")
    assert "stamp_wait_ms" not in [m["name"] for m in old.per_layer]
