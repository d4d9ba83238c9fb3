"""BENCHMARK.json against the contract: the key sets, the characters of
names and units, and that every entry resolves to the files it names."""

import json
import os
import re

import pytest

from benchmark import registry

from tiny_root import REPO, repo_benchmark

BENCH = repo_benchmark()
CELLS = [w["name"] for w in BENCH["workloads"]]
METRICS = BENCH["end_to_end"] + BENCH["per_layer"]
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
TEXT = re.compile(r"^[^\t\n]{1,200}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(REPO, "BENCHMARK.json")) <= 64 * 1024
    assert 1 <= len(BENCH["command"]) <= 32
    assert all(TEXT.match(w) for w in BENCH["command"])
    assert 1 <= len(BENCH["paths"]) <= 16
    assert all(PATH.match(p) and not p.startswith("/") and ".." not in p
               for p in BENCH["paths"])
    script = BENCH["command"][1]
    assert any(script.startswith(p + "/") for p in BENCH["paths"])


def test_run_seconds_fits_a_full_check_of_24_cells():
    s = BENCH["run_seconds"]
    assert isinstance(s, int) and 1 <= s <= 51
    assert (2 + 14 * 24) * (s + 60) + 24 * 2 * 90 + 1200 <= 43200


def test_four_chip_quota():
    four = sum(1 for w in BENCH["workloads"] if w["chips"] == 4)
    assert four <= max(1, len(BENCH["workloads"]) // 4)


@pytest.mark.parametrize("entry", BENCH["configs"], ids=lambda c: c["name"])
def test_config_entry(entry):
    assert set(entry) == {"name", "source", "file", "reduced", "why"}
    assert registry.NAME.match(entry["name"])
    assert TEXT.match(entry["source"]) and TEXT.match(entry["why"])
    assert any(entry["file"].startswith(p + "/") for p in BENCH["paths"])
    assert len(entry["reduced"]) <= 16
    with open(os.path.join(REPO, entry["file"])) as f:
        config = json.load(f)
    assert config["source"] == entry["source"]
    assert config["reduced"] == entry["reduced"] == []
    for key in ("flags", "reference", "image_shape", "normalise", "backdoor",
                "check", "assumed", "examples_per_round", "parameters"):
        assert key in config
    assert any(entry["name"] == w["config"] for w in BENCH["workloads"])
    files = [c["file"] for c in BENCH["configs"]]
    assert files.count(entry["file"]) == 1


@pytest.mark.parametrize("name", CELLS)
def test_cell_resolves_to_its_files(name):
    bench = registry.load_benchmark()
    entry = next(w for w in bench["workloads"] if w["name"] == name)
    assert set(entry) == {"name", "config", "traffic", "chips", "why"}
    assert all(registry.NAME.match(entry[k])
               for k in ("name", "config", "traffic"))
    assert entry["chips"] in (1, 4) and TEXT.match(entry["why"])
    cell = registry.resolve(bench, name)
    assert cell.traffic["flags"] and cell.config["flags"]
    assert "setup_s" in cell.end_to_end and len(cell.end_to_end) >= 2
    assert cell.per_layer
    registry.load_module(cell.search_dirs, "reference",
                         cell.config["reference"]).forward_flops(
        tuple(cell.config["image_shape"]))
    for m in cell.per_layer:
        reader = registry.load_module(cell.search_dirs, "layer_metrics",
                                      m["name"])
        assert (reader.LAYER, reader.UNIT, reader.SOURCE, reader.MOVES) == (
            m["layer"], m["unit"], m["source"], m["moves"])
        assert m["moves"] in cell.end_to_end
        assert callable(reader.read)


@pytest.mark.parametrize("name", CELLS)
def test_cell_flags_parse_to_the_config_it_states(name):
    from defending_against_backdoors_with_robust_learning_rate_tpu.config import (
        args_parser)
    cell = registry.resolve(registry.load_benchmark(), name)
    cfg = args_parser(cell.flags)
    assert cfg.agents_per_round == cell.config["agents"]
    assert cfg.image_shape == tuple(cell.config["image_shape"])
    assert cfg.base_class == cell.config["backdoor"]["base_class"]
    assert cfg.target_class == cell.config["backdoor"]["target_class"]
    assert cfg.pattern_type == cell.config["backdoor"]["pattern"]
    assert (cfg.local_ep * cfg.synth_train_size
            == cell.config["examples_per_round"])
    assert cfg.robustLR_threshold > 0 and cfg.num_corrupt > 0
    assert (cfg.mesh == 4) == (cell.chips == 4)


def test_pairs_and_names_are_unique():
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(set(pairs)) == len(pairs)
    for group in (BENCH["configs"], BENCH["workloads"], METRICS):
        names = [e["name"] for e in group]
        assert len(set(names)) == len(names)


@pytest.mark.parametrize("metric", METRICS, ids=lambda m: m["name"])
def test_metric_entry(metric):
    end_to_end = metric in BENCH["end_to_end"]
    keys = ({"name", "unit", "better", "bound", "source"} if end_to_end
            else {"name", "unit", "better", "source", "layer", "moves"})
    assert set(metric) - {"workloads"} == keys
    assert registry.NAME.match(metric["name"])
    assert registry.UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    assert metric["source"] in SOURCES
    if end_to_end:
        assert metric["source"] in ("host_clock", "device_trace")
        assert 0.01 <= metric["bound"] <= 0.1
    else:
        assert TEXT.match(metric["layer"])
        assert metric["moves"] in [m["name"] for m in BENCH["end_to_end"]]
    for w in metric.get("workloads", []):
        assert w in CELLS


def test_setup_s_is_an_end_to_end_metric_of_every_cell():
    setup = next(m for m in BENCH["end_to_end"] if m["name"] == "setup_s")
    assert "workloads" not in setup and setup["bound"] <= 0.1


def test_files_under_paths_use_only_the_allowed_characters():
    for p in BENCH["paths"]:
        for base, dirs, files in os.walk(os.path.join(REPO, p)):
            dirs[:] = [d for d in dirs if d != "__pycache__"]
            for f in files:
                if f.endswith(".pyc"):
                    continue
                rel = os.path.relpath(os.path.join(base, f), REPO)
                assert PATH.match(rel), rel
