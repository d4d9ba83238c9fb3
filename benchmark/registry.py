"""Finds a cell's files by the names `BENCHMARK.json` gives them.

A configuration is the file its entry names; a traffic mix is
`traffic/<mix>.json`, a per-layer metric `layer_metrics/<name>.py` and a
plain reference `reference/<name>.py`, each looked for in every directory of
`paths` and then in this directory. So a later PR adds files and entries,
and edits nothing that is there."""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import re
from typing import Any, Dict, List, Optional

HERE = os.path.dirname(os.path.abspath(__file__))
REPO_ROOT = os.path.dirname(HERE)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    config: Dict[str, Any]            # the configuration file, parsed
    traffic_name: str
    traffic: Dict[str, Any]           # the traffic mix, parsed
    end_to_end: List[str]             # names this cell reports, trace 0
    per_layer: List[Dict[str, Any]]   # entries this cell reports, trace 1
    search_dirs: List[str]

    @property
    def flags(self) -> List[str]:
        """What a user types: the configuration's flags, then the mix's."""
        return list(self.config["flags"]) + list(self.traffic["flags"])


def load_benchmark(path: Optional[str] = None) -> Dict[str, Any]:
    path = path or os.path.join(REPO_ROOT, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    bench["_root"] = os.path.dirname(os.path.abspath(path))
    return bench


def search_dirs(bench: Dict[str, Any]) -> List[str]:
    dirs = [os.path.join(bench["_root"], p) for p in bench["paths"]]
    if HERE not in [os.path.abspath(d) for d in dirs]:
        dirs.append(HERE)
    return dirs


def find_file(dirs: List[str], *parts: str) -> str:
    for d in dirs:
        cand = os.path.join(d, *parts)
        if os.path.isfile(cand):
            return cand
    raise FileNotFoundError(
        f"{os.path.join(*parts)} is in none of {dirs}")


def _applies(metric: Dict[str, Any], workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def resolve(bench: Dict[str, Any], workload: str) -> Cell:
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"workload {workload!r} is not in BENCHMARK.json; "
                       f"it has {sorted(cells)}")
    w = cells[workload]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[w["config"]]
    dirs = search_dirs(bench)
    with open(os.path.join(bench["_root"], cfg_entry["file"])) as f:
        config = json.load(f)
    with open(find_file(dirs, "traffic", w["traffic"] + ".json")) as f:
        traffic = json.load(f)
    return Cell(
        name=workload, chips=int(w["chips"]), config_name=w["config"],
        config=config, traffic_name=w["traffic"], traffic=traffic,
        end_to_end=[m["name"] for m in bench["end_to_end"]
                    if _applies(m, workload)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, workload)],
        search_dirs=dirs)


def load_module(dirs: List[str], kind: str, name: str):
    """`<kind>/<name>.py` as a module of its own (a name may hold dots and
    dashes, so it is loaded by path, not imported by name)."""
    path = find_file(dirs, kind, name + ".py")
    spec = importlib.util.spec_from_file_location(
        f"_bench_{kind}_{re.sub(r'[^A-Za-z0-9_]', '_', name)}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
