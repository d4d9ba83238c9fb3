"""Cross-run perf trajectory: fold bench artifacts into one
series and judge regressions against a pinned tolerance.

This module (driven by ``scripts/bench_trajectory.py``) folds each bench
artifact — either the session-runner record shape
(``{"n", "cmd", "rc", "tail", "parsed": {...}}``) or a bare bench.py
result object (``{"metric": "fl_rounds_per_sec", ...}``) — into a
series file::

    {"version": 1, "tolerance": 0.15, "series": [
        {"label": "r01", "source": "BENCH_r01.json", "ok": false,
         "note": "bench rc 1"},
        {"label": "r03", "ok": true, "rounds_per_sec": 2.2268,
         "mfu": 0.1011, "group": "tpu|fmnist|f32", ...}, ...]}

No series is committed: the driver's ``PERF_LEDGER.jsonl`` is the record
of chip numbers. Judgement extends the ``obs/report.py`` PASS/FAIL
workflow to the time axis: points are grouped by comparability (backend
class, bench config, dtype — a ``--platform cpu`` debug number must never
be judged against a TPU run), and within a group each point is compared to
the best earlier point; a drop past ``tolerance`` is a REGRESSION. Exit
codes mirror the report gate: 0 all pass, 1 regression, 2 malformed
input. Stdlib-only.
"""

from __future__ import annotations

import json
import os
from typing import Any, Dict, List, Optional, Tuple

DEFAULT_TOLERANCE = 0.15
VERSION = 1

# judged metrics and the point key each stores its value under; fleet
# artifacts (service/queue.py --scheduler writes fleet_bench.json) join
# the same series in their own comparability group — a fleet cells/hour
# number is never compared against a solo rounds/sec flagship
METRICS = {"fl_rounds_per_sec": "rounds_per_sec",
           "fleet_cells_per_hour": "cells_per_hour",
           "bank_build_clients_per_sec": "clients_per_sec"}


class MalformedArtifact(ValueError):
    """A file that is neither a session bench record nor a bench result
    object (exit code 2 — distinct from a *recorded* failed run, which
    folds as an ok:false point and is skipped by the judge)."""


def _group_key(parsed: Dict[str, Any]) -> str:
    device = str(parsed.get("device", ""))
    plat = "tpu" if "tpu" in device.lower() else "cpu"
    config = parsed.get("bench_config", "fmnist")
    dtype = parsed.get("dtype", "f32")
    return f"{plat}|{config}|{dtype}"


def parse_artifact(path: str) -> Dict[str, Any]:
    """One bench artifact -> one trajectory point."""
    try:
        with open(path, encoding="utf-8") as f:
            data = json.load(f)
    except (OSError, ValueError) as e:
        raise MalformedArtifact(f"{path}: {e}") from e
    if not isinstance(data, dict):
        raise MalformedArtifact(f"{path}: expected a JSON object")
    source = os.path.basename(path)
    if "metric" in data:                       # bare bench.py result
        parsed: Optional[Dict[str, Any]] = data
        label = os.path.splitext(source)[0]
        rc = 0
    elif "cmd" in data or "rc" in data:        # session-runner record
        parsed = data.get("parsed")
        label = f"r{int(data.get('n', 0)):02d}"
        rc = int(data.get("rc", 0))
    else:
        raise MalformedArtifact(
            f"{path}: neither a bench result (no 'metric') nor a "
            f"session record (no 'cmd'/'rc')")
    if rc != 0 or not isinstance(parsed, dict) \
            or parsed.get("metric") not in METRICS \
            or "value" not in parsed:
        return {"label": label, "source": source, "ok": False,
                "note": (f"bench rc {rc}" if rc else "no parsed metric")}
    metric = parsed["metric"]
    group = _group_key(parsed)
    if metric == "fleet_cells_per_hour":
        group = f"fleet_{group}"
    elif metric == "bank_build_clients_per_sec":
        # build throughput joins its own group keyed by the pinned cell
        # (population + worker count) — a 4-worker 1M number must never
        # be judged against serial or a different population
        group = (f"bank_build_{group}|pop{parsed.get('population', 0)}"
                 f"|w{parsed.get('workers', 1)}")
    point = {
        "label": label, "source": source, "ok": True,
        "metric": metric,
        METRICS[metric]: float(parsed["value"]),
        "group": group,
        "device": parsed.get("device"),
    }
    for key in ("mfu", "tflops_per_sec", "tflop_per_round", "compile_s",
                "chain", "vs_baseline", "dtype", "bench_config",
                "slot_occupancy",
                "cells", "scheduler_bins", "wall_s", "population",
                "workers", "shard_clients"):
        if key in parsed:
            point[key] = parsed[key]
    return point


def point_value(point: Dict[str, Any]) -> float:
    """The judged value of an ok point, whichever metric it carries
    (pre-fleet points have no 'metric' field and store
    rounds_per_sec — the historical schema stays readable)."""
    for key in METRICS.values():
        if key in point:
            return float(point[key])
    raise MalformedArtifact(
        f"point {point.get('label')!r} has no judged value "
        f"(expected one of {sorted(METRICS.values())})")


# --------------------------------------------------------------------------
# the series file
# --------------------------------------------------------------------------

def load(path: str) -> Dict[str, Any]:
    if not os.path.exists(path):
        return {"version": VERSION, "tolerance": DEFAULT_TOLERANCE,
                "series": []}
    try:
        with open(path, encoding="utf-8") as f:
            traj = json.load(f)
    except (OSError, ValueError) as e:
        raise MalformedArtifact(f"{path}: {e}") from e
    if not isinstance(traj, dict) or not isinstance(
            traj.get("series"), list):
        raise MalformedArtifact(f"{path}: expected "
                                f"{{version, tolerance, series: []}}")
    return traj


def save(path: str, traj: Dict[str, Any]) -> None:
    with open(path, "w", encoding="utf-8") as f:
        json.dump(traj, f, indent=1, sort_keys=True)
        f.write("\n")


def _label_key(label: str):
    """Session labels sort numerically (r2 < r10 < r100 — a plain
    lexicographic sort would misorder the time axis from session 100
    on); anything else sorts after them, alphabetically."""
    if label.startswith("r") and label[1:].isdigit():
        return (0, int(label[1:]), label)
    return (1, 0, label)


def fold(traj: Dict[str, Any], points: List[Dict[str, Any]]
         ) -> Dict[str, Any]:
    """Merge points into the series (replace-by-label, then ordered by
    session number — the time axis judge() walks)."""
    by_label = {p["label"]: p for p in traj["series"]}
    for point in points:
        by_label[point["label"]] = point
    traj["series"] = [by_label[k] for k in sorted(by_label,
                                                  key=_label_key)]
    return traj


def judge(traj: Dict[str, Any]) -> Tuple[List[Dict[str, Any]], bool]:
    """[{label, group, value, best_prev, floor, pass, note}] for every
    ok point, plus the overall verdict. Each point is judged against the
    best EARLIER ok point of its comparability group; the first point of
    a group establishes it."""
    tol = float(traj.get("tolerance", DEFAULT_TOLERANCE))
    best: Dict[str, float] = {}
    results: List[Dict[str, Any]] = []
    for point in traj["series"]:
        if not point.get("ok"):
            results.append({"label": point["label"], "group": None,
                            "value": None, "pass": True,
                            "note": point.get("note",
                                              "recorded failure")})
            continue
        group = point["group"]
        value = point_value(point)
        prev = best.get(group)
        if prev is None:
            results.append({"label": point["label"], "group": group,
                            "value": value, "best_prev": None,
                            "floor": None, "pass": True,
                            "note": "group baseline"})
        else:
            floor = prev * (1.0 - tol)
            ok = value >= floor
            results.append({
                "label": point["label"], "group": group, "value": value,
                "best_prev": prev, "floor": round(floor, 6), "pass": ok,
                "note": "" if ok else
                f"regression: {value:.4f} < {floor:.4f} "
                f"(best {prev:.4f} - {100 * tol:.0f}%)"})
        best[group] = max(best.get(group, 0.0), value)
    return results, all(r["pass"] for r in results)
