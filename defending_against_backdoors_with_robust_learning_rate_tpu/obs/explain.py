"""Cross-run regression forensics: diff two runs into a per-span /
per-phase delta table and a classified verdict.

The trajectory gate (``obs/trajectory.py``) says THAT a run regressed —
a bare ratio against the best earlier point. This module says WHERE:
it loads two sides (each a run directory holding ``metrics.jsonl``, or
a bench artifact — a bare ``bench.py`` result object or a session
``BENCH_r*.json`` record), normalizes every span's total host time to
ms per dispatched round, groups spans into phase families::

    compile     bench/data, bench/aot_acquire, bench/first_block
                (+ the artifact's compile_s scalar)
    steady      round/*, prefetch/*, bench/steady_blocks,
                bench/profile_blocks
    eval        eval/*, metrics/*
    drain       drain/*
    checkpoint  ckpt/*

and classifies the verdict: which family grew the most, whether the
collective share moved, and whether the headline throughput drop
clears the trajectory tolerance. Consumed three ways: the
``scripts/bench_trajectory.py --explain`` CLI, the auto-explain a gate
FAIL prints, and the "Regression forensics" section of
``obs/report.py``'s markdown. Stdlib-only — runs on machines without
jax, like every offline obs tool.
"""

from __future__ import annotations

import json
import os
from typing import Any, Dict, List, Optional

from . import flight as obs_flight
from . import report as obs_report
from . import trajectory

FAMILIES = ("compile", "steady", "eval", "drain", "checkpoint", "other")

_COMPILE_SPANS = ("bench/data", "bench/aot_acquire", "bench/first_block")
_STEADY_SPANS = ("bench/steady_blocks", "bench/profile_blocks")

# a collective-share move this large reclassifies a steady regression:
# the rounds got slower because the devices talk more, not compute more
COLLECTIVE_SHIFT = 0.05


class MalformedInput(ValueError):
    """Neither a run dir with metrics.jsonl nor a recognizable bench
    artifact (CLI exit code 2, mirroring the trajectory gate)."""


def span_family(name: str) -> str:
    if name in _COMPILE_SPANS:
        return "compile"
    if name in _STEADY_SPANS:
        return "steady"
    if name.startswith(("eval/", "metrics/")):
        return "eval"
    if name.startswith("drain/"):
        return "drain"
    if name.startswith("ckpt/"):
        return "checkpoint"
    if name.startswith(("round/", "prefetch/")):
        return "steady"
    return "other"


# --------------------------------------------------------------------------
# sides
# --------------------------------------------------------------------------

def load_side(path: str) -> Dict[str, Any]:
    """Normalize one comparison side::

        {label, kind, value, units, spans, compile_s,
         collective_frac, incident}

    ``spans`` is the report-shaped ``{name: {count, total_s, ...}}``
    table; ``units`` is the dispatched-round count the totals are
    normalized by (None when the side doesn't record it); ``incident``
    is the run dir's last flight-snapshot reason, when one exists."""
    if os.path.isdir(path):
        jsonl = os.path.join(path, "metrics.jsonl")
        if not os.path.exists(jsonl):
            raise MalformedInput(
                f"{path}: a directory but no metrics.jsonl — "
                f"not a run dir")
        metrics = obs_report.flat_metrics(obs_report.read_metrics(jsonl))
        spans = obs_report.span_table(metrics)
        value = metrics.get("Throughput/Steady_Rounds_Per_Sec",
                            metrics.get("Throughput/Rounds_Per_Sec"))
        units = spans.get("round/dispatch", {}).get("count")
        snap = obs_flight.read_snapshot(
            os.path.join(path, obs_flight.SNAPSHOT_NAME))
        return {
            "label": os.path.basename(os.path.normpath(path)),
            "kind": "run_dir", "value": value, "units": units,
            "spans": spans, "compile_s": None,
            "collective_frac": metrics.get("Device/Collective_Frac"),
            "incident": snap.get("reason") if snap else None,
        }
    try:
        with open(path, encoding="utf-8") as f:
            data = json.load(f)
    except (OSError, ValueError) as e:
        raise MalformedInput(f"{path}: {e}") from e
    if not isinstance(data, dict):
        raise MalformedInput(f"{path}: expected a JSON object")
    label = os.path.splitext(os.path.basename(path))[0]
    if "parsed" in data and isinstance(data.get("parsed"), dict):
        label = f"r{int(data.get('n', 0)):02d}"   # session record
        data = data["parsed"]
    if "metric" not in data and "spans" not in data:
        raise MalformedInput(
            f"{path}: neither a bench result (no 'metric'/'spans') "
            f"nor a session record (no 'parsed')")
    spans = data.get("spans") or {}
    if not isinstance(spans, dict):
        raise MalformedInput(f"{path}: 'spans' is not a table")
    units: Optional[float] = None
    blocks, chain = data.get("blocks"), data.get("chain")
    if isinstance(blocks, (int, float)) and isinstance(chain,
                                                       (int, float)):
        units = float(blocks) * float(chain)
    attr = data.get("attribution") or {}
    return {
        "label": label, "kind": "artifact",
        "value": data.get("value"), "units": units, "spans": spans,
        "compile_s": data.get("compile_s"),
        "collective_frac": attr.get("collective_frac"),
        "incident": None,
    }


# --------------------------------------------------------------------------
# the diff
# --------------------------------------------------------------------------

def _per_unit_ms(side: Dict[str, Any], name: str) -> Optional[float]:
    st = side["spans"].get(name)
    if not st or "total_s" not in st:
        return None
    total_ms = st["total_s"] * 1e3
    units = side.get("units")
    return total_ms / units if units else total_ms


def _pct(base: Optional[float], cand: Optional[float]
         ) -> Optional[float]:
    if base is None or cand is None or base == 0:
        return None
    return round(100.0 * (cand - base) / base, 1)


def diff(base: Dict[str, Any], cand: Dict[str, Any],
         tolerance: float = trajectory.DEFAULT_TOLERANCE
         ) -> Dict[str, Any]:
    """The explain document: per-span and per-family deltas (base vs
    candidate, ms per dispatched round), the headline value delta, the
    collective-share move, and a classified verdict naming the phase
    that regressed. Sides with different unit normalization still
    compare fairly — each side is normalized by its OWN round count."""
    normalized = bool(base.get("units")) and bool(cand.get("units"))
    span_rows: List[Dict[str, Any]] = []
    for name in sorted(set(base["spans"]) | set(cand["spans"])):
        b, c = _per_unit_ms(base, name), _per_unit_ms(cand, name)
        span_rows.append({
            "span": name, "family": span_family(name),
            "base_ms": None if b is None else round(b, 3),
            "cand_ms": None if c is None else round(c, 3),
            "delta_ms": (None if b is None or c is None
                         else round(c - b, 3)),
            "delta_pct": _pct(b, c),
        })
    families: Dict[str, Dict[str, Any]] = {}
    for fam in FAMILIES:
        rows = [r for r in span_rows if r["family"] == fam]
        if not rows:
            continue
        b = sum(r["base_ms"] for r in rows
                if r["base_ms"] is not None)
        c = sum(r["cand_ms"] for r in rows
                if r["cand_ms"] is not None)
        families[fam] = {"base_ms": round(b, 3), "cand_ms": round(c, 3),
                         "delta_ms": round(c - b, 3),
                         "delta_pct": _pct(b, c)}

    value_pct = _pct(base.get("value"), cand.get("value"))
    compile_pct = _pct(base.get("compile_s"), cand.get("compile_s"))
    coll_b, coll_c = (base.get("collective_frac"),
                      cand.get("collective_frac"))
    coll_shift = (round(coll_c - coll_b, 4)
                  if coll_b is not None and coll_c is not None else None)

    # ---- verdict: did it regress, and which phase owns the delta ----
    if value_pct is not None:
        regressed = value_pct < -100.0 * tolerance
    else:
        regressed = any(
            f["delta_pct"] is not None
            and f["delta_pct"] > 100.0 * tolerance
            for f in families.values())
    phase: Optional[str] = None
    phase_note = ""
    grown = [(fam, f["delta_ms"]) for fam, f in families.items()
             if f["delta_ms"] > 0]
    if grown:
        phase, delta = max(grown, key=lambda kv: kv[1])
        f = families[phase]
        unit = "ms/round" if normalized else "ms total"
        phase_note = (f"{phase} grew {f['base_ms']} -> {f['cand_ms']} "
                      f"{unit} ({_fmt_pct(f['delta_pct'])})")
    if compile_pct is not None and compile_pct > 100.0 * tolerance \
            and (phase is None or phase != "compile"):
        # the compile_s scalar sees recompiles the span table may not
        phase = "compile"
        phase_note = (f"compile_s grew {base.get('compile_s')} -> "
                      f"{cand.get('compile_s')} s "
                      f"({_fmt_pct(compile_pct)})")
    if coll_shift is not None and coll_shift > COLLECTIVE_SHIFT:
        phase_note += (f"; collective share rose "
                       f"{coll_b:.2f} -> {coll_c:.2f}" if phase_note
                       else f"collective share rose "
                            f"{coll_b:.2f} -> {coll_c:.2f}")
        if phase in (None, "steady"):
            phase = phase or "steady"

    return {
        "base": {k: base.get(k) for k in
                 ("label", "kind", "value", "units", "compile_s",
                  "collective_frac", "incident")},
        "cand": {k: cand.get(k) for k in
                 ("label", "kind", "value", "units", "compile_s",
                  "collective_frac", "incident")},
        "tolerance": tolerance,
        "normalized": normalized,
        "value_delta_pct": value_pct,
        "compile_delta_pct": compile_pct,
        "collective_shift": coll_shift,
        "spans": span_rows,
        "families": families,
        "verdict": {"regressed": regressed, "phase": phase,
                    "note": phase_note},
    }


def explain_paths(base_path: str, cand_path: str,
                  tolerance: float = trajectory.DEFAULT_TOLERANCE
                  ) -> Dict[str, Any]:
    """load_side both sides and diff them (the CLI entry point)."""
    return diff(load_side(base_path), load_side(cand_path),
                tolerance=tolerance)


# --------------------------------------------------------------------------
# rendering
# --------------------------------------------------------------------------

def _fmt_pct(pct: Optional[float]) -> str:
    return "—" if pct is None else f"{pct:+.1f}%"


def _fmt(v: Optional[float]) -> str:
    return obs_report._fmt(v)


def render_text(doc: Dict[str, Any]) -> List[str]:
    """The CLI / gate-FAIL view: one ``[explain]`` line per fact, the
    verdict first — a FAIL should name its phase before the table."""
    v = doc["verdict"]
    lines = []
    if v["regressed"]:
        head = f"REGRESSED — phase: {v['phase'] or 'unclassified'}"
    else:
        head = "no regression past tolerance"
    lines.append(f"[explain] {doc['base']['label']} -> "
                 f"{doc['cand']['label']}: {head}")
    if v["note"]:
        lines.append(f"[explain]   {v['note']}")
    if doc["value_delta_pct"] is not None:
        lines.append(
            f"[explain]   value {_fmt(doc['base']['value'])} -> "
            f"{_fmt(doc['cand']['value'])} "
            f"({_fmt_pct(doc['value_delta_pct'])}, tolerance "
            f"-{100 * doc['tolerance']:.0f}%)")
    unit = "ms/round" if doc["normalized"] else "ms total"
    for fam, f in doc["families"].items():
        lines.append(f"[explain]   {fam:<10} {f['base_ms']:>10} -> "
                     f"{f['cand_ms']:>10} {unit}  "
                     f"({_fmt_pct(f['delta_pct'])})")
    for side in (doc["base"], doc["cand"]):
        if side.get("incident"):
            lines.append(f"[explain]   {side['label']}: last flight "
                         f"snapshot reason: {side['incident']}")
    return lines


def render_markdown_section(doc: Dict[str, Any]) -> str:
    """The ``## Regression forensics`` block obs/report.py appends when
    invoked with ``--explain_baseline``."""
    v = doc["verdict"]
    lines: List[str] = []
    add = lines.append
    add("## Regression forensics")
    add("")
    add(f"Baseline `{doc['base']['label']}` vs candidate "
        f"`{doc['cand']['label']}` — verdict: "
        + (f"**REGRESSED ({v['phase'] or 'unclassified'})**"
           if v["regressed"] else "PASS"))
    if v["note"]:
        add("")
        add(f"_{v['note']}_")
    add("")
    unit = "ms/round" if doc["normalized"] else "ms total"
    add(f"| phase | base {unit} | cand {unit} | delta |")
    add("|---|---:|---:|---:|")
    for fam, f in doc["families"].items():
        mark = "**" if v["regressed"] and fam == v["phase"] else ""
        add(f"| {mark}{fam}{mark} | {_fmt(f['base_ms'])} "
            f"| {_fmt(f['cand_ms'])} | {_fmt_pct(f['delta_pct'])} |")
    add("")
    add("| span | family | base | cand | delta |")
    add("|---|---|---:|---:|---:|")
    for r in sorted(doc["spans"],
                    key=lambda r: -(r["delta_ms"] or 0)):
        add(f"| `{r['span']}` | {r['family']} | {_fmt(r['base_ms'])} "
            f"| {_fmt(r['cand_ms'])} | {_fmt_pct(r['delta_pct'])} |")
    add("")
    return "\n".join(lines)
