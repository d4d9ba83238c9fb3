"""Run-report generator: one run dir -> ``report.md`` + ``report.json``.

    python -m defending_against_backdoors_with_robust_learning_rate_tpu.obs.report <run_dir>
        [--baseline PATH] [--write-baseline] [--headroom 4.0]
        [--trace_dir DIR] [--out DIR] [--backend cpu|tpu]

A training run leaves its observability in three places: ``Spans/*`` /
``Device/*`` / ``Memory/*`` rows in `metrics.jsonl`, the host-side
`trace.json`, and (under ``--profile_rounds``) a `profile/` dir of
jax.profiler captures. This CLI folds them into one judged artifact:

- a per-span table with host and device time side by side,
- the device compute/collective/gap split and named-scope attribution
  (re-parsed from the profile dir via `obs.attribution` when present),
- collective share per compiled program family,
- HBM live/peak watermarks,
- and a **PASS/FAIL budget table** against the pinned `obs_baseline.json`
  (tolerance-gated; refresh via ``--write-baseline``, mirroring the
  `analysis_baseline.json` workflow of the static-analysis gate).

Exit codes: 0 all budgets pass (or none pinned for this backend),
1 budget violation (or a pinned metric missing from the run — missing
observability is a regression too), 2 usage/IO error. Stdlib-only: runs
on machines without jax (the parse half of `obs.attribution` is
stdlib-only by design).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from typing import Any, Dict, List, Optional, Tuple

from defending_against_backdoors_with_robust_learning_rate_tpu.obs import (
    attribution)
from defending_against_backdoors_with_robust_learning_rate_tpu.obs.spans import (
    SPAN_STATS)

BASELINE_NAME = "obs_baseline.json"
DEFAULT_TOLERANCE = 1.5

# metrics --write-baseline pins (those present in the run): per-phase
# host latencies that catch a host-sync regression, the device split, and
# the memory watermark. Values are written with `--headroom` slack so CI
# machine jitter doesn't flake the gate.
DEFAULT_PIN_METRICS = (
    "Spans/round/dispatch/p50_ms",
    "Spans/metrics/emit/p50_ms",
    "Spans/eval/val_dispatch/p50_ms",
    "Device/Collective_Frac",
    "Device/Gap_Ms_Per_Round",
    "Memory/HBM_Peak_Bytes",
)

def repo_root() -> str:
    pkg_dir = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    return os.path.dirname(pkg_dir)


# --------------------------------------------------------------------------
# inputs
# --------------------------------------------------------------------------

def read_metrics(jsonl_path: str) -> List[Dict[str, Any]]:
    """Records of the LAST run segment in metrics.jsonl (the deterministic
    run_name means reruns append to one file, separated by `_run/start`
    boundary records)."""
    records: List[Dict[str, Any]] = []
    with open(jsonl_path) as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            try:
                rec = json.loads(line)
            except ValueError:
                continue
            if rec.get("tag") == "_run/start":
                records = []
                continue
            records.append(rec)
    return records


def flat_metrics(records: List[Dict[str, Any]]) -> Dict[str, float]:
    """tag -> last-written value (the run-final aggregates for Spans/*;
    the latest boundary for eval scalars)."""
    out: Dict[str, float] = {}
    for rec in records:
        tag, value = rec.get("tag"), rec.get("value")
        if isinstance(tag, str) and isinstance(value, (int, float)):
            out[tag] = float(value)
    return out


def span_table(metrics: Dict[str, float]) -> Dict[str, Dict[str, float]]:
    """{span_name: {stat: value}} from the Spans/<name>/<stat> rows."""
    spans: Dict[str, Dict[str, float]] = {}
    for tag, value in metrics.items():
        if not tag.startswith("Spans/"):
            continue
        name_stat = tag[len("Spans/"):]
        name, _, stat = name_stat.rpartition("/")
        if stat in SPAN_STATS and name:
            spans.setdefault(name, {})[stat] = value
    return spans


# --------------------------------------------------------------------------
# budgets (obs_baseline.json)
# --------------------------------------------------------------------------

def load_baseline(path: str) -> Dict[str, Any]:
    if not os.path.exists(path):
        return {"tolerance": DEFAULT_TOLERANCE, "budgets": {}}
    with open(path) as f:
        return json.load(f)


def check_budgets(baseline: Dict[str, Any], backend: str,
                  metrics: Dict[str, float]) -> List[Dict[str, Any]]:
    """[{metric, value, max, limit, pass, note}] for this backend's pins.
    A pinned metric missing from the run FAILS: silently losing a span or
    the device split is exactly the regression this gate exists for."""
    tol = float(baseline.get("tolerance", DEFAULT_TOLERANCE))
    results: List[Dict[str, Any]] = []
    for metric, pin in sorted(
            baseline.get("budgets", {}).get(backend, {}).items()):
        cap = float(pin["max"])
        limit = cap * tol
        value = metrics.get(metric)
        if value is None:
            results.append({"metric": metric, "value": None, "max": cap,
                            "limit": limit, "pass": False,
                            "note": "metric missing from the run"})
        else:
            results.append({"metric": metric, "value": value, "max": cap,
                            "limit": round(limit, 6),
                            "pass": value <= limit, "note": ""})
    return results


def write_baseline(path: str, backend: str, metrics: Dict[str, float],
                   headroom: float,
                   pins: Tuple[str, ...] = DEFAULT_PIN_METRICS) -> str:
    """Refresh this backend's section with measured*headroom ceilings for
    every default pin the run actually produced (other backends' pins and
    the tolerance are preserved)."""
    baseline = load_baseline(path)
    baseline.setdefault("tolerance", DEFAULT_TOLERANCE)
    section = baseline.setdefault("budgets", {}).setdefault(backend, {})
    for metric in pins:
        if metric in metrics:
            section[metric] = {"max": round(metrics[metric] * headroom, 6)}
    with open(path, "w") as f:
        json.dump(baseline, f, indent=1, sort_keys=True)
        f.write("\n")
    return path


# --------------------------------------------------------------------------
# rendering
# --------------------------------------------------------------------------

def _fmt(v: Optional[float], nd: int = 3) -> str:
    if v is None:
        return "—"
    if isinstance(v, float) and abs(v) >= 1e6:
        return f"{v:.3e}"
    s = f"{v:.{nd}f}"
    # strip trailing zeros only past a decimal point (at nd=0 there is
    # none, and "20" must not become "2")
    if "." in s:
        s = s.rstrip("0").rstrip(".")
    return s or "0"


def render_markdown(doc: Dict[str, Any]) -> str:
    lines: List[str] = []
    add = lines.append
    add(f"# Run report — `{doc['run_dir']}`")
    add("")
    add(f"Backend: **{doc['backend']}** · generated by "
        f"`python -m ...obs.report` · budgets: "
        f"{'PASS' if doc['pass'] else '**FAIL**'}")
    add("")
    tp = doc.get("throughput", {})
    if tp:
        add("## Throughput")
        add("")
        for tag, v in sorted(tp.items()):
            add(f"- `{tag}`: {_fmt(v)}")
        add("")

    add("## Spans — host vs device")
    add("")
    attr = doc.get("attribution") or {}
    per_round = attr.get("per_round") or {}
    add("| span | count | host p50 ms | host p95 ms | host total s "
        "| device ms/round |")
    add("|---|---:|---:|---:|---:|---:|")
    spans = doc.get("spans", {})
    for name in sorted(spans, key=lambda n: -spans[n].get("total_s", 0.0)):
        st = spans[name]
        # device time correlates to the dispatch phase: everything the
        # device executes per round was dispatched inside round/dispatch
        dev = (per_round.get("busy_ms")
               if name == "round/dispatch" else None)
        add(f"| `{name}` | {_fmt(st.get('count'), 0)} "
            f"| {_fmt(st.get('p50_ms'))} | {_fmt(st.get('p95_ms'))} "
            f"| {_fmt(st.get('total_s'))} | {_fmt(dev)} |")
    add("")

    add("## Device attribution")
    add("")
    if not attr:
        add("_No profiler capture found (run with `--profile_rounds N` "
            "to sample a device-trace window)._")
    elif not attr.get("device_present"):
        add(f"_No device track in the capture: "
            f"{attr.get('note', 'XLA:CPU')}_")
    else:
        add(f"- window {_fmt(attr['window_ms'])} ms over "
            f"{attr.get('rounds', '?')} rounds on "
            f"{', '.join(attr.get('devices', []))}")
        add(f"- busy {_fmt(attr['busy_ms'])} ms = compute "
            f"{_fmt(attr['compute_ms'])} + collective "
            f"{_fmt(attr['collective_ms'])} "
            f"({100 * attr['collective_frac']:.1f}%); gap "
            f"{_fmt(attr['gap_ms'])} ms")
        add("")
        add("| named scope | device ms | ms/round |")
        add("|---|---:|---:|")
        rounds = attr.get("rounds") or 0
        for scope, ms in sorted(attr.get("by_scope_ms", {}).items(),
                                key=lambda kv: -kv[1]):
            add(f"| `{scope}` | {_fmt(ms)} "
                f"| {_fmt(ms / rounds if rounds else None)} |")
        add("")
        add("### Collective share per program family")
        add("")
        add("| program | compute ms | collective ms | collective % |")
        add("|---|---:|---:|---:|")
        for mod, v in attr.get("by_program", {}).items():
            add(f"| `{mod}` | {_fmt(v['compute_ms'])} "
                f"| {_fmt(v['collective_ms'])} "
                f"| {100 * v['collective_frac']:.1f} |")
    add("")

    rep = doc.get("reputation", {})
    if rep:
        # defense-provenance section (obs/reputation.py): present only
        # when the run emitted Reputation/* rows — an off run's report
        # is byte-identical to the pre-plane format
        add("## Defense provenance")
        add("")
        add(f"- clients tracked: {_fmt(rep.get('Reputation/Clients_Tracked'), 0)}")
        add(f"- suspects past streak threshold: "
            f"{_fmt(rep.get('Reputation/Suspect_Count'), 0)}")
        add(f"- agreement (mean / min over sampled): "
            f"{_fmt(rep.get('Reputation/Mean_Agree'))} / "
            f"{_fmt(rep.get('Reputation/Min_Agree'))}")
        if "Reputation/Top_Suspect_Score" in rep:
            add(f"- top suspicion score: "
                f"{_fmt(rep['Reputation/Top_Suspect_Score'])}")
        if "Reputation/Suspicion_AUC" in rep:
            add(f"- suspicion ranking AUC vs known corrupt ids: "
                f"{_fmt(rep['Reputation/Suspicion_AUC'])}")
        tops = sorted((t, v) for t, v in rep.items()
                      if t.startswith("Reputation/Top_Suspects/"))
        if tops:
            add("")
            add("| rank | client id |")
            add("|---:|---:|")
            for t, v in tops:
                add(f"| {t.rsplit('/', 1)[1]} | {int(v)} |")
        add("")

    add("## Memory")
    add("")
    mem = doc.get("memory", {})
    if mem:
        for tag, v in sorted(mem.items()):
            add(f"- `{tag}`: {int(v):,} bytes")
    else:
        add("_No HBM watermarks recorded (device.memory_stats() is "
            "unavailable on this backend)._ ")
    add("")

    add("## Budgets")
    add("")
    results = doc.get("budget_results", [])
    if not results:
        add(f"_No budgets pinned for backend `{doc['backend']}` in "
            f"{BASELINE_NAME} (run `--write-baseline` on a good run)._ ")
    else:
        add("| metric | value | pinned max | limit (×tol) | verdict |")
        add("|---|---:|---:|---:|---|")
        for r in results:
            verdict = "PASS" if r["pass"] else "**FAIL**"
            note = f" ({r['note']})" if r.get("note") else ""
            add(f"| `{r['metric']}` | {_fmt(r['value'])} "
                f"| {_fmt(r['max'])} | {_fmt(r['limit'])} "
                f"| {verdict}{note} |")
    add("")
    if doc.get("explain"):
        # cross-run forensics (obs/explain.py, --explain_baseline): the
        # per-phase delta table against the named baseline run/artifact
        from defending_against_backdoors_with_robust_learning_rate_tpu.obs import (
            explain as explain_mod)
        add(explain_mod.render_markdown_section(doc["explain"]))
    return "\n".join(lines)


# --------------------------------------------------------------------------
# driver
# --------------------------------------------------------------------------

def generate(run_dir: str, trace_dir: Optional[str] = None,
             baseline_path: Optional[str] = None,
             backend: str = "",
             explain_baseline: str = "") -> Dict[str, Any]:
    """Build the report document for one run dir (no files written).
    ``explain_baseline`` names a reference run dir or bench artifact to
    diff this run against (obs/explain.py forensics section)."""
    jsonl = os.path.join(run_dir, "metrics.jsonl")
    if not os.path.exists(jsonl):
        raise FileNotFoundError(f"no metrics.jsonl under {run_dir!r} — "
                                f"is this a run directory?")
    metrics = flat_metrics(read_metrics(jsonl))
    spans = span_table(metrics)

    trace_dir = trace_dir or os.path.join(run_dir, "profile")
    attr = (attribution.attribute(trace_dir)
            if os.path.isdir(trace_dir) else None)
    # Device/* rows may already be in metrics.jsonl (the driver parses its
    # own window); the offline re-parse wins when both exist — it is the
    # fresher view of the same trace, and the always-available mode
    if attr and attr.get("device_present"):
        metrics.update(attribution.scalar_rows(attr))

    if not backend:
        backend = (attr.get("backend") if attr else "") or \
            ("tpu" if attr and attr.get("device_present") else "cpu")

    doc: Dict[str, Any] = {
        "run_dir": os.path.abspath(run_dir),
        "backend": backend,
        "generated_at": time.time(),
        "throughput": {t: v for t, v in metrics.items()
                       if t.startswith("Throughput/")},
        "spans": spans,
        "attribution": attr,
        "memory": {t: v for t, v in metrics.items()
                   if t.startswith("Memory/")},
        # defense-provenance rows (obs/reputation.py) — empty (and the
        # report section absent) when the run had --reputation off
        "reputation": {t: v for t, v in metrics.items()
                       if t.startswith("Reputation/")},
        "metrics": metrics,
    }
    bl = load_baseline(baseline_path
                       or os.path.join(repo_root(), BASELINE_NAME))
    doc["budget_results"] = check_budgets(bl, backend, metrics)
    doc["pass"] = all(r["pass"] for r in doc["budget_results"])
    if explain_baseline:
        # local import: obs/explain.py imports this module's readers
        from defending_against_backdoors_with_robust_learning_rate_tpu.obs import (
            explain as explain_mod)
        doc["explain"] = explain_mod.explain_paths(explain_baseline,
                                                   run_dir)
    return doc


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="obs.report",
        description="Render report.md/report.json for one run dir and "
                    "judge it against obs_baseline.json")
    ap.add_argument("run_dir", help="run directory (holds metrics.jsonl)")
    ap.add_argument("--trace_dir", default="",
                    help="profiler capture dir to attribute "
                         "(default <run_dir>/profile)")
    ap.add_argument("--baseline", default="",
                    help=f"budget file (default <repo>/{BASELINE_NAME})")
    ap.add_argument("--write-baseline", action="store_true",
                    help="refresh this backend's pins from the measured "
                         "values instead of judging against them")
    ap.add_argument("--headroom", type=float, default=4.0,
                    help="--write-baseline slack factor over the "
                         "measured values")
    ap.add_argument("--backend", default="",
                    help="override the judged backend section "
                         "(default: inferred from the capture, else cpu)")
    ap.add_argument("--out", default="",
                    help="output dir for report.md/report.json "
                         "(default: the run dir)")
    ap.add_argument("--explain_baseline", default="",
                    help="reference run dir or bench artifact to diff "
                         "this run against (obs/explain.py: adds the "
                         "Regression forensics section)")
    args = ap.parse_args(argv)

    baseline_path = args.baseline or os.path.join(repo_root(),
                                                  BASELINE_NAME)
    try:
        doc = generate(args.run_dir, trace_dir=args.trace_dir or None,
                       baseline_path=baseline_path,
                       backend=args.backend,
                       explain_baseline=args.explain_baseline)
    except (OSError, ValueError) as e:
        print(f"[report] ERROR: {e}", file=sys.stderr)
        return 2

    if args.write_baseline:
        path = write_baseline(baseline_path, doc["backend"],
                              doc["metrics"], args.headroom)
        print(f"[report] baseline written: {path}", file=sys.stderr)
        doc["budget_results"] = check_budgets(
            load_baseline(baseline_path), doc["backend"], doc["metrics"])
        doc["pass"] = all(r["pass"] for r in doc["budget_results"])

    out_dir = args.out or args.run_dir
    os.makedirs(out_dir, exist_ok=True)
    md_path = os.path.join(out_dir, "report.md")
    json_path = os.path.join(out_dir, "report.json")
    with open(md_path, "w") as f:
        f.write(render_markdown(doc))
    slim = {k: v for k, v in doc.items() if k != "metrics"}
    with open(json_path, "w") as f:
        json.dump(slim, f, indent=1, sort_keys=True)
        f.write("\n")
    print(f"[report] {md_path}")
    print(f"[report] {json_path}")
    failed = [r for r in doc["budget_results"] if not r["pass"]]
    for r in failed:
        print(f"[report] BUDGET FAIL: {r['metric']} = "
              f"{r['value'] if r['value'] is not None else 'missing'} "
              f"(limit {r['limit']})", file=sys.stderr)
    if doc["budget_results"]:
        print(f"[report] budgets: "
              f"{len(doc['budget_results']) - len(failed)}"
              f"/{len(doc['budget_results'])} pass", file=sys.stderr)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
