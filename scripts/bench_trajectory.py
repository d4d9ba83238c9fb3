#!/usr/bin/env python
"""The cross-run perf trajectory gate (obs/trajectory.py CLI).

Judge a series (no series is committed — PERF_LEDGER.jsonl is the record
of chip numbers; a missing file is the empty series)::

    python scripts/bench_trajectory.py [--trajectory FILE]

Fold bench artifacts in (--write saves the series file)::

    python scripts/bench_trajectory.py --fold 'BENCH_*.json' --write

Exit codes extend the obs/report.py workflow: 0 every point passes,
1 regression against the pinned tolerance, 2 malformed input. Points
are judged only within their comparability group (backend class x bench
config x dtype) — a `--platform cpu` debug run is recorded, never
compared against a TPU run. Stdlib-only.
"""

from __future__ import annotations

import argparse
import glob
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from defending_against_backdoors_with_robust_learning_rate_tpu.obs import (  # noqa: E402
    explain as explain_mod, trajectory)


def _auto_explain(traj, results, traj_path) -> None:
    """On a gate FAIL, diff each failing point against its group's best
    earlier point when both source artifacts are still on disk — the
    FAIL then names the regressed phase, not just the ratio."""
    failed = {r["label"] for r in results if not r["pass"]}
    base_dir = os.path.dirname(os.path.abspath(traj_path))
    tol = float(traj.get("tolerance", trajectory.DEFAULT_TOLERANCE))
    best = {}   # group -> (value, label) of the best EARLIER ok point
    for point in traj["series"]:
        if not point.get("ok"):
            continue
        value = trajectory.point_value(point)
        group, label = point["group"], point["label"]
        prev = best.get(group)
        if label in failed and prev is not None:
            prev_point = next(p for p in traj["series"]
                              if p["label"] == prev[1])
            paths = [os.path.join(base_dir, p.get("source") or "")
                     for p in (prev_point, point)]
            if all(p.get("source") for p in (prev_point, point)) \
                    and all(os.path.exists(pth) for pth in paths):
                try:
                    doc = explain_mod.explain_paths(paths[0], paths[1],
                                                    tolerance=tol)
                except explain_mod.MalformedInput as e:
                    print(f"[explain] skipped ({e})", file=sys.stderr)
                else:
                    for line in explain_mod.render_text(doc):
                        print(line)
            else:
                print(f"[explain] hint: source artifacts for "
                      f"{prev[1]!r} / {label!r} not on disk — run "
                      f"scripts/bench_trajectory.py --explain <base> "
                      f"<cand> on the artifact pair to localize the "
                      f"regression")
        if prev is None or value > prev[0]:
            best[group] = (value, label)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="Fold bench artifacts into trajectory.json and "
                    "judge regressions against the pinned tolerance")
    ap.add_argument("--trajectory",
                    default=os.path.join(REPO, "trajectory.json"),
                    help="series file (default <repo>/trajectory.json)")
    ap.add_argument("--fold", nargs="*", default=None,
                    help="bench artifact paths/globs to fold in "
                         "(BENCH_r*.json records or bare bench.py "
                         "result JSON)")
    ap.add_argument("--write", action="store_true",
                    help="commit the folded series back to the "
                         "trajectory file (default: judge only)")
    ap.add_argument("--tolerance", type=float, default=None,
                    help="override the pinned regression tolerance "
                         "(fraction; persisted with --write)")
    ap.add_argument("--explain", nargs=2, metavar=("BASE", "CAND"),
                    default=None,
                    help="regression forensics (obs/explain.py): diff "
                         "two run dirs or bench artifacts into a "
                         "per-span/per-phase delta table and name the "
                         "regressed phase; exit 1 when the candidate "
                         "regressed past tolerance, 2 on malformed "
                         "input")
    args = ap.parse_args(argv)

    if args.explain is not None:
        try:
            doc = explain_mod.explain_paths(
                args.explain[0], args.explain[1],
                tolerance=(args.tolerance
                           if args.tolerance is not None
                           else trajectory.DEFAULT_TOLERANCE))
        except explain_mod.MalformedInput as e:
            print(f"[explain] ERROR: {e}", file=sys.stderr)
            return 2
        for line in explain_mod.render_text(doc):
            print(line)
        return 1 if doc["verdict"]["regressed"] else 0

    try:
        traj = trajectory.load(args.trajectory)
        if args.tolerance is not None:
            traj["tolerance"] = args.tolerance
        if args.fold is not None:
            paths = []
            for pattern in args.fold or [os.path.join(REPO,
                                                      "BENCH_r*.json")]:
                hits = sorted(glob.glob(pattern))
                if not hits and not os.path.exists(pattern):
                    print(f"[trajectory] ERROR: no artifacts match "
                          f"{pattern!r}", file=sys.stderr)
                    return 2
                paths.extend(hits or [pattern])
            points = [trajectory.parse_artifact(p) for p in paths]
            trajectory.fold(traj, points)
            print(f"[trajectory] folded {len(points)} artifact(s) "
                  f"into {len(traj['series'])} point(s)")
            if args.write:
                trajectory.save(args.trajectory, traj)
                print(f"[trajectory] written: {args.trajectory}")
    except trajectory.MalformedArtifact as e:
        print(f"[trajectory] ERROR: {e}", file=sys.stderr)
        return 2

    results, ok = trajectory.judge(traj)
    judged = [r for r in results if r.get("group")]
    for r in results:
        verdict = "PASS" if r["pass"] else "FAIL"
        value = "—" if r["value"] is None else f"{r['value']:.4f}"
        note = f"  ({r['note']})" if r.get("note") else ""
        # fleet points judge cells/hour, bank-build points clients/sec;
        # everything else rounds/sec
        group = r.get("group") or ""
        unit = ("c/h" if group.startswith("fleet")
                else "c/s" if group.startswith("bank_build")
                else "r/s")
        print(f"[trajectory] {r['label']:>8}  {value:>10} {unit}  "
              f"{verdict}{note}")
    print(f"[trajectory] {sum(r['pass'] for r in judged)}/{len(judged)} "
          f"judged point(s) pass (tolerance "
          f"{traj.get('tolerance', trajectory.DEFAULT_TOLERANCE)})")
    if not ok:
        # a FAIL should localize itself: diff the failing point against
        # its group's best earlier artifact when both are on disk
        _auto_explain(traj, results, args.trajectory)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
