"""What the per-layer readers take from the program's own tracer
(`obs/spans.py`): the spans `RoundEngine` and the modules under it record
inside themselves, with their parents, units and CPU time, and its
counters. The readers run after `eng.close()` and `ctx` holds no engine, so
the tracer comes from `spans.current()`: the newest engine's, `None` under
`--no_spans`, and absent altogether in a program older than the accessor.
Every function here returns `None` (or an empty table) where there is
nothing to read, and never raises for that.

The tracer's clock is `time.perf_counter`, the clock of the harness's own
spans (`ctx["spans"].closed`), so the records are cut by the harness's
phases: `setup` ends, and `window` begins, where the first span of the
window phase starts."""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple

ADOPT = "setup/acquire/"
ACQUIRE = "xla/acquire"
BUILD = "engine/build"
# the spans of a unit that enter the runtime: their wall time is the
# device's wherever the runtime makes the host wait, their CPU time is not
DISPATCH_LEAVES = ("round/dispatch",)
EVAL_LEAVES = ("eval/finite_dispatch", "eval/val_dispatch",
               "eval/poison_dispatch")


def tracer():
    try:
        from defending_against_backdoors_with_robust_learning_rate_tpu.obs import (
            spans)
    except ImportError:
        return None
    current = getattr(spans, "current", None)
    tr = current() if current is not None else None
    return tr if tr is not None and hasattr(tr, "records") else None


def phase_bounds(ctx, phase: str) -> Optional[Tuple[float, float]]:
    """(first start, last end) of the harness's spans of one phase."""
    own = [(s, e) for _n, s, e, p, _c in ctx["spans"].closed if p == phase]
    if not own:
        return None
    return min(s for s, _e in own), max(e for _s, e in own)


def seconds(span) -> float:
    return span.end - span.start


def before_window(ctx, tr) -> Optional[Tuple[List, float]]:
    """(the records that ended before the window's first stamp, the
    stamp)."""
    bounds = phase_bounds(ctx, "window")
    if bounds is None:
        return None
    return [s for s in tr.records() if s.end <= bounds[0]], bounds[0]


def acquisitions(records) -> List:
    """Every span in which a program was acquired, each program once: the
    `setup/acquire/<family>` spans, and the `xla/acquire` spans outside
    them (a bank miss compiles inside its family's span)."""
    by_id = {s.id: s for s in records}
    out = []
    for s in records:
        if s.name.startswith(ADOPT):
            out.append(s)
        elif s.name == ACQUIRE:
            up = by_id.get(s.parent)
            if up is None or not up.name.startswith(ADOPT):
                out.append(s)
    return out


def window_units(ctx, tr) -> Dict[Any, List]:
    """The window's records by unit: the units whose `engine/dispatch`
    began inside the window phase."""
    bounds = phase_bounds(ctx, "window")
    if bounds is None:
        return {}
    records = tr.records()
    units = {s.unit for s in records if s.name == "engine/dispatch"
             and bounds[0] <= s.start <= bounds[1]}
    out: Dict[Any, List] = {u: [] for u in units}
    for s in records:
        if s.unit in out:
            out[s.unit].append(s)
    return out


def host_ms(spans: List, outer: Tuple[str, ...], leaves: Tuple[str, ...]
            ) -> Dict[str, float]:
    """Of one unit: wall milliseconds of the `outer` spans, of the `leaves`
    inside them, the leaves' CPU milliseconds, and the sum the metric is:
    wall outside the leaves plus the leaves' CPU."""
    wall = 1e3 * sum(seconds(s) for s in spans if s.name in outer)
    leaf = 1e3 * sum(seconds(s) for s in spans if s.name in leaves)
    leaf_cpu = 1e3 * sum(s.cpu_s for s in spans if s.name in leaves)
    cpu = 1e3 * sum(s.cpu_s for s in spans if s.name in outer)
    return {"wall_ms": wall, "leaf_wall_ms": leaf, "leaf_cpu_ms": leaf_cpu,
            "cpu_ms": cpu, "host_ms": wall - leaf + leaf_cpu}


def mean_table(rows: List[Dict[str, float]]) -> Dict[str, float]:
    return ({k: sum(r[k] for r in rows) / len(rows) for k in rows[0]}
            if rows else {})


def setup_table(ctx, tr) -> Optional[Dict[str, Any]]:
    """Set-up as the program saw it: every child of `engine/build` with its
    seconds, the share of the build they cover, the parts of `setup/data`,
    the byte counters, per family its source, seconds and dispatch count,
    and the programs acquired outside `adopt` with the span they were
    acquired under."""
    cut = before_window(ctx, tr)
    if cut is None:
        return None
    records, first = cut
    by_id = {s.id: s for s in records}
    build = next((s for s in records if s.name == BUILD), None)
    if build is None:
        return None
    children: Dict[str, float] = {}
    data_parts: Dict[str, float] = {}
    for s in records:
        up = by_id.get(s.parent)
        if up is build:
            children[s.name] = children.get(s.name, 0.0) + seconds(s)
        elif up is not None and up.name == "setup/data":
            data_parts[s.name] = data_parts.get(s.name, 0.0) + seconds(s)
    counts = tr.counted()
    sources = {lab.get("family"): lab.get("source")
               for name, _n, lab in counts if name == "programs"}
    dispatched: Dict[str, float] = {}
    for name, n, lab in counts:
        if name == "dispatch":
            fam = lab.get("family")
            dispatched[fam] = dispatched.get(fam, 0) + n
    families, outside = [], {}
    for s in acquisitions(records):
        if s.name.startswith(ADOPT):
            fam = s.name[len(ADOPT):]
            families.append({"family": fam, "source": sources.get(fam),
                             "seconds": seconds(s),
                             "dispatched": dispatched.get(fam, 0)})
        else:
            up = by_id.get(s.parent)
            key = (s.args.get("program"), s.args.get("source"),
                   up.name if up else None, s.unit)
            n, secs = outside.get(key, (0, 0.0))
            outside[key] = (n + 1, secs + seconds(s))
    loaded = sorted(outside.items(), key=lambda kv: -kv[1][1])
    return {
        "engine_build_s": seconds(build),
        "children_s": children,
        "covered_pct": 100.0 * sum(children.values()) / seconds(build),
        "setup_data_parts_s": data_parts,
        "bytes": {name: sum(n for other, n, _lab in counts if other == name)
                  for name in ("data_bytes_host", "data_bytes_placed")},
        "families": families,
        "dispatched": dispatched,
        "acquired_outside_adopt": [
            {"program": k[0], "source": k[1], "under": k[2], "unit": k[3],
             "n": n, "seconds": secs} for k, (n, secs) in loaded[:16]],
        "acquired_outside_adopt_rest_s": sum(
            secs for _k, (_n, secs) in loaded[16:]),
    }
