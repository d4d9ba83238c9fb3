"""From a device trace to numbers: busy union, time per named scope, time
per program, collective time and what the host was doing in each idle gap.

Device planes are `/device:TPU:<n>`. Their `XLA Ops` line holds the
operations, nested where one encloses others (a `while` holds its body), so
time per scope is *self* time; `Async XLA Ops` holds the spans of
asynchronous operations (copies, collectives) from start to done. The host
plane holds the benchmark's own `bench/<span>` annotations on the same
clock. All times are nanoseconds until `summarize` turns them into
seconds."""

from __future__ import annotations

import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

from benchmark.trace import xplane

Interval = Tuple[float, float]

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE, ASYNC_LINE = "XLA Ops", "Async XLA Ops"
SPAN_PREFIX = "bench/"
# jax.named_scope names the round programs plant (fl/rounds.py)
SCOPES = ("sample_gather", "local_train", "aggregate_rlr", "health",
          "telemetry")
COLLECTIVE = re.compile(
    r"^%?(all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all)")
BETWEEN = "between-units"


def find_xplane(trace_dir: str) -> Optional[str]:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return found[-1] if found else None


def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Sorted, disjoint intervals covering the same points."""
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def length(disjoint: Sequence[Interval]) -> float:
    return sum(e - s for s, e in disjoint)


def subtract(a: Sequence[Interval], b: Sequence[Interval]) -> List[Interval]:
    """Points of disjoint sorted `a` that are in none of disjoint sorted
    `b`."""
    out, j = [], 0
    for s, e in a:
        while j < len(b) and b[j][1] <= s:
            j += 1
        k, cur = j, s
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def self_times(events: Sequence[Tuple[float, float, int]]) -> List[float]:
    """Duration of each event less its direct children's, for events that
    nest (same order as `events`, which is sorted by start, longest
    first)."""
    selfs = [d for _s, d, _m in events]
    stack: List[int] = []
    for i, (s, d, _m) in enumerate(events):
        while stack and events[stack[-1]][0] + events[stack[-1]][1] <= s:
            stack.pop()
        if stack:
            selfs[stack[-1]] -= d
        stack.append(i)
    return selfs


def scope_of(tf_op: str) -> Tuple[str, str]:
    """(program, scope) of an operation's `tf_op` path: the program is the
    path's head (`jit(step)`), the scope the first planted scope on it."""
    parts = [p for p in str(tf_op or "").rstrip(":").split("/") if p]
    program = parts[0] if parts else ""
    for p in parts[1:]:
        if p in SCOPES:
            return program, p
    return program, ""


def _short(meta: xplane.EventMeta) -> str:
    name = meta.display_name or meta.name.split(" = ")[0]
    return name.lstrip("%")


def _want(plane: str, line: str) -> bool:
    if DEVICE_PLANE.match(plane):
        return line in (OPS_LINE, ASYNC_LINE)
    return plane.startswith("/host:")


def host_spans(planes: Sequence[xplane.Plane]) -> List[Tuple[float, float, str]]:
    """The benchmark's own annotations, (start, end, name without prefix)."""
    out = []
    for p in planes:
        if not p.name.startswith("/host:"):
            continue
        for line in p.lines:
            for s, d, mid in line.events:
                meta = p.event_meta.get(mid)
                if meta is not None and meta.name.startswith(SPAN_PREFIX):
                    out.append((s, s + d, meta.name[len(SPAN_PREFIX):]))
    return sorted(out)


def attribute_gap(gap: Interval, spans: Sequence[Tuple[float, float, str]]
                  ) -> str:
    """The host span covering most of an idle gap; `between-units` where
    the host was in none of them."""
    best, best_len = BETWEEN, 0.0
    for s, e, name in spans:
        cover = min(e, gap[1]) - max(s, gap[0])
        if cover > best_len:
            best, best_len = name, cover
    return best if best_len >= 0.5 * (gap[1] - gap[0]) else BETWEEN


def reduce_device(plane: xplane.Plane,
                  spans: Sequence[Tuple[float, float, str]]) -> Dict:
    ops = next((ln.events for ln in plane.lines if ln.name == OPS_LINE), [])
    asyn = next((ln.events for ln in plane.lines if ln.name == ASYNC_LINE), [])
    ops = sorted(ops, key=lambda e: (e[0], -e[1]))
    out = {"busy_ns": 0.0, "window_ns": 0.0, "by_scope": {}, "by_program": {},
           "by_group": {}, "collective_ns": 0.0, "collective_exposed_ns": 0.0,
           "gaps": [], "n_ops": len(ops)}
    if not ops:
        return out
    busy = union((s, s + d) for s, d, _m in ops)
    start, end = busy[0][0], busy[-1][1]
    out["busy_ns"], out["window_ns"] = length(busy), end - start
    compute: List[Interval] = []
    coll: List[Interval] = []
    for (s, d, mid), own in zip(ops, self_times(ops), strict=True):
        meta = plane.event_meta.get(mid) or xplane.EventMeta()
        program, scope = scope_of(meta.stats.get("tf_op", ""))
        category = str(meta.stats.get("hlo_category") or "op")
        if COLLECTIVE.match(_short(meta)):
            coll.append((s, s + d))
        else:
            compute.append((s, s + d))
        for table, key in ((out["by_scope"], scope or "unscoped"),
                           (out["by_program"], program or "unnamed"),
                           (out["by_group"],
                            f"{scope or program or 'unnamed'}:{category}")):
            table[key] = table.get(key, 0.0) + own
    for s, d, mid in asyn:
        meta = plane.event_meta.get(mid) or xplane.EventMeta()
        if COLLECTIVE.match(_short(meta)):
            coll.append((s, s + d))
    coll_u = union(coll)
    out["collective_ns"] = length(coll_u)
    out["collective_exposed_ns"] = length(subtract(coll_u, union(compute)))
    gaps = subtract([(start, end)], busy)
    out["gaps"] = [(e - s, attribute_gap((s, e), spans)) for s, e in gaps]
    return out


def summarize(path: str) -> Optional[Dict]:
    """One trace, in seconds, averaged over its device planes; None where
    it holds no device operation."""
    planes = xplane.read(path, _want)
    spans = host_spans(planes)
    devices = [reduce_device(p, spans) for p in planes
               if DEVICE_PLANE.match(p.name)]
    devices = [d for d in devices if d["n_ops"]]
    if not devices:
        return None
    n = len(devices)

    def mean_table(key):
        table: Dict[str, float] = {}
        for d in devices:
            for k, v in d[key].items():
                table[k] = table.get(k, 0.0) + v / n / 1e9
        return table

    gaps: Dict[str, float] = {}
    for d in devices:
        for dur, name in d["gaps"]:
            gaps[name] = gaps.get(name, 0.0) + dur / n / 1e9
    return {
        "devices": n,
        "busy_s": sum(d["busy_ns"] for d in devices) / n / 1e9,
        "window_s": sum(d["window_ns"] for d in devices) / n / 1e9,
        "busy_s_per_device": [d["busy_ns"] / 1e9 for d in devices],
        "window_s_per_device": [d["window_ns"] / 1e9 for d in devices],
        "by_scope_s": mean_table("by_scope"),
        "by_program_s": mean_table("by_program"),
        "by_group_s": mean_table("by_group"),
        "collective_s": sum(d["collective_ns"] for d in devices) / n / 1e9,
        "collective_exposed_s": sum(d["collective_exposed_ns"]
                                    for d in devices) / n / 1e9,
        "idle_by_span_s": gaps,
        "longest_gaps_s": sorted(
            ((dur / 1e9, name) for d in devices for dur, name in d["gaps"]),
            reverse=True)[:10],
        "host_spans": len(spans),
    }


def top(table: Dict[str, float], n: int = 10) -> List[List]:
    return [[k, v] for k, v in sorted(table.items(),
                                      key=lambda kv: -kv[1])[:n]]
