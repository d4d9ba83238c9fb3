"""Idle device time by what the program was doing: the program's own spans
(`obs/spans.py`) enter the profiler as `TraceAnnotation`s under their own
names, so they share the device's clock. `reduce.py` puts every idle gap
down to one of the harness's four `bench/` spans; this puts it down to the
innermost span of the program that covers most of it, and says how much of
the idle time lies under no leaf span, which is host time nobody has named.

The harness keeps the profile at `<dirname(cfg.log_dir)>/trace` until the
per-layer readers have run (`harness.run_cell` removes its temporary
directory last), so a reader can open the `.xplane.pb` again. Times are
nanoseconds until `idle_by_program_span` turns them into seconds."""

from __future__ import annotations

from typing import Dict, Iterable, List, Optional, Sequence, Tuple

from benchmark.trace import reduce, xplane

NONE = "none"
# (start, end, name, has no program span nested in it)
ProgramSpan = Tuple[float, float, str, bool]


def _want(plane: str, line: str) -> bool:
    if reduce.DEVICE_PLANE.match(plane):
        return line == reduce.OPS_LINE
    return plane.startswith("/host:")


def program_spans(planes: Sequence[xplane.Plane], names: Iterable[str]
                  ) -> List[ProgramSpan]:
    """The host-plane annotations whose names the tracer recorded. Spans
    nest per thread, and a thread is a line: a span is a leaf where no other
    of them lies inside it on its line."""
    names = set(names)
    out: List[ProgramSpan] = []
    for p in planes:
        if not p.name.startswith("/host:"):
            continue
        for line in p.lines:
            own = []
            for s, d, mid in line.events:
                meta = p.event_meta.get(mid)
                if meta is not None and meta.name in names:
                    own.append((s, s + d, meta.name))
            own.sort(key=lambda e: (e[0], -e[1]))
            leaf = [True] * len(own)
            stack: List[int] = []
            for i, (s, _e, _n) in enumerate(own):
                while stack and own[stack[-1]][1] <= s:
                    stack.pop()
                if stack:
                    leaf[stack[-1]] = False
                stack.append(i)
            out.extend((s, e, n, leaf[i]) for i, (s, e, n) in enumerate(own))
    return sorted(out)


def attribute_gap(gap: reduce.Interval, spans: Sequence[ProgramSpan]
                  ) -> Tuple[str, bool]:
    """(name, leaf?) of the innermost span that covers at least half of the
    gap: of those that do, the shortest. (`none`, False) where none does."""
    need = 0.5 * (gap[1] - gap[0])
    best: Optional[ProgramSpan] = None
    for span in spans:
        s, e = span[0], span[1]
        if min(e, gap[1]) - max(s, gap[0]) >= need and (
                best is None or e - s < best[1] - best[0]):
            best = span
    return (NONE, False) if best is None else (best[2], best[3])


def device_gaps(plane: xplane.Plane) -> Optional[List[reduce.Interval]]:
    """The idle gaps of one device between its first and its last
    operation, as `reduce.reduce_device` cuts them; None where it ran no
    operation."""
    ops = next((ln.events for ln in plane.lines
                if ln.name == reduce.OPS_LINE), [])
    if not ops:
        return None
    busy = reduce.union((s, s + d) for s, d, _m in ops)
    return reduce.subtract([(busy[0][0], busy[-1][1])], busy)


def idle_by_program_span(path: str, names: Iterable[str]) -> Optional[Dict]:
    """Idle seconds by program span, mean over the devices; None where the
    trace holds no device operation."""
    planes = xplane.read(path, _want)
    spans = program_spans(planes, names)
    devices = [gaps for gaps in (
        device_gaps(p) for p in planes if reduce.DEVICE_PLANE.match(p.name))
        if gaps is not None]
    if not devices:
        return None
    n = len(devices)
    by_span: Dict[str, float] = {}
    idle = outside = 0.0
    for gaps in devices:
        for gap in gaps:
            name, leaf = attribute_gap(gap, spans)
            dur = (gap[1] - gap[0]) / n / 1e9
            by_span[name] = by_span.get(name, 0.0) + dur
            idle += dur
            if not leaf:
                outside += dur
    return {"devices": n, "program_spans": len(spans), "idle_s": idle,
            "outside_leaves_s": outside, "by_span_s": by_span}
