"""Zero-dependency host-side span tracer.

A `SpanTracer` wraps each phase of the driver in a
`with tracer.span("round/dispatch"):` block. Every closed span is kept in
memory as a `Span` record:

- `id`, and `parent`: the id of the span open around it on the same
  thread, or, for work handed to another thread, the span that enqueued
  it (`handoff()` on the enqueuing thread, passed along with the work and
  given back as `span(..., parent=...)`);
- `unit`: one identifier shared by every span of a dispatch unit (the
  engine calls `set_unit(<the unit's last round>)`; `setup` before the
  first unit);
- `start` and `end` on the tracer's clock (`time.perf_counter` unless one
  is injected: absolute, so a reader can cut the records by its own
  stamps on that clock), `cpu_s` (the thread's own CPU seconds,
  `time.thread_time`) and `self_s` (duration less what the child spans on
  the same thread cover).

`count(name, n, **labels)` keeps counters beside the spans. An enabled
tracer can also listen to `jax.monitoring` (`watch_compiles()`): each
program the backend compiles or fetches from the persistent cache becomes
a span `xla/acquire` under the span open on that thread, and a count
`programs{family, source}`.

From the records come

- a Chrome-trace / Perfetto `trace.json` (`write_trace`; the new fields
  ride each event's `args`),
- per-span aggregates (count, total, p50/p95/max, self and CPU
  milliseconds) and the counters, for metrics.jsonl (`Spans/*`) and the
  bench JSON,
- a dispatch unit's milliseconds by span name, which the flight recorder
  takes at the unit's end (`unit_ms`),
- matching `jax.profiler.TraceAnnotation` annotations, so a device trace
  captured meanwhile holds the same names on the device's clock.

The module keeps the tracer of the newest `RoundEngine` (`current()`,
`None` under `--no_spans`); module-level `span()` / `count()` go to it and
are no-ops without one, so code that is handed no tracer (data/registry.py,
utils/compile_cache.py, the heartbeat's and the flight recorder's writes)
can still say where its time goes.

Thread-safe: spans may open and close on the metrics-drain and prefetch
threads beside the round loop's; nesting is tracked per thread. A disabled
tracer's `span()` is a no-op context manager (one attribute check, no
locks), so the tracer can be threaded unconditionally.
"""

from __future__ import annotations

import itertools
import json
import os
import threading
import time
from contextlib import contextmanager
from typing import Any, Dict, List, NamedTuple, Optional, Tuple

# growth bound: a multi-day run must not accumulate records without limit.
# Past the cap, records are dropped (counted) but aggregates keep updating —
# percentile summaries stay honest while the trace covers the run's head.
MAX_EVENTS = 200_000
# per-name duration reservoir for the percentile aggregates; past the cap
# new durations still update count/total/max but stop entering the sample
MAX_DURATIONS_PER_NAME = 50_000
SETUP_UNIT = "setup"
# spans of the observers' own I/O: recording one fires no completion hook
# (the heartbeat's hook writes the heartbeat: no write caused by recording
# a write)
OBS_PREFIX = "obs/"
ACQUIRE_SPAN = "xla/acquire"
ADOPT_PREFIX = "setup/acquire/"
PROGRAMS_COUNTER = "programs"
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"
SPAN_STATS = ("count", "total_s", "p50_ms", "p95_ms", "max_ms", "self_ms",
              "cpu_ms")


class Span(NamedTuple):
    id: int
    parent: Optional[int]
    unit: Any
    name: str
    start: float
    end: float
    cpu_s: float
    self_s: float
    tid: int
    args: Dict[str, Any]


class _Open:
    """A span that is open on this thread. `acquired` is what the compile
    listener saw inside it: (program, source, seconds)."""
    __slots__ = ("id", "name", "unit", "child_s", "acquired")

    def __init__(self, sid: int, name: str, unit):
        self.id, self.name, self.unit = sid, name, unit
        self.child_s = 0.0
        self.acquired: List[Tuple[str, str, float]] = []


def _tid() -> int:
    return threading.get_ident() & 0x7FFFFFFF


def _percentile(sorted_vals: List[float], q: float) -> float:
    """Nearest-rank percentile of an ascending-sorted non-empty list."""
    idx = min(len(sorted_vals) - 1, max(0, int(q * len(sorted_vals))))
    return sorted_vals[idx]


def counter_key(name: str, labels: Dict[str, Any]) -> str:
    """`programs{family=round,source=compiled}`: a counter's row name."""
    if not labels:
        return name
    inner = ",".join(f"{k}={labels[k]}" for k in sorted(labels))
    return f"{name}{{{inner}}}"


class SpanTracer:
    def __init__(self, enabled: bool = True, clock=time.perf_counter,
                 annotate: bool = True, on_end=None,
                 cpu_clock=time.thread_time):
        """`clock` and `cpu_clock` are injectable for exactness tests;
        `annotate` wires the matching `jax.profiler.TraceAnnotation`
        (skipped when jax is unavailable — the tracer itself is zero-dep);
        `on_end(name, dur_s)` is an optional completion hook (the
        heartbeat's last-span field), not fired for `obs/*` spans."""
        self.enabled = enabled
        self._clock = clock
        self._cpu_clock = cpu_clock
        self._on_end = on_end
        self._lock = threading.Lock()
        self._spans: List[Span] = []
        self._dropped = 0
        self._ids = itertools.count(1)   # next() is atomic in CPython
        self._unit: Any = SETUP_UNIT
        self._durations: Dict[str, List[float]] = {}
        # name -> [count, total, max, self, cpu] seconds
        self._totals: Dict[str, List[float]] = {}
        self._counts: Dict[str, float] = {}
        self._count_log: List[Tuple[float, str, float, Dict[str, Any]]] = []
        # milliseconds by span name since the flight recorder last took
        # them (one entry per name: bounded whether or not anyone takes)
        self._pending_ms: Dict[str, float] = {}
        self._local = threading.local()
        self._t0 = clock()
        self._watching = False
        self._annotation = None
        if annotate:
            try:
                import jax.profiler
                self._annotation = jax.profiler.TraceAnnotation
            except Exception:
                self._annotation = None

    # --- recording -------------------------------------------------------
    def _top(self) -> Optional[_Open]:
        stack = getattr(self._local, "stack", None)
        return stack[-1] if stack else None

    def set_unit(self, unit) -> None:
        """Spans that open from now on, outside any handed-over parent,
        belong to this dispatch unit."""
        if self.enabled:
            with self._lock:
                self._unit = unit

    def handoff(self) -> Optional[Tuple[int, Any]]:
        """(id, unit) of the span open on this thread, to pass along with
        work that another thread will do: `span(name, parent=<this>)`
        there names the span that enqueued it."""
        top = self._top() if self.enabled else None
        return None if top is None else (top.id, top.unit)

    @contextmanager
    def span(self, name: str, parent: Optional[Tuple[int, Any]] = None,
             **args):
        if not self.enabled:
            yield None
            return
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._local.stack = []
        above = stack[-1] if stack else None
        if parent is not None:
            parent_id, unit = parent
        elif above is not None:
            parent_id, unit = above.id, above.unit
        else:
            parent_id, unit = None, self._unit
        sid = next(self._ids)
        me = _Open(sid, name, unit)
        stack.append(me)
        annotation = self._annotation(name) if self._annotation else None
        if annotation is not None:
            annotation.__enter__()
        cpu0 = self._cpu_clock()
        start = self._clock()
        try:
            yield me
        finally:
            end = self._clock()
            cpu_s = self._cpu_clock() - cpu0
            if annotation is not None:
                annotation.__exit__(None, None, None)
            stack.pop()
            dur = end - start
            if above is not None:
                above.child_s += dur
            self._record(Span(sid, parent_id, unit, name, start, end, cpu_s,
                              dur - me.child_s, _tid(), args))
            if self._on_end is not None and not name.startswith(OBS_PREFIX):
                try:
                    self._on_end(name, dur)
                except Exception:
                    pass  # observability must never take down the run

    def _record(self, s: Span) -> None:
        dur = s.end - s.start
        with self._lock:
            agg = self._totals.setdefault(s.name, [0, 0.0, 0.0, 0.0, 0.0])
            agg[0] += 1
            agg[1] += dur
            agg[2] = max(agg[2], dur)
            agg[3] += s.self_s
            agg[4] += s.cpu_s
            sample = self._durations.setdefault(s.name, [])
            if len(sample) < MAX_DURATIONS_PER_NAME:
                sample.append(dur)
            self._pending_ms[s.name] = (self._pending_ms.get(s.name, 0.0)
                                        + dur * 1e3)
            if len(self._spans) >= MAX_EVENTS:
                self._dropped += 1
                return
            self._spans.append(s)

    def count(self, name: str, n: float = 1, **labels) -> None:
        """Add `n` to the counter `name{labels}`."""
        if not self.enabled:
            return
        key, now = counter_key(name, labels), self._clock()
        with self._lock:
            self._counts[key] = self._counts.get(key, 0) + n
            if len(self._count_log) < MAX_EVENTS:
                self._count_log.append((now, name, n, labels))

    # --- programs the backend acquires -----------------------------------
    def watch_compiles(self) -> None:
        """Listen to `jax.monitoring`: every program the backend compiles,
        or fetches from the persistent cache, on any thread and at any time
        of the run, becomes a span `xla/acquire` (with the seconds JAX
        reports) under the span open on that thread. Outside a
        `setup/acquire/<family>` span, which counts its own family, it
        also counts `programs{family=<program>, source}`. `close()` removes
        the listener."""
        if not self.enabled or self._watching:
            return
        import jax.monitoring
        jax.monitoring.register_event_duration_secs_listener(
            self._on_jax_event)
        with self._lock:
            self._watching = True

    def _on_jax_event(self, event: str, secs: float, **kw) -> None:
        if event == _CACHE_HIT_EVENT:
            # fired inside the acquisition, just before its own event
            self._local.cache_hit = True
            return
        if event != _COMPILE_EVENT:
            return
        source = ("xla_cache_hit" if getattr(self._local, "cache_hit", False)
                  else "compiled")
        self._local.cache_hit = False
        program = str(kw.get("fun_name", ""))
        above = self._top()
        end = self._clock()
        # the compiler's own threads did the work: no CPU time of this one
        self._record(Span(
            next(self._ids), None if above is None else above.id,
            self._unit if above is None else above.unit, ACQUIRE_SPAN,
            end - secs, end, 0.0, secs, _tid(),
            {"program": program, "source": source}))
        if above is not None:
            above.child_s += secs
            above.acquired.append((program, source, secs))
        if above is None or not above.name.startswith(ADOPT_PREFIX):
            self.count(PROGRAMS_COUNTER, family=program, source=source)

    def close(self) -> None:
        """Stop listening for compilations; the records stay readable."""
        with self._lock:
            watching, self._watching = self._watching, False
        if watching:
            import jax.monitoring
            jax.monitoring.unregister_event_duration_listener(
                self._on_jax_event)

    # --- reporting -------------------------------------------------------
    def records(self) -> List[Span]:
        """Every span closed so far, in closing order (a copy)."""
        with self._lock:
            return list(self._spans)

    def counted(self, before: Optional[float] = None
                ) -> List[Tuple[str, float, Dict[str, Any]]]:
        """(name, n, labels) of every `count` call made before the clock
        read `before` (all of them without it)."""
        with self._lock:
            return [(name, n, labels) for t, name, n, labels
                    in self._count_log if before is None or t < before]

    def unit_ms(self, take: bool = True) -> Dict[str, float]:
        """Milliseconds by span name of the spans closed since the last
        take: the unit that is ending, and what the drain thread closed
        late for the units before it."""
        with self._lock:
            out = self._pending_ms
            if take:
                self._pending_ms = {}
            return {name: round(ms, 3) for name, ms in out.items()}

    def aggregates(self) -> Dict[str, Dict[str, float]]:
        """{name: {count, total_s, p50_ms, p95_ms, max_ms, self_ms,
        cpu_ms}} per span type, and {`name{labels}`: {count}} per
        counter."""
        with self._lock:
            out = {}
            for name, (count, total, mx, own, cpu) in sorted(
                    self._totals.items()):
                sample = sorted(self._durations.get(name, ()))
                out[name] = {
                    "count": count,
                    "total_s": round(total, 4),
                    "p50_ms": round(_percentile(sample, 0.50) * 1e3, 3)
                    if sample else 0.0,
                    "p95_ms": round(_percentile(sample, 0.95) * 1e3, 3)
                    if sample else 0.0,
                    "max_ms": round(mx * 1e3, 3),
                    "self_ms": round(own * 1e3, 3),
                    "cpu_ms": round(cpu * 1e3, 3),
                }
            for key, n in sorted(self._counts.items()):
                out[key] = {"count": n}
            return out

    def write_trace(self, path: str) -> Optional[str]:
        """Write the Chrome-trace JSON (atomic: tmp + rename). Returns the
        path, or None when disabled / nothing recorded."""
        if not self.enabled:
            return None
        with self._lock:
            spans = list(self._spans)
            dropped = self._dropped
        if not spans:
            return None
        pid = os.getpid()
        events = [{"name": s.name, "ph": "X", "cat": "host",
                   "ts": round((s.start - self._t0) * 1e6, 3),
                   "dur": round((s.end - s.start) * 1e6, 3),
                   "pid": pid, "tid": s.tid,
                   "args": {"id": s.id, "parent": s.parent, "unit": s.unit,
                            "cpu_ms": round(s.cpu_s * 1e3, 3),
                            "self_ms": round(s.self_s * 1e3, 3), **s.args}}
                  for s in spans]
        doc = {"traceEvents": events, "displayTimeUnit": "ms",
               "otherData": {"tracer": "rlr_fl.obs.spans",
                             "dropped_events": dropped}}
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        tmp = f"{path}.tmp.{os.getpid()}"
        with open(tmp, "w") as f:
            json.dump(doc, f)
        os.replace(tmp, path)
        return path

    def scalar_rows(self):
        """Flat (tag, value) rows for metrics.jsonl: Spans/<name>/<stat>."""
        rows = []
        for name, agg in self.aggregates().items():
            for stat in SPAN_STATS:
                if stat in agg:
                    rows.append((f"Spans/{name}/{stat}", float(agg[stat])))
        return rows


# --- the process-wide tracer ---------------------------------------------

_current: Optional[SpanTracer] = None


def set_current(tracer: Optional[SpanTracer]) -> None:
    """Make `tracer` the one `current()` returns (None where it is
    disabled). `RoundEngine.__init__` calls this, from the constructing
    thread only; with two engines alive the newest has it."""
    global _current
    _current = tracer if tracer is not None and tracer.enabled else None


def current() -> Optional[SpanTracer]:
    """The tracer of the newest `RoundEngine`; None before the first one
    and under `--no_spans`."""
    return _current


@contextmanager
def _no_span():
    yield None


def span(name: str, **args):
    """`current().span(...)`, or a no-op without a tracer."""
    tracer = _current
    return _no_span() if tracer is None else tracer.span(name, **args)


def count(name: str, n: float = 1, **labels) -> None:
    """`current().count(...)`, or nothing without a tracer."""
    tracer = _current
    if tracer is not None:
        tracer.count(name, n, **labels)
