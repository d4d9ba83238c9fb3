"""Incident flight recorder: a bounded per-round ring of flight data,
streamed crash-exactly and snapshotted atomically on any incident.

The fleet plane can already *detect* that something went wrong (health
ladder rungs, supervisor degradation, the trajectory gate) — but by the
time anyone looks, the rounds AROUND the incident are gone. This module
keeps them:

- ``FlightRecorder`` records one compact record per dispatch unit —
  span durations, the dispatch gap, metrics-drain depth, the async
  buffer fill and HBM watermarks when the boundaries produced them —
  into an in-memory ring (default ``DEFAULT_WINDOW`` rounds) AND an
  append-only ``flight.jsonl`` stream next to ``metrics.jsonl``;
- ``snapshot(reason, round)`` atomically rewrites ``flight.json``
  (tmp + ``os.replace``, the heartbeat idiom) with the ring's contents
  — the service driver calls it on every warn/error ledger record
  (health rungs, supervisor retries/give-ups, chaos injections, eval/
  drain degradation) and on clean exit, so the LAST snapshot is always
  the evidence closest to the last incident.

**Crash-exact semantics**, mirroring ``obs/events.EventLedger``:

- torn-tail truncation: a SIGKILL mid-write leaves at most one partial
  line; opening the stream truncates back to the last complete record;
- resumed ``seq`` numbering and a round high-water mark: a crash-exact
  resume (or an in-process recovery re-entry) that replays rounds at or
  below the mark appends nothing — the ring still folds the replayed
  record in, so a post-resume snapshot shows fresh data;
- the correlation id (``obs/events.corr_id``) threads every segment of
  one logical run, exactly like the event ledger.

Together these make a ``kill_recover@N`` drill's flight stream
byte-identical to its unkilled twin's under ``strip_timing`` — the
non-timing projection (``seq``/``round``/``corr``/``slot``/unit size)
is deterministic; durations, gaps, drain depth and memory are honest
wall-clock/machine facts and are named in ``TIMING_FIELDS`` /
``VOLATILE_FIELDS`` for the comparisons that must exclude them.

Like every obs component: IO failure disables the recorder, never the
run. Stdlib-only — the console and offline forensics import this on
machines without jax.
"""

from __future__ import annotations

import collections
import json
import os
import threading
import time
from typing import Any, Callable, Dict, List, Optional

from defending_against_backdoors_with_robust_learning_rate_tpu.obs import (
    spans as obs_spans)

DEFAULT_WINDOW = 64
STREAM_NAME = "flight.jsonl"
SNAPSHOT_NAME = "flight.json"

# wall-clock / duration facts: differ between byte-identical twins
TIMING_FIELDS = ("gap_ms", "spans", "t")
# machine-local / pipeline-state facts: deterministic within one
# process life but not across a kill-resume (a resumed drain starts
# empty, a fresh allocator has fresh watermarks)
VOLATILE_FIELDS = ("drain_depth", "buffer_fill", "hbm_live_bytes",
                   "hbm_peak_bytes")


class FlightRecorder:
    """Per-round flight data: ring buffer + crash-exact stream +
    atomic incident snapshots (module docstring).

    The hot-path cost per round is one buffered line write. A unit's
    per-span milliseconds come from ``span_source`` (the engine hands in
    ``SpanTracer.unit_ms``: the tracer already groups its spans by unit),
    plus whatever a caller without a tracer fed ``observe_span``."""

    def __init__(self, path: str, run: str = "", corr: str = "",
                 slot: str = "", window: int = DEFAULT_WINDOW,
                 clock=time.time,
                 span_source: Optional[Callable[..., Dict[str, float]]]
                 = None):
        self.path = path
        self.snapshot_path = os.path.join(
            os.path.dirname(path) or ".", SNAPSHOT_NAME)
        self.run = run
        self.corr = corr
        self.slot = slot
        self._clock = clock
        self._span_source = span_source
        self._lock = threading.Lock()
        self._ring: collections.deque = collections.deque(maxlen=window)
        self._spans: Dict[str, float] = {}
        self._notes: Dict[str, Any] = {}
        self.seq = 0
        self.hw = -1          # highest round already streamed (dedupe)
        self._t_begin: Optional[float] = None
        self._t_last_end: Optional[float] = None
        self._f = None
        self.enabled = bool(path)
        if not self.enabled:
            return
        try:
            os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
            self._recover_tail()
            self._f = open(path, "ab")
        except OSError:
            self.enabled = False

    # ------------------------------------------------------------ recovery

    def _recover_tail(self) -> None:
        """Truncate a torn tail back to the last complete, parseable
        line; resume seq numbering, rebuild the round high-water mark
        and reload the ring's tail from the surviving records (so a
        snapshot right after a resume still has a window)."""
        if not os.path.exists(self.path):
            return
        with open(self.path, "rb") as f:
            data = f.read()
        good_end = 0
        for line in data.splitlines(keepends=True):
            if not line.endswith(b"\n"):
                break   # torn tail: a kill landed mid-write
            try:
                rec = json.loads(line)
                self.seq = int(rec["seq"]) + 1
            except (ValueError, KeyError, TypeError):
                break   # corrupt line: everything after it is suspect
            rnd = rec.get("round")
            if isinstance(rnd, int):
                self.hw = max(self.hw, rnd)
            self._ring.append(rec)
            good_end += len(line)
        if good_end < len(data):
            with open(self.path, "r+b") as f:
                f.truncate(good_end)

    # ----------------------------------------------------------- recording

    def _with_source(self, own: Dict[str, float], take: bool
                     ) -> Dict[str, float]:
        """`own` (what ``observe_span`` was fed) plus the span source's
        milliseconds for the unit so far."""
        if self._span_source is not None:
            for name, ms in self._span_source(take=take).items():
                own[name] = round(own.get(name, 0.0) + ms, 3)
        return own

    def observe_span(self, name: str, dur_s: float) -> None:
        """Accumulate this round's per-span milliseconds by hand, for a
        caller with no tracer to be the ``span_source``. Thread-safe."""
        if not self.enabled:
            return
        with self._lock:
            self._spans[name] = round(
                self._spans.get(name, 0.0) + dur_s * 1e3, 3)

    def note(self, **facts) -> None:
        """Stash boundary-sourced volatile facts (async buffer fill,
        HBM watermarks) for the next record — the values were already
        materialized on the host by the boundary's own machinery, so
        recording them costs no extra device sync."""
        if not self.enabled:
            return
        with self._lock:
            for key, value in facts.items():
                if value is not None:
                    self._notes[key] = value

    def begin_unit(self) -> None:
        """Mark the start of a dispatch unit (for the dispatch-gap
        clock)."""
        if self.enabled:
            with self._lock:
                self._t_begin = time.perf_counter()

    def end_unit(self, rnd: int, unit_rounds: int = 1,
                 drain_depth: Optional[int] = None
                 ) -> Optional[Dict[str, Any]]:
        """Close the round's record: fold it into the ring and append
        it to the stream — unless ``rnd`` is at or below the high-water
        mark (a crash-exact replay / recovery re-dispatch), where the
        ring is refreshed but nothing is written, so interrupted and
        uninterrupted twins leave byte-identical streams."""
        if not self.enabled:
            return None
        now = time.perf_counter()
        # one critical section end to end: the drain thread's
        # observe_span must never interleave with the seq/hw/stream
        # mutation (the torn-tail bug class this recorder exists to
        # catch must not live in the recorder itself)
        with self._lock:
            own, self._spans = self._spans, {}
            spans = self._with_source(own, take=True)
            notes, self._notes = self._notes, {}
            gap_ms = (round((self._t_begin - self._t_last_end) * 1e3, 3)
                      if self._t_begin is not None
                      and self._t_last_end is not None else None)
            self._t_last_end = now
            replay = rnd <= self.hw
            # fixed field order: the non-timing head first, then the
            # timing/volatile tail, then the wall stamp — the
            # strip_timing projection of identical round sequences is
            # byte-identical
            rec: Dict[str, Any] = {
                "seq": self.seq, "v": 1, "round": rnd, "corr": self.corr,
                "slot": self.slot, "rounds": unit_rounds,
                "gap_ms": gap_ms, "spans": spans,
                "drain_depth": drain_depth,
                "buffer_fill": notes.get("buffer_fill"),
                "hbm_live_bytes": notes.get("hbm_live_bytes"),
                "hbm_peak_bytes": notes.get("hbm_peak_bytes"),
                "t": self._clock(),
            }
            if replay:
                # refresh the ring's view of the replayed round (the
                # fresh record carries this life's real timings) without
                # touching the stream — and without consuming a seq
                rec["seq"] = next(
                    (r["seq"] for r in self._ring
                     if r.get("round") == rnd),
                    self.seq)
                kept = [r for r in self._ring if r.get("round") != rnd]
                self._ring.clear()
                self._ring.extend(kept)
                self._ring.append(rec)
                return None
            if self._f is not None:
                try:
                    with obs_spans.span("obs/flight_write"):
                        self._f.write((json.dumps(rec) + "\n").encode())
                        self._f.flush()
                except (OSError, ValueError):
                    # observability never downs the run
                    self.enabled = False
                    return None
            self.seq += 1
            self.hw = rnd
            self._ring.append(rec)
        return rec

    # ----------------------------------------------------------- snapshots

    def window(self) -> List[Dict[str, Any]]:
        """The ring's current contents, oldest first (a copy)."""
        with self._lock:
            return list(self._ring)

    def snapshot(self, reason: str, rnd: Optional[int] = None,
                 **extra) -> Optional[str]:
        """Atomically rewrite ``flight.json`` with the ring (latest
        incident wins). Works after ``close()`` — the ring outlives the
        stream handle, so the driver can snapshot a recovery re-entry
        after the engine was torn down. Never raises."""
        if not self.path:
            return None
        with self._lock:
            win = list(self._ring)
            current = self._with_source(dict(self._spans), take=False)
        doc: Dict[str, Any] = {
            "v": 1, "run": self.run, "corr": self.corr,
            "slot": self.slot, "reason": reason, "round": rnd,
            "window_rounds": len(win), "t": self._clock(),
        }
        for key in sorted(extra):
            doc[key] = extra[key]
        if current:
            doc["current_spans"] = current
        doc["window"] = win
        tmp = f"{self.snapshot_path}.tmp.{os.getpid()}"
        try:
            with open(tmp, "w", encoding="utf-8") as f:
                json.dump(doc, f)
                f.write("\n")
            os.replace(tmp, self.snapshot_path)
        except OSError:
            return None
        return self.snapshot_path

    def close(self) -> None:
        """Close the stream handle; the ring (and ``snapshot``) stay
        usable — the driver snapshots the recovery re-entry AFTER the
        engine teardown closed the stream."""
        with self._lock:
            if self._f is not None:
                try:
                    self._f.close()
                except OSError:
                    pass
                self._f = None


# --------------------------------------------------------------------------
# readers (tests, CI drills, offline forensics)
# --------------------------------------------------------------------------

def read_flight(path: str) -> List[Dict[str, Any]]:
    """Parse a flight stream; unparseable/torn lines terminate the read
    (they are what a fresh writer would truncate)."""
    out: List[Dict[str, Any]] = []
    try:
        with open(path, encoding="utf-8") as f:
            for line in f:
                try:
                    out.append(json.loads(line))
                except ValueError:
                    break
    except OSError:
        return []
    return out


def strip_timing(records: List[Dict[str, Any]],
                 drop_volatile: bool = True) -> List[Dict[str, Any]]:
    """The byte-comparison view: records minus the wall-clock/duration
    fields (and, by default, the machine-local volatile ones) — what a
    ``kill_recover@N`` drill's stream shares with its unkilled twin."""
    drop = set(TIMING_FIELDS) | (set(VOLATILE_FIELDS)
                                 if drop_volatile else set())
    return [{k: v for k, v in rec.items() if k not in drop}
            for rec in records]


def read_snapshot(path: str) -> Optional[Dict[str, Any]]:
    """The last incident snapshot, or None when absent/unreadable."""
    try:
        with open(path, encoding="utf-8") as f:
            return json.load(f)
    except (OSError, ValueError):
        return None
