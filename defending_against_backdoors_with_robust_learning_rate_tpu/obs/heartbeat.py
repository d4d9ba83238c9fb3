"""Structured run heartbeat: an atomically-rewritten ``status.json``.

Liveness inferred from stderr byte growth or from whether
`jax.devices()` answers confuses "quiet but computing" with "hung". The
heartbeat replaces the guesswork with structure: the driver (and
bench.py) rewrite one small JSON file —

    {"phase": "train", "round": 120, "rounds": 200,
     "last_span": "round/dispatch", "compile_in_flight": false,
     "pid": 4242, "started_at": ..., "updated_at": ...}

— via write-to-tmp + ``os.replace``, so a reader NEVER observes a partial
file. ``compile_in_flight`` is the flag the stall detectors need most:
a first-time compile is minutes of legitimate silence, and the heartbeat
says exactly when that is.

Writes are rate-limited (default: one per second) except on phase
changes, so per-round updates cost nothing measurable at hundreds of
rounds/sec. Consumption: ``read_status`` + ``is_stale`` here (the
service supervisor and the fleet console, obs/console.py).
"""

from __future__ import annotations

import json
import os
import time
from typing import Any, Dict, Optional

from defending_against_backdoors_with_robust_learning_rate_tpu.obs import (
    spans)

DEFAULT_MIN_INTERVAL_S = 1.0
# a heartbeat older than this is stale — unless a compile is in flight,
# which legitimately produces no updates for minutes (stall detectors must
# use the larger compile budget then; see is_stale)
DEFAULT_STALE_S = 300.0
DEFAULT_COMPILE_STALE_S = 3600.0


class Heartbeat:
    def __init__(self, path: str, enabled: bool = True,
                 min_interval_s: float = DEFAULT_MIN_INTERVAL_S,
                 clock=time.time):
        self.path = path
        self.enabled = enabled and bool(path)
        self._clock = clock
        self._min_interval = min_interval_s
        self._last_write = 0.0
        self._state: Dict[str, Any] = {
            "phase": "starting", "round": 0, "rounds": 0,
            "last_span": "", "compile_in_flight": False,
            "pid": os.getpid(), "started_at": clock(), "updated_at": 0.0,
        }
        if self.enabled:
            try:
                os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
            except OSError:
                # same contract as _write: observability must never take
                # down the run (read-only log dir on a borrowed machine)
                self.enabled = False
                return
            self._write()

    def update(self, phase: Optional[str] = None, force: bool = False,
               **fields) -> None:
        """Merge fields and rewrite the file. Rate-limited; a phase change
        or `force` always writes (phase is what the detectors key on)."""
        if not self.enabled:
            return
        changed_phase = phase is not None and phase != self._state["phase"]
        if phase is not None:
            self._state["phase"] = phase
        self._state.update(fields)
        now = self._clock()
        if (force or changed_phase
                or now - self._last_write >= self._min_interval):
            self._write(now)

    def span_hook(self, name: str, dur_s: float) -> None:
        """SpanTracer on_end hook: records the last completed span (rides
        the normal rate limit — span churn must not turn into fsync churn)."""
        self.update(last_span=name)

    def close(self, phase: str = "exited") -> None:
        if self.enabled:
            self.update(phase=phase, force=True)

    def _write(self, now: Optional[float] = None) -> None:
        now = self._clock() if now is None else now
        self._state["updated_at"] = now
        tmp = f"{self.path}.tmp.{os.getpid()}"
        try:
            with spans.span("obs/heartbeat_write"):
                with open(tmp, "w") as f:
                    json.dump(self._state, f)
                os.replace(tmp, self.path)
            self._last_write = now
        except OSError:
            # observability must never take down the run (e.g. read-only
            # log dir on a borrowed machine): disable after first failure
            self.enabled = False


class NullHeartbeat:
    """No-op stand-in (non-lead processes of a multi-host job)."""

    def update(self, phase=None, force=False, **fields) -> None:
        pass

    def span_hook(self, name, dur_s) -> None:
        pass

    def close(self, phase="exited") -> None:
        pass


def read_status(path: str) -> Optional[Dict[str, Any]]:
    """Parse status.json; None when absent or (transiently) unreadable."""
    try:
        with open(path) as f:
            return json.load(f)
    except (OSError, ValueError):
        return None


def is_stale(status: Optional[Dict[str, Any]], now: Optional[float] = None,
             stale_s: float = DEFAULT_STALE_S,
             compile_stale_s: float = DEFAULT_COMPILE_STALE_S) -> bool:
    """Stall verdict for a status record: no heartbeat within the budget.
    A compile-in-flight record gets the (much larger) compile budget —
    a cold compile is legitimately silent for minutes, so the detector
    must be patient exactly then."""
    if status is None:
        return True
    now = time.time() if now is None else now
    budget = (compile_stale_s if status.get("compile_in_flight")
              else stale_s)
    return now - float(status.get("updated_at", 0.0)) > budget
