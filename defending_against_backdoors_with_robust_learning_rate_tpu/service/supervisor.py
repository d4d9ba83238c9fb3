"""Unit supervision: deadline + exponential-backoff retry with failure
classification.

External babysitting (a watcher probing the backend, stall clocks grown
off stderr bytes) has `kill` as its only remedy. The service driver uses
in-process supervision instead: every dispatch / eval / checkpoint unit
runs under this supervisor, which

- **classifies** a failure before reacting:
  * ``transient`` — the error message carries an RPC/XLA retry-worthy
    signature (UNAVAILABLE, RESOURCE_EXHAUSTED, ...):
    retry with exponential backoff;
  * ``wedged``    — the unit ran into a deadline/timeout (a stalled drain
    flush, a unit past ``--service_deadline_s``): retry, and let the
    driver degrade (sync-metrics fallback, skipped eval) when retries
    drain;
  * ``poisoned``  — a deterministic error (shape mismatch, NaN abort,
    assertion): retrying would reproduce it, so fail fast and let the
    driver's degradation policy decide what to drop.
- **consumes the heartbeat's stall vocabulary** instead of stderr
  heuristics: the wedge budget defaults to obs/heartbeat.py's
  ``DEFAULT_STALE_S`` (the same constant the external watchers key on),
  and every retry/backoff transition is written INTO the heartbeat
  (phase="retry"/"backoff" + cumulative counters), so `status.json` shows
  the self-healing in progress rather than a silent gap the watchdogs
  would misread as a wedge.

Determinism: backoff is a pure function of the attempt index (no jitter —
the chaos tests replay schedules exactly); `sleep`/`clock` are injectable.
"""

from __future__ import annotations

import time
from typing import Any, Callable, Dict, List, Optional

from defending_against_backdoors_with_robust_learning_rate_tpu.obs import (
    events as obs_events, heartbeat as hb_mod)

# substrings that mark an error retry-worthy: the gRPC/absl status names
# XLA:TPU runtime errors carry. Case-sensitive on the status names (they
# are ALL-CAPS constants), case-insensitive on the prose.
TRANSIENT_SIGNATURES = (
    "UNAVAILABLE", "DEADLINE_EXCEEDED", "RESOURCE_EXHAUSTED", "ABORTED",
    "UNKNOWN: ", "INTERNAL: ",
    "temporarily unavailable", "transient", "retry",
)

TRANSIENT, WEDGED, POISONED = "transient", "wedged", "poisoned"
RETRYABLE = (TRANSIENT, WEDGED)


def classify(exc: BaseException) -> str:
    """Failure class of one exception (see module docstring)."""
    if isinstance(exc, TimeoutError):
        return WEDGED
    text = f"{type(exc).__name__}: {exc}"
    low = text.lower()
    for sig in TRANSIENT_SIGNATURES:
        if (sig in text) if sig.isupper() else (sig in low):
            return TRANSIENT
    return POISONED


class UnitFailure(RuntimeError):
    """A unit that failed past its retry budget (or failed fast as
    poisoned). The driver's degradation policy dispatches on
    ``classification``."""

    def __init__(self, kind: str, unit, classification: str,
                 attempts: int, cause: BaseException):
        super().__init__(
            f"{kind} unit {unit}: {classification} failure after "
            f"{attempts} attempt(s): {type(cause).__name__}: {cause}")
        self.kind = kind
        self.unit = unit
        self.classification = classification
        self.attempts = attempts
        self.cause = cause


class Supervisor:
    """Retry/backoff/deadline wrapper around the engine's step methods."""

    def __init__(self, retries: int = 3, backoff_s: float = 0.25,
                 deadline_s: float = 0.0, hb=None,
                 sleep: Callable[[float], None] = time.sleep,
                 clock: Callable[[], float] = time.monotonic):
        self.retries = max(0, int(retries))
        self.backoff_s = float(backoff_s)
        self.deadline_s = float(deadline_s)
        self.hb = hb if hb is not None else hb_mod.NullHeartbeat()
        self._sleep = sleep
        self._clock = clock
        self.counters: Dict[str, int] = {
            "retries": 0, "transient": 0, "wedged": 0, "poisoned": 0,
            "gave_up": 0, "slow_units": 0}
        self.phases_seen: List[str] = []
        # optional incident hook, on_incident(kind, unit_round): the
        # driver wires the flight recorder's snapshot + the profile
        # trigger here so retries/give-ups/slow units leave evidence
        # even when the event ledger is off
        self.on_incident: Optional[Callable[[str, Optional[int]],
                                            None]] = None

    def _incident(self, kind: str, unit) -> None:
        if self.on_incident is None:
            return
        try:
            self.on_incident(kind,
                             unit if isinstance(unit, int) else None)
        except Exception:
            pass  # observability must never take down the run

    # ------------------------------------------------------------- helpers

    def stall_budget(self) -> float:
        """Wedge budget for host-side waits (drain flushes, payload
        fetches): the configured per-unit deadline, else the heartbeat
        module's stale budget — the SAME constant the external stall
        detectors use, so in-process self-healing triggers no later than
        an external killer would have."""
        return self.deadline_s if self.deadline_s > 0 \
            else hb_mod.DEFAULT_STALE_S

    def phase(self, phase: str, **fields) -> None:
        if not self.phases_seen or self.phases_seen[-1] != phase:
            self.phases_seen.append(phase)
        self.hb.update(phase=phase, force=True,
                       service_phases=self.phases_seen, **fields,
                       **self.counters)

    def backoff(self, attempt: int) -> float:
        """Deterministic exponential backoff for attempt N (0-based)."""
        return self.backoff_s * (2 ** attempt)

    # ----------------------------------------------------------------- run

    def run(self, kind: str, fn: Callable[[], Any], unit=None) -> Any:
        """Run one unit supervised. Returns fn()'s value; raises
        UnitFailure when the unit is poisoned or the retry budget is
        spent. KeyboardInterrupt/SystemExit always propagate — the
        supervisor heals the run, it does not trap the operator."""
        attempt = 0
        while True:
            t0 = self._clock()
            try:
                out = fn()
            except (KeyboardInterrupt, SystemExit):
                raise
            except BaseException as e:  # noqa: BLE001 — classified below
                cls = classify(e)
                self.counters[cls] += 1
                if cls not in RETRYABLE or attempt >= self.retries:
                    self.counters["gave_up"] += 1
                    self.phase("degraded", failed_kind=kind)
                    obs_events.emit("supervisor/give_up", severity="error",
                                    round=unit if isinstance(unit, int)
                                    else None,
                                    kind=kind, classification=cls,
                                    attempts=attempt + 1)
                    self._incident(f"supervisor/give_up:{kind}", unit)
                    raise UnitFailure(kind, unit, cls, attempt + 1, e) \
                        from e
                delay = self.backoff(attempt)
                attempt += 1
                self.counters["retries"] += 1
                print(f"[service] {kind} unit {unit}: {cls} failure "
                      f"({type(e).__name__}: {e}); retry "
                      f"{attempt}/{self.retries} after {delay:.2f}s")
                # one typed ledger record per retry: backoff_s is the
                # deterministic schedule value, not measured time, so the
                # record joins the twin-drill byte comparison
                obs_events.emit("supervisor/retry", severity="warn",
                                round=unit if isinstance(unit, int)
                                else None,
                                kind=kind, classification=cls,
                                attempt=attempt, backoff_s=delay)
                self._incident(f"supervisor/retry:{kind}", unit)
                self.phase("retry", retry_kind=kind)
                self.phase("backoff", retry_kind=kind)
                self._sleep(delay)
                continue
            elapsed = self._clock() - t0
            if self.deadline_s > 0 and elapsed > self.deadline_s:
                # the unit COMPLETED but blew its deadline — the wedge
                # signal for degradation policy (e.g. stop overlapping
                # eval), recorded rather than retried: the work is done
                self.counters["slow_units"] += 1
                print(f"[service] {kind} unit {unit}: completed but took "
                      f"{elapsed:.2f}s (deadline {self.deadline_s:.2f}s) "
                      f"— flagged wedged-slow")
                obs_events.emit("supervisor/slow", severity="warn",
                                round=unit if isinstance(unit, int)
                                else None, kind=kind)
                self._incident(f"supervisor/slow:{kind}", unit)
                self.phase("slow", slow_kind=kind)
            return out

    def heartbeat_fields(self) -> Dict[str, Any]:
        """Cumulative counters for status.json (the CI chaos drill asserts
        these survive to the final heartbeat)."""
        return {**self.counters, "service_phases": list(self.phases_seen)}
