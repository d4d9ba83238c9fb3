"""Observability subsystem: round-trace spans, in-jit defense telemetry,
and the structured run heartbeat.

Three layers, built to be cheap enough to leave on:

- `obs.spans`      host-side span tracer emitting Chrome-trace/Perfetto
                   `trace.json` plus matching `jax.profiler` annotations;
                   a span records id, parent (also across a hand-over to
                   another thread), dispatch unit, absolute start/end, the
                   thread's CPU seconds and self time; counters and an
                   `xla/acquire` span per program the backend acquires
                   sit beside them. Per-span p50/p95/max/self/cpu
                   aggregates land in metrics.jsonl (`Spans/*`) and the
                   bench JSON; `spans.current()` is the newest engine's
                   tracer (None under `--no_spans`), and module-level
                   `spans.span()` / `spans.count()` go to it.
- `obs.telemetry`  defense telemetry computed INSIDE the jitted round fn
                   (vote-margin histogram, lr flip fraction, update-norm
                   percentiles, honest-vs-corrupt cosine) — device-resident
                   scalars that ride the async MetricsDrain, gated by
                   `--telemetry off|basic|full`. `off` leaves the traced
                   program untouched: training is bit-identical.
- `obs.heartbeat`  an atomically-rewritten `status.json` (phase, round,
                   last span, compile-in-flight flag, PID, HBM live/peak
                   watermarks) that the service supervisor and the fleet
                   console consume instead of parsing stderr growth.
- `obs.attribution` device-time attribution from `jax.profiler` traces:
                   the `--profile_rounds` sampled capture window, the
                   shared Chrome-trace parser (compute vs collective vs
                   gap, per program family and per `jax.named_scope`),
                   and the `device.memory_stats()` watermarks — rows in
                   metrics.jsonl (`Device/*`, `Memory/*`), fields in the
                   bench JSON, and the input of `obs.report`.
- `obs.report`     the run-report generator (`python -m ...obs.report
                   <run_dir>`): report.md/report.json with the host-vs-
                   device span table, collective share per family and
                   memory watermarks, PASS/FAIL-gated against the pinned
                   `obs_baseline.json` budgets.

The fleet plane (ISSUE 15) — cross-run, service-level observability:

- `obs.events`     the structured event ledger: every lifecycle
                   transition (supervisor retries, recovery-ladder
                   rungs, adaptation moves, chaos injections, checkpoint
                   save/restore, AOT bank hit/miss, queue cells) as one
                   typed, seq-numbered record in `<run_dir>/events.jsonl`
                   — crash-exact (torn-tail truncation + exactly-once
                   episodic emission + replay dedupe).
- `obs.export`     stdlib Prometheus exporter: atomically-rewritten
                   textfile + optional HTTP `/metrics`
                   (`--metrics_textfile` / `--metrics_port`).
- `obs.console`    the fleet console (`python -m ...obs.console
                   <log_root> [--watch|--html]`): the live multi-run
                   table from heartbeats + ledgers.
- `obs.trajectory` the cross-run perf trajectory
                   (`scripts/bench_trajectory.py`): bench artifacts
                   folded into a `trajectory.json` series,
                   regressions judged against a pinned tolerance.
- `obs.constants`  `NON_TIMING_PREFIXES`, the single-sourced exclusion
                   list every crash-exact metrics byte-compare filters
                   on.

The forensics layer (ISSUE 18) — what happened, why, and what changed:

- `obs.flight`     the always-on incident flight recorder: a bounded
                   per-round ring of span durations, dispatch gaps,
                   drain depth, async buffer fill and HBM watermarks
                   streamed to `<run_dir>/flight.jsonl` with ledger-
                   grade crash-exact semantics, snapshotted atomically
                   to `flight.json` on any incident (health rung,
                   supervisor retry/wedge, chaos action, clean exit).
- `obs.trigger`    budgeted anomaly-triggered profiling: a span-p95
                   z-score over the flight window (or a monitor/
                   supervisor incident) arms `obs.attribution`'s
                   RoundProfiler for N steady rounds, max 2 captures
                   per run (`--trigger_profile on|off`), attaching the
                   device split as `obs/trigger_*` ledger events and
                   exporter gauges.
- `obs.explain`    cross-run regression forensics: diff two run dirs or
                   bench artifacts into a per-span/per-phase delta
                   table (compile vs steady vs drain vs eval vs
                   collective share) with a classified verdict —
                   `scripts/bench_trajectory.py --explain` and the
                   auto-explain on a trajectory gate FAIL.
"""

from defending_against_backdoors_with_robust_learning_rate_tpu.obs.heartbeat import (  # noqa: F401
    Heartbeat, NullHeartbeat, is_stale, read_status)
from defending_against_backdoors_with_robust_learning_rate_tpu.obs.spans import (  # noqa: F401
    SpanTracer)
