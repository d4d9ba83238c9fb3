"""Metrics / observability.

Reference: TensorBoard SummaryWriter with a hyperparameter-derived run name
(src/federated.py:27-31) and seven scalar series (src/federated.py:81-91).
Scalar names are preserved exactly — curve parity against the reference's
TensorBoard output is the acceptance test (SURVEY.md section 5.5):

    Validation/Loss, Validation/Accuracy,
    Poison/Base_Class_Accuracy, Poison/Poison_Accuracy, Poison/Poison_Loss,
    Poison/Cumulative_Poison_Accuracy_Mean

Additions: a JSONL sink (always on — greppable, no TB dependency) and
rounds/sec throughput scalars (SURVEY.md section 5.1: the reference has no
profiling; BASELINE's metric is FL rounds/sec)."""

from __future__ import annotations

import collections
import json
import os
import threading
import time
from typing import Optional


def run_name(cfg) -> str:
    """Hyperparam-derived run dir name (src/federated.py:27-31, minus the
    duplicated num_corrupt quirk, SURVEY.md 2.3.9, and minus the
    reference's time.ctime() prefix: the name is a pure function of the
    config, so two runs of the same --seed land in the same directory and
    their metrics.jsonl streams can be diffed directly)."""
    faults = ""
    if cfg.faults_enabled:
        # every fault knob that changes the experiment must be in the name:
        # two sweep cells differing only in threshold mode / spare-corrupt
        # used to collide into one run dir and interleave their
        # metrics.jsonl streams. corrupt_mode / straggler_epochs ride the
        # cell at non-default values only (the coverage pass's
        # run-name-blind rule caught both; default-valued names keep
        # every historical run dir)
        faults = (f"-flt:d{cfg.dropout_rate}"
                  f"s{cfg.straggler_rate}c{cfg.corrupt_rate}"
                  + (f"m{cfg.corrupt_mode}"
                     if cfg.corrupt_mode != "nan" else "")
                  + (f"e{cfg.straggler_epochs}"
                     if cfg.straggler_epochs != 1 else "")
                  + f"-thrm:{cfg.rlr_threshold_mode}"
                  + ("-spare" if cfg.faults_spare_corrupt else ""))
    churn = ""
    if cfg.churn_enabled:
        # same collision rule as the fault knobs: two cells differing only
        # in the churn process must not share a run dir
        churn = (f"-chrn:a{cfg.churn_available}p{cfg.churn_period}"
                 f"s{cfg.churn_seed}")
    traffic = ""
    if cfg.traffic_enabled:
        # diurnal-traffic cell (ISSUE 17): same collision rule; "flat"
        # stays cell-free so every historical run dir is preserved
        # the latency sigma shapes the buffered-mode staleness draw
        # (data/traffic.py) — it rides the cell only in buffered mode,
        # where it changes the experiment (run-name-blind rule; sync
        # traffic names stay historical)
        from defending_against_backdoors_with_robust_learning_rate_tpu.fl import (
            buffered as _buffered)
        traffic = (f"-tfc:{cfg.traffic}p{cfg.traffic_peak_frac}"
                   f"t{cfg.traffic_trough_frac}d{cfg.traffic_day_rounds}"
                   + (f"l{cfg.traffic_latency_sigma}"
                      if _buffered.is_buffered(cfg) else "")
                   + f"s{cfg.traffic_seed}")
    cohort = ""
    from defending_against_backdoors_with_robust_learning_rate_tpu.utils import (
        compile_cache)
    if compile_cache.is_cohort_mode(cfg) or cfg.churn_enabled:
        # population-axis cells (ISSUE 7): two runs differing only in
        # population / cohort size / partitioner must not share a run
        # dir. Churn runs get the cell too: a host-sampled run under
        # churn reroutes to the cohort program at engine construction
        # (train.py — a data-size decision run_name cannot see), and its
        # results then depend on cohort_seed/cohort_size.
        part = cfg.partitioner
        # the partition-shaping params ride the cell too — two runs
        # differing only in the bank's content must not share a dir
        if part == "dirichlet":
            part += f":a{cfg.dirichlet_alpha}n{cfg.samples_per_client}"
        elif part == "pathological":
            part += (f":c{cfg.classes_per_client}"
                     f"n{cfg.samples_per_client}")
        cohort = (f"-coh:K{cfg.num_agents}m{cfg.agents_per_round}"
                  f"-{part}-cs{cfg.cohort_seed}")
    atk = ""
    if cfg.attack != "static":
        # attack-registry cell (ISSUE 11): scenario-matrix cells
        # differing only in strategy / boost / schedule must not collide
        # into one run dir (the rlr_threshold_mode bug class PR 3 fixed).
        # `static` stays cell-free so every pre-registry baseline keeps
        # its historical run dir.
        from defending_against_backdoors_with_robust_learning_rate_tpu.attack import (
            schedule as attack_schedule)
        # poison_frac rides the cell too: it is the attack's data
        # intensity, and scenario cells differing only in it (e.g. the
        # signflip vs signflip_clean vocabulary pair) must not share a
        # run dir. Base (static) names never carried it and stay as-is.
        atk = f"-atk:{cfg.attack}b{cfg.attack_boost}p{cfg.poison_frac}"
        if not attack_schedule.is_trivial(cfg):
            atk += (f"s{cfg.attack_start}e{cfg.attack_every}"
                    + (f"t{cfg.attack_stop}" if cfg.attack_stop else ""))
    agm = ""
    from defending_against_backdoors_with_robust_learning_rate_tpu.fl import (
        buffered)
    if buffered.is_buffered(cfg):
        # buffered-aggregation cell: two runs differing only in commit
        # threshold / staleness weighting / latency range must not share
        # a run dir (the sweep-cell collision class PR 3 fixed); sync
        # runs stay cell-free so every historical dir is preserved
        agm = (f"-agm:bufK{buffered.buffer_k(cfg)}"
               f"a{cfg.async_staleness_exp}S{cfg.async_max_staleness}")
    qrt = ""
    if cfg.quarantine:
        # static quarantine list (ISSUE 14): excluding clients from the
        # aggregate changes the experiment's results, so two cells
        # differing only in the exclusion list must not share a run dir
        # (run-name-blind rule; the empty default stays cell-free so
        # every historical dir is preserved)
        qrt = f"-qrt:{str(cfg.quarantine).replace(',', '.')}"
    return (f"clip_val:{cfg.clip}"
            f"-noise_std:{cfg.noise}-aggr:{cfg.aggr}"
            f"-s_lr:{cfg.effective_server_lr}-num_cor:{cfg.num_corrupt}"
            f"-thrs_robustLR:{cfg.robustLR_threshold}"
            f"-pttrn:{cfg.pattern_type}-seed:{cfg.seed}"
            f"{faults}{churn}{traffic}{cohort}{atk}{agm}{qrt}")


class NullWriter:
    """No-op writer — non-lead processes of a multi-host job use this so
    only process 0 touches the log directory."""

    def scalar(self, tag: str, value, step: int) -> None:
        pass

    def flush(self) -> None:
        pass

    def close(self) -> None:
        pass


class MetricsDrain:
    """Async host-sync pipeline: the round loop queues callbacks with their
    *device* values and moves on; a background thread fetches the values
    (one batched `jax.device_get` across everything queued at that moment —
    a Podracer-style host loop free of synchronous readbacks) and runs the
    callbacks in strict FIFO order, so the metrics stream is bit-identical
    to the synchronous path (tests/test_async_metrics.py pins this).

    Error policy: a callback exception stops the drain and is re-raised on
    the submitting thread at the NEXT submit() — i.e. at the next dispatch
    unit, not only at the next (possibly much later) flush()/close() —
    whichever of submit/flush/close comes first. After the error is
    delivered once, later submissions are silently dropped — metrics can
    lag, never corrupt silently.

    ``flush(timeout=...)`` raises TimeoutError when the drain makes no
    progress within the budget — the service supervisor's wedge signal
    (service/supervisor.py classifies it and degrades to sync metrics).
    ``close()`` interrupted by KeyboardInterrupt still flushes cleanly:
    the worker is told to stop, drains everything already queued, and the
    interrupt then propagates — a ^C never loses recorded rows."""

    def __init__(self, tracer=None):
        self._items = collections.deque()
        self._lock = threading.Lock()
        self._cond = threading.Condition(self._lock)
        self._pending = 0
        self._stop = False
        self._error = None
        self._dead = False      # drain thread exited on error: reject work
        self._thread = None
        # optional obs.spans.SpanTracer: attributes the batched device_get
        # (the host sync this pipeline hides) on the drain thread's track,
        # as a child of the span that submitted the batch's first item
        self._tracer = tracer

    @property
    def dead(self) -> bool:
        """True once the drain thread has exited on an error: callbacks no
        longer execute and submits are dropped. The service driver checks
        this after every supervised eval unit — a dead drain means the
        boundary's rows were lost, so it degrades to synchronous metrics
        and replays the boundary inline instead of serving on with a
        silently dark pipeline."""
        with self._lock:
            return self._dead

    @property
    def pending(self) -> int:
        """Queued-but-undrained callback count — the backpressure gauge
        the flight recorder samples per round (a growing depth is the
        earliest sign a boundary is outrunning the host sync)."""
        with self._lock:
            return self._pending

    def _raise_pending_locked(self) -> None:
        """Deliver the drain thread's error exactly once (caller holds the
        lock)."""
        if self._error is not None:
            err, self._error = self._error, None
            raise err

    def submit(self, fn, device_vals, *host_args) -> None:
        """Queue fn(fetched_device_vals, *host_args) for the drain thread.
        `device_vals` may be any pytree of jax arrays (or host scalars).
        A pending drain-thread error is re-raised HERE — the main loop
        learns about a failed metrics callback at its next dispatch, not
        only at the next checkpoint flush."""
        with self._cond:
            self._raise_pending_locked()
            if self._dead:
                return
            if self._thread is None:
                self._thread = threading.Thread(
                    target=self._loop, name="metrics-drain", daemon=True)
                self._thread.start()
            parent = (self._tracer.handoff()
                      if self._tracer is not None else None)
            self._items.append((fn, device_vals, host_args, parent))
            self._pending += 1
            self._cond.notify_all()

    def _loop(self):
        import jax
        while True:
            with self._cond:
                while not self._items and not self._stop:
                    self._cond.wait()
                if self._stop and not self._items:
                    return
                batch = list(self._items)
                self._items.clear()
            try:
                # ONE transfer for everything queued right now: the whole
                # batch's device scalars come back in a single device_get
                if self._tracer is not None:
                    with self._tracer.span("drain/device_get",
                                           parent=batch[0][3],
                                           batch=len(batch)):
                        fetched = jax.device_get([b[1] for b in batch])
                else:
                    fetched = jax.device_get([b[1] for b in batch])
                for (fn, _, host_args, _), vals in zip(batch, fetched,
                                                       strict=True):
                    fn(vals, *host_args)
            except BaseException as e:  # noqa: BLE001 — re-raised at flush
                with self._cond:
                    self._error = e
                    self._dead = True
                    self._pending = 0
                    self._items.clear()
                    self._cond.notify_all()
                return
            with self._cond:
                self._pending -= len(batch)
                self._cond.notify_all()

    def flush(self, timeout: Optional[float] = None) -> None:
        """Block until every queued callback has run; re-raise the first
        drain-thread error on this (the submitting) thread. With a
        ``timeout`` (seconds), raise TimeoutError when callbacks are still
        pending past it — the wedged-drain signal the service supervisor
        consumes."""
        deadline = (None if timeout is None
                    else time.monotonic() + timeout)
        with self._cond:
            while self._pending > 0 and self._error is None:
                if deadline is None:
                    self._cond.wait()
                    continue
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    raise TimeoutError(
                        f"metrics drain stalled: {self._pending} "
                        f"callback(s) still pending after {timeout:.1f}s")
                self._cond.wait(remaining)
            self._raise_pending_locked()

    def _stop_and_join(self, join_timeout: float = 30.0) -> None:
        with self._cond:
            self._stop = True
            self._cond.notify_all()
        if self._thread is not None:
            self._thread.join(timeout=join_timeout)
            self._thread = None

    def close(self, raise_errors: bool = True,
              timeout: Optional[float] = None) -> None:
        try:
            self.flush(timeout=timeout)
        except KeyboardInterrupt:
            # ^C mid-flush: flush cleanly anyway. The worker's stop
            # protocol drains everything already queued before exiting
            # (_loop returns only when stop is set AND the queue is
            # empty), so recorded rows still land; then the interrupt
            # propagates — regardless of raise_errors, a user interrupt
            # is never swallowed.
            self._stop_and_join(join_timeout=5.0)
            raise
        except BaseException:
            self._stop_and_join()
            if raise_errors:
                raise
            return
        self._stop_and_join()


class MetricsWriter:
    """JSONL always; TensorBoard when available and enabled."""

    def __init__(self, log_dir: str, name: Optional[str] = None,
                 tensorboard: bool = True, boundary: bool = True,
                 start_fields: Optional[dict] = None):
        os.makedirs(log_dir, exist_ok=True)
        self.dir = os.path.join(log_dir, name) if name else log_dir
        os.makedirs(self.dir, exist_ok=True)
        self.jsonl_path = os.path.join(self.dir, "metrics.jsonl")
        self._jsonl = open(self.jsonl_path, "a")
        self._tb = None
        if tensorboard:
            try:
                from torch.utils.tensorboard import SummaryWriter
                self._tb = SummaryWriter(self.dir)
            except Exception:
                self._tb = None
        # deterministic run_name means reruns of one config share this file
        # (resume appends by design); a boundary record lets readers split
        # the stream into runs instead of seeing duplicate (tag, step) rows.
        # `boundary=False` is the crash-exact resume path (service/driver):
        # the stream was truncated to a journaled offset and the continued
        # rows must splice in with NO extra record, so the recovered file
        # is byte-identical to an uninterrupted run's. `start_fields` ride
        # the boundary record (train.py: `device`, what the segment ran on).
        if boundary:
            self._jsonl.write(json.dumps(
                {"tag": "_run/start", "value": time.time(), "step": -1,
                 **(start_fields or {})}) + "\n")

    def offset(self) -> int:
        """Current byte offset of metrics.jsonl (flushed) — what the round
        journal records at checkpoint boundaries (utils/checkpoint.py)."""
        self._jsonl.flush()
        return self._jsonl.tell()

    def scalar(self, tag: str, value, step: int) -> None:
        self._jsonl.write(json.dumps(
            {"tag": tag, "value": float(value), "step": int(step)}) + "\n")
        if self._tb is not None:
            self._tb.add_scalar(tag, float(value), step)

    def flush(self) -> None:
        self._jsonl.flush()
        if self._tb is not None:
            self._tb.flush()

    def close(self) -> None:
        self.flush()
        self._jsonl.close()
        if self._tb is not None:
            self._tb.close()
