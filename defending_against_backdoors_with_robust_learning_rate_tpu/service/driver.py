"""The continuous-service driver: rounds stream indefinitely, supervised.

``train.run`` is one-shot — it assumes every dispatch lands, every eval
returns, and the process lives to ``cfg.rounds``. ``serve`` turns the same
RoundEngine into a long-running service (FL_PyTorch, arXiv:2202.03099,
frames exactly this simulator-as-service gap):

- **rounds stream** until ``--service_rounds`` is reached, or — with 0 —
  until ``<log_dir>/service.stop`` appears; the client population churns
  underneath via service/churn.py on every path (a host-sampled run
  under churn routes through the cohort program, sampling cohorts from
  the churn-present set — data/cohort.py).
- **every unit is supervised** (service/supervisor.py): dispatch, eval and
  checkpoint each run under deadline + exponential-backoff retry with
  failure classification. Degradation policy on exhausted retries:
  * eval failed        -> skip THIS boundary's eval (training continues;
                          ``Service/Evals_Skipped`` counts the damage);
  * checkpoint wedged  -> the async drain is stalled: close it (bounded)
                          and fall back to synchronous metrics for the
                          rest of the run, then checkpoint again;
  * dispatch poisoned  -> nothing sane to drop — exit loudly with the
                          journal intact (the next start resumes
                          crash-exactly).
- **crash-exact recovery**: before the metrics writer opens, the driver
  finds the newest digest-valid checkpoint (utils/checkpoint.py),
  truncates ``metrics.jsonl`` back to that round's journaled byte offset,
  and resumes — replayed rounds rewrite the identical rows, so an
  interrupted-and-resumed service produces a byte-identical metrics file
  (modulo wall-clock rows) to one that never crashed. A ``kill -9`` at
  ANY point (mid-round, mid-save, mid-journal) lands in one of the cases
  utils/checkpoint.py enumerates; tests/test_service.py drives them
  through service/chaos.py.

Entry point::

    python -m defending_against_backdoors_with_robust_learning_rate_tpu.service.driver \
        --data synthetic --service_rounds 64 --snap 4 \
        --churn_available 0.7 --checkpoint_dir ck --chaos kill@6
"""

from __future__ import annotations

import os
import time
from typing import Dict, List, Optional

import jax

from defending_against_backdoors_with_robust_learning_rate_tpu.config import (
    Config, args_parser)
from defending_against_backdoors_with_robust_learning_rate_tpu.health import (
    monitor as health_monitor)
from defending_against_backdoors_with_robust_learning_rate_tpu.obs import (
    attribution as obs_attribution, events as obs_events,
    export as obs_export, trigger as obs_trigger)
from defending_against_backdoors_with_robust_learning_rate_tpu.service import (
    chaos as chaos_mod, churn as churn_mod)
from defending_against_backdoors_with_robust_learning_rate_tpu.service.supervisor import (
    POISONED, Supervisor, UnitFailure, WEDGED)
from defending_against_backdoors_with_robust_learning_rate_tpu.train import (
    RoundEngine, device_record)
from defending_against_backdoors_with_robust_learning_rate_tpu.utils import (
    checkpoint as ckpt)
from defending_against_backdoors_with_robust_learning_rate_tpu.utils.metrics import (
    MetricsWriter, NullWriter, run_name)

STOP_FILE = "service.stop"

# the churn population census (churn.active_count) is an O(population)
# host-side draw — observability, never worth O(1M) work per boundary on
# the cohort-sampled population axis
CENSUS_MAX_POPULATION = 100_000


def _metrics_path(cfg: Config) -> str:
    return os.path.join(cfg.log_dir, run_name(cfg), "metrics.jsonl")


def _events_path(cfg: Config) -> str:
    return os.path.join(cfg.log_dir, run_name(cfg), "events.jsonl")


def prepare_crash_exact_resume(cfg: Config, truncate: bool = True) -> Dict:
    """Truncate the metrics stream to the journaled offset of the newest
    digest-valid checkpoint, BEFORE any writer opens the file; a fresh
    stream instead journals the file's current end as the round-0 splice
    base. Returns what the recovery report needs.
    ``boundary`` in the result says whether the writer should emit a
    ``_run/start`` record: yes on a fresh stream or a pre-journal append
    (readers must be able to split the runs), no on a crash-exact splice
    (the recovered file must byte-match an uninterrupted run's).
    ``truncate=False`` (non-lead processes) computes everything but leaves
    the file alone — only the lead writer may cut the shared stream."""
    info = {"resumed_from": 0, "metrics_offset": 0, "truncated_bytes": 0,
            "resume_upto": None, "boundary": True}
    if not cfg.checkpoint_dir:
        return info
    # the journal-AGREED round, not the newest digest-valid one: a kill
    # between ckpt.save and journal_record leaves a newer unjournaled
    # checkpoint whose metrics offset is unknown — resuming there would
    # truncate the whole stream. resume_upto pins the engine's restore to
    # the same round.
    rnd = ckpt.newest_resumable_round(cfg.checkpoint_dir)
    info["resumed_from"] = rnd or 0
    info["resume_upto"] = rnd or 0
    path = _metrics_path(cfg)
    size = os.path.getsize(path) if os.path.exists(path) else 0
    journal = ckpt.journal_read(cfg.checkpoint_dir)
    if rnd is not None and not journal:
        # pre-journal checkpoint dir: resumable, but no splice point
        # exists — append rather than drop rows that cannot be replayed
        print(f"[service] checkpoint dir has no round journal — resuming "
              f"from round {rnd} without the crash-exact metrics splice")
        info["metrics_offset"] = size
        return info
    if not journal:
        # fresh service stream: journal the current end of the (append-
        # across-runs) metrics file as the round-0 splice base, so a kill
        # before the first checkpoint resumes by truncating back to HERE —
        # never to 0, which would wipe rows earlier runs wrote
        if truncate:
            ckpt.journal_record(cfg.checkpoint_dir, 0, size)
        info["metrics_offset"] = size
        return info
    offset = ckpt.journal_offset_for(cfg.checkpoint_dir, rnd or 0)
    info["metrics_offset"] = offset
    # a splice past a real checkpoint continues that run mid-stream with no
    # extra record (byte-identity with an uninterrupted run); a round-0
    # base resume restarts the run, which an uninterrupted serve would
    # open with a boundary record
    info["boundary"] = not rnd
    if truncate and size > offset:
        with open(path, "r+b") as f:
            f.truncate(offset)
        info["truncated_bytes"] = size - offset
        print(f"[service] crash-exact resume: metrics.jsonl truncated "
              f"to the round-{rnd or 0} journal offset "
              f"({size - offset} bytes of un-checkpointed rows "
              f"dropped for exact replay)")
    return info


def serve(cfg: Config, writer: Optional[MetricsWriter] = None,
          max_rounds: Optional[int] = None, _adapt=None,
          _adapt_reentry: bool = False, _health=None,
          _phases: Optional[List[str]] = None, _ledger=None,
          _export=None) -> Dict:
    """Run the continuous service; returns the engine summary extended
    with a ``service`` section (retry/degradation counters, recovery
    info).

    With ``--rlr_adapt on`` the service additionally hosts the online
    defense-adaptation loop (attack/adapt.py): at eval boundaries the
    controller reads the drained Defense/* telemetry; when it recommends
    a threshold move, the current engine is torn down at the boundary
    checkpoint and serve re-enters with
    ``robustLR_threshold=<new>`` — same writer (one continuous metrics
    stream), same checkpoint dir, the controller carried through
    (``_adapt``) so its cadence and decision log survive the restart.
    Revisited thresholds are AOT/XLA cache hits, not recompiles.

    Observability plane (ISSUE 15): with ``--events on`` (the default)
    a lead-process event ledger (obs/events.py) records every lifecycle
    transition into ``<run_dir>/events.jsonl``; ``--metrics_port`` /
    ``--metrics_textfile`` arm the Prometheus exporter (obs/export.py).
    Both are carried through every re-entry (``_ledger`` / ``_export``)
    — one ledger stream and one scrape endpoint per logical run, whoever
    created them closes them."""
    lead = jax.process_index() == 0
    ledger, created_ledger = _ledger, False
    if ledger is None and lead and cfg.events == "on":
        run = run_name(cfg)
        ledger = obs_events.EventLedger(_events_path(cfg), run=run,
                                        corr=obs_events.corr_id(run))
        created_ledger = True
    exporter, created_export = _export, False
    if exporter is None and lead and (cfg.metrics_port > 0
                                      or cfg.metrics_textfile):
        run = run_name(cfg)
        exporter = obs_export.MetricsExporter(
            port=cfg.metrics_port if cfg.metrics_port > 0 else None,
            textfile=cfg.metrics_textfile,
            info={"run": run, "backend": jax.default_backend(),
                  "jax_version": jax.__version__},
            base_labels={"run": run})
        created_export = True
        # bank-build progress counters (ISSUE 17) ride the same scrape
        # endpoint; the bank module keeps its numpy-only import surface
        # by taking the exporter by reference rather than importing it
        from defending_against_backdoors_with_robust_learning_rate_tpu.data import (
            bank as bank_mod)
        bank_mod.install_build_exporter(exporter)
        if exporter.port:
            print(f"[export] Prometheus /metrics on port {exporter.port}"
                  + (f" + textfile {cfg.metrics_textfile}"
                     if cfg.metrics_textfile else ""))
        elif cfg.metrics_textfile:
            print(f"[export] Prometheus textfile {cfg.metrics_textfile}")
    prev_ledger = obs_events.install(ledger)
    try:
        return _serve(cfg, writer, max_rounds, _adapt, _adapt_reentry,
                      _health, _phases, ledger, exporter)
    finally:
        obs_events.install(prev_ledger)
        if created_export and exporter is not None:
            exporter.close()
        if created_ledger and ledger is not None:
            ledger.close()


def _serve(cfg: Config, writer, max_rounds, _adapt, _adapt_reentry,
           _health, _phases, ledger, exporter) -> Dict:
    """The supervised round stream (see ``serve``); runs with the
    ledger installed as the process-wide emission target."""
    t_start = time.perf_counter()
    total = max_rounds if max_rounds is not None else cfg.service_rounds
    # supervision granularity is one round per dispatch unit; `rounds`
    # is runtime-only (EXCLUDED_FIELDS), so neither replace recompiles
    cfg = cfg.replace(chain=1, resume=bool(cfg.checkpoint_dir),
                      rounds=(total or cfg.rounds),
                      # -1 = auto: the service checkpoints forever and must
                      # bound the directory (one-shot runs keep everything)
                      service_keep_ckpts=(3 if cfg.service_keep_ckpts < 0
                                          else cfg.service_keep_ckpts))
    lead = jax.process_index() == 0
    if _adapt_reentry:
        # adaptation re-entry is NOT a crash: the stream and its writer
        # are alive and must continue untouched. The crash-exact prepare
        # would compute a phantom metrics path (run_name embeds the
        # adapted threshold) and report recovery against a file nobody
        # writes — resume directly from the boundary checkpoint instead.
        rnd0 = ckpt.newest_resumable_round(cfg.checkpoint_dir) or 0
        recovery = {"resumed_from": rnd0, "metrics_offset": None,
                    "truncated_bytes": 0, "resume_upto": rnd0,
                    "boundary": False}
    else:
        recovery = prepare_crash_exact_resume(cfg, truncate=lead)
    if writer is None:
        if lead:
            writer = MetricsWriter(cfg.log_dir, run_name(cfg),
                                   cfg.tensorboard,
                                   boundary=recovery["boundary"],
                                   start_fields={"device":
                                                 device_record()})
        else:
            writer = NullWriter()
    if recovery["boundary"]:
        # the ledger's stream-segment boundary, mirroring the metrics
        # _run/start semantics: a fresh stream (or a pre-journal append)
        # starts a segment; a crash-exact splice and the in-process
        # re-entries do NOT — their streams must byte-match an
        # uninterrupted run's. Deliberately field-free: the round budget
        # lives in the heartbeat, and an interrupted run relaunched with
        # a different --service_rounds must still splice byte-identically
        obs_events.emit("service/start")

    chaos = chaos_mod.Chaos(
        cfg.chaos, state_path=(os.path.join(cfg.log_dir, "chaos_state.json")
                               if cfg.chaos else None))
    if chaos.active:
        if chaos.requires_buffered() and cfg.agg_mode != "buffered":
            raise ValueError(
                "--chaos kill_midbuf is the buffered-aggregation drill "
                "(the kill must land on a non-empty carried buffer); run "
                "with --agg_mode buffered, or use the plain kill@N")
        print(f"[service] chaos injections armed: {cfg.chaos}")

    if chaos.active:
        # data-plane drill (ISSUE 14): a bank_corrupt term fires BEFORE
        # the engine opens the bank, so verify-on-open meets the damage
        # — searching the SAME root the engine will resolve
        from defending_against_backdoors_with_robust_learning_rate_tpu.data.registry import (
            resolve_bank_root)
        chaos.corrupt_bank(resolve_bank_root(cfg), dataset=cfg.data)

    ladder = _health
    if health_monitor.resolve_policy(cfg) == "recover" \
            and cfg.rlr_adapt == "on":
        # an adapted segment's live metrics stream sits at the ORIGINAL
        # threshold's run_name (the _adapt_reentry comment above); a
        # ladder re-entry inside that segment would crash-exact-splice a
        # phantom path computed from the ADAPTED cfg, stranding the real
        # stream. Refuse the combination until the re-entry threads the
        # stream's run dir explicitly.
        raise ValueError(
            "--health_policy recover is not supported together with "
            "--rlr_adapt on (the ladder's rollback re-entry would "
            "splice the wrong metrics stream inside an adapted "
            "segment); run with --health_policy record, or without "
            "adaptation")
    if health_monitor.resolve_policy(cfg) == "recover" and ladder is None:
        ladder = health_monitor.HealthLadder(
            cfg, state_path=os.path.join(cfg.log_dir,
                                         health_monitor.STATE_NAME))
        print("[health] auto-recovery ladder armed (--health_policy "
              "recover): discard -> rollback -> quarantine -> halt; "
              f"state in {ladder.state_path}")
        # a kill AFTER a QUARANTINE rung was recorded but BEFORE its
        # re-entry completed leaves the suspect set only in the state
        # file — re-arm it, or the resumed process would serve with the
        # suspects still voting (the ladder resumes, not the failure)
        spec = ",".join(str(i) for i in ladder.state["quarantined"])
        if spec and spec != cfg.quarantine:
            print(f"[health] re-arming journaled quarantine set "
                  f"[{spec}] from {ladder.state_path}")
            cfg = cfg.replace(quarantine=spec)

    adapt = _adapt
    if cfg.rlr_adapt == "on" and adapt is None:
        from defending_against_backdoors_with_robust_learning_rate_tpu.attack import (
            adapt as adapt_mod)
        adapt = adapt_mod.ThresholdController(cfg)   # validates loudly
        print(f"[adapt] online RLR-threshold adaptation armed: start "
              f"thr={adapt.thr}, decide every {adapt.every} eval "
              f"boundary(ies) from Defense/* telemetry")

    eng = RoundEngine(cfg, writer=writer,
                      resume_upto=recovery["resume_upto"])
    sup = Supervisor(retries=cfg.service_retries,
                     backoff_s=cfg.service_backoff_s,
                     deadline_s=cfg.service_deadline_s, hb=eng.hb)
    # forensics plane (ISSUE 18): the engine's flight recorder snapshots
    # its ring on every incident, and (opt-in) the anomaly trigger arms
    # a bounded profiler capture. Wired through hooks so the evidence
    # lands even when the event ledger is off.
    flight = getattr(eng, "flight", None)
    trigger = None
    if lead and cfg.trigger_profile == "on" and cfg.profile_rounds <= 0:
        trigger = obs_trigger.ProfileTrigger(
            eng, getattr(writer, "dir", None) or cfg.log_dir,
            exporter=exporter)
        print(f"[service] anomaly-triggered profiling armed: span "
              f"z>={obs_trigger.Z_THRESHOLD} or any incident opens a "
              f"{obs_trigger.DEFAULT_CAPTURE_ROUNDS}-round capture "
              f"(max {obs_trigger.MAX_CAPTURES}/run)")
    elif lead and cfg.trigger_profile == "on":
        print("[service] --trigger_profile ignored: an explicit "
              "--profile_rounds capture owns the profiler seat")

    def _on_incident(kind, rnd):
        if flight is not None:
            flight.snapshot(kind, rnd)
        if trigger is not None:
            trigger.note_incident(kind, rnd)

    sup.on_incident = _on_incident
    if ladder is not None:
        ladder.on_rung = lambda rung, r: _on_incident(f"health/{rung}", r)
    if ledger is not None:
        # heartbeat upgrade (ISSUE 15 satellite): every emitted record
        # mirrors its seq + identity into status.json, so watchers can
        # detect a wedged ledger without tailing events.jsonl. Rides the
        # heartbeat's normal rate limit — event churn must not become
        # fsync churn. Warn/error records double as the flight
        # recorder's incident feed (chaos actions, degradations — every
        # incident the hooks above don't already cover).
        def _hb_event(rec, hb=eng.hb):
            hb.update(ledger_seq=rec["seq"],
                      last_event={"event": rec["event"],
                                  "severity": rec["severity"],
                                  "round": rec["round"]})
            if rec["severity"] != "info" and \
                    not rec["event"].startswith("obs/trigger_"):
                # the trigger's own armed event is warn-severity; feeding
                # it back would re-arm the trigger on itself
                _on_incident(rec["event"], rec["round"])
        ledger.on_emit = _hb_event
    if _phases:
        # in-process re-entry (health ladder / adaptation): the phase
        # history is one continuous record — status.json must still show
        # the health_rollback that CAUSED this re-entry
        sup.phases_seen.extend(_phases)
    if recovery["resumed_from"] and eng.start_round:
        sup.phase("recover", recovered_round=eng.start_round)
        # a per-life record (obs/events.PER_LIFE_PREFIXES): the resumed
        # process's real action. Deliberately WITHOUT truncated_bytes:
        # that value counts whatever rows were flushed before death —
        # buffer state, not logical history — and would break the
        # kill-vs-no-kill twin byte-identity (it stays in the run
        # summary's service section, where it belongs)
        obs_events.emit("service/recover", round=eng.start_round,
                        resumed_from=recovery["resumed_from"])
        print(f"[service] recovered at round {eng.start_round} "
              f"in {time.perf_counter() - t_start:.2f}s")
    stop_path = os.path.join(cfg.log_dir, STOP_FILE)
    census = cfg.churn_enabled and cfg.num_agents <= CENSUS_MAX_POPULATION
    if census:
        print(f"[service] population census at start: "
              f"{churn_mod.active_count(cfg, eng.start_round)}/"
              f"{cfg.num_agents} clients active")
    elif cfg.churn_enabled:
        print(f"[service] population census skipped "
              f"({cfg.num_agents:,} clients > {CENSUS_MAX_POPULATION:,}; "
              f"O(population) draw)")

    def unit_stream():
        rnd = eng.start_round
        while True:
            if total and rnd >= total:
                return
            if not total and os.path.exists(stop_path):
                print(f"[service] stop file {stop_path} — draining out")
                return
            rnd += 1
            yield (rnd,)

    # two independent iterations of the SAME stream: one for the loop, one
    # pinned as the host-mode prefetcher's production order
    eng.set_schedule(unit_stream())
    evals_skipped = 0
    adapt_to = None   # (new_threshold, boundary_round) when a move fires
    recover_to = None  # a ladder rung that rebuilds the engine fired
    try:
        for unit in unit_stream():
            rnd = unit[0]
            # retained for the ladder's DISCARD rung (a reference, not a
            # copy — per-round families deliberately do not donate) and
            # the spike chaos injector's delta
            prev_params = eng.params

            def do_dispatch(unit=unit, rnd=rnd):
                chaos.on_dispatch(rnd)
                eng.dispatch(unit)

            sup.run("dispatch", do_dispatch, unit=rnd)
            _numerics_chaos(chaos, eng, rnd, prev_params)
            # kill-mid-round drill: after dispatch, before the boundary's
            # eval/checkpoint — the rows for this round must be replayed
            # bit-identically by the resumed process
            chaos.maybe_kill(rnd)

            if rnd % cfg.snap == 0:
                if ladder is not None:
                    # the recovery ladder judges the round's sentinel
                    # lanes BEFORE the boundary's eval/checkpoint: a bad
                    # commit must never reach the checkpoint, and a
                    # DISCARD heals in place before any row is emitted
                    _run_ladder(cfg, eng, sup, ladder, chaos, rnd, unit,
                                prev_params)
                def do_eval(rnd=rnd):
                    chaos.on_eval(rnd)
                    eng.eval_boundary(rnd)

                try:
                    sup.run("eval", do_eval, unit=rnd)
                except UnitFailure as e:
                    if not (eng.drain is not None and eng.drain.dead):
                        # degrade: skip THIS boundary's eval, keep
                        # training — a broken eval set must not take down
                        # the service
                        evals_skipped += 1
                        obs_events.emit("service/eval_skipped",
                                        severity="warn", round=rnd,
                                        classification=e.classification)
                        print(f"[service] degraded: eval at round {rnd} "
                              f"skipped ({e.classification}); training "
                              f"continues")
                if eng.drain is not None and eng.drain.dead:
                    # the drain thread died (its error surfaced through the
                    # supervisor above, delivered-once): every later submit
                    # would be a silent drop, so the skip-eval degradation
                    # must not absorb this one. Fall back to synchronous
                    # metrics and replay the boundary inline — if THAT
                    # fails too, exit loudly with the journal intact.
                    sup.phase("degraded", drain_dead_round=rnd)
                    obs_events.emit("service/drain_degraded",
                                    severity="warn", round=rnd,
                                    mode="dead")
                    print("[service] degraded: metrics drain died — "
                          "falling back to synchronous metrics and "
                          f"replaying round {rnd}'s eval inline")
                    eng.drain.close(raise_errors=False)
                    eng.drain = None
                    eng.eval_boundary(rnd)

                secs = chaos.drain_blocker_secs(rnd)
                if secs and eng.drain is not None:
                    eng.drain.submit(lambda _v, s=secs: time.sleep(s), ())

                def do_ckpt(rnd=rnd):
                    if cfg.checkpoint_dir:
                        eng.save_checkpoint(rnd,
                                            drain_timeout=sup.stall_budget())
                    else:
                        # no checkpoint flush will run: barrier the drain
                        # anyway, so the inline Service/* writes below never
                        # race the drain thread on the shared writer
                        eng.drain_flush(timeout=sup.stall_budget())

                try:
                    sup.run("checkpoint", do_ckpt, unit=rnd)
                except UnitFailure as e:
                    if e.classification == WEDGED and eng.drain is not None:
                        # the drain is stalled: degrade to sync metrics.
                        # close() gives the wedged callback a bounded
                        # grace to finish (its rows land in order), then
                        # the service continues inline.
                        obs_events.emit("service/drain_degraded",
                                        severity="warn", round=rnd,
                                        mode="wedged")
                        print("[service] degraded: metrics drain wedged — "
                              "falling back to synchronous metrics")
                        eng.drain.close(raise_errors=False,
                                        timeout=2 * sup.stall_budget())
                        eng.drain = None
                        eng.save_checkpoint(rnd)
                    else:
                        raise
                chaos.corrupt_checkpoint(cfg.checkpoint_dir, rnd)
                if lead and census:
                    eng.writer.scalar(
                        "Service/Active_Clients",
                        churn_mod.active_count(cfg, rnd), rnd)
                _emit_service_rows(eng, sup, evals_skipped, rnd)
                if eng.mstate.get("defense_round") == rnd:
                    # anomaly-gated defense telemetry (ISSUE 15
                    # satellite): the drained flip-fraction / margin
                    # summary judged for over-defense and electorate
                    # splitting — a LOW-severity ledger record in the
                    # same stream as the numerics incidents, never a
                    # ladder trigger. Replay-deduped, so a rollback's
                    # re-evaluated boundary re-emits nothing.
                    why = health_monitor.defense_anomaly(
                        eng.mstate.get("defense"),
                        flip_hi=cfg.defense_flip_frac_hi,
                        low_margin_hi=cfg.defense_low_margin_hi)
                    if why:
                        obs_events.emit(
                            "health/defense_anomaly", severity="info",
                            round=rnd, why=why,
                            flip_frac=float(eng.mstate["defense"]
                                            ["tel_flip_frac"]))
                if exporter is not None:
                    _update_exporter(exporter, eng, sup, ladder,
                                     evals_skipped, rnd, ledger)
                if (adapt is not None
                        and eng.mstate.get("defense_round") == rnd):
                    # the boundary's checkpoint step flushed the drain,
                    # so the telemetry stash is host-complete here; the
                    # freshness stamp gates out boundaries whose eval
                    # was skipped/degraded — the controller must never
                    # decide (or advance its cadence) on the PREVIOUS
                    # boundary's snapshot
                    new_thr = adapt.consider(eng.mstate.get("defense"),
                                             rnd)
                    if new_thr is not None:
                        adapt_to = (new_thr, rnd)
                        break
            eng.post_unit()
            if trigger is not None:
                # after post_unit, so the flight window the z-scan reads
                # already includes this unit's record
                trigger.step(rnd)
        if eng.drain is not None:
            eng.hb.update(phase="drain", force=True)
            eng.drain.flush()
    except health_monitor.HealthRecovery as hr:
        # ROLLBACK / QUARANTINE: tear this engine down and re-enter
        # through the crash-exact resume machinery below (the finally
        # still closes the engine first)
        recover_to = hr
    except UnitFailure:
        # poisoned/give-up on a non-degradable unit: exit loudly, journal
        # intact — the next `serve` resumes crash-exactly
        eng.hb.update(phase="failed", force=True,
                      **sup.heartbeat_fields())
        raise
    finally:
        eng.close()
    if recover_to is not None:
        eng.hb.update(phase=f"health_{recover_to.rung}", force=True,
                      health_round=recover_to.rnd)
        # flushed BEFORE the kill-mid-recovery window below, so a killed
        # and an unkilled recovery leave byte-identical ledgers: the
        # resumed process walks the journaled ladder and re-emits nothing
        obs_events.emit("health/reenter", severity="warn",
                        round=recover_to.rnd, rung=recover_to.rung,
                        quarantine=recover_to.quarantine)
        # kill-mid-rollback drill window: the rung is recorded (ladder
        # state saved) and the engine is closed, but recovery has not
        # completed — a kill HERE must resume the ladder, not the failure
        chaos.maybe_kill_recover(recover_to.rnd)
        print(f"[health] {recover_to.rung.upper()} at round "
              f"{recover_to.rnd}: re-entering through the crash-exact "
              f"resume (newest digest-valid checkpoint + metrics splice)"
              + (f"; quarantining clients [{recover_to.quarantine}]"
                 if recover_to.quarantine else ""))
        writer.close()
        # each recovery re-enters serve() recursively: bound the depth
        # per PROCESS so a long-lived service surviving many healed
        # episodes cannot creep toward the interpreter's recursion
        # limit — the crash-exact machinery makes a process restart
        # free, so the bound trades nothing away (the ladder state file
        # carries everything across it)
        ladder.reentries += 1
        if ladder.reentries > health_monitor.MAX_REENTRIES_PER_PROCESS:
            raise UnitFailure(
                "health", recover_to.rnd, POISONED, ladder.reentries,
                health_monitor.HealthIncident(
                    f"{ladder.reentries} recovery re-entries in one "
                    f"process (> "
                    f"{health_monitor.MAX_REENTRIES_PER_PROCESS}); "
                    f"restart the service — it resumes crash-exactly "
                    f"with the ladder state intact"))
        new_cfg = (cfg.replace(quarantine=recover_to.quarantine)
                   if recover_to.quarantine else cfg)
        outer_wall = time.perf_counter() - t_start
        # writer=None: the re-entry must reopen the stream AFTER the
        # crash-exact truncate (run_name deliberately ignores
        # --quarantine, so the stream path is unchanged)
        sub = serve(new_cfg, writer=None, max_rounds=total, _adapt=adapt,
                    _health=ladder, _phases=sup.phases_seen,
                    _ledger=ledger, _export=exporter)
        svc = sub.setdefault("service", {})
        # rounds_served counts DISTINCT rounds: the inner serve resumed
        # from a checkpoint BEHIND this segment's last round and
        # re-serves the overlap, so this segment only contributes the
        # prefix the inner did not replay (unlike the adapt re-entry
        # below, which resumes exactly at the boundary — no overlap)
        distinct = max(0, int(svc.get("resumed_from", 0))
                       - eng.start_round)
        for key, extra in ({**sup.counters,
                            "evals_skipped": evals_skipped,
                            "rounds_served": distinct,
                            "wall_s": outer_wall}).items():
            svc[key] = round(svc.get(key, 0) + extra, 3)
        svc["phases_seen"] = sorted(set(svc.get("phases_seen", []))
                                    | set(sup.phases_seen))
        svc["health"] = ladder.summary()
        return sub
    if adapt_to is not None:
        new_thr, at_rnd = adapt_to
        old_thr = cfg.robustLR_threshold
        eng.hb.update(phase="adapt", force=True, adapt_round=at_rnd,
                      adapt_threshold=new_thr)
        print(f"[adapt] RLR threshold {old_thr} -> {new_thr} at round "
              f"{at_rnd} (Defense/* telemetry; rebuilding round programs "
              f"from the boundary checkpoint)")
        # re-enter with the adapted program constant: same writer (one
        # continuous metrics stream), same checkpoint dir (the boundary's
        # checkpoint is the resume point), controller carried through so
        # the decision cadence/log survive
        outer_wall = time.perf_counter() - t_start
        sub = serve(cfg.replace(robustLR_threshold=new_thr),
                    writer=writer, max_rounds=total, _adapt=adapt,
                    _adapt_reentry=True, _health=ladder,
                    _phases=sup.phases_seen, _ledger=ledger,
                    _export=exporter)
        # the reliability record must cover the WHOLE run, not just the
        # last segment: fold this segment's supervisor counters into the
        # inner serve's service section
        svc = sub.setdefault("service", {})
        for key, extra in ({**sup.counters,
                            "evals_skipped": evals_skipped,
                            "rounds_served": eng.rounds_done,
                            "wall_s": outer_wall}).items():
            svc[key] = round(svc.get(key, 0) + extra, 3)
        svc["phases_seen"] = sorted(set(svc.get("phases_seen", []))
                                    | set(sup.phases_seen))
        if not _adapt_reentry:
            # the outermost segment's recovery info is the run's real
            # origin (inner re-entries report the adaptation boundary)
            svc["resumed_from"] = recovery["resumed_from"]
            svc["truncated_bytes"] = recovery["truncated_bytes"]
        svc["adaptations"] = [
            {"round": r, "from": f, "to": t} for r, f, t in adapt.moves]
        return sub
    if trigger is not None:
        # a capture window still open at exit: harvest what it caught
        # (eng.close() already stopped the trace on the engine's seat)
        trigger.finalize(eng.rnd)
    eng.hb.update(force=True, evals_skipped=evals_skipped,
                  **sup.heartbeat_fields())
    if exporter is not None:
        # final scrape state before the writer closes — a fleet console
        # polling the textfile sees the finished run's last values
        _update_exporter(exporter, eng, sup, ladder, evals_skipped,
                         eng.rnd, ledger)
    summary = eng.finalize()
    summary["service"] = {
        **sup.counters,
        "evals_skipped": evals_skipped,
        "phases_seen": list(sup.phases_seen),
        "resumed_from": recovery["resumed_from"],
        "truncated_bytes": recovery["truncated_bytes"],
        "rounds_served": eng.rounds_done,
        "wall_s": round(time.perf_counter() - t_start, 3),
    }
    if ledger is not None:
        summary["service"]["ledger_events"] = ledger.seq
    if ladder is not None:
        summary["service"]["health"] = ladder.summary()
    print(f"[service] served {eng.rounds_done} round(s); "
          f"retries={sup.counters['retries']} "
          f"evals_skipped={evals_skipped} "
          f"resumed_from={recovery['resumed_from']}")
    return summary


def _numerics_chaos(chaos, eng, rnd: int, prev_params) -> None:
    """Apply the numerics chaos injections (nan@N / spike@N:x) to the
    round's committed params. In buffered mode only the MODEL half of
    the (params, buffer) carry is touched — the buffer holds integer
    counters whose dtype a float transform would silently change."""
    if not chaos.active:
        return
    if chaos.nan_due(rnd):
        if eng.async_mode:
            eng.params = (health_monitor.poison_params(eng.params[0]),
                          eng.params[1])
        else:
            eng.params = health_monitor.poison_params(eng.params)
    factor = chaos.spike_due(rnd)
    if factor:
        if eng.async_mode:
            eng.params = (health_monitor.spike_params(
                prev_params[0], eng.params[0], factor), eng.params[1])
        else:
            eng.params = health_monitor.spike_params(prev_params,
                                                     eng.params, factor)


def _run_ladder(cfg, eng, sup, ladder, chaos, rnd: int, unit,
                prev_params) -> None:
    """One boundary's walk of the auto-recovery ladder
    (health/monitor.py). Healthy: fold the boundary into the EMA
    baseline and return. Incident: DISCARD in place (withdraw the
    commit, re-dispatch with a recovery nonce — a persistent fault, like
    a chaos nan@NxK with fire budget left, re-poisons the replay and
    escalates), then ROLLBACK / QUARANTINE via HealthRecovery (serve
    re-enters through the crash-exact machinery), then HALT loudly."""
    model_prev = prev_params[0] if eng.async_mode else prev_params
    report = ladder.check(cfg, eng, rnd, prev_params=model_prev)
    incident_emitted = False
    while not report["healthy"]:
        if not incident_emitted:
            # one typed record per incident episode (the rung records
            # below count the escalation walk)
            obs_events.emit("health/incident", severity="warn",
                            round=rnd, why=report["why"])
            incident_emitted = True
        # the QUARANTINE rung feeds --quarantine, which the host-sampled
        # program refuses (it never sees the sampled client ids) — that
        # path escalates past it. DISCARD is safe everywhere: the
        # prefetcher retains the last-served payload precisely for
        # same-unit re-dispatch (data/prefetch.RoundPrefetcher.get).
        rung = ladder.next_rung(cfg, quarantine_ok=not eng.host_mode)
        ladder.record(rung, rnd, sup)
        print(f"[health] incident at round {rnd} ({report['why']}) "
              f"-> {rung.upper()}")
        if rung == "discard":
            eng.params = prev_params
            eng.rounds_done -= 1
            eng.dispatch(unit, nonce=ladder.state["episode"]["discards"])
            _numerics_chaos(chaos, eng, rnd, prev_params)
            report = ladder.check(cfg, eng, rnd,
                                  prev_params=model_prev)
            continue
        if rung == "rollback":
            raise health_monitor.HealthRecovery("rollback", rnd)
        if rung == "quarantine":
            spec = ladder.quarantine_spec(eng, rnd)
            if spec:
                raise health_monitor.HealthRecovery("quarantine", rnd,
                                                    quarantine=spec)
            # no suspect evidence at all: nothing to quarantine — the
            # episode budget is spent either way, so fall through
            report = ladder.check(cfg, eng, rnd,
                                  prev_params=model_prev)
            continue
        raise UnitFailure(
            "health", rnd, POISONED, ladder.state["incidents"],
            health_monitor.HealthIncident(
                f"health ladder exhausted at round {rnd}: "
                f"{report['why']}"))
    ladder.note_healthy(report)


def _update_exporter(exporter, eng, sup: Supervisor, ladder,
                     evals_skipped: int, rnd: int, ledger) -> None:
    """Publish the boundary's service state through the Prometheus
    exporter (obs/export.py): heartbeat-plane gauges, supervisor/ladder
    counters, the drained eval scalars and the HBM watermarks — then
    rewrite the textfile. Values come from host state the boundary's
    drain flush already materialized; nothing here touches the device
    beyond the (cheap, possibly absent) allocator stats query."""
    exporter.observe_rounds(rnd)
    exporter.set("round", rnd, help_text="current round")
    exporter.set("rounds_target", eng.cfg.rounds,
                 help_text="configured total rounds (0 = indefinite)")
    summ = eng.mstate.get("summary") or {}
    for key, name in (("val_acc", "val_acc"),
                      ("poison_acc", "poison_acc"),
                      ("rounds_per_sec", "rounds_per_sec")):
        if key in summ:
            exporter.set(name, summ[key],
                         help_text=f"last boundary's {key}")
    for key, value in sup.counters.items():
        exporter.set(f"supervisor_{key}_total", value, mtype="counter",
                     help_text="supervisor census "
                               "(service/supervisor.py)")
    exporter.set("evals_skipped_total", evals_skipped, mtype="counter",
                 help_text="eval boundaries skipped by degradation")
    if ladder is not None:
        health = ladder.summary()
        exporter.set("health_incidents_total", health["incidents"],
                     mtype="counter",
                     help_text="health incidents (health/monitor.py)")
        for rung in health_monitor.RUNGS:
            exporter.set("health_rung_total", health[f"health_{rung}s"],
                         labels={"rung": rung}, mtype="counter",
                         help_text="recovery-ladder rung census")
        exporter.set("health_quarantined", len(health["quarantined"]),
                     help_text="quarantined client count")
    if ledger is not None:
        exporter.set("ledger_seq", ledger.seq,
                     help_text="event-ledger sequence number "
                               "(obs/events.py)")
    susp = summ.get("suspicion")
    if susp:
        # defense-provenance gauges (obs/reputation.py): the fleet's
        # scrape sees WHO the defense is flagging, not just whether it
        # is flipping — absent entirely when --reputation off
        exporter.set("rep_suspects", susp["suspect_count"],
                     help_text="clients past the suspicion streak "
                               "threshold (obs/reputation.py)")
        exporter.set("rep_clients_tracked", susp["clients"],
                     help_text="clients with longitudinal "
                               "reputation state")
        if susp.get("scores"):
            exporter.set("rep_top_suspect_score", susp["scores"][0],
                         help_text="highest suspicion score "
                                   "(suspicion EMA, obs/reputation.py)")
        if "auc" in susp:
            exporter.set("rep_suspicion_auc", susp["auc"],
                         help_text="suspicion ranking AUC vs known "
                                   "corrupt ids (evaluation only)")
    cfg = eng.cfg
    if cfg.traffic_enabled and cfg.num_agents <= CENSUS_MAX_POPULATION:
        # diurnal-traffic census (data/traffic.py, ISSUE 17 follow-up):
        # computed per boundary for the console print but never exported
        # until now. Host-side O(population) draw, same bound as the
        # churn census.
        from defending_against_backdoors_with_robust_learning_rate_tpu.data import (
            traffic as traffic_mod)
        exporter.set("traffic_present_clients",
                     traffic_mod.census(cfg, rnd),
                     help_text="clients traffic-present this round "
                               "(data/traffic.py census)")
    for key, value in obs_attribution.memory_watermarks().items():
        exporter.set(key, value,
                     help_text="device allocator watermark (bytes)")
    exporter.flush()


def _emit_service_rows(eng, sup: Supervisor, evals_skipped: int,
                       rnd: int) -> None:
    """Service/* counters at each boundary. Written inline (not through
    the drain): they are service-life observability, excluded — like
    Throughput/* — from the crash-exact row comparison."""
    w = eng.writer
    w.scalar("Service/Retries", sup.counters["retries"], rnd)
    w.scalar("Service/Transient_Failures", sup.counters["transient"], rnd)
    w.scalar("Service/Wedged_Failures", sup.counters["wedged"], rnd)
    w.scalar("Service/Poisoned_Failures", sup.counters["poisoned"], rnd)
    w.scalar("Service/Slow_Units", sup.counters["slow_units"], rnd)
    w.scalar("Service/Evals_Skipped", evals_skipped, rnd)


def main(argv=None) -> int:
    cfg = args_parser(argv)
    if cfg.platform:
        jax.config.update("jax_platforms", cfg.platform)
    if cfg.num_processes > 1 or cfg.coordinator:
        from defending_against_backdoors_with_robust_learning_rate_tpu.parallel import (
            multihost)
        multihost.maybe_initialize(cfg.coordinator, cfg.num_processes,
                                   cfg.process_id)
    serve(cfg)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
