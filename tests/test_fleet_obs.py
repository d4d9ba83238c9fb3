"""Fleet observability plane (ISSUE 15): event ledger, Prometheus
exporter, fleet console, trajectory gate.

Acceptance drilled here:
- ledger crash-exactness: interrupted-vs-uninterrupted event streams
  equal modulo wall timestamps (+ the per-life resume records a twin
  genuinely lacks), torn tails truncated on open;
- the full recovery-ladder stream (incident -> rungs -> reenter ->
  recover) is byte-deterministic across reruns and shares ONE
  correlation id;
- ``--events off`` arms nothing and leaves the metrics stream
  byte-identical;
- heartbeat upgrade: status.json carries ledger_seq + last_event;
- exporter scrape parses as valid Prometheus text and round-trips the
  heartbeat values; console renders a 3-run fixture fleet; trajectory
  gate rc 0/1/2 on pass/regress/malformed.

The true-SIGKILL ``kill_recover`` twin drill is a subprocess pair (the
in-process rollback re-entry drills the identical machinery) and also
runs in CI ``obs-fleet-smoke``.
"""

import json
import os
import subprocess
import sys
import urllib.request

import pytest

from defending_against_backdoors_with_robust_learning_rate_tpu.config import Config
from defending_against_backdoors_with_robust_learning_rate_tpu.health import (
    monitor as health_monitor)
from defending_against_backdoors_with_robust_learning_rate_tpu.obs import (
    console as obs_console, events as obs_events, export as obs_export,
    flight as obs_flight, trajectory as obs_trajectory)
from defending_against_backdoors_with_robust_learning_rate_tpu.obs.constants import (
    NON_TIMING_PREFIXES)
from defending_against_backdoors_with_robust_learning_rate_tpu.service.driver import (
    serve)
from defending_against_backdoors_with_robust_learning_rate_tpu.utils.metrics import (
    run_name)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# the test_service.SVC shape: identical program fields, so CI's shared
# AOT bank serves every serve() here warm
SVC = Config(data="synthetic", num_agents=8, bs=16, local_ep=1,
             synth_train_size=256, synth_val_size=64, eval_bs=64,
             snap=2, seed=5, tensorboard=False, num_corrupt=2,
             poison_frac=1.0, robustLR_threshold=3,
             service_backoff_s=0.01)


# --------------------------------------------------------------------------
# ledger unit tests (no jax, no serve)
# --------------------------------------------------------------------------


def test_ledger_seq_schema_and_resume(tmp_path):
    path = str(tmp_path / "events.jsonl")
    led = obs_events.EventLedger(path, run="r", corr="abc123")
    led.emit("service/start")
    led.emit("health/rung", severity="warn", round=4, rung="discard")
    led.close()
    # a reopened ledger continues the numbering
    led2 = obs_events.EventLedger(path, run="r", corr="abc123")
    led2.emit("checkpoint/save", round=6)
    led2.close()
    recs = obs_events.read_events(path)
    assert [r["seq"] for r in recs] == [0, 1, 2]
    head = list(recs[0])[:7]
    assert head == ["seq", "event", "severity", "run", "corr", "round",
                    "t"]
    assert recs[1]["rung"] == "discard" and recs[1]["corr"] == "abc123"


def test_ledger_torn_tail_truncated_on_open(tmp_path):
    path = str(tmp_path / "events.jsonl")
    led = obs_events.EventLedger(path, run="r")
    led.emit("service/start")
    led.emit("checkpoint/save", round=2)
    led.close()
    size = os.path.getsize(path)
    with open(path, "ab") as f:   # a SIGKILL mid-write
        f.write(b'{"seq": 2, "event": "torn')
    led2 = obs_events.EventLedger(path, run="r")
    assert os.path.getsize(path) == size   # torn tail gone
    assert led2.seq == 2
    led2.emit("checkpoint/save", round=4)
    led2.close()
    assert [r["seq"] for r in obs_events.read_events(path)] == [0, 1, 2]


def test_ledger_replay_dedupe_and_severity(tmp_path):
    path = str(tmp_path / "events.jsonl")
    led = obs_events.EventLedger(path, run="r")
    assert led.emit("checkpoint/save", round=4) is not None
    # a crash-exact replay re-saving the boundary emits nothing...
    assert led.emit("checkpoint/save", round=4) is None
    assert led.emit("checkpoint/save", round=2) is None
    # ...and fresh progress does
    assert led.emit("checkpoint/save", round=6) is not None
    with pytest.raises(ValueError, match="severity"):
        led.emit("x", severity="fatal")
    led.close()
    # the dedupe mark survives a process restart (rebuilt from the file)
    led2 = obs_events.EventLedger(path, run="r")
    assert led2.emit("checkpoint/save", round=6) is None
    led2.close()


def test_emit_is_noop_without_installed_ledger(tmp_path):
    assert obs_events.active() is None
    assert obs_events.emit("service/start") is None
    led = obs_events.EventLedger(str(tmp_path / "e.jsonl"), run="r")
    prev = obs_events.install(led)
    try:
        assert obs_events.emit("service/start") is not None
    finally:
        obs_events.install(prev)
        led.close()
    assert obs_events.active() is None


def test_defense_anomaly_unit():
    ok = {"tel_flip_frac": 0.1,
          "tel_margin_hist": [0.0, 0.0, 0.0, 0.0, 0.2, 0.3, 0.3, 0.2]}
    assert health_monitor.defense_anomaly(ok) == ""
    assert health_monitor.defense_anomaly(None) == ""
    over = dict(ok, tel_flip_frac=0.7)
    assert "flip fraction" in health_monitor.defense_anomaly(over)
    split = dict(ok, tel_margin_hist=[0.3, 0.2, 0.1, 0.0,
                                      0.1, 0.1, 0.1, 0.1])
    assert "electorate splitting" in health_monitor.defense_anomaly(split)


# --------------------------------------------------------------------------
# exporter
# --------------------------------------------------------------------------


def test_exporter_render_parse_roundtrip_and_textfile(tmp_path):
    path = str(tmp_path / "m.prom")
    exp = obs_export.MetricsExporter(
        textfile=path, info={"run": "r1", "backend": "cpu"},
        base_labels={"run": "r1"})
    exp.set("round", 6)
    exp.set("health_rung_total", 1, labels={"rung": "rollback"},
            mtype="counter")
    exp.flush()
    metrics = obs_export.read_textfile(path)   # parses or raises
    assert metrics["rlr_round"]['{run="r1"}'] == 6.0
    assert metrics["rlr_build_info"]
    key = '{run="r1",rung="rollback"}'
    assert metrics["rlr_health_rung_total"][key] == 1.0
    text = open(path).read()
    assert "# TYPE rlr_health_rung_total counter" in text
    assert obs_export.summary_labels(path)["run"] == "r1"
    exp.close()


def test_exporter_http_scrape(tmp_path):
    exp = obs_export.MetricsExporter(port=0, info={"run": "r1"})
    try:
        assert exp.port and exp.port > 0
        exp.set("round", 3)
        with urllib.request.urlopen(
                f"http://127.0.0.1:{exp.port}/metrics", timeout=10) as r:
            body = r.read().decode()
        parsed = obs_export.parse_prometheus_text(body)
        assert parsed["rlr_round"][""] == 3.0
    finally:
        exp.close()


def test_exporter_ema_skips_rollbacks():
    clock = iter([0.0, 1.0, 2.0, 3.0]).__next__
    exp = obs_export.MetricsExporter(clock=clock)
    exp.observe_rounds(0)
    exp.observe_rounds(10)          # 10 r/s
    exp.observe_rounds(4)           # rollback: negative delta skipped
    exp.observe_rounds(8)           # 4 r/s
    ema = exp._ema
    assert ema is not None and 4.0 < ema < 10.0


# --------------------------------------------------------------------------
# console + trajectory
# --------------------------------------------------------------------------


def _fixture_fleet(root):
    """Three fake runs: healthy, erroring, heartbeat-less."""
    now = 1_000_000.0
    for i, name in enumerate(("run_a", "run_b", "run_c")):
        log_dir = os.path.join(root, f"exp{i}")
        run_dir = os.path.join(log_dir, name)
        os.makedirs(run_dir)
        with open(os.path.join(run_dir, "metrics.jsonl"), "w") as f:
            f.write(json.dumps({"tag": "Validation/Accuracy",
                                "value": 0.9 - 0.1 * i, "step": 4}) + "\n")
            f.write(json.dumps({"tag": "Throughput/Rounds_Per_Sec",
                                "value": 1.5, "step": 4}) + "\n")
        led = obs_events.EventLedger(
            os.path.join(run_dir, "events.jsonl"), run=name)
        led.emit("service/start", rounds=8)
        if i == 1:
            led.emit("supervisor/give_up", severity="error", round=3,
                     kind="dispatch")
            with open(os.path.join(run_dir, "flight.json"), "w") as f:
                json.dump({"v": 1, "reason": "supervisor/give_up",
                           "round": 3, "window": []}, f)
        led.close()
        if i < 2:
            with open(os.path.join(log_dir, "status.json"), "w") as f:
                json.dump({"phase": "train", "round": 4, "rounds": 8,
                           "updated_at": now - 5, "pid": 1,
                           "ledger_seq": led.seq,
                           "last_event": {"event": "service/start",
                                          "severity": "info",
                                          "round": None}}, f)
    return now


def test_console_renders_fixture_fleet(tmp_path):
    now = _fixture_fleet(str(tmp_path))
    rows = obs_console.scan_fleet(str(tmp_path), now=now)
    assert {r["run"] for r in rows} == {"run_a", "run_b", "run_c"}
    by = {r["run"]: r for r in rows}
    assert by["run_b"]["errors"] == 1
    assert by["run_a"]["val_acc"] == pytest.approx(0.9)
    assert by["run_a"]["ledger_seq"] == 1
    assert by["run_c"]["stale"]          # no heartbeat at all
    # ISSUE 18 satellite: the INCIDENT column — last warn/error from the
    # ledger tail, "+fl" when a flight snapshot sits next to the stream
    assert by["run_b"]["last_incident"]["event"] == "supervisor/give_up"
    assert by["run_b"]["flight_snapshot"]
    assert by["run_a"]["last_incident"] is None
    assert not by["run_a"]["flight_snapshot"]
    text = obs_console.render_table(rows)
    for name in ("run_a", "run_b", "run_c", "RUN", "LAST EVENT",
                 "INCIDENT", "supervisor/give_up@3 +fl"):
        assert name in text
    # --html writes a standalone table
    rc = obs_console.main([str(tmp_path), "--html",
                           "--out", str(tmp_path / "c.html")])
    assert rc == 0
    html = open(tmp_path / "c.html").read()
    assert "run_b" in html and "<table>" in html


def test_trajectory_gate_rc_0_1_2(tmp_path):
    script = os.path.join(REPO, "scripts", "bench_trajectory.py")

    def gate(*args):
        return subprocess.run([sys.executable, script, *args],
                              capture_output=True, text=True)

    # rc 0: a recorded failure, then an improving series
    bad = {"version": 1, "tolerance": 0.15, "series": [
        {"label": "x", "ok": False, "note": "bench rc 1"},
        {"label": "a", "ok": True, "rounds_per_sec": 2.0,
         "group": "tpu|fmnist|f32"},
        {"label": "b", "ok": True, "rounds_per_sec": 2.2,
         "group": "tpu|fmnist|f32"}]}
    p = tmp_path / "traj.json"
    p.write_text(json.dumps(bad))
    assert gate("--trajectory", str(p)).returncode == 0
    # rc 1: a regression past tolerance within one comparability group
    bad["series"][2]["rounds_per_sec"] = 1.0
    p.write_text(json.dumps(bad))
    r = gate("--trajectory", str(p))
    assert r.returncode == 1 and "regression" in r.stdout
    # ...but a cross-group drop is NOT a regression (cpu vs tpu)
    bad["series"][2]["group"] = "cpu|fmnist|f32"
    p.write_text(json.dumps(bad))
    assert gate("--trajectory", str(p)).returncode == 0
    # rc 2: malformed input
    p.write_text("{not json")
    assert gate("--trajectory", str(p)).returncode == 2
    q = tmp_path / "artifact.json"
    q.write_text(json.dumps({"neither": "shape"}))
    assert gate("--fold", str(q)).returncode == 2
    # folding a real session record works and judges
    r02 = tmp_path / "BENCH_x.json"
    r02.write_text(json.dumps({
        "n": 7, "cmd": "bench", "rc": 0,
        "parsed": {"metric": "fl_rounds_per_sec", "value": 3.0,
                   "device": "TPU v5 lite0"}}))
    p.write_text(json.dumps({"version": 1, "tolerance": 0.15,
                             "series": []}))
    r = gate("--trajectory", str(p), "--fold", str(r02), "--write")
    assert r.returncode == 0
    saved = json.load(open(p))
    assert saved["series"][0]["label"] == "r07"
    assert saved["series"][0]["group"] == "tpu|fmnist|f32"


# --------------------------------------------------------------------------
# serve() integration
# --------------------------------------------------------------------------


def _cfg(root, tag, **kw):
    return SVC.replace(log_dir=os.path.join(root, f"{tag}_logs"),
                       checkpoint_dir=os.path.join(root, f"{tag}_ck"), **kw)


@pytest.fixture(scope="module")
def fleet(tmp_path_factory):
    """Every serve() of this module, run once: a cold warmup drill (the
    resumed-engine program variant must be banked before strict ledger
    comparisons — cold-vs-warm AOT hit/miss records differ by design),
    then the comparison runs."""
    root = str(tmp_path_factory.mktemp("fleet"))
    drill = dict(service_rounds=6, chaos="nan@3",
                 health_policy="recover")
    serve(_cfg(root, "warm", **drill))                 # warmup
    out = {"root": root}
    out["d1"] = _cfg(root, "d1", **drill,
                     metrics_textfile=os.path.join(root, "d1.prom"))
    out["d1_summary"] = serve(out["d1"])
    out["d2"] = _cfg(root, "d2", **drill)
    serve(out["d2"])
    # uninterrupted twin A vs clean-stop-and-continue B (+ torn tail)
    out["a"] = _cfg(root, "a", service_rounds=8)
    serve(out["a"])
    out["b"] = _cfg(root, "b", service_rounds=8)
    serve(out["b"].replace(service_rounds=4))
    with open(_events(out["b"]), "ab") as f:
        f.write(b'{"seq": 99, "event": "torn')   # kill mid-write
    with open(_flight(out["b"]), "ab") as f:
        f.write(b'{"seq": 99, "round')           # ...torn flight too
    serve(out["b"])
    # events off: nothing armed, metrics stream untouched
    out["c"] = _cfg(root, "c", service_rounds=8,
                    events="off")
    serve(out["c"])
    return out


def _events(cfg):
    return os.path.join(cfg.log_dir, run_name(cfg), "events.jsonl")


def _flight(cfg):
    return os.path.join(cfg.log_dir, run_name(cfg),
                        obs_flight.STREAM_NAME)


def _metric_lines(cfg):
    path = os.path.join(cfg.log_dir, run_name(cfg), "metrics.jsonl")
    return [line for line in open(path)
            if not json.loads(line)["tag"].startswith(
                NON_TIMING_PREFIXES)]


def test_ladder_stream_typed_and_deterministic(fleet):
    """The nan drill's full event stream — chaos, incident, rungs,
    reenter, restore, recover, replayed saves — rerun-deterministic
    byte-for-byte modulo wall clocks, under ONE correlation id."""
    recs = obs_events.read_events(_events(fleet["d1"]))
    evs = [r["event"] for r in recs]
    for want in ("service/start", "chaos/nan", "health/incident",
                 "health/rung", "health/reenter", "checkpoint/restore",
                 "service/recover", "checkpoint/save", "aot/hit"):
        assert want in evs, (want, evs)
    rungs = [r["rung"] for r in recs if r["event"] == "health/rung"]
    assert rungs == ["discard", "rollback"]
    assert [r["seq"] for r in recs] == list(range(len(recs)))
    assert len({r["corr"] for r in recs}) == 1
    assert recs[0]["corr"] == obs_events.corr_id(run_name(fleet["d1"]))
    # replayed rounds re-save boundaries exactly once (dedupe)
    saves = [r["round"] for r in recs if r["event"] == "checkpoint/save"]
    assert saves == sorted(set(saves))
    # rerun determinism: the strict (wall-clock-only-stripped) streams
    # of two independent drills are identical
    d2 = obs_events.read_events(_events(fleet["d2"]))
    assert obs_events.strip_wallclock(recs) == \
        obs_events.strip_wallclock(d2)


def test_heartbeat_carries_ledger_fields(fleet):
    """ISSUE 15 satellite: status.json mirrors ledger_seq + last_event
    so watchers detect a wedged ledger without tailing events.jsonl."""
    st = json.load(open(os.path.join(fleet["d1"].log_dir,
                                     "status.json")))
    recs = obs_events.read_events(_events(fleet["d1"]))
    assert st["ledger_seq"] == recs[-1]["seq"]
    assert st["last_event"]["event"] == recs[-1]["event"]
    assert st["last_event"]["severity"] in obs_events.SEVERITIES
    assert st["phase"] == "done"
    assert fleet["d1_summary"]["service"]["ledger_events"] == len(recs)


def test_exporter_roundtrips_service_state(fleet):
    """Scrape parses as Prometheus text and round-trips the heartbeat
    values + the ladder census."""
    prom = os.path.join(fleet["root"], "d1.prom")
    metrics = obs_export.read_textfile(prom)   # parses or raises
    run = run_name(fleet["d1"])
    key = '{run="%s"}' % run
    st = json.load(open(os.path.join(fleet["d1"].log_dir,
                                     "status.json")))
    assert metrics["rlr_round"][key] == float(st["round"])
    # "incidents" counts rung records (the historical ladder semantic):
    # the nan drill walks discard -> rollback = 2
    assert metrics["rlr_health_incidents_total"][key] == 2.0
    rollback_key = '{run="%s",rung="rollback"}' % run
    assert metrics["rlr_health_rung_total"][rollback_key] == 1.0
    assert metrics["rlr_supervisor_retries_total"][key] == \
        float(st["retries"])
    assert metrics["rlr_ledger_seq"][key] == float(st["ledger_seq"]) + 1
    assert obs_export.summary_labels(prom)["run"] == run


def test_ledger_splice_across_interrupted_resume(fleet):
    """Satellite: interrupted-vs-uninterrupted event streams equal
    modulo wall timestamps and the per-life resume records (the resumed
    process's real restore/recover/aot actions, which the twin genuinely
    lacks — obs/events.PER_LIFE_PREFIXES); the torn tail injected before
    the resume was truncated on open."""
    a = obs_events.read_events(_events(fleet["a"]))
    b = obs_events.read_events(_events(fleet["b"]))
    assert obs_events.strip_wallclock(b, drop_per_life=True) == \
        obs_events.strip_wallclock(a, drop_per_life=True)
    assert all(r["event"] != "torn" for r in b)
    assert [r["seq"] for r in b] == list(range(len(b)))
    # the resume evidence IS present on the interrupted run
    b_events = [r["event"] for r in b]
    assert "service/recover" in b_events
    assert "checkpoint/restore" in b_events
    assert "service/recover" not in [r["event"] for r in a]


def test_events_off_arms_nothing_and_metrics_identical(fleet):
    """Acceptance: --events off produces no ledger and a bit-identical
    metrics stream (non-timing rows byte-compared)."""
    assert not os.path.exists(_events(fleet["c"]))
    assert _metric_lines(fleet["c"]) == _metric_lines(fleet["a"])
    # ...and events ON also never touches the metrics stream
    assert "ledger_events" not in json.dumps(
        _metric_lines(fleet["a"]))


def test_flight_stream_deterministic_across_drills(fleet):
    """ISSUE 18: two independent nan drills leave flight streams whose
    non-timing projection is byte-identical — same rounds streamed, same
    seq numbering, same correlation id and slot."""
    d1 = obs_flight.read_flight(_flight(fleet["d1"]))
    d2 = obs_flight.read_flight(_flight(fleet["d2"]))
    assert d1, "flight recorder is default-on and must stream"
    assert obs_flight.strip_timing(d1) == obs_flight.strip_timing(d2)
    assert [r["seq"] for r in d1] == list(range(len(d1)))
    assert len({r["corr"] for r in d1}) == 1
    assert d1[0]["corr"] == obs_events.corr_id(run_name(fleet["d1"]))
    # the timing tail is populated, not dead weight
    assert any(r["spans"] for r in d1)
    assert any(r.get("drain_depth") is not None for r in d1)


def test_flight_snapshot_written_on_incident(fleet):
    """Acceptance: a chaos health incident produces flight.json — the
    nan drill snapshots on every rung/incident and again on clean exit,
    and the LAST snapshot still carries the incident window."""
    snap_path = os.path.join(os.path.dirname(_flight(fleet["d1"])),
                             obs_flight.SNAPSHOT_NAME)
    doc = obs_flight.read_snapshot(snap_path)
    assert doc is not None and doc["reason"]
    assert doc["corr"] == obs_events.corr_id(run_name(fleet["d1"]))
    assert doc["window"] and doc["window_rounds"] == len(doc["window"])


def test_flight_splice_across_interrupted_resume(fleet):
    """ISSUE 18 crash-exactness: the clean-stop-and-continue run's
    flight stream (with a torn tail injected at the kill point) equals
    the uninterrupted twin's under strip_timing — the resume truncated
    the tear, continued the seq numbering and deduped replays."""
    a = obs_flight.read_flight(_flight(fleet["a"]))
    b = obs_flight.read_flight(_flight(fleet["b"]))
    assert a and obs_flight.strip_timing(b) == obs_flight.strip_timing(a)
    assert [r["seq"] for r in b] == list(range(len(b)))
    rounds = [r["round"] for r in b]
    assert rounds == sorted(set(rounds))   # replays streamed nothing


def test_flight_never_touches_metrics_or_events(fleet):
    """Default-on must not move existing byte-identity drills: the
    flight recorder writes ONLY its own files (the a/c metrics equality
    in test_events_off_arms_nothing_and_metrics_identical already pins
    the metrics bytes; here: no flight rows leak into either stream)."""
    joined = json.dumps(obs_events.read_events(_events(fleet["a"])))
    assert "flight" not in joined
    assert "flight" not in json.dumps(_metric_lines(fleet["a"]))
    # --events off still flies the recorder (independent planes)
    assert os.path.exists(_flight(fleet["c"]))


def test_console_on_real_fleet(fleet):
    """The console renders the module's real runs (ledgers + heartbeats
    from actual serves, not fixtures)."""
    rows = obs_console.scan_fleet(fleet["root"])
    runs = {r["run_dir"] for r in rows}
    assert _events(fleet["d1"]).rsplit("/", 1)[0] in runs
    text = obs_console.render_table(rows)
    assert "done" in text


def test_kill_recover_ledger_byte_identical_to_unkilled_twin(tmp_path):
    """THE ledger acceptance: a kill_recover@N drill's events.jsonl is
    byte-identical (modulo wall clocks) to its unkilled twin's — the
    kill adds no record, the resumed process re-emits nothing, rungs and
    correlation id thread the re-entry."""
    pkg = "defending_against_backdoors_with_robust_learning_rate_tpu"
    base = ["--data", "synthetic", "--num_agents", "8", "--bs", "16",
            "--local_ep", "1", "--synth_train_size", "256",
            "--synth_val_size", "64", "--eval_bs", "64", "--snap", "2",
            "--seed", "5", "--num_corrupt", "2", "--poison_frac", "1.0",
            "--robustLR_threshold", "3", "--no_tensorboard",
            "--service_rounds", "6", "--service_backoff_s", "0.01",
            "--health_policy", "recover", "--platform", "cpu"]

    def run(tag, chaos, killed=False):
        cmd = [sys.executable, "-m", f"{pkg}.service.driver", *base,
               "--chaos", chaos,
               "--log_dir", str(tmp_path / f"{tag}_logs"),
               "--checkpoint_dir", str(tmp_path / f"{tag}_ck")]
        p = subprocess.run(cmd, capture_output=True, text=True,
                           timeout=600)
        # SIGKILL is -9 from subprocess.run, 137 through a shell
        want = (-9, 137) if killed else (0,)
        assert p.returncode in want, (p.returncode, p.stdout[-2000:],
                                      p.stderr[-2000:])

    # warmup banks every program variant (incl. the resumed engine's):
    # cold-vs-warm AOT hit/miss records differ by design
    run("warm", "nan@3")
    run("twin", "nan@3")                             # the unkilled twin
    run("drill", "nan@3,kill_recover@4", killed=True)   # life 1
    run("drill", "nan@3,kill_recover@4")             # life 2: the ladder
    cfg_t = SVC.replace(log_dir=str(tmp_path / "twin_logs"))
    cfg_d = SVC.replace(log_dir=str(tmp_path / "drill_logs"))
    twin = obs_events.read_events(_events(cfg_t))
    drill = obs_events.read_events(_events(cfg_d))
    assert twin and obs_events.strip_wallclock(drill) == \
        obs_events.strip_wallclock(twin)
    assert len({r["corr"] for r in drill}) == 1
    # ISSUE 18: the flight stream shares the ledger's crash-exactness —
    # the SIGKILLed run's flight.jsonl is byte-identical (non-timing
    # projection) to its unkilled twin's, and the kill left a snapshot
    fl_twin = obs_flight.read_flight(_flight(cfg_t))
    fl_drill = obs_flight.read_flight(_flight(cfg_d))
    assert fl_twin and obs_flight.strip_timing(fl_drill) == \
        obs_flight.strip_timing(fl_twin)
    assert obs_flight.read_snapshot(
        os.path.join(os.path.dirname(_flight(cfg_d)),
                     obs_flight.SNAPSHOT_NAME)) is not None
