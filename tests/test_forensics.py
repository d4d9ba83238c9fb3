"""Forensics layer (ISSUE 18): flight recorder, anomaly-triggered
profiling, regression explain.

Acceptance drilled here:
- flight crash-exactness mirrors the event ledger: bounded ring, torn
  tails truncated on open, resumed seq numbering, replay dedupe via the
  round high-water mark, atomic snapshots that outlive close();
- ``strip_timing`` is the byte-comparison projection (the twin drills
  in test_fleet_obs compare real serve() streams through it);
- the profile trigger's hard budget: at most MAX_CAPTURES windows per
  process life, an explicit --profile_rounds capture owns the seat;
- ``span_zscores`` fires on a spike and stays quiet on flat history;
- ``obs/explain`` names the planted phase on a synthetic regression and
  the ``bench_trajectory.py --explain`` CLI exits 0/1/2.

Integration (real serve() drills) lives in test_fleet_obs.py.
"""

import json
import os
import subprocess
import sys
from types import SimpleNamespace

import pytest

from defending_against_backdoors_with_robust_learning_rate_tpu.obs import (
    events as obs_events, explain as obs_explain, flight as obs_flight,
    trigger as obs_trigger)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# --------------------------------------------------------------------------
# flight recorder
# --------------------------------------------------------------------------


def _fly(tmp_path, **kw):
    kw.setdefault("run", "r")
    kw.setdefault("corr", "abc123")
    kw.setdefault("slot", "p0")
    return obs_flight.FlightRecorder(
        str(tmp_path / obs_flight.STREAM_NAME), **kw)


def _spin(fr, rounds, drain_depth=2):
    for rnd in rounds:
        fr.begin_unit()
        fr.observe_span("round/dispatch", 0.001)
        fr.end_unit(rnd, unit_rounds=1, drain_depth=drain_depth)


def test_flight_ring_bound_and_record_shape(tmp_path):
    fr = _fly(tmp_path, window=4)
    _spin(fr, range(6))
    win = fr.window()
    assert len(win) == 4 and fr.seq == 6       # ring bounded, stream not
    assert [r["round"] for r in win] == [2, 3, 4, 5]
    rec = win[-1]
    # the fixed field order: non-timing head, timing/volatile tail, t
    assert list(rec) == ["seq", "v", "round", "corr", "slot", "rounds",
                         "gap_ms", "spans", "drain_depth", "buffer_fill",
                         "hbm_live_bytes", "hbm_peak_bytes", "t"]
    assert rec["corr"] == "abc123" and rec["slot"] == "p0"
    assert rec["spans"]["round/dispatch"] == pytest.approx(1.0)
    assert rec["drain_depth"] == 2 and rec["gap_ms"] is not None
    assert len(obs_flight.read_flight(fr.path)) == 6
    fr.close()


def test_flight_notes_ride_next_record_only(tmp_path):
    fr = _fly(tmp_path)
    fr.note(buffer_fill=0.75, hbm_live_bytes=None)   # None never lands
    fr.begin_unit()
    fr.end_unit(0)
    fr.begin_unit()
    fr.end_unit(1)
    recs = obs_flight.read_flight(fr.path)
    assert recs[0]["buffer_fill"] == 0.75
    assert recs[0]["hbm_live_bytes"] is None
    assert recs[1]["buffer_fill"] is None            # consumed, not sticky
    fr.close()


def test_flight_torn_tail_resume_and_replay_dedupe(tmp_path):
    fr = _fly(tmp_path)
    _spin(fr, range(4))
    fr.close()
    size = os.path.getsize(fr.path)
    with open(fr.path, "ab") as f:                   # SIGKILL mid-write
        f.write(b'{"seq": 99, "round')
    fr2 = _fly(tmp_path)
    assert os.path.getsize(fr2.path) == size         # torn tail gone
    assert fr2.seq == 4 and fr2.hw == 3
    assert [r["round"] for r in fr2.window()] == [0, 1, 2, 3]
    # a crash-exact replay of round 2 refreshes the ring, streams nothing
    fr2.begin_unit()
    assert fr2.end_unit(2) is None
    assert os.path.getsize(fr2.path) == size
    assert fr2.seq == 4
    replayed = next(r for r in fr2.window() if r["round"] == 2)
    assert replayed["seq"] == 2                      # original seq kept
    # fresh progress streams with the resumed numbering
    fr2.begin_unit()
    rec = fr2.end_unit(4)
    assert rec["seq"] == 4
    assert [r["seq"] for r in obs_flight.read_flight(fr2.path)] == \
        [0, 1, 2, 3, 4]
    fr2.close()


def test_flight_strip_timing_projection(tmp_path):
    fr = _fly(tmp_path)
    _spin(fr, range(2))
    fr.close()
    recs = obs_flight.read_flight(fr.path)
    strict = obs_flight.strip_timing(recs)
    assert strict == [
        {"seq": 0, "v": 1, "round": 0, "corr": "abc123", "slot": "p0",
         "rounds": 1},
        {"seq": 1, "v": 1, "round": 1, "corr": "abc123", "slot": "p0",
         "rounds": 1}]
    loose = obs_flight.strip_timing(recs, drop_volatile=False)
    assert loose[0]["drain_depth"] == 2
    assert "t" not in loose[0] and "spans" not in loose[0]


def test_flight_snapshot_atomic_readable_and_post_close(tmp_path):
    fr = _fly(tmp_path, window=4)
    _spin(fr, range(3))
    fr.observe_span("eval/loop", 0.002)              # mid-round spans
    path = fr.snapshot("health/discard", 2, extra_b=2, extra_a=1)
    doc = obs_flight.read_snapshot(path)
    assert doc["reason"] == "health/discard" and doc["round"] == 2
    assert doc["run"] == "r" and doc["corr"] == "abc123"
    assert doc["window_rounds"] == 3 == len(doc["window"])
    assert doc["extra_a"] == 1 and doc["extra_b"] == 2
    assert doc["current_spans"]["eval/loop"] == pytest.approx(2.0)
    # latest incident wins, and the ring outlives the stream handle
    fr.close()
    fr.snapshot("clean_exit", 3)
    doc = obs_flight.read_snapshot(path)
    assert doc["reason"] == "clean_exit"
    assert "current_spans" in doc                    # spans still pending
    assert not [p for p in os.listdir(tmp_path) if ".tmp." in p]


def test_flight_io_failure_disables_never_raises(tmp_path):
    blocker = tmp_path / "blocked"
    blocker.write_text("a file where a dir must go")
    fr = obs_flight.FlightRecorder(
        str(blocker / obs_flight.STREAM_NAME))
    assert not fr.enabled
    fr.begin_unit()
    assert fr.end_unit(0) is None                    # all methods no-op
    assert fr.snapshot("incident", 0) is None
    # a write failure mid-run flips enabled off, run continues
    fr2 = _fly(tmp_path)
    _spin(fr2, range(1))
    fr2._f.close()                                   # simulate dead disk
    fr2.begin_unit()
    assert fr2.end_unit(1) is None and not fr2.enabled
    fr2.observe_span("x", 0.1)
    assert fr2.end_unit(2) is None
    # the unexported recorder path: empty path disables cleanly
    fr3 = obs_flight.FlightRecorder("")
    assert not fr3.enabled and fr3.snapshot("x") is None


def test_read_flight_stops_at_unparseable_line(tmp_path):
    p = tmp_path / obs_flight.STREAM_NAME
    p.write_text('{"seq": 0, "round": 0}\nnot json\n{"seq": 9}\n')
    recs = obs_flight.read_flight(str(p))
    assert [r["seq"] for r in recs] == [0]
    assert obs_flight.read_flight(str(tmp_path / "absent.jsonl")) == []


# --------------------------------------------------------------------------
# trigger
# --------------------------------------------------------------------------


class _FakeProf:
    """The RoundProfiler surface the trigger drives."""

    def __init__(self, n_rounds, trace_dir, attr=None):
        self.n_rounds = n_rounds
        self.trace_dir = trace_dir
        self.done = False
        self.captured = 0
        self.closed = False
        self._attr = attr if attr is not None else {
            "device_present": True, "collective_frac": 0.2,
            "per_round": {"compute_ms": 5.0, "collective_ms": 1.0,
                          "gap_ms": 0.5}}

    def close(self, params=None):
        self.closed = True

    def result(self):
        return self._attr


def _trig(tmp_path, eng=None, **kw):
    eng = eng or SimpleNamespace(flight=None, prof=None, params=None)
    made = []

    def factory(n, trace_dir):
        made.append(_FakeProf(n, trace_dir))
        return made[-1]

    kw.setdefault("make_profiler", factory)
    return (obs_trigger.ProfileTrigger(eng, str(tmp_path), **kw),
            eng, made)


def test_trigger_budget_exhaustion(tmp_path):
    """THE budget drill: two incident-armed windows run to completion,
    the third incident is refused — an unstable run must not profile
    itself into the ground."""
    led = obs_events.EventLedger(str(tmp_path / "events.jsonl"), run="r")
    prev = obs_events.install(led)
    try:
        trig, eng, made = _trig(tmp_path, n_rounds=2)
        for capture in range(obs_trigger.MAX_CAPTURES):
            trig.note_incident("health/discard", 3)
            trig.step(4)                             # arms
            assert eng.prof is made[-1]
            assert made[-1].trace_dir.endswith(f"cap{capture}")
            trig.step(5)                             # window still open
            made[-1].done = True
            trig.step(6)                             # closes + attributes
            assert eng.prof is None
            assert trig.captures == capture + 1
        trig.note_incident("health/rollback", 7)     # budget exhausted
        trig.step(8)
        assert len(made) == obs_trigger.MAX_CAPTURES
        assert trig._pending is None
    finally:
        obs_events.install(prev)
        led.close()
    evs = [r["event"] for r in obs_events.read_events(led.path)]
    assert evs.count("obs/trigger_armed") == 2
    assert evs.count("obs/trigger_capture") == 2
    assert evs.count("obs/trigger_attribution") == 2
    armed = next(r for r in obs_events.read_events(led.path)
                 if r["event"] == "obs/trigger_armed")
    assert armed["severity"] == "warn"
    assert armed["cause"] == "health/discard"


def test_trigger_explicit_profile_owns_seat(tmp_path):
    trig, eng, made = _trig(tmp_path)
    eng.prof = object()          # a --profile_rounds capture is active
    trig.note_incident("health/discard", 3)
    trig.step(4)
    assert trig.prof is None and not made      # trigger never preempts


def test_trigger_zscore_arms_and_snapshots(tmp_path):
    win = [{"spans": {"round/dispatch": 5.0}} for _ in range(12)]
    win.append({"spans": {"round/dispatch": 80.0}})
    fr = obs_flight.FlightRecorder(
        str(tmp_path / obs_flight.STREAM_NAME), run="r")
    fr._ring.extend(win)
    eng = SimpleNamespace(flight=fr, prof=None, params=None)
    trig, eng, made = _trig(tmp_path, eng=eng)
    trig.step(13)
    assert made and made[-1] is eng.prof
    snap = obs_flight.read_snapshot(
        str(tmp_path / obs_flight.SNAPSHOT_NAME))
    assert snap["reason"].startswith("trigger_armed:zscore:")
    fr.close()
    # flat history never arms
    fr2 = obs_flight.FlightRecorder("", run="r")
    fr2._ring.extend([{"spans": {"round/dispatch": 5.0}}] * 13)
    trig2, eng2, made2 = _trig(tmp_path,
                               eng=SimpleNamespace(flight=fr2, prof=None,
                                                   params=None))
    trig2.step(13)
    assert not made2


def test_trigger_finalize_harvests_or_discards(tmp_path):
    # a window that captured something is harvested at exit
    trig, eng, made = _trig(tmp_path)
    trig.note_incident("chaos/nan", 2)
    trig.step(3)
    made[-1].captured = 2
    trig.finalize(5)
    assert made[-1].closed and trig.captures == 1 and eng.prof is None
    # an empty window is torn down without burning evidence
    trig2, eng2, made2 = _trig(tmp_path)
    trig2.note_incident("chaos/nan", 2)
    trig2.step(3)
    trig2.finalize(4)
    assert made2[-1].closed and trig2.captures == 0
    assert trig2.prof is None and eng2.prof is None


def test_span_zscores_spike_flat_and_short_window():
    spike = [{"spans": {"a": 1.0}} for _ in range(9)]
    spike.append({"spans": {"a": 50.0}})
    z = obs_trigger.span_zscores(spike, min_points=8)
    assert z["a"] >= obs_trigger.Z_THRESHOLD
    flat = [{"spans": {"a": 1.0}} for _ in range(10)]
    zf = obs_trigger.span_zscores(flat, min_points=8)
    assert abs(zf["a"]) < obs_trigger.Z_THRESHOLD
    assert obs_trigger.span_zscores(spike[:5], min_points=8) == {}
    # a span with a thin history is skipped, not mis-scored
    thin = [{"spans": {"a": 1.0}} for _ in range(9)]
    thin.append({"spans": {"a": 1.0, "b": 99.0}})
    assert "b" not in obs_trigger.span_zscores(thin, min_points=8)


# --------------------------------------------------------------------------
# explain
# --------------------------------------------------------------------------


def test_span_family_mapping():
    fam = obs_explain.span_family
    assert fam("bench/data") == "compile"
    assert fam("bench/aot_acquire") == "compile"
    assert fam("bench/steady_blocks") == "steady"
    assert fam("round/dispatch") == "steady"
    assert fam("prefetch/wait") == "steady"
    assert fam("eval/loop") == "eval"
    assert fam("metrics/drain") == "eval"
    assert fam("drain/flush") == "drain"
    assert fam("ckpt/save") == "checkpoint"
    assert fam("mystery/thing") == "other"


def _artifact(path, value, steady_ms, compile_s, collective=None):
    """A minimal bench.py result JSON with a steady + compile span."""
    doc = {"metric": "fl_rounds_per_sec", "value": value,
           "unit": "rounds/s", "compile_s": compile_s, "chain": 4,
           "blocks": 8,
           "spans": {"bench/steady_blocks": {
                         "count": 8, "total_s": steady_ms * 32 / 1e3,
                         "p95_ms": steady_ms},
                     "bench/data": {"count": 1, "total_s": compile_s}}}
    if collective is not None:
        doc["attribution"] = {"device_present": True,
                              "collective_frac": collective}
    path.write_text(json.dumps(doc))
    return str(path)


def test_explain_names_planted_steady_regression(tmp_path):
    base = _artifact(tmp_path / "base.json", 10.0, 5.0, 2.0)
    cand = _artifact(tmp_path / "cand.json", 7.0, 9.0, 2.0)
    doc = obs_explain.explain_paths(base, cand)
    assert doc["verdict"]["regressed"]
    assert doc["verdict"]["phase"] == "steady"
    assert doc["normalized"]       # blocks*chain units on both sides
    assert doc["families"]["steady"]["delta_pct"] == pytest.approx(
        80.0, abs=0.1)
    assert doc["value_delta_pct"] == pytest.approx(-30.0, abs=0.1)
    text = obs_explain.render_text(doc)
    assert "REGRESSED — phase: steady" in text[0]
    md = obs_explain.render_markdown_section(doc)
    assert md.startswith("## Regression forensics")
    assert "**steady**" in md


def test_explain_compile_and_collective_classification(tmp_path):
    # compile_s growth reclassifies even when the span table is quiet
    # (an AOT-miss recompile bypasses the bench/data span entirely)
    base = _artifact(tmp_path / "b.json", 10.0, 5.0, 2.0)
    cand = _artifact(tmp_path / "c.json", 9.9, 5.0, 2.0)
    doc = json.loads((tmp_path / "c.json").read_text())
    doc["compile_s"] = 9.0                 # scalar only, span unchanged
    (tmp_path / "c.json").write_text(json.dumps(doc))
    doc = obs_explain.explain_paths(base, cand)
    assert doc["verdict"]["phase"] == "compile"
    assert "compile_s grew" in doc["verdict"]["note"]
    # a collective-share move is named next to the phase
    base = _artifact(tmp_path / "b2.json", 10.0, 5.0, 2.0,
                     collective=0.10)
    cand = _artifact(tmp_path / "c2.json", 7.0, 9.0, 2.0,
                     collective=0.30)
    doc = obs_explain.explain_paths(base, cand)
    assert doc["collective_shift"] == pytest.approx(0.20)
    assert "collective share rose" in doc["verdict"]["note"]


def test_explain_session_record_and_run_dir_sides(tmp_path):
    rec = tmp_path / "BENCH_r07.json"
    rec.write_text(json.dumps({
        "n": 7, "rc": 0,
        "parsed": json.loads(
            open(_artifact(tmp_path / "raw.json", 8.0, 5.0, 2.0))
            .read())}))
    side = obs_explain.load_side(str(rec))
    assert side["label"] == "r07" and side["kind"] == "artifact"
    assert side["units"] == 32.0
    # a run dir side: metrics.jsonl spans + a flight snapshot reason
    run_dir = tmp_path / "run"
    run_dir.mkdir()
    with open(run_dir / "metrics.jsonl", "w") as f:
        for tag, value in (
                ("Throughput/Rounds_Per_Sec", 1.5),
                ("Spans/round/dispatch/count", 8),
                ("Spans/round/dispatch/total_s", 0.4),
                ("Spans/eval/loop/count", 2),
                ("Spans/eval/loop/total_s", 0.1)):
            f.write(json.dumps({"tag": tag, "value": value,
                                "step": 8}) + "\n")
    fr = obs_flight.FlightRecorder(
        str(run_dir / obs_flight.STREAM_NAME), run="run")
    fr.snapshot("health/rollback", 5)
    fr.close()
    side = obs_explain.load_side(str(run_dir))
    assert side["kind"] == "run_dir" and side["value"] == 1.5
    assert side["units"] == 8
    assert side["incident"] == "health/rollback"
    assert obs_explain._per_unit_ms(side, "round/dispatch") == \
        pytest.approx(50.0)
    doc = obs_explain.explain_paths(str(run_dir), str(run_dir))
    assert not doc["verdict"]["regressed"]
    assert "last flight snapshot reason: health/rollback" in \
        "\n".join(obs_explain.render_text(doc))


def test_explain_malformed_inputs(tmp_path):
    nojson = tmp_path / "x.json"
    nojson.write_text("{not json")
    with pytest.raises(obs_explain.MalformedInput):
        obs_explain.load_side(str(nojson))
    shapeless = tmp_path / "y.json"
    shapeless.write_text(json.dumps({"neither": "shape"}))
    with pytest.raises(obs_explain.MalformedInput):
        obs_explain.load_side(str(shapeless))
    empty_dir = tmp_path / "d"
    empty_dir.mkdir()
    with pytest.raises(obs_explain.MalformedInput, match="metrics"):
        obs_explain.load_side(str(empty_dir))


def test_explain_cli_rc_0_1_2(tmp_path):
    """scripts/bench_trajectory.py --explain mirrors the gate's exit
    codes: 0 pass, 1 regressed past tolerance, 2 malformed."""
    script = os.path.join(REPO, "scripts", "bench_trajectory.py")
    base = _artifact(tmp_path / "base.json", 10.0, 5.0, 2.0)
    cand = _artifact(tmp_path / "cand.json", 7.0, 9.0, 2.0)

    def cli(*args):
        return subprocess.run([sys.executable, script, "--explain",
                               *args], capture_output=True, text=True)

    r = cli(base, cand)
    assert r.returncode == 1, r.stderr
    assert "REGRESSED — phase: steady" in r.stdout
    assert cli(base, base).returncode == 0
    bad = tmp_path / "bad.json"
    bad.write_text("{not json")
    r = cli(base, str(bad))
    assert r.returncode == 2 and "ERROR" in r.stderr
    # a loose tolerance flips the verdict
    r = subprocess.run([sys.executable, script, "--explain", base, cand,
                        "--tolerance", "0.5"],
                       capture_output=True, text=True)
    assert r.returncode == 0


def test_gate_fail_auto_explains_with_sources(tmp_path):
    """A trajectory FAIL localizes itself when the failing point's and
    its group-best's source artifacts are still on disk."""
    script = os.path.join(REPO, "scripts", "bench_trajectory.py")
    _artifact(tmp_path / "good.json", 10.0, 5.0, 2.0)
    _artifact(tmp_path / "slow.json", 7.0, 9.0, 2.0)
    traj = {"version": 1, "tolerance": 0.15, "series": [
        {"label": "good", "ok": True, "rounds_per_sec": 10.0,
         "group": "tpu|fmnist|f32", "source": "good.json"},
        {"label": "slow", "ok": True, "rounds_per_sec": 7.0,
         "group": "tpu|fmnist|f32", "source": "slow.json"}]}
    p = tmp_path / "traj.json"
    p.write_text(json.dumps(traj))
    r = subprocess.run([sys.executable, script, "--trajectory", str(p)],
                       capture_output=True, text=True)
    assert r.returncode == 1
    assert "REGRESSED — phase: steady" in r.stdout
    # sources gone -> the FAIL prints the hint, not a crash
    traj["series"][1]["source"] = "deleted.json"
    p.write_text(json.dumps(traj))
    r = subprocess.run([sys.executable, script, "--trajectory", str(p)],
                       capture_output=True, text=True)
    assert r.returncode == 1 and "[explain] hint" in r.stdout
