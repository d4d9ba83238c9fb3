"""Observability subsystem (obs/): spans, in-jit defense telemetry, and
the structured heartbeat — plus their driver integration (ISSUE 3
acceptance: trace.json with >=5 span types, Defense/* + Spans/* scalars
in metrics.jsonl, status.json heartbeat, and --telemetry off bit-identity
with a build that never computes telemetry)."""

import json
import os
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from defending_against_backdoors_with_robust_learning_rate_tpu.config import Config
from defending_against_backdoors_with_robust_learning_rate_tpu.obs import (
    Heartbeat, SpanTracer, heartbeat as hb_mod, spans as spans_mod,
    telemetry)
from defending_against_backdoors_with_robust_learning_rate_tpu.obs.spans import (
    _percentile)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# --- spans ---------------------------------------------------------------

class FakeClock:
    def __init__(self, t=100.0):
        self.t = t

    def __call__(self):
        return self.t


def test_span_nesting_and_exactness(tmp_path):
    clock = FakeClock()
    tr = SpanTracer(clock=clock, annotate=False)
    with tr.span("outer"):
        clock.t += 1.0
        with tr.span("inner"):
            clock.t += 0.25
        clock.t += 0.5
    agg = tr.aggregates()
    assert agg["inner"]["count"] == 1 and agg["outer"]["count"] == 1
    # exact durations through the injected clock
    assert agg["inner"]["total_s"] == pytest.approx(0.25)
    assert agg["outer"]["total_s"] == pytest.approx(1.75)
    path = tr.write_trace(str(tmp_path / "trace.json"))
    doc = json.load(open(path))
    ev = {e["name"]: e for e in doc["traceEvents"]}
    # chrome-trace schema: complete events with microsecond ts/dur; the
    # inner span nests inside the outer on the same tid
    for e in ev.values():
        assert e["ph"] == "X" and {"name", "ts", "dur", "pid",
                                   "tid"} <= set(e)
    assert ev["inner"]["tid"] == ev["outer"]["tid"]
    assert ev["inner"]["dur"] == pytest.approx(0.25e6)
    assert ev["outer"]["dur"] == pytest.approx(1.75e6)
    assert ev["outer"]["ts"] <= ev["inner"]["ts"]
    assert (ev["inner"]["ts"] + ev["inner"]["dur"]
            <= ev["outer"]["ts"] + ev["outer"]["dur"] + 1e-6)
    # the new fields ride each event's args
    assert ev["inner"]["args"]["parent"] == ev["outer"]["args"]["id"]
    assert ev["outer"]["args"]["parent"] is None
    assert ev["inner"]["args"]["unit"] == spans_mod.SETUP_UNIT
    assert ev["outer"]["args"]["self_ms"] == pytest.approx(1500.0)
    assert doc["displayTimeUnit"] == "ms"


def test_span_parent_unit_self_and_cpu_exact():
    """Nested and sibling spans under injected clocks: ids, parents, the
    unit, self time (duration less same-thread children) and CPU time."""
    clock, cpu = FakeClock(), FakeClock(7.0)
    tr = SpanTracer(clock=clock, cpu_clock=cpu, annotate=False)
    with tr.span("setup_span"):
        clock.t += 0.125
    tr.set_unit(7)
    with tr.span("outer", note="x"):
        clock.t += 1.0
        cpu.t += 0.5
        with tr.span("a"):
            clock.t += 0.25
            cpu.t += 0.25
            with tr.span("leaf"):
                clock.t += 0.125
        with tr.span("b"):          # sibling of a
            clock.t += 0.5          # blocked: no CPU time
        clock.t += 0.25
    by = {s.name: s for s in tr.records()}
    assert len({s.id for s in by.values()}) == 5
    assert by["setup_span"].unit == spans_mod.SETUP_UNIT
    assert by["setup_span"].parent is None and by["outer"].parent is None
    assert by["a"].parent == by["outer"].id == by["b"].parent
    assert by["leaf"].parent == by["a"].id
    assert {by[n].unit for n in ("outer", "a", "b", "leaf")} == {7}
    assert by["outer"].start == pytest.approx(100.125)   # absolute clock
    assert by["outer"].end - by["outer"].start == pytest.approx(2.125)
    assert by["outer"].self_s == pytest.approx(1.25)     # less a and b
    assert by["a"].self_s == pytest.approx(0.25)         # less leaf
    assert by["b"].self_s == pytest.approx(0.5)
    assert by["outer"].cpu_s == pytest.approx(0.75)
    assert by["a"].cpu_s == pytest.approx(0.25)
    assert by["b"].cpu_s == 0.0
    assert by["outer"].args == {"note": "x"}
    agg = tr.aggregates()
    assert agg["outer"]["self_ms"] == pytest.approx(1250.0)
    assert agg["outer"]["cpu_ms"] == pytest.approx(750.0)
    assert agg["leaf"]["self_ms"] == pytest.approx(125.0)
    rows = dict(tr.scalar_rows())
    assert rows["Spans/outer/self_ms"] == pytest.approx(1250.0)
    assert rows["Spans/b/cpu_ms"] == 0.0
    # the flight recorder's view: milliseconds by name since the last take
    assert tr.unit_ms(take=False)["a"] == pytest.approx(375.0)
    assert tr.unit_ms()["outer"] == pytest.approx(2125.0)
    assert tr.unit_ms() == {}


def test_span_handed_to_another_thread_names_its_enqueuer():
    tr = SpanTracer(annotate=False)
    tr.set_unit(3)
    handed = {}

    def worker(parent):
        assert tr.handoff() is None          # nothing open on this thread
        with tr.span("metrics/emit", parent=parent):
            with tr.span("inner"):
                pass

    with tr.span("engine/eval_boundary"):
        handed["link"] = tr.handoff()
    tr.set_unit(4)                           # the loop has moved on
    t = threading.Thread(target=worker, args=(handed["link"],))
    t.start()
    t.join(timeout=10)
    assert not t.is_alive()
    by = {s.name: s for s in tr.records()}
    assert by["metrics/emit"].parent == by["engine/eval_boundary"].id
    assert by["metrics/emit"].unit == 3 and by["inner"].unit == 3
    assert by["inner"].parent == by["metrics/emit"].id
    assert by["metrics/emit"].tid != by["engine/eval_boundary"].tid
    # a handed-over child ran elsewhere: it covers none of its parent
    assert by["engine/eval_boundary"].self_s == pytest.approx(
        by["engine/eval_boundary"].end - by["engine/eval_boundary"].start)


def test_counters_with_labels():
    clock = FakeClock()
    tr = SpanTracer(clock=clock, annotate=False)
    tr.count("programs", family="round", source="compiled")
    clock.t += 1.0
    tr.count("programs", family="round", source="compiled")
    tr.count("programs", family="eval_val", source="bank_hit")
    tr.count("data_bytes_host", 4096)
    agg = tr.aggregates()
    assert agg["programs{family=round,source=compiled}"] == {"count": 2}
    assert agg["programs{family=eval_val,source=bank_hit}"] == {"count": 1}
    assert agg["data_bytes_host"] == {"count": 4096}
    rows = dict(tr.scalar_rows())
    assert rows["Spans/data_bytes_host/count"] == 4096.0
    assert "Spans/data_bytes_host/p50_ms" not in rows
    # cut by the caller's own stamp on the tracer's clock
    assert tr.counted(before=100.5) == [
        ("programs", 1, {"family": "round", "source": "compiled"})]
    assert len(tr.counted()) == 4


def test_obs_span_fires_no_completion_hook():
    seen = []
    tr = SpanTracer(annotate=False, on_end=lambda n, d: seen.append(n))
    with tr.span("round/dispatch"):
        with tr.span("obs/heartbeat_write"):
            pass
    assert seen == ["round/dispatch"]
    assert {s.name for s in tr.records()} == {"round/dispatch",
                                              "obs/heartbeat_write"}


def test_compile_listener_records_acquisitions():
    """Every program the backend acquires becomes an `xla/acquire` span
    under the span open on that thread, and a `programs` count, except
    inside `setup/acquire/<family>`, which counts its own family."""
    tr = SpanTracer(annotate=False)
    x = jnp.ones((3,))          # its own small programs: before the watch
    tr.watch_compiles()
    try:
        with tr.span("round/dispatch") as sp:
            jax.jit(lambda x: x * 3 + 1)(x).block_until_ready()
        with tr.span(spans_mod.ADOPT_PREFIX + "fam"):
            jax.jit(lambda x: x * 5 - 2)(x).block_until_ready()
    finally:
        tr.close()
    jax.jit(lambda x: x * 7 + 4)(x).block_until_ready()   # not watched
    acq = [s for s in tr.records() if s.name == spans_mod.ACQUIRE_SPAN]
    by_id = {s.id: s for s in tr.records()}
    assert [by_id[s.parent].name for s in acq] == [
        "round/dispatch", spans_mod.ADOPT_PREFIX + "fam"]
    assert all(s.args["source"] in ("compiled", "xla_cache_hit")
               and s.args["program"] for s in acq)
    assert [src for _p, src, _s in sp.acquired] == [acq[0].args["source"]]
    counted = [lab for n, _c, lab in tr.counted() if n == "programs"]
    assert counted == [{"family": acq[0].args["program"],
                        "source": acq[0].args["source"]}]


def test_span_aggregates_percentiles():
    clock = FakeClock()
    tr = SpanTracer(clock=clock, annotate=False)
    for ms in range(1, 101):          # 1..100 ms spans
        with tr.span("x"):
            clock.t += ms / 1e3
    agg = tr.aggregates()["x"]
    assert agg["count"] == 100
    assert agg["p50_ms"] == pytest.approx(51.0)
    assert agg["p95_ms"] == pytest.approx(96.0)
    assert agg["max_ms"] == pytest.approx(100.0)
    # nearest-rank helper is total-order sane
    assert _percentile([1.0], 0.95) == 1.0
    rows = dict(tr.scalar_rows())
    assert rows["Spans/x/count"] == 100.0
    assert rows["Spans/x/max_ms"] == pytest.approx(100.0)


def test_disabled_tracer_is_noop(tmp_path):
    """One attribute check and nothing else: no record, no counter, no
    compile listener, no hand-over, and never the process's current."""
    tr = SpanTracer(enabled=False)
    with tr.span("never") as sp:
        assert sp is None and tr.handoff() is None
    tr.count("never")
    tr.set_unit(5)
    tr.watch_compiles()
    assert tr.aggregates() == {} and tr.records() == []
    assert tr.unit_ms() == {} and not tr._watching
    spans_mod.set_current(tr)
    try:
        assert spans_mod.current() is None
        with spans_mod.span("never") as sp:     # module level: no-ops
            assert sp is None
        spans_mod.count("never")
    finally:
        spans_mod.set_current(None)
    assert tr.write_trace(str(tmp_path / "t.json")) is None
    assert not (tmp_path / "t.json").exists()


def test_span_tracer_thread_safety():
    tr = SpanTracer(annotate=False)

    def work():
        for _ in range(200):
            with tr.span("t"):
                pass

    threads = [threading.Thread(target=work) for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert tr.aggregates()["t"]["count"] == 800


# --- heartbeat -----------------------------------------------------------

def test_heartbeat_atomic_under_concurrent_reads(tmp_path):
    path = str(tmp_path / "status.json")
    hb = Heartbeat(path, min_interval_s=0.0)
    stop = threading.Event()
    failures = []

    def reader():
        while not stop.is_set():
            s = hb_mod.read_status(path)
            if s is None or "phase" not in s or "pid" not in s:
                failures.append(s)

    t = threading.Thread(target=reader)
    t.start()
    for i in range(300):
        hb.update(phase=f"p{i % 7}", round=i, force=True)
    stop.set()
    t.join()
    # os.replace is atomic: a reader never observes a partial/missing file
    assert failures == []
    final = hb_mod.read_status(path)
    assert final["phase"] == "exited" or final["round"] == 299
    hb.close()
    assert hb_mod.read_status(path)["phase"] == "exited"


def test_heartbeat_rate_limit_and_phase_change(tmp_path):
    clock = FakeClock()
    path = str(tmp_path / "s.json")
    hb = Heartbeat(path, min_interval_s=10.0, clock=clock)
    hb.update(round=1)                     # within interval: no write
    assert hb_mod.read_status(path)["round"] == 0
    hb.update(phase="train", round=2)      # phase change: writes
    assert hb_mod.read_status(path)["round"] == 2
    hb.update(round=3)                     # rate-limited again
    assert hb_mod.read_status(path)["round"] == 2
    clock.t += 11.0
    hb.update(round=4)                     # interval elapsed
    assert hb_mod.read_status(path)["round"] == 4


def test_heartbeat_stall_detector_semantics():
    now = 1000.0
    assert hb_mod.is_stale(None, now)
    fresh = {"updated_at": now - 10.0, "compile_in_flight": False}
    assert not hb_mod.is_stale(fresh, now)
    quiet = {"updated_at": now - 600.0, "compile_in_flight": False}
    assert hb_mod.is_stale(quiet, now)
    # the same silence during a compile is NOT a stall (a cold compile
    # is minutes of legitimate quiet)
    compiling = {"updated_at": now - 600.0, "compile_in_flight": True}
    assert not hb_mod.is_stale(compiling, now)
    assert hb_mod.is_stale({"updated_at": now - 4000.0,
                            "compile_in_flight": True}, now)


# --- telemetry: pure math ------------------------------------------------

def _cfg(**kw):
    kw.setdefault("telemetry", "full")
    return Config(data="synthetic", num_agents=8, **kw)


def test_telemetry_cosine_separates_honest_from_corrupt():
    m, k = 8, 16
    rng = np.random.default_rng(0)
    direction = rng.normal(size=(k,)).astype(np.float32)
    honest = direction[None, :] + 0.05 * rng.normal(size=(m, k))
    updates = {"w": jnp.asarray(honest, jnp.float32)}
    corrupt_flags = jnp.asarray([True, True] + [False] * (m - 2))
    # corrupt agents push the OPPOSITE direction
    updates["w"] = updates["w"].at[:2].set(-updates["w"][:2])
    agg = {"w": jnp.mean(updates["w"], axis=0)}
    out = jax.jit(lambda u, a, c: telemetry.compute(
        _cfg(), u, None, a, corrupt_flags=c))(updates, agg, corrupt_flags)
    assert float(out["tel_cos_honest"]) > 0.5
    assert float(out["tel_cos_corrupt"]) < 0.0
    assert -1.0 - 1e-5 <= float(out["tel_cos_corrupt"])
    assert float(out["tel_cos_honest"]) <= 1.0 + 1e-5
    # margin histogram is a distribution over all coordinates
    hist = np.asarray(out["tel_margin_hist"])
    assert hist.shape == (telemetry.N_MARGIN_BUCKETS,)
    assert np.isclose(hist.sum(), 1.0)
    assert 0.0 <= float(out["tel_margin_mean"]) <= 1.0
    # norm percentiles are ordered
    assert (float(out["tel_upd_norm_p50"])
            <= float(out["tel_upd_norm_p95"])
            <= float(out["tel_upd_norm_max"]))


def test_telemetry_flip_fraction_counts_negative_lr():
    lr = {"a": jnp.asarray([1.0, -1.0, -1.0, 1.0]),
          "b": jnp.asarray([[1.0, -1.0]])}
    updates = {"a": jnp.zeros((4, 4)), "b": jnp.zeros((4, 1, 2))}
    agg = {"a": jnp.zeros((4,)), "b": jnp.zeros((1, 2))}
    cfg = _cfg(robustLR_threshold=4, telemetry="basic")
    out = telemetry.compute(cfg, updates, lr, agg)
    assert float(out["tel_flip_frac"]) == pytest.approx(3.0 / 6.0)


def test_telemetry_keys_match_levels():
    assert telemetry.telemetry_keys(_cfg(telemetry="off")) == ()
    basic = telemetry.telemetry_keys(_cfg(telemetry="basic",
                                          robustLR_threshold=4))
    assert "tel_flip_frac" in basic and "tel_margin_hist" not in basic
    full = set(telemetry.telemetry_keys(_cfg()))
    assert {"tel_margin_hist", "tel_cos_honest",
            "tel_cos_corrupt"} <= full
    with pytest.raises(ValueError, match="telemetry"):
        telemetry.check_level("verbose")


def test_telemetry_sharded_matches_vmap():
    """compute_sharded under shard_map over the 8-device CPU mesh must
    reproduce compute's scalars (same math through psum/all_gather)."""
    from jax.sharding import PartitionSpec as P
    from jax import shard_map
    from defending_against_backdoors_with_robust_learning_rate_tpu.parallel.mesh import (
        AGENTS_AXIS, make_mesh)

    m, k = 8, 12
    rng = np.random.default_rng(1)
    updates = {"w": jnp.asarray(rng.normal(size=(m, k)), jnp.float32)}
    agg = {"w": jnp.mean(updates["w"], axis=0)}
    flags = jnp.asarray([True] + [False] * (m - 1))
    cfg = _cfg()
    ref = telemetry.compute(cfg, updates, None, agg, corrupt_flags=flags)

    mesh = make_mesh(8)
    f = shard_map(
        lambda u, a, c: telemetry.compute_sharded(
            cfg, u, None, a, AGENTS_AXIS, corrupt_full=c),
        mesh=mesh, in_specs=(P(AGENTS_AXIS), P(), P()),
        out_specs={key: P() for key in telemetry.telemetry_keys(cfg)},
        check_vma=False)
    sharded = f(updates, agg, flags)
    for key in ref:
        np.testing.assert_allclose(np.asarray(sharded[key]),
                                   np.asarray(ref[key]), rtol=1e-5,
                                   atol=1e-6, err_msg=key)

    # the shared-psum path (ISSUE 5): handing the vote's sign sums in as
    # `sign_sums` must reproduce the self-psum'd margins bit-for-bit —
    # that is what makes the zero-extra-psum contract safe to enforce
    sums = {"w": jnp.abs(jnp.sum(jnp.sign(updates["w"]), axis=0))}
    f2 = shard_map(
        lambda u, a, c, s: telemetry.compute_sharded(
            cfg, u, None, a, AGENTS_AXIS, corrupt_full=c, sign_sums=s),
        mesh=mesh, in_specs=(P(AGENTS_AXIS), P(), P(), P()),
        out_specs={key: P() for key in telemetry.telemetry_keys(cfg)},
        check_vma=False)
    shared = f2(updates, agg, flags, sums)
    for key in ("tel_margin_hist", "tel_margin_mean"):
        np.testing.assert_array_equal(np.asarray(shared[key]),
                                      np.asarray(sharded[key]),
                                      err_msg=key)


# --- telemetry: round-fn bit-identity ------------------------------------

def test_telemetry_off_params_bit_identical_to_full():
    """--telemetry off must leave the round program untouched; and since
    telemetry only ADDS outputs, even `full` must not change the params
    math — both pins in one: off/full final params bit-equal, and only
    full emits tel_* keys."""
    from defending_against_backdoors_with_robust_learning_rate_tpu.data.registry import (
        get_federated_data)
    from defending_against_backdoors_with_robust_learning_rate_tpu.fl.common import (
        make_normalizer)
    from defending_against_backdoors_with_robust_learning_rate_tpu.fl.rounds import (
        make_round_fn)
    from defending_against_backdoors_with_robust_learning_rate_tpu.models.registry import (
        get_model, init_params)

    cfg = Config(data="synthetic", num_agents=4, bs=16, local_ep=1,
                 synth_train_size=64, synth_val_size=32,
                 num_corrupt=1, poison_frac=1.0, robustLR_threshold=3)
    fed = get_federated_data(cfg)
    model = get_model(cfg.data, cfg.model_arch, cfg.dtype)
    norm = make_normalizer(fed.mean, fed.std, fed.raw_is_normalized)
    arrays = tuple(map(jnp.asarray, (fed.train.images, fed.train.labels,
                                     fed.train.sizes)))
    params = init_params(model, fed.train.images.shape[2:],
                         jax.random.PRNGKey(0))
    key = jax.random.PRNGKey(3)
    p_off, info_off = make_round_fn(cfg, model, norm, *arrays)(params, key)
    p_full, info_full = make_round_fn(cfg.replace(telemetry="full"), model,
                                      norm, *arrays)(params, key)
    assert not any(k.startswith("tel_") for k in info_off)
    assert any(k.startswith("tel_") for k in info_full)
    for a, b in zip(jax.tree_util.tree_leaves(p_off),
                    jax.tree_util.tree_leaves(p_full), strict=True):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# --- driver integration --------------------------------------------------

SMOKE = Config(data="synthetic", num_agents=8, bs=16, local_ep=1,
               synth_train_size=256, synth_val_size=64, eval_bs=64,
               rounds=2, snap=1, seed=5, tensorboard=False,
               num_corrupt=2, poison_frac=1.0, robustLR_threshold=3)


def _tags(jsonl_path):
    with open(jsonl_path) as f:
        return [json.loads(line) for line in f]


def test_driver_smoke_full_observability(tmp_path):
    """The ISSUE-3 acceptance run: --telemetry full produces a
    Perfetto-loadable trace.json with >=5 distinct span types, Defense/*
    and Spans/* scalars in metrics.jsonl, and a status.json heartbeat."""
    from defending_against_backdoors_with_robust_learning_rate_tpu import train
    from defending_against_backdoors_with_robust_learning_rate_tpu.utils.metrics import (
        MetricsWriter, run_name)

    cfg = SMOKE.replace(telemetry="full", log_dir=str(tmp_path / "logs"),
                        compile_cache_dir=str(tmp_path / "cache"))
    writer = MetricsWriter(cfg.log_dir, run_name(cfg), tensorboard=False)
    summary = train.run(cfg, writer=writer)

    run_dir = writer.dir
    records = _tags(os.path.join(run_dir, "metrics.jsonl"))
    tags = {r["tag"] for r in records}
    defense = {t for t in tags if t.startswith("Defense/")}
    assert {"Defense/Update_Norm_P50", "Defense/LR_Flip_Fraction",
            "Defense/Vote_Margin_Mean",
            "Defense/Cosine_Honest_To_Agg"} <= defense
    assert sum(1 for t in defense if "Vote_Margin_Hist" in t) \
        == telemetry.N_MARGIN_BUCKETS
    assert any(t.startswith("Spans/") for t in tags)
    # margin-hist rows at one boundary sum to 1 (a distribution)
    hist = [r["value"] for r in records
            if r["tag"].startswith("Defense/Vote_Margin_Hist/")
            and r["step"] == 2]
    assert np.isclose(sum(hist), 1.0)

    doc = json.load(open(os.path.join(run_dir, "trace.json")))
    names = {e["name"] for e in doc["traceEvents"]}
    assert len(names) >= 5, names
    assert {"round/dispatch", "eval/val_dispatch",
            "eval/poison_dispatch", "metrics/emit"} <= names
    assert summary["spans"]["round/dispatch"]["count"] == cfg.rounds
    # the engine's own spans: every public call is a parent of what it
    # does, set-up is split under engine/build, the observers' writes
    # are inside spans, and each span names its unit
    ev = doc["traceEvents"]
    by_id = {e["args"]["id"]: e for e in ev}

    def parent_name(e):
        up = by_id.get(e["args"]["parent"])
        return up["name"] if up else None

    assert {"engine/build", "engine/dispatch", "engine/eval_boundary",
            "engine/post_unit", "setup/data", "setup/model_init",
            "setup/place", "setup/build_programs", "setup/obs",
            "setup/acquire/eval_val", "obs/heartbeat_write",
            "obs/flight_write", "obs/memory_poll"} <= names
    for e in ev:
        want = {"round/dispatch": "engine/dispatch",
                "round/data_prep": "engine/dispatch",
                "eval/val_dispatch": "engine/eval_boundary",
                "metrics/emit": "engine/eval_boundary",
                "setup/data": "engine/build",
                "setup/data/poison": "setup/data",
                "obs/flight_write": "engine/post_unit"}.get(e["name"])
        if want:
            assert parent_name(e) == want, e
        if e["name"].startswith(("setup/", "engine/build")):
            assert e["args"]["unit"] == "setup"
    assert sorted(e["args"]["unit"] for e in ev
                  if e["name"] == "metrics/emit") == [1, 2]
    assert summary["spans"]["dispatch{family=round}"] == {
        "count": cfg.rounds}
    assert any(k.startswith("programs{family=eval_val,")
               for k in summary["spans"])
    # the flight record's per-span milliseconds are the tracer's unit group
    from defending_against_backdoors_with_robust_learning_rate_tpu.obs import (
        flight)
    recs = flight.read_flight(os.path.join(run_dir, flight.STREAM_NAME))
    assert [r["round"] for r in recs] == [1, 2]
    assert all(r["spans"]["round/dispatch"] > 0 for r in recs)
    assert "engine/build" in recs[0]["spans"]
    assert "engine/build" not in recs[1]["spans"]

    status = json.load(open(os.path.join(cfg.log_dir, "status.json")))
    assert status["phase"] == "done"
    assert status["pid"] == os.getpid()
    assert status["compile_in_flight"] is False
    assert status["round"] == cfg.rounds


def test_current_tracer_follows_newest_engine(tmp_path):
    """`spans.current()` is the tracer of the newest RoundEngine, None
    under --no_spans; close() stops the compile listener and keeps the
    records readable."""
    from defending_against_backdoors_with_robust_learning_rate_tpu import train
    from defending_against_backdoors_with_robust_learning_rate_tpu.utils.metrics import (
        NullWriter)

    cfg = SMOKE.replace(log_dir=str(tmp_path / "logs"), flight="off",
                        heartbeat=False)
    a = train.RoundEngine(cfg, writer=NullWriter())
    try:
        assert spans_mod.current() is a.tracer and a.tracer._watching
        b = train.RoundEngine(cfg.replace(seed=6), writer=NullWriter())
        try:
            assert spans_mod.current() is b.tracer
            with spans_mod.span("setup/data/poison"):   # to the newest
                pass
            assert [s.name for s in b.tracer.records()][-1] == \
                "setup/data/poison"
        finally:
            b.close()
        assert not b.tracer._watching
        assert spans_mod.current() is b.tracer      # readers come after
        build = [s for s in b.tracer.records() if s.name == "engine/build"]
        assert len(build) == 1 and build[0].parent is None
        c = train.RoundEngine(cfg.replace(spans=False), writer=NullWriter())
        try:
            assert spans_mod.current() is None and not c.tracer.enabled
            c.dispatch((1,))
            c.eval_boundary(1)
            c.post_unit()
            c.drain_flush()
            assert c.tracer.records() == []
        finally:
            c.close()
    finally:
        a.close()
        spans_mod.set_current(None)


def test_driver_telemetry_sync_async_defense_parity(tmp_path):
    """Defense/* scalars ride the MetricsDrain: the async stream must be
    bit-identical to --sync_metrics for every Defense record."""
    from defending_against_backdoors_with_robust_learning_rate_tpu import train
    from defending_against_backdoors_with_robust_learning_rate_tpu.utils.metrics import (
        MetricsWriter, run_name)

    base = SMOKE.replace(telemetry="basic",
                         compile_cache_dir=str(tmp_path / "cache"))

    def records(mode_dir, **kw):
        cfg = base.replace(log_dir=str(tmp_path / mode_dir), **kw)
        writer = MetricsWriter(cfg.log_dir, run_name(cfg),
                               tensorboard=False)
        train.run(cfg, writer=writer)
        return [r for r in _tags(os.path.join(writer.dir, "metrics.jsonl"))
                if r["tag"].startswith("Defense/")]

    ra = records("async")
    rs = records("sync", async_metrics=False)
    assert ra == rs and len(ra) >= 2 * 4  # >=4 Defense rows per boundary


def test_driver_profile_rounds_window_report_and_off_bit_identity(
        tmp_path, monkeypatch):
    """ISSUE-5 acceptance, driver side: --profile_rounds 2 samples a
    steady capture window (trace + capture_meta under <run_dir>/profile),
    degrades gracefully on XLA:CPU (no device track), feeds the
    heartbeat the HBM watermarks, and the run report renders from the
    run dir — while the default --profile_rounds 0 stream stays
    bit-identical (every non-timing metrics row equal)."""
    from defending_against_backdoors_with_robust_learning_rate_tpu import train
    from defending_against_backdoors_with_robust_learning_rate_tpu.obs import (
        attribution, report)
    from defending_against_backdoors_with_robust_learning_rate_tpu.utils.metrics import (
        MetricsWriter, run_name)

    # fake allocator stats: XLA:CPU has none, but the watermark plumbing
    # (per-captured-unit polling -> Memory/* rows + heartbeat fields)
    # must be exercised in tier-1, not first on a TPU session
    monkeypatch.setattr(attribution, "memory_watermarks",
                        lambda device=None: {"hbm_live_bytes": 1000,
                                             "hbm_peak_bytes": 2000})

    def run(mode_dir, **kw):
        cfg = SMOKE.replace(log_dir=str(tmp_path / mode_dir),
                            compile_cache_dir=str(tmp_path / "cache"),
                            rounds=4, snap=2, **kw)
        writer = MetricsWriter(cfg.log_dir, run_name(cfg),
                               tensorboard=False)
        summary = train.run(cfg, writer=writer)
        return cfg, writer.dir, summary

    cfg, run_dir, summary = run("prof", profile_rounds=2)
    # the window captured 2 steady rounds (units 2..3; never the compile)
    meta = json.load(open(os.path.join(run_dir, "profile",
                                       "capture_meta.json")))
    assert meta["rounds"] == 2 and meta["backend"] == "cpu"
    assert attribution.find_trace_file(
        os.path.join(run_dir, "profile")) is not None
    # XLA:CPU: no device track, said so instead of fake numbers
    assert summary["attribution"]["device_present"] is False
    # memory watermarks: summary + Memory/* rows + heartbeat fields
    assert summary["memory"]["hbm_peak_bytes"] == 2000
    tags = {r["tag"] for r in _tags(os.path.join(run_dir,
                                                 "metrics.jsonl"))}
    assert {"Memory/HBM_Live_Bytes", "Memory/HBM_Peak_Bytes"} <= tags
    status = json.load(open(os.path.join(cfg.log_dir, "status.json")))
    assert status["hbm_peak_bytes"] == 2000

    # the run report renders from the run dir and passes the repo pins
    assert report.main([run_dir, "--backend", "cpu"]) == 0
    assert os.path.exists(os.path.join(run_dir, "report.md"))
    doc = json.load(open(os.path.join(run_dir, "report.json")))
    assert doc["attribution"]["device_present"] is False

    # default-off run: no capture dir, no Device/* rows (Memory rows stay
    # — the watermark poll is backend-gated, not profile-gated), and
    # every value-carrying row equal to the profiled run's
    _, off_dir, off_summary = run("off")
    assert "attribution" not in off_summary
    assert not os.path.exists(os.path.join(off_dir, "profile"))
    off_tags = {r["tag"] for r in _tags(os.path.join(off_dir,
                                                     "metrics.jsonl"))}
    assert not any(t.startswith("Device/") for t in off_tags)

    def value_rows(d):
        # single source (ISSUE 15 satellite): obs/constants.py
        from defending_against_backdoors_with_robust_learning_rate_tpu.obs.constants import (
            NON_TIMING_PREFIXES)
        return [r for r in _tags(os.path.join(d, "metrics.jsonl"))
                if not r["tag"].startswith(NON_TIMING_PREFIXES)]

    prof_rows = value_rows(run_dir)
    assert prof_rows == value_rows(off_dir) and len(prof_rows) >= 2 * 7


def test_run_name_distinguishes_fault_sweep_cells():
    """Satellite: two sweep cells differing only in rlr_threshold_mode or
    faults_spare_corrupt must land in different run dirs."""
    from defending_against_backdoors_with_robust_learning_rate_tpu.utils.metrics import (
        run_name)

    base = Config(dropout_rate=0.3)
    names = {run_name(base),
             run_name(base.replace(rlr_threshold_mode="scaled")),
             run_name(base.replace(faults_spare_corrupt=True)),
             run_name(base.replace(rlr_threshold_mode="scaled",
                                   faults_spare_corrupt=True))}
    assert len(names) == 4
    # and the faultless name is unchanged by the fault-only fields
    assert run_name(Config()) == run_name(
        Config(rlr_threshold_mode="scaled", faults_spare_corrupt=True))


def _load_sweep_module():
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "sweep_faults", os.path.join(ROOT, "scripts", "sweep_faults.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_sweep_faults_rows_and_cells(tmp_path, monkeypatch):
    """scripts/sweep_faults.py: one JSONL row per cell with the sweep axes
    and the outcome scalars, crash-safe append. train.run is stubbed so
    tier-1 tests the driver logic, not another flagship compile (the real
    1-cell run is the slow-tier test below)."""
    mod = _load_sweep_module()
    # dropout=0 disables the faults path entirely, so the threshold mode
    # cannot matter there: a single baseline cell, not one per mode
    assert mod.sweep_cells([0.0, 0.3], ["abs", "scaled"]) == [
        (0.0, "abs"), (0.3, "abs"), (0.3, "scaled")]

    from defending_against_backdoors_with_robust_learning_rate_tpu import train
    seen = []

    def fake_run(cfg):
        seen.append(cfg)
        return {"round": cfg.rounds, "val_acc": 0.9, "val_loss": 0.3,
                "poison_acc": 0.1, "poison_loss": 2.0,
                "rounds_per_sec": 5.0}

    monkeypatch.setattr(train, "run", fake_run)
    out = tmp_path / "sweep.jsonl"
    rc = mod.main([
        "--dropout_rates", "0,0.3", "--modes", "scaled", "--rounds", "2",
        "--out", str(out), "--log_dir", str(tmp_path / "logs")])
    assert rc == 0
    rows = [json.loads(line) for line in open(out)]
    assert len(rows) == 2 and len(seen) == 2
    assert [r["dropout_rate"] for r in rows] == [0.0, 0.3]
    for row, cfg in zip(rows, seen, strict=True):
        assert row["rlr_threshold_mode"] == "scaled"
        assert row["faults_spare_corrupt"] is True
        assert {"val_acc", "poison_acc", "rounds_per_sec"} <= set(row)
        assert cfg.faults_spare_corrupt and cfg.rlr_threshold_mode == "scaled"
    # distinct cells land in distinct run dirs (the run_name satellite)
    from defending_against_backdoors_with_robust_learning_rate_tpu.utils.metrics import (
        run_name)
    assert run_name(seen[1]) != run_name(seen[0])


@pytest.mark.slow  # one real flagship-shaped cell (~50s CPU compile);
# the sweep driver logic is covered by the stubbed tier-1 test above
def test_sweep_faults_driver_e2e(tmp_path):
    mod = _load_sweep_module()
    out = tmp_path / "sweep.jsonl"
    rc = mod.main([
        "--dropout_rates", "0.3", "--modes", "scaled", "--rounds", "1",
        "--snap", "1", "--synth_train_size", "256", "--telemetry", "off",
        "--out", str(out), "--log_dir", str(tmp_path / "logs")])
    assert rc == 0
    rows = [json.loads(line) for line in open(out)]
    assert len(rows) == 1
    assert rows[0]["dropout_rate"] == 0.3
    assert {"val_acc", "poison_acc", "rounds_per_sec"} <= set(rows[0])
