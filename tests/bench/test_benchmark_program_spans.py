"""The six per-layer metrics read from the program's own tracer
(obs/spans.py): the readers on a tracer with injected clocks, where every
number can be worked out by hand; `trace/program_spans.py` on a hand-built
XSpace; and tiny traced runs on the CPU, with the program's spans on, from
an empty cache directory, and under --no_spans. What a CPU run prints
names the CPU; no number from here is a device metric."""

import contextlib
import io
import json
import types

import jax
import pytest

from benchmark import harness, program_view, registry
from benchmark.trace import program_spans

from defending_against_backdoors_with_robust_learning_rate_tpu.obs import (
    spans as spans_mod)
from defending_against_backdoors_with_robust_learning_rate_tpu.utils import (
    compile_cache)

from test_benchmark_trace import device_plane, event, plane
from tiny_root import make_root, repo_benchmark

NEW = ("setup_data_s", "setup_programs_s", "programs_compiled",
       "engine_host_ms", "obs_io_max_ms", "idle_outside_spans_pct")
HOST = NEW[:5]
ENTRIES = {m["name"]: m for m in repo_benchmark()["per_layer"]}


def reader(name):
    bench = registry.load_benchmark()
    return registry.load_module(registry.search_dirs(bench),
                                "layer_metrics", name)


# ---- the readers on a tracer with injected clocks -------------------------
class Clock:
    def __init__(self, t):
        self.t = t

    def __call__(self):
        return self.t


@pytest.fixture()
def staged():
    """A set-up of 10 s, then a window of two units from t = 120, as the
    engine records them; the harness's own spans beside them."""
    clock, cpu = Clock(100.0), Clock(0.0)
    tr = spans_mod.SpanTracer(clock=clock, cpu_clock=cpu, annotate=False)
    with tr.span("engine/build"):
        with tr.span("setup/data"):
            with tr.span("setup/data/poison"):
                clock.t += 3.0
            clock.t += 1.0
        with tr.span("setup/place"):
            clock.t += 0.5
        tr.count("data_bytes_placed", 4096)       # the stacks
        with tr.span("setup/acquire/round") as sp:
            clock.t += 2.0
            # what the compile listener does for a bank miss
            tr._on_jax_event(spans_mod._COMPILE_EVENT, 1.5,
                             fun_name="jit(step)")
        assert [a[1] for a in sp.acquired] == ["compiled"]
        tr.count("programs", family="round", source="compiled")
        with tr.span("setup/acquire/eval_val"):
            clock.t += 0.25
        tr.count("programs", family="eval_val", source="bank_hit")
        tr.count("data_bytes_placed", 512)        # the eval sets
        clock.t += 0.25                       # nobody's: the build's self
    closed = [("engine_build", 100.0, 107.0, "setup", 0.0)]
    # a sharded family loads at the first dispatch, in the warm-up
    tr.set_unit(1)
    with tr.span("engine/dispatch"):
        with tr.span("round/dispatch"):
            clock.t += 4.0
            tr._on_jax_event(spans_mod._CACHE_HIT_EVENT, 0.7)
            tr._on_jax_event(spans_mod._COMPILE_EVENT, 0.75,
                             fun_name="jit(step)")
    closed.append(("warmup", 107.0, 111.0, "setup", 0.0))
    clock.t = 120.0
    for unit in (2, 3):
        t0 = clock.t
        tr.set_unit(unit)
        with tr.span("engine/dispatch"):
            clock.t += 0.002
            cpu.t += 0.002
            with tr.span("round/dispatch"):
                tr.count("dispatch", family="round")
                clock.t += 1.0                # blocked on the device
                cpu.t += 0.003
        closed.append(("dispatch", t0, clock.t, "window", 0.005))
        with tr.span("engine/eval_boundary"):
            with tr.span("obs/memory_poll"):
                clock.t += 0.0005
            with tr.span("eval/val_dispatch"):
                clock.t += 0.5
                cpu.t += 0.001
            link = tr.handoff()
        with tr.span("metrics/emit", parent=link):
            clock.t += 0.004 * unit
        t1 = clock.t
        with tr.span("engine/post_unit"):
            with tr.span("obs/flight_write"):
                clock.t += 0.001
                cpu.t += 0.001
        closed.append(("post_unit", t1, clock.t, "window", 0.001))
    # a compile after the window is not set-up's
    tr._on_jax_event(spans_mod._COMPILE_EVENT, 9.0, fun_name="late")
    spans_mod.set_current(tr)
    ctx = {"spans": types.SimpleNamespace(closed=closed), "trace": None,
           "cfg": types.SimpleNamespace(log_dir="/nonexistent/logs")}
    yield ctx, tr
    spans_mod.set_current(None)


@pytest.mark.parametrize("name,want", [
    ("setup_data_s", 4.5),                    # setup/data 4 + setup/place .5
    # the two families, and the load under round/dispatch in the warm-up;
    # the compile inside setup/acquire/round is its family's, once
    ("setup_programs_s", 2.0 + 0.25 + 0.75),
    ("programs_compiled", 1.0),
    # per unit: 1.002 + 0.001 wall, less the leaf's 1.0, plus its 3 ms CPU
    ("engine_host_ms", 2.0 + 1.0 + 3.0),
    ("obs_io_max_ms", 12.0),                  # metrics/emit of unit 3
    ("idle_outside_spans_pct", None),         # no device trace
])
def test_reader_on_staged_spans(staged, name, want, capsys):
    ctx, _tr = staged
    got = reader(name).read(ctx)
    assert got == (pytest.approx(want) if want is not None else None)
    out = capsys.readouterr().out
    if name == "setup_data_s":
        table = json.loads(out.split("[bench] setup_spans ", 1)[1])
        assert table["engine_build_s"] == pytest.approx(7.0)
        assert table["children_s"] == pytest.approx(
            {"setup/data": 4.0, "setup/place": 0.5,
             "setup/acquire/round": 2.0, "setup/acquire/eval_val": 0.25})
        assert table["covered_pct"] == pytest.approx(100 * 6.75 / 7.0)
        assert table["setup_data_parts_s"] == {"setup/data/poison": 3.0}
        assert table["bytes"] == {"data_bytes_host": 0,
                                  "data_bytes_placed": 4608}
        assert table["families"] == [
            {"family": "round", "source": "compiled", "seconds": 2.0,
             "dispatched": 2},
            {"family": "eval_val", "source": "bank_hit", "seconds": 0.25,
             "dispatched": 0}]       # acquired and never dispatched
        assert table["acquired_outside_adopt"] == [
            {"program": "jit(step)", "source": "xla_cache_hit",
             "under": "round/dispatch", "unit": 1, "n": 1,
             "seconds": 0.75}]
    if name == "engine_host_ms":
        parts = json.loads(out.split("[bench] engine_host ", 1)[1])
        assert parts["units"] == 2 and parts["eval_boundaries"] == 2
        # the boundary: 500.5 ms wall, less the leaf's 500, plus 1 ms CPU
        assert parts["eval_boundary"]["host_ms"] == pytest.approx(1.5)
        assert parts["dispatch_and_post_unit"]["cpu_ms"] == pytest.approx(
            6.0)


@pytest.mark.parametrize("name", NEW)
def test_reader_without_a_tracer_returns_none(staged, name):
    ctx, _tr = staged
    spans_mod.set_current(None)
    assert program_view.tracer() is None
    assert reader(name).read(ctx) is None
    # and with a tracer but no window phase: nothing to cut by
    spans_mod.set_current(staged[1])
    ctx["spans"].closed = [c for c in ctx["spans"].closed
                           if c[3] != "window"]
    assert reader(name).read(ctx) is None


# ---- program_spans.py on a hand-built trace -------------------------------
def host_plane_with_program_spans():
    metas = {1: ("engine/dispatch", []), 2: ("round/dispatch", []),
             3: ("bench/dispatch", []), 4: ("engine/post_unit", []),
             5: ("metrics/emit", [])}
    main = [event(3, 0, 400),           # the harness's: not the program's
            event(1, 100, 45),          # engine/dispatch [100,145)
            event(2, 118, 14),          # round/dispatch [118,132) inside it
            event(4, 150, 20)]          # engine/post_unit [150,170): a leaf
    drain = [event(5, 185, 10)]         # metrics/emit [185,195)
    return plane("/host:CPU", [("python3", main), ("metrics-drain", drain)],
                 metas, {})


def test_gap_goes_to_the_innermost_program_span(tmp_path):
    """The device plane of test_benchmark_trace: busy [0,120) [130,140)
    [200,250), so idle [120,130) and [140,200)."""
    path = tmp_path / "hand.xplane.pb"
    path.write_bytes(device_plane(0) + host_plane_with_program_spans())
    names = {"engine/dispatch", "round/dispatch", "engine/post_unit",
             "metrics/emit", "never/recorded"}
    planes = program_spans.xplane.read(str(path), program_spans._want)
    spans = program_spans.program_spans(planes, names)
    assert [(s[2], s[3]) for s in spans] == [
        ("engine/dispatch", False), ("round/dispatch", True),
        ("engine/post_unit", True), ("metrics/emit", True)]
    # both spans cover [120,130): the inner one has it
    assert program_spans.attribute_gap((120, 130), spans) == (
        "round/dispatch", True)
    # [140,200): post_unit covers 20 of 60, emit 10: under none of them
    assert program_spans.attribute_gap((140, 200), spans) == (
        program_spans.NONE, False)
    # the outer span's own time is no leaf's
    assert program_spans.attribute_gap((132, 145), spans) == (
        "engine/dispatch", False)
    table = program_spans.idle_by_program_span(str(path), names)
    assert table["devices"] == 1 and table["program_spans"] == 4
    assert table["idle_s"] == pytest.approx(70e-9)
    assert table["by_span_s"] == pytest.approx(
        {"round/dispatch": 10e-9, program_spans.NONE: 60e-9})
    assert table["outside_leaves_s"] == pytest.approx(60e-9)
    # only the names the tracer recorded are collected
    assert program_spans.idle_by_program_span(
        str(path), {"never/recorded"})["by_span_s"] == pytest.approx(
            {program_spans.NONE: 70e-9})


def test_program_spans_on_a_trace_without_device_operations(tmp_path):
    path = tmp_path / "host_only.xplane.pb"
    path.write_bytes(host_plane_with_program_spans())
    assert program_spans.idle_by_program_span(
        str(path), {"round/dispatch"}) is None


# ---- tiny traced runs on the CPU -------------------------------------------
def traced(root, seed):
    lines, out = [], io.StringIO()
    with contextlib.redirect_stdout(out):
        result = harness.run_cell("tiny-cnn.round-eval", seed, 0.3, True,
                                  platform="cpu", bench_path=root,
                                  say=lines.append)
    return result, lines + out.getvalue().splitlines()


@pytest.fixture(scope="module")
def cold_run(tmp_path_factory):
    """From an empty cache directory of its own (the suite's variable takes
    precedence over the flag, so it is dropped for the run)."""
    tmp = tmp_path_factory.mktemp("cold")
    root = make_root(tmp, extra_flags=[f"--compile_cache_dir={tmp}/cache"])
    suite_dir = jax.config.jax_compilation_cache_dir
    with pytest.MonkeyPatch.context() as mp:
        mp.delenv(compile_cache.CACHE_DIR_ENV)
        try:
            yield traced(root, 21)
        finally:
            jax.config.update("jax_compilation_cache_dir", suite_dir)
            compile_cache._reset_jax_cache_state()
            spans_mod.set_current(None)


def bench_line(lines, tag):
    return json.loads(next(ln for ln in lines
                           if ln.startswith(f"[bench] {tag} ")
                           ).split(" ", 2)[2])


@pytest.mark.parametrize("name", HOST)
def test_traced_run_reports_the_host_metric_with_its_unit(cold_run, name):
    result, _lines = cold_run
    assert result["correct"] is True
    m = result["metrics"][name]
    assert m["unit"] == ENTRIES[name]["unit"] and m["value"] >= 0
    # the nine that were there are still read
    assert {"engine_build_s", "dispatch_host_ms"} <= set(result["metrics"])


def test_traced_run_on_the_cpu_has_no_device_trace_to_attribute(cold_run):
    result, lines = cold_run
    assert "idle_outside_spans_pct" not in result["metrics"]
    assert any("idle_outside_spans_pct: nothing to read" in ln
               for ln in lines)


def test_children_cover_the_engine_build(cold_run):
    result, lines = cold_run
    table = bench_line(lines, "setup_spans")
    assert table["covered_pct"] >= 90.0
    assert {"setup/data", "setup/model_init", "setup/place",
            "setup/build_programs", "setup/obs"} <= set(table["children_s"])
    # the span inside and the harness's span around the constructor agree
    assert table["engine_build_s"] == pytest.approx(
        result["metrics"]["engine_build_s"]["value"], abs=0.1)
    assert set(table["setup_data_parts_s"]) == {
        "setup/data/load_or_generate", "setup/data/partition",
        "setup/data/poison", "setup/data/poisoned_val"}
    assert table["bytes"]["data_bytes_host"] > 0
    assert table["bytes"]["data_bytes_placed"] > 0
    assert (result["metrics"]["setup_data_s"]["value"]
            == pytest.approx(table["children_s"]["setup/data"]
                             + table["children_s"]["setup/place"]))


def test_an_empty_cache_directory_compiles_every_family(cold_run):
    result, lines = cold_run
    table = bench_line(lines, "setup_spans")
    fams = {f["family"]: f for f in table["families"]}
    assert {"round", "eval_val", "eval_poison"} <= set(fams)
    assert fams["round"]["source"] == "compiled"
    assert fams["round"]["dispatched"] >= harness.MIN_UNITS
    assert fams["eval_val"]["dispatched"] == 0     # counted by round only
    assert result["metrics"]["programs_compiled"]["value"] >= len(fams)
    assert (result["metrics"]["setup_programs_s"]["value"]
            >= fams["round"]["seconds"])


def test_window_units_have_every_engine_span(cold_run):
    _result, lines = cold_run
    parts = bench_line(lines, "engine_host")
    assert parts["units"] >= harness.MIN_UNITS
    assert parts["eval_boundaries"] == parts["units"]      # --snap=1
    d = parts["dispatch_and_post_unit"]
    assert d["wall_ms"] >= d["leaf_wall_ms"] > 0
    assert d["host_ms"] == pytest.approx(
        d["wall_ms"] - d["leaf_wall_ms"] + d["leaf_cpu_ms"])
    assert set(bench_line(lines, "obs_io")["max_ms"]) >= {
        "obs/heartbeat_write", "obs/flight_write", "metrics/emit"}


def test_no_spans_run_is_correct_and_reads_nothing(tmp_path):
    root = make_root(tmp_path, extra_flags=["--no_spans"])
    result, lines = traced(root, 22)
    assert result["correct"] is True
    assert spans_mod.current() is None
    for name in NEW:
        assert name not in result["metrics"]
        assert any(f"{name}: nothing to read" in ln for ln in lines)
    assert {"engine_build_s", "dispatch_host_ms"} <= set(result["metrics"])
