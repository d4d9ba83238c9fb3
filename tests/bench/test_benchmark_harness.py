"""The harness end to end on the CPU at a tiny size: the same functions the
command calls, with the platform passed as an argument. What it prints
names the CPU; no number from here is a device metric."""

import json
import os
import subprocess
import sys

import pytest

from benchmark import harness, registry

from tiny_root import REPO, make_root

RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    return make_root(tmp_path_factory.mktemp("tiny"))


@pytest.fixture(scope="module")
def plain_run(tiny):
    lines = []
    result = harness.run_cell("tiny-cnn.round-eval", 11, 0.5, False,
                              platform="cpu", bench_path=tiny,
                              say=lines.append)
    return result, lines


@pytest.fixture(scope="module")
def traced_run(tiny):
    lines = []
    result = harness.run_cell("tiny-cnn.pairs", 12, 0.5, True,
                              platform="cpu", bench_path=tiny,
                              say=lines.append)
    return result, lines


def test_result_line_has_exactly_the_contract_keys(plain_run):
    result, _lines = plain_run
    assert set(result) == RESULT_KEYS
    assert json.loads(json.dumps(result)) == result
    assert set(result["device"]) == {"platform", "kind", "count",
                                     "memory_peak_bytes"}
    assert result["device"]["platform"] == "cpu"


def test_plain_run_reports_the_cells_end_to_end_metrics(plain_run):
    result, _lines = plain_run
    assert set(result["metrics"]) == {"rounds_per_s", "round_p90_ms",
                                      "setup_s"}
    for name, unit in (("rounds_per_s", "rounds/s"), ("round_p90_ms", "ms"),
                       ("setup_s", "s")):
        m = result["metrics"][name]
        assert set(m) == {"value", "unit"} and m["unit"] == unit
        assert m["value"] > 0


def test_plain_run_is_correct_and_counts_rounds(plain_run):
    result, lines = plain_run
    assert result["correct"] is True
    assert result["attempted"] >= harness.MIN_UNITS and result["failed"] == 0
    c3 = json.loads(next(ln for ln in lines if ln.startswith("[bench] C3")
                         ).split(" ", 2)[2])
    assert c3["compilations_in_window"] == 0
    # warm-up boundary + one per round of the window
    assert c3["eval_rows"] == c3["eval_boundaries"] == result["attempted"] + 1


def test_traced_run_reports_per_layer_metrics_only(traced_run):
    result, lines = traced_run
    assert set(result) == RESULT_KEYS        # no device trace on the CPU
    assert result["correct"] is True
    # a cell of two rounds per unit: operations are rounds
    assert result["attempted"] % 2 == 0 and result["attempted"] >= 6
    names = set(result["metrics"])
    assert {"engine_build_s", "dispatch_host_ms"} <= names
    assert not names & {"rounds_per_s", "setup_s", "round_p90_ms"}
    # readers of the device trace found nothing to read, and said so
    assert "device_idle_pct" not in names
    assert any("device_idle_pct: nothing to read" in ln for ln in lines)
    assert "collective_ms" not in names      # not a metric of this cell


def test_at_most_two_units_are_in_flight(tiny, monkeypatch):
    depth = {"now": 0, "max": 0}
    real_dispatch, real_wait = (harness.Driver.dispatch_next,
                                harness.Driver.wait)

    def dispatch_next(self):
        depth["now"] += 1
        depth["max"] = max(depth["max"], depth["now"])
        return real_dispatch(self)

    def wait(self, handle):
        out = real_wait(self, handle)
        depth["now"] -= 1
        return out

    monkeypatch.setattr(harness.Driver, "dispatch_next", dispatch_next)
    monkeypatch.setattr(harness.Driver, "wait", wait)
    result = harness.run_cell("tiny-cnn.round-eval", 13, 0.3, False,
                              platform="cpu", bench_path=tiny,
                              say=lambda _ln: None)
    assert result["correct"] and depth["max"] == harness.IN_FLIGHT
    assert depth["now"] == 0


def test_every_run_goes_through_the_engine(tiny, monkeypatch):
    from defending_against_backdoors_with_robust_learning_rate_tpu import train
    calls = {"dispatch": 0, "eval_boundary": 0, "post_unit": 0}
    for name in calls:
        real = getattr(train.RoundEngine, name)

        def counted(self, *a, _real=real, _name=name, **kw):
            calls[_name] += 1
            return _real(self, *a, **kw)

        monkeypatch.setattr(train.RoundEngine, name, counted)
    result = harness.run_cell("tiny-cnn.round-eval", 14, 0.0, False,
                              platform="cpu", bench_path=tiny,
                              say=lambda _ln: None)
    assert calls["dispatch"] == calls["post_unit"] == result["attempted"] + 1
    assert calls["eval_boundary"] == calls["dispatch"]


def test_a_compilation_inside_the_window_fails_the_run(tiny, monkeypatch):
    import jax
    import jax.numpy as jnp
    real = harness.Driver.dispatch_next
    seen = []

    def dispatch_next(self):
        out = real(self)
        if len(seen) == 2:      # the second unit of the window
            jax.jit(lambda x: x * 3 + len(seen))(jnp.ones(7))
        seen.append(1)
        return out

    monkeypatch.setattr(harness.Driver, "dispatch_next", dispatch_next)
    lines = []
    result = harness.run_cell("tiny-cnn.round-eval", 15, 0.0, False,
                              platform="cpu", bench_path=tiny,
                              say=lines.append)
    assert result["correct"] is False
    c3 = json.loads(next(ln for ln in lines if ln.startswith("[bench] C3")
                         ).split(" ", 2)[2])
    assert c3["compilations_in_window"] >= 1


def test_unknown_workload_is_refused(tiny):
    with pytest.raises(KeyError):
        registry.resolve(registry.load_benchmark(tiny), "no-such.cell")


def test_the_command_fails_without_a_tpu():
    """The real command, the repo's own first cell: naming the platform makes
    JAX itself fail here, the exit code is not 0 and no result is printed."""
    bench = registry.load_benchmark()
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, bench["command"][1]),
         "--workload", bench["workloads"][0]["name"], "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, timeout=120, cwd=REPO)
    assert r.returncode != 0
    assert r.stdout.strip() == ""


def test_four_chip_cell_on_virtual_devices(tmp_path):
    """The mesh4 mix through the same harness on the suite's virtual CPU
    devices: sharded round, replica equality in C3, C1 on one device."""
    root = make_root(tmp_path, cells=(("tiny-cnn.mesh4", "mesh4"),))
    bench = json.loads(open(root).read())
    bench["workloads"][0]["chips"] = 4
    open(root, "w").write(json.dumps(bench))
    lines = []
    result = harness.run_cell("tiny-cnn.mesh4", 16, 0.0, False,
                              platform="cpu", bench_path=root,
                              say=lines.append)
    assert result["correct"] is True and result["device"]["count"] >= 4
    c3 = json.loads(next(ln for ln in lines if ln.startswith("[bench] C3")
                         ).split(" ", 2)[2])
    assert c3["replicas_equal"] is True and c3["compilations_in_window"] == 0
