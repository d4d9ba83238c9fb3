"""The trace reducer: on a small hand-built XSpace, where every number can
be worked out by hand, and on one small recorded v5e trace kept with the
reducer (benchmark/trace/fixture_v5e.xplane.pb: the harness's traced part
of two tiny rounds on one TPU v5e chip, cut to the device plane and the
benchmark's own host annotations)."""

import os
import struct

import pytest

from benchmark.trace import reduce, xplane

from tiny_root import REPO

FIXTURE = os.path.join(REPO, "benchmark", "trace", "fixture_v5e.xplane.pb")


# ---- a minimal protocol-buffer writer, for the hand-built trace ----------
def varint(v):
    v &= (1 << 64) - 1
    out = bytearray()
    while True:
        b = v & 0x7F
        v >>= 7
        out.append(b | (0x80 if v else 0))
        if not v:
            return bytes(out)


def field(no, payload):
    if isinstance(payload, int):
        return varint(no << 3) + varint(payload)
    if isinstance(payload, str):
        payload = payload.encode()
    return varint(no << 3 | 2) + varint(len(payload)) + payload


def stat(meta_id, value):
    body = field(1, meta_id)
    body += (field(5, value) if isinstance(value, str)
             else varint(2 << 3 | 1) + struct.pack("<d", value))
    return body


def event(meta_id, start_ns, dur_ns):
    return field(1, meta_id) + field(2, start_ns * 1000) + field(
        3, dur_ns * 1000)


def plane(name, lines, metas, stat_names):
    body = field(2, name)
    for lname, events in lines:
        body += field(3, field(2, lname) + b"".join(
            field(4, e) for e in events))
    for mid, (mname, stats) in metas.items():
        meta = field(1, mid) + field(2, mname) + b"".join(
            field(5, stat(k, v)) for k, v in stats)
        body += field(4, field(1, mid) + field(2, meta))
    for sid, sname in stat_names.items():
        body += field(5, field(1, sid) + field(2, field(1, sid)
                                               + field(2, sname)))
    return field(1, body)


STATS = {1: "tf_op", 2: "hlo_category"}


def device_plane(no, shift=0):
    metas = {
        1: ("%while.1 = (f32[]) while(...)",
            [(1, "jit(step)/local_train/while:"), (2, "while")]),
        2: ("%fusion.2 = f32[8] fusion(...)",
            [(1, "jit(step)/local_train/while/body/conv:"),
             (2, "convolution fusion")]),
        3: ("%fusion.3 = f32[8] fusion(...)",
            [(1, "jit(step)/aggregate_rlr/reduce_sum:"),
             (2, "loop fusion")]),
        4: ("%all-reduce.4 = f32[8] all-reduce(...)",
            [(1, "jit(step)/aggregate_rlr/psum:"), (2, "all-reduce")]),
        5: ("%all-reduce-start.5 = f32[8] all-reduce-start(...)",
            [(2, "all-reduce-start")]),
        6: ("%fusion.6 = f32[8] fusion(...)",
            [(1, "jit(eval_fn)/while/body/dot:"), (2, "loop fusion")]),
    }
    s = shift
    ops = [event(1, 0 + s, 100),        # while [0,100) holding two bodies
           event(2, 10 + s, 30), event(2, 50 + s, 40),
           event(3, 100 + s, 20),       # server step [100,120)
           event(4, 130 + s, 10),       # exposed all-reduce [130,140)
           event(6, 200 + s, 50)]       # eval [200,250)
    asyn = [event(5, 90 + s, 30)]       # async all-reduce [90,120): hidden
    return plane(f"/device:TPU:{no}", [("XLA Ops", ops),
                                       ("Async XLA Ops", asyn),
                                       ("Steps", [event(1, 0, 999)])],
                 metas, STATS)


def host_plane():
    metas = {1: ("bench/dispatch", []), 2: ("bench/wait", []),
             3: ("SomethingElse", [])}
    return plane("/host:CPU", [("python3", [
        event(1, 118, 10),      # covers most of the gap [120,130)
        event(3, 0, 500),
        event(2, 135, 100),     # covers the gap [140,200)
    ])], metas, {})


@pytest.fixture()
def handmade(tmp_path):
    path = tmp_path / "hand.xplane.pb"
    path.write_bytes(device_plane(0) + device_plane(1, shift=1000)
                     + host_plane())
    return str(path)


def test_handmade_busy_union_and_window(handmade):
    s = reduce.summarize(handmade)
    assert s["devices"] == 2
    # busy [0,120) + [130,140) + [200,250) = 180 ns of a 250 ns window
    assert s["busy_s"] == pytest.approx(180e-9)
    assert s["window_s"] == pytest.approx(250e-9)
    assert s["busy_s_per_device"] == pytest.approx([180e-9, 180e-9])


def test_handmade_scope_time_is_self_time(handmade):
    s = reduce.summarize(handmade)
    # the while keeps 100 - 30 - 40 = 30 of its own; bodies 70: 100 in all
    assert s["by_scope_s"]["local_train"] == pytest.approx(100e-9)
    assert s["by_scope_s"]["aggregate_rlr"] == pytest.approx(30e-9)
    assert s["by_scope_s"]["unscoped"] == pytest.approx(50e-9)
    assert s["by_program_s"]["jit(eval_fn)"] == pytest.approx(50e-9)
    assert s["by_program_s"]["jit(step)"] == pytest.approx(130e-9)
    assert s["by_group_s"]["local_train:convolution fusion"] == pytest.approx(
        70e-9)
    assert reduce.top(s["by_group_s"], 1)[0][0] == (
        "local_train:convolution fusion")


def test_handmade_all_reduce_time_and_its_exposed_part(handmade):
    s = reduce.summarize(handmade)
    # union of [90,120) and [130,140) = 40; compute covers [0,120) and
    # [200,250), so only [130,140) ran with no compute beside it
    assert s["collective_s"] == pytest.approx(40e-9)
    assert s["collective_exposed_s"] == pytest.approx(10e-9)


def test_handmade_gap_attribution(handmade):
    s = reduce.summarize(handmade)
    assert s["host_spans"] == 2
    # the second chip's operations come 1000 ns later, where the host was in
    # no span of the benchmark's; each table is the mean over the two chips
    assert s["idle_by_span_s"] == pytest.approx(
        {"dispatch": 5e-9, "wait": 30e-9, reduce.BETWEEN: 35e-9})
    assert s["longest_gaps_s"][0] == (pytest.approx(60e-9), "wait")


def test_a_trace_without_device_operations_reads_as_none(tmp_path):
    path = tmp_path / "host_only.xplane.pb"
    path.write_bytes(host_plane())
    assert reduce.summarize(str(path)) is None


def test_find_xplane(tmp_path):
    assert reduce.find_xplane(str(tmp_path)) is None
    d = tmp_path / "plugins" / "profile" / "2026_01_01"
    d.mkdir(parents=True)
    (d / "host.xplane.pb").write_bytes(b"")
    assert reduce.find_xplane(str(tmp_path)).endswith("host.xplane.pb")


# ---- the recorded trace ---------------------------------------------------
@pytest.fixture(scope="module")
def recorded():
    assert os.path.getsize(FIXTURE) < 1_500_000
    return reduce.summarize(FIXTURE)


def test_recorded_planes_and_lines_are_what_the_reducer_keys_on():
    planes = xplane.read(FIXTURE, lambda _p, _l: True)
    names = [p.name for p in planes]
    assert "/device:TPU:0" in names and "/host:CPU" in names
    dev = next(p for p in planes if p.name == "/device:TPU:0")
    assert {"XLA Ops", "Async XLA Ops"} <= {ln.name for ln in dev.lines}
    ops = next(ln for ln in dev.lines if ln.name == "XLA Ops")
    assert len(ops.events) > 1000
    metas = [dev.event_meta[m] for _s, _d, m in ops.events]
    assert any("local_train" in str(m.stats.get("tf_op")) for m in metas)
    assert any(m.stats.get("hlo_category") == "convolution fusion"
               for m in metas)


def test_recorded_busy_union(recorded):
    assert recorded["devices"] == 1
    assert 0 < recorded["busy_s"] < recorded["window_s"]
    # nested operations are not counted twice: self times add up to the union
    total = sum(recorded["by_scope_s"].values())
    assert total == pytest.approx(recorded["busy_s"], rel=0.02)


def test_recorded_scopes_and_programs(recorded):
    scopes = recorded["by_scope_s"]
    for name in ("local_train", "sample_gather", "aggregate_rlr", "health"):
        assert scopes[name] > 0
    assert scopes["local_train"] > 0.8 * recorded["busy_s"]
    programs = recorded["by_program_s"]
    assert programs["jit(step)"] > programs["jit(eval_fn)"] > 0
    assert programs["jit(probe)"] > 0


def test_recorded_has_no_collective_on_one_chip(recorded):
    assert recorded["collective_s"] == 0.0
    assert recorded["collective_exposed_s"] == 0.0


def test_recorded_gaps_fall_under_the_benchmarks_own_spans(recorded):
    assert recorded["host_spans"] >= 4
    idle = recorded["window_s"] - recorded["busy_s"]
    assert sum(recorded["idle_by_span_s"].values()) == pytest.approx(idle)
    assert set(recorded["idle_by_span_s"]) <= {
        "dispatch", "eval_boundary", "post_unit", "wait", reduce.BETWEEN}
    assert set(recorded["idle_by_span_s"]) & {"dispatch", "eval_boundary",
                                              "post_unit", "wait"}
