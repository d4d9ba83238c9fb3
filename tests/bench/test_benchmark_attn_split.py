"""The readers that split attention at its seams (PR 36): `attn_core_ms`,
`attn_core_mxu_pct`, `window_core_ms`, `window_core_mxu_pct`,
`attn_rope_ms`, `attn_layout_ms`, and `benchmark/trace/scope_pairs.py`
under them, on hand-built device planes whose operations lie under the
scopes the token models plant (`attn_core` in `token_ops.causal_attention`,
`attn_rope` around each model's rotary step, both inside the layer's own
scope); without a trace; and on a plane of a program that plants neither
(the parent of PR 36). No number here is a device metric.

`BENCHMARK.json` does not list these readers yet: the entries that would,
`PROPOSED`, are held here to the contract `test_benchmark_contract.py`
holds every entry to, on a copy of the file (PERF.md section 7 says why
they wait for a `benchmark` PR). `attn_layout_ms` is not among them: on
the chip under half of what it filed was an operand or a result of the
kernel's custom calls themselves (PERF.md section 6, PR 36)."""

import copy
import json
import types

import pytest

from benchmark import flops, registry
from benchmark.trace import scope_pairs

from test_benchmark_contract import SOURCES, TEXT
from test_benchmark_trace import STATS, event, plane
from tiny_root import repo_benchmark

TOKEN_CELLS = ["lfm2-8b-a1b-ep4.round-eval", "joyai-llm-flash-ep32.round-eval",
               "laguna-xs2-ep16.round-eval"]
WINDOW_CELL = "laguna-xs2-ep16.round-eval"
READERS = ["attn_core_ms", "attn_core_mxu_pct", "window_core_ms",
           "window_core_mxu_pct", "attn_rope_ms", "attn_layout_ms"]
PROPOSED = [
    {"name": "attn_core_ms", "unit": "ms", "better": "lower",
     "source": "device_trace", "layer": "attention core",
     "moves": "rounds_per_s", "workloads": TOKEN_CELLS},
    {"name": "attn_core_mxu_pct", "unit": "%", "better": "higher",
     "source": "device_trace", "layer": "attention core",
     "moves": "rounds_per_s", "workloads": TOKEN_CELLS},
    {"name": "window_core_ms", "unit": "ms", "better": "lower",
     "source": "device_trace", "layer": "attention core",
     "moves": "rounds_per_s", "workloads": [WINDOW_CELL]},
    {"name": "window_core_mxu_pct", "unit": "%", "better": "higher",
     "source": "device_trace", "layer": "attention core",
     "moves": "rounds_per_s", "workloads": [WINDOW_CELL]},
    {"name": "attn_rope_ms", "unit": "ms", "better": "lower",
     "source": "device_trace", "layer": "rotary embedding",
     "moves": "rounds_per_s", "workloads": TOKEN_CELLS},
]
TOP = "jit(step)/local_train/jvp(SwaMoE)"
KERNEL = "jvp(vmap(jit(_splash_attention)))/splash_mha_fwd/pallas_call:"
# (tf_op, category, duration) in the order they run on the device
OPS = [
    (f"{TOP}/layer_1/checkpoint/window_attention/dot_general:",
     "convolution fusion", 12),                                  # q product
    (f"{TOP}/layer_1/checkpoint/window_attention/attn_rope/mul:",
     "loop fusion", 10),
    ("", "data formatting", 5),                 # copy.3: operand of the core
    (f"{TOP}/layer_1/checkpoint/window_attention/attn_core/{KERNEL}",
     "custom-call", 40),
    ("", "data formatting", 7),                 # copy.5: its result
    (f"{TOP}/layer_1/moe_router/sort:", "sort", 9),
    ("", "copy-start", 3),                      # copy.7: between two
    (f"{TOP}/layer_1/moe_router/gather:", "loop fusion", 8),    # router ops
    (f"{TOP}/layer_4/global_attention/attn_rope/cos:", "loop fusion", 6),
    (f"{TOP}/layer_4/transpose(jvp(global_attention))/"
     "transpose(jvp(attn_core))/dot:", "convolution fusion", 20),
    ("", "data formatting", 4),                 # copy.11: its result
    ("ragged-dot-none", "custom-call", 30),     # the experts' products
    ("jit(eval_fn)/layer_1/window_attention/attn_core/dot:",
     "convolution fusion", 99),
    ("", "data formatting", 2),                 # copy.14: an eval program's
]
ROUNDS = 2
# nanoseconds of OPS by what each reader reads, over ROUNDS traced rounds
WANT_MS = {"attn_core_ms": (40 + 20) / 2e6, "window_core_ms": 40 / 2e6,
           "attn_rope_ms": (10 + 6) / 2e6, "attn_layout_ms": (5 + 7 + 4) / 2e6}
# attn_core_flops{kind} of one step, and the steps of a round of the context
FLOPS = {"full": 3.0e12, "window": 1.0e12}
STEPS = 10 * 2 * 1


def _plane(ops):
    metas, events, at = {}, [], 0
    for i, (tf_op, cat, dur) in enumerate(ops, start=1):
        name = ("fusion" if tf_op else cat if cat.startswith("copy-")
                else "copy")
        metas[i] = (f"%{name}.{i}", [(1, tf_op), (2, cat)])
        events.append(event(i, at, dur))
        at += dur + 1
    return plane("/device:TPU:0", [("XLA Ops", events)], metas, STATS)


class Tracer:
    """What the readers take from `program_view.tracer()`."""

    def __init__(self, counts):
        self.counts = counts

    def counted(self, before=None):
        return list(self.counts)


def _counts(flops_by_kind):
    return [("attn_core_flops", n, {"kind": k})
            for k, n in flops_by_kind.items()] + [
        ("attn_squares_computed", 10, {}), ("attn_squares", 16, {}),
        ("attn_path", 5, {"path": "kernel"})]


def _ctx(tmp_path, ops, monkeypatch, counts=FLOPS):
    prof = tmp_path / "trace" / "plugins" / "profile" / "run"
    prof.mkdir(parents=True)
    (prof / "host.xplane.pb").write_bytes(_plane(ops))
    from benchmark import program_view
    monkeypatch.setattr(program_view, "tracer",
                        lambda: Tracer(_counts(counts)))
    scope_pairs._device_ops.cache_clear()
    return {"trace": {"by_scope_s": {}}, "traced_rounds": ROUNDS,
            "cfg": types.SimpleNamespace(
                log_dir=str(tmp_path / "logs"), agents_per_round=10,
                local_ep=2, synth_train_size=20, num_agents=10, bs=2),
            "flops": flops, "device": {"kind": "TPU v5 lite"}, "chips": 1}


def _reader(name):
    cell = registry.resolve(registry.load_benchmark(), WINDOW_CELL)
    return registry.load_module(cell.search_dirs, "layer_metrics", name)


def _share(ms, ops):
    return 100.0 * 3 * ops * STEPS / (ms * 1e-3 * 197e12)


@pytest.mark.parametrize("name", READERS)
def test_reader_reads_the_plane_and_nothing_without_a_trace(
        tmp_path, monkeypatch, capsys, name):
    ctx = _ctx(tmp_path, OPS, monkeypatch)
    got = _reader(name).read(ctx)
    want = dict(WANT_MS,
                attn_core_mxu_pct=_share(WANT_MS["attn_core_ms"],
                                         sum(FLOPS.values())),
                window_core_mxu_pct=_share(WANT_MS["window_core_ms"],
                                           FLOPS["window"]))[name]
    assert isinstance(got, float) and got == pytest.approx(want, rel=1e-9)
    if name == "attn_core_mxu_pct":
        line = next(ln for ln in capsys.readouterr().out.splitlines()
                    if ln.startswith("[bench] attn_core "))
        said = json.loads(line.split(" ", 2)[2])
        assert said["ms"] == pytest.approx(
            {"full": 20 / 2e6, "window": 40 / 2e6}, rel=1e-9)
        assert said["flops"] == {k: v * STEPS for k, v in FLOPS.items()}
        assert said["squares"] == {"attn_squares_computed": 10,
                                   "attn_squares": 16}
        assert said["path"] == {"kernel": 5}
    assert _reader(name).read(dict(ctx, trace=None, traced_rounds=0)) is None


@pytest.mark.parametrize("name", READERS)
def test_a_program_that_plants_neither_scope_leaves_every_reader_silent(
        tmp_path, monkeypatch, name):
    """The parent of PR 36: the same operations with `attn_core` and
    `attn_rope` taken off their paths, and no counter."""
    parent = [(tf.replace("/attn_core", "").replace(
        "/transpose(jvp(attn_core))", "").replace("/attn_rope", ""), c, d)
        for tf, c, d in OPS]
    ctx = _ctx(tmp_path, parent, monkeypatch, counts={})
    assert _reader(name).read(ctx) is None


def test_the_share_readers_are_silent_without_the_counter(tmp_path,
                                                          monkeypatch):
    ctx = _ctx(tmp_path, OPS, monkeypatch, counts={})
    assert _reader("attn_core_ms").read(ctx) > 0
    assert _reader("attn_core_mxu_pct").read(ctx) is None
    assert _reader("window_core_mxu_pct").read(ctx) is None


def test_a_model_without_window_layers_has_no_window_core(tmp_path,
                                                          monkeypatch):
    ops = [(tf.replace("window_attention", "mla_attention"), c, d)
           for tf, c, d in OPS]
    ctx = _ctx(tmp_path, ops, monkeypatch, counts={"full": 1.0e12})
    assert _reader("attn_core_ms").read(ctx) == pytest.approx(60 / 2e6)
    assert _reader("window_core_ms").read(ctx) is None
    assert _reader("window_core_mxu_pct").read(ctx) is None


def test_pairs_file_the_core_by_the_layer_around_it(tmp_path, monkeypatch):
    """The innermost of each set, transformations' wrappers taken off, the
    eval program left out."""
    ctx = _ctx(tmp_path, OPS, monkeypatch)
    path = str(next((tmp_path / "trace").rglob("*.xplane.pb")))
    table = scope_pairs.pair_seconds(
        path, ("attn_core", "attn_rope"),
        ("window_attention", "global_attention"), ("jit(eval_fn)",))
    assert table == pytest.approx({
        ("window_attention", "attn_core"): 40e-9,
        ("window_attention", "attn_rope"): 10e-9,
        ("global_attention", "attn_core"): 20e-9,
        ("global_attention", "attn_rope"): 6e-9})
    with_eval = scope_pairs.pair_seconds(path, ("attn_core",),
                                         ("window_attention",))
    assert with_eval[("window_attention", "attn_core")] == pytest.approx(
        (40 + 99) * 1e-9)
    assert ctx["traced_rounds"] == ROUNDS


def _beside(tmp_path, ops, scope="attn_core"):
    prof = tmp_path / "plugins" / "profile" / "run"
    prof.mkdir(parents=True)
    (prof / "host.xplane.pb").write_bytes(_plane(ops))
    scope_pairs._device_ops.cache_clear()
    return scope_pairs.beside(str(prof / "host.xplane.pb"), scope,
                              ("jit(eval_fn)",))


def test_a_copy_beside_the_core_counts_and_one_between_router_ops_not(
        tmp_path):
    got = _beside(tmp_path, OPS)
    # before the kernel (its operand), after it and after the dot (their
    # results); not the copy between two router operations, not the eval
    # program's
    assert got == pytest.approx({"copy.3": 5e-9, "copy.5": 7e-9,
                                 "copy.11": 4e-9})


def test_an_operation_that_holds_the_core_is_no_neighbour(tmp_path):
    """A `while` that holds a copy and the kernel: the copy's neighbour is
    the kernel, not the loop around it."""
    metas = {1: ("%while.1", [(1, f"{TOP}/while:"), (2, "while")]),
             2: ("%copy.2", [(1, ""), (2, "data formatting")]),
             3: ("%fusion.3", [(1, f"{TOP}/layer_1/attention/attn_core/"
                                   f"{KERNEL}"), (2, "custom-call")]),
             4: ("%copy.4", [(1, ""), (2, "data formatting")]),
             5: ("%fusion.5", [(1, f"{TOP}/layer_1/moe_router/sort:"),
                               (2, "sort")])}
    events = [event(1, 0, 100), event(2, 1, 5), event(3, 7, 40),
              event(5, 200, 10), event(4, 211, 3)]
    prof = tmp_path / "plugins" / "profile" / "run"
    prof.mkdir(parents=True)
    (prof / "host.xplane.pb").write_bytes(
        plane("/device:TPU:0", [("XLA Ops", events)], metas, STATS))
    scope_pairs._device_ops.cache_clear()
    got = scope_pairs.beside(str(prof / "host.xplane.pb"), "attn_core")
    # copy.4 follows a router op and ends the plane: not beside the core
    assert got == pytest.approx({"copy.2": 5e-9})


def test_an_asynchronous_copy_beside_the_core_is_not_filed(tmp_path):
    """`copy-start` / `copy-done` move buffers between memory spaces
    wherever XLA fits them: on the chip most of those beside the kernel
    were not its operands."""
    got = _beside(tmp_path, [("", "copy-done", 5), OPS[3], ("", "copy-start",
                                                             2)])
    assert got == {}
    assert not scope_pairs.is_layout("copy-done.7", "copy-done")
    assert scope_pairs.is_layout("copy.7", "data formatting")
    assert scope_pairs.is_layout("copy_bitcast_fusion.2", "loop fusion")


def test_no_operation_under_the_scope_reads_none(tmp_path):
    ops = [(tf, c, d) for tf, c, d in OPS if "attn_core" not in tf]
    assert _beside(tmp_path, ops) is None


@pytest.mark.parametrize("entry", PROPOSED, ids=lambda m: m["name"])
def test_each_proposed_entry_meets_the_contract_and_its_reader(entry):
    """As `test_benchmark_contract.test_metric_entry` and
    `test_cell_resolves_to_its_files` would hold it, on a copy of
    BENCHMARK.json with the six entries appended."""
    assert entry["name"] in READERS
    assert set(entry) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    assert registry.NAME.match(entry["name"])
    assert registry.UNIT.match(entry["unit"])
    assert entry["source"] in SOURCES and TEXT.match(entry["layer"])
    bench = copy.deepcopy(repo_benchmark())
    assert entry["name"] not in [m["name"] for m in bench["per_layer"]
                                 + bench["end_to_end"]]
    assert entry["moves"] in [m["name"] for m in bench["end_to_end"]]
    bench["per_layer"] += PROPOSED
    bench["_root"] = registry.REPO_ROOT
    for cell in [w["name"] for w in bench["workloads"]]:
        resolved = registry.resolve(bench, cell)
        mine = [m for m in resolved.per_layer if m["name"] == entry["name"]]
        assert bool(mine) == (cell in entry["workloads"])
        if mine:
            assert entry["moves"] in resolved.end_to_end
            reader = registry.load_module(resolved.search_dirs,
                                          "layer_metrics", entry["name"])
            assert (reader.LAYER, reader.UNIT, reader.SOURCE,
                    reader.MOVES) == (entry["layer"], entry["unit"],
                                      entry["source"], entry["moves"])
            assert callable(reader.read)
