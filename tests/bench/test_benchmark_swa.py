"""The window/full-attention token cell (`laguna-xs2-ep16.round-eval`): its
entries and files as `BENCHMARK.json` names them, the harness at a toy size
on the CPU with the configuration's own three checks and reference, a
reference told another window failing `c2_swa_token_eval`, and each of the
ten per-layer readers this cell brings, on a hand-built trace whose
operations lie under the scopes the model plants, and without a trace. No
number here is a device metric."""

import copy
import json
import os
import types

import pytest

from benchmark import flops, harness, registry
from benchmark.trace import inner_scopes

from test_benchmark_trace import STATS, event, plane
from tiny_root import REPO, make_root, repo_benchmark

CELL = "laguna-xs2-ep16.round-eval"
TINY_LM = os.path.join(REPO, "tests", "data", "swa_tiny.json")
T, VOCAB, HELD = 32, 96, [0, 1, 2, 3, 4]
READERS = ["swa_window_attention_ms", "swa_window_attention_mxu_pct",
           "swa_global_attention_ms", "swa_global_attention_mxu_pct",
           "swa_moe_route_ms", "swa_moe_experts_ms", "swa_moe_experts_mxu_pct",
           "swa_shared_expert_ms", "swa_moe_load_max_over_mean",
           "swa_moe_overflow_share"]
PER_LAYER = ("layer_types", "mlp_layer_types",
             "num_attention_heads_per_layer")


def real_config():
    with open(os.path.join(REPO, "benchmark", "configs",
                           "laguna-xs2-ep16.json")) as f:
        return json.load(f)


def tiny_config():
    real = real_config()
    with open(TINY_LM) as f:
        pub = json.load(f)
    config = copy.deepcopy(real)
    config.update({k: v for k, v in pub.items()
                   if k not in ("name", "source", "assumed")})
    config.update({k: [pub[k][i] for i in HELD] for k in PER_LAYER})
    config.update(
        name="tiny-swa", source="tests",
        flags=["--data=tokens", "--arch=swa_moe", f"--lm_config={TINY_LM}",
               "--lm_layers=0,1,2,3,4", "--lm_experts_held=4",
               "--lm_expert_offset=2", f"--lm_vocab_held={VOCAB}",
               f"--seq_len={T}", "--num_agents=4", "--num_corrupt=1",
               "--poison_frac=0.5", "--robustLR_threshold=3", "--local_ep=2",
               "--bs=2", "--remat", "--agent_chunk=1",
               "--synth_train_size=8", "--synth_val_size=4", "--eval_bs=2"],
        layers_held=HELD, num_hidden_layers=5, num_experts=4,
        expert_offset=2, vocab_size=VOCAB, seq_len=T, agents=4, parameters=1,
        examples_per_round=2 * 8 * T)
    config["published"]["num_experts"] = {"source": 16, "here": 4}
    config["backdoor"].update(trigger=[VOCAB - 3, VOCAB - 2, VOCAB - 1],
                              triggers_per_sequence=8)
    # float32 on XLA:CPU against the same arithmetic
    config["check"].update(val_loss_rtol=1e-4, poison_loss_rtol=1e-4,
                           acc_tokens=0.5, pairs_rtol=0.0, pairs_atol=0.5,
                           pairs_moved_share=1e-9,
                           c1_sample=64, round_sample=256,
                           train_loss_rtol=1e-5, update_rel_err=1e-3,
                           vote_flipped_share=5e-3)
    return config


@pytest.fixture(scope="module")
def ran(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("swa")
    root = make_root(tmp, cells=(("tiny-cnn.round-eval", "round-eval"),),
                     config=tiny_config())
    lines = []
    result = harness.run_cell("tiny-cnn.round-eval", 2147483659, 0.0, True,
                              platform="cpu", bench_path=root,
                              say=lines.append)
    return root, result, lines


def test_the_entries_name_these_files_and_every_width_is_published():
    bench = repo_benchmark()
    cell = registry.resolve(registry.load_benchmark(), CELL)
    entry = next(c for c in bench["configs"] if c["name"] == "laguna-xs2-ep16")
    assert registry.config_problems(cell.config, entry) == []
    assert cell.chips == 1 and cell.traffic_name == "round-eval"
    assert cell.checks == ["c1_fold_blocks", "c2_swa_token_eval",
                           "c2_token_round"]
    assert cell.config["reference"] == "laguna_xs2"
    assert cell.config["parameters"] == 490_297_344
    assert cell.config["examples_per_round"] == 163_840
    assert cell.config["deployment"]["chips_sharing_a_layer"] == 16
    new = [m["name"] for m in bench["per_layer"]
           if m.get("workloads") == [CELL]]
    assert new == READERS
    assert all(m["moves"] == "rounds_per_s" for m in bench["per_layer"]
               if m["name"] in READERS)
    # the cell is in no list of a metric that was there
    assert sum(CELL in m.get("workloads", ()) for m in bench["per_layer"]
               + bench["end_to_end"]) == len(READERS)
    with open(os.path.join(
            REPO, "defending_against_backdoors_with_robust_learning_rate_tpu",
            "models", "laguna_xs2.json")) as f:
        pub = json.load(f)
    assert cell.config["reduced"] == [
        "num_hidden_layers", "layer_types", "mlp_layer_types",
        "num_attention_heads_per_layer", "num_experts", "vocab_size"]
    for key, value in pub.items():
        if key in cell.config["reduced"] or key in ("name", "source",
                                                    "assumed"):
            continue
        assert cell.config[key] == value, key
    for key in cell.config["reduced"]:
        assert cell.config["published"][key]["source"] == pub[key], key
        assert cell.config["published"][key]["here"] == cell.config[key], key
    for key in PER_LAYER:
        assert cell.config[key] == [pub[key][i] for i in HELD], key
    parts = cell.config["parameters_by_part"]
    assert (sum(parts["layers"]) + parts["embedding_held"]
            + parts["head_held"] + parts["final_norm"]
            ) == cell.config["parameters"]
    sparse = (parts["experts_held_a_layer"] + parts["shared_expert"]
              + parts["router"] + parts["block_norms"])
    assert parts["layers"] == [
        parts["full_attention"] + parts["dense_ffn"] + parts["block_norms"],
        *[parts["window_attention"] + sparse] * 3,
        parts["full_attention"] + sparse]
    for kind in ("full_attention", "window_attention"):
        assert sum(parts[kind + "_parts"].values()) == parts[kind]
    assert parts["experts_held_a_layer"] == 16 * parts["one_expert"]
    for key in ("gating", "scoring_func", "e_score_correction_bias",
                "topk_eps", "qk_norm", "rotary_layout", "yarn",
                "sliding_window", "initializer_range"):
        assert key in cell.config["assumed"], key


def test_the_reference_counts_the_band_and_the_causal_half():
    cell = registry.resolve(registry.load_benchmark(), CELL)
    ref = registry.load_module(cell.search_dirs, "reference", "laguna_xs2")
    dims = ref.dims_of(cell.config)
    assert [(k, h, s) for _i, k, h, s in dims["layers"]] == [
        ("full_attention", 48, False), ("sliding_attention", 64, True),
        ("sliding_attention", 64, True), ("sliding_attention", 64, True),
        ("full_attention", 48, True)]
    assert (dims["router_experts"], dims["experts_held"]) == (256, 16)
    band = (512 * 513 / 2 + (4096 - 512) * 512) / 4096     # keys a query
    assert ref.keys_per_query(4096, "sliding_attention", 512) == band
    assert ref.keys_per_query(4096, "full_attention", 512) == 4097 / 2
    assert ref.keys_per_query(256, "sliding_attention", 512) == 257 / 2
    # projections and the gate are a layer's parameters, one multiply-add
    # each; scores and values 2 x 128 a head and key
    window = 37_879_808 + band * 64 * 256
    full = 29_458_432 + 4097 / 2 * 48 * 256
    assert ref.window_attention_flops(1, dims) == pytest.approx(
        2 * 3 * window, rel=1e-12)
    assert ref.full_attention_flops(1, dims) == pytest.approx(
        2 * 2 * full, rel=1e-12)
    # half a routed expert a token (8 x 16 / 256), the shared expert and
    # the router every token, one head product
    sparse = 2048 * 256 + 3 * 2048 * 512 + 0.5 * 3 * 2048 * 512
    want = (12544 * 2048 + 3 * window + 2 * full + 3 * 2048 * 8192
            + 4 * sparse)
    assert ref.forward_flops_of(cell.config) == pytest.approx(2 * want,
                                                              rel=1e-12)
    assert 0.65e9 < 2 * want < 0.72e9      # about 0.69 GFLOP a token
    assert ref.moe_expert_flops(256, dims) == 3 * 6 * 2048 * 512 * 256


def test_tiny_cell_is_correct_and_the_three_checks_ran(ran):
    _root, result, lines = ran
    assert result["correct"] is True, lines
    names = [ln.split(" ", 3)[2] for ln in lines
             if ln.startswith("[bench] check ")]
    assert names == ["c1_fold_blocks", "c2_swa_token_eval", "c2_token_round"]
    c2 = json.loads(next(ln for ln in lines if "check c2_swa_token_eval" in ln
                         ).split(" ", 3)[3])
    assert c2["backdoor"] == {"wrong_place": 0, "wrong_ids": 0, "stray": 0}
    assert len(c2["pairs_reference"]) == 4       # four sparse layers
    assert all(sum(row) == 4 * T * 4 for row in c2["pairs_reference"])
    for key in ("c2_swa_token_eval.Validation/Loss",
                "c2_swa_token_eval.pairs_unaccounted",
                "c2_token_round.train_loss", "c2_token_round.update_rel_err",
                "c1_fold_blocks.ulps_of_leaf_scale"):
        value, limit = result["compared"][key]
        assert value <= limit, key


def test_counters_reach_the_readers_and_trace_readers_stay_silent(ran):
    """On the CPU there is no device trace: the eight trace readers leave
    their metrics out, the two counter readers read what the program
    counted for the traced rounds."""
    _root, result, _lines = ran
    got = {k for k in result["metrics"] if k in READERS}
    assert got == {"swa_moe_load_max_over_mean", "swa_moe_overflow_share"}
    assert result["metrics"]["swa_moe_load_max_over_mean"]["value"] >= 1.0
    assert 0.0 <= result["metrics"]["swa_moe_overflow_share"]["value"] <= 100.0
    from benchmark import program_view
    tr = program_view.tracer()
    counted = {n: v for n, v, _l in tr.counted()}
    assert (counted["attn_window"], counted["attn_window_layers"],
            counted["attn_full_layers"], counted["shared_experts"]) == (
        8, 3, 2, 1)
    assert (counted["attn_window_squares_computed"],
            counted["attn_squares_computed"], counted["attn_squares"]) == (
        1, 1, 1)
    held = [v for n, v, _l in tr.counted() if n == "moe_pairs_held"]
    absent = [v for n, v, _l in tr.counted() if n == "moe_pairs_absent"]
    # 4 clients x 2 steps x 2 sequences x T tokens x 4 experts x 4 layers
    assert held and all(h + a == 4 * 2 * 2 * T * 4 * 4
                        for h, a in zip(held, absent, strict=True))


@pytest.mark.parametrize("change", [
    {"sliding_window": 6}, {"sliding_window": 12},
    {"rope_parameters": "swapped"}])
def test_a_reference_told_another_window_fails_the_eval_check(ran, tmp_path,
                                                              change):
    """`c2_swa_token_eval.run` on the engine's own evaluation against a
    reference told a narrower or a wider window than the program's 8 keys,
    or the two layer kinds' rotary parameters the other way round: the
    check fails; told the truth it holds."""
    root, _result, _lines = ran
    cell = registry.resolve(registry.load_benchmark(root),
                            "tiny-cnn.round-eval")
    check = registry.load_module(cell.search_dirs, "checks",
                                 "c2_swa_token_eval")
    from defending_against_backdoors_with_robust_learning_rate_tpu import (
        train)
    from defending_against_backdoors_with_robust_learning_rate_tpu.config import (
        args_parser)
    writer = harness.MemoryWriter()
    eng = train.RoundEngine(args_parser(cell.flags + [
        "--seed=5", "--rounds=1", "--platform=cpu", "--no_tensorboard",
        "--no_compile_cache", f"--log_dir={tmp_path}"]), writer=writer)
    if change.get("rope_parameters") == "swapped":
        rp = cell.config["rope_parameters"]
        change = {"rope_parameters": dict(
            rp, full_attention=rp["sliding_attention"],
            sliding_attention=rp["full_attention"])}
    try:
        eng.dispatch((1,))
        eng.eval_boundary(1)
        eng.drain_flush()
        out = {}
        for told in ({}, change):
            out[bool(told)] = check.run({
                "config": dict(cell.config, **told), "rows": writer.at(1),
                "eng": eng, "val": eng.val, "params": eng.model_params,
                "reference": registry.load_module(
                    cell.search_dirs, "reference", "laguna_xs2")})
    finally:
        eng.close()
    assert out[False]["ok"] is True, out[False]["compared"]
    assert out[True]["ok"] is False
    # at toy widths and normal(0.02) weights attention moves the loss
    # little; the routing of the layers behind it tells at once
    left = {k for k, (v, lim) in out[True]["compared"].items() if v > lim}
    assert left & {"Validation/Loss", "pairs_moved_share"}, left
    moved = out[True]["compared"]["Validation/Loss"][0]
    assert moved > 10 * out[False]["compared"]["Validation/Loss"][0]


# ---- the readers, on a hand-built device plane -----------------------------
def _swa_plane():
    top = "jit(step)/local_train/jvp(SwaMoE)"
    paths = {
        1: f"{top}/layer_1/checkpoint/window_attention/dot_general:",    # 40
        2: f"{top}/layer_1/moe_router/sort:",                            # 10
        3: "ragged-dot-none",                                            # 30
        4: f"{top}/layer_1/shared_expert/dot_general:",                  # 8
        5: f"{top}/layer_4/transpose(jvp(global_attention))/dot:",       # 20
        6: f"{top}/lm_head/dot_general:",                                # 6
        7: "jit(eval_fn)/while/body/layer_1/window_attention/dot:",      # 99
        8: f"{top}/layer_1/moe_router/moe_experts/convert:",             # 4
        9: f"{top}/layer_0/checkpoint/global_attention/reduce:",         # 12
    }
    metas = {i: (f"%op.{i}", [(1, p), (2, "fusion")])
             for i, p in paths.items()}
    durs = {1: 40, 2: 10, 3: 30, 4: 8, 5: 20, 6: 6, 7: 99, 8: 4, 9: 12}
    ops, at = [], 0
    for i in (9, 1, 2, 3, 8, 4, 5, 6, 7):
        ops.append(event(i, at, durs[i]))
        at += durs[i] + 1
    return plane("/device:TPU:0", [("XLA Ops", ops)], metas, STATS)


@pytest.fixture()
def reader_ctx(tmp_path):
    prof = tmp_path / "trace" / "plugins" / "profile" / "run"
    prof.mkdir(parents=True)
    (prof / "host.xplane.pb").write_bytes(_swa_plane())
    cell = registry.resolve(registry.load_benchmark(), CELL)
    return {"trace": {"by_scope_s": {}}, "traced_rounds": 2, "cell": cell,
            "cfg": types.SimpleNamespace(
                log_dir=str(tmp_path / "logs"), agents_per_round=10,
                local_ep=2, synth_train_size=20, num_agents=10, bs=2),
            "flops": flops, "device": {"kind": "TPU v5 lite"}, "chips": 1}


def _reader(name, monkeypatch, counts):
    cell = registry.resolve(registry.load_benchmark(), CELL)
    reader = registry.load_module(cell.search_dirs, "layer_metrics", name)
    fake = lambda _ctx, key: list(counts.get(key, []))       # noqa: E731
    if hasattr(reader, "traced_counts"):
        monkeypatch.setattr(reader, "traced_counts", fake)
    # the readers of the shared sparse code's scopes and counters are the
    # latent-attention cell's, under this cell's names
    from benchmark.layer_metrics import (mla_moe_experts_mxu_pct,
                                         mla_moe_overflow_share,
                                         moe_load_max_over_mean)
    for mod in (moe_load_max_over_mean, mla_moe_overflow_share,
                mla_moe_experts_mxu_pct):
        monkeypatch.setattr(mod, "traced_counts", fake)
    return reader


COUNTS = {"moe_pairs_held": [40960.0, 40960.0], "moe_load_max": [1500.0] * 2,
          "moe_load_mean": [1000.0] * 2, "moe_overflow_steps": [3.0, 1.0]}
# nanoseconds of the plane above by scope, over two traced rounds
WANT_MS = {"swa_window_attention_ms": 40 / 2e6,
           "swa_global_attention_ms": (20 + 12) / 2e6,
           "swa_shared_expert_ms": 8 / 2e6, "swa_moe_route_ms": 10 / 2e6,
           "swa_moe_experts_ms": (30 + 4) / 2e6}


@pytest.mark.parametrize("name", READERS)
def test_reader_returns_a_number_on_the_trace_and_none_without(
        reader_ctx, monkeypatch, name):
    entry = next(m for m in repo_benchmark()["per_layer"]
                 if m["name"] == name)
    reader = _reader(name, monkeypatch, COUNTS)
    assert (reader.LAYER, reader.UNIT, reader.SOURCE, reader.MOVES) == (
        entry["layer"], entry["unit"], entry["source"], entry["moves"])
    got = reader.read(reader_ctx)
    assert isinstance(got, float) and got > 0
    if name in WANT_MS:
        assert got == pytest.approx(WANT_MS[name], rel=1e-9)
    if name == "swa_moe_load_max_over_mean":
        assert got == pytest.approx(1.5)
    if name == "swa_moe_overflow_share":
        # 4 forwards of 2 rounds x 20 steps x 4 sparse layers
        assert got == pytest.approx(100.0 * 4 / (2 * 20 * 4))
    # without a trace, or a program that counted nothing: left out
    inner_scopes._by_inner_scope.cache_clear()
    silent = _reader(name, monkeypatch, {})
    assert silent.read(dict(reader_ctx, trace=None, traced_rounds=0)) is None


def test_a_program_that_plants_no_such_scope_leaves_the_readers_silent(
        tmp_path, monkeypatch):
    """The parent of this PR has neither scope: on its trace every reader
    of a scope returns None and raises nothing."""
    prof = tmp_path / "trace" / "plugins" / "profile" / "run"
    prof.mkdir(parents=True)
    metas = {1: ("%op.1", [(1, "jit(step)/local_train/attention/dot:"),
                           (2, "fusion")])}
    (prof / "host.xplane.pb").write_bytes(plane(
        "/device:TPU:0", [("XLA Ops", [event(1, 0, 50)])], metas, STATS))
    cell = registry.resolve(registry.load_benchmark(), CELL)
    ctx = {"trace": {"by_scope_s": {}}, "traced_rounds": 1, "cell": cell,
           "cfg": types.SimpleNamespace(log_dir=str(tmp_path / "logs")),
           "flops": flops, "device": {"kind": "TPU v5 lite"}, "chips": 1}
    inner_scopes._by_inner_scope.cache_clear()
    for name in READERS[:8]:
        assert _reader(name, monkeypatch, COUNTS).read(ctx) is None, name


@pytest.mark.parametrize("name,scope,fn", [
    ("swa_window_attention_mxu_pct", "swa_window_attention_ms",
     "window_attention_flops"),
    ("swa_global_attention_mxu_pct", "swa_global_attention_ms",
     "full_attention_flops")])
def test_attention_share_of_the_peak_counts_the_kinds_own_operations(
        reader_ctx, monkeypatch, name, scope, fn):
    reader = _reader(name, monkeypatch, COUNTS)
    cell = reader_ctx["cell"]
    ref = registry.load_module(cell.search_dirs, "reference", "laguna_xs2")
    ops = 3 * getattr(ref, fn)(163_840, ref.dims_of(cell.config))
    want = 100.0 * ops / (WANT_MS[scope] * 1e-3 * 197e12)
    assert reader.read(reader_ctx) == pytest.approx(want, rel=1e-9)


def test_experts_share_of_the_peak_counts_pairs_times_widths(reader_ctx,
                                                             monkeypatch):
    reader = _reader("swa_moe_experts_mxu_pct", monkeypatch, COUNTS)
    ms = WANT_MS["swa_moe_experts_ms"]
    want = 100.0 * (18 * 2048 * 512 * 40960) / (ms * 1e-3 * 197e12)
    assert reader.read(reader_ctx) == pytest.approx(want, rel=1e-9)
