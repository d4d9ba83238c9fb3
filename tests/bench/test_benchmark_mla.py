"""The latent-attention token cell (`joyai-llm-flash-ep32.round-eval`):
its entries and files as `BENCHMARK.json` names them, the harness at a toy
size on the CPU with the configuration's own three checks and reference,
and each of the nine per-layer readers this cell brings, on a hand-built
trace whose operations lie under the scopes the model plants, and without a
trace. No number here is a device metric."""

import copy
import json
import os
import types

import pytest

from benchmark import flops, harness, registry
from benchmark.trace import inner_scopes

from test_benchmark_trace import STATS, event, plane
from tiny_root import REPO, make_root, repo_benchmark

CELL = "joyai-llm-flash-ep32.round-eval"
TINY_LM = os.path.join(REPO, "tests", "data", "mla_tiny.json")
T, VOCAB = 16, 96
READERS = ["mla_attention_ms", "mla_attention_mxu_pct", "mtp_ms",
           "shared_expert_ms", "mla_moe_route_ms", "mla_moe_experts_ms",
           "mla_moe_experts_mxu_pct", "mla_moe_load_max_over_mean",
           "mla_moe_overflow_share"]


def real_config():
    with open(os.path.join(REPO, "benchmark", "configs",
                           "joyai-llm-flash-ep32.json")) as f:
        return json.load(f)


def tiny_config():
    real = real_config()
    with open(TINY_LM) as f:
        pub = json.load(f)
    config = copy.deepcopy(real)
    config.update({k: v for k, v in pub.items()
                   if k not in ("name", "source", "assumed")})
    config.update(
        name="tiny-mla", source="tests",
        flags=["--data=tokens", "--arch=mla_moe", f"--lm_config={TINY_LM}",
               "--lm_layers=0,1,2", "--lm_experts_held=4",
               "--lm_expert_offset=2", f"--lm_vocab_held={VOCAB}",
               f"--seq_len={T}", "--num_agents=4", "--num_corrupt=1",
               "--poison_frac=0.5", "--robustLR_threshold=3", "--local_ep=2",
               "--bs=2", "--remat", "--agent_chunk=1",
               "--synth_train_size=8", "--synth_val_size=4", "--eval_bs=2"],
        layers_held=[0, 1, 2], num_hidden_layers=3, n_routed_experts=4,
        expert_offset=2, vocab_size=VOCAB, seq_len=T, agents=4, parameters=1,
        examples_per_round=2 * 8 * T)
    config["published"]["n_routed_experts"] = {"source": 16, "here": 4}
    config["published"]["num_hidden_layers"] = {"source": 6, "here": 3}
    config["backdoor"].update(trigger=[VOCAB - 3, VOCAB - 2, VOCAB - 1],
                              triggers_per_sequence=4)
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
    tmp = tmp_path_factory.mktemp("mla")
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
    entry = next(c for c in bench["configs"]
                 if c["name"] == "joyai-llm-flash-ep32")
    assert registry.config_problems(cell.config, entry) == []
    assert cell.chips == 1 and cell.traffic_name == "round-eval"
    assert cell.checks == ["c1_fold_blocks", "c2_mla_token_eval",
                           "c2_token_round"]
    assert cell.config["reference"] == "joyai_llm_flash"
    assert cell.config["parameters"] == 491_696_128
    assert cell.config["examples_per_round"] == 163_840
    assert cell.config["deployment"]["chips_sharing_a_layer"] == 32
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
            "models", "joyai_llm_flash.json")) as f:
        pub = json.load(f)
    assert cell.config["reduced"] == ["num_hidden_layers", "n_routed_experts",
                                      "vocab_size"]
    for key, value in pub.items():
        if key in cell.config["reduced"] or key in ("name", "source",
                                                    "assumed"):
            continue
        assert cell.config[key] == value, key
    for key in cell.config["reduced"]:
        assert cell.config["published"][key]["source"] == pub[key]
    assert cell.config["mtp_loss_weight"] == \
        pub["assumed"]["mtp_loss_weight"]
    parts = cell.config["parameters_by_part"]
    assert (sum(parts["layers"]) + parts["mtp_module"]
            + parts["embedding_held"] + parts["head_held"]
            + parts["final_norm"]) == cell.config["parameters"]
    for key in ("mtp_loss_weight", "initializer_range",
                "e_score_correction_bias", "mtp_concatenation",
                "mtp_hidden_state"):
        assert key in cell.config["assumed"], key


def test_tiny_cell_is_correct_and_the_three_checks_ran(ran):
    _root, result, lines = ran
    assert result["correct"] is True, lines
    names = [ln.split(" ", 3)[2] for ln in lines
             if ln.startswith("[bench] check ")]
    assert names == ["c1_fold_blocks", "c2_mla_token_eval", "c2_token_round"]
    c2 = json.loads(next(ln for ln in lines if "check c2_mla_token_eval" in ln
                         ).split(" ", 3)[3])
    assert c2["backdoor"] == {"wrong_place": 0, "wrong_ids": 0, "stray": 0}
    # eval routes through the main model's two sparse layers only
    assert len(c2["pairs_reference"]) == 2
    assert all(sum(row) == 4 * T * 4 for row in c2["pairs_reference"])
    for key in ("c2_mla_token_eval.Validation/Loss",
                "c2_mla_token_eval.pairs_unaccounted",
                "c2_token_round.train_loss", "c2_token_round.update_rel_err",
                "c1_fold_blocks.ulps_of_leaf_scale"):
        value, limit = result["compared"][key]
        assert value <= limit, key


def test_counters_reach_the_readers_and_trace_readers_stay_silent(ran):
    """On the CPU there is no device trace: the seven trace readers leave
    their metrics out, the two counter readers read what the program
    counted for the traced rounds."""
    _root, result, _lines = ran
    got = {k for k in result["metrics"] if k in READERS}
    assert got == {"mla_moe_load_max_over_mean", "mla_moe_overflow_share"}
    assert result["metrics"]["mla_moe_load_max_over_mean"]["value"] >= 1.0
    assert 0.0 <= result["metrics"]["mla_moe_overflow_share"]["value"] <= 100.0
    from benchmark import program_view
    tr = program_view.tracer()
    counted = {n: v for n, v, _l in tr.counted()}
    assert counted["mtp_depth"] == 1 and counted["shared_experts"] == 1
    held = [v for n, v, _l in tr.counted() if n == "moe_pairs_held"]
    absent = [v for n, v, _l in tr.counted() if n == "moe_pairs_absent"]
    # 4 clients x 2 steps x 2 sequences x T tokens x 4 experts x (2 main
    # sparse layers + the MTP module's block)
    assert held and all(h + a == 4 * 2 * 2 * T * 4 * 3
                        for h, a in zip(held, absent, strict=True))


def test_a_departing_mtp_weight_fails_the_round_check(ran, tmp_path):
    """`c2_token_round` on the same round against a reference told another
    weight for the auxiliary term: the loss and the update both leave."""
    root, _result, _lines = ran
    cell = registry.resolve(registry.load_benchmark(root),
                            "tiny-cnn.round-eval")
    check = registry.load_module(cell.search_dirs, "checks", "c2_token_round")
    from defending_against_backdoors_with_robust_learning_rate_tpu import (
        train)
    from defending_against_backdoors_with_robust_learning_rate_tpu.config import (
        args_parser)
    writer = harness.MemoryWriter()
    eng = train.RoundEngine(args_parser(cell.flags + [
        "--seed=5", "--rounds=1", "--platform=cpu", "--no_tensorboard",
        "--no_compile_cache", f"--log_dir={tmp_path}"]), writer=writer)
    try:
        eng.dispatch((1,))
        eng.eval_boundary(1)
        eng.drain_flush()
        rows = writer.at(1)
        assert rows["Train/MTP_Loss"] > 0
        out = {}
        for weight in (0.3, 0.0):
            out[weight] = check.run({
                "config": dict(cell.config, mtp_loss_weight=weight),
                "rows": rows, "eng": types.SimpleNamespace(cfg=eng.cfg),
                "params": eng.model_params,
                "reference": registry.load_module(
                    cell.search_dirs, "reference", "joyai_llm_flash")})
    finally:
        eng.close()
    assert out[0.3]["ok"] is True, out[0.3]["compared"]
    assert out[0.0]["ok"] is False
    value, limit = out[0.0]["compared"]["train_loss"]
    assert value > limit


# ---- the readers, on a hand-built device plane -----------------------------
def _mla_plane():
    top = "jit(step)/local_train/jvp(MlaMoE)"
    paths = {
        1: f"{top}/layer_1/checkpoint/mla_attention/dot_general:",     # 40
        2: f"{top}/layer_1/moe_router/sort:",                          # 10
        3: "ragged-dot-none",                                          # 30
        4: f"{top}/layer_1/shared_expert/dot_general:",                # 8
        5: f"{top}/mtp/mtp_0/block/transpose(jvp(mla_attention))/dot:",  # 20
        6: f"{top}/mtp/lm_head/dot_general:",                          # 6
        7: "jit(eval_fn)/while/body/layer_1/mla_attention/dot:",       # 99
        8: f"{top}/layer_1/moe_router/moe_experts/convert:",           # 4
    }
    metas = {i: (f"%op.{i}", [(1, p), (2, "fusion")])
             for i, p in paths.items()}
    durs = {1: 40, 2: 10, 3: 30, 4: 8, 5: 20, 6: 6, 7: 99, 8: 4}
    ops, at = [], 0
    for i in (1, 2, 3, 8, 4, 5, 6, 7):
        ops.append(event(i, at, durs[i]))
        at += durs[i] + 1
    return plane("/device:TPU:0", [("XLA Ops", ops)], metas, STATS)


@pytest.fixture()
def reader_ctx(tmp_path):
    prof = tmp_path / "trace" / "plugins" / "profile" / "run"
    prof.mkdir(parents=True)
    (prof / "host.xplane.pb").write_bytes(_mla_plane())
    cell = registry.resolve(registry.load_benchmark(), CELL)
    return {"trace": {"by_scope_s": {}}, "traced_rounds": 2, "cell": cell,
            "cfg": types.SimpleNamespace(
                log_dir=str(tmp_path / "logs"), agents_per_round=10,
                local_ep=2, synth_train_size=40, num_agents=10, bs=4),
            "flops": flops, "device": {"kind": "TPU v5 lite"}, "chips": 1}


def _reader(name, monkeypatch, counts):
    cell = registry.resolve(registry.load_benchmark(), CELL)
    reader = registry.load_module(cell.search_dirs, "layer_metrics", name)
    fake = lambda _ctx, key: list(counts.get(key, []))       # noqa: E731
    if hasattr(reader, "traced_counts"):
        monkeypatch.setattr(reader, "traced_counts", fake)
    from benchmark.layer_metrics import moe_load_max_over_mean
    monkeypatch.setattr(moe_load_max_over_mean, "traced_counts", fake)
    return reader


COUNTS = {"moe_pairs_held": [40960.0, 40960.0], "moe_load_max": [1500.0] * 2,
          "moe_load_mean": [1000.0] * 2, "moe_overflow_steps": [3.0, 1.0]}
# nanoseconds of the plane above by scope, over two traced rounds
WANT_MS = {"mla_attention_ms": (40 + 20) / 2e6, "mtp_ms": (20 + 6) / 2e6,
           "shared_expert_ms": 8 / 2e6, "mla_moe_route_ms": 10 / 2e6,
           "mla_moe_experts_ms": (30 + 4) / 2e6}


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
    if name == "mla_moe_load_max_over_mean":
        assert got == pytest.approx(1.5)
    if name == "mla_moe_overflow_share":
        # 4 forwards of 2 rounds x 20 steps x (4 sparse layers + MTP)
        assert got == pytest.approx(100.0 * 4 / (2 * 20 * 5))
    # without a trace, or a program that counted nothing: left out
    inner_scopes._by_inner_scope.cache_clear()
    silent = _reader(name, monkeypatch, {})
    assert silent.read(dict(reader_ctx, trace=None, traced_rounds=0)) is None


def test_experts_share_of_the_peak_counts_pairs_times_widths(reader_ctx,
                                                             monkeypatch):
    reader = _reader("mla_moe_experts_mxu_pct", monkeypatch, COUNTS)
    ms = WANT_MS["mla_moe_experts_ms"]
    want = 100.0 * (18 * 2048 * 768 * 40960) / (ms * 1e-3 * 197e12)
    assert reader.read(reader_ctx) == pytest.approx(want, rel=1e-9)
