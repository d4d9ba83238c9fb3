"""The linear/full-attention token cell (`qwen3-next-80b-a3b-ep32.round-eval`):
its entries and files as `BENCHMARK.json` names them, every width not cut
equal to the published file, the reference's counts, the harness at a toy
size on the CPU with the configuration's own three checks and reference, a
reference told another rule failing `c2_gdn_token_eval`, and each of the
four per-layer readers this cell brings, on a hand-built trace whose
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

CELL = "qwen3-next-80b-a3b-ep32.round-eval"
TINY_LM = os.path.join(REPO, "tests", "data", "gdn_tiny.json")
T, VOCAB, HELD = 24, 96, [0, 1, 2, 3]
READERS = ["gdn_layers_ms", "gdn_core_ms", "gdn_core_roofline_pct",
           "gated_attention_ms"]
PUBLISHED = os.path.join(
    REPO, "defending_against_backdoors_with_robust_learning_rate_tpu",
    "models", "qwen3_next_80b_a3b.json")


def real_config():
    with open(os.path.join(REPO, "benchmark", "configs",
                           "qwen3-next-80b-a3b-ep32.json")) as f:
        return json.load(f)


def tiny_config():
    real = real_config()
    with open(TINY_LM) as f:
        pub = json.load(f)
    config = copy.deepcopy(real)
    config.update({k: v for k, v in pub.items()
                   if k not in ("name", "source", "assumed")})
    config.update(
        name="tiny-gdn", source="tests",
        flags=["--data=tokens", "--arch=gdn_moe", f"--lm_config={TINY_LM}",
               "--lm_layers=0,1,2,3", "--lm_experts_held=4",
               "--lm_expert_offset=2", f"--lm_vocab_held={VOCAB}",
               f"--seq_len={T}", "--num_agents=4", "--num_corrupt=1",
               "--poison_frac=0.5", "--robustLR_threshold=3", "--local_ep=2",
               "--bs=2", "--remat", "--agent_chunk=1",
               "--synth_train_size=8", "--synth_val_size=4", "--eval_bs=2"],
        layers_held=HELD, num_hidden_layers=4, num_experts=4,
        expert_offset=2, vocab_size=VOCAB, seq_len=T, agents=4, parameters=1,
        examples_per_round=2 * 8 * T)
    config["published"]["num_experts"] = {"source": 16, "here": 4}
    config["backdoor"].update(trigger=[VOCAB - 3, VOCAB - 2, VOCAB - 1],
                              triggers_per_sequence=6)
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
    tmp = tmp_path_factory.mktemp("gdn")
    root = make_root(tmp, cells=(("tiny-cnn.round-eval", "round-eval"),),
                     config=tiny_config())
    lines = []
    result = harness.run_cell("tiny-cnn.round-eval", 2147483677, 0.0, True,
                              platform="cpu", bench_path=root,
                              say=lines.append)
    return root, result, lines


def test_the_entries_name_these_files_and_every_width_is_published():
    bench = repo_benchmark()
    cell = registry.resolve(registry.load_benchmark(), CELL)
    entry = next(c for c in bench["configs"]
                 if c["name"] == "qwen3-next-80b-a3b-ep32")
    assert registry.config_problems(cell.config, entry) == []
    assert cell.chips == 1 and cell.traffic_name == "round-eval"
    assert cell.checks == ["c1_fold_blocks", "c2_gdn_token_eval",
                           "c2_token_round"]
    assert cell.config["reference"] == "qwen3_next"
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
    with open(PUBLISHED) as f:
        pub = json.load(f)
    assert cell.config["reduced"] == ["num_hidden_layers", "layer_types",
                                      "num_experts", "vocab_size"]
    for key, value in pub.items():
        if key in cell.config["reduced"] or key in ("name", "source",
                                                    "assumed"):
            continue
        assert cell.config[key] == value, key
    published = cell.config["published"]
    for key in ("num_hidden_layers", "num_experts", "vocab_size"):
        assert published[key]["source"] == pub[key], key
        assert published[key]["here"] == cell.config[key], key
    kinds = published["layer_types"]["source"]
    assert len(kinds) == 48 and kinds.count("full_attention") == 12
    assert cell.config["layer_types"] == kinds[:4] == \
        published["layer_types"]["here"]
    for key in ("in_proj_qkvz", "A_log", "dt_bias", "l2norm_eps", "mtp",
                "state", "initializer_range", "gated_norm"):
        assert key in cell.config["assumed"], key


def test_the_parts_add_up_to_the_parameters_the_model_has():
    import jax.numpy as jnp

    from defending_against_backdoors_with_robust_learning_rate_tpu.models import (
        gdn_moe)
    from defending_against_backdoors_with_robust_learning_rate_tpu.models.registry import (
        abstract_params, param_count)
    config = real_config()
    parts = config["parameters_by_part"]
    spec = gdn_moe.spec_from("qwen3-next-80b-a3b", "0,1,2,3", 16, 0, 18992)
    shapes = abstract_params(gdn_moe.GdnMoE(spec=spec, dtype=jnp.bfloat16),
                             (4096,))
    assert param_count(shapes) == config["parameters"] == 424_340_544
    assert (sum(parts["layers"]) + parts["embedding_held"]
            + parts["head_held"] + parts["final_norm"]) == config["parameters"]
    for kind in ("gdn_mixer", "gated_attention"):
        assert sum(parts[kind + "_parts"].values()) == parts[kind]
        names = parts[kind + "_parts"]
        layer = shapes["layer_0" if kind == "gdn_mixer" else "layer_3"]
        assert {n: param_count(layer[n]) for n in names} == names
    assert parts["experts_held_a_layer"] == 16 * parts["one_expert"]
    assert parts["gdn_mixer"] == 33_718_464
    assert parts["gated_attention"] == 27_263_488


def test_the_reference_counts_the_chunked_core_and_the_forward():
    cell = registry.resolve(registry.load_benchmark(), CELL)
    ref = registry.load_module(cell.search_dirs, "reference", "qwen3_next")
    dims = ref.dims_of(cell.config)
    assert [k for _s, k in dims["layers"]] == ["linear_attention"] * 3 + [
        "full_attention"]
    assert (dims["router_experts"], dims["experts_held"]) == (512, 16)
    # a value head and token: 10 C d + 6 d^2 + (2/3) C^2 at C 64, d 128
    per = 10 * 64 * 128 + 6 * 128 ** 2 + 2 / 3 * 64 ** 2
    assert ref.gdn_core_flops(1, cell.config) == pytest.approx(3 * 32 * per)
    assert ref.gdn_core_bytes(1, cell.config) == 3 * (
        (2048 + 2048 + 4096) * 2 + 2 * 32 * 4 + 4096 * 4)
    gdn = 33_718_464 - 4 * 8192 - 32 - 32 - 128       # the products
    full = 27_263_488 - 512 + 4097 / 2 * 16 * 2 * 256   # and the causal half
    moe = 2048 * 512 + 3 * 2048 * 512 + 2048 + 10 * 16 / 512 * 3 * 2048 * 512
    want = 2 * (18992 * 2048 + 3 * gdn + full + 4 * moe) + 3 * 32 * per
    assert ref.forward_flops_of(cell.config) == pytest.approx(want,
                                                              rel=1e-12)
    assert 0.42e9 < want < 0.43e9         # about 0.43 GFLOP a token


def test_the_program_counts_what_the_reference_counts():
    import jax.numpy as jnp

    from defending_against_backdoors_with_robust_learning_rate_tpu.models import (
        gdn_moe)
    cell = registry.resolve(registry.load_benchmark(), CELL)
    ref = registry.load_module(cell.search_dirs, "reference", "qwen3_next")
    spec = gdn_moe.spec_from("qwen3-next-80b-a3b", "0,1,2,3", 16, 0, 18992)
    built = gdn_moe.GdnMoE(spec=spec, dtype=jnp.bfloat16).build_counters(
        8192, 4096)
    assert built["gdn_core_flops"] == pytest.approx(
        ref.gdn_core_flops(8192, cell.config), rel=1e-12)
    assert (built["gdn_layers"], built["gdn_chunk"], built["gdn_chunks"]) \
        == (3, 64, 64)
    assert built["gdn_state_bytes"] == 2 * 32 * 128 * 128 * 4
    assert built["attn_full_layers"] == 1
    assert built["moe_rows_worst"] == 8192 * 10


def test_tiny_cell_is_correct_and_the_three_checks_ran(ran):
    _root, result, lines = ran
    assert result["correct"] is True, lines
    names = [ln.split(" ", 3)[2] for ln in lines
             if ln.startswith("[bench] check ")]
    assert names == ["c1_fold_blocks", "c2_gdn_token_eval", "c2_token_round"]
    c2 = json.loads(next(ln for ln in lines if "check c2_gdn_token_eval" in ln
                         ).split(" ", 3)[3])
    assert c2["backdoor"] == {"wrong_place": 0, "wrong_ids": 0, "stray": 0}
    assert len(c2["pairs_reference"]) == 4       # every layer is sparse
    assert all(sum(row) == 4 * T * 4 for row in c2["pairs_reference"])
    c3 = json.loads(next(ln for ln in lines if ln.startswith("[bench] C3 ")
                         ).split(" ", 2)[2])
    assert c3["ok"] and c3["rows_finite"] and c3["params_finite"]
    for key in ("c2_gdn_token_eval.Validation/Loss",
                "c2_gdn_token_eval.pairs_unaccounted",
                "c2_token_round.train_loss", "c2_token_round.update_rel_err",
                "c1_fold_blocks.ulps_of_leaf_scale"):
        value, limit = result["compared"][key]
        assert value <= limit, key


def test_counters_reach_the_program_and_trace_readers_stay_silent(ran):
    """On the CPU there is no device trace: the four readers leave their
    metrics out; the program counted its linear layers at build."""
    _root, result, _lines = ran
    assert not {k for k in result["metrics"] if k in READERS}
    from benchmark import program_view
    counted = {n: v for n, v, _l in program_view.tracer().counted()}
    assert (counted["gdn_layers"], counted["gdn_chunk"],
            counted["gdn_chunks"], counted["attn_full_layers"]) == (3, 64, 1, 1)
    assert counted["gdn_core_flops"] > 0


@pytest.mark.parametrize("change", ["forgetful_state", "other_experts"])
def test_a_reference_told_another_rule_fails_the_eval_check(ran, tmp_path,
                                                           monkeypatch,
                                                           change):
    """`c2_gdn_token_eval.run` on the engine's own evaluation against a
    reference whose recurrence forgets its state at every token, or that
    holds experts from another offset than the program's: the check fails;
    told the truth it holds."""
    root, _result, _lines = ran
    cell = registry.resolve(registry.load_benchmark(root),
                            "tiny-cnn.round-eval")
    check = registry.load_module(cell.search_dirs, "checks",
                                 "c2_gdn_token_eval")
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
        out = {}
        for told in (False, True):
            ref = registry.load_module(cell.search_dirs, "reference",
                                       "qwen3_next")
            config = cell.config
            if told and change == "forgetful_state":
                rule = ref.delta_rule

                def forgetful(q, k, v, g, beta):
                    return rule(q, k, v, g - 1e4, beta)
                monkeypatch.setattr(ref, "delta_rule", forgetful)
            elif told:
                config = dict(config, expert_offset=0)
            out[told] = check.run({
                "config": config, "rows": writer.at(1), "eng": eng,
                "val": eng.val, "params": eng.model_params,
                "reference": ref})
    finally:
        eng.close()
    assert out[False]["ok"] is True, out[False]["compared"]
    assert out[True]["ok"] is False
    left = {k for k, (v, lim) in out[True]["compared"].items() if v > lim}
    assert left & {"Validation/Loss", "pairs_moved_share",
                   "pairs_worst_share_of_allowed"}, left


# ---- the readers, on a hand-built device plane -----------------------------
def _gdn_plane():
    top = "jit(step)/local_train/jvp(GdnMoE)"
    paths = {
        1: f"{top}/layer_0/checkpoint/gdn/dot_general:",                 # 40
        2: f"{top}/layer_0/checkpoint/gdn/gdn_core/while/body/dot:",     # 30
        3: f"{top}/layer_1/transpose(jvp(gdn))/transpose(jvp(gdn_core))"
           "/triangular-solve:",                                         # 10
        4: f"{top}/layer_3/gated_attention/attn_core/custom-call:",      # 20
        5: f"{top}/layer_3/transpose(jvp(gated_attention))/dot:",        # 6
        6: f"{top}/layer_0/moe_router/sort:",                            # 8
        7: "jit(eval_fn)/while/body/layer_0/gdn/gdn_core/dot:",          # 99
        8: f"{top}/lm_head/dot_general:",                                # 4
    }
    metas = {i: (f"%op.{i}", [(1, p), (2, "fusion")])
             for i, p in paths.items()}
    durs = {1: 40, 2: 30, 3: 10, 4: 20, 5: 6, 6: 8, 7: 99, 8: 4}
    ops, at = [], 0
    for i in (1, 2, 3, 4, 5, 6, 8, 7):
        ops.append(event(i, at, durs[i]))
        at += durs[i] + 1
    return plane("/device:TPU:0", [("XLA Ops", ops)], metas, STATS)


@pytest.fixture()
def reader_ctx(tmp_path):
    prof = tmp_path / "trace" / "plugins" / "profile" / "run"
    prof.mkdir(parents=True)
    (prof / "host.xplane.pb").write_bytes(_gdn_plane())
    cell = registry.resolve(registry.load_benchmark(), CELL)
    inner_scopes._by_inner_scope.cache_clear()
    return {"trace": {"by_scope_s": {}}, "traced_rounds": 2, "cell": cell,
            "cfg": types.SimpleNamespace(log_dir=str(tmp_path / "logs")),
            "flops": flops, "device": {"kind": "TPU v5 lite"}, "chips": 1}


# nanoseconds of the plane above by scope, over two traced rounds
WANT_MS = {"gdn_layers_ms": (40 + 30 + 10) / 2e6,
           "gdn_core_ms": (30 + 10) / 2e6,
           "gated_attention_ms": (20 + 6) / 2e6}


@pytest.mark.parametrize("name", READERS)
def test_reader_returns_a_number_on_the_trace_and_none_without(
        reader_ctx, name):
    entry = next(m for m in repo_benchmark()["per_layer"]
                 if m["name"] == name)
    cell = reader_ctx["cell"]
    reader = registry.load_module(cell.search_dirs, "layer_metrics", name)
    assert (reader.LAYER, reader.UNIT, reader.SOURCE, reader.MOVES) == (
        entry["layer"], entry["unit"], entry["source"], entry["moves"])
    got = reader.read(reader_ctx)
    assert isinstance(got, float) and got > 0
    if name in WANT_MS:
        assert got == pytest.approx(WANT_MS[name], rel=1e-9)
    else:
        ref = registry.load_module(cell.search_dirs, "reference",
                                   "qwen3_next")
        least = max(3 * ref.gdn_core_flops(163_840, cell.config) / 197e12,
                    3 * ref.gdn_core_bytes(163_840, cell.config) / 819e9)
        assert got == pytest.approx(
            100 * least / (WANT_MS["gdn_core_ms"] * 1e-3), rel=1e-9)
    assert reader.read(dict(reader_ctx, trace=None, traced_rounds=0)) is None


def test_a_program_that_plants_no_such_scope_leaves_the_readers_silent(
        tmp_path):
    """A program without the linear-attention model plants none of the
    scopes: on its trace every reader returns None and raises nothing."""
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
    for name in READERS:
        reader = registry.load_module(cell.search_dirs, "layer_metrics",
                                      name)
        assert reader.read(ctx) is None, name
