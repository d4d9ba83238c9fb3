"""The token task through the harness at a toy size on the CPU: the
configuration's own two checks (`c1_fold_blocks`, `c2_token_eval`) and its
reference, as `BENCHMARK.json`'s `lfm2-8b-a1b-ep4` names them, against a
program whose model check has to fail when the program departs from the
reference; and the helper that files device time by the innermost scope.
No number here is a device metric."""

import copy
import json
import os

import pytest

from benchmark import harness, registry
from benchmark.trace import inner_scopes

from tiny_root import REPO, make_root, repo_benchmark

TINY_LM = os.path.join(REPO, "tests", "data", "lm_tiny.json")
T, VOCAB = 16, 96


def tiny_config():
    with open(os.path.join(REPO, "benchmark", "configs",
                           "lfm2-8b-a1b-ep4.json")) as f:
        real = json.load(f)
    with open(TINY_LM) as f:
        pub = json.load(f)
    held = [1, 2, 3, 4]
    config = copy.deepcopy(real)
    config.update({k: pub[k] for k in (
        "hidden_size", "intermediate_size", "moe_intermediate_size",
        "num_attention_heads", "num_key_value_heads", "num_experts_per_tok")})
    config.update(
        name="tiny-lm", source="tests",
        flags=["--data=tokens", "--arch=lfm2_moe", f"--lm_config={TINY_LM}",
               "--lm_layers=1,2,3,4", "--lm_experts_held=4",
               "--lm_expert_offset=2", f"--lm_vocab_held={VOCAB}",
               f"--seq_len={T}", "--num_agents=4", "--num_corrupt=1",
               "--poison_frac=0.5", "--robustLR_threshold=3", "--local_ep=2",
               "--bs=2", "--remat", "--agent_chunk=1",
               "--synth_train_size=8", "--synth_val_size=4", "--eval_bs=2"],
        layers_held=held, layer_types=[pub["layer_types"][i] for i in held],
        num_hidden_layers=4, num_dense_layers=1, num_experts=4,
        expert_offset=2, vocab_size=VOCAB, head_dim=8, seq_len=T,
        agents=4, parameters=1, examples_per_round=2 * 8 * T)
    config["published"]["num_experts"] = {"source": 8, "here": 4}
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
    tmp = tmp_path_factory.mktemp("tokens")
    root = make_root(tmp, cells=(("tiny-cnn.round-eval", "round-eval"),),
                     config=tiny_config())
    lines = []
    result = harness.run_cell("tiny-cnn.round-eval", 2147483659, 0.0, True,
                              platform="cpu", bench_path=root,
                              say=lines.append)
    return root, result, lines


def test_the_real_entry_names_these_files():
    bench = repo_benchmark()
    cell = registry.resolve(registry.load_benchmark(),
                            "lfm2-8b-a1b-ep4.round-eval")
    assert cell.checks == ["c1_fold_blocks", "c2_token_eval",
                           "c2_token_round"]
    assert cell.config["reference"] == "lfm2_moe"
    assert cell.config["parameters"] == 507_820_160
    assert cell.config["examples_per_round"] == 163_840
    new = [m["name"] for m in bench["per_layer"]
           if m.get("workloads") == [cell.name]]
    assert new == ["moe_experts_ms", "moe_experts_mxu_pct", "moe_route_ms",
                   "short_conv_ms", "moe_load_max_over_mean"]
    # every width is the published one
    with open(os.path.join(
            REPO, "defending_against_backdoors_with_robust_learning_rate_tpu",
            "models", "lfm2_8b_a1b.json")) as f:
        pub = json.load(f)
    for key, value in pub.items():
        if key in cell.config["reduced"] or key in ("name", "source",
                                                    "assumed"):
            continue
        assert cell.config[key] == value, key
    for key in cell.config["reduced"]:
        assert cell.config["published"][key]["source"] == pub[key]


def test_token_cell_is_correct_and_both_checks_ran(ran):
    _root, result, lines = ran
    assert result["correct"] is True, lines
    names = [ln.split(" ", 3)[2] for ln in lines
             if ln.startswith("[bench] check ")]
    assert names == ["c1_fold_blocks", "c2_token_eval", "c2_token_round"]
    c1 = json.loads(next(ln for ln in lines if "check c1_fold_blocks" in ln
                         ).split(" ", 3)[3])
    assert c1["lr_mismatched"] == 0 and c1["agents"] == 4
    assert c1["coordinates_folded"] > c1["coordinates_compared"] > 0
    c2 = json.loads(next(ln for ln in lines if "check c2_token_eval" in ln
                         ).split(" ", 3)[3])
    assert c2["backdoor"] == {"wrong_place": 0, "wrong_ids": 0, "stray": 0}
    assert c2["n_val"] == 4 * T and c2["n_poison"] == 4 * 4
    assert len(c2["pairs_reference"]) == 3
    assert all(sum(row) == 4 * T * 2 for row in c2["pairs_reference"])
    for key in ("c2_token_eval.Validation/Loss",
                "c2_token_eval.pairs_worst_share_of_allowed",
                "c2_token_eval.pairs_unaccounted",
                "c1_fold_blocks.ulps_of_leaf_scale"):
        value, limit = result["compared"][key]
        assert value <= limit, key


def test_program_counters_reach_the_readers(ran):
    """On the CPU there is no device trace, so the trace readers leave
    their metrics out; the counter reader reads what the program counted
    for the traced rounds."""
    _root, result, lines = ran
    assert "moe_experts_ms" not in result["metrics"]
    from benchmark import program_view
    tr = program_view.tracer()
    held = [v for n, v, _l in tr.counted() if n == "moe_pairs_held"]
    absent = [v for n, v, _l in tr.counted() if n == "moe_pairs_absent"]
    # 4 clients x 2 steps x 2 sequences x T tokens x 2 experts x 3 layers
    assert held and all(h + a == 4 * 2 * 2 * T * 2 * 3
                        for h, a in zip(held, absent, strict=True))
    names = {n for n, _v, _l in tr.counted()}
    assert {"agg_path", "agg_stack_bytes", "agg_limit_bytes", "experts_held",
            "vocab_held", "moe_load_max", "moe_load_mean"} <= names
    assert any(s.name == "setup/task" for s in tr.records())


@pytest.mark.parametrize("defect", ["no_bias", "dropped_pairs",
                                    "wrong_target"])
def test_model_check_fails_a_departing_program(ran, defect, monkeypatch,
                                               tmp_path):
    """The same check on rows a departing program would have written."""
    root, _result, lines = ran
    cell = registry.resolve(registry.load_benchmark(root),
                            "tiny-cnn.round-eval")
    check = registry.load_module(cell.search_dirs, "checks", "c2_token_eval")
    from defending_against_backdoors_with_robust_learning_rate_tpu import (
        train)
    from defending_against_backdoors_with_robust_learning_rate_tpu.config import (
        args_parser)
    from defending_against_backdoors_with_robust_learning_rate_tpu.models import (
        lfm2_moe)
    import numpy as np
    if defect == "no_bias":
        monkeypatch.setattr(
            lfm2_moe, "expert_bias",
            lambda spec, layer: np.zeros((spec.n_experts,), np.float32))
    cfg = args_parser(cell.flags + ["--seed=5", "--rounds=1", "--platform=cpu",
                                    "--no_tensorboard", "--no_compile_cache",
                                    f"--log_dir={tmp_path}"])
    writer = harness.MemoryWriter()
    eng = train.RoundEngine(cfg, writer=writer)
    try:
        eng.dispatch((1,))
        eng.eval_boundary(1)
        eng.drain_flush()
        rows = writer.at(1)
        config = copy.deepcopy(cell.config)
        if defect == "dropped_pairs":
            rows["Moe/Eval_Pairs/L0E0"] -= 1
        if defect == "wrong_target":
            config["backdoor"]["target"] = 8
        out = check.run({"config": config, "cfg": cfg, "eng": eng,
                         "params": eng.model_params, "val": eng.val,
                         "rows": rows,
                         "reference": registry.load_module(
                             cell.search_dirs, "reference", "lfm2_moe")})
    finally:
        eng.close()
    assert out["ok"] is False


@pytest.mark.parametrize("defect", ["unchanged", "dropped_client",
                                    "momentum_off", "half_batch"])
def test_round_check_fails_a_departing_round(ran, defect, monkeypatch,
                                             tmp_path):
    """`c2_token_round` on what a departing round would have produced: a
    state left unchanged, a client missing from the mean, an optimiser
    without its momentum, half of every batch left out."""
    import types

    import jax

    root, _result, _lines = ran
    cell = registry.resolve(registry.load_benchmark(root),
                            "tiny-cnn.round-eval")
    check = registry.load_module(cell.search_dirs, "checks", "c2_token_round")
    from defending_against_backdoors_with_robust_learning_rate_tpu import (
        train)
    from defending_against_backdoors_with_robust_learning_rate_tpu.config import (
        args_parser)
    from defending_against_backdoors_with_robust_learning_rate_tpu.fl import (
        rounds, task)
    flags = cell.flags + ["--seed=5", "--rounds=1", "--platform=cpu",
                          "--no_tensorboard", "--no_compile_cache",
                          f"--log_dir={tmp_path}"]
    stated = args_parser(flags)
    if defect == "momentum_off":
        flags = flags + ["--client_moment=0"]
    if defect == "dropped_client":
        real = rounds.aggregate_updates

        def without_the_first(updates, sizes, *args, **kw):
            updates = jax.tree_util.tree_map(lambda u: u.at[0].set(0.0),
                                             updates)
            return real(updates, sizes.at[0].set(0), *args, **kw)
        monkeypatch.setattr(rounds, "aggregate_updates", without_the_first)
    if defect == "half_batch":
        real_loss = task.make_batch_loss

        def half(model, cfg, normalize, **kw):
            inner = real_loss(model, cfg, normalize, **kw)
            return lambda p, x, y, w, rng: inner(p, x, y, w.at[0].set(False),
                                                 rng)
        monkeypatch.setattr(task, "make_batch_loss", half)
    writer = harness.MemoryWriter()
    eng = train.RoundEngine(args_parser(flags), writer=writer)
    try:
        before = eng.model_params
        eng.dispatch((1,))
        eng.eval_boundary(1)
        eng.drain_flush()
        out = check.run({
            "config": cell.config, "rows": writer.at(1),
            "eng": types.SimpleNamespace(cfg=eng.cfg.replace(
                client_moment=stated.client_moment)),
            "params": before if defect == "unchanged" else eng.model_params,
            "reference": registry.load_module(cell.search_dirs, "reference",
                                              "lfm2_moe")})
    finally:
        eng.close()
    assert out["ok"] is False
    value, limit = out["compared"]["update_rel_err"]
    assert value > limit
    if defect == "unchanged":
        assert value == pytest.approx(1.0)


def test_innermost_scope_of_a_path():
    names = inner_scopes.MODEL_SCOPES
    cases = {
        "jit(step)/local_train/while/body/layer_1/moe_experts/ragged_dot:":
            "moe_experts",
        "jit(step)/local_train/transpose(jvp(LFM2MoE))/layer_2/"
        "transpose(jvp(moe_router))/gather:": "moe_router",
        "jit(step)/local_train/jvp(LFM2MoE)/layer_0/checkpoint/"
        "rematted_computation/jvp(short_conv)/dot_general:": "short_conv",
        "jit(step)/local_train/moe_router/moe_experts/dot:": "moe_experts",
        "jit(step)/aggregate_rlr/add:": "",
        "ragged-dot-none": "moe_experts",      # the compiler's own name
        "ragged-dot-metadata:": "moe_experts",
        "jit(eval_fn)/while/body/lm_head/dot_general:": "lm_head",
        "": "",
    }
    for path, want in cases.items():
        assert inner_scopes.innermost(path, names) == want, path
    assert inner_scopes.bare("transpose(jvp(moe_experts))") == "moe_experts"


def test_inner_scopes_on_the_recorded_trace():
    """The recorded v5e trace plants no model scope: nothing to read, and
    the outer scopes, asked for as inner ones, read what the reducer reads
    for them."""
    from benchmark.trace import reduce
    path = os.path.join(REPO, "benchmark", "trace", "fixture_v5e.xplane.pb")
    assert inner_scopes.self_seconds(path, inner_scopes.MODEL_SCOPES) == {
        n: 0.0 for n in inner_scopes.MODEL_SCOPES}
    outer = inner_scopes.self_seconds(path, ("local_train", "aggregate_rlr"))
    want = reduce.summarize(path)["by_scope_s"]
    for name, seconds in outer.items():
        assert seconds == pytest.approx(want[name], rel=1e-9)
    # a program's operations can be left out: the eval programs plant none
    # of these scopes, the round program all of them
    names = ("local_train", "aggregate_rlr")
    assert inner_scopes.self_seconds(path, names,
                                     inner_scopes.EVAL_PROGRAMS) == outer
    assert inner_scopes.self_seconds(path, names, ("jit(step)",)) == {
        n: 0.0 for n in names}


def test_an_operation_without_a_path_takes_the_program_before_it():
    assert inner_scopes.program_of(
        "jit(eval_fn)/while/body/lm_head/dot_general:") == "jit(eval_fn)"
    assert inner_scopes.program_of("ragged-dot-none") == ""
    assert inner_scopes.program_of("") == ""
