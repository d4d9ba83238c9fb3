"""The token task's data (data/tokens.py) and its way through the round:
seeded packed documents with per-client skew, the trigger n-gram -> target
token backdoor, eval over the positions a mask counts, and the folded round
against the stacked round on the token model. CPU, toy widths."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from defending_against_backdoors_with_robust_learning_rate_tpu.config import (
    Config)
from defending_against_backdoors_with_robust_learning_rate_tpu.data import (
    tokens)
from defending_against_backdoors_with_robust_learning_rate_tpu.data.registry import (
    get_federated_data)
from defending_against_backdoors_with_robust_learning_rate_tpu.fl import task
from defending_against_backdoors_with_robust_learning_rate_tpu.fl.evaluate import (
    pad_eval_set)
from defending_against_backdoors_with_robust_learning_rate_tpu.fl.rounds import (
    make_round_fn)
from defending_against_backdoors_with_robust_learning_rate_tpu.models import (
    lfm2_moe as lm, token_ops)
from defending_against_backdoors_with_robust_learning_rate_tpu.models.registry import (
    get_model, init_params)

TINY = os.path.join(os.path.dirname(__file__), "data", "lm_tiny.json")
T, VOCAB = 64, 96


def cfg_of(**kw):
    base = dict(data="tokens", arch="lfm2_moe", lm_config=TINY,
                lm_layers="1,2,3", lm_experts_held=4, lm_vocab_held=VOCAB,
                seq_len=T, num_agents=4, bs=2, local_ep=1,
                synth_train_size=16, synth_val_size=6, eval_bs=2,
                num_corrupt=1, poison_frac=0.5, robustLR_threshold=3,
                agent_chunk=1, seed=11, tensorboard=False,
                compile_cache=False, data_dir="/nonexistent")
    base.update(kw)
    return Config(**base)


@pytest.fixture(scope="module")
def fed():
    return get_federated_data(cfg_of())


def test_shapes_and_the_fields_they_ride(fed):
    tr = fed.train
    assert tr.images.shape == (4, 4, T + 1) and tr.images.dtype == np.int32
    assert tr.labels.shape == (4, 4) and not tr.labels.any()
    assert list(tr.sizes) == [4, 4, 4, 4]
    assert fed.val_images.shape == fed.pval_images.shape == (6, T + 1)
    assert fed.val_labels.shape == fed.pval_labels.shape == (6, T)
    assert fed.val_labels.all()            # every validation position counts
    assert tr.images.min() >= 0 and tr.images.max() < VOCAB
    assert (tr.images == tokens.SEPARATOR).any()       # documents end
    assert fed.synthetic and fed.nbytes > 0
    assert task.input_shape(cfg_of(), fed) == (T,)


def test_same_seed_same_data_and_another_seed_other_data(fed):
    again = get_federated_data(cfg_of())
    np.testing.assert_array_equal(fed.train.images, again.train.images)
    np.testing.assert_array_equal(fed.pval_images, again.pval_images)
    other = get_federated_data(cfg_of(seed=2147483659))   # over 31 bits
    assert (other.train.images != fed.train.images).mean() > 0.5


def test_clients_rank_the_vocabulary_differently():
    big = get_federated_data(cfg_of(seq_len=512, num_corrupt=0))
    tops = []
    for rows in big.train.images:
        counts = np.bincount(rows.reshape(-1), minlength=VOCAB)
        counts[tokens.SEPARATOR] = 0
        tops.append(set(np.argsort(-counts)[:5]))
    # a shared ranking would give the same five most frequent ids
    assert len({frozenset(t) for t in tops}) == 4
    assert all(len(a & b) < 5 for i, a in enumerate(tops)
               for b in tops[i + 1:])


def test_backdoor_is_in_half_the_corrupt_clients_sequences_only(fed):
    trig = tokens.trigger_ids(VOCAB)
    tr = fed.train

    def places(row):
        return [p for p in range(len(row) - 3)
                if np.array_equal(row[p:p + 3], trig)
                and row[p + 3] == cfg_of().target_class]
    per_row = [[len(places(r)) for r in client] for client in tr.images]
    assert sorted(per_row[0]) == [0, 0, 16, 16]        # poison_frac 0.5
    assert all(n == 0 for client in per_row[1:] for n in client)
    assert list(tr.poison_mask.sum(axis=1)) == [2, 0, 0, 0]


def test_poisoned_validation_marks_the_positions_that_predict_the_target(fed):
    trig = tokens.trigger_ids(VOCAB)
    assert list(fed.pval_labels.sum(axis=1)) == [16] * 6
    for clean, row, mask in zip(fed.val_images, fed.pval_images,
                                fed.pval_labels, strict=True):
        for pos in np.flatnonzero(mask):
            np.testing.assert_array_equal(row[pos - 2:pos + 1], trig)
            assert row[pos + 1] == 7
        assert (clean != row).sum() <= 16 * 4


@pytest.mark.parametrize("kw,word", [
    (dict(synth_train_size=10), "whole batches"),
    (dict(synth_train_size=12, bs=2), "whole batches"),
    (dict(target_class=95), "cannot hold"),
])
def test_a_task_the_data_cannot_deal_is_refused(kw, word):
    with pytest.raises(ValueError, match=word):
        get_federated_data(cfg_of(**kw))


def test_eval_counts_only_the_positions_the_mask_marks(fed):
    cfg = cfg_of()
    model = get_model(cfg.data, cfg.model_arch, "f32", cfg=cfg)
    params = init_params(model, (T,), jax.random.PRNGKey(0))
    eval_fn = task.make_eval_fn(model, None, cfg)
    assert eval_fn.__name__ == "eval_fn"     # the benchmark reads the name
    val = tuple(map(jnp.asarray, pad_eval_set(
        fed.pval_images, fed.pval_labels, 4)))      # 6 rows: 2 of padding
    assert val[1].shape == (2, 4, T) and float(val[2].sum()) == 6
    loss, acc, pairs = eval_fn(params, *val)
    logits, _ = model.apply({"params": params},
                            jnp.asarray(fed.pval_images[:, :-1]))
    logp = jax.nn.log_softmax(logits)
    tgt = fed.pval_images[:, 1:]
    ce = -np.take_along_axis(np.asarray(logp), tgt[..., None], -1)[..., 0]
    want = (ce * fed.pval_labels).sum() / fed.pval_labels.sum()
    np.testing.assert_allclose(float(loss), want, rtol=1e-5)
    assert 0.0 <= float(acc) <= 1.0
    # two sparse layers; the padded rows are routed too (8 rows in all)
    assert pairs.shape == (2, 5)
    np.testing.assert_array_equal(np.asarray(pairs.sum(axis=1)),
                                  [8 * T * 2] * 2)


def test_folded_round_matches_stacked_round_on_the_token_model(fed):
    cfg = cfg_of()
    model = get_model(cfg.data, cfg.model_arch, "f32", remat=True,
                      cfg=cfg)
    params = init_params(model, (T,), jax.random.PRNGKey(0))
    arrays = tuple(map(jnp.asarray, (fed.train.images, fed.train.labels,
                                     fed.train.sizes)))
    out = {}
    for path in ("stack", "fold"):
        fn = make_round_fn(cfg.replace(agg_path=path), model, None, *arrays)
        out[path] = fn(params, jax.random.PRNGKey(5))
    for a, b in zip(jax.tree_util.tree_leaves(out["stack"][0]),
                    jax.tree_util.tree_leaves(out["fold"][0]), strict=True):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=0,
                                   atol=2e-6)
    s_info, f_info = out["stack"][1], out["fold"][1]
    for k in ("train_loss",) + task.MOE_ROUND_KEYS:
        np.testing.assert_allclose(float(s_info[k]), float(f_info[k]),
                                   rtol=1e-6)
    # 4 clients x 2 steps x 2 sequences x T tokens x 2 experts x 2 layers
    assert float(f_info["moe_pairs_held"] + f_info["moe_pairs_absent"]) == \
        4 * 2 * 2 * T * 2 * 2
    assert float(f_info["moe_load_max"]) >= float(f_info["moe_load_mean"]) > 0


def test_folded_round_matches_stacked_round_on_the_mla_model():
    """The latent-attention model through both aggregation paths at a tiny
    size: the MTP module's leaves are trained, folded and voted like any
    other, and the auxiliary term's row rides both."""
    tiny = os.path.join(os.path.dirname(__file__), "data", "mla_tiny.json")
    cfg = cfg_of(arch="mla_moe", lm_config=tiny, lm_layers="0,1,2")
    fed = get_federated_data(cfg)
    model = get_model(cfg.data, cfg.model_arch, "f32", remat=True, cfg=cfg)
    params = init_params(model, (T,), jax.random.PRNGKey(0))
    arrays = tuple(map(jnp.asarray, (fed.train.images, fed.train.labels,
                                     fed.train.sizes)))
    out = {}
    for path in ("stack", "fold"):
        fn = make_round_fn(cfg.replace(agg_path=path), model, None, *arrays)
        out[path] = fn(params, jax.random.PRNGKey(5))
    moved = jax.tree_util.tree_map(
        lambda a, b: float(jnp.abs(a - b).max()), out["fold"][0], params)
    # (a held expert no token picked has no gradient: at toy widths the
    # fixed bias decides most selections)
    assert all(v > 0 for k, v in moved["mtp_0"].items() if k != "block")
    assert all(moved["mtp_0"]["block"][k] > 0
               for k in ("gate", "q_a_proj", "kv_a_norm", "shared_w2"))
    assert max(jax.tree_util.tree_leaves(moved["mtp_0"])) > 0
    for a, b in zip(jax.tree_util.tree_leaves(out["stack"][0]),
                    jax.tree_util.tree_leaves(out["fold"][0]), strict=True):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=0,
                                   atol=2e-6)
    s_info, f_info = out["stack"][1], out["fold"][1]
    for k in ("train_loss", "mtp_loss") + task.MOE_ROUND_KEYS:
        np.testing.assert_allclose(float(s_info[k]), float(f_info[k]),
                                   rtol=1e-6)
    assert float(f_info["mtp_loss"]) > 0
    assert float(f_info["train_loss"]) > float(f_info["mtp_loss"]) * 0.3
    # 4 clients x 2 steps x 2 sequences x T tokens x 4 experts x (2 sparse
    # layers + the MTP module's block)
    assert float(f_info["moe_pairs_held"] + f_info["moe_pairs_absent"]) == \
        4 * 2 * 2 * T * 4 * 3


def test_folded_round_matches_stacked_round_on_the_window_model():
    """The window/full-attention model through both aggregation paths at a
    tiny size (T 64 over a window of 8 keys): both layer kinds' leaves, the
    output gates among them, are trained, folded and voted like any other."""
    tiny = os.path.join(os.path.dirname(__file__), "data", "swa_tiny.json")
    cfg = cfg_of(arch="swa_moe", lm_config=tiny, lm_layers="0,1,2,3,4")
    fed = get_federated_data(cfg)
    model = get_model(cfg.data, cfg.model_arch, "f32", remat=True, cfg=cfg)
    params = init_params(model, (T,), jax.random.PRNGKey(0))
    arrays = tuple(map(jnp.asarray, (fed.train.images, fed.train.labels,
                                     fed.train.sizes)))
    out = {}
    for path in ("stack", "fold"):
        fn = make_round_fn(cfg.replace(agg_path=path), model, None, *arrays)
        out[path] = fn(params, jax.random.PRNGKey(5))
    moved = jax.tree_util.tree_map(
        lambda a, b: float(jnp.abs(a - b).max()), out["fold"][0], params)
    for layer in ("layer_1", "layer_4"):        # a window and a full layer
        assert all(moved[layer][k] > 0 for k in (
            "q_proj", "k_proj", "v_proj", "g_proj", "o_proj", "gate",
            "shared_w2")), (layer, moved[layer])
    for a, b in zip(jax.tree_util.tree_leaves(out["stack"][0]),
                    jax.tree_util.tree_leaves(out["fold"][0]), strict=True):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=0,
                                   atol=2e-6)
    s_info, f_info = out["stack"][1], out["fold"][1]
    assert "mtp_loss" not in f_info
    for k in ("train_loss",) + task.MOE_ROUND_KEYS:
        np.testing.assert_allclose(float(s_info[k]), float(f_info[k]),
                                   rtol=1e-6)
    # 4 clients x 2 steps x 2 sequences x T tokens x 4 experts x 4 layers
    assert float(f_info["moe_pairs_held"] + f_info["moe_pairs_absent"]) == \
        4 * 2 * 2 * T * 4 * 4


@pytest.fixture
def every_pair_held(monkeypatch):
    """Steering for the sorted buffer's second pass, in the test alone: a
    routing bias under which every token picks held experts only, and
    whole tiles of 8 rows (at toy size the module's 512 cover every pair,
    and with them the rule gives the single pass)."""
    def bias(spec, _src_layer):
        e = np.arange(spec.n_experts) - spec.expert_offset
        return np.where((e >= 0) & (e < spec.experts_held), 8.0,
                        0.0).astype(np.float32)
    monkeypatch.setattr(lm, "expert_bias", bias)
    monkeypatch.setattr(token_ops, "MOE_ROWS_TILE", 8)
    monkeypatch.setattr(token_ops, "MOE_ROWS_PER_TOKEN", 1)


def test_round_counts_the_forwards_that_took_the_second_pass(
        fed, every_pair_held):
    cfg = cfg_of(lm_experts_held=2)
    model = get_model(cfg.data, cfg.model_arch, "f32", remat=True, cfg=cfg)
    n_pairs = cfg.bs * T * model.spec.top_k
    assert model.dispatch_rows(cfg.bs * T) == n_pairs // 2
    params = init_params(model, (T,), jax.random.PRNGKey(0))
    arrays = tuple(map(jnp.asarray, (fed.train.images, fed.train.labels,
                                     fed.train.sizes)))
    fn = make_round_fn(cfg.replace(agg_path="fold"), model, None, *arrays)
    new_params, info = fn(params, jax.random.PRNGKey(5))
    # 4 clients x 2 steps x 2 sparse layers, each with all its pairs held
    assert float(info["moe_overflow_steps"]) == 4 * 2 * 2
    assert float(info["moe_pairs_absent"]) == 0
    assert float(info["moe_pairs_held"]) == 4 * 2 * 2 * n_pairs
    assert all(bool(jnp.all(jnp.isfinite(leaf)))
               for leaf in jax.tree_util.tree_leaves(new_params))
    # the experts learned from both passes' pairs
    moved = jax.tree_util.tree_map(lambda a, b: float(jnp.abs(a - b).max()),
                                   new_params, params)
    assert moved["layer_1"]["experts_w2"] > 0 and moved["layer_1"]["gate"] > 0


@pytest.mark.parametrize("held,overflow", [(2, True), (4, False)])
def test_engine_counts_the_buffers_rows_and_its_overflow(
        tmp_path, capsys, monkeypatch, request, held, overflow):
    """The counters' way out of the program: once at build the rows of the
    first pass and of the worst case, every round the forwards that took
    the second pass, in the tracer and in the `Moe/*` rows."""
    import json

    from defending_against_backdoors_with_robust_learning_rate_tpu import (
        train)
    from defending_against_backdoors_with_robust_learning_rate_tpu.config import (
        args_parser)
    if overflow:
        request.getfixturevalue("every_pair_held")
    cfg = args_parser([
        "--platform=cpu", "--data=tokens", "--arch=lfm2_moe",
        f"--lm_config={TINY}", "--lm_layers=1,2", f"--lm_experts_held={held}",
        f"--lm_vocab_held={VOCAB}", "--seq_len=16", "--num_agents=2",
        "--bs=2", "--local_ep=1", "--synth_train_size=8",
        "--synth_val_size=4", "--eval_bs=2", "--num_corrupt=1",
        "--poison_frac=0.5", "--robustLR_threshold=2", "--agent_chunk=1", "--remat", "--rounds=1",
        "--snap=1", "--no_tensorboard", "--no_compile_cache",
        f"--log_dir={tmp_path}", "--data_dir=/nonexistent"])
    eng = train.RoundEngine(cfg)
    try:
        for unit in eng.schedule():
            eng.dispatch(unit)
            eng.eval_boundary(eng.rnd)
            eng.post_unit()
        eng.drain.flush()
    finally:
        eng.close()
    pairs = 2 * 16 * 2                    # a step: 2 sequences x 16 x top-2
    rows = pairs // 2 if overflow else pairs
    counted = {name: n for name, n, _labels in eng.tracer.counted()}
    assert counted["moe_rows"] == rows and counted["moe_rows_worst"] == pairs
    assert counted["experts_held"] == held
    # 2 clients x 2 steps x 1 sparse layer
    assert counted["moe_overflow_steps"] == (4 if overflow else 0)
    # sequences of 16: one block, its one square
    assert counted["attn_squares_computed"] == counted["attn_squares"] == 1
    # layer 2 of (1, 2) is the attention layer, and here its core is plain
    assert [(n, labels) for name, n, labels in eng.tracer.counted()
            if name == "attn_path"] == [(1, {"path": "plain"})]
    # its core: q k^T and p v of 4 heads of 8 over the causal half of two
    # sequences of 16
    assert [(n, labels) for name, n, labels in eng.tracer.counted()
            if name == "attn_core_flops"] == [
        (2 * 4 * (8 + 8) * (16 * 17 // 2) * 2, {"kind": "full"})]
    out = capsys.readouterr().out
    assert f"[model] moe rows {rows} of {pairs}" in out
    assert "[model] attention squares 1 of 1" in out
    assert "; path plain in 1 layer(s)" in out
    # and its rotary step is rope_half's product
    assert counted["attn_rope_product"] == 1
    assert "; rotary by product in 1 layer(s)" in out
    run_dirs = [d for d in tmp_path.iterdir() if d.is_dir()]
    with open(run_dirs[0] / "metrics.jsonl") as fh:
        written = [json.loads(line) for line in fh]
    steps = [r["value"] for r in written
             if r.get("tag") == "Moe/Overflow_Steps"]
    assert steps == [counted["moe_overflow_steps"]]
