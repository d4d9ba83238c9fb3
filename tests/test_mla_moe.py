"""The latent-attention token model (models/mla_moe.py) against the plain
reference (benchmark/reference/joyai_llm_flash.py) at toy widths on the CPU,
seeded random weights: each operator, the whole forward, the loss with its
multi-token-prediction term and the gradients, one client's update against
the reference's SGD loop, and the share test of the model-configs guide: the
shares' partial outputs, the shared expert counted once, add up to the uncut
reference's layer. Nothing here is a device metric."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import joyai_llm_flash as ref
from defending_against_backdoors_with_robust_learning_rate_tpu.config import (
    Config)
from defending_against_backdoors_with_robust_learning_rate_tpu.fl import task
from defending_against_backdoors_with_robust_learning_rate_tpu.fl.client import (
    make_local_train)
from defending_against_backdoors_with_robust_learning_rate_tpu.models import (
    mla_moe as mm, token_ops)
from defending_against_backdoors_with_robust_learning_rate_tpu.models.registry import (
    abstract_params, get_model, init_params, param_count)

TINY = os.path.join(os.path.dirname(__file__), "data", "mla_tiny.json")
LAYERS = "0,1,2"          # the dense layer and two sparse ones, plus MTP
HELD, OFFSET, VOCAB, T = 4, 2, 96, 12
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def tiny_cfg(**kw):
    base = dict(data="tokens", arch="mla_moe", lm_config=TINY,
                lm_layers=LAYERS, lm_experts_held=HELD,
                lm_expert_offset=OFFSET, lm_vocab_held=VOCAB, seq_len=T,
                num_agents=4, bs=2, local_ep=2, synth_train_size=8,
                synth_val_size=4, eval_bs=2, num_corrupt=1, poison_frac=0.5,
                robustLR_threshold=3, agent_chunk=1, target_class=7,
                tensorboard=False, compile_cache=False,
                data_dir="/nonexistent_use_synthetic")
    base.update(kw)
    return Config(**base)


def ref_dims(spec):
    """The reference's view of the same cut, from a configuration dict as
    a benchmark file would state it."""
    with open(TINY) as f:
        pub = json.load(f)
    held = [src for src, _s in spec.layers]
    config = dict(pub, layers_held=held, num_hidden_layers=len(held),
                  n_routed_experts=spec.experts_held,
                  expert_offset=spec.expert_offset,
                  vocab_size=spec.vocab_held, seq_len=T,
                  mtp_loss_weight=pub["assumed"]["mtp_loss_weight"],
                  published={
                      "n_routed_experts": {"source": pub["n_routed_experts"],
                                           "here": spec.experts_held},
                      "num_hidden_layers": {"source": pub["num_hidden_layers"],
                                            "here": len(held)}})
    return ref.dims_of(config), config


@pytest.fixture(scope="module")
def built():
    cfg = tiny_cfg()
    spec = mm.spec_from_cfg(cfg)
    model = get_model(cfg.data, cfg.model_arch, "f32", cfg=cfg)
    params = init_params(model, (T,), jax.random.PRNGKey(3))
    # norms away from one, so that a dropped norm weight would show
    params = jax.tree_util.tree_map(
        lambda p: p + 0.1 * jax.random.normal(jax.random.PRNGKey(p.size),
                                              p.shape) if p.ndim == 1 else p,
        params)
    dims, config = ref_dims(spec)
    rows = jax.random.randint(jax.random.PRNGKey(5), (3, T + 1), 0, VOCAB)
    return cfg, spec, model, params, dims, config, rows


def _close(a, b, tol=2e-5):
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=tol,
                               atol=tol)


@pytest.mark.parametrize("op", ["mla_attention", "dense_ffn", "shared_expert",
                                "sparse_ffn", "rope", "mtp"])
def test_operator_matches_reference(built, op):
    _cfg, spec, model, params, dims, _c, rows = built
    x = jax.random.normal(jax.random.PRNGKey(11), (2, T, spec.hidden))
    p = params["layer_0" if op == "dense_ffn" else "layer_1"]
    if op == "mla_attention":
        got, want = mm.mla_attention(p, x, spec, jnp.float32), \
            ref.mla_attention(x, p, dims)
    elif op == "dense_ffn":
        got, want = mm.dense_ffn(p, x, jnp.float32), ref.dense_ffn(x, p)
    elif op == "shared_expert":
        got, want = mm.shared_expert(p, x, jnp.float32), \
            ref.shared_expert(x, p)
    elif op == "rope":
        x = x.reshape(2, T, 4, 8)
        got, want = mm._rope_pairs(x, 10000.0), ref.rope_pairs(x, 10000.0)
        # position 0 is left as it is, and a rotation keeps each pair's norm
        _close(got[:, 0], x[:, 0], 1e-6)
        pair = lambda a: np.asarray(a).reshape(2, T, 4, 4, 2)   # noqa: E731
        _close((pair(got) ** 2).sum(-1), (pair(x) ** 2).sum(-1), 1e-5)
    elif op == "mtp":
        # the module on the main model's own hidden states: logits for the
        # token after next on the first T - 1 positions
        tokens = rows[:, :-1]
        _lg, _pr, ahead = model.apply({"params": params}, tokens, train=True)
        hidden, _ = ref.hidden_with_pairs(params, tokens, dims)
        want, _h = ref.mtp_logits(params, hidden, tokens, dims, 1)
        assert len(ahead) == 1 and ahead[0].shape == (3, T, VOCAB)
        got = ahead[0][:, :-1]
    else:
        src = spec.layers[1][0]
        got, pairs = mm.sparse_ffn(p, x, spec, src, jnp.float32)
        want, want_pairs = ref.sparse_ffn(x, p, dims, src)
        np.testing.assert_array_equal(np.asarray(pairs),
                                      np.asarray(want_pairs))
        assert int(pairs.sum()) == 2 * T * spec.top_k
        assert 0 < int(pairs[-1]) < int(pairs.sum())   # some held, some not
    _close(got, want)


def test_forward_and_pairs_match_reference_and_eval_runs_no_mtp(built):
    _cfg, _spec, model, params, dims, _c, rows = built
    out = model.apply({"params": params}, rows[:, :-1])
    assert len(out) == 2          # eval: the main model only
    logits, pairs = out
    want, want_pairs = ref.forward_with_pairs(params, rows[:, :-1], dims)
    assert logits.dtype == jnp.float32 and logits.shape == (3, T, VOCAB)
    _close(logits, want)
    np.testing.assert_array_equal(np.asarray(pairs), np.asarray(want_pairs))
    assert pairs.shape == model.pairs_shape == (2, HELD + 1)
    # and no leaf of the module moves an eval forward
    shaken = dict(params, mtp_0=jax.tree_util.tree_map(
        lambda a: a + 1.0, params["mtp_0"]))
    again, _ = model.apply({"params": shaken}, rows[:, :-1])
    np.testing.assert_array_equal(np.asarray(again), np.asarray(logits))
    # in training the module's block routes too: one more row of pairs
    _lg, train_pairs, _ahead = model.apply({"params": params}, rows[:, :-1],
                                           train=True)
    assert train_pairs.shape == (3, HELD + 1)
    np.testing.assert_array_equal(np.asarray(train_pairs[:2]),
                                  np.asarray(want_pairs))
    assert int(train_pairs[2].sum()) == 3 * T * 4


@pytest.mark.parametrize("remat", [False, True])
def test_loss_with_its_mtp_term_and_gradients_match_reference(built, remat):
    cfg, spec, _m, params, dims, _c, rows = built
    model = get_model(cfg.data, cfg.model_arch, "f32", remat=remat,
                      cfg=cfg)
    loss = task.make_batch_loss(model, cfg, None)
    (got, sums), grads = jax.value_and_grad(
        lambda p: loss(p, rows, None, jnp.ones((3,)), None),
        has_aux=True)(params)
    want, want_grads = ref.loss_and_grads(params, rows, dims)
    main, aux = ref.loss_parts(params, rows, dims)
    _close(got, want, 1e-5)
    _close(got, main + 0.3 * aux, 1e-5)
    _close(sums[task.MTP_LOSS], aux, 1e-5)
    assert float(sums[task.MTP_STEPS]) == 1.0
    assert sums[task.MOE_PAIRS].shape == (3, HELD + 1)
    for g, w in zip(jax.tree_util.tree_leaves(grads),
                    jax.tree_util.tree_leaves(want_grads), strict=True):
        _close(g, w, 5e-5)
    # the router, the experts, the shared expert and every leaf of the MTP
    # module are trained: no stop-gradient crept in
    for name in ("gate", "experts_w2", "shared_w2", "kv_a_norm"):
        assert float(jnp.abs(grads["layer_2"][name]).max()) > 0, name
    for leaf in jax.tree_util.tree_leaves(grads["mtp_0"]):
        assert float(jnp.abs(leaf).max()) > 0
    # and the module's term reaches the main model through h_i, the shared
    # embedding and the shared head: without it their gradients differ
    plain = jax.grad(lambda p: ref.loss_parts(p, rows, dims)[0])(params)
    assert float(jnp.abs(grads["head"] - plain["head"]).max()) > 1e-6
    assert all(float(jnp.abs(x).max()) == 0
               for x in jax.tree_util.tree_leaves(plain["mtp_0"]))


def test_padding_rows_do_not_enter_the_loss(built):
    cfg, _spec, model, params, dims, _c, rows = built
    loss = task.make_batch_loss(model, cfg, None)
    got, sums = loss(params, rows, None, jnp.array([1.0, 1.0, 0.0]), None)
    _close(got, ref.loss(params, rows[:2], dims), 1e-5)
    _close(sums[task.MTP_LOSS], ref.loss_parts(params, rows[:2], dims)[1],
           1e-5)
    _got, sums = loss(params, rows, None, jnp.zeros((3,)), None)
    assert float(sums[task.MTP_STEPS]) == 0.0 == float(sums[task.MTP_LOSS])


def test_client_update_matches_reference_sgd(built):
    """No dropout in this model, so one client's whole local training is
    comparable: two epochs of one batch, momentum from zero, clip at 10;
    the MTP module's leaves are updated like any other."""
    cfg, _spec, model, params, dims, _c, _rows = built
    shard = jax.random.randint(jax.random.PRNGKey(9), (cfg.bs, T + 1), 0,
                               VOCAB)
    local_train = make_local_train(model, cfg, None)
    assert local_train.sequential
    update, per = jax.jit(local_train)(
        params, shard, jnp.zeros((cfg.bs,), jnp.int32), jnp.int32(cfg.bs),
        jax.random.PRNGKey(1))
    want = ref.client_update(params, [shard] * cfg.local_ep, dims,
                             cfg.client_lr, cfg.client_moment)
    for g, w in zip(jax.tree_util.tree_leaves(update),
                    jax.tree_util.tree_leaves(want), strict=True):
        _close(g, w, 5e-5)
    assert float(jnp.abs(update["mtp_0"]["eh_proj"]).max()) > 0
    assert per["loss"] < float(ref.loss(params, shard, dims))
    assert float(per[task.MTP_STEPS]) == cfg.local_ep
    # pairs summed over the two steps, the module's block included
    assert float(per[task.MOE_PAIRS].sum()) == \
        cfg.local_ep * 3 * cfg.bs * T * 4
    rows = task.round_counters(jax.tree_util.tree_map(
        lambda a: a[None], {k: v for k, v in per.items() if k != "loss"}))
    _close(rows["mtp_loss"], per[task.MTP_LOSS] / cfg.local_ep, 1e-6)


def test_four_shares_add_up_to_the_uncut_layer():
    """The guide's share test: each share routes over all 16 experts and
    computes its own four; the routed partial outputs plus the shared
    expert, which every share computes alike, counted ONCE, add up to the
    uncut reference's layer, and the pairs to every pair."""
    whole = mm.spec_from(TINY, "1", 0, 0, 0)
    x = jax.random.normal(jax.random.PRNGKey(2), (2, T, whole.hidden))
    p = init_params(mm.MlaMoE(spec=whole), (T,),
                    jax.random.PRNGKey(4))["layer_0"]
    dims, _ = ref_dims(whole)
    want, want_pairs = ref.sparse_ffn(x, p, dims, 1)
    assert int(want_pairs[-1]) == 0
    shared = mm.shared_expert(p, x, jnp.float32)
    total, held_pairs = shared, []
    for off in (0, 4, 8, 12):
        share = mm.spec_from(TINY, "1", 4, off, 0)
        ps = dict(p, **{k: p[k][off:off + 4]
                        for k in ("experts_w1", "experts_w2", "experts_w3")})
        out, pairs = mm.sparse_ffn(ps, x, share, 1, jnp.float32)
        total = total + (out - shared)      # every share adds it: once
        held_pairs += [int(c) for c in pairs[:-1]]
    _close(total, want)
    assert held_pairs == [int(c) for c in want_pairs[:-1]]
    assert float(jnp.abs(shared).max()) > 0


def test_correction_bias_is_a_buffer_that_changes_selections(built):
    _cfg, spec, _m, params, dims, _c, _r = built
    assert not any("bias" in k for k, _v in
                   jax.tree_util.tree_flatten_with_path(params)[0]
                   for k in [jax.tree_util.keystr(k)])
    b = mm.expert_bias(spec, 3)
    np.testing.assert_array_equal(b, ref.expert_bias(dims, 3))
    assert b.shape == (16,) and np.all(b != 0)
    assert np.abs(b).max() <= token_ops.EXPERT_BIAS_SCALE
    x = jax.random.normal(jax.random.PRNGKey(8), (64, spec.hidden))
    gate = params["layer_2"]["gate"]
    with_b, w = ref.route(x, gate, dims, 3)
    without, _ = ref.route(x, gate, dims, 3, use_bias=False)
    assert np.any(np.asarray(with_b) != np.asarray(without))
    # weights normalised over the selected and scaled by 2.5
    _close(jnp.sum(w, axis=-1), jnp.full((64,), 2.5), 1e-5)


def test_bf16_products_stay_close_to_float32(built):
    cfg, _spec, model, params, _d, _c, rows = built
    half = get_model(cfg.data, cfg.model_arch, "bf16", cfg=cfg)
    a, _, (a2,) = model.apply({"params": params}, rows[:, :-1], train=True)
    b, _, (b2,) = half.apply({"params": params}, rows[:, :-1], train=True)
    assert b.dtype == b2.dtype == jnp.float32
    assert float(jnp.max(jnp.abs(a - b))) < 0.05 * float(jnp.max(jnp.abs(a)))
    assert float(jnp.max(jnp.abs(a2 - b2))) < \
        0.05 * float(jnp.max(jnp.abs(a2)))


def test_published_file_is_the_catalog_row_and_the_cut_counts_491_7m():
    """ISSUE 31's arithmetic at the published widths, from shapes alone,
    and the published file key by key against the catalog's row."""
    with open(mm.PUBLISHED["joyai-llm-flash"]) as f:
        pub = json.load(f)
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "JoyAI-LLM-Flash")
        assert pub["source"] == row["source_url"]
        for key, value in row["config"].items():
            assert pub[key] == value, key
        assert set(pub) == set(row["config"]) | {"name", "source", "assumed"}
    spec = mm.spec_from("joyai-llm-flash", "0,1,2,3,4", 8, 0, 16160)
    assert [s for _i, s in spec.layers] == [False, True, True, True, True]
    assert (spec.hidden, spec.heads, spec.q_rank, spec.kv_rank, spec.nope_dim,
            spec.rope_dim, spec.v_dim, spec.dense_ffn, spec.moe_ffn,
            spec.n_experts, spec.top_k, spec.shared_ffn, spec.routed_scale,
            spec.mtp_depth, spec.mtp_src_layer, spec.mtp_weight) == (
        2048, 32, 1536, 512, 128, 64, 128, 7168, 768, 256, 8, 768, 2.5, 1,
        40, 0.3)
    model = mm.MlaMoE(spec=spec, dtype=jnp.bfloat16)
    shapes = abstract_params(model, (2048,))
    assert param_count(shapes) == 491_696_128
    parts = {k: param_count(v) for k, v in shapes.items()}
    assert parts == {"embed": 33_095_680, "head": 33_095_680,
                     "final_norm": 2048, "layer_0": 70_391_808,
                     "layer_1": 69_343_232, "layer_2": 69_343_232,
                     "layer_3": 69_343_232, "layer_4": 69_343_232,
                     "mtp_0": 77_737_984}
    assert shapes["layer_1"]["gate"].shape == (2048, 256)
    assert shapes["layer_1"]["experts_w1"].shape == (8, 2048, 768)
    assert shapes["mtp_0"]["eh_proj"].shape == (4096, 2048)
    # 8 of 256 held: twice the expected share is 4096 of a step's 65536
    # sorted rows; two rows a token, 16384, is the floor
    assert model.dispatch_rows(8192) == 16384
    assert model.build_counters(8192, 2048) == {
        "experts_held": 8, "vocab_held": 16160, "moe_rows": 16384,
        "moe_rows_worst": 65536, "attn_squares_computed": 36,
        "attn_squares": 64, "attn_path": {"plain": 6}, "mtp_depth": 1,
        "shared_experts": 1, "attn_rope_product": 0,
        # q k^T and p v on the causal half of four sequences, in the five
        # blocks and the MTP module's: 32 heads of 192 + 128
        "attn_core_flops": {"full": 6 * 2 * 32 * 320 * (2048 * 2049 // 2)
                            * 4}}
    whole = mm.spec_from("joyai-llm-flash", "", 0, 0, 0)
    full = param_count(abstract_params(
        mm.MlaMoE(spec=whole, dtype=jnp.bfloat16), (2048,)))
    assert abs(full / 1e9 - 50.19) < 0.01       # 48.95B + the MTP module


@pytest.mark.parametrize("kw,word", [
    (dict(lm_layers="3,2"), "ascending"),
    (dict(lm_layers="9"), "ascending"),
    (dict(lm_experts_held=6, lm_expert_offset=12), "does not lie inside"),
    (dict(lm_vocab_held=500), "is not in"),
    (dict(lm_config="lfm2-8b-a1b"), "neither one of"),
    (dict(lm_config=os.path.join(os.path.dirname(TINY), "lm_tiny.json")),
     "no latent-attention"),
])
def test_a_cut_outside_the_source_is_refused(kw, word):
    with pytest.raises(ValueError, match=word):
        mm.spec_from_cfg(tiny_cfg(**kw))


def test_reference_counts_the_cut_and_reads_low():
    with open(os.path.join(os.path.dirname(__file__), "..", "benchmark",
                           "configs", "joyai-llm-flash-ep32.json")) as f:
        config = json.load(f)
    dims = ref.dims_of(config)
    assert [s for _i, s in dims["layers"]] == [False, True, True, True, True]
    assert (dims["router_experts"], dims["experts_held"],
            dims["mtp_src_layer"]) == (256, 8, 40)
    # one block's latent attention: 26.34M multiply-adds of projections a
    # token and the causal half of 32 heads' 192-wide scores and 128-wide
    # values
    proj = 26_347_520 - 1536 - 512
    scores = (2048 + 1) / 2 * 32 * (192 + 128)
    per_block = ref.mla_attention_flops(1, dims) / 2 / (5 + 2047 / 2048)
    assert abs(per_block - proj - scores) < 1.0
    # a routed expert a quarter of the tokens (8 x 8 / 256), the shared
    # expert and the router every token, both head products
    macs = ref.forward_flops_of(config) / 2
    share = 2047 / 2048
    sparse = (2048 * 256 + 3 * 2048 * 768 + 0.25 * 3 * 2048 * 768)
    want = (16160 * 2048 * (1 + share) + (5 + share) * (proj + scores)
            + 3 * 2048 * 7168 + (4 + share) * sparse + share * 2 * 2048 ** 2)
    assert abs(macs - want) < 1.0
    assert 0.70e9 < 2 * macs < 0.80e9      # about 0.74 GFLOP a token
    assert ref.moe_expert_flops(256, dims) == 3 * 6 * 2048 * 768 * 256
