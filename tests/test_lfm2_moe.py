"""The token task's model (models/lfm2_moe.py) against the plain reference
(benchmark/reference/lfm2_moe.py) at toy widths on the CPU, seeded random
weights: each operator, the sparse layer, the whole forward, loss and
gradients, one client's update against the reference's SGD loop, and the
share test of the model-configs guide: the four shares' partial outputs add
up to the uncut reference's sparse layer. Nothing here is a device metric."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import lfm2_moe as ref
from defending_against_backdoors_with_robust_learning_rate_tpu.config import (
    Config)
from defending_against_backdoors_with_robust_learning_rate_tpu.fl import task
from defending_against_backdoors_with_robust_learning_rate_tpu.fl.client import (
    make_local_train)
from defending_against_backdoors_with_robust_learning_rate_tpu.models import (
    lfm2_moe as lm, token_ops)
from defending_against_backdoors_with_robust_learning_rate_tpu.models.registry import (
    abstract_params, get_model, init_params, param_count)

TINY = os.path.join(os.path.dirname(__file__), "data", "lm_tiny.json")
LAYERS = "1,2,3,4"        # dense conv, sparse attention, two sparse convs
HELD, OFFSET, VOCAB, T = 4, 2, 96, 12


def tiny_cfg(**kw):
    base = dict(data="tokens", arch="lfm2_moe", lm_config=TINY,
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
    held = [src for src, _k, _s in spec.layers]
    config = dict(pub, layers_held=held,
                  layer_types=[pub["layer_types"][i] for i in held],
                  num_hidden_layers=len(held),
                  num_dense_layers=sum(1 for i in held
                                       if i < pub["num_dense_layers"]),
                  num_experts=spec.experts_held,
                  expert_offset=spec.expert_offset,
                  vocab_size=spec.vocab_held, head_dim=8, seq_len=T,
                  published={"num_experts": {"source": pub["num_experts"],
                                             "here": spec.experts_held}})
    return ref.dims_of(config), config


@pytest.fixture(scope="module")
def built():
    cfg = tiny_cfg()
    spec = lm.spec_from_cfg(cfg)
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


@pytest.mark.parametrize("op", ["short_conv", "attention", "dense_ffn",
                                "sparse_ffn"])
def test_operator_matches_reference(built, op):
    _cfg, spec, _m, params, dims, _c, _r = built
    x = jax.random.normal(jax.random.PRNGKey(11), (2, T, spec.hidden))
    layer = {"short_conv": 0, "attention": 1, "dense_ffn": 0,
             "sparse_ffn": 2}[op]
    p = params[f"layer_{layer}"]
    src = spec.layers[layer][0]
    if op == "short_conv":
        got, want = lm.short_conv(p, x, spec, jnp.float32), \
            ref.short_conv(x, p, dims)
    elif op == "attention":
        got, want = lm.attention(p, x, spec, jnp.float32), \
            ref.attention(x, p, dims)
    elif op == "dense_ffn":
        got, want = lm.dense_ffn(p, x, jnp.float32), ref.dense_ffn(x, p)
    else:
        got, pairs = lm.sparse_ffn(p, x, spec, src, jnp.float32)
        want, want_pairs = ref.sparse_ffn(x, p, dims, src)
        np.testing.assert_array_equal(np.asarray(pairs),
                                      np.asarray(want_pairs))
        assert int(pairs.sum()) == 2 * T * spec.top_k
        assert 0 < int(pairs[-1]) < int(pairs.sum())   # some held, some not
    _close(got, want)


def test_forward_loss_and_pairs_match_reference(built):
    _cfg, _spec, model, params, dims, _c, rows = built
    logits, pairs = model.apply({"params": params}, rows[:, :-1])
    want, want_pairs = ref.forward_with_pairs(params, rows[:, :-1], dims)
    assert logits.dtype == jnp.float32 and logits.shape == (3, T, VOCAB)
    _close(logits, want)
    np.testing.assert_array_equal(np.asarray(pairs), np.asarray(want_pairs))
    assert pairs.shape == (3, HELD + 1)


@pytest.mark.parametrize("remat", [False, True])
def test_loss_and_gradients_match_reference(built, remat):
    cfg, spec, _m, params, dims, _c, rows = built
    model = get_model(cfg.data, cfg.model_arch, "f32", remat=remat,
                      cfg=cfg)
    loss = task.make_batch_loss(model, cfg, None)
    (got, sums), grads = jax.value_and_grad(
        lambda p: loss(p, rows, None, jnp.ones((3,)), None),
        has_aux=True)(params)
    want, want_grads = ref.loss_and_grads(params, rows, dims)
    _close(got, want, 1e-5)
    assert sums[task.MOE_PAIRS].shape == (3, HELD + 1)
    for g, w in zip(jax.tree_util.tree_leaves(grads),
                    jax.tree_util.tree_leaves(want_grads), strict=True):
        _close(g, w, 5e-5)
    # the router and the experts are trained: no stop-gradient crept in
    assert float(jnp.abs(grads["layer_2"]["gate"]).max()) > 0
    assert float(jnp.abs(grads["layer_2"]["experts_w2"]).max()) > 0


def test_padding_rows_do_not_enter_the_loss(built):
    cfg, _spec, model, params, dims, _c, rows = built
    loss = task.make_batch_loss(model, cfg, None)
    got, _ = loss(params, rows, None, jnp.array([1.0, 1.0, 0.0]), None)
    _close(got, ref.loss(params, rows[:2], dims), 1e-5)


def test_client_update_matches_reference_sgd(built):
    """No dropout in this model, so one client's whole local training is
    comparable: two epochs of one batch (the shard, reshuffled: a batch
    mean does not depend on the order), momentum from zero, clip at 10."""
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
    losses = [float(ref.loss(params, shard, dims))]
    assert per["loss"] < losses[0]          # the second epoch's is lower
    # pairs summed over the two steps: every (token, slot) pair counted once
    assert float(per[task.MOE_PAIRS].sum()) == \
        cfg.local_ep * 3 * cfg.bs * T * 2


def test_four_shares_add_up_to_the_uncut_layer():
    """The guide's share test: each share routes over all 8 experts and
    computes its own two; the partial outputs add up to the uncut
    reference's layer, and the pairs to every pair."""
    whole = lm.spec_from(TINY, "2", 0, 0, 0)
    x = jax.random.normal(jax.random.PRNGKey(2), (2, T, whole.hidden))
    model = lm.LFM2MoE(spec=whole)
    p = init_params(model, (T,), jax.random.PRNGKey(4))["layer_0"]
    dims, _ = ref_dims(whole)
    want, want_pairs = ref.sparse_ffn(x, p, dims, 2)
    assert int(want_pairs[-1]) == 0
    total, held_pairs = 0.0, []
    for off in (0, 2, 4, 6):
        share = lm.spec_from(TINY, "2", 2, off, 0)
        ps = dict(p, **{k: p[k][off:off + 2]
                        for k in ("experts_w1", "experts_w2", "experts_w3")})
        out, pairs = lm.sparse_ffn(ps, x, share, 2, jnp.float32)
        total = total + out
        held_pairs += [int(c) for c in pairs[:-1]]
    _close(total, want)
    assert held_pairs == [int(c) for c in want_pairs[:-1]]


def test_expert_bias_is_a_buffer_that_changes_selections(built):
    _cfg, spec, _m, params, dims, _c, _r = built
    assert not any("bias" in k for layer in params.values()
                   if isinstance(layer, dict) for k in layer)
    b = lm.expert_bias(spec, 3)
    np.testing.assert_array_equal(b, ref.expert_bias(dims, 3))
    assert np.all(b != 0) and np.abs(b).max() <= token_ops.EXPERT_BIAS_SCALE
    x = jax.random.normal(jax.random.PRNGKey(8), (64, spec.hidden))
    gate = params["layer_2"]["gate"]
    with_b, _ = ref.route(x, gate, dims, 3)
    without, _ = ref.route(x, gate, dict(dims, use_bias=False), 3)
    assert np.any(np.asarray(with_b) != np.asarray(without))


def test_bf16_products_stay_close_to_float32(built):
    cfg, spec, model, params, _d, _c, rows = built
    half = get_model(cfg.data, cfg.model_arch, "bf16", cfg=cfg)
    a, _ = model.apply({"params": params}, rows[:, :-1])
    b, _ = half.apply({"params": params}, rows[:, :-1])
    assert b.dtype == jnp.float32
    assert float(jnp.max(jnp.abs(a - b))) < 0.05 * float(jnp.max(jnp.abs(a)))


def test_published_widths_and_the_cut_count_507_8m_parameters():
    """ISSUE 27's arithmetic at the published widths, from shapes alone."""
    spec = lm.spec_from("lfm2-8b-a1b", "0,2,3,4,5", 8, 0, 16384)
    assert [k for _s, k, _x in spec.layers] == [
        "conv", "full_attention", "conv", "conv", "conv"]
    assert [x for _s, _k, x in spec.layers] == [False, True, True, True, True]
    model = lm.LFM2MoE(spec=spec, dtype=jnp.bfloat16)
    shapes = abstract_params(model, (2048,))
    assert param_count(shapes) == 507_820_160
    layer = {k: param_count(v) for k, v in shapes.items()
             if k.startswith("layer_")}
    assert layer == {"layer_0": 60_827_648, "layer_1": 98_635_904,
                     "layer_2": 104_933_376, "layer_3": 104_933_376,
                     "layer_4": 104_933_376}
    assert shapes["layer_1"]["gate"].shape == (2048, 32)
    assert shapes["layer_1"]["experts_w1"].shape == (8, 2048, 1792)
    whole = lm.spec_from("lfm2-8b-a1b", "", 0, 0, 0)
    full = abstract_params(lm.LFM2MoE(spec=whole, dtype=jnp.bfloat16),
                           (2048,))
    assert abs(param_count(full) / 1e9 - 8.34) < 0.01     # tied embedding


@pytest.mark.parametrize("kw,word", [
    (dict(lm_layers="3,2"), "ascending"),
    (dict(lm_layers="9"), "ascending"),
    (dict(lm_experts_held=6, lm_expert_offset=4), "does not lie inside"),
    (dict(lm_vocab_held=500), "is not in"),
])
def test_a_cut_outside_the_source_is_refused(kw, word):
    with pytest.raises(ValueError, match=word):
        lm.spec_from_cfg(tiny_cfg(**kw))


def test_reference_counts_one_expert_a_token_at_the_cut():
    config = {"hidden_size": 2048, "intermediate_size": 7168,
              "moe_intermediate_size": 1792, "num_attention_heads": 32,
              "num_key_value_heads": 8, "conv_L_cache": 3, "num_experts": 8,
              "num_experts_per_tok": 4, "norm_topk_prob": True,
              "routed_scaling_factor": 1, "use_expert_bias": True,
              "norm_eps": 1e-5, "rope_theta": 1e6, "vocab_size": 16384,
              "num_hidden_layers": 5, "num_dense_layers": 1, "seq_len": 2048,
              "layer_types": ["conv", "full_attention", "conv", "conv",
                              "conv"],
              "layers_held": [0, 2, 3, 4, 5],
              "published": {"num_experts": {"source": 32, "here": 8}}}
    macs = ref.forward_flops_of(config) / 2
    # ISSUE 27 counts 199.5M multiply-adds without the attention scores;
    # causal scores over half of 2048 keys on average add 4.2M
    scores = (2048 + 1) / 2 * 64 * 32 * 2
    assert abs(macs - scores - 199.5e6) < 0.1e6
    assert ref.moe_expert_flops(1024, ref.dims_of(config)) == \
        3 * 6 * 2048 * 1792 * 1024


def worst_case_sparse_ffn(p, x, sp, src_layer, dtype=jnp.float32):
    """`sparse_ffn` as it stood before the sorted buffer was cut: every
    one of the n * top_k pairs sorted into a row of its own, in plain
    `jax.numpy` and differentiated as written."""
    e_held, top_k = sp.experts_held, sp.top_k
    shape = x.shape
    x = x.reshape(-1, shape[-1])
    n = x.shape[0]
    s = jax.nn.sigmoid(jnp.dot(x.astype(jnp.float32), p["gate"],
                               precision=jax.lax.Precision.HIGHEST))
    pick = (s + lm.expert_bias(sp, src_layer)) if sp.use_expert_bias else s
    _, sel = jax.lax.top_k(pick, top_k)
    w = jnp.take_along_axis(s, sel, axis=-1)
    if sp.norm_topk:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-6)
    w = w * sp.routed_scale
    local = sel - sp.expert_offset
    held = (local >= 0) & (local < e_held)
    key = jnp.where(held, local, e_held).reshape(-1)
    order = jnp.argsort(key, stable=True)
    inv = jnp.argsort(order)
    counts = jnp.sum(key[:, None] == jnp.arange(e_held + 1)[None, :],
                     axis=0, dtype=jnp.int32)
    sizes = counts[:e_held]
    valid = (jnp.arange(n * top_k) < jnp.sum(sizes))[:, None]
    xs = jnp.where(valid, x.astype(dtype)[order // top_k], 0)
    h1 = jax.lax.ragged_dot(xs, p["experts_w1"].astype(dtype), sizes)
    h3 = jax.lax.ragged_dot(xs, p["experts_w3"].astype(dtype), sizes)
    ys = jax.lax.ragged_dot(jax.nn.silu(h1) * h3,
                            p["experts_w2"].astype(dtype), sizes)
    y = jnp.where(valid, ys, 0)[inv].reshape(n, top_k, shape[-1])
    wk = jnp.where(held, w, 0.0).astype(dtype)
    return jnp.sum(y * wk[:, :, None], axis=1).reshape(shape), counts


CUT_TOKENS = 64        # 128 pairs; a quarter of the experts held: 64 rows


@pytest.fixture
def small_tiles(monkeypatch):
    """At toy size the rule's 512-row tile covers every pair: whole tiles
    of 8 rows make the cut real. The rule itself is the module's."""
    monkeypatch.setattr(token_ops, "MOE_ROWS_TILE", 8)
    # (and a row a token as the floor: at top-2 two rows are every pair)
    monkeypatch.setattr(token_ops, "MOE_ROWS_PER_TOKEN", 1)


def layer_of(spec, seed):
    """A sparse layer's parameters, the gate scaled up so that the tokens
    and not the fixed bias decide the selection."""
    model = lm.LFM2MoE(spec=spec)
    p = init_params(model, (T,), jax.random.PRNGKey(seed))["layer_0"]
    return dict(p, gate=40.0 * p["gate"])


def value_and_grads(fn, p, x, spec):
    """(output, pairs, gradients of a fixed weighting of the output with
    respect to the layer's parameters and its input)."""
    tilt = jax.random.normal(jax.random.PRNGKey(21), x.shape)

    def scalar(p, x):
        out, pairs = fn(p, x, spec, 2, jnp.float32)
        return jnp.sum(out * tilt), (out, pairs)

    (_, (out, pairs)), grads = jax.value_and_grad(
        scalar, argnums=(0, 1), has_aux=True)(p, x)
    return out, pairs, grads


def only_held_experts(spec, p, x):
    """A gate under which every token picks held experts only, with
    weights that still differ by token: the held experts' columns read the
    input's common offset, the others its negative."""
    x = x + 3.0
    lean = jnp.where(jnp.arange(spec.n_experts) < spec.experts_held, 1.0,
                     -1.0) / spec.hidden
    return dict(p, gate=lean[None, :] + 0.01 * p["gate"]), x


@pytest.mark.parametrize("remat", [False, True])
@pytest.mark.parametrize("held,overflow", [(2, False), (8, False),
                                           (2, True)])
def test_cut_buffer_equals_the_worst_case_formulation(small_tiles, held,
                                                      overflow, remat):
    """A quarter of the experts held: 64 rows for 128 pairs, with the held
    pairs inside them, and with every pair held, so that the second pass
    computes half of them; all experts held: the single pass."""
    spec = lm.spec_from(TINY, "2", held, 0, 0)
    n_pairs = CUT_TOKENS * spec.top_k
    rows = lm.dispatch_rows(spec, CUT_TOKENS)
    assert rows == (n_pairs if held == 8 else 64)
    p = layer_of(spec, 6)
    x = jax.random.normal(jax.random.PRNGKey(12),
                          (2, CUT_TOKENS // 2, spec.hidden))
    if overflow:
        p, x = only_held_experts(spec, p, x)
    fn = lm.sparse_ffn
    if remat:
        fn = jax.checkpoint(fn, static_argnums=(2, 3, 4))
    out, pairs, grads = value_and_grads(fn, p, x, spec)
    want, want_pairs, want_grads = value_and_grads(worst_case_sparse_ffn, p,
                                                   x, spec)
    np.testing.assert_array_equal(np.asarray(pairs), np.asarray(want_pairs))
    assert int(pairs.sum()) == n_pairs
    if overflow:
        assert int(pairs[:-1].sum()) == n_pairs > rows
    else:
        assert 0 < int(pairs[:-1].sum()) <= rows
    _close(out, want, 1e-6)
    for g, w in zip(jax.tree_util.tree_leaves(grads),
                    jax.tree_util.tree_leaves(want_grads), strict=True):
        _close(g, w, 1e-5)
    # every held expert and the router are reached, by both passes
    assert float(jnp.abs(grads[0]["experts_w1"]).max(axis=(1, 2)).min()) > 0
    assert float(jnp.abs(grads[0]["gate"]).max()) > 0


@pytest.mark.parametrize("tokens,held,want", [
    (8192, 8, 16384), (8192, 32, 32768), (8192, 1, 16384), (8192, 16, 32768),
    (24, 4, 96), (1000, 8, 2048)])
def test_dispatch_rows_follow_the_share_of_experts_held(tokens, held, want):
    spec = lm.spec_from("lfm2-8b-a1b", "2", held, 0, 16384)
    assert lm.dispatch_rows(spec, tokens) == want
    assert want <= tokens * spec.top_k and (
        want == tokens * spec.top_k or want % token_ops.MOE_ROWS_TILE == 0)
