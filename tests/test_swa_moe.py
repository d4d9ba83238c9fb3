"""The window/full-attention token model (models/swa_moe.py) against the
plain reference (benchmark/reference/laguna_xs2.py) at toy widths on the
CPU, seeded random weights: each operator (window and full attention with 6
/ 4 query heads over 2 key-value heads, partial rotary embedding, YaRN's
frequencies, the output gate, the sparse layer with its shared expert), the
whole forward, the loss and the gradients with and without recompute, one
client's update against the reference's SGD loop, and the share test of the
model-configs guide. Nothing here is a device metric."""

import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import laguna_xs2 as ref
from defending_against_backdoors_with_robust_learning_rate_tpu.config import (
    Config)
from defending_against_backdoors_with_robust_learning_rate_tpu.fl import task
from defending_against_backdoors_with_robust_learning_rate_tpu.fl.client import (
    make_local_train)
from defending_against_backdoors_with_robust_learning_rate_tpu.models import (
    mla_moe, swa_moe as sm, token_ops)
from defending_against_backdoors_with_robust_learning_rate_tpu.models.registry import (
    abstract_params, get_model, init_params, param_count)

TINY = os.path.join(os.path.dirname(__file__), "data", "swa_tiny.json")
LAYERS = "0,1,2,4"        # dense + full, two window layers, a sparse full one
HELD, OFFSET, VOCAB, T = 4, 2, 96, 24       # three windows of 8 keys
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
PER_LAYER = ("layer_types", "mlp_layer_types",
             "num_attention_heads_per_layer")


def tiny_cfg(**kw):
    base = dict(data="tokens", arch="swa_moe", lm_config=TINY,
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
    held = [src for src, _k, _h, _s in spec.layers]
    config = dict(pub, layers_held=held, num_hidden_layers=len(held),
                  num_experts=spec.experts_held,
                  expert_offset=spec.expert_offset,
                  vocab_size=spec.vocab_held, seq_len=T,
                  published={"num_experts": {"source": pub["num_experts"],
                                             "here": spec.experts_held}},
                  **{k: [pub[k][i] for i in held] for k in PER_LAYER})
    return ref.dims_of(config), config


@pytest.fixture(scope="module")
def built():
    cfg = tiny_cfg()
    spec = sm.spec_from_cfg(cfg)
    model = get_model(cfg.data, cfg.model_arch, "f32", cfg=cfg)
    params = init_params(model, (T,), jax.random.PRNGKey(3))
    # norms away from one, so that a dropped norm weight would show; and
    # weights five times the initialiser's, so that attention moves the
    # output by more than a tolerance
    params = jax.tree_util.tree_map(
        lambda p: p + 0.1 * jax.random.normal(jax.random.PRNGKey(p.size),
                                              p.shape) if p.ndim == 1
        else 5.0 * p, params)
    dims, config = ref_dims(spec)
    rows = jax.random.randint(jax.random.PRNGKey(5), (3, T + 1), 0, VOCAB)
    return cfg, spec, model, params, dims, config, rows


def _close(a, b, tol=2e-5):
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=tol,
                               atol=tol)


def test_the_spec_reads_heads_and_rotary_by_layer_kind(built):
    _cfg, spec, _m, params, _d, _c, _r = built
    assert spec.layers == ((0, sm.FULL, 4, False), (1, sm.WINDOW, 6, True),
                           (2, sm.WINDOW, 6, True), (4, sm.FULL, 4, True))
    assert (spec.kv_heads, spec.head_dim, spec.window) == (2, 16, 8)
    rope = dict(spec.rope)
    assert rope[sm.WINDOW] == sm.Rope(10000.0, 16)
    assert rope[sm.FULL] == sm.Rope(100.0, 8, (4.0, 64, 8.0, 1.0),
                                    0.1 * math.log(4) + 1)
    # q, gate and output products follow the layer's own head count
    assert params["layer_0"]["q_proj"].shape == (32, 4 * 16)
    assert params["layer_1"]["q_proj"].shape == (32, 6 * 16)
    assert params["layer_1"]["g_proj"].shape == (32, 6)
    assert params["layer_1"]["o_proj"].shape == (6 * 16, 32)
    assert params["layer_0"]["k_proj"].shape == \
        params["layer_1"]["k_proj"].shape == (32, 2 * 16)
    assert "w1" in params["layer_0"] and "gate" not in params["layer_0"]
    assert "shared_w1" in params["layer_3"]


@pytest.mark.parametrize("op", ["window_attention", "full_attention",
                                "dense_ffn", "shared_expert", "sparse_ffn"])
def test_operator_matches_reference(built, op):
    _cfg, spec, _model, params, dims, _c, _rows = built
    x = jax.random.normal(jax.random.PRNGKey(11), (2, T, spec.hidden))
    if op == "window_attention":
        p = params["layer_1"]
        got = sm.attention(p, x, spec, sm.WINDOW, jnp.float32)
        want = ref.attention(x, p, dims, sm.WINDOW)
        # and the window is felt: the same layer read causally departs
        causal = ref.attention(x, p, dims, sm.FULL)
        assert float(jnp.abs(want - causal).max()) > 1e-3
    elif op == "full_attention":
        p = params["layer_3"]
        got = sm.attention(p, x, spec, sm.FULL, jnp.float32)
        want = ref.attention(x, p, dims, sm.FULL)
    elif op == "dense_ffn":
        p = params["layer_0"]
        got, want = sm.dense_ffn(p, x, jnp.float32), ref.dense_ffn(x, p)
    elif op == "shared_expert":
        p = params["layer_1"]
        assert sm.shared_expert is token_ops.shared_expert \
            is mla_moe.shared_expert
        got, want = sm.shared_expert(p, x, jnp.float32), \
            ref.shared_expert(x, p)
    else:
        p = params["layer_1"]
        got, pairs = sm.sparse_ffn(p, x, spec, jnp.float32)
        want, want_pairs = ref.sparse_ffn(x, p, dims)
        np.testing.assert_array_equal(np.asarray(pairs),
                                      np.asarray(want_pairs))
        assert int(pairs.sum()) == 2 * T * spec.top_k
        assert 0 < int(pairs[-1]) < int(pairs.sum())   # some held, some not
        # no bias: the scores alone select, weights sum to the 2.5 scale
        sel, w = ref.route(x.reshape(-1, spec.hidden), p["gate"], dims)
        _close(jnp.sum(w, axis=-1), jnp.full((2 * T,), 2.5), 1e-5)
        assert not any("bias" in jax.tree_util.keystr(k) for k, _v in
                       jax.tree_util.tree_flatten_with_path(params)[0])
    _close(got, want)


def test_the_gate_scales_each_heads_output_by_its_own_scalar(built):
    """With `o_proj` the identity on a head's widths, the layer's output is
    sigmoid(z W_g)[head] times the ungated attention of that head."""
    _cfg, spec, _model, params, dims, _c, _rows = built
    p = dict(params["layer_1"])
    x = jax.random.normal(jax.random.PRNGKey(12), (1, T, spec.hidden))
    p["o_proj"] = jnp.eye(6 * 16)
    gated = sm.attention(p, x, spec, sm.WINDOW, jnp.float32)
    open_gate = dict(p, g_proj=jnp.zeros_like(p["g_proj"]))   # sigmoid(0)
    half = sm.attention(open_gate, x, spec, sm.WINDOW, jnp.float32)
    g = jax.nn.sigmoid(x @ p["g_proj"])                       # [1, T, 6]
    want = (2.0 * half).reshape(1, T, 6, 16) * g[..., None]
    _close(gated.reshape(1, T, 6, 16), want, 1e-5)
    assert float(jnp.std(g)) > 1e-3


@pytest.mark.parametrize("kind", [sm.WINDOW, sm.FULL])
def test_rotary_by_kind_rotates_the_first_widths_and_passes_the_rest(built,
                                                                     kind):
    _cfg, spec, _m, _p, dims, _c, _r = built
    r = dict(spec.rope)[kind]
    x = jax.random.normal(jax.random.PRNGKey(13), (2, T, 3, 16))
    got = sm.rope(x, r)
    _close(got, ref.rotary(x, dims["rope"][kind]), 1e-5)
    n = r.rotated
    assert n == {sm.WINDOW: 16, sm.FULL: 8}[kind]
    # position 0 is scaled only; the widths past the rotated ones pass
    _close(got[:, 0, :, :n], r.scale * x[:, 0, :, :n], 1e-6)
    np.testing.assert_array_equal(np.asarray(got[..., n:]),
                                  np.asarray(x[..., n:]))
    # a rotation keeps each pair's norm, up to the scale
    pair = lambda a: jnp.stack([a[..., :n // 2], a[..., n // 2:n]], -1)  # noqa: E731
    _close((pair(got) ** 2).sum(-1), r.scale ** 2 * (pair(x) ** 2).sum(-1),
           1e-4)
    assert float(jnp.abs(got[:, 1:, :, :n] - r.scale * x[:, 1:, :, :n]
                         ).max()) > 0.1


def test_yarn_frequencies_follow_the_formula_on_the_published_parameters():
    """Hand-computed: dim(n) = 64 ln(4096 / (2 pi n)) / (2 ln 500000) reads
    5.66 at n = 64 and 15.80 at n = 1, so the ramp runs from pair 5 to 16:
    below it the plain frequencies, above it those over 64."""
    spec = sm.spec_from("laguna-xs.2", "0,1,2,3,4", 16, 0, 12544)
    rope = dict(spec.rope)
    assert rope[sm.WINDOW] == sm.Rope(10000.0, 128)
    full = rope[sm.FULL]
    assert full == sm.Rope(500000.0, 64, (64.0, 4096, 64.0, 1.0),
                           1.4158883083359672)
    assert full.scale == pytest.approx(0.1 * math.log(64) + 1, rel=1e-12)

    def dim(n):
        return 64 * math.log(4096 / (2 * math.pi * n)) / (
            2 * math.log(500000))

    assert (round(dim(64), 2), round(dim(1), 2)) == (5.66, 15.80)
    assert sm.yarn_range(full) == (5, 16)
    inv = sm.rope_inv_freq(full)
    plain = 500000.0 ** (-np.arange(32) / 32.0)
    assert inv.shape == (32,) and inv.dtype == np.float32
    np.testing.assert_allclose(inv[:6], plain[:6], rtol=1e-6)
    np.testing.assert_allclose(inv[16:], plain[16:] / 64, rtol=1e-6)
    i = np.arange(6, 16)
    ramp = (i - 5) / 11.0
    np.testing.assert_allclose(
        inv[6:16], plain[6:16] / 64 * ramp + plain[6:16] * (1 - ramp),
        rtol=1e-6)
    np.testing.assert_allclose(sm.rope_inv_freq(rope[sm.WINDOW]),
                               10000.0 ** (-np.arange(64) / 64.0), rtol=1e-6)
    # the reference computes its own, from the configuration's keys
    with open(sm.PUBLISHED["laguna-xs.2"]) as f:
        pub = json.load(f)
    np.testing.assert_allclose(
        ref.inv_freq(pub["rope_parameters"][sm.FULL], 128), inv, rtol=1e-6)
    assert ref.yarn_range(pub["rope_parameters"][sm.FULL], 64) == (5, 16)
    # and `transformers`, where it imports (the test does not depend on it)
    try:
        from transformers import PretrainedConfig
        from transformers.modeling_rope_utils import _compute_yarn_parameters
        hf = PretrainedConfig(
            rope_theta=500000, head_dim=128, hidden_size=2048,
            num_attention_heads=48, partial_rotary_factor=0.5,
            max_position_embeddings=262144,
            rope_scaling=dict(pub["rope_parameters"][sm.FULL]))
        theirs, factor = _compute_yarn_parameters(hf, "cpu")
    except Exception:    # noqa: BLE001 - absent, or another signature
        return
    np.testing.assert_allclose(theirs.numpy(), inv, rtol=1e-5)
    assert factor == pytest.approx(full.scale)


def test_forward_and_pairs_match_reference(built):
    _cfg, _spec, model, params, dims, _c, rows = built
    logits, pairs = model.apply({"params": params}, rows[:, :-1])
    want, want_pairs = ref.forward_with_pairs(params, rows[:, :-1], dims)
    assert logits.dtype == jnp.float32 and logits.shape == (3, T, VOCAB)
    _close(logits, want)
    np.testing.assert_array_equal(np.asarray(pairs), np.asarray(want_pairs))
    assert pairs.shape == model.pairs_shape == (3, HELD + 1)
    # a training forward returns the same two values: no auxiliary head
    assert len(model.apply({"params": params}, rows[:, :-1], train=True)) == 2
    # a reference told another window, or the kinds' rotary parameters the
    # other way round, departs from the program
    for told in (dict(dims, window=6), dict(dims, window=T),
                 dict(dims, rope={sm.WINDOW: dims["rope"][sm.FULL],
                                  sm.FULL: dims["rope"][sm.WINDOW]})):
        other, _ = ref.forward_with_pairs(params, rows[:, :-1], told)
        assert float(jnp.abs(other - want).max()) > 1e-3


@pytest.mark.parametrize("remat", [False, True])
def test_loss_and_gradients_match_reference(built, remat):
    cfg, _spec, _m, params, dims, _c, rows = built
    model = get_model(cfg.data, cfg.model_arch, "f32", remat=remat, cfg=cfg)
    loss = task.make_batch_loss(model, cfg, None)
    (got, sums), grads = jax.value_and_grad(
        lambda p: loss(p, rows, None, jnp.ones((3,)), None),
        has_aux=True)(params)
    want, want_grads = ref.loss_and_grads(params, rows, dims)
    _close(got, want, 1e-5)
    assert sums[task.MOE_PAIRS].shape == (3, HELD + 1)
    assert task.MTP_LOSS not in sums
    for (path, g), w in zip(
            jax.tree_util.tree_flatten_with_path(grads)[0],
            jax.tree_util.tree_leaves(want_grads), strict=True):
        np.testing.assert_allclose(
            np.asarray(g), np.asarray(w), rtol=5e-5, atol=5e-5,
            err_msg=jax.tree_util.keystr(path))
    # the router, the experts, the shared expert and the output gate of
    # both layer kinds are trained: no stop-gradient crept in
    for layer, name in (("layer_1", "gate"), ("layer_1", "experts_w2"),
                        ("layer_1", "shared_w2"), ("layer_1", "g_proj"),
                        ("layer_3", "g_proj"), ("layer_0", "q_proj")):
        assert float(jnp.abs(grads[layer][name]).max()) > 0, (layer, name)


def test_padding_rows_do_not_enter_the_loss(built):
    cfg, _spec, model, params, dims, _c, rows = built
    loss = task.make_batch_loss(model, cfg, None)
    got, _sums = loss(params, rows, None, jnp.array([1.0, 1.0, 0.0]), None)
    _close(got, ref.loss(params, rows[:2], dims), 1e-5)


def test_client_update_matches_reference_sgd(built):
    """No dropout in this model, so one client's whole local training is
    comparable: two epochs of one batch, momentum from zero, clip at 10."""
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
    assert per["loss"] < float(ref.loss(params, shard, dims))
    # pairs summed over the two steps and the three sparse layers
    assert float(per[task.MOE_PAIRS].sum()) == \
        cfg.local_ep * 3 * cfg.bs * T * 4


def test_four_shares_add_up_to_the_uncut_layer():
    """The guide's share test: each share routes over all 16 experts and
    computes its own four; the routed partial outputs plus the shared
    expert, which every share computes alike, counted ONCE, add up to the
    uncut reference's layer, and the pairs to every pair."""
    whole = sm.spec_from(TINY, "1", 0, 0, 0)
    x = jax.random.normal(jax.random.PRNGKey(2), (2, T, whole.hidden))
    p = jax.tree_util.tree_map(lambda a: 5.0 * a, init_params(
        sm.SwaMoE(spec=whole), (T,), jax.random.PRNGKey(4))["layer_0"])
    dims, _ = ref_dims(whole)
    want, want_pairs = ref.sparse_ffn(x, p, dims)
    assert int(want_pairs[-1]) == 0
    shared = sm.shared_expert(p, x, jnp.float32)
    total, held_pairs = shared, []
    for off in (0, 4, 8, 12):
        share = sm.spec_from(TINY, "1", 4, off, 0)
        ps = dict(p, **{k: p[k][off:off + 4]
                        for k in ("experts_w1", "experts_w2", "experts_w3")})
        out, pairs = sm.sparse_ffn(ps, x, share, jnp.float32)
        total = total + (out - shared)      # every share adds it: once
        held_pairs += [int(c) for c in pairs[:-1]]
    _close(total, want)
    assert held_pairs == [int(c) for c in want_pairs[:-1]]
    assert float(jnp.abs(shared).max()) > 0


def test_bf16_products_stay_close_to_float32(built):
    cfg, _spec, model, params, _d, _c, rows = built
    half = get_model(cfg.data, cfg.model_arch, "bf16", cfg=cfg)
    a, _ = model.apply({"params": params}, rows[:, :-1])
    b, _ = half.apply({"params": params}, rows[:, :-1])
    assert b.dtype == jnp.float32
    assert float(jnp.max(jnp.abs(a - b))) < 0.05 * float(jnp.max(jnp.abs(a)))


def test_published_file_is_the_catalog_row_and_the_cut_counts_490_3m():
    """ISSUE 33's arithmetic at the published widths, from shapes alone,
    and the published file key by key against the catalog's row."""
    with open(sm.PUBLISHED["laguna-xs.2"]) as f:
        pub = json.load(f)
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "Laguna-XS.2")
        assert pub["source"] == row["source_url"]
        for key, value in row["config"].items():
            assert pub[key] == value, key
        assert set(pub) == set(row["config"]) | {"name", "source", "assumed"}
    spec = sm.spec_from("laguna-xs.2", "0,1,2,3,4", 16, 0, 12544)
    assert [(k, h, s) for _i, k, h, s in spec.layers] == [
        (sm.FULL, 48, False), (sm.WINDOW, 64, True), (sm.WINDOW, 64, True),
        (sm.WINDOW, 64, True), (sm.FULL, 48, True)]
    assert (spec.hidden, spec.kv_heads, spec.head_dim, spec.window,
            spec.dense_ffn, spec.moe_ffn, spec.shared_ffn, spec.n_experts,
            spec.top_k, spec.norm_topk, spec.routed_scale, spec.topk_eps,
            spec.norm_eps) == (2048, 8, 128, 512, 8192, 512, 512, 256, 8,
                               True, 2.5, 1e-20, 1e-6)
    model = sm.SwaMoE(spec=spec, dtype=jnp.bfloat16)
    shapes = abstract_params(model, (4096,))
    assert param_count(shapes) == 490_297_344
    parts = {k: param_count(v) for k, v in shapes.items()}
    assert parts == {"embed": 25_690_112, "head": 25_690_112,
                     "final_norm": 2048, "layer_0": 79_794_176,
                     "layer_1": 91_885_568, "layer_2": 91_885_568,
                     "layer_3": 91_885_568, "layer_4": 83_464_192}
    by_name = {k: param_count(v) for k, v in shapes["layer_4"].items()}
    assert by_name == {
        "q_proj": 12_582_912, "k_proj": 2_097_152, "v_proj": 2_097_152,
        "o_proj": 12_582_912, "g_proj": 98_304, "gate": 524_288,
        "experts_w1": 16_777_216, "experts_w2": 16_777_216,
        "experts_w3": 16_777_216, "shared_w1": 1_048_576,
        "shared_w2": 1_048_576, "shared_w3": 1_048_576, "attn_norm": 2048,
        "ffn_norm": 2048}
    assert shapes["layer_1"]["q_proj"].shape == (2048, 8192)
    assert shapes["layer_1"]["g_proj"].shape == (2048, 64)
    assert shapes["layer_1"]["experts_w1"].shape == (16, 2048, 512)
    with open(os.path.join(os.path.dirname(__file__), "..", "benchmark",
                           "configs", "laguna-xs2-ep16.json")) as f:
        config = json.load(f)
    assert config["parameters"] == 490_297_344
    assert config["parameters_by_part"]["layers"] == [
        parts[f"layer_{i}"] for i in range(5)]
    assert config["parameters_by_part"]["window_attention"] == sum(
        param_count(shapes["layer_1"][k])
        for k in ("q_proj", "k_proj", "v_proj", "o_proj", "g_proj"))
    # 16 of 256 held: twice the expected share is 8192 of a step's 65536
    # sorted rows; two rows a token, 16384, is the floor
    assert model.dispatch_rows(8192) == 16384
    assert model.build_counters(8192, 4096) == {
        "experts_held": 16, "vocab_held": 12544, "moe_rows": 16384,
        "moe_rows_worst": 65536, "attn_squares_computed": 136,
        "attn_squares": 256, "attn_window": 512,
        "attn_window_squares_computed": 45, "attn_window_squares": 256,
        "attn_path": {"plain": 5}, "attn_window_layers": 3,
        "attn_full_layers": 2, "shared_experts": 1, "attn_rope_product": 5,
        # q k^T and p v of a step's two sequences: two full layers of 48
        # heads on the causal half, three of 64 on the band of 512
        "attn_core_flops": {
            "full": 2 * 2 * 48 * 256 * (4096 * 4097 // 2) * 2,
            "window": 3 * 2 * 64 * 256 * (512 * 513 // 2 + 3584 * 512) * 2}}
    whole = sm.spec_from("laguna-xs.2", "", 0, 0, 0)
    full = param_count(abstract_params(
        sm.SwaMoE(spec=whole, dtype=jnp.bfloat16), (4096,)))
    assert abs(full / 1e9 - 33.4) < 0.1          # 33.4B-A3B
    # 32 experts held (8 chips a layer) would be over the chip
    more = sm.spec_from("laguna-xs.2", "0,1,2,3,4", 32, 0, 12544)
    assert param_count(abstract_params(
        sm.SwaMoE(spec=more, dtype=jnp.bfloat16), (4096,))) == 691_623_936


@pytest.mark.parametrize("kw,word", [
    (dict(lm_layers="3,2"), "ascending"),
    (dict(lm_layers="8"), "ascending"),
    (dict(lm_experts_held=6, lm_expert_offset=12), "does not lie inside"),
    (dict(lm_vocab_held=500), "is not in"),
    (dict(lm_config="lfm2-8b-a1b"), "neither one of"),
    (dict(lm_config="joyai-llm-flash"), "neither one of"),
    (dict(lm_config=os.path.join(os.path.dirname(TINY), "mla_tiny.json")),
     "no window/full-attention"),
])
def test_a_cut_outside_the_source_is_refused(kw, word):
    with pytest.raises(ValueError, match=word):
        sm.spec_from_cfg(tiny_cfg(**kw))


def test_a_file_that_asks_for_what_the_module_lacks_is_refused(tmp_path):
    with open(TINY) as f:
        pub = json.load(f)
    for change, word in (
            ({"gating": False}, "ungated"),
            ({"tie_word_embeddings": True}, "tied head"),
            ({"num_attention_heads_per_layer": [4, 5, 6, 6, 4, 6, 6, 6]},
             "no multiple"),
            ({"rope_parameters": dict(pub["rope_parameters"], full_attention={
                "rope_type": "llama3", "rope_theta": 1e4})}, "rope_type")):
        path = tmp_path / f"{word.split()[0]}.json"
        path.write_text(json.dumps(dict(pub, **change)))
        with pytest.raises(ValueError, match=word):
            sm.spec_from(str(path), "", 0, 0, 0)
