"""The Gated DeltaNet token model (models/gdn_moe.py) against the plain
reference (benchmark/reference/qwen3_next.py) at toy widths on the CPU,
seeded random weights: the chunked delta rule against the token-by-token
recurrence (lengths the chunk divides and does not, across packed
documents, forward and gradient), the triangular inverse both ways, the
zero-centred norm and partial rotary of a head of 256, the softmax selection
and the gated shared expert, the whole forward, loss and gradients, one
client's update, the shares of the experts adding up to the uncut layer,
the counters against the reference's counts, and the cut's parameter count
at the published widths. Nothing here is a device metric."""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import qwen3_next as ref
from defending_against_backdoors_with_robust_learning_rate_tpu.config import (
    Config)
from defending_against_backdoors_with_robust_learning_rate_tpu.fl import task
from defending_against_backdoors_with_robust_learning_rate_tpu.fl.client import (
    make_local_train)
from defending_against_backdoors_with_robust_learning_rate_tpu.models import (
    attention_kernel, gdn_moe as gm, token_ops)
from defending_against_backdoors_with_robust_learning_rate_tpu.models.registry import (
    abstract_params, get_model, init_params, param_count)

TINY = os.path.join(os.path.dirname(__file__), "data", "gdn_tiny.json")
LAYERS = "0,1,3,4"        # two linear layers, a full one, a linear one
HELD, OFFSET, VOCAB, T = 4, 2, 96, 70       # two chunks of 64, the second cut


def tiny_cfg(**kw):
    base = dict(data="tokens", arch="gdn_moe", lm_config=TINY,
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
    held = [src for src, _k in spec.layers]
    config = dict(pub, layers_held=held, num_hidden_layers=len(held),
                  layer_types=[k for _s, k in spec.layers],
                  num_experts=spec.experts_held,
                  expert_offset=spec.expert_offset,
                  vocab_size=spec.vocab_held, seq_len=T,
                  published={"num_experts": {"source": pub["num_experts"],
                                             "here": spec.experts_held}})
    return ref.dims_of(config), config


def _perturbed(params):
    """Norms away from their initial values, so that a dropped norm weight
    would show; slow decays (exp(A_log) in [0.02, 0.3]), so that the state
    carries across a chunk; matrices five times the initialiser's."""
    def one(path, p):
        name = path[-1].key
        if name == "A_log":
            return jnp.log(jnp.linspace(0.02, 0.3, p.size)).reshape(p.shape)
        if p.ndim == 1:
            return p + 0.1 * jax.random.normal(jax.random.PRNGKey(p.size),
                                               p.shape)
        return 5.0 * p
    return jax.tree_util.tree_map_with_path(one, params)


@pytest.fixture(scope="module")
def built():
    cfg = tiny_cfg()
    spec = gm.spec_from_cfg(cfg)
    model = get_model(cfg.data, cfg.model_arch, "f32", cfg=cfg)
    params = _perturbed(init_params(model, (T,), jax.random.PRNGKey(3)))
    dims, config = ref_dims(spec)
    # packed documents: separators (id 0) inside every row
    rows = jax.random.randint(jax.random.PRNGKey(5), (2, T + 1), 1, VOCAB)
    rows = rows.at[:, 17].set(0).at[:, 44].set(0)
    return cfg, spec, model, params, dims, config, rows


def _close(a, b, tol=2e-5):
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=tol,
                               atol=tol)


def _rule_inputs(t, key=0, b=2, hk=2, hv=4, d=8):
    ks = jax.random.split(jax.random.PRNGKey(key), 5)
    q = ref.l2norm(jax.random.normal(ks[0], (b, t, hk, d)), 1e-6) * d ** -0.5
    k = ref.l2norm(jax.random.normal(ks[1], (b, t, hk, d)), 1e-6)
    v = jax.random.normal(ks[2], (b, t, hv, d))
    g = -jax.random.uniform(ks[3], (b, t, hv)) * 0.3
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (b, t, hv)))
    return q, k, v, g, beta


def _token_rule(q, k, v, g, beta):
    rep = v.shape[2] // q.shape[2]
    return ref.delta_rule(jnp.repeat(q, rep, 2), jnp.repeat(k, rep, 2), v,
                          g, beta)


@pytest.mark.parametrize("t,chunk", [(16, 4), (19, 4), (19, 8), (70, 64),
                                     (128, 64)])
def test_chunked_rule_matches_the_token_recurrence(t, chunk):
    """Forward and the gradient of every input, at lengths the chunk
    divides and does not (the last chunk padded with beta = 0, g = 0)."""
    args = _rule_inputs(t)

    def chunked(*a):
        return gm.chunked_delta_rule(*a, jnp.float32, chunk=chunk)

    _close(chunked(*args), _token_rule(*args), 2e-6)
    w = jax.random.normal(jax.random.PRNGKey(7), args[2].shape)
    got = jax.grad(lambda *a: jnp.sum(chunked(*a) * w),
                   argnums=range(5))(*args)
    want = jax.grad(lambda *a: jnp.sum(_token_rule(*a) * w),
                    argnums=range(5))(*args)
    for name, a, b in zip("qkvgb", got, want, strict=True):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5,
                                   atol=1e-5, err_msg=name)


def test_chunked_rule_takes_decays_too_steep_to_exponentiate_whole():
    """Decays of -30 a token sum to -1900 over a chunk of 64: exp of the
    unmasked difference would overflow; masked before, nothing does."""
    q, k, v, g, beta = _rule_inputs(128)
    g = g * 100.0
    got = gm.chunked_delta_rule(q, k, v, g, beta, jnp.float32)
    assert bool(jnp.all(jnp.isfinite(got)))
    _close(got, _token_rule(q, k, v, g, beta), 1e-5)
    grads = jax.grad(lambda g: jnp.sum(gm.chunked_delta_rule(
        q, k, v, g, beta, jnp.float32)))(g)
    assert bool(jnp.all(jnp.isfinite(grads)))


@pytest.mark.parametrize("c", [5, 64])
def test_the_triangular_inverse_both_ways(monkeypatch, c):
    """By halves (every program built for the CPU) and by the solve (a
    program built for the TPU) against SciPy's solve of the same system."""
    # the delta rule's own: beta_t (k_t . k_j) exp(G_t - G_j) below the
    # diagonal, keys of unit length
    q, k, _v, g, beta = _rule_inputs(c, hv=2)
    k = jnp.moveaxis(k, 1, 2)                           # [B, H, C, d]
    G = jnp.cumsum(jnp.moveaxis(g, 1, 2), axis=-1)
    a = jnp.tril(jnp.moveaxis(beta, 1, 2)[..., None]
                 * (k @ jnp.swapaxes(k, -1, -2))
                 * jnp.exp(G[..., :, None] - G[..., None, :]), -1)
    eye = jnp.broadcast_to(jnp.eye(c), a.shape)
    want = jax.scipy.linalg.solve_triangular(a + jnp.eye(c), eye, lower=True,
                                             unit_diagonal=True)
    _close(gm.inverse_by_halves(a), want, 1e-5)
    _close(gm.unit_lower_inverse(a), want, 1e-5)
    monkeypatch.setattr(attention_kernel, "on_tpu", lambda: True)
    _close(gm.unit_lower_inverse(a), want, 1e-5)


def test_the_spec_reads_the_layer_kinds_and_both_mixers(built):
    _cfg, spec, _m, params, _d, _c, _r = built
    assert spec.layers == ((0, gm.LINEAR), (1, gm.LINEAR), (3, gm.FULL),
                           (4, gm.LINEAR))
    assert (spec.k_heads, spec.v_heads, spec.k_dim, spec.v_dim,
            spec.conv_taps) == (2, 4, 8, 8, 4)
    assert (spec.heads, spec.kv_heads, spec.head_dim, spec.rotated) == (
        4, 2, 16, 4)
    assert (spec.scoring, spec.top_k, spec.norm_topk, spec.routed_scale,
            spec.topk_eps) == ("softmax", 4, True, 1.0, 0.0)
    p0, p2 = params["layer_0"], params["layer_2"]
    assert p0["in_proj_qkvz"].shape == (32, 2 * 16 + 2 * 32)
    assert p0["in_proj_ba"].shape == (32, 8)
    assert p0["conv_weight"].shape == (4, 2 * 16 + 32)
    assert p0["out_proj"].shape == (32, 32)
    assert p2["q_proj"].shape == (32, 2 * 4 * 16)
    assert p2["k_proj"].shape == (32, 2 * 16)
    assert "in_proj_qkvz" not in p2 and "q_proj" not in p0
    for p in (p0, p2):
        assert p["shared_gate"].shape == (32, 1)
        assert p["gate"].shape == (32, 16)


def test_zero_centred_norm_and_partial_rotary_of_a_head_of_256():
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 12, 3, 256))
    w = 0.1 * jax.random.normal(jax.random.PRNGKey(1), (256,))
    _close(gm.zero_centred_norm(x, w, 1e-6), ref.norm(x, w, 1e-6), 1e-6)
    # a weight of zero is the plain norm: (1 + 0) x / rms(x)
    _close(gm.zero_centred_norm(x, jnp.zeros(256), 1e-6),
           x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + 1e-6), 1e-6)
    spec = gm.spec_from("qwen3-next-80b-a3b", "3", 16, 0, 18992)
    assert (spec.head_dim, spec.rotated, spec.rope_theta) == (256, 64, 1e7)
    got = token_ops.rope_half(x, gm.rope_inv_freq(spec))
    want = ref.rotary(x, {"rotated": 64, "rope_theta": 1e7})
    _close(got, want, 2e-5)
    # the 192 widths past the rotated 64 pass through; position 0 is still
    np.testing.assert_array_equal(np.asarray(got[..., 64:]),
                                  np.asarray(x[..., 64:]))
    _close(got[:, 0], x[:, 0], 1e-6)
    assert float(jnp.abs(got[:, 5, :, :64] - x[:, 5, :, :64]).max()) > 1e-3


@pytest.mark.parametrize("op", ["linear_attention", "full_attention",
                                "sparse_ffn", "shared_expert"])
def test_operator_matches_reference(built, op):
    _cfg, spec, _model, params, dims, _c, _rows = built
    x = jax.random.normal(jax.random.PRNGKey(11), (2, T, spec.hidden))
    if op == "linear_attention":
        p = params["layer_0"]
        got = gm.gdn(p, x, spec, jnp.float32)
        want = ref.linear_attention(x, p, dims)
    elif op == "full_attention":
        p = params["layer_2"]
        got = gm.gated_attention(p, x, spec, jnp.float32)
        want = ref.full_attention(x, p, dims)
        # and the gate is felt: without it the layer departs
        ungated = dict(p, q_proj=p["q_proj"].reshape(32, 4, 2, 16).at[
            :, :, 1].set(0.0).reshape(32, -1))
        assert float(jnp.abs(gm.gated_attention(ungated, x, spec,
                                                jnp.float32) - got).max()) \
            > 1e-3
    elif op == "sparse_ffn":
        p = params["layer_1"]
        got, pairs = gm.sparse_ffn(p, x, spec, jnp.float32)
        want, want_pairs = ref.sparse_ffn(x, p, dims)
        np.testing.assert_array_equal(np.asarray(pairs),
                                      np.asarray(want_pairs))
        # the selection is the softmax's, not the sigmoid's: the weights
        # of a token's four sum to one after the renormalisation either
        # way, but the routed output differs from sigmoid scores
        sig = dataclasses.replace(spec, scoring="sigmoid")
        other, _ = token_ops.sparse_ffn(p, x, sig, None, jnp.float32)
        mine, _ = token_ops.sparse_ffn(p, x, spec, None, jnp.float32)
        assert float(jnp.abs(other - mine).max()) > 1e-4
    else:
        p = params["layer_1"]
        got = token_ops.shared_expert(p, x, jnp.float32, gated=True)
        want = ref.shared_expert(x, p)
        plain = token_ops.shared_expert(p, x, jnp.float32)
        gate = jax.nn.sigmoid(x @ p["shared_gate"])
        _close(got, plain * gate)
    _close(got, want)


def test_forward_and_pairs_match_reference(built):
    _cfg, _spec, model, params, dims, _c, rows = built
    logits, pairs = model.apply({"params": params}, rows[:, :-1])
    want, want_pairs = ref.forward_with_pairs(params, rows[:, :-1], dims)
    assert logits.dtype == jnp.float32 and logits.shape == (2, T, VOCAB)
    _close(logits, want)
    np.testing.assert_array_equal(np.asarray(pairs), np.asarray(want_pairs))
    assert pairs.shape == model.pairs_shape == (4, HELD + 1)
    assert len(model.apply({"params": params}, rows[:, :-1], train=True)) == 2
    # causal: the first chunk's logits are the reference's on the first
    # 64 tokens alone
    cut, _ = ref.forward_with_pairs(params, rows[:, :64], dims)
    _close(logits[:, :64], cut)


@pytest.mark.parametrize("remat", [False, True])
def test_loss_and_gradients_match_reference(built, remat):
    cfg, _spec, _m, params, dims, _c, rows = built
    model = get_model(cfg.data, cfg.model_arch, "f32", remat=remat, cfg=cfg)
    loss = task.make_batch_loss(model, cfg, None)
    (got, sums), grads = jax.value_and_grad(
        lambda p: loss(p, rows, None, jnp.ones((2,)), None),
        has_aux=True)(params)
    want, want_grads = ref.loss_and_grads(params, rows, dims)
    _close(got, want, 1e-5)
    assert sums[task.MOE_PAIRS].shape == (4, HELD + 1)
    for (path, g), w in zip(
            jax.tree_util.tree_flatten_with_path(grads)[0],
            jax.tree_util.tree_leaves(want_grads), strict=True):
        np.testing.assert_allclose(
            np.asarray(g), np.asarray(w), rtol=5e-5, atol=5e-5,
            err_msg=jax.tree_util.keystr(path))
    # the decays, the gates, the convolution, the router and the shared
    # expert's gate are trained: no stop-gradient crept in
    for layer, name in (("layer_0", "A_log"), ("layer_0", "dt_bias"),
                        ("layer_0", "conv_weight"), ("layer_0", "gdn_norm"),
                        ("layer_0", "gate"), ("layer_3", "shared_gate"),
                        ("layer_2", "q_norm"), ("layer_2", "q_proj")):
        assert float(jnp.abs(grads[layer][name]).max()) > 0, (layer, name)


def test_client_update_matches_reference_sgd(built):
    """No dropout in this model, so one client's whole local training is
    comparable: two epochs of one batch, momentum from zero, clip at 10."""
    cfg, _spec, model, params, dims, _c, rows = built
    local_train = make_local_train(model, cfg, None)
    update, per = jax.jit(local_train)(
        params, rows, jnp.zeros((cfg.bs,), jnp.int32), jnp.int32(cfg.bs),
        jax.random.PRNGKey(1))
    want = ref.client_update(params, [rows] * cfg.local_ep, dims,
                             cfg.client_lr, cfg.client_moment)
    for g, w in zip(jax.tree_util.tree_leaves(update),
                    jax.tree_util.tree_leaves(want), strict=True):
        _close(g, w, 5e-5)
    assert float(per[task.MOE_PAIRS].sum()) == \
        cfg.local_ep * 4 * cfg.bs * T * 4


def test_four_shares_add_up_to_the_uncut_layer():
    """Each share routes over all 16 experts and
    computes its own four; the routed partial outputs plus the gated shared
    expert, which every share computes alike, counted ONCE, add up to the
    uncut reference's layer, and the pairs to every pair."""
    whole = gm.spec_from(TINY, "0", 0, 0, 0)
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 24, whole.hidden))
    p = jax.tree_util.tree_map(lambda a: 5.0 * a, init_params(
        gm.GdnMoE(spec=whole), (24,), jax.random.PRNGKey(4))["layer_0"])
    dims, _ = ref_dims(whole)
    want, want_pairs = ref.sparse_ffn(x, p, dims)
    assert int(want_pairs[-1]) == 0
    shared = token_ops.shared_expert(p, x, jnp.float32, gated=True)
    total, held_pairs = shared, []
    for off in (0, 4, 8, 12):
        share = gm.spec_from(TINY, "0", 4, off, 0)
        ps = dict(p, **{k: p[k][off:off + 4]
                        for k in ("experts_w1", "experts_w2", "experts_w3")})
        out, pairs = gm.sparse_ffn(ps, x, share, jnp.float32)
        total = total + (out - shared)      # every share adds it: once
        held_pairs += [int(c) for c in pairs[:-1]]
    _close(total, want)
    assert held_pairs == [int(c) for c in want_pairs[:-1]]
    assert float(jnp.abs(shared).max()) > 0


def test_bf16_products_stay_close_to_float32(built):
    """At the initialiser's weights (the perturbed ones move a token's
    routing under rounding, and one expert more or less moves the output
    by more than rounding does)."""
    cfg, _spec, model, _p, _d, _c, rows = built
    params = init_params(model, (T,), jax.random.PRNGKey(3))
    half = get_model(cfg.data, cfg.model_arch, "bf16", cfg=cfg)
    a, _ = model.apply({"params": params}, rows[:, :-1])
    b, _ = half.apply({"params": params}, rows[:, :-1])
    assert b.dtype == jnp.float32
    assert float(jnp.max(jnp.abs(a - b))) < 0.05 * float(jnp.max(jnp.abs(a)))


def test_counters_count_what_the_reference_counts(built):
    _cfg, spec, model, _p, _d, config, _r = built
    got = model.build_counters(2 * T, T)
    assert got["gdn_core_flops"] == pytest.approx(
        ref.gdn_core_flops(2 * T, config), rel=1e-12)
    assert (got["gdn_layers"], got["gdn_chunk"], got["gdn_chunks"],
            got["attn_full_layers"], got["attn_rope_product"]) == (
        3, 64, 2, 1, 1)
    assert got["gdn_state_bytes"] == 2 * 4 * 8 * 8 * 4
    assert got["attn_core_flops"] == {"full": token_ops.core_flops(
        2 * T, T, 4, 16, 16)}
    assert got["moe_rows_worst"] == 2 * T * 4


def test_the_core_path_is_counted_at_build_and_named(built, monkeypatch,
                                                     tmp_path, capsys):
    """`gdn_path`: the linear layers by the lowering their core takes,
    `gdn_kernel.plan`'s answer. On the CPU the XLA rule; traced
    for the TPU, the kernel at the cell's heads of 128 and still the XLA
    rule at the toy's heads of 8. The `[model] gdn` line ends with the
    layers the kernel takes."""
    cfg, _spec, model, _p, _d, _c, _r = built
    cell = gm.GdnMoE(spec=gm.spec_from("qwen3-next-80b-a3b", "0,1,2,3", 16,
                                       0, 18992), dtype=jnp.bfloat16)
    assert model.build_counters(2 * T, T)["gdn_path"] == {"xla": 3}
    assert cell.build_counters(8192, 4096)["gdn_path"] == {"xla": 3}
    monkeypatch.setattr(attention_kernel, "on_tpu", lambda: True)
    assert cell.build_counters(8192, 4096)["gdn_path"] == {"kernel": 3}
    assert model.build_counters(2 * T, T)["gdn_path"] == {"xla": 3}
    monkeypatch.undo()

    from defending_against_backdoors_with_robust_learning_rate_tpu import (
        train)

    class Built(Exception):
        """Raised where the engine has printed its model lines."""

    def stop(*_a, **_k):
        raise Built

    monkeypatch.setattr(train, "make_normalizer", stop)
    with pytest.raises(Built):
        train.RoundEngine(cfg.replace(log_dir=str(tmp_path)))
    line, = [ln for ln in capsys.readouterr().out.splitlines()
             if ln.startswith("[model] gdn ")]
    assert line.startswith("[model] gdn 3 linear-attention layer(s)")
    assert line.endswith("; core by kernel in 0 layer(s)")


def test_the_cut_of_the_published_widths_counts_424_3m():
    """The cut's arithmetic at the published widths, from shapes alone."""
    with open(gm.PUBLISHED["qwen3-next-80b-a3b"]) as f:
        pub = json.load(f)
    spec = gm.spec_from("qwen3-next-80b-a3b", "0,1,2,3", 16, 0, 18992)
    assert spec.layers == ((0, gm.LINEAR), (1, gm.LINEAR), (2, gm.LINEAR),
                           (3, gm.FULL))
    assert len(gm.layer_kinds(pub)) == 48
    assert gm.layer_kinds(pub).count(gm.FULL) == 12
    model = gm.GdnMoE(spec=spec, dtype=jnp.bfloat16)
    shapes = abstract_params(model, (4096,))
    assert param_count(shapes) == 424_340_544
    parts = {k: param_count(v) for k, v in shapes.items()}
    assert parts == {"embed": 38_895_616, "head": 38_895_616,
                     "final_norm": 2048, "layer_0": 88_250_560,
                     "layer_1": 88_250_560, "layer_2": 88_250_560,
                     "layer_3": 81_795_584}
    # 10 of 512 over 16 held: 160 rows a step's 8192 tokens draw in
    # expectation, 2 x that is 2560; two rows a token, 16384, is the floor
    assert model.dispatch_rows(8192) == 16384
    # 32 experts held (16 chips a layer) would be over the chip
    more = gm.spec_from("qwen3-next-80b-a3b", "0,1,2,3", 32, 0, 18992)
    assert param_count(abstract_params(
        gm.GdnMoE(spec=more, dtype=jnp.bfloat16), (4096,))) == 625_667_136


@pytest.mark.parametrize("kw,word", [
    (dict(lm_layers="3,2"), "ascending"),
    (dict(lm_layers="8"), "ascending"),
    (dict(lm_experts_held=6, lm_expert_offset=12), "does not lie inside"),
    (dict(lm_vocab_held=500), "is not in"),
    (dict(lm_config="laguna-xs.2"), "neither one of"),
    (dict(lm_config=os.path.join(os.path.dirname(TINY), "swa_tiny.json")),
     "no Gated DeltaNet"),
])
def test_a_cut_outside_the_source_is_refused(kw, word):
    with pytest.raises(ValueError, match=word):
        gm.spec_from_cfg(tiny_cfg(**kw))


@pytest.mark.parametrize("change,word", [
    ({"tie_word_embeddings": True}, "tied head"),
    ({"mlp_only_layers": [0]}, "dense layers"),
    ({"rope_scaling": {"rope_type": "yarn", "factor": 4}}, "scaled rotary"),
    ({"linear_num_value_heads": 3}, "no multiple"),
])
def test_a_file_that_asks_for_what_the_module_lacks_is_refused(
        tmp_path, change, word):
    with open(TINY) as f:
        pub = json.load(f)
    path = tmp_path / "asks.json"
    path.write_text(json.dumps(dict(pub, **change)))
    with pytest.raises(ValueError, match=word):
        gm.spec_from(str(path), "0", 0, 0, 0)
