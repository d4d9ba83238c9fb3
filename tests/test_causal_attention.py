"""`token_ops.causal_attention` over more than one query block, which no
model test reaches (every toy sequence is shorter than `ATTN_QUERY_BLOCK`):
a query block reads the keys at or before its last row and no others.
Outputs and gradients against a full-matrix masked softmax written here, in
float32, to the tolerance of a reordered sum; and, at the benchmark's
sequence length, which products the traced function holds."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from defending_against_backdoors_with_robust_learning_rate_tpu.models import (
    lfm2_moe, mla_moe, token_ops)
from defending_against_backdoors_with_robust_learning_rate_tpu.models.token_ops import (
    attention_squares, causal_attention)

# (H, KV, d, dv): MLA's layout (a key a head, values narrower than keys) and
# LFM2's (four query heads a key-value head)
LAYOUTS = {"mla": (4, 4, 12, 8), "gqa": (8, 2, 8, 8)}


def _qkv(layout, t, seed=0, b=2):
    h, kv, d, dv = LAYOUTS[layout]
    kq, kk, kv_ = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(kq, (b, t, h, d), jnp.float32),
            jax.random.normal(kk, (b, t, kv, d), jnp.float32),
            jax.random.normal(kv_, (b, t, kv, dv), jnp.float32))


def full_matrix_attention(q, k, v):
    """The whole [T, T] score matrix, masked above the diagonal."""
    b, t, h, d = q.shape
    g = h // k.shape[2]
    k, v = jnp.repeat(k, g, axis=2), jnp.repeat(v, g, axis=2)
    s = jnp.einsum("bqhd,bshd->bhqs", q, k,
                   precision=jax.lax.Precision.HIGHEST) * d ** -0.5
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhqs,bshd->bqhd", p, v,
                   precision=jax.lax.Precision.HIGHEST)
    return o.reshape(b, t, -1)


@pytest.mark.parametrize("layout", sorted(LAYOUTS))
@pytest.mark.parametrize("t,q_block", [(64, 16), (64, 32), (48, 16)])
def test_blocks_match_a_full_matrix_softmax_in_output_and_gradients(
        layout, t, q_block):
    q, k, v = _qkv(layout, t)
    assert attention_squares(t, q_block)[1] > 1      # the block path
    w = jax.random.normal(jax.random.PRNGKey(7),
                          (2, t, LAYOUTS[layout][0] * LAYOUTS[layout][3]))

    def scalar(fn):
        return lambda q, k, v: jnp.sum(fn(q, k, v) * w)

    got = causal_attention(q, k, v, q_block)
    want = full_matrix_attention(q, k, v)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-6)
    grads = jax.grad(scalar(lambda *a: causal_attention(*a, q_block)),
                     argnums=(0, 1, 2))(q, k, v)
    wants = jax.grad(scalar(full_matrix_attention),
                     argnums=(0, 1, 2))(q, k, v)
    for name, g, wnt in zip("qkv", grads, wants):
        np.testing.assert_allclose(g, wnt, rtol=2e-5, atol=2e-5,
                                   err_msg=f"d{name}")


@pytest.mark.parametrize("layout", sorted(LAYOUTS))
def test_a_later_key_does_not_touch_an_earlier_row(layout):
    t, q_block, at = 64, 16, 40
    q, k, v = _qkv(layout, t, seed=1)
    base = causal_attention(q, k, v, q_block)
    moved = causal_attention(q, k.at[:, at].add(3.0), v.at[:, at].add(-2.0),
                             q_block)
    np.testing.assert_array_equal(np.asarray(base[:, :at]),
                                  np.asarray(moved[:, :at]))
    assert not np.array_equal(np.asarray(base[:, at:]),
                              np.asarray(moved[:, at:]))


@pytest.mark.parametrize("layout", sorted(LAYOUTS))
def test_blocks_batch_over_clients(layout):
    """The stacked round trains clients under `jax.vmap`: the ordering
    between the blocks has to batch, gradients too."""
    t, q_block = 48, 16
    q, k, v = (jnp.stack(x) for x in zip(_qkv(layout, t, seed=3),
                                         _qkv(layout, t, seed=4)))

    def loss(q, k, v):
        return jnp.sum(causal_attention(q, k, v, q_block) ** 2)

    got = jax.vmap(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v)
    for i in range(2):
        want = jax.grad(loss, argnums=(0, 1, 2))(q[i], k[i], v[i])
        for g, w in zip(got, want):
            np.testing.assert_allclose(g[i], w, rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("layout", sorted(LAYOUTS))
def test_a_length_the_block_does_not_divide_takes_one_block(layout):
    t, q_block = 40, 16
    q, k, v = _qkv(layout, t, seed=2)
    assert attention_squares(t, q_block) == (1, 1)
    np.testing.assert_allclose(causal_attention(q, k, v, q_block),
                               full_matrix_attention(q, k, v),
                               rtol=2e-5, atol=2e-6)
    text = str(jax.make_jaxpr(
        lambda *a: causal_attention(*a, q_block))(q, k, v))
    assert "checkpoint" not in text and "remat" not in text


@pytest.mark.parametrize("seq_len,q_block,want", [
    (2048, 512, (10, 16)), (2048, 256, (36, 64)), (64, 512, (1, 1)),
    (512, 512, (1, 1)), (2000, 512, (1, 1)), (4096, 512, (36, 64))])
def test_attention_squares(seq_len, q_block, want):
    assert attention_squares(seq_len, q_block) == want


def _dot_shapes(jaxpr, found):
    """The output shape of every `dot_general` of a jaxpr and of the jaxprs
    inside its equations."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            found.append(eqn.outvars[0].aval.shape)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _dot_shapes(sub, found)
    return found


@pytest.mark.parametrize("layout", sorted(LAYOUTS))
@pytest.mark.parametrize("backward", [False, True])
@pytest.mark.parametrize("qb", [512, token_ops.ATTN_QUERY_BLOCK])
def test_no_product_spans_the_sequence_at_the_benchmarks_length(layout,
                                                                backward, qb):
    """Traced, not run: at T 2048 in blocks of 512 the score products are
    512 x (512, 1024, 1536, 2048), one of each, where a product against
    all 2048 keys for each of the four blocks would be 16 squares; and the
    like at the block the constant sets."""
    t = 2048
    h, kv, d, dv = LAYOUTS[layout]
    g = h // kv
    q, k, v = (jax.ShapeDtypeStruct((1, t, n, w), jnp.bfloat16)
               for n, w in ((h, d), (kv, d), (kv, dv)))

    def fn(q, k, v):
        return jnp.sum(causal_attention(q, k, v, qb).astype(jnp.float32))

    closed = jax.make_jaxpr(jax.grad(fn, argnums=(0, 1, 2))
                            if backward else fn)(q, k, v)
    # a product over a block's rows and `cols` keys is [1, KV, g, qb, cols]
    # in some order of its axes: forward the scores; under `grad` the
    # scores, the scores again (each block is recomputed) and the gradient
    # of the softmax's output
    widths = sorted(max(out) for out in _dot_shapes(closed.jaxpr, [])
                    if sorted(out) == sorted((1, kv, g, qb, max(out))))
    each = 3 if backward else 1
    assert widths == sorted(list(range(qb, t + 1, qb)) * each), widths
    computed, square = attention_squares(t, qb)
    assert (computed, square) == {512: (10, 16), 256: (36, 64)}[qb]
    assert sum(widths) * qb == each * computed * qb * qb


@pytest.mark.parametrize("module,model", [
    (lfm2_moe, lambda: lfm2_moe.LFM2MoE(
        spec=lfm2_moe.spec_from("lfm2-8b-a1b", "0,2,3,4,5", 8, 0, 16384))),
    (mla_moe, lambda: mla_moe.MlaMoE(
        spec=mla_moe.spec_from("joyai-llm-flash", "0,1,2,3,4", 8, 0, 16160))),
], ids=["lfm2_moe", "mla_moe"])
def test_both_token_models_count_the_squares_at_build(module, model):
    assert module.causal_attention is causal_attention
    at_cell = model().build_counters(4 * 2048, 2048)
    assert (at_cell["attn_squares_computed"], at_cell["attn_squares"]) == \
        attention_squares(2048) == (36, 64)
    toy = model().build_counters(2 * 16, 16)
    assert (toy["attn_squares_computed"], toy["attn_squares"]) == (1, 1)


# ---- a sliding window in the same core (PR 33) ------------------------------
def banded_matrix_attention(q, k, v, window):
    """The whole [T, T] score matrix, masked outside the band: query r
    reads keys c with r - window < c <= r."""
    b, t, h, d = q.shape
    g = h // k.shape[2]
    k, v = jnp.repeat(k, g, axis=2), jnp.repeat(v, g, axis=2)
    s = jnp.einsum("bqhd,bshd->bhqs", q, k,
                   precision=jax.lax.Precision.HIGHEST) * d ** -0.5
    r, c = jnp.arange(t)[:, None], jnp.arange(t)[None, :]
    s = jnp.where((c <= r) & (r - c < window), s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhqs,bshd->bqhd", p, v,
                   precision=jax.lax.Precision.HIGHEST)
    return o.reshape(b, t, -1)


@pytest.mark.parametrize("layout", sorted(LAYOUTS))
@pytest.mark.parametrize("window", [16, 24, 40])
@pytest.mark.parametrize("t,q_block", [(64, 16), (64, 32), (48, 16)])
def test_window_blocks_match_a_banded_softmax_in_output_and_gradients(
        layout, t, q_block, window):
    """Windows the block divides, one it does not (24 over 16, 40 over 16
    and 32) and one wider than a block: outputs and q/k/v gradients."""
    q, k, v = _qkv(layout, t, seed=5)
    computed, square = attention_squares(t, q_block, window)
    assert square > 1 and computed <= attention_squares(t, q_block)[0]
    w = jax.random.normal(jax.random.PRNGKey(7),
                          (2, t, LAYOUTS[layout][0] * LAYOUTS[layout][3]))

    def scalar(fn):
        return lambda q, k, v: jnp.sum(fn(q, k, v) * w)

    got = causal_attention(q, k, v, q_block, window)
    want = banded_matrix_attention(q, k, v, window)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-6)
    # the window is felt: the causal result is another
    assert float(jnp.abs(want - full_matrix_attention(q, k, v)).max()) > 1e-3
    grads = jax.grad(scalar(lambda *a: causal_attention(*a, q_block, window)),
                     argnums=(0, 1, 2))(q, k, v)
    wants = jax.grad(scalar(lambda *a: banded_matrix_attention(*a, window)),
                     argnums=(0, 1, 2))(q, k, v)
    for name, g, wnt in zip("qkv", grads, wants):
        np.testing.assert_allclose(g, wnt, rtol=2e-5, atol=2e-5,
                                   err_msg=f"d{name}")


@pytest.mark.parametrize("layout", sorted(LAYOUTS))
@pytest.mark.parametrize("t,q_block,window", [(40, 16, 24), (16, 16, 4)])
def test_a_window_inside_one_block_is_masked_there(layout, t, q_block,
                                                   window):
    """A sequence that takes one block (the block does not divide it, or it
    is the block): the band is masked inside it."""
    q, k, v = _qkv(layout, t, seed=6)
    assert attention_squares(t, q_block, window) == (1, 1)
    np.testing.assert_allclose(causal_attention(q, k, v, q_block, window),
                               banded_matrix_attention(q, k, v, window),
                               rtol=2e-5, atol=2e-6)


@pytest.mark.parametrize("layout", sorted(LAYOUTS))
@pytest.mark.parametrize("window", [16, 24])
def test_a_key_outside_the_band_does_not_touch_a_row(layout, window):
    """Perturb the key and value at position 20: rows before it (causal)
    and rows from 20 + window on (below the band) stay bit-equal, whether
    the key's square is formed and masked or never formed; rows inside the
    band move."""
    t, q_block, at = 64, 16, 20
    q, k, v = _qkv(layout, t, seed=1)
    base = causal_attention(q, k, v, q_block, window)
    moved = causal_attention(q, k.at[:, at].add(3.0), v.at[:, at].add(-2.0),
                             q_block, window)
    outside = np.r_[0:at, at + window:t]
    np.testing.assert_array_equal(np.asarray(base[:, outside]),
                                  np.asarray(moved[:, outside]))
    inside = np.asarray(base[:, at:at + window]
                        != moved[:, at:at + window])
    assert inside.any(axis=(0, 2)).all()


@pytest.mark.parametrize("layout", sorted(LAYOUTS))
def test_window_blocks_batch_over_clients(layout):
    t, q_block, window = 48, 16, 24
    q, k, v = (jnp.stack(x) for x in zip(_qkv(layout, t, seed=3),
                                         _qkv(layout, t, seed=4)))

    def loss(q, k, v):
        return jnp.sum(causal_attention(q, k, v, q_block, window) ** 2)

    got = jax.vmap(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v)
    for i in range(2):
        want = jax.grad(loss, argnums=(0, 1, 2))(q[i], k[i], v[i])
        for g, w in zip(got, want):
            np.testing.assert_allclose(g[i], w, rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("seq_len,q_block,window,want", [
    (4096, 256, 512, (45, 256)), (4096, 256, None, (136, 256)),
    (2048, 256, 512, (21, 64)), (4096, 256, 256, (31, 256)),
    (4096, 256, 513, (45, 256)), (4096, 256, 514, (58, 256)),
    (4096, 256, 1, (16, 256)),
    (4096, 256, 4096, (136, 256)), (64, 16, 24, (9, 16)),
    (64, 16, 40, (10, 16)), (64, 32, 40, (3, 4)), (40, 16, 24, (1, 1))])
def test_attention_squares_under_a_window(seq_len, q_block, window, want):
    assert attention_squares(seq_len, q_block, window) == want


@pytest.mark.parametrize("backward", [False, True])
@pytest.mark.parametrize("heads", [64, 48])
def test_no_window_product_spans_more_than_three_blocks_at_4096(backward,
                                                               heads):
    """Traced, not run, at the new cell's shape (T 4096, 64 or 48 query
    heads over 8 key-value heads of 128, a window of 512, blocks of 256): a
    block's score products are over at most 768 columns, 256 + 512 + 14 x
    768 in all where the causal core holds 256 x (1 + ... + 16)."""
    t, qb, window, kv, d = 4096, token_ops.ATTN_QUERY_BLOCK, 512, 8, 128
    g = heads // kv
    q, k, v = (jax.ShapeDtypeStruct((1, t, n, d), jnp.bfloat16)
               for n in (heads, kv, kv))

    def fn(q, k, v, window=window):
        return jnp.sum(causal_attention(q, k, v, qb, window
                                        ).astype(jnp.float32))

    closed = jax.make_jaxpr(jax.grad(fn, argnums=(0, 1, 2))
                            if backward else fn)(q, k, v)
    widths = sorted(max(out) for out in _dot_shapes(closed.jaxpr, [])
                    if sorted(out) == sorted((1, kv, g, qb, max(out))))
    each = 3 if backward else 1
    assert widths == sorted(([256, 512] + [768] * 14) * each), widths
    assert max(widths) == 768
    computed, square = attention_squares(t, qb, window)
    assert (computed, square) == (45, 256)
    assert sum(widths) * qb == each * computed * qb * qb
    # the causal core at the same shape: 136 squares
    causal = jax.make_jaxpr(lambda q, k, v: fn(q, k, v, None))(q, k, v)
    assert sum(max(out) for out in _dot_shapes(causal.jaxpr, [])
               if sorted(out) == sorted((1, kv, g, qb, max(out)))
               ) == 136 * qb


def parent_causal_attention(q, k, v, q_block):
    """`token_ops.causal_attention` as PR 32 left it (commit da118ef), kept
    here word for word: what `window=None` has to trace."""
    b, t, h, d = q.shape
    kv = k.shape[2]
    g = h // kv
    scale = d ** -0.5
    qb = token_ops._query_block(t, q_block)
    qs = q.reshape(b, t // qb, qb, kv, g, d)

    def block(qi, ki, vi):
        s = jnp.einsum("bqkgd,bskd->bkgqs", qi, ki,
                       preferred_element_type=jnp.float32) * scale
        cols = jnp.arange(ki.shape[1])
        rows = ki.shape[1] - qb + jnp.arange(qb)
        s = jnp.where(rows[:, None] >= cols[None, :], s,
                      jnp.finfo(jnp.float32).min)
        p = jax.nn.softmax(s, axis=-1).astype(vi.dtype)
        return jnp.einsum("bkgqs,bskd->bqkgd", p, vi)

    if t == qb:
        return block(qs[:, 0], k, v).reshape(b, t, h * v.shape[-1])
    outs = []
    for i, end in enumerate(range(qb, t + 1, qb)):
        qi = qs[:, i]
        if outs:
            qi, outs[-1] = jax.lax.optimization_barrier((qi, outs[-1]))
        outs.append(jax.checkpoint(block)(qi, k[:, :end], v[:, :end]))
    return jnp.concatenate(outs, axis=1).reshape(b, t, h * v.shape[-1])


@pytest.mark.parametrize("layout", sorted(LAYOUTS))
@pytest.mark.parametrize("backward", [False, True])
@pytest.mark.parametrize("t,q_block", [(2048, 256), (64, 16), (40, 16)])
def test_without_a_window_the_core_traces_what_the_parent_traced(
        layout, backward, t, q_block):
    h, kv, d, dv = LAYOUTS[layout]
    q, k, v = (jax.ShapeDtypeStruct((2, t, n, w), jnp.bfloat16)
               for n, w in ((h, d), (kv, d), (kv, dv)))

    def trace(fn):
        def scalar(q, k, v):
            return jnp.sum(fn(q, k, v).astype(jnp.float32))
        return str(jax.make_jaxpr(jax.grad(scalar, argnums=(0, 1, 2))
                                  if backward else scalar)(q, k, v))

    want = trace(lambda *a: parent_causal_attention(*a, q_block))
    assert trace(lambda *a: causal_attention(*a, q_block)) == want
    assert trace(lambda *a: causal_attention(*a, q_block, None)) == want
    if t > q_block and t % q_block == 0:
        assert trace(lambda *a: causal_attention(*a, q_block, 32)) != want


def test_the_window_model_counts_both_kinds_squares_at_build():
    from defending_against_backdoors_with_robust_learning_rate_tpu.models import (
        swa_moe)
    assert swa_moe.causal_attention is causal_attention
    model = swa_moe.SwaMoE(spec=swa_moe.spec_from(
        "laguna-xs.2", "0,1,2,3,4", 16, 0, 12544))
    at_cell = model.build_counters(2 * 4096, 4096)
    assert (at_cell["attn_window_squares_computed"],
            at_cell["attn_squares_computed"], at_cell["attn_squares"]) == \
        (45, 136, 256)
    assert (at_cell["attn_window"], at_cell["attn_window_layers"],
            at_cell["attn_full_layers"]) == (512, 3, 2)
    # at the other token cells' 2048 the window would save 15 of 36
    at_2048 = model.build_counters(4 * 2048, 2048)
    assert (at_2048["attn_window_squares_computed"],
            at_2048["attn_squares_computed"]) == (21, 36)
