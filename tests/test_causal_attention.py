"""`token_ops.causal_attention` over more than one query block, which no
model test reaches (every toy sequence is shorter than `ATTN_QUERY_BLOCK`):
a query block reads the keys at or before its last row and no others.
Outputs and gradients against a full-matrix masked softmax written here, in
float32, to the tolerance of a reordered sum; and, at the benchmark's
sequence length, which products the traced function holds.

From PR 35 the function has a second lowering, the fused kernel of
`models/attention_kernel.py`, taken where a program is traced for the TPU
(`token_ops.kernel_plan`). Here on the CPU `causal_attention` IS the plain
path, word for word what the parent traced; the kernel runs in Pallas's
interpret mode against it and against the masked softmax written here, and
`as_on_the_tpu` shows which sequences would take it. (Compiled for a
described v5e at the published widths: `test_tpu_compile_lfm2.py`.)"""

import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from defending_against_backdoors_with_robust_learning_rate_tpu.models import (
    attention_kernel, lfm2_moe, mla_moe, token_ops)
from defending_against_backdoors_with_robust_learning_rate_tpu.models.token_ops import (
    attention_squares, causal_attention, plain_causal_attention)

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


@pytest.mark.parametrize("seq_len,q_block,k_block,want", [
    (2048, 512, None, (10, 16)), (2048, 256, None, (36, 64)),
    (64, 512, None, (1, 1)), (512, 512, None, (1, 1)),
    (2000, 512, None, (1, 1)), (4096, 512, None, (36, 64)),
    # the kernel's blocks (`attention_kernel.plan`): a key block of its
    # own width; 3 of 4 and 10 of 16 are the cells' causal layers
    (2048, 1024, 1024, (3, 4)), (4096, 1024, 1024, (10, 16)),
    (2048, 512, 512, (10, 16)), (4096, 512, 512, (36, 64)),
    (2048, 512, 256, (20, 32)), (2048, 256, 512, (20, 32)),
    (512, 512, 128, (4, 4)), (256, 128, 128, (3, 4))])
def test_attention_squares(seq_len, q_block, k_block, want):
    assert attention_squares(seq_len, q_block, k_block=k_block) == want
    assert want == _blocks_the_mask_touches(seq_len, q_block, None, k_block)


def _blocks_the_mask_touches(seq_len, q_block, window, k_block):
    """`attention_squares` by counting: the [T, T] mask cut into the
    path's blocks, those that keep any score."""
    qb = token_ops._query_block(seq_len, q_block)
    kb = k_block or qb
    r, c = np.arange(seq_len)[:, None], np.arange(seq_len)[None, :]
    keep = (c <= r) if window is None else (c <= r) & (r - c < window)
    tiles = keep.reshape(seq_len // qb, qb, seq_len // kb, kb).any((1, 3))
    return int(tiles.sum()), tiles.size


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


MODELS = [
    (lfm2_moe, lambda: lfm2_moe.LFM2MoE(
        spec=lfm2_moe.spec_from("lfm2-8b-a1b", "0,2,3,4,5", 8, 0, 16384)),
     1),
    (mla_moe, lambda: mla_moe.MlaMoE(
        spec=mla_moe.spec_from("joyai-llm-flash", "0,1,2,3,4", 8, 0, 16160)),
     6),
]


@pytest.fixture
def as_on_the_tpu(monkeypatch):
    """Programs traced in this test are built as the TPU's are: the
    platform's name is all `token_ops.kernel_plan` asks of it."""
    monkeypatch.setattr(attention_kernel, "on_tpu", lambda: True)


@pytest.mark.parametrize("module,model,layers", MODELS,
                         ids=["lfm2_moe", "mla_moe"])
def test_both_token_models_count_the_squares_at_build(module, model, layers):
    assert module.causal_attention is causal_attention
    at_cell = model().build_counters(4 * 2048, 2048)
    assert (at_cell["attn_squares_computed"], at_cell["attn_squares"]) == \
        attention_squares(2048) == (36, 64)
    # on the CPU every attention layer (LFM2's one of five; MLA's five and
    # the MTP block's) takes the plain path
    assert at_cell["attn_path"] == {"plain": layers}
    toy = model().build_counters(2 * 16, 16)
    assert (toy["attn_squares_computed"], toy["attn_squares"]) == (1, 1)
    assert toy["attn_path"] == {"plain": layers}


@pytest.mark.parametrize("module,model,layers", MODELS,
                         ids=["lfm2_moe", "mla_moe"])
def test_built_for_the_tpu_the_models_count_the_kernels_blocks(
        module, model, layers, as_on_the_tpu):
    """The kernel's path and the squares at the kernel's blocks (3 of 4 at
    2048); a sequence the kernel does not take stays plain there too."""
    at_cell = model().build_counters(4 * 2048, 2048)
    assert (at_cell["attn_squares_computed"], at_cell["attn_squares"]) == \
        attention_squares(2048, 1024, k_block=1024) == (3, 4)
    assert at_cell["attn_path"] == {"kernel": layers}
    toy = model().build_counters(2 * 16, 16)
    assert (toy["attn_squares_computed"], toy["attn_squares"]) == (1, 1)
    assert toy["attn_path"] == {"plain": layers}


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
    assert want == _blocks_the_mask_touches(seq_len, q_block, window, None)


@pytest.mark.parametrize("seq_len,q_block,k_block,window,want", [
    (4096, 512, 256, 512, (30, 128)), (4096, 256, 256, 512, (45, 256)),
    (4096, 512, 512, 512, (15, 64)), (4096, 512, 128, 512, (60, 256)),
    (4096, 128, 128, 512, (150, 1024)), (384, 128, 128, 200, (6, 9)),
    (512, 128, 128, 1, (4, 16)), (512, 256, 128, 129, (5, 8))])
def test_attention_squares_under_a_window_at_the_kernels_blocks(
        seq_len, q_block, k_block, window, want):
    assert attention_squares(seq_len, q_block, window, k_block) == want
    assert want == _blocks_the_mask_touches(seq_len, q_block, window,
                                            k_block)


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
    assert at_cell["attn_window_squares"] == 256
    assert at_cell["attn_path"] == {"plain": 5}
    # at the other token cells' 2048 the window would save 15 of 36
    at_2048 = model.build_counters(4 * 2048, 2048)
    assert (at_2048["attn_window_squares_computed"],
            at_2048["attn_squares_computed"]) == (21, 36)


def test_built_for_the_tpu_the_window_model_counts_the_kernels_blocks(
        as_on_the_tpu):
    from defending_against_backdoors_with_robust_learning_rate_tpu.models import (
        swa_moe)
    model = swa_moe.SwaMoE(spec=swa_moe.spec_from(
        "laguna-xs.2", "0,1,2,3,4", 16, 0, 12544))
    at_cell = model.build_counters(2 * 4096, 4096)
    full, band = attention_kernel.plan(4096), attention_kernel.plan(4096, 512)
    assert (at_cell["attn_squares_computed"], at_cell["attn_squares"]) == \
        attention_squares(4096, full.q, k_block=full.k) == (10, 16)
    assert (at_cell["attn_window_squares_computed"],
            at_cell["attn_window_squares"]) == \
        attention_squares(4096, band.q, 512, band.k) == (15, 64)
    assert at_cell["attn_path"] == {"kernel": 5}
    toy = model.build_counters(2 * 32, 32)
    assert toy["attn_path"] == {"plain": 5}


# ---- the fused kernel, the TPU's lowering of the same core (PR 35) ----------
# (H, KV, d, dv, T, window, (query block, key block) where they are not the
# module's own, wrapper): the cells' head layouts at fewer heads, in
# Pallas's interpret mode (the backward fused under a causal mask, two
# kernels under a window: `attention_kernel.plan`)
KERNEL_CASES = {
    # MLA: a key a head, 192 wide with ONE rotary part broadcast to every
    # head, values of 128
    "mla_causal": (2, 2, 192, 128, 256, None, (128, 128), None),
    "mla_causal_checkpoint": (2, 2, 192, 128, 256, None, (128, 128),
                              "checkpoint"),
    # LFM2: 32 / 8 heads of 64
    "lfm2_causal": (4, 1, 64, 64, 384, None, None, None),
    "lfm2_one_kernel_block": (4, 1, 64, 64, 512, None, None, None),
    "lfm2_window_one_key": (4, 1, 64, 64, 256, 1, (128, 128), None),
    # Laguna: 48 (full layers) and 64 (window layers) over 8 of 128
    "laguna_causal": (6, 1, 128, 128, 256, None, (128, 128), None),
    "laguna_window_one_block": (8, 1, 128, 128, 384, 128, None, None),
    "laguna_window_undivided": (8, 1, 128, 128, 384, 200, None, None),
    "laguna_window_wide_q": (8, 2, 128, 128, 512, 130, (256, 128), None),
    "laguna_window_vmap": (8, 2, 128, 128, 256, 72, (128, 128), "vmap"),
}


def _kernel_qkv(case, lead=(1,), seed=0):
    h, kv, d, dv, t = KERNEL_CASES[case][:5]
    kq, kk, kp, kv_ = jax.random.split(jax.random.PRNGKey(seed), 4)
    k = jax.random.normal(kk, lead + (t, kv, d), jnp.float32)
    if case.startswith("mla"):
        # k = [k_nope | k_pe], the last 64 widths one vector for all heads
        k_pe = jax.random.normal(kp, lead + (t, 1, 64), jnp.float32)
        k = jnp.concatenate(
            [k[..., :d - 64], jnp.broadcast_to(k_pe, lead + (t, kv, 64))],
            axis=-1)
    return (jax.random.normal(kq, lead + (t, h, d), jnp.float32), k,
            jax.random.normal(kv_, lead + (t, kv, dv), jnp.float32))


def _kernel_fns(case):
    """(the kernel in interpret mode, the plain path, the masked softmax)
    for a case, wrapped as the case says."""
    _h, _kv, _d, _dv, t, window, blocks, wrapper = KERNEL_CASES[case]
    took = attention_kernel.plan(t, window)
    if blocks:
        took = took._replace(q=blocks[0], k=blocks[1], compute=blocks[1])
    assert t % took.q == 0 and t % took.k == 0
    fns = [functools.partial(attention_kernel.attention, window=window,
                             took=took, interpret=True),
           functools.partial(plain_causal_attention, q_block=128,
                             window=window),
           full_matrix_attention if window is None else
           functools.partial(banded_matrix_attention, window=window)]
    if wrapper == "vmap":      # clients, each a batch of sequences
        fns = [jax.vmap(fn) for fn in fns]
    elif wrapper == "checkpoint":
        fns[0] = jax.checkpoint(fns[0])
    return fns


@pytest.mark.parametrize("case", sorted(KERNEL_CASES))
def test_the_kernel_matches_the_plain_path_and_a_masked_softmax(case):
    """Outputs and q / k / v gradients, in float32, to the tolerance of a
    reordered sum (an online softmax reorders; it leaves nothing out)."""
    h, _kv, _d, dv, t, window, _blocks, wrapper = KERNEL_CASES[case]
    lead = (2, 1) if wrapper == "vmap" else (1,)
    q, k, v = _kernel_qkv(case, lead)
    w = jax.random.normal(jax.random.PRNGKey(7), lead + (t, h * dv))

    def scalar(fn):
        def with_output(q, k, v):
            o = fn(q, k, v)
            return jnp.sum(o * w), o
        return jax.value_and_grad(with_output, argnums=(0, 1, 2),
                                  has_aux=True)

    kernel, plain, matrix = _kernel_fns(case)
    (_, got), grads = scalar(kernel)(q, k, v)
    for other in (plain, matrix):
        (_, want), wants = scalar(other)(q, k, v)
        np.testing.assert_allclose(got, want, rtol=2e-5, atol=5e-6)
        for g, wg in zip(grads, wants):
            np.testing.assert_allclose(g, wg, rtol=2e-4, atol=5e-5)
    if window is not None:
        # the window is felt: the causal result is another
        causal = jax.vmap(full_matrix_attention) if wrapper == "vmap" \
            else full_matrix_attention
        assert float(jnp.abs(want - causal(q, k, v)).max()) > 1e-3


@pytest.mark.parametrize("case", ["lfm2_causal", "laguna_window_undivided"])
def test_in_the_kernel_a_key_outside_a_rows_band_leaves_the_row_bit_equal(
        case):
    """A key and value moved at one position: the rows before it and, under
    a window, the rows whose band has passed it, come out bit for bit."""
    t, window = KERNEL_CASES[case][4:6]
    at = 150
    kernel = _kernel_fns(case)[0]
    q, k, v = _kernel_qkv(case, seed=1)
    base = kernel(q, k, v)
    moved = kernel(q, k.at[:, at].add(3.0), v.at[:, at].add(-2.0))
    reads = np.r_[at:t if window is None else at + window]
    outside = np.setdiff1d(np.arange(t), reads)
    np.testing.assert_array_equal(np.asarray(base[:, outside]),
                                  np.asarray(moved[:, outside]))
    assert (np.asarray(base[:, reads]) != np.asarray(moved[:, reads])) \
        .any(axis=-1).all()


@pytest.mark.parametrize("t,window,want", [
    (2048, None, (1024, 1024, 512, "fused")),
    (4096, None, (1024, 1024, 512, "fused")),
    (256, None, (256, 256, 256, "fused")),
    (384, None, (128, 128, 128, "fused")),
    (1280, None, (256, 256, 256, "fused")),
    # under a window: two backward kernels, a key block no wider than
    # the band
    (4096, 512, (512, 512, 512, "apart")),
    (4096, 256, (512, 256, 256, "apart")),
    (2048, 100, (512, 128, 128, "apart")),
    (4096, 2048, (512, 512, 512, "apart")),
    (2048, 300, (512, 256, 256, "apart")),
    (256, 512, (256, 256, 256, "apart")),
    (384, 200, (128, 128, 128, "apart")),
    (16, None, None), (32, 8, None), (2000, None, None), (200, 64, None)])
def test_the_kernels_plan_follows_the_sequence_and_the_mask(t, window, want):
    assert attention_kernel.plan(t, window) == want


def _traced(t, window, q_block=token_ops.ATTN_QUERY_BLOCK, fn=None):
    q, k, v = (jax.ShapeDtypeStruct((1, t, n, 64), jnp.bfloat16)
               for n in (4, 2, 2))
    fn = fn or causal_attention

    def scalar(q, k, v):
        return jnp.sum(fn(q, k, v, q_block, window).astype(jnp.float32))

    return str(jax.make_jaxpr(jax.grad(scalar, argnums=(0, 1, 2)))(q, k, v))


@pytest.mark.parametrize("t,window", [
    (2048, None), (4096, 512), (384, None), (384, 200)])
def test_the_kernel_is_taken_only_where_a_program_is_traced_for_the_tpu(
        t, window, monkeypatch):
    """Here `causal_attention` traces the plain path and nothing of the
    kernel; traced as on the TPU, the kernel's forward and backward calls
    and no score tensor."""
    plain = _traced(t, window, fn=plain_causal_attention)
    assert token_ops.kernel_plan(t, window=window) is None
    assert _traced(t, window) == plain and "pallas_call" not in plain
    monkeypatch.setattr(attention_kernel, "on_tpu", lambda: True)
    took = token_ops.kernel_plan(t, window=window)
    assert took == attention_kernel.plan(t, window)
    kernel = _traced(t, window)
    # the forward kernel, and dq with dkv in one kernel or in two
    assert kernel.count("pallas_call") == {"fused": 2, "apart": 3}[
        took.backward]
    # float32 scores [B, KV, g, query rows, keys]
    scores = re.compile(r"f32\[1,2,2,\d+,\d+\]")
    assert scores.search(plain) and not scores.search(kernel)


@pytest.mark.parametrize("t,window,q_block", [
    (200, None, 256), (2000, None, 256), (16, None, 256), (48, 8, 16),
    (600, 64, 200), (256, None, 256), (128, 64, 256)])
def test_what_the_kernel_does_not_serve_stays_plain_on_the_tpu_too(
        t, window, q_block, as_on_the_tpu):
    """A length the 128-lane blocks do not divide, and a sequence the plain
    path runs as one block: the function is the plain path's to the letter
    on every platform."""
    assert token_ops.kernel_plan(t, q_block, window) is None
    assert _traced(t, window, q_block) == \
        _traced(t, window, q_block, fn=plain_causal_attention)
