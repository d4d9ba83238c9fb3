"""What the token models share (`lfm2_moe.py`, `mla_moe.py`, `swa_moe.py`):
RMSNorm, the matrix product in the products' dtype, rotate-half rotary
embedding, causal attention (with a sliding window where a layer has one),
the gated feed-forward, the shared expert, and ONE implementation of the
sparse feed-forward: sigmoid routing over every
published expert, the sort of the (token, expert) pairs by held expert,
dispatch, the held experts' grouped products and combine.

A sparse layer reads its shape off the model's spec, whatever its class:
`n_experts` (the router's width), `top_k`, `norm_topk`, `topk_eps` (what the
source adds to the selected scores' sum before dividing), `routed_scale`,
`experts_held` from `expert_offset`. The routing bias is the model's own
buffer and comes as an argument (None: the scores alone select).

`s = sigmoid(x W_g)` in float32 over ALL published experts, `sel =
top_k(s + b)`, `w = s[sel] / (sum s[sel] + topk_eps) * routed_scale`, and of
`y = sum_e w_e expert_e(x)` the terms whose expert is held here. That
partial sum goes on to the next layer; nothing stands in for the experts
of other chips. No (token, expert) pair is dropped: pairs are sorted by
expert, those held first, and the held experts' products are three
`jax.lax.ragged_dot` calls over the first `dispatch_rows` sorted rows,
twice the share of the pairs that the held experts draw in expectation and
at least two rows a token; a step that holds more computes the rest in a
second pass under a `jax.lax.cond`. Scopes: `moe_router`, `moe_experts`,
`dense_ffn`, `shared_expert`.

The attention core, `causal_attention`, is one function with two lowerings,
chosen when a program is traced (`kernel_plan`). `plain_causal_attention`,
in `jax.numpy` over query blocks, is the reference: what every program built
for the CPU runs, what the TPU runs for a sequence of one block or of a
length that 128 lanes do not divide, and what the kernel is tested against.
A program built for the TPU runs every other sequence through the fused
kernel of `attention_kernel.py`, forward and backward, where the float32
scores stay in VMEM."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from defending_against_backdoors_with_robust_learning_rate_tpu.models import (
    attention_kernel)

ATTN_QUERY_BLOCK = 256     # attention runs over query blocks of this many
# the sorted buffer of a sparse layer holds MOE_ROWS_OVER_EXPECTED times the
# pairs its held experts draw when every expert draws alike, in whole tiles
# of MOE_ROWS_TILE rows (`dispatch_rows`)
MOE_ROWS_OVER_EXPECTED = 2
MOE_ROWS_TILE = 512
# and never fewer than this many rows a token. With few of many experts held
# the expected share says little about what they draw when tokens crowd onto
# them: at 8 of 256, top-8, seeded random gates, the held experts drew up to
# four times their share, over a row a token in one forward of sixty, and a
# round's length followed the seed through the second pass (PERF.md section
# 6, PR 31). Two rows a token is what 8 of 32 at top-4 had already.
MOE_ROWS_PER_TOKEN = 2
EXPERT_BIAS_SCALE = 0.05


def held_cut(layers: str, experts_held: int, expert_offset: int,
             vocab_held: int, n_layers: int, n_experts: int, vocab: int):
    """(source layer indices held, experts held, vocabulary rows held) of
    the flags that state a cut, checked against what the source has.
    `layers` is a comma list of the source's layer indices ("" = all); 0
    experts or rows held = all of them."""
    held = ([int(t) for t in layers.split(",") if t.strip()]
            if layers else list(range(n_layers)))
    if not held or any(not 0 <= i < n_layers for i in held) \
            or sorted(set(held)) != held:
        raise ValueError(
            f"--lm_layers must be ascending source layer indices in "
            f"[0, {n_layers}), got {layers!r}")
    e_held = experts_held or n_experts
    if not (0 < e_held <= n_experts and 0 <= expert_offset
            and expert_offset + e_held <= n_experts):
        raise ValueError(
            f"--lm_experts_held {experts_held} from --lm_expert_offset "
            f"{expert_offset} does not lie inside the {n_experts} experts "
            f"the source has")
    v_held = vocab_held or vocab
    if not 0 < v_held <= vocab:
        raise ValueError(f"--lm_vocab_held {vocab_held} is not in "
                         f"(0, {vocab}]")
    return held, e_held, v_held


def fixed_bias(n_experts: int, src_layer: int) -> np.ndarray:
    """A routing bias that is a fixed small non-zero function of the
    source's layer index and the expert: a buffer that selection reads and
    no gradient reaches (the sources train theirs by a load-balancing rule
    this system does not run, and publish it only with the weights)."""
    e = np.arange(n_experts, dtype=np.float64)
    b = EXPERT_BIAS_SCALE * np.sin(12.9898 * (n_experts * src_layer + e) + 1.0)
    return b.astype(np.float32)


def _rms(x, w, eps):
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return y * w


def _query_block(seq_len: int, q_block: int) -> int:
    """Rows of a query block: `q_block` where it divides a longer sequence,
    else the whole sequence in one block."""
    return (q_block if seq_len % q_block == 0 and seq_len > q_block
            else seq_len)


def _first_key_block(i: int, qb: int, window, kb=None) -> int:
    """The key block query block `i` starts reading at: the one that holds
    column `i * qb - window + 1`, the earliest key its first row reads
    (block 0 without a window). Key blocks are `kb` wide, the query
    block's width unless given."""
    return 0 if window is None else max(0, (i * qb - window + 1)
                                        // (kb or qb))


def attention_squares(seq_len: int, q_block: int = ATTN_QUERY_BLOCK,
                      window=None, k_block=None):
    """(computed, square): how many (query block, key block) squares of
    scores `causal_attention` forms for a sequence of `seq_len`, those at
    or below the diagonal and, under a `window`, not wholly below the
    band, and how many the square score matrix has. The plain path's key
    blocks are its query blocks; the kernel's have a width of their own,
    `k_block` (`attention_kernel.plan`)."""
    qb = _query_block(seq_len, q_block)
    kb = k_block or qb
    nq, nk = seq_len // qb, seq_len // kb
    # query block i reads from its first key block to the one that holds
    # its last row's own column
    return (sum(((i + 1) * qb - 1) // kb + 1
                - _first_key_block(i, qb, window, kb) for i in range(nq)),
            nq * nk)


def rope_half(x, inv_freq, scale: float = 1.0):
    """Rotate-half rotary embedding, `x cos + rot(x) sin` with `rot(x) =
    [-x2, x1]`, over the first `2 * len(inv_freq)` widths of a head, the
    rest passed through; x [B, T, n, d], positions from 0. `scale`
    multiplies cos and sin (YaRN's attention factor)."""
    d, t, r = x.shape[-1], x.shape[1], 2 * inv_freq.shape[0]
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    ang = jnp.concatenate([ang, ang], axis=-1)[None, :, None, :]
    xr = x if r == d else x[..., :r]
    x1, x2 = xr[..., :r // 2], xr[..., r // 2:]
    rot = jnp.concatenate([-x2, x1], axis=-1)

    def scaled(fn):
        return fn(ang) if scale == 1.0 else fn(ang) * scale

    out = xr * scaled(jnp.cos) + rot * scaled(jnp.sin)
    return out if r == d else jnp.concatenate([out, x[..., r:]], axis=-1)


def plain_causal_attention(q, k, v, q_block: int = ATTN_QUERY_BLOCK,
                           window=None):
    """Causal softmax attention over query blocks, in plain `jax.numpy`:
    q [B, T, H, d], k [B, T, KV, d] and v [B, T, KV, dv], H a multiple of
    KV; -> [B, T, H * dv]. Scores (scaled by d ** -0.5) and softmax are
    float32. A query block reads the keys and values at or before its last
    row and no others: the squares above the diagonal are never formed
    (`attention_squares`), the diagonal square is masked. With a `window`,
    query r reads the `window` keys c with `r - window < c <= r`: a block
    reads from the start of the key block that holds its first row's
    earliest key, masks the band inside what it reads, and the squares
    below the band are never formed either. Every block's scores are
    recomputed in backward, so what is saved is q, k and v."""
    b, t, h, d = q.shape
    kv = k.shape[2]
    g = h // kv
    scale = d ** -0.5
    qb = _query_block(t, q_block)
    qs = q.reshape(b, t // qb, qb, kv, g, d)

    def block(qi, ki, vi):
        """`qi`'s rows are the last `qb` of the `ki.shape[1]` positions."""
        s = jnp.einsum("bqkgd,bskd->bkgqs", qi, ki,
                       preferred_element_type=jnp.float32) * scale
        cols = jnp.arange(ki.shape[1])
        rows = ki.shape[1] - qb + jnp.arange(qb)
        keep = rows[:, None] >= cols[None, :]
        if window is not None:
            keep = keep & (rows[:, None] - cols[None, :] < window)
        s = jnp.where(keep, s, jnp.finfo(jnp.float32).min)
        p = jax.nn.softmax(s, axis=-1).astype(vi.dtype)
        return jnp.einsum("bkgqs,bskd->bqkgd", p, vi)

    if t == qb:
        return block(qs[:, 0], k, v).reshape(b, t, h * v.shape[-1])
    # the blocks have each its own width, so they are traced in turn, and
    # each waits for the one before it (backward, for the one after it):
    # left to the scheduler, eight blocks' scores and key gradients at once
    # put the MLA layer's temporaries 9% over what a rolled loop took
    # (XLA's memory analysis for a described v5e; PERF.md section 6, PR 32)
    outs = []
    for i, end in enumerate(range(qb, t + 1, qb)):
        qi = qs[:, i]
        if outs:
            qi, outs[-1] = jax.lax.optimization_barrier((qi, outs[-1]))
        start = _first_key_block(i, qb, window) * qb
        outs.append(jax.checkpoint(block)(
            qi, k[:, start:end], v[:, start:end]))
    return jnp.concatenate(outs, axis=1).reshape(b, t, h * v.shape[-1])


def kernel_plan(seq_len: int, q_block: int = ATTN_QUERY_BLOCK, window=None):
    """The `attention_kernel.Plan` by which `causal_attention` runs the
    fused kernel, or None where it takes the plain path: off the TPU, for
    a sequence the plain path runs as one block (`seq_len <= q_block`), and
    for a length the kernel's 128-lane blocks do not divide."""
    if not attention_kernel.on_tpu() or seq_len <= q_block:
        return None
    return attention_kernel.plan(seq_len, window)


def causal_attention(q, k, v, q_block: int = ATTN_QUERY_BLOCK, window=None):
    """Causal softmax attention, q [B, T, H, d], k [B, T, KV, d], v [B, T,
    KV, dv] -> [B, T, H * dv], under a `window` of keys where a layer has
    one: `plain_causal_attention`'s mathematics by one of two lowerings,
    chosen when the program is traced, from the platform it is built for,
    the sequence's length and the window (`kernel_plan`). On the TPU a
    sequence of several whole kernel blocks runs the fused kernel, forward
    and backward (`attention_kernel.attention`: the float32 scores never
    leave VMEM); everywhere else, and for every other sequence on the TPU
    too, the plain path over query blocks of `q_block`, which is also the
    reference the kernel is tested against."""
    took = kernel_plan(q.shape[1], q_block, window)
    if took is None:
        return plain_causal_attention(q, k, v, q_block, window)
    return attention_kernel.attention(q, k, v, window, took)


def attention_plan(seq_len: int, window=None):
    """(path, computed, square) of `causal_attention` for a sequence of
    `seq_len` in a program built in this process: "kernel" or "plain", and
    that path's `attention_squares` at its own blocks."""
    took = kernel_plan(seq_len, window=window)
    if took is None:
        return ("plain",) + attention_squares(seq_len, window=window)
    return ("kernel",) + attention_squares(seq_len, took.q, window, took.k)


def dispatch_rows(sp, n_tokens: int) -> int:
    """Rows of the sorted buffer a sparse layer's first pass computes for
    `n_tokens` tokens: all `n_tokens * top_k` pairs where every expert is
    held, else the larger of MOE_ROWS_OVER_EXPECTED times the held experts'
    share and MOE_ROWS_PER_TOKEN rows a token, in whole tiles."""
    worst = n_tokens * sp.top_k

    def tiles(rows):
        return -(-rows // MOE_ROWS_TILE) * MOE_ROWS_TILE

    share = -(-MOE_ROWS_OVER_EXPECTED * worst * sp.experts_held
              // sp.n_experts)
    return min(worst, max(tiles(share),
                          tiles(MOE_ROWS_PER_TOKEN * n_tokens)))


def _rows(src, idx):
    """src[idx], an index outside clipped to the nearest row: `jnp.take`'s
    default is a second pass over the output that fills such rows in."""
    return jnp.take(src, idx, axis=0, mode="clip")


def _inside(at, src):
    """Which of the rows `at` are rows of `src`."""
    return (at >= 0) & (at < src.shape[0])


def _gather_sum(src, at, wt):
    """sum_j wt[j, t] * src[at[j, t]] over the pairs whose row `at` lies in
    `src`: [rows, d], [k, n], [k, n] -> [n, d]."""
    wt = jnp.where(_inside(at, src), wt, 0).astype(src.dtype)
    y = _rows(src, at.reshape(-1))
    return jnp.sum(y.reshape(at.shape + src.shape[1:]) * wt[:, :, None],
                   axis=0)


@jax.custom_vjp
def _dispatch(x, idx, at):
    """x[idx % n], the token rows of the sorted pairs `idx` [rows]; `at`
    [k, n] is the row of every pair (outside [0, rows) where it is not
    among `idx`): backward is a gather through `at` and a sum over a
    token's k pairs, where autodiff would scatter-add."""
    return _rows(x, idx % at.shape[1])


def _dispatch_fwd(x, idx, at):
    return _dispatch(x, idx, at), at


def _dispatch_bwd(at, g):
    return _gather_sum(g, at, jnp.ones(at.shape, g.dtype)), None, None


_dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@jax.custom_vjp
def _combine(ys, wk, idx, at):
    """A token's weighted sum of its pairs' rows of `ys` [rows, d], with
    `idx` and `at` as `_dispatch` has them. Backward works on the sorted
    rows: the output's gradient gathered by token, times the pair's weight
    for `ys`, times `ys` and summed for the weight."""
    return _gather_sum(ys, at, wk)


def _combine_fwd(ys, wk, idx, at):
    return _combine(ys, wk, idx, at), (ys, wk, idx, at)


def _combine_bwd(res, g):
    ys, wk, idx, at = res
    gs = _rows(g, idx % at.shape[1])
    d_ys = gs * _rows(wk.reshape(-1), idx)[:, None].astype(gs.dtype)
    d_rows = jnp.sum(gs.astype(jnp.float32) * ys.astype(jnp.float32),
                     axis=-1)
    d_wk = jnp.where(_inside(at, ys), _rows(d_rows, at), 0)
    return d_ys, d_wk.astype(wk.dtype), None, None


_combine.defvjp(_combine_fwd, _combine_bwd)


def _mm(x, w, dtype):
    return jnp.dot(x.astype(dtype), w.astype(dtype))


def dense_ffn(p, x, dtype):
    with jax.named_scope("dense_ffn"):
        return _mm(jax.nn.silu(_mm(x, p["w1"], dtype))
                   * _mm(x, p["w3"], dtype), p["w2"], dtype)


def shared_expert(p, x, dtype):
    """The gated feed-forward every token passes beside its routed experts
    (`shared_w1`, `shared_w3`, `shared_w2`), added ungated."""
    with jax.named_scope("shared_expert"):
        return _mm(jax.nn.silu(_mm(x, p["shared_w1"], dtype))
                   * _mm(x, p["shared_w3"], dtype), p["shared_w2"], dtype)


def _expert_rows(lo, hi, trained, sort):
    """The held experts' weighted outputs over the sorted rows [lo, hi) of
    the pairs, summed by token: [n, d]. `trained` is (tokens [n, d], pair
    weights [k, n], the three expert matrices), all in the products'
    dtype; `sort` (order, inverse, held experts' group sizes) of the
    pairs."""
    x, wk, w1, w3, w2 = trained
    order, inv, sizes = sort
    with jax.named_scope("moe_router"):
        idx, at = order[lo:hi], inv.reshape(wk.shape) - lo
        # the part of every expert's group that lies in these rows
        end = jnp.cumsum(sizes)
        part = jnp.clip(jnp.minimum(end, hi) - jnp.maximum(end - sizes, lo),
                        0)
        # rows past the held pairs belong to no group: a grouped product
        # leaves them undefined, so they are zeroed going in and coming out
        valid = (jnp.arange(lo, hi) < end[-1])[:, None]
        xs = jnp.where(valid, _dispatch(x, idx, at), 0)
    with jax.named_scope("moe_experts"):
        # bfloat16 operands go at the default precision whatever the
        # process-wide setting: the TPU's grouped product refuses them at
        # float32 precision
        grouped = functools.partial(
            jax.lax.ragged_dot, group_sizes=part,
            precision=(jax.lax.Precision.DEFAULT if x.dtype == jnp.bfloat16
                       else None))
        h1 = grouped(xs, w1)
        h3 = grouped(xs, w3)
        ys = grouped(jax.nn.silu(h1) * h3, w2)
    with jax.named_scope("moe_router"):
        return _combine(jnp.where(valid, ys, 0), wk, idx, at)


def _cast(trained, dtype):
    """`trained` with the expert matrices, float32 parameters, in the
    products' dtype."""
    with jax.named_scope("moe_experts"):
        return trained[:2] + tuple(w.astype(dtype) for w in trained[2:])


def _overflows(rows, sort):
    """Whether a step's held pairs number more than the first pass takes."""
    return jnp.sum(sort[2]) > rows


def _rest_rows(rows, trained, sort):
    return _expert_rows(rows, sort[0].shape[0], trained, sort)


def _add_rest(rows, dtype, out, trained, sort):
    with jax.named_scope("moe_router"):
        return jax.lax.cond(
            _overflows(rows, sort),
            lambda o: o + _rest_rows(rows, _cast(trained, dtype), sort),
            lambda o: o, out)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _experts(rows, dtype, trained, sort):
    """`_expert_rows` over every sorted row: the first `rows` always, the
    rest only in a step whose held pairs number more. The second pass saves
    nothing: its backward recomputes it from the arguments and adds to the
    first pass's gradients in place, so a step that does not take it
    carries no buffer of its size. The expert matrices come as the float32
    parameters and each pass casts them itself, so that their gradients
    cross the backward's `cond` in the products' dtype, as the grouped
    products give them, and become float32 after it: crossing it as
    float32 they made XLA lay the float32 expert parameters of the whole
    client loop out the other way round beside copies the usual way (the
    round program's temporaries, compiled for a described v5e: 11.8 GB,
    8.1 GB this way, 7.4 GB before the buffer was cut)."""
    out = _expert_rows(0, rows, _cast(trained, dtype), sort)
    return _add_rest(rows, dtype, out, trained, sort)


def _experts_fwd(rows, dtype, trained, sort):
    out, first_vjp = jax.vjp(lambda t: _expert_rows(0, rows, t, sort),
                             _cast(trained, dtype))
    return (_add_rest(rows, dtype, out, trained, sort),
            (first_vjp, trained, sort))


def _experts_bwd(rows, dtype, res, g):
    first_vjp, trained, sort = res

    def with_rest(grads):
        _, rest_vjp = jax.vjp(lambda t: _rest_rows(rows, t, sort),
                              _cast(trained, dtype))
        return jax.tree_util.tree_map(jnp.add, grads, rest_vjp(g)[0])

    with jax.named_scope("moe_router"):
        grads = jax.lax.cond(_overflows(rows, sort), with_rest,
                             lambda grads: grads, first_vjp(g)[0])
    return tuple(d.astype(t.dtype) for d, t in zip(grads, trained)), None


_experts.defvjp(_experts_fwd, _experts_bwd)


def sparse_ffn(p, x, sp, bias, dtype):
    """(partial output of the held experts, [experts_held + 1] pairs routed
    to each held expert and, last, to experts not held here). `bias`
    [n_experts] is added to the scores for selection only; None: no bias."""
    e_held, top_k = sp.experts_held, sp.top_k
    shape = x.shape
    x = x.reshape(-1, shape[-1])
    n = x.shape[0]
    rows = dispatch_rows(sp, n)
    with jax.named_scope("moe_router"):
        logits = jnp.dot(x.astype(jnp.float32), p["gate"],
                         precision=jax.lax.Precision.HIGHEST)
        s = jax.nn.sigmoid(logits)
        pick = s if bias is None else s + bias
        _, sel = jax.lax.top_k(pick, top_k)              # [n, k]
        w = jnp.take_along_axis(s, sel, axis=-1)
        if sp.norm_topk:
            w = w / (jnp.sum(w, axis=-1, keepdims=True) + sp.topk_eps)
        w = w * sp.routed_scale
        local = sel - sp.expert_offset
        held = (local >= 0) & (local < e_held)
        # pairs sorted by held expert, those of absent experts last. Pair
        # j * n + t is token t's j-th expert: what is gathered back by pair
        # is then k slabs of [n, d] to add, in the layout the rows have
        key = jnp.where(held, local, e_held).T.reshape(-1)
        order = jnp.argsort(key, stable=True)
        inv = jnp.argsort(order)
        counts = jnp.sum(key[:, None] == jnp.arange(e_held + 1)[None, :],
                         axis=0, dtype=jnp.int32)
        wk = jnp.where(held, w, 0.0).T.astype(dtype)     # [k, n]
    trained = (x.astype(dtype), wk, p["experts_w1"], p["experts_w3"],
               p["experts_w2"])
    sort = (order, inv, counts[:e_held])
    if rows == n * top_k:     # every pair has its row: one pass is all
        out = _expert_rows(0, rows, _cast(trained, dtype), sort)
    else:
        out = _experts(rows, dtype, trained, sort)
    return out.reshape(shape), counts
