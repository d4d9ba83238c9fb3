"""The TPU lowering of `gdn_moe.chunked_delta_rule`: the chunked gated delta
rule as one Pallas (Mosaic) kernel a direction, under one `custom_vjp`.

The forward kernel walks a sequence's chunks in order, the state S of each
value head in VMEM scratch from the first chunk to the last. Per chunk it
forms the decays, `K K^T`, the unit lower triangular inverse, `W`, `U0` and
the intra-chunk products in VMEM and writes only the output, so no chunk
tensor, no repeated key head and no transposed copy of an input reaches HBM.
Differentiated, the same kernel also writes its residuals: the state at
each chunk's start (256 MiB a layer at the Qwen3-Next cell's shape) and the
chunk's inverses (64 MiB), which `gdn()`'s checkpoint keeps (`SAVED`). The
backward kernel takes the chunks in reverse with dS in VMEM scratch, reads
both, forms the chunk's other parts again (the inverse is most of a
chunk's time) and takes the gradient through them down to q, k, v, g and
beta.

A grid step is one (sequence, key head, chunk), or several key heads
(`Plan.key_heads`). The r value heads that read a key head are stacked:
their chunks are the r diagonal blocks of [r C, r C] tiles (128 wide at
r = 2, C = 64, the MXU's width), so one product serves every head where
the state does not differ between them (K K^T, Q K^T, the inverse, W, U0,
P u) and only the products with S are a head's own. q and k are read at
their key head straight from the projections' `[B, T, H * d]` layout, g
and beta a key head's whole sequence at a time (`[B, Hk, N, r C]`, a few
KiB).

The arithmetic is `chunked_delta_rule`'s, cast for cast: every product takes
`dtype` operands with float32 accumulation, the state included; the state,
decays, cumulative sums, inverse, beta and g are float32, the inverse's
products at float32 precision; the output is float32. The inverse is
`gdn_moe.inverse_by_halves` written on the whole tile: the diagonal blocks'
inverses of width b, held in one matrix X, become those of width 2b as `X -
X (M_b * A) X`, M_b the mask of the lower-left block inside each block of
2b, so no slice of a tile is ever taken. What the kernel does not take
(widths that are no multiple of 128 lanes, any program not built for the
TPU) runs `chunked_delta_rule` itself: `plan` says which, from the platform
and shapes alone."""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from defending_against_backdoors_with_robust_learning_rate_tpu.models import (
    attention_kernel)

LANES = 128
F32 = jnp.float32
HIGHEST, DEFAULT = jax.lax.Precision.HIGHEST, jax.lax.Precision.DEFAULT
KEY_HEADS = 4


class Plan(NamedTuple):
    """How the kernel cuts a sequence: tokens a chunk, chunks, and the most
    key heads a grid step takes (with the value heads that read them)."""
    chunk: int
    chunks: int
    key_heads: int = KEY_HEADS


def plan(t: int, dk: int, dv: int, chunk: int):
    """The Plan the kernel runs a sequence of `t` tokens by, key and value
    heads of `dk` and `dv`, or None where it does not take it (programs
    not built for the TPU; widths that are no whole number of lanes; a
    chunk that is no power of two, which the masks' shifts need)."""
    if not attention_kernel.on_tpu() or dk % LANES or dv % LANES \
            or chunk & (chunk - 1):
        return None
    return Plan(chunk, -(-t // chunk))


def _dot(x, y, dtype, dims):
    """A product of `x` and `y` cast to `dtype`, float32 accumulation;
    float32 operands at float32 precision."""
    precision = HIGHEST if dtype == F32 else DEFAULT
    return jax.lax.dot_general(x.astype(dtype), y.astype(dtype),
                               (dims, ((), ())), precision=precision,
                               preferred_element_type=F32)


def _mm(x, y, dtype):                          # x @ y
    return _dot(x, y, dtype, ((1,), (0,)))


def _mm_t(x, y, dtype):                        # x @ y^T
    return _dot(x, y, dtype, ((1,), (1,)))


def _t_mm(x, y, dtype):                        # x^T @ y
    return _dot(x, y, dtype, ((0,), (0,)))


def _grid(c):
    """Row and column indices of a [c, c] tile."""
    return (jax.lax.broadcasted_iota(jnp.int32, (c, c), 0),
            jax.lax.broadcasted_iota(jnp.int32, (c, c), 1))


def _col(row, eye):
    """[1, c] -> [c, 1] through the diagonal (exact: sums of zeros)."""
    return jnp.sum(jnp.where(eye, row, 0.0), axis=1, keepdims=True)


def _row(col, eye):
    """[c, 1] -> [1, c]."""
    return jnp.sum(jnp.where(eye, col, 0.0), axis=0, keepdims=True)


def unit_lower_inverse(a, block=None):
    """`(I + a)^-1` of a strictly lower triangular [c, c] float32 tile by
    halves on the whole tile (the module's docstring): log2(block) levels,
    the first without a product. With `block`, a is block-diagonal in
    blocks of that many rows (several heads' chunks stacked), and so is the
    inverse."""
    c = a.shape[0]
    rows, cols = _grid(c)
    x = jnp.where(rows == cols, 1.0, 0.0)
    b = 1
    while b < (block or c):
        shift = b.bit_length() - 1
        below = ((rows >> (shift + 1)) == (cols >> (shift + 1))) & (
            (rows >> shift) != (cols >> shift))
        m = jnp.where(below, a, 0.0)
        x = x - m if b == 1 else x - _mm(_mm(x, m, F32), x, F32)
        b *= 2
    return x


class _Stack(NamedTuple):
    """The state-free parts of one chunk for the r value heads that read
    one key head, as `chunked_delta_rule` forms them, stacked: head i's
    rows are i C to (i + 1) C, and the [R, R] tiles (R = r C) are block
    diagonal."""
    c: int                # tokens a chunk
    same: jax.Array       # [R, R] the block-diagonal mask
    eye: jax.Array        # [R, R]
    beta: jax.Array       # [R, 1]
    e: jax.Array          # [R, 1] exp(G), G the cumulative sum of g
    e_last: jax.Array     # [R, 1] exp(G_C) of the row's head
    to_end: jax.Array     # [R, 1] exp(G_C - G)
    D: jax.Array          # [R, R] exp(G_i - G_j), j <= i
    X: jax.Array          # [R, R] (I + A)^-1, float32
    kk: jax.Array         # [R, R] K K^T, float32
    qk: jax.Array         # [R, R] Q K^T, float32
    q: jax.Array          # [R, dk] Q of each head's rows, dtype
    k: jax.Array          # [R, dk] K, dtype
    v: jax.Array          # [R, dv] each head's V, dtype
    kb: jax.Array         # [R, dk] beta exp(G) K, dtype
    vb: jax.Array         # [R, dv] beta V, dtype
    w: jax.Array          # [R, dk] X kb, dtype
    u0: jax.Array         # [R, dv] X vb, float32
    p: jax.Array          # [R, R] (Q K^T * D), dtype
    qg: jax.Array         # [R, dk] exp(G) Q, dtype
    kd: jax.Array         # [R, dk] exp(G_C - G) K, dtype

    def rows(self, i):
        return slice(i * self.c, (i + 1) * self.c)


def _stack(q, k, vs, g_row, b_row, dtype, xcat=None):
    """q, k [C, dk] of one key head and the [C, dv] values of the r value
    heads that read it, in `dtype`; g_row, b_row [1, R] their g and beta
    head after head; the heads' inverses side by side ([C, R], see
    `_side_by_side`) when they were formed before."""
    c, r = k.shape[0], len(vs)
    rows, cols = _grid(r * c)
    eye = rows == cols
    shift = c.bit_length() - 1
    same = (rows >> shift) == (cols >> shift)
    causal = same & (cols <= rows)
    G = jnp.sum(jnp.where(causal, g_row, 0.0), axis=1, keepdims=True)
    G_row = _row(G, eye)
    last = jnp.sum(jnp.where(same & ((cols & (c - 1)) == c - 1), G_row,
                             0.0), axis=1, keepdims=True)
    beta = _col(b_row, eye)
    q, k = (jnp.concatenate([x] * r, axis=0) for x in (q, k))
    v = jnp.concatenate(vs, axis=0)
    kk, qk = _mm_t(k, k, dtype), _mm_t(q, k, dtype)
    # masked before exponentiated, so that no exponent is positive
    D = jnp.exp(jnp.where(causal, G - G_row, -jnp.inf))
    if xcat is None:
        X = unit_lower_inverse(
            jnp.where(same & (cols < rows), beta * kk * D, 0.0), block=c)
    else:
        X = jnp.where(same, jnp.concatenate([xcat] * r, axis=0), 0.0)
    e = jnp.exp(G)
    to_end = jnp.exp(last - G)
    kf = k.astype(F32)
    kb = ((beta * e) * kf).astype(dtype)
    vb = (beta * v.astype(F32)).astype(dtype)
    return _Stack(
        c=c, same=same, eye=eye, beta=beta, e=e, e_last=jnp.exp(last),
        to_end=to_end, D=D, X=X, kk=kk, qk=qk, q=q, k=k, v=v, kb=kb, vb=vb,
        w=_mm(X, kb, dtype).astype(dtype), u0=_mm(X, vb, dtype),
        p=(qk * D).astype(dtype), qg=(e * q.astype(F32)).astype(dtype),
        kd=(to_end * kf).astype(dtype))


def _side_by_side(st: _Stack, r: int):
    """The heads' [C, C] inverses side by side, [C, R]: the block-diagonal
    X's row blocks summed (each is zero outside its own head's lanes)."""
    return sum(st.X[st.rows(i)] for i in range(r))


def _key_heads(q_ref, v_ref, kh, r):
    """Per key head of a grid step: (its index in the step, its q / k
    lanes, and per value head that reads it: its index in the step and its
    v / o lanes)."""
    dk, dv = q_ref.shape[-1] // kh, v_ref.shape[-1] // (kh * r)
    for a in range(kh):
        yield a, slice(a * dk, (a + 1) * dk), [
            (h, slice(h * dv, (h + 1) * dv)) for h in range(a * r, (a + 1) * r)]


def _forward_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, o_ref, *rest, dtype,
                    kh, r, residuals):
    """One chunk of a step's key heads and their value heads, in order, S
    carried in VMEM scratch: the output and, with `residuals`, the state
    at the chunk's start and the chunk's inverses."""
    st_ref, x_ref, s_ref = rest if residuals else (None, None) + rest
    n = pl.program_id(2)

    @pl.when(n == 0)
    def _():
        s_ref[...] = jnp.zeros(s_ref.shape, F32)

    # every key head's state-free parts first: independent chains of
    # products the scheduler may interleave, ahead of the first store
    stacks = [(a, heads, _stack(q_ref[0, :, kl], k_ref[0, :, kl],
                                [v_ref[0, :, vl] for _h, vl in heads],
                                g_ref[0, a, pl.ds(n, 1), :],
                                b_ref[0, a, pl.ds(n, 1), :], dtype))
              for a, kl, heads in _key_heads(q_ref, v_ref, kh, r)]
    for a, heads, st in stacks:
        s = [s_ref[h] for h, _vl in heads]
        s_b = [x.astype(dtype) for x in s]
        u_b = jnp.concatenate(
            [(st.u0[st.rows(i)] - _mm(st.w[st.rows(i)], s_b[i], dtype))
             .astype(dtype) for i in range(r)], axis=0)
        pu = _mm(st.p, u_b, dtype)
        for i, (h, vl) in enumerate(heads):
            rows = st.rows(i)
            o_ref[0, :, vl] = _mm(st.qg[rows], s_b[i], dtype) + pu[rows]
            if residuals:
                st_ref[0, h, 0] = s[i]
            s_ref[h] = (st.e_last[i * st.c:i * st.c + 1] * s[i]
                        + _t_mm(st.kd[rows], u_b[rows], dtype))
        if residuals:
            x_ref[0, a, 0] = _side_by_side(st, r)


def _backward_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, st_ref, x_ref, do_ref,
                     dq_ref, dk_ref, dv_ref, dg_ref, db_ref, ds_ref, *,
                     dtype, kh, r, chunks):
    """One chunk of a step's key heads and their value heads, last chunk
    first: the gradient through the chunk's parts (the inverses read, the
    rest formed again), dS carried in VMEM scratch."""
    n = chunks - 1 - pl.program_id(2)

    @pl.when(n == chunks - 1)
    def _():
        ds_ref[...] = jnp.zeros(ds_ref.shape, F32)

    for a, kl, heads in _key_heads(q_ref, v_ref, kh, r):
        st = _stack(q_ref[0, :, kl], k_ref[0, :, kl],
                    [v_ref[0, :, vl] for _h, vl in heads],
                    g_ref[0, a, pl.ds(n, 1), :], b_ref[0, a, pl.ds(n, 1), :],
                    dtype, xcat=x_ref[0, a, 0])
        c = st.c
        rows, cols = _grid(r * c)

        def stacked(xs):
            return jnp.concatenate(xs, axis=0)

        s = [st_ref[0, h, 0] for h, _vl in heads]
        ds = [ds_ref[h] for h, _vl in heads]
        s_b = [x.astype(dtype) for x in s]
        ds_b = [x.astype(dtype) for x in ds]
        u_b = stacked([(st.u0[st.rows(i)]
                        - _mm(st.w[st.rows(i)], s_b[i], dtype)).astype(dtype)
                       for i in range(r)])
        do_b = stacked([do_ref[0, :, vl] for _h, vl in heads]).astype(dtype)
        # through the state-dependent step: u = u0 - W S, o = Qg S + P u,
        # S' = e_C S + Kd^T u, a head at a time where S is its own
        du = _t_mm(st.p, do_b, dtype) + stacked(
            [_mm(st.kd[st.rows(i)], ds_b[i], dtype) for i in range(r)])
        du_b = du.astype(dtype)
        for i, (h, _vl) in enumerate(heads):
            rs = st.rows(i)
            ds_ref[h] = (_t_mm(st.qg[rs], do_b[rs], dtype)
                         + st.e_last[i * c:i * c + 1] * ds[i]
                         - _t_mm(st.w[rs], du_b[rs], dtype))
        dp = _mm_t(do_b, u_b, dtype)
        dqg = stacked([_mm_t(do_b[st.rows(i)], s_b[i], dtype)
                       for i in range(r)])
        dkd = stacked([_mm_t(u_b[st.rows(i)], ds_b[i], dtype)
                       for i in range(r)])
        dw_b = stacked([-_mm_t(du_b[st.rows(i)], s_b[i], dtype)
                        for i in range(r)]).astype(dtype)
        # through W = X kb, u0 = X vb and X = (I + A)^-1
        dx = _mm_t(dw_b, st.kb, dtype) + _mm_t(du_b, st.vb, dtype)
        x_b = st.X.astype(dtype)
        dkb = _t_mm(x_b, dw_b, dtype)
        dvb = _t_mm(x_b, du_b, dtype)
        da = jnp.where(st.same & (cols < rows),
                       -_mm_t(_t_mm(st.X, dx, F32), st.X, F32), 0.0)
        # through P = Q K^T * D and A = beta K K^T * D (D is zero off the
        # heads' blocks, so what dp and dx hold there goes no further)
        dpd = (dp * st.D).astype(dtype)
        dkk = (da * st.beta * st.D).astype(dtype)
        dq = _mm(dpd, st.k, dtype)
        dk = (_t_mm(dpd, st.q, dtype) + _mm(dkk, st.k, dtype)
              + _t_mm(dkk, st.k, dtype))
        dd = dp * st.qk + da * st.beta * st.kk
        dbeta = jnp.sum(da * st.kk * st.D, axis=1, keepdims=True)
        # through qg = e Q, kb = beta e K, vb = beta V, kd = exp(G_C - G) K
        qf, kf = st.q.astype(F32), st.k.astype(F32)
        dq += st.e * dqg
        de = jnp.sum(dqg * qf, axis=1, keepdims=True)
        t = jnp.sum(dkb * kf, axis=1, keepdims=True)
        dk += (st.beta * st.e) * dkb + st.to_end * dkd
        dbeta += st.e * t + jnp.sum(dvb * st.v.astype(F32), axis=1,
                                    keepdims=True)
        de += st.beta * t
        dv = st.beta * dvb
        for i, (_h, vl) in enumerate(heads):
            dv_ref[0, :, vl] = dv[st.rows(i)].astype(dv_ref.dtype)
        to_end = st.to_end * jnp.sum(dkd * kf, axis=1, keepdims=True)
        # back to G: e = exp(G), e_C, exp(G_C - G) and the decays; e_C and
        # exp(G_C - G) reach G_C, the last row of each head's block
        ddd = dd * st.D
        dG = (st.e * de - to_end + jnp.sum(ddd, axis=1, keepdims=True)
              - _col(jnp.sum(ddd, axis=0, keepdims=True), st.eye))
        block = rows[:, :1] >> (c.bit_length() - 1)
        state = sum(jnp.where(block == i, jnp.sum(
            jnp.sum(s[i] * ds[i], axis=1, keepdims=True), axis=0,
            keepdims=True), 0.0) for i in range(r))
        d_last = (jnp.sum(jnp.where(st.same, _row(to_end, st.eye), 0.0),
                          axis=1, keepdims=True) + st.e_last * state)
        dG += jnp.where((rows[:, :1] & (c - 1)) == c - 1, d_last, 0.0)
        # and through the cumulative sum: dg_j = the sum of dG_i over the
        # i >= j of j's head
        dg_ref[0, a, pl.ds(n, 1), :] = jnp.sum(
            jnp.where(st.same & (rows >= cols), dG, 0.0), axis=0,
            keepdims=True)
        db_ref[0, a, pl.ds(n, 1), :] = _row(dbeta, st.eye)
        dq_ref[0, :, kl] = sum(dq[st.rows(i)]
                               for i in range(r)).astype(dq_ref.dtype)
        dk_ref[0, :, kl] = sum(dk[st.rows(i)]
                               for i in range(r)).astype(dk_ref.dtype)


def _specs(g, q, v, kh, r, reverse=False):
    """The grid (sequence, `kh` key heads, chunk) and the block specs of q /
    k, v / o, g / beta, the states and the inverses, chunks in order or
    reversed."""
    b, hk, n, rc = g.shape
    c = rc // r
    dk, dv = q.shape[-1] // hk, v.shape[-1] // (hk * r)

    def at(i):
        return n - 1 - i if reverse else i
    return ((b, hk // kh, n),
            pl.BlockSpec((1, c, kh * dk), lambda bi, j, i: (bi, at(i), j)),
            pl.BlockSpec((1, c, kh * r * dv),
                         lambda bi, j, i: (bi, at(i), j)),
            pl.BlockSpec((1, kh, n, rc), lambda bi, j, i: (bi, j, 0, 0)),
            pl.BlockSpec((1, kh * r, 1, dk, dv),
                         lambda bi, j, i: (bi, j, at(i), 0, 0)),
            pl.BlockSpec((1, kh, 1, c, rc),
                         lambda bi, j, i: (bi, j, at(i), 0, 0)))


def _params():
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"))


def _forward(q, k, v, g, beta, *, dtype, kh, r, residuals, interpret):
    """q, k [B, T, Hk * dk], v [B, T, Hv * dv], g, beta [B, Hk, N, r C]
    (the r value heads of a key head side by side) -> o [B, T, Hv * dv]
    float32 and, with `residuals`, the state at each chunk's start [B, Hv,
    N, dk, dv] and each chunk's inverses [B, Hk, N, C, r C], float32."""
    grid, qk_spec, v_spec, g_spec, s_spec, x_spec = _specs(g, q, v, kh, r)
    b, hk, n, rc = g.shape
    dk, dv = s_spec.block_shape[-2:]
    out_shape = [jax.ShapeDtypeStruct(v.shape, F32)]
    out_specs = [v_spec]
    if residuals:
        out_shape += [jax.ShapeDtypeStruct((b, hk * r, n, dk, dv), F32),
                      jax.ShapeDtypeStruct((b, hk, n, rc // r, rc), F32)]
        out_specs += [s_spec, x_spec]
    return pl.pallas_call(
        functools.partial(_forward_kernel, dtype=dtype, kh=kh, r=r,
                          residuals=residuals),
        grid=grid, out_shape=out_shape,
        in_specs=[qk_spec, qk_spec, v_spec, g_spec, g_spec],
        out_specs=out_specs,
        scratch_shapes=[pltpu.VMEM((kh * r, dk, dv), F32)],
        compiler_params=_params(), interpret=interpret,
        name="gdn_forward_res" if residuals else "gdn_forward")(
            q, k, v, g, beta)


def _backward(q, k, v, g, beta, s, x, do, *, dtype, kh, r, interpret):
    grid, qk_spec, v_spec, g_spec, s_spec, x_spec = _specs(
        g, q, v, kh, r, reverse=True)
    dk, dv = s_spec.block_shape[-2:]
    return pl.pallas_call(
        functools.partial(_backward_kernel, dtype=dtype, kh=kh, r=r,
                          chunks=g.shape[2]),
        grid=grid,
        out_shape=(jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype),
                   jax.ShapeDtypeStruct(g.shape, F32),
                   jax.ShapeDtypeStruct(beta.shape, F32)),
        in_specs=[qk_spec, qk_spec, v_spec, g_spec, g_spec, s_spec, x_spec,
                  v_spec],
        out_specs=(qk_spec, qk_spec, v_spec, g_spec, g_spec),
        scratch_shapes=[pltpu.VMEM((kh * r, dk, dv), F32)],
        compiler_params=_params(), interpret=interpret,
        name="gdn_backward")(q, k, v, g, beta, s, x, do)


# the residuals' names, which `gdn()`'s checkpoint keeps (SAVED) rather
# than forming the forward a third time in the backward
SAVED = jax.checkpoint_policies.save_only_these_names("gdn_states",
                                                      "gdn_inverses")


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8))
def _rule(q, k, v, g, beta, dtype, kh, r, interpret):
    o, = _forward(q, k, v, g, beta, dtype=dtype, kh=kh, r=r,
                  residuals=False, interpret=interpret)
    return o


def _rule_fwd(q, k, v, g, beta, dtype, kh, r, interpret):
    o, s, x = _forward(q, k, v, g, beta, dtype=dtype, kh=kh, r=r,
                       residuals=True, interpret=interpret)
    return o, (q, k, v, g, beta, checkpoint_name(s, "gdn_states"),
               checkpoint_name(x, "gdn_inverses"))


def _rule_bwd(dtype, kh, r, interpret, res, do):
    return _backward(*res, do, dtype=dtype, kh=kh, r=r, interpret=interpret)


_rule.defvjp(_rule_fwd, _rule_bwd)


def chunked_delta_rule(q, k, v, g, beta, dtype, took: Plan,
                       interpret: bool = False):
    """`gdn_moe.chunked_delta_rule`'s contract through the kernels: q, k
    [B, T, Hk, dk], v [B, T, Hv, dv] in `dtype`, g, beta [B, T, Hv]
    float32 -> o [B, T, Hv, dv] float32. A length the chunk does not
    divide is padded with beta = 0 and g = 0, which leave S as it is."""
    b, t, hk, dk = q.shape
    hv, dv = v.shape[2:]
    c, n, most = took
    kh = max(d for d in range(1, most + 1) if hk % d == 0)
    pad = ((0, 0), (0, n * c - t))

    def lanes(x):                    # [B, T, H, d] -> [B, N C, H d]
        return jnp.pad(x, pad + ((0, 0),) * 2).reshape(b, n * c, -1)

    def rows(x):                     # [B, T, Hv] -> [B, Hk, N, r C]
        x = jnp.pad(x, pad + ((0, 0),)).reshape(b, n, c, hk, hv // hk)
        return x.transpose(0, 3, 1, 4, 2).reshape(b, hk, n, hv // hk * c)

    o = _rule(lanes(q), lanes(k), lanes(v), rows(g), rows(beta), dtype,
              kh, hv // hk, interpret)
    return o.reshape(b, n * c, hv, dv)[:, :t]
