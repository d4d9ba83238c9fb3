"""`lfm2_moe`: gated short convolutions, grouped-query attention and a
sigmoid-routed sparse feed-forward, as a federated client's model.

Widths come from one file of published values (`lfm2_8b_a1b.json` beside
this module, or the file `--lm_config` names); flags state only the cut
(`LMSpec`): which of the source's layers are held, how many of a sparse
layer's experts live here and from which offset, and how many rows of the
vocabulary. Equations as the source's `lfm2_moe` implementation has them:

- RMSNorm `x * rsqrt(mean(x^2) + eps) * w`; block `h = x + op(norm(x));
  y = h + ffn(norm(h))`; a final norm, and the head is the embedding.
- `conv` operator: `B, C, X = split3(x W_in)`; `y = (C * conv1d(B * X)) W_out`
  with a causal depthwise kernel of `conv_L_cache` taps and no bias.
- `full_attention`: q, k, v without bias, RMSNorm over each head of q and k,
  rotary embedding over the whole head (rotate-half), causal softmax
  attention, each key-value head serving `heads / kv_heads` query heads.
- dense feed-forward `W2(silu(W1 x) * W3 x)`.
- sparse feed-forward: `s = sigmoid(x W_g)` in float32 over ALL published
  experts, `sel = top_k(s + b)` with the expert bias `b` (a buffer: a fixed
  function of layer and expert, outside the trained and voted tree),
  `w = s[sel] / (sum s[sel] + 1e-6)`, and of `y = sum_e w_e expert_e(x)` the
  terms whose expert is held here. That partial sum goes on to the next
  layer; nothing stands in for the experts of other chips. No (token,
  expert) pair is dropped: pairs are sorted by expert, those held first,
  and the held experts' products are three `jax.lax.ragged_dot` calls over
  the first `dispatch_rows` sorted rows, twice the share of the pairs that
  the held experts draw in expectation; a step that holds more computes
  the rest in a second pass under a `jax.lax.cond`.

Precision (`--dtype bf16`): parameters stay float32; matrix products take
bfloat16 operands; router logits, sigmoid, softmax, norms and the logits
are float32. Named scopes tell the parts apart in a device trace:
`short_conv`, `attention`, `moe_router`, `moe_experts`, `dense_ffn`,
`lm_head`."""

from __future__ import annotations

import dataclasses
import functools
import json
import os
from typing import Any, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

_HERE = os.path.dirname(os.path.abspath(__file__))
PUBLISHED = {"lfm2-8b-a1b": os.path.join(_HERE, "lfm2_8b_a1b.json")}
ATTN_QUERY_BLOCK = 512     # attention runs over query blocks of this many
# the sorted buffer of a sparse layer holds MOE_ROWS_OVER_EXPECTED times the
# pairs its held experts draw when every expert draws alike, in whole tiles
# of MOE_ROWS_TILE rows (`dispatch_rows`)
MOE_ROWS_OVER_EXPECTED = 2
MOE_ROWS_TILE = 512
EXPERT_BIAS_SCALE = 0.05


@dataclasses.dataclass(frozen=True)
class LMSpec:
    """The published widths and the cut held here (hashable: a flax
    module attribute)."""
    hidden: int
    dense_ffn: int
    moe_ffn: int
    heads: int
    kv_heads: int
    head_dim: int
    conv_taps: int
    n_experts: int            # the router's width: every published expert
    top_k: int
    norm_topk: bool
    routed_scale: float
    use_expert_bias: bool
    norm_eps: float
    rope_theta: float
    init_std: float
    conv_init_std: float
    # (source layer index, "conv" | "full_attention", sparse?) per held layer
    layers: Tuple[Tuple[int, str, bool], ...]
    experts_held: int
    expert_offset: int
    vocab_held: int


@functools.lru_cache(maxsize=8)
def _load(name_or_path: str) -> dict:
    path = PUBLISHED.get(name_or_path, name_or_path)
    with open(path) as f:
        return json.load(f)


def spec_from(lm_config: str, layers: str, experts_held: int,
              expert_offset: int, vocab_held: int) -> LMSpec:
    """The spec of one cut. `layers` is a comma list of the source's layer
    indices ("" = all); 0 experts or rows held = all of them."""
    pub = _load(lm_config)
    assumed = pub.get("assumed", {})
    n_layers = int(pub["num_hidden_layers"])
    held = ([int(t) for t in layers.split(",") if t.strip()]
            if layers else list(range(n_layers)))
    if not held or any(not 0 <= i < n_layers for i in held) \
            or sorted(set(held)) != held:
        raise ValueError(
            f"--lm_layers must be ascending source layer indices in "
            f"[0, {n_layers}), got {layers!r}")
    n_experts = int(pub["num_experts"])
    e_held = experts_held or n_experts
    if not (0 < e_held <= n_experts and 0 <= expert_offset
            and expert_offset + e_held <= n_experts):
        raise ValueError(
            f"--lm_experts_held {experts_held} from --lm_expert_offset "
            f"{expert_offset} does not lie inside the {n_experts} experts "
            f"the source has")
    vocab = int(pub["vocab_size"])
    v_held = vocab_held or vocab
    if not 0 < v_held <= vocab:
        raise ValueError(f"--lm_vocab_held {vocab_held} is not in "
                         f"(0, {vocab}]")
    heads = int(pub["num_attention_heads"])
    return LMSpec(
        hidden=int(pub["hidden_size"]),
        dense_ffn=int(pub["intermediate_size"]),
        moe_ffn=int(pub["moe_intermediate_size"]),
        heads=heads, kv_heads=int(pub["num_key_value_heads"]),
        head_dim=int(assumed.get("head_dim", pub["hidden_size"] // heads)),
        conv_taps=int(pub["conv_L_cache"]),
        n_experts=n_experts, top_k=int(pub["num_experts_per_tok"]),
        norm_topk=bool(pub["norm_topk_prob"]),
        routed_scale=float(pub["routed_scaling_factor"]),
        use_expert_bias=bool(pub["use_expert_bias"]),
        norm_eps=float(pub["norm_eps"]),
        rope_theta=float(pub["rope_theta"]),
        init_std=float(assumed.get("initializer_range", 0.02)),
        conv_init_std=float(assumed.get("conv_init_std", 3 ** -0.5)),
        layers=tuple((i, str(pub["layer_types"][i]),
                      i >= int(pub["num_dense_layers"])) for i in held),
        experts_held=e_held, expert_offset=expert_offset,
        vocab_held=v_held)


def spec_from_cfg(cfg) -> LMSpec:
    return spec_from(cfg.lm_config, cfg.lm_layers, cfg.lm_experts_held,
                     cfg.lm_expert_offset, cfg.lm_vocab_held)


def vocab_from_cfg(cfg) -> int:
    return spec_from_cfg(cfg).vocab_held


def from_cfg(cfg, dtype=jnp.float32, remat: bool = False) -> "LFM2MoE":
    """What models/registry.get_model builds for this arch."""
    return LFM2MoE(spec=spec_from_cfg(cfg), dtype=dtype, remat=remat)


def expert_bias(spec: LMSpec, src_layer: int) -> np.ndarray:
    """The routing bias of one sparse layer: a buffer that selection reads
    and no gradient reaches, a fixed small non-zero function of the source's
    layer index and the expert (the source trains it by a load-balancing
    rule this system does not run)."""
    e = np.arange(spec.n_experts, dtype=np.float64)
    b = EXPERT_BIAS_SCALE * np.sin(
        12.9898 * (spec.n_experts * src_layer + e) + 1.0)
    return b.astype(np.float32)


def _rms(x, w, eps):
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return y * w


def _rope(x, theta):
    """Rotate-half rotary embedding over the whole head; x [B, T, n, d]."""
    d, t = x.shape[-1], x.shape[1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    ang = jnp.concatenate([ang, ang], axis=-1)[None, :, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    rot = jnp.concatenate([-x2, x1], axis=-1)
    return x * jnp.cos(ang) + rot * jnp.sin(ang)


def causal_attention(q, k, v, q_block: int = ATTN_QUERY_BLOCK):
    """Causal softmax attention over query blocks, in plain `jax.numpy`:
    q [B, T, H, d], k and v [B, T, KV, d], H a multiple of KV. Scores and
    softmax are float32; a block's scores are recomputed in backward, so
    one block's [B, H, q_block, T] is what is live."""
    b, t, h, d = q.shape
    kv = k.shape[2]
    g = h // kv
    scale = d ** -0.5
    qb = q_block if t % q_block == 0 and t > q_block else t
    nb = t // qb
    qs = q.reshape(b, nb, qb, kv, g, d).transpose(1, 0, 2, 3, 4, 5)
    cols = jnp.arange(t)

    def block(args):
        qi, i = args
        s = jnp.einsum("bqkgd,bskd->bkgqs", qi, k,
                       preferred_element_type=jnp.float32) * scale
        rows = i * qb + jnp.arange(qb)
        s = jnp.where(rows[:, None] >= cols[None, :], s,
                      jnp.finfo(jnp.float32).min)
        p = jax.nn.softmax(s, axis=-1).astype(v.dtype)
        return jnp.einsum("bkgqs,bskd->bqkgd", p, v)

    if nb == 1:
        out = block((qs[0], 0))[None]
    else:
        out = jax.lax.map(jax.checkpoint(block), (qs, jnp.arange(nb)))
    return out.transpose(1, 0, 2, 3, 4, 5).reshape(b, t, h * d)


def dispatch_rows(sp: LMSpec, n_tokens: int) -> int:
    """Rows of the sorted buffer a sparse layer's first pass computes for
    `n_tokens` tokens: all `n_tokens * top_k` pairs where every expert is
    held, else MOE_ROWS_OVER_EXPECTED times the held experts' share."""
    worst = n_tokens * sp.top_k
    tiles = -(-MOE_ROWS_OVER_EXPECTED * worst * sp.experts_held
              // (sp.n_experts * MOE_ROWS_TILE))
    return min(worst, tiles * MOE_ROWS_TILE)


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


def short_conv(p, x, sp: LMSpec, dtype):
    """The `conv` operator on normed x [B, T, D]."""
    with jax.named_scope("short_conv"):
        b_, c_, x_ = jnp.split(_mm(x, p["conv_in_proj"], dtype), 3, axis=-1)
        bx = b_ * x_
        t = bx.shape[1]
        pad = jnp.pad(bx, ((0, 0), (sp.conv_taps - 1, 0), (0, 0)))
        wc = p["conv_weight"].astype(dtype)
        conv = sum(pad[:, j:j + t] * wc[j] for j in range(sp.conv_taps))
        return _mm(c_ * conv, p["conv_out_proj"], dtype)


def attention(p, x, sp: LMSpec, dtype):
    """The `full_attention` operator on normed x [B, T, D]."""
    h, kv, hd = sp.heads, sp.kv_heads, sp.head_dim
    with jax.named_scope("attention"):
        b, t = x.shape[:2]
        q = _mm(x, p["q_proj"], dtype).reshape(b, t, h, hd)
        k = _mm(x, p["k_proj"], dtype).reshape(b, t, kv, hd)
        v = _mm(x, p["v_proj"], dtype).reshape(b, t, kv, hd)
        q = _rope(_rms(q, p["q_norm"], sp.norm_eps), sp.rope_theta)
        k = _rope(_rms(k, p["k_norm"], sp.norm_eps), sp.rope_theta)
        o = causal_attention(q.astype(dtype), k.astype(dtype), v)
        return _mm(o, p["o_proj"], dtype)


def dense_ffn(p, x, dtype):
    with jax.named_scope("dense_ffn"):
        return _mm(jax.nn.silu(_mm(x, p["w1"], dtype))
                   * _mm(x, p["w3"], dtype), p["w2"], dtype)


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


def sparse_ffn(p, x, sp: LMSpec, src_layer: int, dtype):
    """(partial output of the held experts, [experts_held + 1] pairs routed
    to each held expert and, last, to experts not held here)."""
    e_held, top_k = sp.experts_held, sp.top_k
    shape = x.shape
    x = x.reshape(-1, shape[-1])
    n = x.shape[0]
    rows = dispatch_rows(sp, n)
    with jax.named_scope("moe_router"):
        logits = jnp.dot(x.astype(jnp.float32), p["gate"],
                         precision=jax.lax.Precision.HIGHEST)
        s = jax.nn.sigmoid(logits)
        pick = (s + expert_bias(sp, src_layer)) if sp.use_expert_bias else s
        _, sel = jax.lax.top_k(pick, top_k)              # [n, k]
        w = jnp.take_along_axis(s, sel, axis=-1)
        if sp.norm_topk:
            w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-6)
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


class Block(nn.Module):
    spec: LMSpec
    src_layer: int
    kind: str
    sparse: bool
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        sp, d = self.spec, self.spec.hidden

        def mat(name, shape, std=sp.init_std):
            return self.param(name, nn.initializers.normal(std), shape,
                              jnp.float32)

        def ones(name, n):
            return self.param(name, nn.initializers.ones, (n,), jnp.float32)

        p = {"operator_norm": ones("operator_norm", d),
             "ffn_norm": ones("ffn_norm", d)}
        if self.kind == "conv":
            p.update(conv_in_proj=mat("conv_in_proj", (d, 3 * d)),
                     conv_weight=mat("conv_weight", (sp.conv_taps, d),
                                     sp.conv_init_std),
                     conv_out_proj=mat("conv_out_proj", (d, d)))
        else:
            hq, hkv = sp.heads * sp.head_dim, sp.kv_heads * sp.head_dim
            p.update(q_proj=mat("q_proj", (d, hq)),
                     k_proj=mat("k_proj", (d, hkv)),
                     v_proj=mat("v_proj", (d, hkv)),
                     o_proj=mat("o_proj", (hq, d)),
                     q_norm=ones("q_norm", sp.head_dim),
                     k_norm=ones("k_norm", sp.head_dim))
        if self.sparse:
            e, f = sp.experts_held, sp.moe_ffn
            p.update(gate=mat("gate", (d, sp.n_experts)),
                     experts_w1=mat("experts_w1", (e, d, f)),
                     experts_w3=mat("experts_w3", (e, d, f)),
                     experts_w2=mat("experts_w2", (e, f, d)))
        else:
            f = sp.dense_ffn
            p.update(w1=mat("w1", (d, f)), w3=mat("w3", (d, f)),
                     w2=mat("w2", (f, d)))
        y = _rms(x, p["operator_norm"], sp.norm_eps).astype(self.dtype)
        y = (short_conv(p, y, sp, self.dtype) if self.kind == "conv"
             else attention(p, y, sp, self.dtype))
        h = x + y.astype(x.dtype)
        z = _rms(h, p["ffn_norm"], sp.norm_eps).astype(self.dtype)
        if self.sparse:
            z, counts = sparse_ffn(p, z, sp, self.src_layer, self.dtype)
        else:
            z = dense_ffn(p, z, self.dtype)
            counts = jnp.zeros((sp.experts_held + 1,), jnp.int32)
        return h + z.astype(x.dtype), counts


class LFM2MoE(nn.Module):
    spec: LMSpec
    dtype: Any = jnp.float32
    remat: bool = False       # recompute each block's activations in backward
    takes_tokens = True       # the batch is token ids (models/registry.py)

    @property
    def pairs_shape(self):
        """Shape of the (token, expert) pair counts a forward returns."""
        return (n_sparse_layers(self.spec), self.spec.experts_held + 1)

    def dispatch_rows(self, n_tokens: int) -> int:
        return dispatch_rows(self.spec, n_tokens)

    def build_counters(self, n_tokens: int):
        """Counted once when an engine is built (obs/spans.py), for a step
        of `n_tokens` tokens."""
        return {"experts_held": self.spec.experts_held,
                "vocab_held": self.spec.vocab_held,
                "moe_rows": self.dispatch_rows(n_tokens),
                "moe_rows_worst": n_tokens * self.spec.top_k}

    @nn.compact
    def __call__(self, tokens, *, train: bool = False):
        """tokens [B, T] int32 -> (logits [B, T, vocab_held] float32,
        pairs [sparse layers, experts_held + 1] int32). `train` is taken
        for the registry's calling convention; there is no dropout."""
        sp = self.spec
        embed = self.param("embed", nn.initializers.normal(sp.init_std),
                           (sp.vocab_held, sp.hidden), jnp.float32)
        x = jnp.take(embed, tokens, axis=0).astype(self.dtype)
        cls = nn.remat(Block) if self.remat else Block
        pairs = []
        for i, (src, kind, sparse) in enumerate(sp.layers):
            x, counts = cls(sp, src, kind, sparse, self.dtype,
                            name=f"layer_{i}")(x)
            if sparse:
                pairs.append(counts)
        w = self.param("final_norm", nn.initializers.ones, (sp.hidden,),
                       jnp.float32)
        with jax.named_scope("lm_head"):
            h = _rms(x, w, sp.norm_eps).astype(self.dtype)
            logits = jnp.einsum("btd,vd->btv", h, embed.astype(self.dtype),
                                preferred_element_type=jnp.float32)
        pairs = (jnp.stack(pairs) if pairs
                 else jnp.zeros((0, sp.experts_held + 1), jnp.int32))
        return logits, pairs


def n_sparse_layers(spec: LMSpec) -> int:
    return sum(1 for _i, _k, sparse in spec.layers if sparse)
