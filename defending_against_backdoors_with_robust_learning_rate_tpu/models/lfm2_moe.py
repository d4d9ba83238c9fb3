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
  expert) pair is dropped: pairs are sorted by expert and the held experts'
  products are three `jax.lax.ragged_dot` calls over the sorted rows.

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


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _take_rows(x, idx, inv, k):
    """x[idx // k] for `idx` a permutation of range(len(x) * k) with
    inverse `inv`: backward is a gather through `inv` and a sum over the k
    copies, where autodiff would scatter-add."""
    return jnp.take(x, idx // k, axis=0)


def _take_rows_fwd(x, idx, inv, k):
    return _take_rows(x, idx, inv, k), (inv, x.shape[0])


def _take_rows_bwd(k, res, g):
    inv, n = res
    back = jnp.take(g, inv, axis=0).reshape((n, k) + g.shape[1:])
    return jnp.sum(back, axis=1), None, None


_take_rows.defvjp(_take_rows_fwd, _take_rows_bwd)


def _permute_rows(x, idx, inv):
    return _take_rows(x, idx, inv, 1)


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


def sparse_ffn(p, x, sp: LMSpec, src_layer: int, dtype):
    """(partial output of the held experts, [experts_held + 1] pairs routed
    to each held expert and, last, to experts not held here)."""
    e_held, top_k = sp.experts_held, sp.top_k
    shape = x.shape
    x = x.reshape(-1, shape[-1])
    n = x.shape[0]
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
        # pairs sorted by held expert, those of absent experts last
        key = jnp.where(held, local, e_held).reshape(-1)
        order = jnp.argsort(key, stable=True)
        inv = jnp.argsort(order)
        counts = jnp.sum(key[:, None] == jnp.arange(e_held + 1)[None, :],
                         axis=0, dtype=jnp.int32)
        sizes = counts[:e_held]
        # rows past the held pairs belong to no group: a grouped product
        # leaves them undefined, so they are zeroed going in and coming out
        valid = (jnp.arange(n * top_k) < jnp.sum(sizes))[:, None]
        xs = jnp.where(valid, _take_rows(x.astype(dtype), order, inv, top_k),
                       0)
    with jax.named_scope("moe_experts"):
        # bfloat16 operands go at the default precision whatever the
        # process-wide setting: the TPU's grouped product refuses them at
        # float32 precision
        grouped = functools.partial(
            jax.lax.ragged_dot, group_sizes=sizes,
            precision=(jax.lax.Precision.DEFAULT if dtype == jnp.bfloat16
                       else None))
        h1 = grouped(xs, p["experts_w1"].astype(dtype))
        h3 = grouped(xs, p["experts_w3"].astype(dtype))
        ys = grouped(jax.nn.silu(h1) * h3, p["experts_w2"].astype(dtype))
    with jax.named_scope("moe_router"):
        ys = jnp.where(valid, ys, 0)
        y = _permute_rows(ys, inv, order).reshape(n, top_k, shape[-1])
        wk = jnp.where(held, w, 0.0).astype(dtype)
        out = jnp.sum(y * wk[:, :, None], axis=1)
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

    def build_counters(self):
        """Counted once when an engine is built (obs/spans.py)."""
        return {"experts_held": self.spec.experts_held,
                "vocab_held": self.spec.vocab_held}

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
