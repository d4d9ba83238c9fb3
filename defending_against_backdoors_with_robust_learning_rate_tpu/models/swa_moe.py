"""`swa_moe`: sliding-window and full attention mixed by layer, with the
query heads, the rotary embedding and the mask each by layer kind, a
per-head output gate, and a sigmoid-routed sparse feed-forward with a
shared expert: the `laguna` block, at the widths of one published file
(`laguna_xs2.json` beside this module, or the file `--lm_config` names).
Flags state only the cut (`SwaSpec`): which of the source's layers are
held, how many routed experts of a sparse layer live here and from which
offset, and how many rows of the vocabulary. Equations, as the file's keys
give them (what the file lacks is under its `assumed`):

- block `h = x + attn(rms(x)); y = h + ffn(rms(h))`, no bias anywhere; a
  final norm; an untied head.
- attention of layer l: `H_l = num_attention_heads_per_layer[l]` query
  heads over `num_key_value_heads` key-value heads of `head_dim`, head h
  reading key-value head `h // (H_l / KV)`; rotary embedding on q and k by
  layer kind (`rope_parameters[layer_types[l]]`): rotate-half over the first
  `partial_rotary_factor x head_dim` widths, the rest passed through, plain
  frequencies `theta ** (-2i / R)` or YaRN's (`yarn_inv_freq`, with its
  attention factor on cos and sin); `softmax(q k^T * head_dim^-0.5 + mask)
  v` with float32 scores, over query blocks (`token_ops.causal_attention`):
  causal on `full_attention` layers, and on `sliding_attention` layers
  query r reads the `sliding_window` keys `r - window < c <= r`; the output
  gate `g = sigmoid(z W_g)`, one scalar a head and token, on each head's
  output; `W_o`.
- `mlp_layer_types[l]` `dense`: `W2(silu(W1 x) * W3 x)`. `sparse`:
  `token_ops.sparse_ffn` (the one implementation every token model uses)
  over all `num_experts` with top-k of the sigmoid scores alone (no bias),
  the weights normalised over the selected (`+ 1e-20`) and scaled by
  `moe_routed_scaling_factor`, plus `token_ops.shared_expert`, which every
  chip of the deployment computes alike.

Precision (`--dtype bf16`): parameters stay float32; matrix products take
bfloat16 operands; router, softmax, norms, rotary, the gate's sigmoid and
logits are float32. Scopes: `window_attention` and `global_attention` (the
two layer kinds, each around projections, rotary, core, gate and output
product; inside them `attn_rope` around the rotary step and the shared
core's `attn_core`), and the shared code's `moe_router`, `moe_experts`,
`shared_expert`, `dense_ffn`; `lm_head`."""

from __future__ import annotations

import dataclasses
import functools
import json
import math
import os
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from defending_against_backdoors_with_robust_learning_rate_tpu.models import (
    token_ops)
from defending_against_backdoors_with_robust_learning_rate_tpu.models.token_ops import (
    _mm, _rms, attention_plan, causal_attention, core_flops, dense_ffn,
    dispatch_rows, rope_half, shared_expert)

_HERE = os.path.dirname(os.path.abspath(__file__))
PUBLISHED = {"laguna-xs.2": os.path.join(_HERE, "laguna_xs2.json")}
WINDOW, FULL = "sliding_attention", "full_attention"
SCOPES = {WINDOW: "window_attention", FULL: "global_attention"}


@dataclasses.dataclass(frozen=True)
class Rope:
    """One layer kind's rotary embedding."""
    theta: float
    rotated: int              # the first widths of a head that rotate
    # YaRN: (factor, original_max_position_embeddings, beta_fast, beta_slow)
    yarn: Optional[Tuple[float, int, float, float]] = None
    scale: float = 1.0        # on cos and sin (YaRN's attention factor)


@dataclasses.dataclass(frozen=True)
class SwaSpec:
    """The published widths and the cut held here (hashable: a flax
    module attribute)."""
    hidden: int
    dense_ffn: int
    moe_ffn: int
    shared_ffn: int
    kv_heads: int
    head_dim: int
    window: int
    rope: Tuple[Tuple[str, Rope], ...]     # by layer kind
    n_experts: int            # the router's width: every published expert
    top_k: int
    norm_topk: bool
    routed_scale: float
    topk_eps: float
    norm_eps: float
    init_std: float
    # (source layer index, layer kind, query heads, sparse?) per held layer
    layers: Tuple[Tuple[int, str, int, bool], ...]
    experts_held: int
    expert_offset: int
    vocab_held: int


@functools.lru_cache(maxsize=8)
def _load(name_or_path: str) -> dict:
    path = PUBLISHED.get(name_or_path, name_or_path)
    with open(path) as f:
        return json.load(f)


def _rope_of(params: dict, head_dim: int) -> Rope:
    rotated = int(head_dim * float(params.get("partial_rotary_factor", 1)))
    kind = params.get("rope_type", "default")
    if kind == "default":
        return Rope(float(params["rope_theta"]), rotated)
    if kind != "yarn":
        raise ValueError(f"rope_type {kind!r}: models/swa_moe.py has the "
                         f"default frequencies and YaRN's")
    factor = float(params["factor"])
    return Rope(
        float(params["rope_theta"]), rotated,
        yarn=(factor, int(params["original_max_position_embeddings"]),
              float(params.get("beta_fast", 32)),
              float(params.get("beta_slow", 1))),
        scale=float(params.get("attention_factor")
                    or 0.1 * math.log(factor) + 1.0))


def yarn_range(rope: Rope) -> Tuple[int, int]:
    """(low, high): the rotary pairs between which YaRN's ramp runs, as
    `transformers`' `_compute_yarn_parameters` finds them (truncated)."""
    _factor, original, beta_fast, beta_slow = rope.yarn
    dim = rope.rotated

    def correction_dim(rotations):
        return (dim * math.log(original / (rotations * 2 * math.pi))
                / (2 * math.log(rope.theta)))

    return (max(math.floor(correction_dim(beta_fast)), 0),
            min(math.ceil(correction_dim(beta_slow)), dim - 1))


def rope_inv_freq(rope: Rope) -> np.ndarray:
    """Inverse frequencies [rotated / 2] float32: `theta ** (-2i / R)`, or
    under YaRN the interpolated ones (`/ factor`) blended in by a linear
    ramp over the pairs `yarn_range` gives."""
    dim = rope.rotated
    extra = 1.0 / rope.theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    if rope.yarn is None:
        return extra.astype(np.float32)
    low, high = yarn_range(rope)
    if low == high:
        high += 0.001         # as the source: no division by zero
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low)
                   / (high - low), 0.0, 1.0)
    return (extra / rope.yarn[0] * ramp + extra * (1.0 - ramp)
            ).astype(np.float32)


def spec_from(lm_config: str, layers: str, experts_held: int,
              expert_offset: int, vocab_held: int) -> SwaSpec:
    """The spec of one cut (`token_ops.held_cut` reads the flags)."""
    if not os.path.isfile(PUBLISHED.get(lm_config, lm_config)):
        raise ValueError(
            f"--lm_config {lm_config!r} is neither one of {sorted(PUBLISHED)} "
            f"nor a file: --arch=swa_moe reads a window/full-attention "
            f"model's published widths")
    pub = _load(lm_config)
    if "num_attention_heads_per_layer" not in pub \
            or "sliding_window" not in pub:
        raise ValueError(
            f"--lm_config {lm_config!r} is no window/full-attention model's "
            f"file (no num_attention_heads_per_layer, no sliding_window): "
            f"--arch=swa_moe reads one of {sorted(PUBLISHED)} or a file "
            f"with the same keys")
    assumed = pub.get("assumed", {})
    n_layers, n_experts = (int(pub["num_hidden_layers"]),
                           int(pub["num_experts"]))
    held, e_held, v_held = token_ops.held_cut(
        layers, experts_held, expert_offset, vocab_held, n_layers, n_experts,
        int(pub["vocab_size"]))
    kinds = set(pub["layer_types"])
    if pub.get("tie_word_embeddings") or pub.get("attention_bias") \
            or not pub.get("gating") or kinds - {WINDOW, FULL} \
            or pub.get("moe_apply_router_weight_on_input") \
            or set(pub["mlp_layer_types"]) - {"dense", "sparse"}:
        raise ValueError(
            f"--lm_config {lm_config!r} asks for a tied head, biased "
            f"projections, ungated attention, router weights on the "
            f"experts' input or a layer kind other than {WINDOW} | {FULL}, "
            f"dense | sparse: models/swa_moe.py has none of them")
    head_dim, kv_heads = int(pub["head_dim"]), int(pub["num_key_value_heads"])
    per_layer = [int(h) for h in pub["num_attention_heads_per_layer"]]
    if any(h % kv_heads for h in per_layer):
        raise ValueError(f"--lm_config {lm_config!r}: a layer's query heads "
                         f"are no multiple of its {kv_heads} key-value heads")
    return SwaSpec(
        hidden=int(pub["hidden_size"]),
        dense_ffn=int(pub["intermediate_size"]),
        moe_ffn=int(pub["moe_intermediate_size"]),
        shared_ffn=int(pub["shared_expert_intermediate_size"]),
        kv_heads=kv_heads, head_dim=head_dim,
        window=int(pub["sliding_window"]),
        rope=tuple((kind, _rope_of(pub["rope_parameters"][kind], head_dim))
                   for kind in sorted(kinds)),
        n_experts=n_experts, top_k=int(pub["num_experts_per_tok"]),
        norm_topk=bool(pub.get("norm_topk_prob",
                               assumed.get("norm_topk_prob", True))),
        routed_scale=float(pub["moe_routed_scaling_factor"]),
        topk_eps=float(assumed.get("topk_eps", 1e-20)),
        norm_eps=float(pub["rms_norm_eps"]),
        init_std=float(assumed.get("initializer_range", 0.02)),
        layers=tuple((i, str(pub["layer_types"][i]), per_layer[i],
                      pub["mlp_layer_types"][i] == "sparse") for i in held),
        experts_held=e_held, expert_offset=expert_offset,
        vocab_held=v_held)


def spec_from_cfg(cfg) -> SwaSpec:
    return spec_from(cfg.lm_config, cfg.lm_layers, cfg.lm_experts_held,
                     cfg.lm_expert_offset, cfg.lm_vocab_held)


def vocab_from_cfg(cfg) -> int:
    return spec_from_cfg(cfg).vocab_held


def from_cfg(cfg, dtype=jnp.float32, remat: bool = False) -> "SwaMoE":
    """What models/registry.get_model builds for this arch."""
    return SwaMoE(spec=spec_from_cfg(cfg), dtype=dtype, remat=remat)


def rope(x, r: Rope):
    """This layer kind's rotary embedding of x [B, T, n, d], in float32."""
    return rope_half(x.astype(jnp.float32), rope_inv_freq(r), r.scale)


def attention(p, x, sp: SwaSpec, kind: str, dtype):
    """Window or full attention, by `kind`, on normed x [B, T, D]; the
    layer's query heads are read off `q_proj`."""
    kv, hd = sp.kv_heads, sp.head_dim
    r = dict(sp.rope)[kind]
    with jax.named_scope(SCOPES[kind]):
        b, t = x.shape[:2]
        h = p["q_proj"].shape[1] // hd
        q = _mm(x, p["q_proj"], dtype).reshape(b, t, h, hd)
        k = _mm(x, p["k_proj"], dtype).reshape(b, t, kv, hd)
        v = _mm(x, p["v_proj"], dtype).reshape(b, t, kv, hd)
        with jax.named_scope("attn_rope"):
            q = rope(q, r).astype(dtype)
            k = rope(k, r).astype(dtype)
        o = causal_attention(q, k, v,
                             window=sp.window if kind == WINDOW else None)
        gate = jax.nn.sigmoid(
            _mm(x, p["g_proj"], dtype).astype(jnp.float32))     # [B, T, H]
        o = o.reshape(b, t, h, hd).astype(jnp.float32) * gate[..., None]
        return _mm(o.reshape(b, t, h * hd), p["o_proj"], dtype)


def sparse_ffn(p, x, sp: SwaSpec, dtype):
    """(the held routed experts' partial output plus the shared expert's,
    [experts_held + 1] pairs as `token_ops.sparse_ffn` counts them)."""
    y, counts = token_ops.sparse_ffn(p, x, sp, None, dtype)
    return y + shared_expert(p, x, dtype), counts


class Block(nn.Module):
    spec: SwaSpec
    kind: str
    heads: int
    sparse: bool
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        sp, d = self.spec, self.spec.hidden

        def mat(name, *shape):
            return self.param(name, nn.initializers.normal(sp.init_std),
                              shape, jnp.float32)

        def ones(name, n):
            return self.param(name, nn.initializers.ones, (n,), jnp.float32)

        hq, hkv = self.heads * sp.head_dim, sp.kv_heads * sp.head_dim
        p = {"attn_norm": ones("attn_norm", d),
             "ffn_norm": ones("ffn_norm", d),
             "q_proj": mat("q_proj", d, hq), "k_proj": mat("k_proj", d, hkv),
             "v_proj": mat("v_proj", d, hkv),
             "g_proj": mat("g_proj", d, self.heads),
             "o_proj": mat("o_proj", hq, d)}
        if self.sparse:
            e, f, s = sp.experts_held, sp.moe_ffn, sp.shared_ffn
            p.update(gate=mat("gate", d, sp.n_experts),
                     experts_w1=mat("experts_w1", e, d, f),
                     experts_w3=mat("experts_w3", e, d, f),
                     experts_w2=mat("experts_w2", e, f, d),
                     shared_w1=mat("shared_w1", d, s),
                     shared_w3=mat("shared_w3", d, s),
                     shared_w2=mat("shared_w2", s, d))
        else:
            f = sp.dense_ffn
            p.update(w1=mat("w1", d, f), w3=mat("w3", d, f),
                     w2=mat("w2", f, d))
        y = _rms(x, p["attn_norm"], sp.norm_eps).astype(self.dtype)
        hid = x + attention(p, y, sp, self.kind, self.dtype).astype(x.dtype)
        z = _rms(hid, p["ffn_norm"], sp.norm_eps).astype(self.dtype)
        if self.sparse:
            z, counts = sparse_ffn(p, z, sp, self.dtype)
        else:
            z = dense_ffn(p, z, self.dtype)
            counts = jnp.zeros((sp.experts_held + 1,), jnp.int32)
        return hid + z.astype(x.dtype), counts


class SwaMoE(nn.Module):
    spec: SwaSpec
    dtype: Any = jnp.float32
    remat: bool = False       # recompute each block's activations in backward
    takes_tokens = True       # the batch is token ids (models/registry.py)

    @property
    def pairs_shape(self):
        """Shape of the (token, expert) pair counts a forward returns."""
        return (n_sparse_layers(self.spec), self.spec.experts_held + 1)

    def dispatch_rows(self, n_tokens: int) -> int:
        return dispatch_rows(self.spec, n_tokens)

    def build_counters(self, n_tokens: int, seq_len: int):
        """Counted once when an engine is built (obs/spans.py), for a step
        of `n_tokens` tokens in sequences of `seq_len`."""
        sp = self.spec
        path, computed, square = attention_plan(seq_len)
        w_path, in_window, w_square = attention_plan(seq_len, sp.window)
        kinds = [kind for _i, kind, _h, _s in sp.layers]
        paths = {}
        for took, kind in ((path, FULL), (w_path, WINDOW)):
            paths[took] = paths.get(took, 0) + kinds.count(kind)
        flops = {}
        for _i, kind, heads, _s in sp.layers:
            label = "window" if kind == WINDOW else "full"
            flops[label] = flops.get(label, 0.0) + core_flops(
                n_tokens, seq_len, heads, sp.head_dim, sp.head_dim,
                sp.window if kind == WINDOW else None)
        return {"experts_held": sp.experts_held,
                "vocab_held": sp.vocab_held,
                "moe_rows": self.dispatch_rows(n_tokens),
                "moe_rows_worst": n_tokens * sp.top_k,
                "attn_squares_computed": computed, "attn_squares": square,
                "attn_window": sp.window,
                "attn_window_squares_computed": in_window,
                "attn_window_squares": w_square, "attn_path": paths,
                "attn_core_flops": flops,
                "attn_rope_product": len(kinds),
                "attn_window_layers": kinds.count(WINDOW),
                "attn_full_layers": kinds.count(FULL),
                "shared_experts": 1}

    @nn.compact
    def __call__(self, tokens, *, train: bool = False):
        """tokens [B, T] int32 -> (logits [B, T, vocab_held] float32,
        pairs [sparse layers, experts_held + 1] int32). `train` is taken
        for the registry's calling convention; there is no dropout."""
        sp = self.spec
        table = nn.initializers.normal(sp.init_std)
        embed = self.param("embed", table, (sp.vocab_held, sp.hidden),
                           jnp.float32)
        head = self.param("head", table, (sp.vocab_held, sp.hidden),
                          jnp.float32)
        x = jnp.take(embed, tokens, axis=0).astype(self.dtype)
        cls = nn.remat(Block) if self.remat else Block
        pairs = []
        for i, (_src, kind, heads, sparse) in enumerate(sp.layers):
            x, counts = cls(sp, kind, heads, sparse, self.dtype,
                            name=f"layer_{i}")(x)
            if sparse:
                pairs.append(counts)
        w = self.param("final_norm", nn.initializers.ones, (sp.hidden,),
                       jnp.float32)
        with jax.named_scope("lm_head"):
            h = _rms(x, w, sp.norm_eps).astype(self.dtype)
            logits = jnp.einsum("btd,vd->btv", h, head.astype(self.dtype),
                                preferred_element_type=jnp.float32)
        pairs = (jnp.stack(pairs) if pairs
                 else jnp.zeros((0, sp.experts_held + 1), jnp.int32))
        return logits, pairs


def n_sparse_layers(spec: SwaSpec) -> int:
    return sum(1 for _i, _k, _h, sparse in spec.layers if sparse)
