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
- sparse feed-forward (`models/token_ops.sparse_ffn`, the one
  implementation both token models use): `s = sigmoid(x W_g)` in float32
  over ALL published experts, `sel = top_k(s + b)` with the expert bias `b`
  (a buffer: a fixed function of layer and expert, outside the trained and
  voted tree), `w = s[sel] / (sum s[sel] + 1e-6)`, and of `y = sum_e w_e
  expert_e(x)` the terms whose expert is held here.

Precision (`--dtype bf16`): parameters stay float32; matrix products take
bfloat16 operands; router logits, sigmoid, softmax, norms and the logits
are float32. Named scopes tell the parts apart in a device trace:
`short_conv`, `attention` (inside it `attn_rope` around the rotary step and
the shared core's `attn_core`), `moe_router`, `moe_experts`, `dense_ffn`,
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

from defending_against_backdoors_with_robust_learning_rate_tpu.models import (
    token_ops)
from defending_against_backdoors_with_robust_learning_rate_tpu.models.token_ops import (
    _mm, _rms, attention_plan, causal_attention, core_flops, dense_ffn,
    dispatch_rows, rope_half)

_HERE = os.path.dirname(os.path.abspath(__file__))
PUBLISHED = {"lfm2-8b-a1b": os.path.join(_HERE, "lfm2_8b_a1b.json")}


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
    topk_eps: float = 1e-6    # added to the selected scores' sum


@functools.lru_cache(maxsize=8)
def _load(name_or_path: str) -> dict:
    path = PUBLISHED.get(name_or_path, name_or_path)
    with open(path) as f:
        return json.load(f)


def spec_from(lm_config: str, layers: str, experts_held: int,
              expert_offset: int, vocab_held: int) -> LMSpec:
    """The spec of one cut (`token_ops.held_cut` reads the flags)."""
    pub = _load(lm_config)
    assumed = pub.get("assumed", {})
    n_experts = int(pub["num_experts"])
    held, e_held, v_held = token_ops.held_cut(
        layers, experts_held, expert_offset, vocab_held,
        int(pub["num_hidden_layers"]), n_experts, int(pub["vocab_size"]))
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
    """The routing bias of one sparse layer (`token_ops.fixed_bias`)."""
    return token_ops.fixed_bias(spec.n_experts, src_layer)


def sparse_ffn(p, x, sp: LMSpec, src_layer: int, dtype):
    """`token_ops.sparse_ffn` under this model's routing bias."""
    bias = expert_bias(sp, src_layer) if sp.use_expert_bias else None
    return token_ops.sparse_ffn(p, x, sp, bias, dtype)


def _rope(x, theta):
    """Rotate-half rotary embedding over the whole head, under the scope
    `attn_rope`; x [B, T, n, d]."""
    with jax.named_scope("attn_rope"):
        d = x.shape[-1]
        with jax.ensure_compile_time_eval():     # known when traced
            inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32)
                                   / d))
        return rope_half(x, inv)


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

    def build_counters(self, n_tokens: int, seq_len: int):
        """Counted once when an engine is built (obs/spans.py), for a step
        of `n_tokens` tokens in sequences of `seq_len`."""
        sp = self.spec
        path, computed, square = attention_plan(seq_len)
        layers = sum(kind != "conv" for _i, kind, _s in sp.layers)
        return {"experts_held": self.spec.experts_held,
                "vocab_held": self.spec.vocab_held,
                "moe_rows": self.dispatch_rows(n_tokens),
                "moe_rows_worst": n_tokens * self.spec.top_k,
                "attn_squares_computed": computed, "attn_squares": square,
                "attn_path": {path: layers}, "attn_rope_product": layers,
                "attn_core_flops": {"full": layers * core_flops(
                    n_tokens, seq_len, sp.heads, sp.head_dim,
                    sp.head_dim)}}

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
