"""`mla_moe`: latent attention (MLA) in every layer, a sigmoid-routed
sparse feed-forward with a shared expert, and multi-token-prediction
modules in the client's loss: the DeepSeek-V3 family's block, at the widths
of one published file (`joyai_llm_flash.json` beside this module, or the
file `--lm_config` names). Flags state only the cut (`MlaSpec`): which of
the source's layers are held, how many routed experts of a sparse layer
live here and from which offset, and how many rows of the vocabulary; the
MTP depth comes from the file. Equations, as the file's keys and the
family's public implementation give them (what the file lacks is under its
`assumed`):

- block `h = x + attn(rms(x)); y = h + ffn(rms(h))`, no bias anywhere; a
  final norm; an untied head.
- MLA: `cq = rms(x W_dq)`; `q = cq W_uq` -> heads of `[q_nope | q_pe]`;
  `[ckv | k_pe] = x W_dkv`; `[k_nope | v]` per head `= rms(ckv) W_ukv`;
  rotary embedding over interleaved pairs `(2i, 2i + 1)` of `q_pe` and of
  the ONE `k_pe` every head shares; `k = [k_nope | k_pe]`; causal
  `softmax(q k^T * qk_head_dim^-0.5) v` with float32 scores, over query
  blocks (`token_ops.causal_attention`); `W_o`.
- the first `first_k_dense_replace` layers: `W2(silu(W1 x) * W3 x)`. The
  others: `token_ops.sparse_ffn` (the one implementation both token models
  use) over all `n_routed_experts` with top-k, the weights normalised over
  the selected (`+ 1e-20`) and scaled by `routed_scaling_factor`, the
  correction bias a buffer outside the trained tree; plus the shared
  expert (`token_ops.shared_expert`), which every chip of the deployment
  computes alike.
- MTP module k (`num_nextn_predict_layers` of them, k from 1): `u_i = W_eh
  [rms_e(embed(t_{i+k})) ; rms_h(h_i)]` with `h_i` the module before's
  output (the last main block's, before the final norm, for k = 1), one
  sparse block with its own router and held experts, its own final norm,
  the main model's head: logits for `t_{i+k+1}`. It runs in training only
  and over all T positions of a step: the last k read a token rolled round
  from the row's start and the loss leaves them out (`fl/task.py`); under
  causal attention no other position reads them.

Precision (`--dtype bf16`): parameters stay float32; matrix products take
bfloat16 operands; router, softmax, norms, rotary and logits are float32.
Scopes: `mla_attention` (inside it `attn_rope` around the rotary pairs and
the assembly of q and k, and the shared core's `attn_core`),
`shared_expert`, `mtp` (around a whole module), and the shared code's
`moe_router`, `moe_experts`, `dense_ffn`; `lm_head`."""

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
    dispatch_rows, shared_expert)

_HERE = os.path.dirname(os.path.abspath(__file__))
PUBLISHED = {"joyai-llm-flash": os.path.join(_HERE, "joyai_llm_flash.json")}


@dataclasses.dataclass(frozen=True)
class MlaSpec:
    """The published widths and the cut held here (hashable: a flax
    module attribute)."""
    hidden: int
    dense_ffn: int
    moe_ffn: int
    heads: int
    q_rank: int
    kv_rank: int
    nope_dim: int
    rope_dim: int
    v_dim: int
    n_experts: int            # the router's width: every published expert
    top_k: int
    norm_topk: bool
    routed_scale: float
    topk_eps: float
    shared_ffn: int           # n_shared_experts x moe_ffn
    norm_eps: float
    rope_theta: float
    init_std: float
    layers: Tuple[Tuple[int, bool], ...]   # (source layer index, sparse?)
    mtp_depth: int
    mtp_src_layer: int        # the first module's index in the source
    mtp_weight: float
    experts_held: int
    expert_offset: int
    vocab_held: int


@functools.lru_cache(maxsize=8)
def _load(name_or_path: str) -> dict:
    path = PUBLISHED.get(name_or_path, name_or_path)
    with open(path) as f:
        return json.load(f)


def spec_from(lm_config: str, layers: str, experts_held: int,
              expert_offset: int, vocab_held: int) -> MlaSpec:
    """The spec of one cut (`token_ops.held_cut` reads the flags)."""
    if not os.path.isfile(PUBLISHED.get(lm_config, lm_config)):
        raise ValueError(
            f"--lm_config {lm_config!r} is neither one of {sorted(PUBLISHED)} "
            f"nor a file: --arch=mla_moe reads a latent-attention model's "
            f"published widths")
    pub = _load(lm_config)
    if "kv_lora_rank" not in pub:
        raise ValueError(
            f"--lm_config {lm_config!r} is no latent-attention model's file "
            f"(no kv_lora_rank): --arch=mla_moe reads one of "
            f"{sorted(PUBLISHED)} or a file with the same keys")
    assumed = pub.get("assumed", {})
    n_layers, n_experts = (int(pub["num_hidden_layers"]),
                           int(pub["n_routed_experts"]))
    held, e_held, v_held = token_ops.held_cut(
        layers, experts_held, expert_offset, vocab_held, n_layers, n_experts,
        int(pub["vocab_size"]))
    if pub.get("tie_word_embeddings") or pub.get("rope_scaling") \
            or int(pub.get("n_group", 1)) != 1 \
            or pub.get("scoring_func", "sigmoid") != "sigmoid":
        raise ValueError(
            f"--lm_config {lm_config!r} asks for a tied head, scaled rotary "
            f"embedding, group-limited routing or softmax scores: "
            f"models/mla_moe.py has none of them")
    dense_first, freq = (int(pub["first_k_dense_replace"]),
                         int(pub.get("moe_layer_freq", 1)))
    return MlaSpec(
        hidden=int(pub["hidden_size"]),
        dense_ffn=int(pub["intermediate_size"]),
        moe_ffn=int(pub["moe_intermediate_size"]),
        heads=int(pub["num_attention_heads"]),
        q_rank=int(pub["q_lora_rank"]), kv_rank=int(pub["kv_lora_rank"]),
        nope_dim=int(pub["qk_nope_head_dim"]),
        rope_dim=int(pub["qk_rope_head_dim"]),
        v_dim=int(pub["v_head_dim"]),
        n_experts=n_experts, top_k=int(pub["num_experts_per_tok"]),
        norm_topk=bool(pub["norm_topk_prob"]),
        routed_scale=float(pub["routed_scaling_factor"]),
        topk_eps=float(assumed.get("topk_eps", 1e-20)),
        shared_ffn=int(pub["n_shared_experts"])
        * int(pub["moe_intermediate_size"]),
        norm_eps=float(pub["rms_norm_eps"]),
        rope_theta=float(pub["rope_theta"]),
        init_std=float(assumed.get("initializer_range", 0.02)),
        layers=tuple((i, i >= dense_first and i % freq == 0) for i in held),
        mtp_depth=int(pub.get("num_nextn_predict_layers", 0)),
        mtp_src_layer=n_layers,
        mtp_weight=float(assumed.get("mtp_loss_weight", 0.3)),
        experts_held=e_held, expert_offset=expert_offset,
        vocab_held=v_held)


def spec_from_cfg(cfg) -> MlaSpec:
    return spec_from(cfg.lm_config, cfg.lm_layers, cfg.lm_experts_held,
                     cfg.lm_expert_offset, cfg.lm_vocab_held)


def vocab_from_cfg(cfg) -> int:
    return spec_from_cfg(cfg).vocab_held


def from_cfg(cfg, dtype=jnp.float32, remat: bool = False) -> "MlaMoE":
    """What models/registry.get_model builds for this arch."""
    return MlaMoE(spec=spec_from_cfg(cfg), dtype=dtype, remat=remat)


def expert_bias(spec: MlaSpec, src_layer: int) -> np.ndarray:
    """The score-correction bias of one sparse layer
    (`token_ops.fixed_bias`)."""
    return token_ops.fixed_bias(spec.n_experts, src_layer)


def _rope_pairs(x, theta):
    """Rotary embedding over interleaved pairs (2i, 2i + 1) of the last
    axis, in float32; x [B, T, n, d]."""
    d, t = x.shape[-1], x.shape[1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = (f(ang)[None, :, None, :] for f in (jnp.cos, jnp.sin))
    xf = x.astype(jnp.float32).reshape(x.shape[:-1] + (d // 2, 2))
    a, b = xf[..., 0], xf[..., 1]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin],
                     axis=-1).reshape(x.shape)


def mla_attention(p, x, sp: MlaSpec, dtype):
    """Latent attention on normed x [B, T, D]."""
    h, dn, dr, dv = sp.heads, sp.nope_dim, sp.rope_dim, sp.v_dim
    with jax.named_scope("mla_attention"):
        b, t = x.shape[:2]
        cq = _rms(_mm(x, p["q_a_proj"], dtype), p["q_a_norm"], sp.norm_eps)
        q = _mm(cq, p["q_b_proj"], dtype).reshape(b, t, h, dn + dr)
        down = _mm(x, p["kv_a_proj"], dtype)
        ckv = _rms(down[..., :sp.kv_rank], p["kv_a_norm"], sp.norm_eps)
        kv = _mm(ckv, p["kv_b_proj"], dtype).reshape(b, t, h, dn + dv)
        with jax.named_scope("attn_rope"):
            q_pe = _rope_pairs(q[..., dn:], sp.rope_theta).astype(dtype)
            k_pe = _rope_pairs(down[:, :, None, sp.kv_rank:],
                               sp.rope_theta).astype(dtype)
            q = jnp.concatenate([q[..., :dn], q_pe], axis=-1)
            k = jnp.concatenate(
                [kv[..., :dn], jnp.broadcast_to(k_pe, (b, t, h, dr))],
                axis=-1)
        o = causal_attention(q, k, kv[..., dn:])
        return _mm(o, p["o_proj"], dtype)


def sparse_ffn(p, x, sp: MlaSpec, src_layer: int, dtype):
    """(the held routed experts' partial output plus the shared expert's,
    [experts_held + 1] pairs as `token_ops.sparse_ffn` counts them)."""
    y, counts = token_ops.sparse_ffn(p, x, sp, expert_bias(sp, src_layer),
                                     dtype)
    return y + shared_expert(p, x, dtype), counts


class Block(nn.Module):
    spec: MlaSpec
    src_layer: int
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

        h = sp.heads
        p = {"attn_norm": ones("attn_norm", d),
             "ffn_norm": ones("ffn_norm", d),
             "q_a_proj": mat("q_a_proj", d, sp.q_rank),
             "q_a_norm": ones("q_a_norm", sp.q_rank),
             "q_b_proj": mat("q_b_proj", sp.q_rank,
                             h * (sp.nope_dim + sp.rope_dim)),
             "kv_a_proj": mat("kv_a_proj", d, sp.kv_rank + sp.rope_dim),
             "kv_a_norm": ones("kv_a_norm", sp.kv_rank),
             "kv_b_proj": mat("kv_b_proj", sp.kv_rank,
                              h * (sp.nope_dim + sp.v_dim)),
             "o_proj": mat("o_proj", h * sp.v_dim, d)}
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
        hid = x + mla_attention(p, y, sp, self.dtype).astype(x.dtype)
        z = _rms(hid, p["ffn_norm"], sp.norm_eps).astype(self.dtype)
        if self.sparse:
            z, counts = sparse_ffn(p, z, sp, self.src_layer, self.dtype)
        else:
            z = dense_ffn(p, z, self.dtype)
            counts = jnp.zeros((sp.experts_held + 1,), jnp.int32)
        return hid + z.astype(x.dtype), counts


class MTP(nn.Module):
    """One multi-token-prediction module: (the module before's hidden
    states [B, T, D], the embeddings of the tokens `k` on [B, T, D]) ->
    (its own hidden states, its normed output for the shared head, its
    block's pair counts)."""
    spec: MlaSpec
    src_layer: int
    dtype: Any = jnp.float32
    remat: bool = False

    @nn.compact
    def __call__(self, hidden, ahead):
        sp, d = self.spec, self.spec.hidden

        def ones(name):
            return self.param(name, nn.initializers.ones, (d,), jnp.float32)

        eh = self.param("eh_proj", nn.initializers.normal(sp.init_std),
                        (2 * d, d), jnp.float32)
        both = jnp.concatenate(
            [_rms(ahead, ones("embed_norm"), sp.norm_eps),
             _rms(hidden, ones("hidden_norm"), sp.norm_eps)], axis=-1)
        u = _mm(both.astype(self.dtype), eh, self.dtype)
        cls = nn.remat(Block) if self.remat else Block
        y, counts = cls(sp, self.src_layer, True, self.dtype,
                        name="block")(u)
        return y, _rms(y, ones("final_norm"), sp.norm_eps), counts


class MlaMoE(nn.Module):
    spec: MlaSpec
    dtype: Any = jnp.float32
    remat: bool = False       # recompute each block's activations in backward
    takes_tokens = True       # the batch is token ids (models/registry.py)

    @property
    def ahead_weight(self) -> float:
        """What the loss multiplies the mean of the MTP modules'
        cross-entropies by (fl/task.make_batch_loss)."""
        return self.spec.mtp_weight

    @property
    def pairs_shape(self):
        """Shape of the (token, expert) pair counts an eval forward
        returns: the main model's sparse layers (a training forward's has
        `mtp_depth` more rows, the modules' blocks)."""
        return (n_sparse_layers(self.spec), self.spec.experts_held + 1)

    def dispatch_rows(self, n_tokens: int) -> int:
        return dispatch_rows(self.spec, n_tokens)

    def build_counters(self, n_tokens: int, seq_len: int):
        """Counted once when an engine is built (obs/spans.py), for a step
        of `n_tokens` tokens in sequences of `seq_len`."""
        sp = self.spec
        path, computed, square = attention_plan(seq_len)
        # every block's core, the MTP modules' over all T positions too
        flops = (len(sp.layers) + sp.mtp_depth) * core_flops(
            n_tokens, seq_len, sp.heads, sp.nope_dim + sp.rope_dim, sp.v_dim)
        return {"experts_held": self.spec.experts_held,
                "vocab_held": self.spec.vocab_held,
                "moe_rows": self.dispatch_rows(n_tokens),
                "moe_rows_worst": n_tokens * self.spec.top_k,
                "attn_squares_computed": computed, "attn_squares": square,
                "attn_path": {path: len(self.spec.layers)
                              + self.spec.mtp_depth},
                "attn_core_flops": {"full": flops},
                # its rotary pairs are its own (`_rope_pairs`), no product
                "attn_rope_product": 0,
                "mtp_depth": self.spec.mtp_depth,
                "shared_experts": self.spec.shared_ffn // self.spec.moe_ffn}

    @nn.compact
    def __call__(self, tokens, *, train: bool = False):
        """tokens [B, T] int32 -> (logits [B, T, vocab_held] float32, pairs
        [sparse layers, experts_held + 1] int32), and in training a third
        value: per MTP module k the logits [B, T, vocab_held] for the token
        k + 1 on (the last k positions have none: the loss leaves them
        out), with the modules' pair counts appended to `pairs`. There is
        no dropout."""
        sp = self.spec
        table = nn.initializers.normal(sp.init_std)
        embed = self.param("embed", table, (sp.vocab_held, sp.hidden),
                           jnp.float32)
        head = self.param("head", table, (sp.vocab_held, sp.hidden),
                          jnp.float32)

        def logits_of(normed):
            with jax.named_scope("lm_head"):
                return jnp.einsum("btd,vd->btv", normed.astype(self.dtype),
                                  head.astype(self.dtype),
                                  preferred_element_type=jnp.float32)

        x = jnp.take(embed, tokens, axis=0).astype(self.dtype)
        cls = nn.remat(Block) if self.remat else Block
        pairs = []
        for i, (src, sparse) in enumerate(sp.layers):
            x, counts = cls(sp, src, sparse, self.dtype,
                            name=f"layer_{i}")(x)
            if sparse:
                pairs.append(counts)
        w = self.param("final_norm", nn.initializers.ones, (sp.hidden,),
                       jnp.float32)
        logits = logits_of(_rms(x, w, sp.norm_eps))
        ahead = []
        if train or self.is_initializing():
            for k in range(1, sp.mtp_depth + 1):
                with jax.named_scope("mtp"):
                    nxt = jnp.take(embed, jnp.roll(tokens, -k, axis=1),
                                   axis=0).astype(self.dtype)
                    x, normed, counts = MTP(
                        sp, sp.mtp_src_layer + k - 1, self.dtype, self.remat,
                        name=f"mtp_{k - 1}")(x, nxt)
                    ahead.append(logits_of(normed))
                    pairs.append(counts)
        pairs = (jnp.stack(pairs) if pairs
                 else jnp.zeros((0, sp.experts_held + 1), jnp.int32))
        return (logits, pairs, tuple(ahead)) if train else (logits, pairs)


def n_sparse_layers(spec: MlaSpec) -> int:
    return sum(1 for _i, sparse in spec.layers if sparse)
