"""`gdn_moe`: Gated DeltaNet linear attention beside gated full attention,
a softmax-routed sparse feed-forward with a sigmoid-gated shared expert in
every layer: the `qwen3_next` block, at the widths of one published file
(`qwen3_next_80b_a3b.json` beside this module, or the file `--lm_config`
names). Flags state only the cut (`GdnSpec`): which of the source's layers
are held, how many routed experts of a layer live here and from which
offset, and how many rows of the vocabulary. Equations, as the file's keys
give them (what the file lacks is under its `assumed`):

- block `h = x + mixer(norm(x)); y = h + moe(norm(h))` with the zero-centred
  norm `norm(x) = x / rms(x) * (1 + w)`; a final norm of that kind; an
  untied head; no bias anywhere. Layer i is `full_attention` where `(i + 1)
  % full_attention_interval == 0`, `linear_attention` elsewhere.
- `linear_attention` (`gdn`), value head i reading key head `i // (Hv /
  Hk)`: `[q, k, v, z] = x W_qkvz`, `[b, a] = x W_ba`; `q, k, v ←
  silu(causal depthwise conv)`; `q ← l2norm(q) / sqrt(d_k)`, `k ←
  l2norm(k)`; `beta = sigmoid(b)`, `g = -exp(A_log) softplus(a + dt_bias)`;
  per value head, from `S = 0` at a sequence's start: `S ← exp(g_t) S`, `u_t
  = beta_t (v_t - S^T k_t)`, `S ← S + k_t u_t^T`, `o_t = S^T q_t`; `y =
  rmsnorm(o) w_n silu(z)`; `W_out`. The recurrence runs in chunks
  (`chunked_delta_rule`), never a token at a time; in a program built for
  the TPU, where the heads are whole 128-lane tiles, as the fused kernels
  of `models/gdn_kernel.py` (`delta_rule` picks when a program is traced).
- `full_attention` (`gated_attention`): `[q | gate] = x W_q` a head at a
  time, zero-centred norms on q and k a head, rotate-half rotary on the
  first `partial_rotary_factor x head_dim` widths (`token_ops.rope_half`),
  `token_ops.causal_attention`, times `sigmoid(gate)` element-wise; `W_o`.
- `token_ops.sparse_ffn` with the softmax selection: softmax over every
  published expert, top-k, renormalised over the k; plus
  `token_ops.shared_expert` under its sigmoid gate.

Precision (`--dtype bf16`): parameters stay float32; products take
bfloat16 operands; the recurrent state, the decays, the triangular solve,
beta, g, the norms, the router, the gates' sigmoids and the logits are
float32. Scopes: `gdn` around a linear-attention mixer, `gdn_core` around
its chunked rule inside it, `gated_attention` around a full layer (with the
shared core's `attn_core` and `attn_rope` inside), the shared code's
`moe_router`, `moe_experts`, `shared_expert`; `lm_head`."""

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
    attention_kernel, gdn_kernel, token_ops)
from defending_against_backdoors_with_robust_learning_rate_tpu.models.token_ops import (
    _mm, _rms, attention_plan, causal_attention, core_flops, dispatch_rows,
    rope_half, shared_expert)

_HERE = os.path.dirname(os.path.abspath(__file__))
PUBLISHED = {"qwen3-next-80b-a3b": os.path.join(_HERE,
                                                "qwen3_next_80b_a3b.json")}
LINEAR, FULL = "linear_attention", "full_attention"
# tokens of a chunk of the delta rule: within one, its sequential updates are
# a unit triangular solve and products; across chunks a scan carries S
GDN_CHUNK = 64


@dataclasses.dataclass(frozen=True)
class GdnSpec:
    """The published widths and the cut held here (hashable: a flax
    module attribute)."""
    hidden: int
    moe_ffn: int
    shared_ffn: int
    heads: int                # full layers: query heads
    kv_heads: int
    head_dim: int
    rope_theta: float
    rotated: int              # the first widths of a full layer's head
    k_heads: int              # linear layers: key heads, value heads,
    v_heads: int              # and their widths
    k_dim: int
    v_dim: int
    conv_taps: int
    n_experts: int            # the router's width: every published expert
    top_k: int
    norm_topk: bool
    norm_eps: float
    l2_eps: float
    init_std: float
    # (source layer index, layer kind) per held layer
    layers: Tuple[Tuple[int, str], ...]
    experts_held: int
    expert_offset: int
    vocab_held: int
    # what token_ops.sparse_ffn reads beside the widths
    scoring: str = "softmax"
    routed_scale: float = 1.0
    topk_eps: float = 0.0


@functools.lru_cache(maxsize=8)
def _load(name_or_path: str) -> dict:
    with open(PUBLISHED.get(name_or_path, name_or_path)) as f:
        return json.load(f)


def layer_kinds(pub: dict):
    """The published layer kinds, from `full_attention_interval`."""
    every = int(pub["full_attention_interval"])
    return [FULL if (i + 1) % every == 0 else LINEAR
            for i in range(int(pub["num_hidden_layers"]))]


def spec_from(lm_config: str, layers: str, experts_held: int,
              expert_offset: int, vocab_held: int) -> GdnSpec:
    """The spec of one cut (`token_ops.held_cut` reads the flags)."""
    if not os.path.isfile(PUBLISHED.get(lm_config, lm_config)):
        raise ValueError(
            f"--lm_config {lm_config!r} is neither one of {sorted(PUBLISHED)} "
            f"nor a file: --arch=gdn_moe reads a Gated DeltaNet model's "
            f"published widths")
    pub = _load(lm_config)
    if "linear_num_value_heads" not in pub \
            or "full_attention_interval" not in pub:
        raise ValueError(
            f"--lm_config {lm_config!r} is no Gated DeltaNet model's file (no "
            f"linear_num_value_heads, no full_attention_interval): "
            f"--arch=gdn_moe reads one of {sorted(PUBLISHED)} or a file with "
            f"the same keys")
    if pub.get("tie_word_embeddings") or pub.get("attention_bias") \
            or pub.get("rope_scaling") or pub.get("use_sliding_window") \
            or pub.get("mlp_only_layers") \
            or int(pub.get("decoder_sparse_step", 1)) != 1 \
            or pub.get("hidden_act", "silu") != "silu":
        raise ValueError(
            f"--lm_config {lm_config!r} asks for a tied head, biased "
            f"projections, scaled rotary, a sliding window, dense layers or "
            f"an activation other than silu: models/gdn_moe.py has none of "
            f"them")
    assumed = pub.get("assumed", {})
    n_experts = int(pub["num_experts"])
    held, e_held, v_held = token_ops.held_cut(
        layers, experts_held, expert_offset, vocab_held,
        int(pub["num_hidden_layers"]), n_experts, int(pub["vocab_size"]))
    head_dim, k_heads = int(pub["head_dim"]), int(pub["linear_num_key_heads"])
    v_heads = int(pub["linear_num_value_heads"])
    if int(pub["num_attention_heads"]) % int(pub["num_key_value_heads"]) \
            or v_heads % k_heads:
        raise ValueError(f"--lm_config {lm_config!r}: query heads are no "
                         f"multiple of key-value heads, or value heads of "
                         f"key heads")
    kinds = layer_kinds(pub)
    return GdnSpec(
        hidden=int(pub["hidden_size"]),
        moe_ffn=int(pub["moe_intermediate_size"]),
        shared_ffn=int(pub["shared_expert_intermediate_size"]),
        heads=int(pub["num_attention_heads"]),
        kv_heads=int(pub["num_key_value_heads"]), head_dim=head_dim,
        rope_theta=float(pub["rope_theta"]),
        rotated=int(head_dim * float(pub.get("partial_rotary_factor", 1))),
        k_heads=k_heads, v_heads=v_heads,
        k_dim=int(pub["linear_key_head_dim"]),
        v_dim=int(pub["linear_value_head_dim"]),
        conv_taps=int(pub["linear_conv_kernel_dim"]),
        n_experts=n_experts, top_k=int(pub["num_experts_per_tok"]),
        norm_topk=bool(pub.get("norm_topk_prob", True)),
        norm_eps=float(pub["rms_norm_eps"]),
        l2_eps=float(assumed.get("l2norm_eps", 1e-6)),
        init_std=float(assumed.get("initializer_range", 0.02)),
        layers=tuple((i, kinds[i]) for i in held),
        experts_held=e_held, expert_offset=expert_offset, vocab_held=v_held)


def spec_from_cfg(cfg) -> GdnSpec:
    return spec_from(cfg.lm_config, cfg.lm_layers, cfg.lm_experts_held,
                     cfg.lm_expert_offset, cfg.lm_vocab_held)


def vocab_from_cfg(cfg) -> int:
    return spec_from_cfg(cfg).vocab_held


def from_cfg(cfg, dtype=jnp.float32, remat: bool = False) -> "GdnMoE":
    """What models/registry.get_model builds for this arch."""
    return GdnMoE(spec=spec_from_cfg(cfg), dtype=dtype, remat=remat)


def _f32_mm(x, w, dtype):
    """`x @ w` with operands in the products' dtype and a float32 result."""
    return jnp.dot(x.astype(dtype), w.astype(dtype),
                   preferred_element_type=jnp.float32)


def zero_centred_norm(x, w, eps):
    """`x / rms(x) * (1 + w)` over the last axis, in float32."""
    return _rms(x, 1.0 + w, eps)


def _l2norm(x, eps):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + eps)


def causal_conv(x, w):
    """Depthwise causal convolution over time: x [B, T, C], w [taps, C];
    `y_t = sum_j w[j] x[t - taps + 1 + j]`, positions before 0 zero."""
    taps, t = w.shape[0], x.shape[1]
    pad = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
    return sum(pad[:, j:j + t] * w[j] for j in range(taps))


def _exact(x, y):
    """`x @ y` of float32 operands at float32 precision."""
    return jnp.matmul(x, y, precision=jax.lax.Precision.HIGHEST)


def inverse_by_halves(a):
    """`(I + a)^-1` of strictly lower triangular a [..., C, C], float32, by
    halves: `[[M11, 0], [M21, M22]]` has the inverse `[[X11, 0], [-X22 M21
    X11, X22]]` with `X11`, `X22` the inverses of the diagonal halves, which
    are found together, stacked, the same way; log2(C) levels of batched
    products (six at C = 64), with no step a row at a time. A C that is no
    power of two is padded with the identity."""
    c = a.shape[-1]
    p = 1 << (c - 1).bit_length()
    if p == 1:
        return jnp.ones_like(a)
    m = jnp.pad(a, [(0, 0)] * (a.ndim - 2) + [(0, p - c)] * 2)
    h = p // 2
    halves = inverse_by_halves(jnp.stack([m[..., :h, :h], m[..., h:, h:]]))
    x11, x22 = halves[0], halves[1]
    below = -_exact(x22, _exact(m[..., h:, :h], x11))
    inv = jnp.concatenate(
        [jnp.concatenate([x11, jnp.zeros_like(below)], axis=-1),
         jnp.concatenate([below, x22], axis=-1)], axis=-2)
    return inv[..., :c, :c]


def unit_lower_inverse(a):
    """`(I + a)^-1` of strictly lower triangular a [..., C, C] in float32:
    in a program built for the TPU (heads the kernel of `gdn_kernel.py`
    does not take), XLA's triangular solve against the identity (the TPU's
    tiles pad the small blocks `inverse_by_halves` multiplies to 128 lanes:
    1.7 GB more of a layer's temporaries at the cell's shape, XLA's
    analysis for a described v5e); elsewhere by halves, since the CPU's
    solve is a LAPACK call that an executable loaded from the compile cache
    by another process cannot reach."""
    if not attention_kernel.on_tpu():
        return inverse_by_halves(a)
    eye = jnp.eye(a.shape[-1], dtype=a.dtype)
    return jax.lax.linalg.triangular_solve(
        a + eye, jnp.broadcast_to(eye, a.shape), left_side=True, lower=True,
        unit_diagonal=True)


def chunked_delta_rule(q, k, v, g, beta, dtype, chunk: int = GDN_CHUNK):
    """The gated delta rule over a sequence in chunks of `chunk` tokens: q,
    k [B, T, Hk, dk], v [B, T, Hv, dv], g, beta [B, T, Hv] float32 ->
    o [B, T, Hv, dv] float32, value head i reading key head `i // (Hv /
    Hk)`, the state zero at position 0.

    Within a chunk, with G the cumulative sum of g and `L[t, j] = exp(G_t -
    G_j)` for j <= t (the differences masked BEFORE they are exponentiated,
    so no exponent is positive), the updates `u` solve the unit lower
    triangular system `(I + A) U = diag(beta) (V - diag(exp G) K S)` with
    `A = strictly_lower(diag(beta) (K K^T * L))`: U = U0 - W S for `[W |
    U0] = (I + A)^-1 [beta exp(G) K | beta V]`, the inverse in float32
    (`unit_lower_inverse`), its products with `dtype` operands. Then `O =
    diag(exp G) Q S + (Q K^T * L) U` and `S ← exp(G_C) S + (exp(G_C - G) K)^T
    U`, a `jax.lax.scan` over chunks carrying S [B, Hv, dk, dv] float32.
    Products take `dtype` operands; a length that `chunk` does not divide
    is padded with beta = 0 and g = 0, which leave S as it is."""
    b, t, hk, dk = q.shape
    hv, dv = v.shape[2:]
    n = -(-t // chunk)
    f32 = jnp.float32

    def by_chunk(x):
        """[B, T, H, ...] -> [N, B, Hv, C, ...]."""
        x = jnp.pad(x, ((0, 0), (0, n * chunk - t))
                    + ((0, 0),) * (x.ndim - 2))
        x = x.reshape((b, n, chunk) + x.shape[2:])
        x = jnp.moveaxis(x, (1, 3), (0, 2))
        return jnp.repeat(x, hv // x.shape[2], axis=2)

    q, k, v = by_chunk(q), by_chunk(k), by_chunk(v)
    g, beta = by_chunk(g), by_chunk(beta)                  # [N, B, Hv, C]
    G = jnp.cumsum(g, axis=-1)
    rows = jnp.arange(chunk)
    causal = rows[:, None] >= rows[None, :]
    decay = jnp.exp(jnp.where(causal, G[..., :, None] - G[..., None, :],
                              -jnp.inf))                   # [.., C, C]

    def prod(spec, x, y):
        return jnp.einsum(spec, x.astype(dtype), y.astype(dtype),
                          preferred_element_type=f32)

    kk = prod("...cd,...ed->...ce", k, k)
    a = jnp.where(rows[:, None] > rows[None, :],
                  beta[..., :, None] * kk * decay, 0.0)
    eg = jnp.exp(G)
    inv = unit_lower_inverse(a)
    # what the scan reads as product operands is kept in their dtype
    w = prod("...cj,...jd->...cd", inv,
             (beta * eg)[..., None] * k.astype(f32)).astype(dtype)
    u0 = prod("...cj,...jd->...cd", inv, beta[..., None] * v.astype(f32))
    qk = (prod("...cd,...ed->...ce", q, k) * decay).astype(dtype)
    qg = (eg[..., None] * q.astype(f32)).astype(dtype)
    kd = (jnp.exp(G[..., -1:] - G)[..., None] * k.astype(f32)).astype(dtype)

    def step(s, xs):
        w_n, u0_n, qg_n, qk_n, kd_n, last = xs
        u = u0_n - prod("...cd,...de->...ce", w_n, s)
        o = (prod("...cd,...de->...ce", qg_n, s)
             + prod("...cj,...je->...ce", qk_n, u))
        s = (last[..., None, None] * s
             + prod("...cd,...ce->...de", kd_n, u))
        return s, o

    # the backward keeps the state a chunk and computes the rest again
    s0 = jnp.zeros((b, hv, dk, dv), f32)
    _, o = jax.lax.scan(jax.checkpoint(step), s0,
                        (w, u0, qg, qk, kd, eg[..., -1]))
    o = jnp.moveaxis(o, (0, 2), (1, 3)).reshape(b, n * chunk, hv, dv)
    return o[:, :t]


def delta_rule(q, k, v, g, beta, dtype):
    """`chunked_delta_rule`'s contract by the lowering chosen when the
    program is traced (`gdn_kernel.plan`: platform and widths): the fused
    kernels of `models/gdn_kernel.py` on the TPU where the heads are whole
    128-lane tiles, `chunked_delta_rule` itself everywhere else. Under
    `jax.checkpoint(policy=gdn_kernel.SAVED)` the kernels' residuals (the
    states and inverses) are kept and the rest formed again."""
    took = gdn_kernel.plan(q.shape[1], q.shape[-1], v.shape[-1], GDN_CHUNK)
    if took is None:
        return chunked_delta_rule(q, k, v, g, beta, dtype)
    return gdn_kernel.chunked_delta_rule(q, k, v, g, beta, dtype, took)


def gdn(p, x, sp: GdnSpec, dtype):
    """The Gated DeltaNet mixer on normed x [B, T, D]."""
    hk, hv, dk, dv = sp.k_heads, sp.v_heads, sp.k_dim, sp.v_dim
    with jax.named_scope("gdn"):
        b, t = x.shape[:2]
        qkvz = _mm(x, p["in_proj_qkvz"], dtype)
        qkv = jax.nn.silu(causal_conv(qkvz[..., :2 * hk * dk + hv * dv],
                                      p["conv_weight"].astype(dtype)))
        q, k, v = jnp.split(qkv, [hk * dk, 2 * hk * dk], axis=-1)
        z = qkvz[..., 2 * hk * dk + hv * dv:].reshape(b, t, hv, dv)
        ba = _f32_mm(x, p["in_proj_ba"], dtype)
        beta = jax.nn.sigmoid(ba[..., :hv])
        g = -jnp.exp(p["A_log"]) * jax.nn.softplus(ba[..., hv:]
                                                   + p["dt_bias"])
        q = _l2norm(q.reshape(b, t, hk, dk).astype(jnp.float32),
                    sp.l2_eps) * dk ** -0.5
        k = _l2norm(k.reshape(b, t, hk, dk).astype(jnp.float32), sp.l2_eps)
        with jax.named_scope("gdn_core"):
            o = jax.checkpoint(functools.partial(delta_rule, dtype=dtype),
                               policy=gdn_kernel.SAVED)(
                q.astype(dtype), k.astype(dtype),
                v.reshape(b, t, hv, dv).astype(dtype), g, beta)
        y = _rms(o, p["gdn_norm"], sp.norm_eps) * jax.nn.silu(
            z.astype(jnp.float32))
        return _mm(y.reshape(b, t, hv * dv), p["out_proj"], dtype)


def rope_inv_freq(sp: GdnSpec) -> np.ndarray:
    return (1.0 / sp.rope_theta ** (np.arange(0, sp.rotated, 2,
                                              dtype=np.float64)
                                    / sp.rotated)).astype(np.float32)


def gated_attention(p, x, sp: GdnSpec, dtype):
    """The gated full-attention layer on normed x [B, T, D]."""
    h, kv, hd = sp.heads, sp.kv_heads, sp.head_dim
    with jax.named_scope("gated_attention"):
        b, t = x.shape[:2]
        qg = _mm(x, p["q_proj"], dtype).reshape(b, t, h, 2 * hd)
        q, gate = qg[..., :hd], qg[..., hd:]
        k = _mm(x, p["k_proj"], dtype).reshape(b, t, kv, hd)
        v = _mm(x, p["v_proj"], dtype).reshape(b, t, kv, hd)
        q = zero_centred_norm(q, p["q_norm"], sp.norm_eps)
        k = zero_centred_norm(k, p["k_norm"], sp.norm_eps)
        with jax.named_scope("attn_rope"):
            q = rope_half(q, rope_inv_freq(sp)).astype(dtype)
            k = rope_half(k, rope_inv_freq(sp)).astype(dtype)
        o = causal_attention(q, k, v).reshape(b, t, h, hd)
        o = o.astype(jnp.float32) * jax.nn.sigmoid(gate.astype(jnp.float32))
        return _mm(o.reshape(b, t, h * hd), p["o_proj"], dtype)


def sparse_ffn(p, x, sp: GdnSpec, dtype):
    """(the held routed experts' partial output plus the gated shared
    expert's, [experts_held + 1] pairs as `token_ops.sparse_ffn` counts
    them)."""
    y, counts = token_ops.sparse_ffn(p, x, sp, None, dtype)
    return y + shared_expert(p, x, dtype, gated=True), counts


class Block(nn.Module):
    spec: GdnSpec
    kind: str
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        sp, d = self.spec, self.spec.hidden

        def mat(name, *shape):
            return self.param(name, nn.initializers.normal(sp.init_std),
                              shape, jnp.float32)

        def vec(name, n, init=nn.initializers.zeros):
            return self.param(name, init, (n,), jnp.float32)

        e, f, s = sp.experts_held, sp.moe_ffn, sp.shared_ffn
        p = {"attn_norm": vec("attn_norm", d), "ffn_norm": vec("ffn_norm", d),
             "gate": mat("gate", d, sp.n_experts),
             "experts_w1": mat("experts_w1", e, d, f),
             "experts_w3": mat("experts_w3", e, d, f),
             "experts_w2": mat("experts_w2", e, f, d),
             "shared_w1": mat("shared_w1", d, s),
             "shared_w3": mat("shared_w3", d, s),
             "shared_w2": mat("shared_w2", s, d),
             "shared_gate": mat("shared_gate", d, 1)}
        if self.kind == LINEAR:
            nk, nv = sp.k_heads * sp.k_dim, sp.v_heads * sp.v_dim
            p.update(
                in_proj_qkvz=mat("in_proj_qkvz", d, 2 * nk + 2 * nv),
                in_proj_ba=mat("in_proj_ba", d, 2 * sp.v_heads),
                conv_weight=mat("conv_weight", sp.conv_taps, 2 * nk + nv),
                A_log=self.param("A_log", lambda key, shape: jnp.log(
                    jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0)),
                    (sp.v_heads,)),
                dt_bias=vec("dt_bias", sp.v_heads, nn.initializers.ones),
                gdn_norm=vec("gdn_norm", sp.v_dim, nn.initializers.ones),
                out_proj=mat("out_proj", nv, d))
            mixer = gdn
        else:
            hq, hkv = sp.heads * sp.head_dim, sp.kv_heads * sp.head_dim
            p.update(q_proj=mat("q_proj", d, 2 * hq),
                     k_proj=mat("k_proj", d, hkv),
                     v_proj=mat("v_proj", d, hkv), o_proj=mat("o_proj", hq, d),
                     q_norm=vec("q_norm", sp.head_dim),
                     k_norm=vec("k_norm", sp.head_dim))
            mixer = gated_attention
        y = zero_centred_norm(x, p["attn_norm"], sp.norm_eps).astype(self.dtype)
        hid = x + mixer(p, y, sp, self.dtype).astype(x.dtype)
        z = zero_centred_norm(hid, p["ffn_norm"], sp.norm_eps).astype(
            self.dtype)
        z, counts = sparse_ffn(p, z, sp, self.dtype)
        return hid + z.astype(x.dtype), counts


def gdn_core_flops(n_tokens: int, sp: GdnSpec, chunk: int = GDN_CHUNK
                   ) -> float:
    """Forward operations (2 a multiply-add) of the chunked rule in every
    held linear layer for `n_tokens` tokens: a value head and token `10 C
    d + 6 d^2 + (2/3) C^2` (K K^T, Q K^T, W, U0, the intra-chunk product,
    W S, Q S and K^T U, and the solve), d the key width, C the chunk."""
    d = sp.k_dim
    linear = sum(1 for _i, kind in sp.layers if kind == LINEAR)
    return float(linear * sp.v_heads * n_tokens
                 * (10 * chunk * d + 6 * d * d + 2.0 / 3.0 * chunk * chunk))


class GdnMoE(nn.Module):
    spec: GdnSpec
    dtype: Any = jnp.float32
    remat: bool = False       # recompute each block's activations in backward
    takes_tokens = True       # the batch is token ids (models/registry.py)

    @property
    def pairs_shape(self):
        """Shape of the (token, expert) pair counts a forward returns."""
        return (len(self.spec.layers), self.spec.experts_held + 1)

    def dispatch_rows(self, n_tokens: int) -> int:
        return dispatch_rows(self.spec, n_tokens)

    def build_counters(self, n_tokens: int, seq_len: int):
        """Counted once when an engine is built (obs/spans.py), for a step
        of `n_tokens` tokens in sequences of `seq_len`."""
        sp = self.spec
        path, computed, square = attention_plan(seq_len)
        kinds = [kind for _i, kind in sp.layers]
        linear, full = kinds.count(LINEAR), kinds.count(FULL)
        core = ("xla" if gdn_kernel.plan(seq_len, sp.k_dim, sp.v_dim,
                                         GDN_CHUNK) is None else "kernel")
        return {"experts_held": sp.experts_held,
                "vocab_held": sp.vocab_held,
                "moe_rows": self.dispatch_rows(n_tokens),
                "moe_rows_worst": n_tokens * sp.top_k,
                "attn_squares_computed": computed, "attn_squares": square,
                "attn_path": {path: full} if full else {},
                "attn_core_flops": {"full": full * core_flops(
                    n_tokens, seq_len, sp.heads, sp.head_dim, sp.head_dim)}
                if full else {},
                "attn_rope_product": full, "attn_full_layers": full,
                "shared_experts": 1,
                "gdn_layers": linear, "gdn_chunk": GDN_CHUNK,
                "gdn_path": {core: linear} if linear else {},
                "gdn_chunks": -(-seq_len // GDN_CHUNK),
                "gdn_core_flops": gdn_core_flops(n_tokens, sp),
                "gdn_state_bytes": (n_tokens // seq_len * sp.v_heads
                                    * sp.k_dim * sp.v_dim * 4)}

    @nn.compact
    def __call__(self, tokens, *, train: bool = False):
        """tokens [B, T] int32 -> (logits [B, T, vocab_held] float32,
        pairs [layers, experts_held + 1] int32). `train` is taken for the
        registry's calling convention; there is no dropout."""
        sp = self.spec
        table = nn.initializers.normal(sp.init_std)
        embed = self.param("embed", table, (sp.vocab_held, sp.hidden),
                           jnp.float32)
        head = self.param("head", table, (sp.vocab_held, sp.hidden),
                          jnp.float32)
        x = jnp.take(embed, tokens, axis=0).astype(self.dtype)
        cls = nn.remat(Block) if self.remat else Block
        pairs = []
        for i, (_src, kind) in enumerate(sp.layers):
            x, counts = cls(sp, kind, self.dtype, name=f"layer_{i}")(x)
            pairs.append(counts)
        w = self.param("final_norm", nn.initializers.zeros, (sp.hidden,),
                       jnp.float32)
        with jax.named_scope("lm_head"):
            h = zero_centred_norm(x, w, sp.norm_eps).astype(self.dtype)
            logits = jnp.einsum("btd,vd->btv", h, head.astype(self.dtype),
                                preferred_element_type=jnp.float32)
        return logits, jnp.stack(pairs)
