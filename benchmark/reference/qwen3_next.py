"""Plain reference of the `qwen3_next` block stack (Gated DeltaNet linear
attention beside gated full attention, a softmax-routed mixture with a
sigmoid-gated shared expert in every layer) as a federated client trains
it: forward, loss, gradients and a client's SGD loop in straightforward
`jax.numpy`, float32, matrix products at the highest precision. No kernel,
no sorting or grouped product, no chunks: the delta rule runs a token at a
time, and every full layer masks a whole `[T, T]` score matrix. The tokens
are scanned in blocks of `REF_BLOCK`, each computed again in the backward
pass, so that a gradient keeps one recurrent state a block and not one a
token (4.2 MB at [2, 32, 128, 128], 268 MB a block of 64, 17 GB a token);
one key-value head's group of query heads is scored at a time, and every
block of layers is computed again in the backward pass too. None of this
changes a number. It shares no code with the package (the parameter tree's
names are the contract: `embed`, `head`, `final_norm`, `layer_<i>/<name>`).

Equations follow the source's config keys
(https://huggingface.co/Qwen/Qwen3-Next-80B-A3B-Instruct, `model_type:
"qwen3_next"`): the zero-centred norm `x / rms(x) * (1 + w)`; `h = x +
mixer(norm(x)); y = h + moe(norm(h))`; layer i `full_attention` where `(i +
1) % full_attention_interval == 0`, `linear_attention` elsewhere.

- linear attention, value head i reading key head `i // (Hv / Hk)`: `[q, k,
  v, z] = x W_qkvz`, `[b, a] = x W_ba`; q, k, v through a causal depthwise
  convolution over time (`linear_conv_kernel_dim` taps, no bias, zeros
  before the sequence) and silu; `q = l2norm(q) / sqrt(d_k)`, `k =
  l2norm(k)`; `beta = sigmoid(b)`, `g = -exp(A_log) softplus(a +
  dt_bias)`; from `S = 0`: `S = exp(g_t) S`, `u_t = beta_t (v_t - S^T
  k_t)`, `S = S + k_t u_t^T`, `o_t = S^T q_t`; `y = rmsnorm(o) w_n
  silu(z)` a value head; `W_out`.
- full attention: `[q | gate] = x W_q` a head at a time (the first
  `head_dim` the query, the rest the gate); zero-centred norms on q and k a
  head; rotate-half rotary over the first `partial_rotary_factor x
  head_dim` widths at `rope_theta`; `softmax(q k^T / sqrt(head_dim) +
  causal mask) v`; times `sigmoid(gate)` element-wise; `W_o`.
- moe: `p = softmax(x W_r)` over every published expert, the top
  `num_experts_per_tok`, `w = p_top / sum p_top`; `y = sum_e w_e
  expert_e(x) + sigmoid(x W_sg) shared(x)`, SwiGLU experts.
- final norm; untied head; next-token cross-entropy.

Departures from the source, each shared with the program and stated under
the configuration's `assumed`:

- the layouts of `in_proj_qkvz` (contiguous q | k | v | z where upstream
  interleaves them by key head) and of `in_proj_ba`: a permutation of
  random weights; `A_log` drawn as log uniform(1, 16); the l2 norm's eps
  1e-6; the gated norm's weight from ones.
- no multi-token prediction module: the published file names none.
- a layer may be given a share of the routed experts (`experts_held` from
  `expert_offset`): routing, top-k and the normalisation run over every
  published expert, and only the held experts' terms are summed, plus the
  shared expert, which every share computes alike. The partial sum goes on
  to the next layer.
- a sliced vocabulary is a smaller vocabulary: ids, logits and loss are over
  the rows held, in the embedding and in the head.
- sequences are packed documents; the recurrent state and attention run
  across the pack, positions count from 0 in the sequence.
- weights are seeded random, float32; the source publishes bfloat16."""

from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference.laguna_xs2 import sgd_step  # noqa: F401

PRECISION = "highest"
LINEAR, FULL = "linear_attention", "full_attention"
# tokens a block of the recurrence's scan holds: one state is kept a block
REF_BLOCK = 64
# the chunk the program's rule runs in, at which `gdn_core_flops` counts
CHUNK = 64
# bytes of a product's operand in the configuration (`--dtype=bf16`), at
# which `gdn_core_bytes` counts the core's q, k and v
OPERAND_BYTES = 2
# None: products take float32 operands. A narrower dtype rounds both
# operands of every product to it first (accumulation stays float32): set
# only to take the reading that places a check's limits, what this
# reference gives in the precision below the one the configuration states.
OPERAND_DTYPE = None


def dims_of(config: Dict[str, Any]) -> Dict[str, Any]:
    """What the functions below read, from a configuration file: the
    catalog's keys at the top level, the cut as the file states it
    (`layer_types` the held layers' kinds, `layers_held` the source's
    indices; `num_experts` held from `expert_offset`; `vocab_size` held) and
    the router's published width under `published`."""
    pub = config.get("published", {})
    n = int(config["num_hidden_layers"])
    held = config.get("layers_held", list(range(n)))
    every = int(config["full_attention_interval"])
    kinds = config.get("layer_types") or [
        FULL if (i + 1) % every == 0 else LINEAR for i in held]
    assert len(held) == len(kinds) == n
    head_dim = int(config["head_dim"])
    return {
        "hidden": int(config["hidden_size"]),
        "moe_ffn": int(config["moe_intermediate_size"]),
        "shared_ffn": int(config["shared_expert_intermediate_size"]),
        "heads": int(config["num_attention_heads"]),
        "kv_heads": int(config["num_key_value_heads"]),
        "head_dim": head_dim,
        "rope_theta": float(config["rope_theta"]),
        "rotated": int(head_dim * float(config["partial_rotary_factor"])),
        "k_heads": int(config["linear_num_key_heads"]),
        "v_heads": int(config["linear_num_value_heads"]),
        "k_dim": int(config["linear_key_head_dim"]),
        "v_dim": int(config["linear_value_head_dim"]),
        "conv_taps": int(config["linear_conv_kernel_dim"]),
        "router_experts": int(pub.get("num_experts", {}).get(
            "source", config["num_experts"])),
        "experts_held": int(config["num_experts"]),
        "expert_offset": int(config.get("expert_offset", 0)),
        "top_k": int(config["num_experts_per_tok"]),
        "eps": float(config["rms_norm_eps"]),
        "l2_eps": float(config.get("assumed", {}).get("l2norm_eps", 1e-6)),
        "vocab": int(config["vocab_size"]),
        "seq_len": int(config.get("seq_len", 0)),
        "layers": [(int(src), str(kind)) for src, kind in zip(held, kinds)],
    }


def _operand(x):
    return (x if OPERAND_DTYPE is None
            else x.astype(OPERAND_DTYPE).astype(jnp.float32))


def mm(a, b):
    return jnp.matmul(_operand(a), _operand(b), precision=PRECISION)


def _einsum(spec, a, b):
    return jnp.einsum(spec, _operand(a), _operand(b), precision=PRECISION)


def norm(x, w, eps):
    """The zero-centred RMSNorm: `x / rms(x) * (1 + w)`."""
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * (1.0 + w)


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def l2norm(x, eps):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + eps)


def conv(x, w):
    """Causal depthwise convolution: x [B, T, C], w [taps, C]; tap
    `taps - 1` multiplies the token itself, tap 0 the one `taps - 1`
    before it; zeros before the sequence."""
    taps, t = w.shape[0], x.shape[1]
    out = jnp.zeros_like(x)
    for j in range(taps):
        back = taps - 1 - j
        shifted = jnp.pad(x, ((0, 0), (back, 0), (0, 0)))[:, :t]
        out = out + shifted * w[j]
    return out


def delta_rule(q, k, v, g, beta):
    """The gated delta rule a token at a time: q, k [B, T, Hv, dk] (already
    repeated to the value heads), v [B, T, Hv, dv], g, beta [B, T, Hv] ->
    o [B, T, Hv, dv]. A scan over blocks of REF_BLOCK tokens, each a scan
    over its tokens, recomputed in the backward pass."""
    b, t, hv, dk = k.shape
    dv = v.shape[-1]
    n = -(-t // REF_BLOCK)

    def blocks(x):
        # beta = 0 and g = 0 past the end: S is left as it is
        x = jnp.pad(x, ((0, 0), (0, n * REF_BLOCK - t))
                    + ((0, 0),) * (x.ndim - 2))
        return jnp.moveaxis(x.reshape((b, n, REF_BLOCK) + x.shape[2:]),
                            (1, 2), (0, 1))

    def token(s, xs):
        q_t, k_t, v_t, g_t, b_t = xs
        s = jnp.exp(g_t)[..., None, None] * s
        u = b_t[..., None] * (v_t - _einsum("bhde,bhd->bhe", s, k_t))
        s = s + _einsum("bhd,bhe->bhde", k_t, u)
        return s, _einsum("bhde,bhd->bhe", s, q_t)

    def block(s, xs):
        return jax.lax.scan(token, s, xs)

    s0 = jnp.zeros((b, hv, dk, dv), jnp.float32)
    _, o = jax.lax.scan(jax.checkpoint(block), s0,
                        tuple(blocks(x) for x in (q, k, v, g, beta)))
    return jnp.moveaxis(o, (0, 1), (1, 2)).reshape(b, n * REF_BLOCK, hv,
                                                   dv)[:, :t]


def linear_attention(x, p, dims):
    """x [B, T, D] normed -> [B, T, D]: the Gated DeltaNet mixer."""
    b, t, _ = x.shape
    hk, hv, dk, dv = (dims["k_heads"], dims["v_heads"], dims["k_dim"],
                      dims["v_dim"])
    qkvz = mm(x, p["in_proj_qkvz"])
    nk, nv = hk * dk, hv * dv
    qkv = jax.nn.silu(conv(qkvz[..., :2 * nk + nv], p["conv_weight"]))
    z = qkvz[..., 2 * nk + nv:].reshape(b, t, hv, dv)
    rep = hv // hk
    q = l2norm(qkv[..., :nk].reshape(b, t, hk, dk), dims["l2_eps"]) \
        * dk ** -0.5
    k = l2norm(qkv[..., nk:2 * nk].reshape(b, t, hk, dk), dims["l2_eps"])
    v = qkv[..., 2 * nk:].reshape(b, t, hv, dv)
    ba = mm(x, p["in_proj_ba"])
    beta = jax.nn.sigmoid(ba[..., :hv])
    g = -jnp.exp(p["A_log"]) * jax.nn.softplus(ba[..., hv:] + p["dt_bias"])
    o = delta_rule(jnp.repeat(q, rep, axis=2), jnp.repeat(k, rep, axis=2),
                   v, g, beta)
    y = rms_norm(o, p["gdn_norm"], dims["eps"]) * jax.nn.silu(z)
    return mm(y.reshape(b, t, nv), p["out_proj"])


def rotary(x, dims):
    """x [B, T, n, d]: the first `rotated` widths rotated (rotate-half),
    the rest passed through; angles in float64 on the host."""
    r, t = dims["rotated"], x.shape[1]
    inv = 1.0 / dims["rope_theta"] ** (np.arange(0, r, 2, dtype=np.float64)
                                       / r)
    ang = np.arange(t, dtype=np.float64)[:, None] * inv[None, :]
    ang = np.concatenate([ang, ang], axis=-1)
    cos = np.cos(ang).astype(np.float32)[None, :, None, :]
    sin = np.sin(ang).astype(np.float32)[None, :, None, :]
    xr, rest = x[..., :r], x[..., r:]
    rot = jnp.concatenate([-xr[..., r // 2:], xr[..., :r // 2]], axis=-1)
    return jnp.concatenate([xr * cos + rot * sin, rest], axis=-1)


def full_attention(x, p, dims):
    """x [B, T, D] normed -> [B, T, D]: gated full attention."""
    b, t, _ = x.shape
    h, kv, hd = dims["heads"], dims["kv_heads"], dims["head_dim"]
    g = h // kv
    qg = mm(x, p["q_proj"]).reshape(b, t, h, 2 * hd)
    q, gate = qg[..., :hd], qg[..., hd:]
    q = rotary(norm(q, p["q_norm"], dims["eps"]), dims)
    k = rotary(norm(mm(x, p["k_proj"]).reshape(b, t, kv, hd), p["k_norm"],
                    dims["eps"]), dims)
    v = mm(x, p["v_proj"]).reshape(b, t, kv, hd)
    mask = np.tril(np.ones((t, t), bool))

    def group(args):
        """One key-value head and the g query heads that read it."""
        qg_, kg, vg = args                  # [B, T, g, d], [B, T, d] twice
        s = _einsum("bqgd,bkd->bgqk", qg_, kg) * hd ** -0.5
        s = jnp.where(mask[None, None], s, -jnp.inf)
        return _einsum("bgqk,bkd->bqgd", jax.nn.softmax(s, axis=-1), vg)

    o = jax.lax.map(jax.checkpoint(group), (
        jnp.moveaxis(q.reshape(b, t, kv, g, hd), 2, 0),
        jnp.moveaxis(k, 2, 0), jnp.moveaxis(v, 2, 0)))    # [KV, B, T, g, d]
    o = jnp.moveaxis(o, 0, 2).reshape(b, t, h, hd) * jax.nn.sigmoid(gate)
    return mm(o.reshape(b, t, h * hd), p["o_proj"])


def swiglu(x, w1, w3, w2):
    return mm(jax.nn.silu(mm(x, w1)) * mm(x, w3), w2)


def shared_expert(x, p):
    """The shared expert under its sigmoid gate, one scalar a token."""
    return jax.nn.sigmoid(mm(x, p["shared_gate"])) * swiglu(
        x, p["shared_w1"], p["shared_w3"], p["shared_w2"])


def route(x, gate, dims):
    """(selected experts [N, k], their weights [N, k]) over every
    published expert; x [N, D]."""
    prob = jax.nn.softmax(jnp.matmul(x, gate, precision=PRECISION), axis=-1)
    sel = jnp.argsort(-prob, axis=-1, stable=True)[:, :dims["top_k"]]
    w = jnp.take_along_axis(prob, sel, axis=-1)
    return sel, w / jnp.sum(w, axis=-1, keepdims=True)


def routed_ffn(x, p, dims, experts_held=None, expert_offset=None):
    """x [B, T, D] -> (the held routed experts' part of the layer's output,
    pairs [held + 1]: (token, expert) pairs routed to each held expert and,
    last, to the experts not held). A loop over the held experts, each
    applied to every token and masked."""
    held = dims["experts_held"] if experts_held is None else experts_held
    off = dims["expert_offset"] if expert_offset is None else expert_offset
    shape = x.shape
    x = x.reshape(-1, shape[-1])
    sel, w = route(x, p["gate"], dims)
    y = jnp.zeros_like(x)
    pairs = []
    for e in range(held):
        hit = sel == (e + off)                            # [N, k]
        w_e = jnp.sum(jnp.where(hit, w, 0.0), axis=-1)    # 0 where not routed
        out = swiglu(x, p["experts_w1"][e], p["experts_w3"][e],
                     p["experts_w2"][e])
        y = y + w_e[:, None] * out
        pairs.append(jnp.sum(hit))
    pairs.append(sel.size - sum(pairs))
    return y.reshape(shape), jnp.stack(pairs).astype(jnp.int32)


def sparse_ffn(x, p, dims, experts_held=None, expert_offset=None):
    """The held routed experts' part plus the gated shared expert."""
    y, pairs = routed_ffn(x, p, dims, experts_held, expert_offset)
    return y + shared_expert(x, p), pairs


def block(x, p, dims, kind):
    """(output, pairs)."""
    mixer = linear_attention if kind == LINEAR else full_attention
    h = x + mixer(norm(x, p["attn_norm"], dims["eps"]), p, dims)
    z, pairs = sparse_ffn(norm(h, p["ffn_norm"], dims["eps"]), p, dims)
    return h + z, pairs


def forward_with_pairs(params, tokens, dims):
    """tokens [B, T] -> (logits [B, T, vocab] float32, pairs [layers,
    held + 1]). Each block is computed again in the backward pass."""
    x = params["embed"][tokens]
    pairs = []
    for i, (_src, kind) in enumerate(dims["layers"]):
        x, pr = jax.checkpoint(lambda x, p, kind=kind: block(
            x, p, dims, kind))(x, params[f"layer_{i}"])
        pairs.append(pr)
    logits = _einsum("btd,vd->btv",
                     norm(x, params["final_norm"], dims["eps"]),
                     params["head"])
    return logits, jnp.stack(pairs)


def forward(params, tokens, dims):
    return forward_with_pairs(params, tokens, dims)[0]


def _ce(logits, targets):
    lse = jax.nn.logsumexp(logits, axis=-1)
    return lse - jnp.take_along_axis(logits, targets[..., None],
                                     axis=-1)[..., 0]


def token_losses(params, rows, dims):
    """rows [B, T + 1] -> (cross-entropy [B, T] of each next token, arg-max
    hits [B, T], pairs)."""
    logits, pairs = forward_with_pairs(params, rows[:, :-1], dims)
    tgt = rows[:, 1:]
    return _ce(logits, tgt), jnp.argmax(logits, axis=-1) == tgt, pairs


def loss(params, rows, dims):
    """What a client minimises: the mean next-token cross-entropy over a
    batch of rows [B, T + 1]."""
    return jnp.mean(token_losses(params, rows, dims)[0])


def loss_and_grads(params, rows, dims):
    return jax.value_and_grad(loss)(params, rows, dims)


def client_update(params, batches, dims, lr, momentum, clip_norm=10.0):
    """A client's local SGD as the source runner has it: a fresh momentum
    buffer, then `sgd_step` per batch. Returns final - initial parameters."""
    p, buf = params, None
    for rows in batches:
        _l, g = loss_and_grads(p, rows, dims)
        p, buf = sgd_step(p, buf, g, lr, momentum, clip_norm)
    return jax.tree_util.tree_map(lambda a, b: a - b, p, params)


# ---- operations and bytes, from widths and the sequence length alone ------
def _linear_layers(dims) -> int:
    return sum(1 for _src, kind in dims["layers"] if kind == LINEAR)


def gdn_core_flops(n_tokens: float, config: Dict[str, Any]) -> float:
    """Forward operations (2 a multiply-add) of the delta rule in chunked
    form at chunk CHUNK, over every held linear layer, for `n_tokens`
    tokens: a value head and token `10 C d + 6 d^2 + (2/3) C^2`, d the key
    width (K K^T, Q K^T, the solve for W and U0, the intra-chunk product,
    W S, Q S and K^T U). The same whatever chunk or lowering a program
    takes; a reader multiplies by 3 for forward + backward."""
    d = dims_of(config)
    c, k = CHUNK, d["k_dim"]
    return float(_linear_layers(d) * d["v_heads"] * n_tokens
                 * (10 * c * k + 6 * k * k + 2.0 / 3.0 * c * c))


def gdn_core_bytes(n_tokens: float, config: Dict[str, Any]) -> float:
    """Bytes the core reads and writes once, over every held linear layer,
    for `n_tokens` tokens: q and k at the key heads and v at the value
    heads in the products' dtype (OPERAND_BYTES), g and beta float32 a
    value head, the output float32."""
    d = dims_of(config)
    qkv = (2 * d["k_heads"] * d["k_dim"] + d["v_heads"] * d["v_dim"])
    token = (qkv * OPERAND_BYTES + 2 * d["v_heads"] * 4
             + d["v_heads"] * d["v_dim"] * 4)
    return float(_linear_layers(d) * n_tokens * token)


def _linear_macs_per_token(d) -> float:
    """Multiply-adds of a token's linear-attention projections."""
    hid = d["hidden"]
    nk, nv = d["k_heads"] * d["k_dim"], d["v_heads"] * d["v_dim"]
    return hid * (2 * nk + 2 * nv) + hid * 2 * d["v_heads"] + nv * hid


def _full_macs_per_token(d) -> float:
    """Multiply-adds of a token's full layer: the q (with its gate), k, v
    and output products, and scores and values over the causal half."""
    hid, h, hd = d["hidden"], d["heads"], d["head_dim"]
    proj = hid * 2 * h * hd + 2 * hid * d["kv_heads"] * hd + h * hd * hid
    return proj + (d["seq_len"] + 1) / 2 * h * 2 * hd


def forward_flops_of(config: Dict[str, Any]) -> float:
    """One token's forward operations (2 a multiply-add) at the cut: every
    held layer's mixer (a linear layer's projections and its chunked core
    at `gdn_core_flops`, a full layer's projections and the causal half),
    the router, the gated shared expert, the head product over the rows
    held, and `num_experts_per_tok x held / published` routed experts a
    token: an expectation under even routing (the true count of a round is
    the program's `moe_pairs_held`). The convolution, norms and elementwise
    work are left out: shares computed from this read low, never over."""
    d = dims_of(config)
    hid = d["hidden"]
    routed = d["top_k"] * d["experts_held"] / d["router_experts"]
    moe = (hid * d["router_experts"] + 3 * hid * d["shared_ffn"] + hid
           + routed * 3 * hid * d["moe_ffn"])
    macs = d["vocab"] * hid                               # the head
    for _src, kind in d["layers"]:
        macs += moe + (_linear_macs_per_token(d) if kind == LINEAR
                       else _full_macs_per_token(d))
    return 2.0 * macs + gdn_core_flops(1, config)
