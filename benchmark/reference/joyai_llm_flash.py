"""Plain reference of the `joyai_llm_flash` block stack (the DeepSeek-V3
family's: latent attention, a sigmoid-routed mixture with a shared expert, a
multi-token-prediction module) as a federated client trains it: forward,
loss with the MTP term, gradients and a client's SGD loop in straightforward
`jax.numpy`, float32, matrix products at the highest precision. No kernel,
no sorting or grouped product, no blocking (a gradient computes each block
again in its backward pass, which changes no number); it shares no code
with the package (the parameter tree's names are the contract: `embed`,
`head`, `final_norm`, `layer_<i>/<name>`, `mtp_<k>/{embed_norm, hidden_norm,
eh_proj, final_norm, block/<name>}`).

Equations follow the source's config keys
(https://huggingface.co/jdopensource/JoyAI-LLM-Flash, `model_type:
"joyai_llm_flash"`) and the family's public implementation: RMSNorm without
unit offset; `h = x + attn(norm(x)); y = h + ffn(norm(h))`; MLA: `cq =
norm(x W_dq)`, `q = cq W_uq` split per head into `[q_nope | q_pe]`, `[ckv |
k_pe] = x W_dkv`, `[k_nope | v]` per head `= norm(ckv) W_ukv`, rotary
embedding over interleaved pairs of `q_pe` and of the one `k_pe` all heads
share, causal softmax at `qk_head_dim ** -0.5`; the first
`first_k_dense_replace` layers `W2(silu(W1 x) * W3 x)`; the others `s =
sigmoid(x W_g)`, `sel = top_k(s + b)`, `w = s[sel] / (sum s[sel] + 1e-20) *
routed_scaling_factor`, `y = sum_e w_e expert_e(x) + shared(x)`; final norm;
untied head. MTP module k: `u_i = W_eh [norm_e(embed(t_{i+k})) ;
norm_h(h_i)]` over the positions that have a token k + 1 on, one sparse
block, its own final norm, the main head; `loss = ce(main, t_{i+1}) +
lambda * mean_k ce(mtp_k, t_{i+k+1})`.

Departures from the source, each shared with the program:

- the score-correction bias `b` is a fixed function of layer and expert,
  `0.05 * sin(12.9898 * (n_routed_experts * layer + expert) + 1)` with the
  source's layer index (the MTP module's block: `num_hidden_layers` of the
  source, + k - 1); the source trains it by a load-balancing rule this
  system does not run, and publishes its values only with the weights.
- `lambda` 0.3 (the DeepSeek-V3 report's; the config gives none); the
  concatenation's order and where `h_i` is taken (before the final norm) are
  the family's implementation's.
- a sparse layer may be given a share of the routed experts (`experts_held`
  from `expert_offset`): routing, top-k and the normalisation run over every
  published expert, and only the held experts' terms are summed, plus the
  shared expert, which every share computes alike. The partial sum goes on
  to the next layer.
- a sliced vocabulary is a smaller vocabulary: ids, logits and loss are over
  the rows held, in the embedding and in the head.
- sequences are packed documents and attention is causal across the pack.
- weights are seeded random, float32; the source publishes bfloat16."""

from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

PRECISION = "highest"
BIAS_SCALE = 0.05
TOPK_EPS = 1e-20
# None: products take float32 operands. A narrower dtype rounds both
# operands of every product to it first (accumulation stays float32): set
# only to take the reading that places a check's limits, what this
# reference gives in the precision below the one the configuration states.
OPERAND_DTYPE = None


def dims_of(config: Dict[str, Any]) -> Dict[str, Any]:
    """What the functions below read, from a configuration file: the
    catalog's keys at the top level, the cut as the file states it
    (`layers_held`: the source's indices; `n_routed_experts` held from
    `expert_offset`; `vocab_size` held) and the router's published width
    and the source's depth under `published`."""
    pub = config.get("published", {})
    held = config.get("layers_held",
                      list(range(int(config["num_hidden_layers"]))))
    assert len(held) == int(config["num_hidden_layers"])
    first, freq = (int(config["first_k_dense_replace"]),
                   int(config.get("moe_layer_freq", 1)))
    return {
        "hidden": int(config["hidden_size"]),
        "dense_ffn": int(config["intermediate_size"]),
        "moe_ffn": int(config["moe_intermediate_size"]),
        "shared_ffn": int(config["n_shared_experts"])
        * int(config["moe_intermediate_size"]),
        "heads": int(config["num_attention_heads"]),
        "q_rank": int(config["q_lora_rank"]),
        "kv_rank": int(config["kv_lora_rank"]),
        "nope": int(config["qk_nope_head_dim"]),
        "rope": int(config["qk_rope_head_dim"]),
        "v_dim": int(config["v_head_dim"]),
        "router_experts": int(pub.get("n_routed_experts", {}).get(
            "source", config["n_routed_experts"])),
        "experts_held": int(config["n_routed_experts"]),
        "expert_offset": int(config.get("expert_offset", 0)),
        "top_k": int(config["num_experts_per_tok"]),
        "norm_topk": bool(config["norm_topk_prob"]),
        "routed_scale": float(config["routed_scaling_factor"]),
        "eps": float(config["rms_norm_eps"]),
        "theta": float(config["rope_theta"]),
        "vocab": int(config["vocab_size"]),
        "seq_len": int(config.get("seq_len", 0)),
        "layers": [(int(src), int(src) >= first and int(src) % freq == 0)
                   for src in held],
        "mtp_depth": int(config.get("num_nextn_predict_layers", 0)),
        "mtp_src_layer": int(pub.get("num_hidden_layers", {}).get(
            "source", config["num_hidden_layers"])),
        "mtp_weight": float(config.get("mtp_loss_weight", 0.3)),
    }


def expert_bias(dims, src_layer: int) -> np.ndarray:
    n = dims["router_experts"]
    e = np.arange(n, dtype=np.float64)
    return (BIAS_SCALE * np.sin(12.9898 * (n * src_layer + e) + 1.0)
            ).astype(np.float32)


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _operand(x):
    return (x if OPERAND_DTYPE is None
            else x.astype(OPERAND_DTYPE).astype(jnp.float32))


def mm(a, b):
    return jnp.matmul(_operand(a), _operand(b), precision=PRECISION)


def _einsum(spec, a, b):
    return jnp.einsum(spec, _operand(a), _operand(b), precision=PRECISION)


def rope_pairs(x, theta):
    """x [B, T, n, d]: dims (2i, 2i + 1) rotated by position x theta^(-2i/d)."""
    d, t = x.shape[-1], x.shape[1]
    inv = 1.0 / (theta ** (np.arange(0, d, 2, dtype=np.float64) / d))
    ang = np.arange(t, dtype=np.float64)[:, None] * inv[None, :]
    cos = np.cos(ang).astype(np.float32)[None, :, None, :]
    sin = np.sin(ang).astype(np.float32)[None, :, None, :]
    even, odd = x[..., 0::2], x[..., 1::2]
    out = jnp.zeros_like(x)
    out = out.at[..., 0::2].set(even * cos - odd * sin)
    return out.at[..., 1::2].set(odd * cos + even * sin)


def mla_attention(x, p, dims):
    b, t, _ = x.shape
    h, dn, dr, dv = dims["heads"], dims["nope"], dims["rope"], dims["v_dim"]
    cq = rms_norm(mm(x, p["q_a_proj"]), p["q_a_norm"], dims["eps"])
    q = mm(cq, p["q_b_proj"]).reshape(b, t, h, dn + dr)
    down = mm(x, p["kv_a_proj"])
    ckv, k_pe = down[..., :dims["kv_rank"]], down[..., dims["kv_rank"]:]
    kv = mm(rms_norm(ckv, p["kv_a_norm"], dims["eps"]),
            p["kv_b_proj"]).reshape(b, t, h, dn + dv)
    k_nope, v = kv[..., :dn], kv[..., dn:]
    q_pe = rope_pairs(q[..., dn:], dims["theta"])
    k_pe = rope_pairs(k_pe[:, :, None, :], dims["theta"])     # one for all
    s = (_einsum("bqhd,bkhd->bhqk", q[..., :dn], k_nope)
         + _einsum("bqhd,bkd->bhqk", q_pe, k_pe[:, :, 0])) * (dn + dr) ** -0.5
    causal = np.tril(np.ones((t, t), bool))
    s = jnp.where(causal[None, None], s, -jnp.inf)
    o = _einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v)
    return mm(o.reshape(b, t, h * dv), p["o_proj"])


def swiglu(x, w1, w3, w2):
    return mm(jax.nn.silu(mm(x, w1)) * mm(x, w3), w2)


def dense_ffn(x, p):
    return swiglu(x, p["w1"], p["w3"], p["w2"])


def shared_expert(x, p):
    return swiglu(x, p["shared_w1"], p["shared_w3"], p["shared_w2"])


def route(x, gate, dims, src_layer, use_bias=True):
    """(selected experts [N, k], their weights [N, k]) over every
    published expert; x [N, D]."""
    # the router's product is float32 in the program whatever --dtype says
    s = jax.nn.sigmoid(jnp.matmul(x, gate, precision=PRECISION))
    pick = s + expert_bias(dims, src_layer) if use_bias else s
    order = jnp.argsort(-pick, axis=-1, stable=True)
    sel = order[:, :dims["top_k"]]
    w = jnp.take_along_axis(s, sel, axis=-1)
    if dims["norm_topk"]:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + TOPK_EPS)
    return sel, w * dims["routed_scale"]


def routed_ffn(x, p, dims, src_layer, experts_held=None, expert_offset=None):
    """x [B, T, D] -> (the held routed experts' part of the layer's output,
    pairs [held + 1]: (token, expert) pairs routed to each held expert and,
    last, to the experts not held). A loop over the held experts, each
    applied to every token and masked."""
    held = dims["experts_held"] if experts_held is None else experts_held
    off = dims["expert_offset"] if expert_offset is None else expert_offset
    shape = x.shape
    x = x.reshape(-1, shape[-1])
    sel, w = route(x, p["gate"], dims, src_layer)
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


def sparse_ffn(x, p, dims, src_layer, experts_held=None, expert_offset=None):
    """The held routed experts' part plus the shared expert."""
    y, pairs = routed_ffn(x, p, dims, src_layer, experts_held, expert_offset)
    return y + shared_expert(x, p), pairs


def block(x, p, dims, src_layer, sparse):
    """(output, pairs or None)."""
    h = x + mla_attention(rms_norm(x, p["attn_norm"], dims["eps"]), p, dims)
    z = rms_norm(h, p["ffn_norm"], dims["eps"])
    if sparse:
        z, pairs = sparse_ffn(z, p, dims, src_layer)
        return h + z, pairs
    return h + dense_ffn(z, p), None


def _block_in_backward(x, p, dims, src_layer, sparse):
    """`block`, its inside computed again in the backward pass: the same
    numbers, and a gradient at the published widths keeps one block's
    scores (32 x 2048 x 2048 float32 and their softmax) and not six."""
    return jax.checkpoint(
        lambda x, p: block(x, p, dims, src_layer, sparse))(x, p)


def hidden_with_pairs(params, tokens, dims):
    """tokens [B, T] -> (the last block's output before the final norm,
    pairs [sparse layers, held + 1])."""
    x = params["embed"][tokens]
    pairs = []
    for i, (src, sparse) in enumerate(dims["layers"]):
        x, pr = _block_in_backward(x, params[f"layer_{i}"], dims, src, sparse)
        if sparse:
            pairs.append(pr)
    return x, (jnp.stack(pairs) if pairs else
               jnp.zeros((0, dims["experts_held"] + 1), jnp.int32))


def head(x, norm_w, params, dims):
    return _einsum("btd,vd->btv", rms_norm(x, norm_w, dims["eps"]),
                   params["head"])


def forward_with_pairs(params, tokens, dims):
    """The main model alone, as eval runs it: tokens [B, T] -> (logits
    [B, T, vocab] float32, pairs [sparse layers, held + 1])."""
    x, pairs = hidden_with_pairs(params, tokens, dims)
    return head(x, params["final_norm"], params, dims), pairs


def forward(params, tokens, dims):
    return forward_with_pairs(params, tokens, dims)[0]


def mtp_logits(params, hidden, tokens, dims, k=1):
    """Module k on the hidden states [B, T, D] of the module before it (the
    main model's for k = 1) and the same tokens [B, T]: (logits [B, T - k,
    vocab] for the tokens k + 1 on, its own hidden states [B, T - k, D])."""
    p = params[f"mtp_{k - 1}"]
    ahead = rms_norm(params["embed"][tokens[:, k:]], p["embed_norm"],
                     dims["eps"])
    here = rms_norm(hidden[:, :hidden.shape[1] - k], p["hidden_norm"],
                    dims["eps"])
    u = mm(jnp.concatenate([ahead, here], axis=-1), p["eh_proj"])
    y, _pairs = _block_in_backward(u, p["block"], dims,
                                   dims["mtp_src_layer"] + k - 1, True)
    return head(y, p["final_norm"], params, dims), y


def _ce(logits, targets):
    lse = jax.nn.logsumexp(logits, axis=-1)
    return lse - jnp.take_along_axis(logits, targets[..., None],
                                     axis=-1)[..., 0]


def token_losses(params, rows, dims):
    """rows [B, T + 1] -> (cross-entropy [B, T] of each next token, arg-max
    hits [B, T], pairs): the main model, as eval counts it."""
    logits, pairs = forward_with_pairs(params, rows[:, :-1], dims)
    tgt = rows[:, 1:]
    return _ce(logits, tgt), jnp.argmax(logits, axis=-1) == tgt, pairs


def loss_parts(params, rows, dims):
    """(mean next-token cross-entropy, mean over the MTP modules of their
    mean cross-entropy) over a batch of rows [B, T + 1]."""
    tokens = rows[:, :-1]
    hidden, _pairs = hidden_with_pairs(params, tokens, dims)
    main = jnp.mean(_ce(head(hidden, params["final_norm"], params, dims),
                        rows[:, 1:]))
    aux = []
    for k in range(1, dims["mtp_depth"] + 1):
        logits, hidden_k = mtp_logits(params, hidden, tokens, dims, k)
        aux.append(jnp.mean(_ce(logits, rows[:, 1 + k:])))
        # the next module reads this one's states; keep the length
        hidden = jnp.pad(hidden_k, ((0, 0), (0, k), (0, 0)))
    return main, (sum(aux) / len(aux) if aux else jnp.float32(0.0))


def loss(params, rows, dims):
    """What a client minimises: main + lambda x MTP."""
    main, aux = loss_parts(params, rows, dims)
    return main + dims["mtp_weight"] * aux


def loss_and_grads(params, rows, dims):
    return jax.value_and_grad(loss)(params, rows, dims)


def sgd_step(p, buf, g, lr, momentum, clip_norm=10.0):
    """One step of the source runner's client optimiser (`src/agent.py`):
    the gradient clipped to a global norm of `clip_norm` (torch's
    `clip_grad_norm_`, with its 1e-6), `buf = mu * buf + g; p = p - lr *
    buf`. `buf` None is a fresh, zero momentum buffer."""
    norm = jnp.sqrt(sum(jnp.sum(x * x) for x in jax.tree_util.tree_leaves(g)))
    scale = jnp.minimum(1.0, clip_norm / (norm + 1e-6))
    buf = (jax.tree_util.tree_map(lambda x: scale * x, g) if buf is None else
           jax.tree_util.tree_map(lambda b, x: momentum * b + scale * x,
                                  buf, g))
    return jax.tree_util.tree_map(lambda a, b: a - lr * b, p, buf), buf


def client_update(params, batches, dims, lr, momentum, clip_norm=10.0):
    """A client's local SGD as the source runner has it: a fresh momentum
    buffer, then `sgd_step` per batch. Returns final - initial parameters."""
    p, buf = params, None
    for rows in batches:
        _l, g = loss_and_grads(p, rows, dims)
        p, buf = sgd_step(p, buf, g, lr, momentum, clip_norm)
    return jax.tree_util.tree_map(lambda a, b: a - b, p, params)


def moe_expert_flops(pairs: float, dims) -> float:
    """Forward + backward operations of the held ROUTED experts' three
    products for `pairs` (token, expert) pairs: 3 matrices x 2 operations a
    multiply-add x 3 (backward is twice forward) x hidden x expert width.
    A function of pairs and widths only; recompute is not counted."""
    return 3.0 * 6.0 * dims["hidden"] * dims["moe_ffn"] * pairs


def _mla_macs_per_token(dims, seq_len: float) -> float:
    """Multiply-adds of one token in one block's latent attention: the five
    projections, and the causal half of the scores and of the weighted
    values (a token attends to (T + 1) / 2 positions on average)."""
    hid, h = dims["hidden"], dims["heads"]
    qk, dv = dims["nope"] + dims["rope"], dims["v_dim"]
    proj = (hid * dims["q_rank"] + dims["q_rank"] * h * qk
            + hid * (dims["kv_rank"] + dims["rope"])
            + dims["kv_rank"] * h * (dims["nope"] + dv) + h * dv * hid)
    return proj + (seq_len + 1) / 2 * h * (qk + dv)


def _mtp_share(dims, k: int) -> float:
    """The share of a sequence's positions module k's loss reads."""
    t = dims["seq_len"]
    return (t - k) / t if t else 1.0


def mla_attention_flops(tokens: float, dims) -> float:
    """Forward operations (2 a multiply-add) of latent attention in every
    block a TRAINING step runs, the MTP modules' counted on the positions
    their loss reads, for `tokens` tokens in sequences of
    `dims["seq_len"]`: projections and the causal half of scores and
    values; norms, rotary and softmax left out. A reader multiplies by 3
    for forward + backward."""
    blocks = len(dims["layers"]) + sum(
        _mtp_share(dims, k) for k in range(1, dims["mtp_depth"] + 1))
    return 2.0 * tokens * blocks * _mla_macs_per_token(dims, dims["seq_len"])


def forward_flops_of(config: Dict[str, Any]) -> float:
    """One token's forward operations in TRAINING (2 a multiply-add) at the
    cut: every held block's latent attention and feed-forward, each MTP
    module (its projection, block and second pass through the head), the
    router, the shared expert, both head products over the rows held, and
    `num_experts_per_tok x held / published` routed experts a token in a
    sparse layer: an expectation under even routing (the true count of a
    round is the program's `moe_pairs_held`). The MTP module is counted on
    T - k of T positions, what the loss reads, though the program runs it
    on all T; elementwise work is left out: shares computed from this read
    low, never over."""
    d = dims_of(config)
    hid, t = d["hidden"], d["seq_len"]
    routed = d["top_k"] * d["experts_held"] / d["router_experts"]
    attn = _mla_macs_per_token(d, t)
    sparse = (hid * d["router_experts"] + 3 * hid * d["shared_ffn"]
              + routed * 3 * hid * d["moe_ffn"])
    macs = d["vocab"] * hid                               # the head
    for _src, is_sparse in d["layers"]:
        macs += attn + (sparse if is_sparse else 3 * hid * d["dense_ffn"])
    for k in range(1, d["mtp_depth"] + 1):
        macs += _mtp_share(d, k) * (2 * hid * hid + attn + sparse + d["vocab"] * hid)
    return 2.0 * macs
