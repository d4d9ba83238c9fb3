"""Plain reference of the `lfm2_moe` block stack as a federated client
trains it: forward, next-token loss, gradients and a client's SGD loop in
straightforward `jax.numpy`, float32, matrix products at the highest
precision. No kernel, no sorting or grouped product, no recompute, no
blocking; it shares no code with the package (the parameter tree's names
are the contract: `embed`, `final_norm`, `layer_<i>/<name>`).

Equations follow the source's `lfm2_moe` implementation
(https://huggingface.co/LiquidAI/LFM2-8B-A1B, `model_type: "lfm2_moe"`):
RMSNorm without unit offset; `h = x + op(norm(x)); y = h + ffn(norm(h))`;
`conv`: `B, C, X = split3(x W_in)`, `y = (C * causal_depthwise_conv(B * X))
W_out`; `full_attention`: q/k/v without bias, RMSNorm over each head of q and
k, rotate-half rotary embedding over the whole head, causal softmax at
`head_dim ** -0.5`, grouped key-value heads; dense `W2(silu(W1 x) * W3 x)`;
sparse: `s = sigmoid(x W_g)`, `sel = top_k(s + b)`, `w = s[sel] / (sum
s[sel] + 1e-6)`, `y = sum_e w_e expert_e(x)`; final norm; tied head.

Departures from the source, each shared with the program:

- the expert bias `b` is a fixed function of layer and expert, `0.05 *
  sin(12.9898 * (num_experts * layer + expert) + 1)` with the source's layer
  index; the source trains it by a load-balancing rule this system does not
  run, and publishes its values only with the weights. No auxiliary loss.
- a sparse layer may be given a share of the experts (`experts_held` from
  `expert_offset`): routing, top-k and the normalisation run over every
  published expert, and only the held experts' terms of `y` are summed.
  The partial sum goes on to the next layer.
- a sliced vocabulary is a smaller vocabulary: ids, logits and loss are over
  the rows held.
- sequences are packed documents and attention is causal across the pack.
- weights are seeded random, float32; the source publishes bfloat16."""

from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

PRECISION = "highest"
EXPERT_BIAS_SCALE = 0.05
# None: products take float32 operands. A narrower dtype rounds both
# operands of every product to it first (accumulation stays float32): set
# only to take the reading that places a check's limits, what this
# reference gives in the precision below the one the configuration states
# (PERF.md section 6, PR 27).
OPERAND_DTYPE = None


def dims_of(config: Dict[str, Any]) -> Dict[str, Any]:
    """What the functions below read, from a configuration file: the
    catalog's keys at the top level, the cut as the file states it
    (`layers_held`: the source's indices; `num_experts` held from
    `expert_offset`; `vocab_size` held) and the router's published width
    under `published`."""
    pub = config.get("published", {})
    held = config.get("layers_held",
                      list(range(int(config["num_hidden_layers"]))))
    types = list(config["layer_types"])
    assert len(types) == len(held) == int(config["num_hidden_layers"])
    heads = int(config["num_attention_heads"])
    return {
        "hidden": int(config["hidden_size"]),
        "dense_ffn": int(config["intermediate_size"]),
        "moe_ffn": int(config["moe_intermediate_size"]),
        "heads": heads, "kv_heads": int(config["num_key_value_heads"]),
        "head_dim": int(config.get("head_dim",
                                   int(config["hidden_size"]) // heads)),
        "taps": int(config["conv_L_cache"]),
        "router_experts": int(pub.get("num_experts", {}).get(
            "source", config["num_experts"])),
        "experts_held": int(config["num_experts"]),
        "expert_offset": int(config.get("expert_offset", 0)),
        "top_k": int(config["num_experts_per_tok"]),
        "norm_topk": bool(config["norm_topk_prob"]),
        "routed_scale": float(config["routed_scaling_factor"]),
        "use_bias": bool(config["use_expert_bias"]),
        "eps": float(config["norm_eps"]),
        "theta": float(config["rope_theta"]),
        "vocab": int(config["vocab_size"]),
        "seq_len": int(config.get("seq_len", 0)),
        "layers": [(int(src), str(kind), i >= int(config["num_dense_layers"]))
                   for i, (src, kind) in enumerate(zip(held, types))],
    }


def expert_bias(dims, src_layer: int) -> np.ndarray:
    n = dims["router_experts"]
    e = np.arange(n, dtype=np.float64)
    return (EXPERT_BIAS_SCALE * np.sin(12.9898 * (n * src_layer + e) + 1.0)
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


def short_conv(x, p, dims):
    """x [B, T, D] -> [B, T, D]."""
    b_, c_, x_ = jnp.split(mm(x, p["conv_in_proj"]), 3, axis=-1)
    bx = b_ * x_
    taps, t = dims["taps"], x.shape[1]
    conv = jnp.zeros_like(bx)
    for j in range(taps):
        shift = taps - 1 - j          # tap j reads the input `shift` back
        moved = jnp.pad(bx, ((0, 0), (shift, 0), (0, 0)))[:, :t]
        conv = conv + moved * p["conv_weight"][j]
    return mm(c_ * conv, p["conv_out_proj"])


def rope(x, theta):
    """x [B, T, n, d]: rotate-half over the whole head."""
    d, t = x.shape[-1], x.shape[1]
    inv = 1.0 / (theta ** (np.arange(0, d, 2, dtype=np.float64) / d))
    ang = np.arange(t, dtype=np.float64)[:, None] * inv[None, :]
    ang = np.concatenate([ang, ang], axis=-1)[None, :, None, :]
    cos, sin = np.cos(ang).astype(np.float32), np.sin(ang).astype(np.float32)
    rot = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], axis=-1)
    return x * cos + rot * sin


def attention(x, p, dims):
    b, t, _ = x.shape
    h, kv, hd = dims["heads"], dims["kv_heads"], dims["head_dim"]
    q = mm(x, p["q_proj"]).reshape(b, t, h, hd)
    k = mm(x, p["k_proj"]).reshape(b, t, kv, hd)
    v = mm(x, p["v_proj"]).reshape(b, t, kv, hd)
    q = rope(rms_norm(q, p["q_norm"], dims["eps"]), dims["theta"])
    k = rope(rms_norm(k, p["k_norm"], dims["eps"]), dims["theta"])
    k = jnp.repeat(k, h // kv, axis=2)      # head j reads kv head j // g
    v = jnp.repeat(v, h // kv, axis=2)
    s = _einsum("bqhd,bkhd->bhqk", q, k) * hd ** -0.5
    causal = np.tril(np.ones((t, t), bool))
    s = jnp.where(causal[None, None], s, -jnp.inf)
    o = _einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v)
    return mm(o.reshape(b, t, h * hd), p["o_proj"])


def dense_ffn(x, p):
    return mm(jax.nn.silu(mm(x, p["w1"])) * mm(x, p["w3"]), p["w2"])


def route(x, gate, dims, src_layer):
    """(selected experts [N, k], their weights [N, k]) over every
    published expert; x [N, D]."""
    # the router's product is float32 in the program whatever --dtype says
    s = jax.nn.sigmoid(jnp.matmul(x, gate, precision=PRECISION))
    pick = s + expert_bias(dims, src_layer) if dims["use_bias"] else s
    order = jnp.argsort(-pick, axis=-1, stable=True)
    sel = order[:, :dims["top_k"]]
    w = jnp.take_along_axis(s, sel, axis=-1)
    if dims["norm_topk"]:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-6)
    return sel, w * dims["routed_scale"]


def sparse_ffn(x, p, dims, src_layer, experts_held=None, expert_offset=None):
    """x [B, T, D] -> (the held experts' part of the layer's output, pairs
    [held + 1]: (token, expert) pairs routed to each held expert and, last,
    to the experts not held). A loop over the held experts, each applied to
    every token and masked."""
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
        out = mm(jax.nn.silu(mm(x, p["experts_w1"][e]))
                 * mm(x, p["experts_w3"][e]), p["experts_w2"][e])
        y = y + w_e[:, None] * out
        pairs.append(jnp.sum(hit))
    pairs.append(sel.size - sum(pairs))
    return y.reshape(shape), jnp.stack(pairs).astype(jnp.int32)


def forward_with_pairs(params, tokens, dims):
    """tokens [B, T] -> (logits [B, T, vocab] float32, pairs [sparse
    layers, held + 1])."""
    eps = dims["eps"]
    x = params["embed"][tokens]
    pairs = []
    for i, (src, kind, sparse) in enumerate(dims["layers"]):
        p = params[f"layer_{i}"]
        y = rms_norm(x, p["operator_norm"], eps)
        y = short_conv(y, p, dims) if kind == "conv" else attention(y, p, dims)
        h = x + y
        z = rms_norm(h, p["ffn_norm"], eps)
        if sparse:
            z, pr = sparse_ffn(z, p, dims, src)
            pairs.append(pr)
        else:
            z = dense_ffn(z, p)
        x = h + z
    x = rms_norm(x, params["final_norm"], eps)
    logits = _einsum("btd,vd->btv", x, params["embed"])
    return logits, (jnp.stack(pairs) if pairs else
                    jnp.zeros((0, dims["experts_held"] + 1), jnp.int32))


def forward(params, tokens, dims):
    return forward_with_pairs(params, tokens, dims)[0]


def token_losses(params, rows, dims):
    """rows [B, T + 1] -> (cross-entropy [B, T] of each next token, arg-max
    hits [B, T], pairs)."""
    logits, pairs = forward_with_pairs(params, rows[:, :-1], dims)
    tgt = rows[:, 1:]
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, tgt[..., None], axis=-1)[..., 0]
    return lse - picked, jnp.argmax(logits, axis=-1) == tgt, pairs


def loss(params, rows, dims):
    """Mean next-token cross-entropy over a batch of rows [B, T + 1]."""
    return jnp.mean(token_losses(params, rows, dims)[0])


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
    """Forward + backward operations of the held experts' three products
    for `pairs` (token, expert) pairs: 3 matrices x 2 operations a
    multiply-add x 3 (backward is twice forward) x hidden x expert width.
    A function of pairs and widths only, whatever computes the products;
    recompute is not counted."""
    return 3.0 * 6.0 * dims["hidden"] * dims["moe_ffn"] * pairs


def forward_flops_of(config: Dict[str, Any]) -> float:
    """One token's forward operations (2 a multiply-add) at the cut: the
    operators' and feed-forwards' products, the router, the tied head over
    the rows held, causal attention over half the sequence on average, and
    `num_experts_per_tok x held / published` experts a token in a sparse
    layer: an expectation under even routing (the true count of a round is
    the program's `moe_pairs_held`). Elementwise work (norms, gates,
    softmax, the convolution's taps) is left out."""
    d = dims_of(config)
    hid = d["hidden"]
    per_token_experts = d["top_k"] * d["experts_held"] / d["router_experts"]
    macs = d["vocab"] * hid
    for _src, kind, sparse in d["layers"]:
        if kind == "conv":
            macs += 4 * hid * hid
        else:
            qo = 2 * hid * d["heads"] * d["head_dim"]
            kv = 2 * hid * d["kv_heads"] * d["head_dim"]
            scores = (d["seq_len"] + 1) / 2 * d["head_dim"] * d["heads"] * 2
            macs += qo + kv + scores
        if sparse:
            macs += hid * d["router_experts"]
            macs += per_token_experts * 3 * hid * d["moe_ffn"]
        else:
            macs += 3 * hid * d["dense_ffn"]
    return 2.0 * macs
