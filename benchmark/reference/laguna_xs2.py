"""Plain reference of the `laguna` block stack (sliding-window and full
attention mixed by layer, query heads and rotary embedding by layer kind, a
per-head output gate, a sigmoid-routed mixture with a shared expert) as a
federated client trains it: forward, loss, gradients and a client's SGD loop
in straightforward `jax.numpy`, float32, matrix products at the highest
precision. No kernel, no sorting or grouped product, no query blocks and no
skipped squares: every layer kind masks a full `[T, T]` score matrix. One
key-value head's group of query heads is scored at a time and computed again
in the backward pass (as is every block), so that 4096 x 4096 float32 scores
of 8 heads fit beside the parameters; neither changes a number. It shares no
code with the package (the parameter tree's names are the contract: `embed`,
`head`, `final_norm`, `layer_<i>/<name>`).

Equations follow the source's config keys
(https://huggingface.co/poolside/Laguna-XS.2, `model_type: "laguna"`):
RMSNorm without unit offset; `h = x + attn(norm(x)); y = h + ffn(norm(h))`;
layer l has `num_attention_heads_per_layer[l]` query heads over
`num_key_value_heads` key-value heads of `head_dim`, head h reading
key-value head `h // (H_l / KV)`; rotate-half rotary embedding over the
first `partial_rotary_factor x head_dim` widths of q and k by layer kind
(`rope_parameters`): plain frequencies on `sliding_attention` layers, YaRN's
(`transformers`' `_compute_yarn_parameters`) with its attention factor on
cos and sin on `full_attention` layers; `softmax(q k^T / sqrt(head_dim) +
mask) v`, the mask causal, and on sliding layers also `r - c <
sliding_window`; `g = sigmoid(z W_g)` one scalar a head and token on each
head's output; `W_o`. `mlp_layer_types[l]` `dense`: `W2(silu(W1 z) * W3
z)`; `sparse`: `s = sigmoid(z W_r)`, the top `num_experts_per_tok` of the
scores, `w = s_top / (sum s_top + 1e-20) * moe_routed_scaling_factor`, `y =
sum_e w_e expert_e(z) + shared(z)`. Final norm; untied head; next-token
cross-entropy.

Departures from the source, each shared with the program:

- what the config has no key for is read as its file's `assumed` says: the
  output gate's form, sigmoid scores normalised over the selected, no
  correction bias, no q/k norm, the rotated widths first in a head.
- a sparse layer may be given a share of the routed experts (`experts_held`
  from `expert_offset`): routing, top-k and the normalisation run over every
  published expert, and only the held experts' terms are summed, plus the
  shared expert, which every share computes alike. The partial sum goes on
  to the next layer.
- a sliced vocabulary is a smaller vocabulary: ids, logits and loss are over
  the rows held, in the embedding and in the head.
- sequences are packed documents; attention is causal (and windowed) across
  the pack, positions count from 0 in the sequence.
- weights are seeded random, float32; the source publishes bfloat16."""

from __future__ import annotations

import math
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

PRECISION = "highest"
TOPK_EPS = 1e-20
WINDOW, FULL = "sliding_attention", "full_attention"
# None: products take float32 operands. A narrower dtype rounds both
# operands of every product to it first (accumulation stays float32): set
# only to take the reading that places a check's limits, what this
# reference gives in the precision below the one the configuration states.
OPERAND_DTYPE = None


def dims_of(config: Dict[str, Any]) -> Dict[str, Any]:
    """What the functions below read, from a configuration file: the
    catalog's keys at the top level, the cut as the file states it (the
    three per-layer lists hold the held layers' entries, `layers_held` the
    source's indices; `num_experts` held from `expert_offset`; `vocab_size`
    held) and the router's published width under `published`."""
    pub = config.get("published", {})
    n = int(config["num_hidden_layers"])
    held = config.get("layers_held", list(range(n)))
    kinds, ffns, heads = (config["layer_types"], config["mlp_layer_types"],
                          config["num_attention_heads_per_layer"])
    assert len(held) == len(kinds) == len(ffns) == len(heads) == n
    return {
        "hidden": int(config["hidden_size"]),
        "dense_ffn": int(config["intermediate_size"]),
        "moe_ffn": int(config["moe_intermediate_size"]),
        "shared_ffn": int(config["shared_expert_intermediate_size"]),
        "kv_heads": int(config["num_key_value_heads"]),
        "head_dim": int(config["head_dim"]),
        "window": int(config["sliding_window"]),
        "rope": {kind: dict(config["rope_parameters"][kind])
                 for kind in (WINDOW, FULL)
                 if kind in config["rope_parameters"]},
        "router_experts": int(pub.get("num_experts", {}).get(
            "source", config["num_experts"])),
        "experts_held": int(config["num_experts"]),
        "expert_offset": int(config.get("expert_offset", 0)),
        "top_k": int(config["num_experts_per_tok"]),
        "routed_scale": float(config["moe_routed_scaling_factor"]),
        "eps": float(config["rms_norm_eps"]),
        "vocab": int(config["vocab_size"]),
        "seq_len": int(config.get("seq_len", 0)),
        # (source index, layer kind, query heads, sparse?) per held layer
        "layers": [(int(src), str(kind), int(h), ffn == "sparse")
                   for src, kind, h, ffn in zip(held, kinds, heads, ffns)],
    }


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _operand(x):
    return (x if OPERAND_DTYPE is None
            else x.astype(OPERAND_DTYPE).astype(jnp.float32))


def mm(a, b):
    return jnp.matmul(_operand(a), _operand(b), precision=PRECISION)


def _einsum(spec, a, b):
    return jnp.einsum(spec, _operand(a), _operand(b), precision=PRECISION)


def rotated_widths(rope: Dict[str, Any], head_dim: int) -> int:
    return int(head_dim * float(rope.get("partial_rotary_factor", 1)))


def yarn_range(rope: Dict[str, Any], dim: int):
    """(low, high) of YaRN's ramp over the `dim` rotated widths."""
    base, orig = float(rope["rope_theta"]), float(
        rope["original_max_position_embeddings"])

    def correction_dim(rotations):
        return (dim * math.log(orig / (rotations * 2 * math.pi))
                / (2 * math.log(base)))

    return (max(math.floor(correction_dim(float(rope.get("beta_fast", 32)))),
                0),
            min(math.ceil(correction_dim(float(rope.get("beta_slow", 1)))),
                dim - 1))


def inv_freq(rope: Dict[str, Any], head_dim: int) -> np.ndarray:
    """float64 inverse frequencies of the rotated pairs: `theta^(-2i/R)`,
    under `rope_type` `yarn` those divided by `factor` blended in over the
    ramp between `low` and `high`."""
    dim = rotated_widths(rope, head_dim)
    pos = float(rope["rope_theta"]) ** (
        np.arange(0, dim, 2, dtype=np.float64) / dim)
    if rope.get("rope_type", "default") == "default":
        return 1.0 / pos
    assert rope["rope_type"] == "yarn", rope["rope_type"]
    low, high = yarn_range(rope, dim)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low)
                   / (high - low), 0.0, 1.0)
    return 1.0 / (float(rope["factor"]) * pos) * ramp + 1.0 / pos * (1 - ramp)


def rope_scale(rope: Dict[str, Any]) -> float:
    """What cos and sin are multiplied by: 1, or YaRN's attention factor
    (`0.1 ln(factor) + 1` where the file gives none)."""
    if rope.get("rope_type", "default") == "default":
        return 1.0
    return float(rope.get("attention_factor")
                 or 0.1 * math.log(float(rope["factor"])) + 1.0)


def rotary(x, rope: Dict[str, Any]):
    """x [B, T, n, d]: the first R widths rotated (rotate-half), the rest
    passed through; angles in float64 on the host."""
    d, t = x.shape[-1], x.shape[1]
    inv = inv_freq(rope, d)
    r = 2 * len(inv)
    ang = np.arange(t, dtype=np.float64)[:, None] * inv[None, :]
    ang = np.concatenate([ang, ang], axis=-1)
    scale = rope_scale(rope)
    cos = (np.cos(ang) * scale).astype(np.float32)[None, :, None, :]
    sin = (np.sin(ang) * scale).astype(np.float32)[None, :, None, :]
    xr, rest = x[..., :r], x[..., r:]
    rot = jnp.concatenate([-xr[..., r // 2:], xr[..., :r // 2]], axis=-1)
    return jnp.concatenate([xr * cos + rot * sin, rest], axis=-1)


def score_mask(t: int, kind: str, window: int) -> np.ndarray:
    """[T, T] bool: query r may read key c."""
    r, c = np.arange(t)[:, None], np.arange(t)[None, :]
    keep = c <= r
    return keep & (r - c < window) if kind == WINDOW else keep


def attention(x, p, dims, kind):
    """x [B, T, D] normed -> [B, T, D]; the layer's heads from `q_proj`."""
    b, t, _ = x.shape
    kv, hd = dims["kv_heads"], dims["head_dim"]
    h = p["q_proj"].shape[1] // hd
    g = h // kv
    rope = dims["rope"][kind]
    q = rotary(mm(x, p["q_proj"]).reshape(b, t, h, hd), rope)
    k = rotary(mm(x, p["k_proj"]).reshape(b, t, kv, hd), rope)
    v = mm(x, p["v_proj"]).reshape(b, t, kv, hd)
    mask = score_mask(t, kind, dims["window"])

    def group(args):
        """One key-value head and the g query heads that read it."""
        qg, kg, vg = args                   # [B, T, g, d], [B, T, d] twice
        s = _einsum("bqgd,bkd->bgqk", qg, kg) * hd ** -0.5
        s = jnp.where(mask[None, None], s, -jnp.inf)
        return _einsum("bgqk,bkd->bqgd", jax.nn.softmax(s, axis=-1), vg)

    o = jax.lax.map(jax.checkpoint(group), (
        jnp.moveaxis(q.reshape(b, t, kv, g, hd), 2, 0),
        jnp.moveaxis(k, 2, 0), jnp.moveaxis(v, 2, 0)))    # [KV, B, T, g, d]
    o = jnp.moveaxis(o, 0, 2).reshape(b, t, h, hd)
    gate = jax.nn.sigmoid(mm(x, p["g_proj"]))             # [B, T, H]
    return mm((o * gate[..., None]).reshape(b, t, h * hd), p["o_proj"])


def swiglu(x, w1, w3, w2):
    return mm(jax.nn.silu(mm(x, w1)) * mm(x, w3), w2)


def dense_ffn(x, p):
    return swiglu(x, p["w1"], p["w3"], p["w2"])


def shared_expert(x, p):
    return swiglu(x, p["shared_w1"], p["shared_w3"], p["shared_w2"])


def route(x, gate, dims):
    """(selected experts [N, k], their weights [N, k]) over every
    published expert; x [N, D]."""
    # the router's product is float32 in the program whatever --dtype says
    s = jax.nn.sigmoid(jnp.matmul(x, gate, precision=PRECISION))
    order = jnp.argsort(-s, axis=-1, stable=True)
    sel = order[:, :dims["top_k"]]
    w = jnp.take_along_axis(s, sel, axis=-1)
    w = w / (jnp.sum(w, axis=-1, keepdims=True) + TOPK_EPS)
    return sel, w * dims["routed_scale"]


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
    """The held routed experts' part plus the shared expert."""
    y, pairs = routed_ffn(x, p, dims, experts_held, expert_offset)
    return y + shared_expert(x, p), pairs


def block(x, p, dims, kind, sparse):
    """(output, pairs or None)."""
    h = x + attention(rms_norm(x, p["attn_norm"], dims["eps"]), p, dims, kind)
    z = rms_norm(h, p["ffn_norm"], dims["eps"])
    if sparse:
        z, pairs = sparse_ffn(z, p, dims)
        return h + z, pairs
    return h + dense_ffn(z, p), None


def _block_in_backward(x, p, dims, kind, sparse):
    """`block`, its inside computed again in the backward pass: the same
    numbers, and a gradient at the published widths keeps one block's
    activations and not five."""
    return jax.checkpoint(
        lambda x, p: block(x, p, dims, kind, sparse))(x, p)


def forward_with_pairs(params, tokens, dims):
    """tokens [B, T] -> (logits [B, T, vocab] float32, pairs [sparse
    layers, held + 1])."""
    x = params["embed"][tokens]
    pairs = []
    for i, (_src, kind, _heads, sparse) in enumerate(dims["layers"]):
        x, pr = _block_in_backward(x, params[f"layer_{i}"], dims, kind,
                                   sparse)
        if sparse:
            pairs.append(pr)
    logits = _einsum("btd,vd->btv",
                     rms_norm(x, params["final_norm"], dims["eps"]),
                     params["head"])
    return logits, (jnp.stack(pairs) if pairs else
                    jnp.zeros((0, dims["experts_held"] + 1), jnp.int32))


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


# ---- operations, from widths and the sequence length alone ----------------
def moe_expert_flops(pairs: float, dims) -> float:
    """Forward + backward operations of the held ROUTED experts' three
    products for `pairs` (token, expert) pairs: 3 matrices x 2 operations a
    multiply-add x 3 (backward is twice forward) x hidden x expert width.
    A function of pairs and widths only; recompute is not counted."""
    return 3.0 * 6.0 * dims["hidden"] * dims["moe_ffn"] * pairs


def keys_per_query(seq_len: float, kind: str, window: int) -> float:
    """Keys a query reads, the mean over a sequence's positions: on the
    band `sum_r min(r + 1, window) / T` for a window layer, the causal half
    `(T + 1) / 2` for a full layer."""
    if kind == WINDOW and seq_len > window:
        return (window * (window + 1) / 2
                + (seq_len - window) * window) / seq_len
    return (seq_len + 1) / 2


def _attention_macs_per_token(dims, kind: str, heads: int) -> float:
    """Multiply-adds of one token in one layer's attention: the q, k, v,
    gate and output products, and the scores and weighted values over the
    keys the mask lets it read."""
    hid, hd = dims["hidden"], dims["head_dim"]
    proj = (2 * hid * heads * hd + 2 * hid * dims["kv_heads"] * hd
            + hid * heads)
    return proj + keys_per_query(dims["seq_len"], kind, dims["window"]) \
        * heads * 2 * hd


def _kind_flops(tokens: float, dims, kind: str) -> float:
    return 2.0 * tokens * sum(
        _attention_macs_per_token(dims, k, h)
        for _src, k, h, _sparse in dims["layers"] if k == kind)


def window_attention_flops(tokens: float, dims) -> float:
    """Forward operations (2 a multiply-add) of every held
    `sliding_attention` layer for `tokens` tokens in sequences of
    `dims["seq_len"]`: projections, gate, and scores and values ON THE BAND
    (what the mask keeps, whatever squares a program forms); norms, rotary
    and softmax left out. A reader multiplies by 3 for forward + backward."""
    return _kind_flops(tokens, dims, WINDOW)


def full_attention_flops(tokens: float, dims) -> float:
    """The same for every held `full_attention` layer, scores and values on
    the causal half."""
    return _kind_flops(tokens, dims, FULL)


def forward_flops_of(config: Dict[str, Any]) -> float:
    """One token's forward operations (2 a multiply-add) at the cut: every
    held layer's attention (window layers on the band, full layers on the
    causal half) and feed-forward, the router, the shared expert, the head
    product over the rows held, and `num_experts_per_tok x held /
    published` routed experts a token in a sparse layer: an expectation
    under even routing (the true count of a round is the program's
    `moe_pairs_held`). Elementwise work and the embedding's gather are left
    out: shares computed from this read low, never over."""
    d = dims_of(config)
    hid = d["hidden"]
    routed = d["top_k"] * d["experts_held"] / d["router_experts"]
    sparse = (hid * d["router_experts"] + 3 * hid * d["shared_ffn"]
              + routed * 3 * hid * d["moe_ffn"])
    macs = d["vocab"] * hid                               # the head
    for _src, kind, heads, is_sparse in d["layers"]:
        macs += _attention_macs_per_token(d, kind, heads)
        macs += sparse if is_sparse else 3 * hid * d["dense_ffn"]
    return 2.0 * macs
