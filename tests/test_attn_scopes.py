"""The seams of attention as the program marks them (PR 36): in every token
model the attention core (`token_ops.causal_attention`) runs under the scope
`attn_core` and the rotary step under `attn_rope`, both inside the layer's
own scope, which keeps the projections and the norms; and the counter
`attn_core_flops{kind}` each model's `build_counters` gives, against a count
made here, query by query, from the reference's `dims_of` at the benchmark
cells' shapes. On the CPU, from the lowered program's `op_name`s: nothing
here is a time."""

import os
import re
from collections import Counter

import jax
import jax.numpy as jnp
import pytest

from benchmark import registry
from defending_against_backdoors_with_robust_learning_rate_tpu.config import (
    args_parser)
from defending_against_backdoors_with_robust_learning_rate_tpu.models import (
    lfm2_moe as lm, mla_moe as mm, swa_moe as sm)

DATA = os.path.join(os.path.dirname(__file__), "data")
T = 32
# model -> (the tiny model, the scopes it opens around a whole layer)
TINY = {
    "lfm2_moe": (lambda: lm.LFM2MoE(spec=lm.spec_from(
        os.path.join(DATA, "lm_tiny.json"), "1,2,3", 4, 0, 96),
        dtype=jnp.bfloat16, remat=True), ("attention",)),
    "mla_moe": (lambda: mm.MlaMoE(spec=mm.spec_from(
        os.path.join(DATA, "mla_tiny.json"), "0,1,2", 0, 0, 0),
        dtype=jnp.bfloat16, remat=True), ("mla_attention",)),
    "swa_moe": (lambda: sm.SwaMoE(spec=sm.spec_from(
        os.path.join(DATA, "swa_tiny.json"), "0,1,2,3,4", 4, 0, 96),
        dtype=jnp.bfloat16, remat=True),
        ("window_attention", "global_attention")),
}
OP = re.compile(r" = .*? ([a-z][a-z0-9-]*)\(.*op_name=\"([^\"]*)\"")
# name, dims, opcode and operands of an instruction
DEF = re.compile(r"^\s*(?:ROOT )?(\S+) = \w+\[([\d,]*)\]\S* "
                 r"([a-z][a-z0-9-]*)\(([^)]*)\)")


def _lowered(model):
    """The lowered training gradient, forward, recompute and backward, in
    HLO before XLA's passes."""
    tokens = jnp.zeros((2, T), jnp.int32)
    params = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0), tokens,
                                               train=True))

    def loss(p, t):
        out = model.apply(p, t, train=True)
        return sum(jnp.sum(x.astype(jnp.float32))
                   for x in (out[0],) + tuple(out[2] if len(out) > 2 else ()))

    return jax.jit(jax.grad(loss)).lower(params, tokens).as_text(
        dialect="hlo", debug_info=True)


def _ops(text):
    """(opcode, op_name) of every operation."""
    return [m.groups() for m in map(OP.search, text.splitlines()) if m]


def _rope_products(text):
    """(opcode of the right operand, its dims, the product's width) of
    every `dot` under `attn_rope`."""
    lines = [(DEF.match(line), line) for line in text.splitlines()]
    defs = {m.group(1): m for m, _line in lines if m}
    out = []
    for m, line in lines:
        on = OP.search(line)
        if m and m.group(3) == "dot" and on and _under(on.group(2),
                                                      "attn_rope"):
            rhs = defs[m.group(4).split(", ")[1]]
            out.append((rhs.group(3), rhs.group(2),
                        m.group(2).split(",")[-1]))
    return out


def _under(op_name, scope):
    return any(re.sub(r"^(\w+\()+|\)+$", "", part) == scope
               for part in op_name.split("/"))


@pytest.fixture(scope="module", params=sorted(TINY))
def lowered(request):
    make, layer_scopes = TINY[request.param]
    text = _lowered(make())
    return request.param, _ops(text), layer_scopes, _rope_products(text)


def test_the_core_and_the_rotary_step_lie_under_their_scopes(lowered):
    name, ops, layer_scopes, products = lowered
    core = Counter(op for op, n in ops if _under(n, "attn_core"))
    rope = Counter(op for op, n in ops if _under(n, "attn_rope"))
    # the core's two products and its softmax, the rotary's tables
    assert core["dot"] >= 2 and core["exponential"] >= 1, core
    assert rope["multiply"] >= 2, rope
    trig = [n for op, n in ops if op in ("cosine", "sine")]
    if name == "mla_moe":
        # its rotary pairs take their angles in the program
        assert trig and all(_under(n, "attn_rope") for n in trig)
        assert not products, products
    else:
        # token_ops.rope_half: cos and sin are constant tables, and the
        # rotation is a product with a constant [d, d] (forward; inside
        # the recomputed block and backward it arrives as an operand)
        assert not trig, trig[:2]
        assert products and all(dims == f"{d},{d}"
                                for _op, dims, d in products), products
        assert any(op == "constant" for op, _dims, _d in products), products
    for op, n in ops:
        if _under(n, "attn_core") or _under(n, "attn_rope"):
            # inside the layer's own scope, and never one inside the other
            assert any(_under(n, s) for s in layer_scopes), n
            assert not (_under(n, "attn_core") and _under(n, "attn_rope")), n
    # the projections stay in the layer's scope, outside both
    outside = [n for op, n in ops if op == "dot"
               and any(_under(n, s) for s in layer_scopes)
               and not _under(n, "attn_core")]
    assert outside
    if name == "lfm2_moe":
        # the q / k norms come before the rotary step and stay outside it
        assert any(op == "rsqrt" and _under(n, "attention")
                   and not _under(n, "attn_rope") for op, n in ops)
    if name == "mla_moe":
        # the assembly of q and k: the shared k_pe's broadcast and the two
        # concatenations; and the MTP module's block has its core
        assert rope["concatenate"] >= 2 and rope["broadcast"] >= 1, rope
        assert any(_under(n, "mtp") for op, n in ops
                   if _under(n, "attn_core"))


def _keys(seq_len, window):
    """Keys the mask keeps over one sequence, query by query."""
    return sum(min(r + 1, window or seq_len) for r in range(seq_len))


def _layers(dims):
    """(kind, query heads, q-k width, v width, window) of every attention
    layer a training step runs, from the reference's `dims_of`."""
    if "nope" in dims:                        # latent attention, MTP too
        return [("full", dims["heads"], dims["nope"] + dims["rope"],
                 dims["v_dim"], None)] * (len(dims["layers"])
                                          + dims["mtp_depth"])
    if "window" in dims:
        return [("window" if kind == "sliding_attention" else "full", heads,
                 dims["head_dim"], dims["head_dim"],
                 dims["window"] if kind == "sliding_attention" else None)
                for _i, kind, heads, _s in dims["layers"]]
    return [("full", dims["heads"], dims["head_dim"], dims["head_dim"], None)
            for _i, kind, _s in dims["layers"] if kind == "full_attention"]


@pytest.mark.parametrize("cell", ["lfm2-8b-a1b-ep4.round-eval",
                                  "joyai-llm-flash-ep32.round-eval",
                                  "laguna-xs2-ep16.round-eval"])
def test_core_flops_equal_a_count_from_the_reference(cell):
    """At the cells' shapes: 2048 (LFM2, JoyAI), 4096 under a window of 512
    (Laguna), a client's step of `bs` sequences."""
    cell = registry.resolve(registry.load_benchmark(), cell)
    cfg = args_parser(cell.flags)
    ref = registry.load_module(cell.search_dirs, "reference",
                               cell.config["reference"])
    dims = ref.dims_of(cell.config)
    n_tokens = cfg.bs * cfg.seq_len
    want = {}
    for kind, heads, qk, v, window in _layers(dims):
        want[kind] = want.get(kind, 0.0) + (
            2 * heads * (qk + v) * _keys(cfg.seq_len, window)
            * n_tokens / cfg.seq_len)
    module = {"lfm2_moe": lm, "mla_moe": mm, "swa_moe": sm}[cfg.model_arch]
    got = module.from_cfg(cfg).build_counters(n_tokens, cfg.seq_len)
    assert set(got["attn_core_flops"]) == set(want)
    for kind, flops in want.items():
        assert got["attn_core_flops"][kind] == pytest.approx(flops,
                                                             rel=1e-12)
