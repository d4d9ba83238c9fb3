"""The token models' layers compiled for a described TPU v5e at the
published widths, with no chip attached: what interpret-free CPU tests
cannot show (the TPU compiler takes `jax.lax.ragged_dot` as its own grouped
product, and the step's temporaries fit). Nothing runs, so nothing here is a
time or a result. All of it, for both token models, lives in this one file:
only the worker that is given the file loads the TPU's library, inside the
fixture (a second such file could go to another worker, whose fixture would
then skip every test in silence).

Traced for the TPU (the `one_chip` fixture says so to
`attention_kernel.on_tpu`, as the chip's own process would),
`token_ops.causal_attention` is the fused kernel of
`models/attention_kernel.py` (PR 35): every case that runs it holds the
kernel's custom calls, each under the scope `attn_core` (PR 36), and no
float32 score tensor."""

import math
import os
import re

import jax
import jax.numpy as jnp
import pytest

from defending_against_backdoors_with_robust_learning_rate_tpu.models import (
    attention_kernel, lfm2_moe as lm, mla_moe as mm, swa_moe as sm,
    token_ops)

TOKENS = 8192          # a client's step: 4 sequences of 2048
# temporaries (bytes) of the cases that run `token_ops.causal_attention`,
# read from these same tests at the parent of PR 32 (commit 47e0b38, where
# every query block still multiplied all 2048 keys under a rolled
# `jax.lax.map`), here, under the suite's settings. The blocks are traced
# in turn since, each ordered behind the one before it so that XLA's
# scheduler does not hold several blocks' scores at once: each case may read
# at most ATTN_TEMP_ROOM times its number
ATTN_TEMP_AT_PARENT = {"attention": 1_195_053_568,
                       "mla_attention": 1_639_820_800,
                       "mla_step": 3_393_176_576}
ATTN_TEMP_ROOM = 1.05


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:    # noqa: BLE001 - any failure to describe skips
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # what is traced here is built for the described chip
    was = attention_kernel.on_tpu
    attention_kernel.on_tpu = lambda: True
    yield SingleDeviceSharding(topo.devices[0])
    attention_kernel.on_tpu = was


@pytest.fixture(scope="module")
def quiet_cache():
    """A compile for a described device is written to the persistent cache
    and cannot be read back without a chip: keep it out."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _aval(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_the_core_is_the_kernel(text, seq_len, causal=0, window=0):
    """The compiled program runs the attention core of `causal` + `window`
    layers as the splash kernel: the custom calls of forward and backward
    are in the text (a causal layer's backward is one kernel that makes dq
    with dk and dv, a window layer's two: `attention_kernel.plan`), and
    ENTRY holds no float32 tensor of the plain path's scores, [B, KV, g,
    query block, a whole number of query blocks] in whatever order the
    compiler keeps the axes (the plain path compiled the same way holds
    eight to sixteen of them a layer)."""
    assert attention_kernel.plan(seq_len).backward == attention_kernel.FUSED
    assert attention_kernel.plan(seq_len, 512).backward == \
        attention_kernel.APART
    for call, layers in (("splash_mha_fwd", causal + window),
                         ("splash_mha_dkv", causal + window),
                         ("splash_mha_dq", window)):
        calls = re.findall(rf'op_name="([^"]*)/{call}[^"]*/pallas_call',
                           text)
        assert len(calls) >= layers and bool(calls) == bool(layers), \
            (call, len(calls))
        # forward, recompute and backward under the core's scope (PR 36)
        assert all(re.search(r"(^|/)(\w+\()*attn_core\)*/", c)
                   for c in calls), calls[:2]
    qb = token_ops.ATTN_QUERY_BLOCK
    entry = text[text.index("\nENTRY "):]
    scores = []
    for dims in re.findall(r"f32\[([\d,]+)\]", entry):
        dims = [int(d) for d in dims.split(",")]
        if len(dims) == 5 and qb in dims:
            dims.remove(qb)
            if any(d % qb == 0 and d <= seq_len for d in dims):
                scores.append(dims)
    assert not scores, scores[:4]


@pytest.mark.parametrize("what", ["sparse_ffn", "short_conv", "attention"])
def test_layer_compiles_for_the_v5e_at_published_widths(one_chip,
                                                        quiet_cache, what):
    spec = lm.spec_from("lfm2-8b-a1b", "0,2,3,4,5", 8, 0, 16384)
    d, f, e = spec.hidden, spec.moe_ffn, spec.experts_held
    f32, bf16 = jnp.float32, jnp.bfloat16
    if what == "sparse_ffn":
        p = {"gate": (d, spec.n_experts), "experts_w1": (e, d, f),
             "experts_w3": (e, d, f), "experts_w2": (e, f, d)}
        x = _aval((TOKENS, d), bf16, one_chip)

        def fn(p, x):
            return lm.sparse_ffn(p, x, spec, 2, bf16)
    elif what == "short_conv":
        p = {"conv_in_proj": (d, 3 * d), "conv_weight": (spec.conv_taps, d),
             "conv_out_proj": (d, d)}
        x = _aval((4, 2048, d), bf16, one_chip)

        def fn(p, x):
            return lm.short_conv(p, x, spec, bf16), ()
    else:
        hq, hkv = spec.heads * spec.head_dim, spec.kv_heads * spec.head_dim
        p = {"q_proj": (d, hq), "k_proj": (d, hkv), "v_proj": (d, hkv),
             "o_proj": (hq, d), "q_norm": (spec.head_dim,),
             "k_norm": (spec.head_dim,)}
        x = _aval((4, 2048, d), bf16, one_chip)

        def fn(p, x):
            return lm.attention(p, x, spec, bf16), ()
    p = {k: _aval(s, f32, one_chip) for k, s in p.items()}

    def loss(p, x):
        out, _aux = fn(p, x)
        return jnp.sum(out.astype(f32))

    # the suite runs at matmul precision `highest` (conftest.py) and the
    # TPU's grouped product takes no bfloat16 operands at float32
    # precision: the layer asks for the default itself
    compiled = jax.jit(jax.grad(loss)).lower(p, x).compile()
    text = compiled.as_text()
    if what == "sparse_ffn":
        # forward and both transposes of three grouped products
        assert text.count('op_name="ragged-dot') >= 3 or \
            text.count("ragged-dot") >= 3
    temp = compiled.memory_analysis().temp_size_in_bytes
    # one layer's backward, without the round's accumulators: well under
    # the 4.7 GB the cut leaves for a step's activations
    assert 0 < temp < 3 * 2 ** 30, temp
    if what in ATTN_TEMP_AT_PARENT:
        assert temp <= ATTN_TEMP_ROOM * ATTN_TEMP_AT_PARENT[what], temp
        _assert_the_core_is_the_kernel(text, 2048, causal=1)
    if what == "sparse_ffn":
        # 8 of 32 experts held: the first pass sorts into 16384 rows of the
        # 32768 pairs, and the rest hangs on a conditional that carries no
        # buffer of its size (one `cond` around the expert section,
        # differentiated as written, read 2600 MiB here; the uncut 612)
        assert lm.dispatch_rows(spec, TOKENS) == 16384 == TOKENS * 2
        assert " conditional(" in text
        entry = text[text.index("\nENTRY "):]
        assert f"[{TOKENS * spec.top_k},{f}]" not in entry
        assert temp < 700 * 2 ** 20, temp


def mla_spec():
    return mm.spec_from("joyai-llm-flash", "0,1,2,3,4", 8, 0, 16160)


@pytest.mark.parametrize("what", ["mla_attention", "sparse_ffn"])
def test_mla_layer_compiles_for_the_v5e_at_published_widths(one_chip,
                                                            quiet_cache, what):
    spec = mla_spec()
    d, f, e = spec.hidden, spec.moe_ffn, spec.experts_held
    f32, bf16 = jnp.float32, jnp.bfloat16
    if what == "sparse_ffn":
        p = {"gate": (d, spec.n_experts), "experts_w1": (e, d, f),
             "experts_w3": (e, d, f), "experts_w2": (e, f, d),
             "shared_w1": (d, spec.shared_ffn),
             "shared_w3": (d, spec.shared_ffn),
             "shared_w2": (spec.shared_ffn, d)}
        x = _aval((TOKENS, d), bf16, one_chip)

        def fn(p, x):
            return mm.sparse_ffn(p, x, spec, 2, bf16)
    else:
        h = spec.heads
        p = {"q_a_proj": (d, spec.q_rank), "q_a_norm": (spec.q_rank,),
             "q_b_proj": (spec.q_rank, h * (spec.nope_dim + spec.rope_dim)),
             "kv_a_proj": (d, spec.kv_rank + spec.rope_dim),
             "kv_a_norm": (spec.kv_rank,),
             "kv_b_proj": (spec.kv_rank, h * (spec.nope_dim + spec.v_dim)),
             "o_proj": (h * spec.v_dim, d)}
        x = _aval((4, 2048, d), bf16, one_chip)

        def fn(p, x):
            return mm.mla_attention(p, x, spec, bf16), ()
    p = {k: _aval(s, f32, one_chip) for k, s in p.items()}

    def loss(p, x):
        out, _aux = fn(p, x)
        return jnp.sum(out.astype(f32))

    compiled = jax.jit(jax.grad(loss)).lower(p, x).compile()
    text = compiled.as_text()
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert 0 < temp < 3 * 2 ** 30, temp
    if what in ATTN_TEMP_AT_PARENT:
        assert temp <= ATTN_TEMP_ROOM * ATTN_TEMP_AT_PARENT[what], temp
        _assert_the_core_is_the_kernel(text, 2048, causal=1)
    if what == "sparse_ffn":
        # 8 of 256 experts held: 16384 of the step's 65536 sorted rows (two
        # rows a token), the rest on a conditional that carries no buffer of its
        # size
        assert text.count("ragged-dot") >= 3
        assert mm.dispatch_rows(spec, TOKENS) == 16384
        assert " conditional(" in text
        entry = text[text.index("\nENTRY "):]
        assert f"[{TOKENS * spec.top_k},{f}]" not in entry
        # what is left is combine's and dispatch's gather of a token's 8
        # pairs, [8, 8192, 2048] (LFM2's top-4: half of it): 1038 MiB here
        assert temp < 1200 * 2 ** 20, temp


def test_mla_training_step_compiles_for_the_v5e_and_its_temporaries_fit(
        one_chip, quiet_cache):
    """A client's whole step at the benchmark's shape: the loss with its
    MTP term through `fl/task.make_batch_loss`, every block recomputed, and
    its gradient, 491.7M parameters. The round holds four more trees of
    this size beside it, so a step has about 5 GiB for its temporaries."""
    import types

    from defending_against_backdoors_with_robust_learning_rate_tpu.fl import (
        task)
    from defending_against_backdoors_with_robust_learning_rate_tpu.models.registry import (
        abstract_params, param_count)
    model = mm.MlaMoE(spec=mla_spec(), dtype=jnp.bfloat16, remat=True)
    shapes = abstract_params(model, (2048,))
    assert param_count(shapes) == 491_696_128
    p = jax.tree_util.tree_map(
        lambda a: _aval(a.shape, a.dtype, one_chip), shapes)
    loss = task.make_batch_loss(model, types.SimpleNamespace(data="tokens"),
                                None)
    step = jax.jit(jax.value_and_grad(
        lambda p, x, w: loss(p, x, None, w, None), has_aux=True))
    compiled = step.lower(p, _aval((4, 2049), jnp.int32, one_chip),
                          _aval((4,), jnp.bool_, one_chip)).compile()
    text = compiled.as_text()
    # five sparse blocks (the MTP module's with them), each three grouped
    # products forward, recomputed, and their transposes
    assert text.count("ragged-dot") >= 5 * 3 * 3
    assert text.count(" conditional(") >= 5
    ma = compiled.memory_analysis()
    assert ma.output_size_in_bytes >= 4 * 491_696_128
    assert 0 < ma.temp_size_in_bytes < 4 * 2 ** 30, ma.temp_size_in_bytes
    assert ma.temp_size_in_bytes <= \
        ATTN_TEMP_ROOM * ATTN_TEMP_AT_PARENT["mla_step"], ma.temp_size_in_bytes
    # five blocks and the MTP module's
    _assert_the_core_is_the_kernel(text, 2048, causal=6)


def swa_spec():
    return sm.spec_from("laguna-xs.2", "0,1,2,3,4", 16, 0, 12544)


# temporaries (bytes) XLA's analysis read for these cases when PR 33 wrote
# them, here, under the suite's settings (MiB: window attention 1228.3,
# global attention 1831.8, the sparse layer 759.7, the whole step 1860.2):
# recorded, and each held to ATTN_TEMP_ROOM times its number from here on
SWA_TEMP_AT_PR33 = {"window_attention": 1_287_950_336,
                    "global_attention": 1_920_769_024,
                    "sparse_ffn": 796_617_216, "swa_step": 1_950_541_824}


@pytest.mark.parametrize("what", ["window_attention", "global_attention",
                                  "sparse_ffn"])
def test_swa_layer_compiles_for_the_v5e_at_published_widths(one_chip,
                                                            quiet_cache, what):
    """Window attention (64 query heads, three key blocks a query block),
    global attention (48 heads, partial rotary under YaRN) and the sparse
    layer at its third shape (16 of 256 experts of width 512, top-8, with
    the shared expert), each with its gradient, at the cell's 2 x 4096
    tokens."""
    spec = swa_spec()
    d, f, e = spec.hidden, spec.moe_ffn, spec.experts_held
    f32, bf16 = jnp.float32, jnp.bfloat16
    if what == "sparse_ffn":
        p = {"gate": (d, spec.n_experts), "experts_w1": (e, d, f),
             "experts_w3": (e, d, f), "experts_w2": (e, f, d),
             "shared_w1": (d, spec.shared_ffn),
             "shared_w3": (d, spec.shared_ffn),
             "shared_w2": (spec.shared_ffn, d)}
        x = _aval((TOKENS, d), bf16, one_chip)

        def fn(p, x):
            return sm.sparse_ffn(p, x, spec, bf16)
    else:
        kind, h = ((sm.WINDOW, 64) if what == "window_attention"
                   else (sm.FULL, 48))
        hq, hkv = h * spec.head_dim, spec.kv_heads * spec.head_dim
        p = {"q_proj": (d, hq), "k_proj": (d, hkv), "v_proj": (d, hkv),
             "g_proj": (d, h), "o_proj": (hq, d)}
        x = _aval((2, 4096, d), bf16, one_chip)

        def fn(p, x):
            return sm.attention(p, x, spec, kind, bf16), ()
    p = {k: _aval(s, f32, one_chip) for k, s in p.items()}

    def loss(p, x):
        out, _aux = fn(p, x)
        return jnp.sum(out.astype(f32))

    compiled = jax.jit(jax.grad(loss)).lower(p, x).compile()
    text = compiled.as_text()
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert 0 < temp < 3 * 2 ** 30, temp
    assert temp <= ATTN_TEMP_ROOM * SWA_TEMP_AT_PR33[what], temp
    if what == "sparse_ffn":
        # 16 of 256 held: 16384 of the step's 65536 sorted rows (two rows a
        # token), the rest on a conditional that carries no buffer of its
        # size
        assert text.count("ragged-dot") >= 3
        assert sm.dispatch_rows(spec, TOKENS) == 16384
        assert " conditional(" in text
        entry = text[text.index("\nENTRY "):]
        assert f"[{TOKENS * spec.top_k},{f}]" not in entry
    else:
        # no product of the core spans the sequence, and no score tensor
        # is left at all
        assert "4096,4096]" not in text
        _assert_the_core_is_the_kernel(
            text, 4096, **{"window" if kind == sm.WINDOW else "causal": 1})


@pytest.mark.parametrize("kind,heads,most_bytes", [
    (sm.WINDOW, 64, 1.6e9), (sm.FULL, 48, 1.3e9)])
def test_swa_rotary_step_is_one_pass_a_direction(one_chip, quiet_cache,
                                                 kind, heads, most_bytes):
    """The layer's rotary step as it casts q back to bfloat16, forward and
    backward, at the cell's [2, 4096, heads, 128]: `token_ops.rope_half`
    is a product with a constant rotation and constant cos / sin tables
    each way, and no float32 copy of q is left between operations. XLA's
    bytes accessed read 4.60 GB (window) and 6.28 GB (full, YaRN over 64
    of 128 widths) when cos and sin were taken in the program and the
    halves sliced and concatenated; 1.36 and 1.03 GB as one pass a
    direction."""
    r = dict(swa_spec().rope)[kind]
    shape = (2, 4096, heads, 128)
    x = _aval(shape, jnp.bfloat16, one_chip)

    def step(x, g):
        y, pull = jax.vjp(lambda x: sm.rope(x, r).astype(jnp.bfloat16), x)
        return y, pull(g)[0]

    compiled = jax.jit(step).lower(x, x).compile()
    cost = compiled.cost_analysis()
    cost = cost[0] if isinstance(cost, list) else cost
    assert 0 < cost["bytes accessed"] <= most_bytes, cost["bytes accessed"]
    text = compiled.as_text()
    entry = text[text.index("\nENTRY "):]
    size = 2 * 4096 * heads * 128
    whole = [dims for dims in re.findall(r"f32\[([\d,]+)\]", entry)
             if math.prod(int(d) for d in dims.split(",")) == size]
    assert not whole, whole[:4]


def test_swa_training_step_compiles_for_the_v5e_and_its_temporaries_fit(
        one_chip, quiet_cache):
    """A client's whole step at the benchmark's shape (2 sequences of 4096):
    the loss through `fl/task.make_batch_loss`, every block recomputed, and
    its gradient, 490.3M parameters. The round holds four more trees of
    this size beside it, so a step has about 5 GiB for its temporaries."""
    import types

    from defending_against_backdoors_with_robust_learning_rate_tpu.fl import (
        task)
    from defending_against_backdoors_with_robust_learning_rate_tpu.models.registry import (
        abstract_params, param_count)
    model = sm.SwaMoE(spec=swa_spec(), dtype=jnp.bfloat16, remat=True)
    shapes = abstract_params(model, (4096,))
    assert param_count(shapes) == 490_297_344
    p = jax.tree_util.tree_map(
        lambda a: _aval(a.shape, a.dtype, one_chip), shapes)
    loss = task.make_batch_loss(model, types.SimpleNamespace(data="tokens"),
                                None)
    step = jax.jit(jax.value_and_grad(
        lambda p, x, w: loss(p, x, None, w, None), has_aux=True))
    compiled = step.lower(p, _aval((2, 4097), jnp.int32, one_chip),
                          _aval((2,), jnp.bool_, one_chip)).compile()
    text = compiled.as_text()
    # four sparse blocks, each three grouped products forward, recomputed,
    # and their transposes
    assert text.count("ragged-dot") >= 4 * 3 * 3
    assert text.count(" conditional(") >= 4
    ma = compiled.memory_analysis()
    assert ma.output_size_in_bytes >= 4 * 490_297_344
    assert 0 < ma.temp_size_in_bytes < 4 * 2 ** 30, ma.temp_size_in_bytes
    assert ma.temp_size_in_bytes <= \
        ATTN_TEMP_ROOM * SWA_TEMP_AT_PR33["swa_step"], ma.temp_size_in_bytes
    _assert_the_core_is_the_kernel(text, 4096, causal=2, window=3)


# temporaries (bytes) of the Qwen3-Next round program, read from the test
# below at commit b165a46 (the chunked rule as XLA's solve and a scan over
# chunks), here, under the suite's settings; the kernels' program may hold
# no more
GDN_TEMP_AT_PARENT = 9_232_519_168


def test_gdn_round_program_compiles_for_the_v5e_and_fits(one_chip,
                                                          quiet_cache,
                                                          monkeypatch,
                                                          tmp_path):
    """The linear/full-attention model's whole `round` program as the
    planner builds it for the benchmark cell's flags
    (`benchmark/configs/qwen3-next-80b-a3b-ep32.json`: 424.3M parameters,
    ten clients folded one at a time, two steps of 2 x 4096 tokens each),
    traced as the chip's process would trace it (no CPU loops, the client
    loop flat). The linear layers' core is the fused kernel of
    `models/gdn_kernel.py`: the forward that keeps its residuals
    (the pass and the block's recompute) and the backward, each under the
    scope `gdn_core` that `gdn_core_ms` reads, with no triangular solve and
    no loop over chunks left under it. The one full layer's core is the
    attention kernel, and the arguments, outputs and temporaries fit a
    16 GB chip with room for the eval programs, the temporaries no more
    than the XLA lowering's."""
    import json

    from defending_against_backdoors_with_robust_learning_rate_tpu.config import (
        args_parser)
    from defending_against_backdoors_with_robust_learning_rate_tpu.data.registry import (
        get_federated_data)
    from defending_against_backdoors_with_robust_learning_rate_tpu.fl.common import (
        make_normalizer)
    from defending_against_backdoors_with_robust_learning_rate_tpu.models.registry import (
        get_model)
    from defending_against_backdoors_with_robust_learning_rate_tpu.ops import (
        loops)
    from defending_against_backdoors_with_robust_learning_rate_tpu.utils import (
        compile_cache as cc)
    with open(os.path.join(os.path.dirname(__file__), os.pardir, "benchmark",
                           "configs", "qwen3-next-80b-a3b-ep32.json")) as f:
        flags = json.load(f)["flags"]
    monkeypatch.setattr(loops, "cpu_backend", lambda: False)
    monkeypatch.setattr(loops, "carry_is_large", lambda params, steps:
                        steps <= loops.LARGE_CARRY_MAX_STEPS)
    cfg = args_parser(flags + [
        "--platform=cpu", "--no_tensorboard", "--no_compile_cache",
        "--data_dir=/nonexistent", f"--log_dir={tmp_path}"]).replace(
            remat_policy="block", agg_path="fold")
    resolved = cc.resolved_agg
    monkeypatch.setattr(cc, "resolved_agg", lambda c, n: resolved(
        c.replace(agg_path="fold"), n))
    fed = get_federated_data(cfg)
    model = get_model(cfg.data, cfg.model_arch, cfg.dtype, remat=cfg.remat,
                      remat_policy=cfg.remat_policy, cfg=cfg)
    spec, = [s for s in cc.plan_programs(
        cfg, model, make_normalizer(fed.mean, fed.std, fed.raw_is_normalized),
        fed) if s.family == "round"]
    args = jax.tree_util.tree_map(
        lambda a: _aval(a.shape, a.dtype, one_chip),
        cc.abstractify(spec.example_args))
    compiled = spec.jit_obj.lower(*args).compile()
    text = compiled.as_text()
    # the kernels of each linear layer's core, under its scope;
    # the XLA lowering's solve (18 of them) and its 24 scans over chunks
    # are gone
    for call, layers in (("gdn_forward_res", 3), ("gdn_backward", 3)):
        calls = re.findall(rf'op_name="([^"]*)/{call}/pallas_call', text)
        held = {re.search(r"layer_\d+", c).group() for c in calls}
        assert len(held) == layers, (call, held)
        assert all(re.search(r"(^|/)gdn/gdn_core(/|$)", c) for c in calls), \
            calls[:2]
    assert "gdn_forward/pallas_call" not in text
    assert "InvertDiagBlocksLowerTriangular" not in text
    whiles = re.findall(r"^\s*\S+ = [^\n]* while\([^\n]*$", text, re.M)
    assert whiles and not [w for w in whiles if "gdn_core" in w], whiles
    assert text.count("ragged-dot") >= 4 * 3 * 3
    _assert_the_core_is_the_kernel(text, 4096, causal=1)
    ma = compiled.memory_analysis()
    assert ma.argument_size_in_bytes >= 4 * 424_340_544
    held = (ma.argument_size_in_bytes + ma.output_size_in_bytes
            - ma.alias_size_in_bytes + ma.temp_size_in_bytes)
    assert held < 14 * 2 ** 30, held
    assert ma.temp_size_in_bytes <= GDN_TEMP_AT_PARENT, ma.temp_size_in_bytes
