"""`--remat_policy auto` (ISSUE 25, ISSUE 30): what the backward pass
recomputes under `--remat` (every block, the elementwise tail, or nothing)
is settled by a rule over the model's shapes, the examples in flight on one
device and the device's memory limit (utils/compile_cache.resolved_remat).
Everything here is arithmetic, tracing and lowering on the CPU: no program
is compiled, and no number below is a device metric. The exactness of the
policies is tests/test_models.py::test_resnet9_selective_remat_matches_block
and ::test_resnet9_remat_policy_none_is_the_unremated_model."""

import os
import sys
import types

import pytest

from defending_against_backdoors_with_robust_learning_rate_tpu.config import (
    Config, args_parser)
from defending_against_backdoors_with_robust_learning_rate_tpu.models.registry import (
    get_model, named_activation_bytes)
from defending_against_backdoors_with_robust_learning_rate_tpu.utils import (
    compile_cache as cc)

# the v5e's bytes_limit as XLA states it ("15.75G hbm")
V5E_LIMIT = int(15.75 * 2 ** 30)
# ResNet-9 on 32x32 images: the eight tagged conv outputs of one example,
# 64x32x32 + 128x32x32 + 2x128x16x16 + 256x16x16 + 512x8x8 + 2x512x4x4
CONV_OUT_VALUES = 376_832

# benchmark/configs/cifar-resnet9.json's program-shaping flags
CELL = Config(data="cifar10", arch="resnet9", num_agents=40, bs=256,
              local_ep=2, num_corrupt=4, robustLR_threshold=8, remat=True,
              agent_chunk=10, tensorboard=False)


@pytest.fixture
def limit(monkeypatch):
    def set_limit(n):
        monkeypatch.setattr(cc, "device_memory_limit", lambda: n)
    return set_limit


@pytest.mark.parametrize("arch,dtype,want", [
    ("resnet9", "f32", 4 * CONV_OUT_VALUES),
    ("resnet9", "bf16", 2 * CONV_OUT_VALUES),
    ("cnn", "f32", 0),          # tags nothing, and never remats
])
def test_conv_out_bytes_come_from_the_models_own_shapes(arch, dtype, want):
    model = get_model("cifar10", arch, dtype)
    assert named_activation_bytes(model, (32, 32, 3)) == want
    if arch == "resnet9":
        # the tag sits inside the remat'd blocks too
        for policy in ("block", "conv", "none"):
            wrapped = get_model("cifar10", arch, dtype, remat=True,
                                remat_policy=policy)
            assert named_activation_bytes(wrapped, (32, 32, 3)) == want


@pytest.mark.parametrize("per_example,in_flight,free,want", [
    # 3.86 GB kept: 3.8x = 14.66 GB of 16.91
    (1_507_328, 2560, V5E_LIMIT, "none"),
    (1_507_328, 10240, V5E_LIMIT, "block"),    # 15.4 GB of conv_out alone
    # bf16 at twice the examples: the same 3.86 GB
    (753_664, 5120, V5E_LIMIT, "none"),
    (1_507_328, 5120, V5E_LIMIT, "block"),     # 7.72 GB: 3x = 23.2 of 16.9
    # 4.82 GB kept: 3.8x = 18.33 GB of 15.67 does not fit, 3x = 14.47 does
    (1_507_328, 3200, 15_667_732_928, "conv"),
    (100, 10, 3800, "none"),                   # exactly 3.8x: fits
    (100, 10, 3799, "conv"),                   # one byte under
    (100, 10, 3000, "conv"),                   # exactly a third: fits
    (100, 10, 2999, "block"),
    (100, 10, 0, "block"),                     # nothing left beside the resident bytes
    (100, 10, -5, "block"),
    (0, 2560, 1, "none"),                      # nothing tagged, nothing kept
    (0, 2560, 0, "none"),
    (1_507_328, 2560, None, "block"),          # no limit reported
])
def test_rule_on_numbers_alone(per_example, in_flight, free, want):
    assert cc.remat_policy_for(per_example, in_flight, free) == want


def test_constants_keep_every_rung_reachable():
    """`conv` is what fits between the two shares: a `none` share below
    the `conv` share would make `conv` unreachable."""
    assert cc.REMAT_NONE_SHARE_DIVISOR >= cc.REMAT_CONV_SHARE_DIVISOR


@pytest.mark.parametrize("per_example,free", [
    (1_507_328, V5E_LIMIT), (753_664, V5E_LIMIT), (1_507_328, 15_667_732_928),
    (100, 3800), (7, 10 ** 9), (1, 1),
])
def test_ladder_only_descends_as_examples_in_flight_grow(per_example, free):
    """With more examples in flight the rule goes none -> conv -> block
    and never back: each rung is a threshold on the same product."""
    rung = {"none": 0, "conv": 1, "block": 2}
    top = free // per_example + 2      # past where even 1x fits
    seen = [rung[cc.remat_policy_for(per_example, n, free)]
            for n in (*range(0, top, max(1, top // 4000)), top)]
    assert seen == sorted(seen)
    assert seen[0] == 0 and seen[-1] == 2
    # every rung is visited where the bytes are fine enough to land on it
    if free // per_example >= 100:
        assert set(seen) == {0, 1, 2}


def _fed(nbytes=192_000_000, train_images=153_600_000):
    """What the rule reads of a dataset: its bytes, and the bytes of the
    train images that decide host-sampled mode."""
    return types.SimpleNamespace(
        nbytes=nbytes,
        train=types.SimpleNamespace(
            images=types.SimpleNamespace(nbytes=train_images)))


# free = 16.911 GB less the update stack (1.052 GB at 40 agents a device,
# 0.263 at ten) and the placed dataset (0.192): 15.668 GB on one chip.
# kept = 1,507,328 B an example in f32, half that in bf16. Each row's
# arithmetic is 3.8 x kept (`none` fits) / 3 x kept (`conv` fits) against
# free.
@pytest.mark.parametrize("name,overrides,want", [
    # cifar-resnet9.round-eval: four sequential chunks of ten on one chip.
    # kept 3.859 GB: 14.66 of 15.67
    ("round-eval", {}, "none"),
    # cifar-resnet9.mesh4: ten agents vmapped on each of four chips.
    # kept 3.859 GB: 14.66 of 16.46
    ("mesh4", {"mesh": 4}, "none"),
    # the run --remat was written for: all 40 agents at once. kept 15.44
    # GB: 46.3 of 15.67
    ("chunk0", {"agent_chunk": 0}, "block"),
    # ... which four chips divide into the cells' shape again
    ("chunk0-mesh4", {"agent_chunk": 0, "mesh": 4}, "none"),
    # kept 7.717 GB: 23.15 of 15.67
    ("chunk20-f32", {"agent_chunk": 20}, "block"),
    # half the bytes: bf16 flips at the first chunk size f32 cannot keep.
    # kept 3.859 GB: 14.66 of 15.67
    ("chunk20-bf16", {"agent_chunk": 20, "dtype": "bf16"}, "none"),
    ("chunk0-bf16", {"agent_chunk": 0, "dtype": "bf16"}, "block"),
    # kept 1.929 GB: 7.33 of 15.67
    ("chunk10-bf16", {"dtype": "bf16"}, "none"),
    # a packed program trains E experiments at once. kept 7.717 GB: 23.15
    # of 14.62 (two update stacks)
    ("tenants2", {"tenants": 2}, "block"),
    # kept 3.859 GB: 3.8x = 14.66 of 14.62 does not fit (the packed
    # program with nothing recomputed needs 18.8 GB by XLA's analysis,
    # and so must not resolve `none`), 3x = 11.58 does: stays conv
    ("tenants2-bf16", {"tenants": 2, "dtype": "bf16"}, "conv"),
    # batch size is in the examples in flight
    ("bs512", {"bs": 512}, "block"),
    # kept 3.859 GB again
    ("bs128-chunk20", {"bs": 128, "agent_chunk": 20}, "none"),
    # the rung between: kept 4.823 GB, 3.8x = 18.33 of 15.67 does not
    # fit, 3x = 14.47 does
    ("bs320", {"bs": 320}, "conv"),
    # kept 4.100 GB: 15.58 of 15.67 fits, with little to spare
    ("bs272", {"bs": 272}, "none"),
    # kept 4.341 GB: 16.50 of 15.67 does not
    ("bs288", {"bs": 288}, "conv"),
    # kept 4.823 GB of bf16 at chunks of twenty: the same rung
    ("bs320-chunk20-bf16", {"bs": 320, "agent_chunk": 20, "dtype": "bf16"},
     "conv"),
    # a host-sampled run shards its agents too, and places no dataset
    #
    ("host-mesh4", {"agent_chunk": 0, "mesh": 4, "host_sampled": "on"},
     "none"),
    # the mesh cannot be larger than the devices there are (conftest: 8):
    # five agents a device, kept 1.929 GB
    ("mesh0", {"agent_chunk": 0, "mesh": 0}, "none"),
])
def test_auto_resolves_from_shapes_and_limit(limit, name, overrides, want):
    limit(V5E_LIMIT)
    cfg = CELL.replace(**overrides)
    assert cfg.remat_policy == "auto"
    got = cc.resolved_remat(cfg, _fed())
    assert (got.policy, got.chosen) == (want, True)
    agents = 40 // {1: 1, 4: 4, 0: 8}[cfg.mesh]
    at_once = cfg.agent_chunk if 0 < cfg.agent_chunk < agents else agents
    per_example = CONV_OUT_VALUES * (2 if cfg.dtype == "bf16" else 4)
    assert got.saved_bytes == (per_example * at_once * cfg.bs
                               * max(1, cfg.tenants))
    # held against the limit less the update stack and the placed dataset
    placed = 0 if cfg.host_sampled == "on" else 192_000_000
    assert got.limit_bytes == (V5E_LIMIT - placed
                               - 4 * 6_573_130 * agents
                               * max(1, cfg.tenants))


def test_dataset_counts_only_where_the_run_places_it(limit):
    """A train stack over the device-resident budget stays on the host
    (`is_host_mode`), and so does a cohort-sampled population: neither is
    taken off the limit. A cohort pack has no sharded family, so its
    --mesh divides nothing."""
    limit(V5E_LIMIT)
    stack = 4 * 6_573_130 * 40
    big = _fed(nbytes=5 << 30, train_images=4 << 30)
    assert cc.resolved_remat(CELL, big).limit_bytes == V5E_LIMIT - stack
    assert cc.resolved_remat(CELL, big, threshold=8 << 30).limit_bytes == (
        V5E_LIMIT - stack - (5 << 30))
    cohort = CELL.replace(cohort_sampled="on", mesh=4)
    assert cc.resolved_remat(cohort, _fed()).limit_bytes == (
        V5E_LIMIT - stack // 4)
    pack = cc.resolved_remat(cohort.replace(tenants=2), _fed())
    assert pack.limit_bytes == V5E_LIMIT - 2 * stack
    # no dataset given (fingerprint's view): nothing taken off for it
    assert cc.resolved_remat(CELL).limit_bytes == V5E_LIMIT - stack


@pytest.mark.parametrize("mesh", [1, 4])
def test_benchmark_cells_resolve_none_with_margin(limit, mesh):
    """Both cells recompute nothing with room to spare, so that a few
    hundred MB more resident on the device does not flip them: 14.66 GB
    asked of 15.67 free on one chip, of 16.46 a chip on four."""
    limit(V5E_LIMIT)
    got = cc.resolved_remat(CELL.replace(mesh=mesh), _fed())
    assert got.policy == "none"
    assert (cc.REMAT_NONE_SHARE_DIVISOR * got.saved_bytes
            < 0.95 * got.limit_bytes)
    # and half a GB more resident still resolves `none`
    limit(V5E_LIMIT - 500_000_000)
    assert cc.resolved_remat(CELL.replace(mesh=mesh), _fed()).policy == "none"


@pytest.mark.parametrize("reported", [None, V5E_LIMIT, 1])
@pytest.mark.parametrize("asked", ["block", "conv", "none"])
def test_explicit_policy_overrides_auto(limit, reported, asked):
    limit(reported)
    got = cc.resolved_remat(CELL.replace(remat_policy=asked))
    assert (got.policy, got.chosen) == (asked, False)


def test_backend_without_a_limit_resolves_block(limit):
    """XLA:CPU reports no memory limit: every CPU-pinned program, every
    tier-1 test and analysis_baseline.json keep the block program."""
    assert cc.device_memory_limit() is None      # the real CPU backend
    got = cc.resolved_remat(CELL)
    assert (got.policy, got.limit_bytes, got.chosen) == ("block", None, True)
    assert "no memory limit" in got.describe()


def test_token_cfg_returns_block_before_the_ladder(limit):
    """The token model tags no tensor and its window holds 12.4 of 16.9 GB
    with every block recomputed: `--remat` there still means what it says,
    whatever the device reports and whatever policy is typed."""
    tokens = Config(data="tokens", arch="lfm2_moe", remat=True,
                    agent_chunk=1, tensorboard=False)
    for reported in (None, V5E_LIMIT, 1 << 50):
        limit(reported)
        for asked in ("auto", "block", "conv", "none"):
            got = cc.resolved_remat(tokens.replace(remat_policy=asked))
            assert got == cc.RematChoice("block", 0, None)


def test_without_remat_the_policy_selects_nothing(limit):
    limit(V5E_LIMIT)
    for asked in ("auto", "block", "conv", "none"):
        got = cc.resolved_remat(CELL.replace(remat=False,
                                             remat_policy=asked))
        assert (got.policy, got.saved_bytes) == ("block", 0)


def test_unknown_policy_is_refused():
    with pytest.raises(ValueError, match="remat_policy"):
        cc.resolved_remat(CELL.replace(remat_policy="some"))
    # the model takes the resolved policy only
    with pytest.raises(ValueError, match="resolve 'auto'"):
        get_model("cifar10", "resnet9", remat=True, remat_policy="auto")


def test_flag_default_is_auto_and_explicit_values_parse():
    assert args_parser([]).remat_policy == "auto"
    for asked in ("auto", "block", "conv", "none"):
        assert args_parser([f"--remat_policy={asked}"]).remat_policy == asked
    with pytest.raises(SystemExit):
        args_parser(["--remat_policy=all"])


def _fp(cfg):
    import jax
    import jax.numpy as jnp
    return cc.fingerprint(cfg, "round",
                          (jax.ShapeDtypeStruct((8, 8), jnp.float32),))


def test_fingerprint_keys_the_resolved_policy(limit):
    """A bank written under one policy must miss under the others, and
    `auto` is never a key of its own."""
    block, conv, none = (_fp(CELL.replace(remat_policy=p))
                         for p in ("block", "conv", "none"))
    assert len({block, conv, none}) == 3
    limit(None)
    assert _fp(CELL) == block
    limit(V5E_LIMIT)
    assert _fp(CELL) == none
    assert _fp(CELL.replace(bs=320)) == _fp(
        CELL.replace(bs=320, remat_policy="conv"))
    assert _fp(CELL.replace(agent_chunk=0)) == _fp(
        CELL.replace(agent_chunk=0, remat_policy="block"))
    # without --remat the field selects nothing and splits nothing
    plain = CELL.replace(remat=False)
    assert (_fp(plain) == _fp(plain.replace(remat_policy="block"))
            == _fp(plain.replace(remat_policy="conv"))
            == _fp(plain.replace(remat_policy="none")))


TINY = Config(data="cifar10", arch="resnet9", num_agents=4, bs=16,
              local_ep=1, agent_chunk=2, remat=True, num_corrupt=1,
              synth_train_size=128, synth_val_size=64, eval_bs=64,
              rounds=1, snap=1, tensorboard=False, data_dir="/nonexistent",
              compile_cache=False, heartbeat=False)


# TINY keeps 48,234,496 B (two agents of 16 at once): 3.8x = 183.3 MB,
# 3x = 144.7 MB, beside a 105 MB update stack and the dataset
@pytest.mark.parametrize("reported,asked,want,how", [
    (None, "auto", "block", "auto"),
    (V5E_LIMIT, "auto", "none", "auto"),
    (260_000_000, "auto", "conv", "auto"),
    (200_000_000, "auto", "block", "auto"),
    (V5E_LIMIT, "block", "block", "as asked"),
    (None, "conv", "conv", "as asked"),
    (None, "none", "none", "as asked"),
    (200_000_000, "none", "none", "as asked"),
])
def test_engine_builds_counts_and_says_the_resolved_policy(
        limit, tmp_path, capsys, reported, asked, want, how):
    """The engine hands get_model the resolved policy, keeps it in its
    cfg (what every family's fingerprint reads), counts it once and says
    it in one [model] line. Nothing is dispatched, so nothing compiles."""
    from defending_against_backdoors_with_robust_learning_rate_tpu.train import (
        RoundEngine)
    limit(reported)
    eng = RoundEngine(TINY.replace(remat_policy=asked,
                                   log_dir=str(tmp_path)))
    try:
        assert eng.cfg.remat_policy == want
        rows = eng.tracer.aggregates()
        assert rows[f"remat{{policy={want}}}"] == {"count": 1}
        assert [k for k in rows if k.startswith("remat{")] == [
            f"remat{{policy={want}}}"]
        saved = 4 * 376_832 * 2 * 16
        assert rows["remat_saved_bytes"] == {"count": saved}
        assert (rows["remat_limit_bytes"]["count"] > 0) == (
            reported is not None)
    finally:
        eng.close()
    out = capsys.readouterr().out
    assert f"[model] remat policy {want} ({how})" in out
    # the line says what the policy does, and the rule's input beside it
    assert ("nothing is recomputed; the convolution outputs" in out) == (
        want == "none")
    assert "take 0.05 GB" in out


def test_engine_without_remat_counts_nothing(limit, tmp_path, capsys):
    from defending_against_backdoors_with_robust_learning_rate_tpu.train import (
        RoundEngine)
    limit(V5E_LIMIT)
    eng = RoundEngine(TINY.replace(remat=False, log_dir=str(tmp_path)))
    try:
        assert eng.cfg.remat_policy == "block"
        assert not [k for k in eng.tracer.aggregates() if "remat" in k]
    finally:
        eng.close()
    assert "remat policy" not in capsys.readouterr().out


TOKENS_TINY = Config(
    data="tokens", arch="lfm2_moe",
    lm_config=os.path.join(os.path.dirname(__file__), "data",
                           "lm_tiny.json"),
    lm_layers="1,2", lm_experts_held=4, lm_vocab_held=96, seq_len=16,
    num_agents=2, bs=2, local_ep=1, synth_train_size=4, synth_val_size=4,
    eval_bs=2, num_corrupt=1, poison_frac=0.5, robustLR_threshold=2,
    agent_chunk=1, remat=True, agg_path="fold", tensorboard=False,
    compile_cache=False, data_dir="/nonexistent")


def _round_text(cfg):
    """The lowered text of the `round` program as every builder gets it:
    policy from `resolved_remat`, model from `get_model`, program from the
    planner."""
    from defending_against_backdoors_with_robust_learning_rate_tpu.data.registry import (
        get_federated_data)
    from defending_against_backdoors_with_robust_learning_rate_tpu.fl.common import (
        make_normalizer)
    fed = get_federated_data(cfg)
    cfg = cfg.replace(remat_policy=cc.resolved_remat(cfg, fed).policy)
    model = get_model(cfg.data, cfg.model_arch, cfg.dtype, remat=cfg.remat,
                      remat_policy=cfg.remat_policy, cfg=cfg)
    norm = make_normalizer(fed.mean, fed.std, fed.raw_is_normalized)
    spec, = [s for s in cc.plan_programs(cfg, model, norm, fed)
             if s.family == "round"]
    return cfg.remat_policy, cc.lower_program(
        spec.jit_obj, spec.example_args).as_text()


@pytest.mark.parametrize("name,cfg,reported,same_as,resolves", [
    # the CPU reports no limit: the stacked ResNet round and the folded
    # token round under `auto` are the programs `block` forced builds
    ("resnet-no-limit", TINY, None, {"remat_policy": "block"}, "block"),
    ("tokens-no-limit", TOKENS_TINY, None, {"remat_policy": "block"},
     "block"),
    # a token cfg returns before the ladder whatever the device reports
    ("tokens-v5e", TOKENS_TINY, V5E_LIMIT, {"remat_policy": "block"},
     "block"),
    # and where everything fits, `--remat` builds what leaving it out does
    ("resnet-v5e", TINY, V5E_LIMIT, {"remat": False}, "none"),
])
def test_auto_lowers_to_the_program_it_resolves_to(
        limit, name, cfg, reported, same_as, resolves):
    """The 'CPU and token programs are untouched' half of ISSUE 30's
    claim, pinned on lowered text: nothing compiles or runs."""
    limit(reported)
    policy, text = _round_text(cfg)
    assert cfg.remat_policy == "auto" and policy == resolves
    _, other = _round_text(cfg.replace(**same_as))
    assert text == other
    # what is recomputed sits behind a barrier in the lowered program
    assert ("optimization_barrier" in text) == (resolves != "none")


def test_static_analysis_env_keeps_the_block_program_on_cpu():
    """analysis/jaxpr_lint builds its models through the same rule; under
    JAX_PLATFORMS=cpu that is `block`, so analysis_baseline.json's pinned
    programs do not move."""
    from defending_against_backdoors_with_robust_learning_rate_tpu.analysis import (
        jaxpr_lint)
    _fed, model, _norm = jaxpr_lint._build_env(TINY)
    assert (model.remat, model.remat_policy) == (True, "block")


@pytest.mark.parametrize("reported,want", [
    # one agent of 16 a device keeps 24.1 MB beside a 26.3 MB stack:
    # 3.8x = 91.6 MB of 123 free
    (150_000_000, "none"),
    # ... of 78 free: 3x = 72.3 MB fits
    (105_000_000, "conv"),
])
def test_planner_keys_match_the_engines_under_a_mesh(limit, tmp_path,
                                                     reported, want):
    """What precompile banks is keyed as the engine will ask for it: both
    resolve from the cfg and the dataset alone. Four devices take one
    agent each, whose activations fit a limit that the four agents of an
    unsharded run would not."""
    from defending_against_backdoors_with_robust_learning_rate_tpu.data.registry import (
        get_federated_data)
    from defending_against_backdoors_with_robust_learning_rate_tpu.train import (
        RoundEngine)
    limit(reported)
    cfg = TINY.replace(mesh=4, agent_chunk=0, log_dir=str(tmp_path))
    fed = get_federated_data(cfg)
    planned = cfg.replace(remat_policy=cc.resolved_remat(cfg, fed).policy)
    assert planned.remat_policy == want
    assert cc.resolved_remat(cfg.replace(mesh=1), fed).policy == "block"
    eng = RoundEngine(cfg)
    try:
        assert eng.n_mesh == 4
        assert eng.cfg.remat_policy == want
        assert _fp(eng.cfg) == _fp(planned) == _fp(cfg)
    finally:
        eng.close()


def test_precompile_manifest_keys_the_resolved_policy(limit, tmp_path,
                                                      monkeypatch, capsys):
    """scripts/precompile.py writes the resolved policy into the cfg it
    plans with (bench_config asks for `block` unless told otherwise): a
    ResNet-9 config left on `auto` is banked under the keys of the policy
    the device's limit resolves it to."""
    import json
    import os
    import bench
    sys.path.insert(0, os.path.join(os.path.dirname(bench.__file__),
                                    "scripts"))
    try:
        import precompile
    finally:
        sys.path.pop(0)

    def manifest(policy):
        base = bench.bench_config
        monkeypatch.setattr(
            bench, "bench_config",
            lambda name, **kw: base(name, remat_policy=policy, **kw))
        monkeypatch.setattr(sys, "argv", [
            "precompile.py", "--print_manifest", "--configs", "resnet9",
            "--synth_train_size", "512",
            "--cache_dir", str(tmp_path)])
        assert precompile.main() == 0
        monkeypatch.setattr(bench, "bench_config", base)
        rows = [json.loads(line)
                for line in capsys.readouterr().out.splitlines()
                if line.startswith("{")]
        assert {"round", "eval_val"} <= {r["family"] for r in rows}
        return {r["family"]: r["fingerprint"] for r in rows}

    forced = {p: manifest(p) for p in ("block", "conv", "none")}
    limit(None)
    assert manifest("auto") == forced["block"]
    limit(V5E_LIMIT)
    assert manifest("auto") == forced["none"]
    # ten agents of 256 keep 3.86 GB beside a 1.05 GB stack: 3x fit 12.7
    # GB free, 3.8x do not
    limit(13_800_000_000)
    assert manifest("auto") == forced["conv"]
    for a, b in (("block", "conv"), ("block", "none"), ("conv", "none")):
        assert not set(forced[a].values()) & set(forced[b].values())
