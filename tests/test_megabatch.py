"""Megabatched local training (ISSUE 10, `--train_layout megabatch`):
the client axis folded into the batch (fl/client.py) must be a pure
COMPUTE-layout change — per-client update pytrees match the vmap layout
within a pinned ulp bound, masking semantics are preserved through the
segment weights, the chained scan adopts it unchanged, and the new
program families ride the AOT bank like every family.

Parity tiers, by what the arithmetic guarantees:

- the per-client losses are bit-identical at the first step (the
  segment-sum over equal [bs] client blocks reduces in the same order
  as the vmapped per-client sum on XLA:CPU) and ulp-close after it
  (later steps read params already shifted by the backward's
  reduction-order ulps);
- the update pytrees cross the fold's reorganization boundary (flat
  gather + fold-built masks + stacked optimizer arithmetic) — measured
  <= 32 leaf-scale ulps over a 2-epoch schedule, pinned at 64 (f32);
  bf16 compute measured <= 3e-6 absolute, pinned at 1e-4;
- everything downstream of the updates (masks, aggregation, RLR vote)
  is the identical code on identical stacked shapes.

The sharded-path twin of the round parity here is the CI
`megabatch-parity` smoke (byte/ulp row compare on the 8-device mesh);
the heavier in-process sharded + telemetry-full variants are slow-gated
behind it.
"""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from defending_against_backdoors_with_robust_learning_rate_tpu.config import (  # noqa: E402
    Config)
from defending_against_backdoors_with_robust_learning_rate_tpu.data.registry import (  # noqa: E402
    get_federated_data)
from defending_against_backdoors_with_robust_learning_rate_tpu.fl.common import (  # noqa: E402
    make_normalizer)
from defending_against_backdoors_with_robust_learning_rate_tpu.fl.client import (  # noqa: E402
    make_local_train, make_local_train_megabatch)
from defending_against_backdoors_with_robust_learning_rate_tpu.fl.rounds import (  # noqa: E402
    make_cohort_step, make_round_fn, megabatch_agents, vmap_agents)
from defending_against_backdoors_with_robust_learning_rate_tpu.models.registry import (  # noqa: E402
    flops_per_example, get_model, init_params)
from defending_against_backdoors_with_robust_learning_rate_tpu.utils import (  # noqa: E402
    compile_cache)

# per-client update parity bound, in ulps of each leaf's largest
# magnitude (near-zero coordinates make value-relative ulps meaningless;
# the leaf scale is what the aggregation rules actually see). Measured
# <= 32 over a 2-epoch, 16-step schedule with PGD + stragglers.
ULP_BOUND = 64
BF16_ATOL = 1e-4   # measured 2.9e-6 absolute on the same schedule


def leaf_scale_ulps(t1, t2) -> float:
    worst = 0.0
    for a, b in zip(jax.tree_util.tree_leaves(t1),
                    jax.tree_util.tree_leaves(t2), strict=True):
        a = np.asarray(a, np.float32)
        b = np.asarray(b, np.float32)
        scale = np.spacing(np.float32(
            max(float(np.max(np.abs(a))), float(np.max(np.abs(b))))))
        worst = max(worst, float(np.max(np.abs(a - b))) / float(scale))
    return worst


def _setup(dtype="f32", m=6, local_ep=2, synth_train_size=256, **kw):
    cfg = Config(data="synthetic", num_agents=m, bs=16, local_ep=local_ep,
                 synth_train_size=synth_train_size, synth_val_size=64,
                 eval_bs=32,
                 num_corrupt=2, poison_frac=1.0, seed=11, dtype=dtype,
                 robustLR_threshold=3, **kw)
    fed = get_federated_data(cfg)
    model = get_model(cfg.data, cfg.model_arch, cfg.dtype)
    params = init_params(model, cfg.image_shape, jax.random.PRNGKey(0))
    norm = make_normalizer(fed.mean, fed.std, fed.raw_is_normalized)
    arrays = (jnp.asarray(fed.train.images), jnp.asarray(fed.train.labels),
              jnp.asarray(fed.train.sizes))
    return cfg, model, params, norm, arrays


def _both_trainers(cfg, model, norm):
    return (make_local_train(model, cfg, norm),
            make_local_train_megabatch(model, cfg, norm))


# ----------------------------------------------------- trainer parity ---

def test_masked_ce_segments_is_the_per_client_reduction():
    """The loss-side fold oracle (fl/common.masked_ce_segments): the
    segment-sum over the folded [m*bs] batch equals the vmapped
    per-client masked_ce means, with the step masks folded into the
    segment weights (all-masked segments divide by the 1.0 floor)."""
    from defending_against_backdoors_with_robust_learning_rate_tpu.fl.common import (
        masked_ce, masked_ce_segments)
    m, bs, c = 5, 8, 10
    k = jax.random.PRNGKey(0)
    logits = jax.random.normal(k, (m, bs, c))
    labels = jax.random.randint(jax.random.fold_in(k, 1), (m, bs), 0, c)
    weights = jax.random.bernoulli(jax.random.fold_in(k, 2), 0.7, (m, bs))
    weights = weights.at[0].set(False)        # an all-masked segment
    total, per, wn = masked_ce_segments(
        logits.reshape(m * bs, c), labels.reshape(-1),
        weights.reshape(-1), m)
    ref = jax.vmap(masked_ce)(logits, labels, weights)
    np.testing.assert_allclose(np.asarray(per), np.asarray(ref),
                               rtol=1e-6)
    assert float(per[0]) == 0.0
    np.testing.assert_allclose(float(total), float(np.sum(ref)),
                               rtol=1e-6)
    np.testing.assert_array_equal(
        np.asarray(wn), np.asarray(weights.sum(axis=1), np.float32))


def test_trainer_parity_f32_small():
    """Cheap tier-1 twin of the slow-gated
    ``test_trainer_parity_f32_with_pgd_and_chunk``: the same three
    assertions (update-pytree ulp bound, chunked fold parity, the
    invalid-chunk error) on a quarter-size schedule — the fold, mask
    and chunk arithmetic are schedule-length-independent; the full
    2-epoch PGD schedule stays pinned behind -m slow."""
    cfg, model, params, norm, (imgs, lbls, szs) = _setup(
        m=4, local_ep=1, synth_train_size=96, clip=5.0)
    m = cfg.num_agents
    keys = jax.random.split(jax.random.PRNGKey(7), m)
    lt, mb = _both_trainers(cfg, model, norm)
    u1, l1 = jax.jit(lambda *a: vmap_agents(lt, *a))(
        params, imgs, lbls, szs, keys)
    u2, l2 = jax.jit(lambda *a: megabatch_agents(mb, *a))(
        params, imgs, lbls, szs, keys)
    assert leaf_scale_ulps(u1, u2) <= ULP_BOUND
    np.testing.assert_allclose(np.asarray(l1), np.asarray(l2),
                               rtol=1e-5, atol=1e-7)
    u3, _ = jax.jit(lambda *a: megabatch_agents(mb, *a, chunk=2))(
        params, imgs, lbls, szs, keys)
    assert leaf_scale_ulps(u2, u3) <= ULP_BOUND
    with pytest.raises(ValueError, match="agent_chunk"):
        megabatch_agents(mb, params, imgs, lbls, szs, keys, chunk=3)


@pytest.mark.slow
def test_trainer_parity_f32_with_pgd_and_chunk():
    """Per-client update pytrees: megabatch vs vmap within ULP_BOUND
    leaf-scale ulps, per-client losses ulp-close; chunked megabatch
    (the HBM lever) equals the full fold within the same bound.
    Slow-gated: ``test_trainer_parity_f32_small`` is the tier-1 twin."""
    cfg, model, params, norm, (imgs, lbls, szs) = _setup(clip=5.0)
    m = cfg.num_agents
    keys = jax.random.split(jax.random.PRNGKey(7), m)
    lt, mb = _both_trainers(cfg, model, norm)
    u1, l1 = jax.jit(lambda *a: vmap_agents(lt, *a))(
        params, imgs, lbls, szs, keys)
    u2, l2 = jax.jit(lambda *a: megabatch_agents(mb, *a))(
        params, imgs, lbls, szs, keys)
    assert leaf_scale_ulps(u1, u2) <= ULP_BOUND
    # per-client losses: bit-identical at step 1; later steps read
    # params that already differ at the ulp level, so the stream is
    # ulp-close, not bitwise
    np.testing.assert_allclose(np.asarray(l1), np.asarray(l2),
                               rtol=1e-5, atol=1e-7)
    u3, _ = jax.jit(lambda *a: megabatch_agents(mb, *a, chunk=3))(
        params, imgs, lbls, szs, keys)
    assert leaf_scale_ulps(u2, u3) <= ULP_BOUND
    with pytest.raises(ValueError, match="agent_chunk"):
        megabatch_agents(mb, params, imgs, lbls, szs, keys, chunk=4)


def test_straggler_segment_masking_small():
    """Cheap tier-1 twin of the slow-gated
    ``test_straggler_segment_masking_equals_masked_step``: mid-schedule
    truncation AND the zero-budget exact no-op in one quarter-size run
    (budgets [2,1,0,2] exercise full/truncated/absent clients at once);
    the full-size schedule stays behind -m slow."""
    cfg, model, params, norm, (imgs, lbls, szs) = _setup(
        m=4, synth_train_size=96, straggler_rate=0.5, straggler_epochs=1)
    keys = jax.random.split(jax.random.PRNGKey(5), cfg.num_agents)
    budgets = jnp.array([2, 1, 0, 2], jnp.int32)
    lt, mb = _both_trainers(cfg, model, norm)
    u1, l1 = jax.jit(lambda *a: vmap_agents(lt, *a[:-1], ep_budget=a[-1]))(
        params, imgs, lbls, szs, keys, budgets)
    u2, l2 = jax.jit(
        lambda *a: megabatch_agents(mb, *a[:-1], ep_budget=a[-1]))(
        params, imgs, lbls, szs, keys, budgets)
    assert leaf_scale_ulps(u1, u2) <= ULP_BOUND
    np.testing.assert_allclose(np.asarray(l1), np.asarray(l2),
                               rtol=1e-5, atol=1e-7)
    # the budget-0 client is an exact no-op on both layouts
    for u in (u1, u2):
        for leaf in jax.tree_util.tree_leaves(u):
            np.testing.assert_array_equal(np.asarray(leaf)[2], 0.0)


@pytest.mark.slow
def test_straggler_segment_masking_equals_masked_step():
    """Folding the per-client step masks into the segment weights must
    equal the vmap layout's per-client masked step: clients truncated
    mid-schedule (epoch budgets 1 of 2) contribute exactly their
    completed epochs (losses ulp-close — later steps read ulp-shifted
    params). Slow-gated: ``test_straggler_segment_masking_small`` is
    the tier-1 twin."""
    cfg, model, params, norm, (imgs, lbls, szs) = _setup(
        straggler_rate=0.5, straggler_epochs=1)
    m = cfg.num_agents
    keys = jax.random.split(jax.random.PRNGKey(5), m)
    budgets = jnp.array([2, 1, 2, 1, 1, 2], jnp.int32)
    lt, mb = _both_trainers(cfg, model, norm)
    u1, l1 = jax.jit(lambda *a: vmap_agents(lt, *a[:-1], ep_budget=a[-1]))(
        params, imgs, lbls, szs, keys, budgets)
    u2, l2 = jax.jit(
        lambda *a: megabatch_agents(mb, *a[:-1], ep_budget=a[-1]))(
        params, imgs, lbls, szs, keys, budgets)
    assert leaf_scale_ulps(u1, u2) <= ULP_BOUND
    # per-client losses: bit-identical at step 1; later steps read
    # params that already differ at the ulp level, so the stream is
    # ulp-close, not bitwise
    np.testing.assert_allclose(np.asarray(l1), np.asarray(l2),
                               rtol=1e-5, atol=1e-7)
    # a fully-truncated client (budget 0) must produce a zero update on
    # both layouts — the all-masked segment is an exact no-op
    zero = jnp.array([0, 2, 2, 2, 2, 2], jnp.int32)
    uz, _ = jax.jit(
        lambda *a: megabatch_agents(mb, *a[:-1], ep_budget=a[-1]))(
        params, imgs, lbls, szs, keys, zero)
    for leaf in jax.tree_util.tree_leaves(uz):
        np.testing.assert_array_equal(np.asarray(leaf)[0], 0.0)


def test_trainer_parity_bf16():
    """bf16 compute rides the megabatch layout through the same parity
    ladder at its measured tolerance (f32-accumulated bf16 rounds)."""
    cfg, model, params, norm, (imgs, lbls, szs) = _setup(
        dtype="bf16", local_ep=1)
    keys = jax.random.split(jax.random.PRNGKey(7), cfg.num_agents)
    lt, mb = _both_trainers(cfg, model, norm)
    u1, l1 = jax.jit(lambda *a: vmap_agents(lt, *a))(
        params, imgs, lbls, szs, keys)
    u2, l2 = jax.jit(lambda *a: megabatch_agents(mb, *a))(
        params, imgs, lbls, szs, keys)
    for a, b in zip(jax.tree_util.tree_leaves(u1),
                    jax.tree_util.tree_leaves(u2), strict=True):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   atol=BF16_ATOL, rtol=0)
    # per-client losses: bit-identical at step 1; later steps read
    # params that already differ at the ulp level, so the stream is
    # ulp-close, not bitwise
    np.testing.assert_allclose(np.asarray(l1), np.asarray(l2),
                               rtol=1e-5, atol=1e-7)


# ------------------------------------------------------- round parity ---

def test_round_parity_faults():
    """Full round program under faults (dropout + corrupt payloads +
    validation + spare-corrupt): the megabatch round must produce the
    same participation decisions (fault scalars bitwise — the draw and
    the masks never touch the layout) and ulp-close new params."""
    cfg, model, params, norm, arrays = _setup(
        m=8, local_ep=1, dropout_rate=0.3, corrupt_rate=0.3,
        payload_norm_cap=100.0, faults_spare_corrupt=True)
    key = jax.random.PRNGKey(42)
    fn_v = make_round_fn(cfg, model, norm, *arrays)
    p1, i1 = fn_v(params, key)
    fn_m = make_round_fn(cfg.replace(train_layout="megabatch"), model,
                         norm, *arrays)
    assert fn_m.family == "round_mb"
    p2, i2 = fn_m(params, key)
    assert leaf_scale_ulps(p1, p2) <= ULP_BOUND
    np.testing.assert_array_equal(np.asarray(i1["sampled"]),
                                  np.asarray(i2["sampled"]))
    for k in ("fault_dropped", "fault_straggled", "fault_voters"):
        np.testing.assert_array_equal(np.asarray(i1[k]), np.asarray(i2[k]),
                                      err_msg=k)
    np.testing.assert_allclose(float(i1["train_loss"]),
                               float(i2["train_loss"]), rtol=1e-6)


def test_chained_adopts_megabatch_small():
    """Cheap tier-1 twin of the slow-gated
    ``test_chained_adopts_megabatch_unchanged``: the same 2-round
    chained_mb vs per-round round_mb comparison on a quarter-size
    setup — block adoption is a program-structure property, not a
    schedule-length one; the full-size run stays behind -m slow."""
    from defending_against_backdoors_with_robust_learning_rate_tpu.fl.rounds import (
        make_chained_round_fn)
    cfg, model, params, norm, arrays = _setup(
        m=4, local_ep=1, synth_train_size=96)
    mcfg = cfg.replace(train_layout="megabatch")
    base = jax.random.PRNGKey(9)
    fn = make_round_fn(mcfg, model, norm, *arrays)
    p_seq = params
    for r in (1, 2):
        p_seq, _ = fn(p_seq, jax.random.fold_in(base, r))
    chained = make_chained_round_fn(mcfg, model, norm, *arrays)
    assert chained.family == "chained_mb"
    p_blk, info = chained(params, base, jnp.arange(1, 3))
    assert info["train_loss"].shape == (2,)
    for a, b in zip(jax.tree_util.tree_leaves(p_seq),
                    jax.tree_util.tree_leaves(p_blk), strict=True):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-5, rtol=1e-5)


@pytest.mark.slow
def test_chained_adopts_megabatch_unchanged():
    """The chained lax.scan block adopts the megabatch step unchanged:
    a 2-round chained_mb block matches two per-round round_mb dispatches
    (the driver-loop key derivation, ~1 ulp fusion differences).
    Slow-gated: ``test_chained_adopts_megabatch_small`` is the tier-1
    twin."""
    from defending_against_backdoors_with_robust_learning_rate_tpu.fl.rounds import (
        make_chained_round_fn)
    cfg, model, params, norm, arrays = _setup(local_ep=1)
    mcfg = cfg.replace(train_layout="megabatch")
    base = jax.random.PRNGKey(9)
    fn = make_round_fn(mcfg, model, norm, *arrays)
    p_seq = params
    for r in (1, 2):
        p_seq, _ = fn(p_seq, jax.random.fold_in(base, r))
    chained = make_chained_round_fn(mcfg, model, norm, *arrays)
    assert chained.family == "chained_mb"
    p_blk, info = chained(params, base, jnp.arange(1, 3))
    assert info["train_loss"].shape == (2,)
    for a, b in zip(jax.tree_util.tree_leaves(p_seq),
                    jax.tree_util.tree_leaves(p_blk), strict=True):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-5, rtol=1e-5)


def test_cohort_churn_flag_plumbing():
    """Cohort + churn compose with the megabatch layout: the in-program
    cohort draw, the churn-present filter and the shortfall active mask
    are layout-independent (ids bitwise), and the trained params stay
    ulp-close."""
    cfg, model, params, norm, arrays = _setup(
        m=8, local_ep=1, cohort_sampled="on", cohort_size=4,
        churn_available=0.75, churn_period=2)
    rows = tuple(a[:4] for a in arrays)   # any fixed [m, ...] cohort rows
    key = jax.random.PRNGKey(21)
    fn_v = jax.jit(make_cohort_step(cfg, model, norm))
    p1, i1 = fn_v(params, key, jnp.int32(3), *rows)
    fn_m = jax.jit(make_cohort_step(cfg.replace(train_layout="megabatch"),
                                    model, norm))
    p2, i2 = fn_m(params, key, jnp.int32(3), *rows)
    np.testing.assert_array_equal(np.asarray(i1["sampled"]),
                                  np.asarray(i2["sampled"]))
    assert leaf_scale_ulps(p1, p2) <= ULP_BOUND
    np.testing.assert_allclose(float(i1["train_loss"]),
                               float(i2["train_loss"]), rtol=1e-6)


@pytest.mark.slow  # sharded twin of the round parity: the CI
# `megabatch-parity` smoke byte/ulp-compares the 8-device sharded path
# end-to-end, and the vmap-vs-sharded cross-path bound is already
# pinned per layout — this in-process pair of shard_map compiles is the
# redundant heavy variant
def test_sharded_megabatch_parity():
    from defending_against_backdoors_with_robust_learning_rate_tpu.parallel.mesh import (
        make_mesh)
    from defending_against_backdoors_with_robust_learning_rate_tpu.parallel.rounds import (
        make_sharded_round_fn)
    assert len(jax.devices()) == 8
    cfg, model, params, norm, arrays = _setup(m=8, local_ep=1)
    mesh = make_mesh(8)
    key = jax.random.PRNGKey(13)
    fn_v = make_sharded_round_fn(cfg, model, norm, mesh, *arrays)
    p1, i1 = fn_v(params, key)
    fn_m = make_sharded_round_fn(cfg.replace(train_layout="megabatch"),
                                 model, norm, mesh, *arrays)
    assert fn_m.family == "round_sharded_mb"
    p2, i2 = fn_m(params, key)
    assert leaf_scale_ulps(p1, p2) <= ULP_BOUND
    np.testing.assert_allclose(float(i1["train_loss"]),
                               float(i2["train_loss"]), rtol=1e-6)


@pytest.mark.slow  # telemetry-full + bucketed-aggregation variant of the
# sharded parity — the tier-1 plain round + the contract pins
# (sharded_rlr_avg_bucket_mb in analysis_baseline.json) are the cheap
# twins; this pair of full-telemetry shard_map compiles is redundant
# coverage of the same fold
def test_sharded_megabatch_bucket_tel_full():
    from defending_against_backdoors_with_robust_learning_rate_tpu.parallel.mesh import (
        make_mesh)
    from defending_against_backdoors_with_robust_learning_rate_tpu.parallel.rounds import (
        make_sharded_round_fn)
    cfg, model, params, norm, arrays = _setup(
        m=8, local_ep=1, telemetry="full", agg_layout="bucket")
    mesh = make_mesh(8)
    key = jax.random.PRNGKey(17)
    fn_v = make_sharded_round_fn(cfg, model, norm, mesh, *arrays)
    p1, i1 = fn_v(params, key)
    fn_m = make_sharded_round_fn(cfg.replace(train_layout="megabatch"),
                                 model, norm, mesh, *arrays)
    p2, i2 = fn_m(params, key)
    assert leaf_scale_ulps(p1, p2) <= ULP_BOUND
    for k in sorted(i1):
        if k.startswith("tel_"):
            np.testing.assert_allclose(np.asarray(i1[k]),
                                       np.asarray(i2[k]),
                                       atol=1e-4, rtol=1e-4, err_msg=k)


# ------------------------------------------- families / bank / naming ---

def test_plan_programs_mb_family_names():
    """The planner vocabulary: megabatch configs plan *_mb families;
    the diagnostics degrade resolves them back to the vmap names (no
    mixed-layout plans); eval families never suffix."""
    cfg, model, _, norm, _ = _setup(local_ep=1, chain=2, snap=2)
    fed = get_federated_data(cfg)
    mcfg = cfg.replace(train_layout="megabatch")
    fams = [s.family for s in compile_cache.plan_programs(
        mcfg, model, norm, fed)]
    assert fams == ["round_mb", "chained_mb", "eval_val", "eval_poison"]
    # diagnostics degrade: the whole plan resolves to the vmap families
    fams_d = [s.family for s in compile_cache.plan_programs(
        mcfg.replace(diagnostics=True), model, norm, fed)]
    assert "round" in fams_d and "round_diag" in fams_d
    assert not any(f.endswith("_mb") for f in fams_d)


def test_aot_bank_roundtrip_mb_family(tmp_path):
    """The megabatch families are AOT-banked like every family: a cold
    get_or_compile banks round_mb, a second call is a pure
    deserialize hit — and the fingerprint differs from the vmap twin's
    (distinct programs must never share an executable)."""
    cfg, model, _, norm, _ = _setup(local_ep=1)
    fed = get_federated_data(cfg)
    mcfg = cfg.replace(train_layout="megabatch",
                       compile_cache_dir=str(tmp_path))
    spec = compile_cache.plan_programs(mcfg, model, norm, fed)[0]
    assert spec.family == "round_mb"
    bank = compile_cache.AotBank(str(tmp_path))
    _, hit, _, entry = bank.get_or_compile(spec.family, mcfg,
                                           spec.jit_obj,
                                           spec.example_args)
    assert not hit
    _, hit2, _, _ = bank.get_or_compile(spec.family, mcfg, spec.jit_obj,
                                        spec.example_args)
    assert hit2
    vfp = compile_cache.fingerprint(mcfg.replace(train_layout="vmap"),
                                    "round", spec.example_args)
    assert entry["fingerprint"] != vfp


def test_chained_families_donate_params():
    """Donation-audit pin (ISSUE 10 / contracts.DONATED_FAMILIES): every
    chained family must donate its params argument — the lowered
    StableHLO carries the input-output alias on arg 0, so no parameter
    copy rides a dispatched block. The per-round families deliberately
    keep params alive (diagnostics prev_params, parity callers,
    supervised retry) — pinned un-aliased here so the asymmetry is a
    contract, not an accident."""
    from defending_against_backdoors_with_robust_learning_rate_tpu.analysis.contracts import (
        DONATED_FAMILIES)
    cfg, model, _, norm, _ = _setup(local_ep=1, chain=2, snap=2)
    fed = get_federated_data(cfg)
    seen = set()
    for layout in ("vmap", "megabatch"):
        lcfg = cfg.replace(train_layout=layout)
        for spec in compile_cache.plan_programs(lcfg, model, norm, fed):
            if not spec.family.startswith(("round", "chained")):
                continue
            text = compile_cache.lower_program(
                spec.jit_obj, spec.example_args).as_text()
            donated = "tf.aliasing_output" in text
            if spec.family in DONATED_FAMILIES:
                assert donated, f"{spec.family} must donate params"
                seen.add(spec.family)
            else:
                assert not donated, \
                    f"{spec.family} must NOT donate (prev_params/retry)"
    assert {"chained", "chained_mb"} <= seen


def test_resolver_run_name_and_degrade():
    """resolved_train_layout is the single source: megabatch +
    diagnostics degrades to vmap, the run_name cell follows the
    RESOLVED layout, and the degraded fingerprint shares the vmap key
    (same program, same cache entry)."""
    from defending_against_backdoors_with_robust_learning_rate_tpu.utils.metrics import (
        run_name)
    cfg = Config(train_layout="megabatch")
    assert compile_cache.resolved_train_layout(cfg) == "megabatch"
    assert compile_cache.family_suffix(cfg) == "_mb"
    assert "-tl:mb" in run_name(cfg)
    d = cfg.replace(diagnostics=True)
    assert compile_cache.resolved_train_layout(d) == "vmap"
    assert compile_cache.family_suffix(d) == ""
    assert "-tl:mb" not in run_name(d)
    ex = (jnp.zeros(3),)
    assert compile_cache.fingerprint(d, "round", ex) == \
        compile_cache.fingerprint(
            Config(train_layout="vmap", diagnostics=True), "round", ex)
    with pytest.raises(ValueError, match="train_layout"):
        compile_cache.resolved_train_layout(
            cfg.replace(train_layout="bogus"))


def test_engine_degrades_megabatch_diagnostics(capsys, tmp_path):
    """The engine prints the loud remediation hint and actually runs the
    vmap layout (run dir has no -tl:mb cell) instead of crashing."""
    from defending_against_backdoors_with_robust_learning_rate_tpu import (
        train)
    cfg = Config(data="synthetic", num_agents=4, bs=16, local_ep=1,
                 synth_train_size=64, synth_val_size=32, eval_bs=32,
                 rounds=1, snap=1, seed=0, diagnostics=True,
                 train_layout="megabatch", robustLR_threshold=2,
                 compile_cache=False, tensorboard=False,
                 log_dir=str(tmp_path))
    train.run(cfg)
    out = capsys.readouterr().out
    assert "degrading this run to" in out
    assert not any("-tl:mb" in d for d in os.listdir(tmp_path))


# --------------------------------------------------- analytic FLOPs -----

def test_flops_per_example_analytic():
    """The registry's analytic FLOP model (bench.py's compile-free MFU
    source): positive, monotone in image size, and within 2x of XLA's
    own cost analysis of the compiled fwd+bwd step (the 3x-forward
    convention vs the compiler's exact count)."""
    from bench import bench_config, train_step_flops
    f28 = flops_per_example("fmnist", "cnn", (28, 28, 1))
    f8 = flops_per_example("synthetic", "cnn", (8, 8, 1))
    assert f28 and f8 and f28 > f8 > 0
    assert flops_per_example("cifar10", "cnn", (32, 32, 3)) > f28
    assert flops_per_example("cifar10", "resnet9", (32, 32, 3)) is None
    cfg = bench_config("fmnist").replace(bs=16)
    model = get_model(cfg.data, cfg.model_arch, cfg.dtype)
    params = init_params(model, (28, 28, 1), jax.random.PRNGKey(0))
    norm = make_normalizer(0.5, 0.5, False)
    xla_step = train_step_flops(model, params, norm, cfg, (28, 28, 1))
    analytic_step = 3.0 * f28 * cfg.bs
    assert 0.5 < analytic_step / xla_step < 2.0, (analytic_step, xla_step)
