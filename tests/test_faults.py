"""Fault-injection & elastic-participation subsystem (faults/).

Pins the subsystem's three contracts:
- parity gate: an all-ones participation mask is bit-identical to the dense
  path for EVERY aggregation rule, on the single-device vmap path and on
  the faked 8-device shard_map mesh (the masked formulations degenerate to
  the same op sequences — faults/masking.py docstring);
- static compilation: varying fault draws across rounds reuse ONE compiled
  round program (fault sampling is in-jit, shapes never change);
- semantics: thinned electorates flip the RLR vote where hand-computed,
  corrupt payloads are validated out server-side, stragglers' updates
  truncate to their epoch budget, spared attackers never drop out.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from defending_against_backdoors_with_robust_learning_rate_tpu.config import Config
from defending_against_backdoors_with_robust_learning_rate_tpu.data.registry import (
    get_federated_data)
from defending_against_backdoors_with_robust_learning_rate_tpu.faults import (
    masking, model as fmodel)
from defending_against_backdoors_with_robust_learning_rate_tpu.fl.common import (
    make_normalizer)
from defending_against_backdoors_with_robust_learning_rate_tpu.fl.rounds import (
    make_chained_round_fn, make_round_fn)
from defending_against_backdoors_with_robust_learning_rate_tpu.models.registry import (
    get_model, init_params)
from defending_against_backdoors_with_robust_learning_rate_tpu.ops.aggregate import (
    agg_avg, agg_comed, agg_krum, agg_rfa, agg_sign, agg_trmean, robust_lr)

AGGRS = ["avg", "comed", "sign", "trmean", "krum", "rfa"]


def _updates(m=8, seed=0):
    rng = np.random.default_rng(seed)
    return {"w": jnp.asarray(rng.normal(size=(m, 5, 3)).astype(np.float32)),
            "b": jnp.asarray(rng.normal(size=(m, 7)).astype(np.float32))}


def _sizes(m=8, seed=1):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.integers(10, 200, size=m).astype(np.int32))


def _dense(aggr, u, sizes, mask=None):
    if aggr == "avg":
        return agg_avg(u, sizes, mask=mask)
    if aggr == "comed":
        return agg_comed(u, mask=mask)
    if aggr == "sign":
        return agg_sign(u, mask=mask)
    if aggr == "trmean":
        return agg_trmean(u, 1, mask=mask)
    if aggr == "krum":
        return agg_krum(u, 1, mask=mask)
    if aggr == "rfa":
        return agg_rfa(u, mask=mask)
    raise ValueError(aggr)


def _leaves_equal(a, b):
    for x, y in zip(jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b), strict=True):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# The masked weighted average multiplies by a reciprocal and reduces a
# `where`-selected stack; whether XLA:CPU emits the bit-same fused sum as
# for the dense expression is its choice, and under jaxlib 0.9.0 it does
# not: avg differs by 1 ulp of the leaf's largest magnitude (3e-8 abs).
# Pinned at 2. The sign rule reduces integer-valued f32 partials,
# which sum exactly in any order, and stays pinned bitwise.
AVG_ULPS = 2


def _leaves_match(aggr, a, b):
    if aggr != "avg":
        return _leaves_equal(a, b)
    for x, y in zip(jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b), strict=True):
        x, y = np.asarray(x, np.float32), np.asarray(y, np.float32)
        scale = np.spacing(max(np.max(np.abs(x)), np.max(np.abs(y))))
        assert np.max(np.abs(x - y)) <= AVG_ULPS * scale


# -------------------------------------------------- parity gate: all-ones ---

@pytest.mark.parametrize("aggr", AGGRS)
def test_all_ones_mask_matches_dense_bitwise(aggr):
    """Every rule with an all-ones mask == the dense rule, bit for bit
    (jitted, so XLA's fusion/strength-reduction choices are in play);
    avg within AVG_ULPS (see there)."""
    u, sizes = _updates(), _sizes()
    mask = jnp.ones((8,), bool)
    dense = jax.jit(lambda u, s: _dense(aggr, u, s))(u, sizes)
    masked = jax.jit(lambda u, s, mk: _dense(aggr, u, s, mask=mk))(
        u, sizes, mask)
    _leaves_match(aggr, dense, masked)


def test_all_ones_mask_rlr_matches_dense_bitwise():
    u = _updates()
    mask = jnp.ones((8,), bool)
    dense = jax.jit(lambda u: robust_lr(u, 4.0, 1.0))(u)
    masked = jax.jit(lambda u, mk: robust_lr(u, 4.0, 1.0, mask=mk))(u, mask)
    _leaves_equal(dense, masked)


@pytest.mark.parametrize("aggr", AGGRS)
def test_all_ones_mask_matches_dense_sharded(aggr):
    """Same parity gate on the faked 8-device mesh: the masked collective
    aggregation (masked psums / sentinel-padded all_to_all chunks) with an
    all-ones mask == the dense collective path, bit for bit (avg within
    AVG_ULPS)."""
    from jax.sharding import PartitionSpec as P
    from jax import shard_map
    from defending_against_backdoors_with_robust_learning_rate_tpu.parallel.mesh import (
        make_mesh)
    from defending_against_backdoors_with_robust_learning_rate_tpu.parallel.rounds import (
        _sharded_aggregate)

    assert len(jax.devices()) == 8, "conftest must fake 8 CPU devices"
    d = 8
    u, sizes = _updates(m=16), _sizes(m=16)
    cfg = Config(aggr=aggr, num_corrupt=1, num_agents=16)
    mask = jnp.ones((16,), bool)

    def dense_body(u, szs):
        return _sharded_aggregate(u, szs, cfg, d, jax.random.PRNGKey(0))

    def masked_body(u, szs, mask):
        ml = jax.lax.dynamic_slice_in_dim(
            mask, jax.lax.axis_index("agents") * 2, 2, 0)
        return _sharded_aggregate(u, szs, cfg, d, jax.random.PRNGKey(0),
                                  mask_local=ml, mask_full=mask)

    mesh = make_mesh(d)
    dense = jax.jit(shard_map(
        dense_body, mesh=mesh, in_specs=(P("agents"), P("agents")),
        out_specs=P(), check_vma=False))(u, sizes)
    masked = jax.jit(shard_map(
        masked_body, mesh=mesh,
        in_specs=(P("agents"), P("agents"), P()),
        out_specs=P(), check_vma=False))(u, sizes, mask)
    _leaves_match(aggr, dense, masked)


def _setup(aggr="avg", num_agents=8, **kw):
    cfg = Config(data="synthetic", num_agents=num_agents, bs=16, local_ep=1,
                 synth_train_size=128, synth_val_size=32, aggr=aggr,
                 num_corrupt=1, poison_frac=1.0,
                 robustLR_threshold=3 if aggr in ("avg", "sign") else 0,
                 seed=11, **kw)
    fed = get_federated_data(cfg)
    model = get_model(cfg.data, cfg.model_arch, cfg.dtype)
    params = init_params(model, cfg.image_shape, jax.random.PRNGKey(0))
    norm = make_normalizer(fed.mean, fed.std, fed.raw_is_normalized)
    arrays = (jnp.asarray(fed.train.images), jnp.asarray(fed.train.labels),
              jnp.asarray(fed.train.sizes))
    return cfg, model, params, norm, arrays


def test_all_ones_faults_round_matches_dense_round_bitwise():
    """End-to-end round-level parity gate on the vmap path: a faults config
    whose draw is an all-ones mask (straggler budget == local_ep) produces
    the dense round's new params — fault sampling must not perturb any
    existing key stream (a perturbed stream differs in the first digits;
    the avg server step itself is held to AVG_ULPS, see there)."""
    cfg, model, params, norm, arrays = _setup("avg")
    key = jax.random.PRNGKey(42)
    p1, i1 = make_round_fn(cfg, model, norm, *arrays)(params, key)
    fcfg = cfg.replace(straggler_rate=1.0, straggler_epochs=cfg.local_ep)
    p2, i2 = make_round_fn(fcfg, model, norm, *arrays)(params, key)
    _leaves_match("avg", p1, p2)
    assert float(i2["fault_voters"]) == cfg.agents_per_round
    assert float(i2["fault_dropped"]) == 0.0


def test_all_ones_faults_round_matches_dense_round_sharded():
    """Round-level parity gate on the faked 8-device shard_map mesh."""
    from defending_against_backdoors_with_robust_learning_rate_tpu.parallel.mesh import (
        make_mesh)
    from defending_against_backdoors_with_robust_learning_rate_tpu.parallel.rounds import (
        make_sharded_round_fn)

    cfg, model, params, norm, arrays = _setup("avg")
    mesh = make_mesh(8)
    key = jax.random.PRNGKey(42)
    p1, _ = make_sharded_round_fn(cfg, model, norm, mesh, *arrays)(params, key)
    fcfg = cfg.replace(straggler_rate=1.0, straggler_epochs=cfg.local_ep)
    p2, i2 = make_sharded_round_fn(fcfg, model, norm, mesh, *arrays)(
        params, key)
    _leaves_equal(p1, p2)
    assert float(i2["fault_voters"]) == cfg.agents_per_round


def test_dropout_round_sharded_matches_vmap():
    """With real dropout the sharded and single-device rounds must still
    agree (same replicated fault draw on every device)."""
    from defending_against_backdoors_with_robust_learning_rate_tpu.parallel.mesh import (
        make_mesh)
    from defending_against_backdoors_with_robust_learning_rate_tpu.parallel.rounds import (
        make_sharded_round_fn)

    cfg, model, params, norm, arrays = _setup("avg")
    cfg = cfg.replace(dropout_rate=0.4)
    key = jax.random.PRNGKey(7)
    p1, i1 = make_round_fn(cfg, model, norm, *arrays)(params, key)
    p2, i2 = make_sharded_round_fn(cfg, model, norm, make_mesh(8), *arrays)(
        params, key)
    for a, b in zip(jax.tree_util.tree_leaves(p1),
                    jax.tree_util.tree_leaves(p2), strict=True):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-5, rtol=1e-5)
    assert float(i1["fault_voters"]) == float(i2["fault_voters"]) \
        < cfg.agents_per_round


# ------------------------------------------------------- RLR under churn ---

def test_thinned_majority_flips_rlr_vote():
    """Hand-computed: 5 voters all agreeing pass threshold 4 (+lr); masking
    2 honest voters thins the vote to 3 < 4 and the lr flips to -lr."""
    u = {"w": jnp.ones((5, 4), jnp.float32)}
    full = robust_lr(u, 4.0, 1.0, mask=jnp.ones((5,), bool))
    np.testing.assert_array_equal(np.asarray(full["w"]), 1.0)
    thinned = robust_lr(u, 4.0, 1.0,
                        mask=jnp.asarray([True, True, True, False, False]))
    np.testing.assert_array_equal(np.asarray(thinned["w"]), -1.0)


def test_scaled_rlr_threshold_tracks_electorate():
    """rlr_threshold_mode='scaled': threshold 4 over m=5 becomes 4*3/5=2.4
    under a 3-voter mask, so 3 agreeing survivors still pass the vote."""
    cfg = Config(robustLR_threshold=4, rlr_threshold_mode="scaled")
    mask = jnp.asarray([True, True, True, False, False])
    thr = masking.rlr_threshold(cfg, mask)
    np.testing.assert_allclose(float(thr), 2.4)
    u = {"w": jnp.ones((5, 4), jnp.float32)}
    lr = robust_lr(u, thr, 1.0, mask=mask)
    np.testing.assert_array_equal(np.asarray(lr["w"]), 1.0)


# ------------------------------------------- corrupt payloads + validation ---

def test_payload_validation_rejects_garbage():
    u = _updates(m=4)
    corrupt = jnp.asarray([False, True, False, False])
    bad = fmodel.inject_corrupt(u, corrupt, "nan")
    valid = fmodel.payload_valid(bad)
    np.testing.assert_array_equal(np.asarray(valid),
                                  [True, False, True, True])
    # huge-but-finite payloads pass the finite check but not the norm cap
    huge = fmodel.inject_corrupt(u, corrupt, "huge")
    assert bool(fmodel.payload_valid(huge)[1])
    np.testing.assert_array_equal(
        np.asarray(fmodel.payload_valid(huge, norm_cap=1e3)),
        [True, False, True, True])


@pytest.mark.parametrize("aggr", AGGRS)
def test_masked_aggregate_ignores_nan_payloads(aggr):
    """A NaN row behind the mask must never reach the aggregate: the masked
    result equals the dense aggregate of the surviving rows alone."""
    u, sizes = _updates(), _sizes()
    corrupt = jnp.zeros((8,), bool).at[2].set(True)
    bad = fmodel.inject_corrupt(u, corrupt, "nan")
    mask = ~corrupt
    masked = jax.jit(lambda u, s, mk: _dense(aggr, u, s, mask=mk))(
        bad, sizes, mask)
    for leaf in jax.tree_util.tree_leaves(masked):
        assert bool(jnp.isfinite(leaf).all()), aggr
    # reference: dense aggregation over the 7 survivors only
    keep = np.asarray(mask)
    u7 = jax.tree_util.tree_map(lambda x: x[keep], u)
    expect = _dense(aggr, u7, sizes[jnp.asarray(keep)])
    for a, b in zip(jax.tree_util.tree_leaves(masked),
                    jax.tree_util.tree_leaves(expect), strict=True):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-6, rtol=1e-6)


# ------------------------------------------------ fault model semantics ---

def test_fault_draw_seeded_and_never_empty():
    cfg = Config(dropout_rate=1.0)
    key = jax.random.PRNGKey(3)
    d1 = fmodel.sample_faults(cfg, key, 16)
    d2 = fmodel.sample_faults(cfg, key, 16)
    np.testing.assert_array_equal(np.asarray(d1.participate),
                                  np.asarray(d2.participate))
    # dropout_rate=1 drops everyone except the guaranteed survivor
    assert int(np.sum(np.asarray(d1.participate))) == 1


def test_spare_corrupt_keeps_attackers_online():
    cfg = Config(dropout_rate=1.0, faults_spare_corrupt=True, num_corrupt=2)
    flags = jnp.asarray([True, True] + [False] * 6)
    d = fmodel.sample_faults(cfg, jax.random.PRNGKey(0), 8, flags)
    # attackers never drop; all honest agents dropped at rate 1.0
    np.testing.assert_array_equal(np.asarray(d.participate),
                                  np.asarray(flags))


def test_straggler_budget_truncates_local_training():
    """ep_budget=local_ep reproduces the dense update bit-for-bit; a zero
    budget produces an exactly-zero update (every step is a masked no-op)."""
    from defending_against_backdoors_with_robust_learning_rate_tpu.fl.client import (
        make_local_train)

    cfg, model, params, norm, arrays = _setup("avg")
    cfg2 = cfg.replace(local_ep=2)
    imgs, lbls, sizes = (np.asarray(a) for a in arrays)
    key = jax.random.PRNGKey(5)

    dense = make_local_train(model, cfg2, norm)
    u_full, _ = jax.jit(dense)(params, jnp.asarray(imgs[0]),
                               jnp.asarray(lbls[0]), jnp.asarray(sizes[0]),
                               key)
    strag = make_local_train(model, cfg2.replace(straggler_rate=0.5), norm)
    u_same, _ = jax.jit(strag)(params, jnp.asarray(imgs[0]),
                               jnp.asarray(lbls[0]), jnp.asarray(sizes[0]),
                               key, jnp.int32(2))
    _leaves_equal(u_full, u_same)
    u_zero, _ = jax.jit(strag)(params, jnp.asarray(imgs[0]),
                               jnp.asarray(lbls[0]), jnp.asarray(sizes[0]),
                               key, jnp.int32(0))
    for leaf in jax.tree_util.tree_leaves(u_zero):
        np.testing.assert_array_equal(np.asarray(leaf), 0.0)
    u_one, _ = jax.jit(strag)(params, jnp.asarray(imgs[0]),
                              jnp.asarray(lbls[0]), jnp.asarray(sizes[0]),
                              key, jnp.int32(1))
    assert any(not np.array_equal(np.asarray(a), np.asarray(b))
               for a, b in zip(jax.tree_util.tree_leaves(u_one),
                               jax.tree_util.tree_leaves(u_full), strict=True))


def test_all_invalid_round_is_a_finite_noop():
    """Every payload corrupt (the dropout survivor guarantee can't help:
    validation kills the survivor too) -> zero aggregate, params unchanged,
    Effective_Voters logs 0 — never NaN poisoning."""
    cfg, model, params, norm, arrays = _setup("avg")
    cfg = cfg.replace(corrupt_rate=1.0, corrupt_mode="nan")
    fn = make_round_fn(cfg, model, norm, *arrays)
    p, info = fn(params, jax.random.PRNGKey(2))
    assert float(info["fault_voters"]) == 0.0
    _leaves_equal(params, p)


def test_norm_cap_alone_enables_validation():
    """--payload_norm_cap without any fault rate must still route through
    the validation + mask path (a cap that silently no-ops is worse than no
    cap), and with no over-norm payloads it matches dense (avg: within
    AVG_ULPS, see there)."""
    assert Config(payload_norm_cap=5.0).faults_enabled
    cfg, model, params, norm, arrays = _setup("avg")
    key = jax.random.PRNGKey(4)
    p1, _ = make_round_fn(cfg, model, norm, *arrays)(params, key)
    p2, i2 = make_round_fn(cfg.replace(payload_norm_cap=1e9), model, norm,
                           *arrays)(params, key)
    _leaves_match("avg", p1, p2)
    assert float(i2["fault_voters"]) == cfg.agents_per_round


# ------------------------------------------------- static compilation ---

def test_fault_draws_reuse_one_compiled_program():
    """Varying fault draws across rounds hit ONE jit cache entry — faults
    are sampled inside the compiled round, shapes never change."""
    cfg, model, params, norm, arrays = _setup("avg")
    cfg = cfg.replace(dropout_rate=0.5, corrupt_rate=0.2, straggler_rate=0.5)
    fn = make_round_fn(cfg, model, norm, *arrays)
    voters = set()
    for r in range(1, 5):
        params, info = fn(params, jax.random.fold_in(jax.random.PRNGKey(0), r))
        voters.add(float(info["fault_voters"]))
    assert fn.jitted._cache_size() == 1, (
        f"{fn.jitted._cache_size()} compilations for 4 fault draws")
    assert len(voters) > 1, "fault draws never varied across rounds"


def test_chained_faults_match_per_round_dispatch():
    """Device-resident chaining with faults on: the lax.scan block derives
    the identical per-round fault draws (fold_in(base_key, r) keys) and
    carries the Faults/* scalars through the scan."""
    cfg, model, params, norm, arrays = _setup("avg")
    cfg = cfg.replace(dropout_rate=0.4)
    base_key = jax.random.PRNGKey(7)
    n = 3
    fn = make_round_fn(cfg, model, norm, *arrays)
    p_seq, voters = params, []
    for r in range(1, n + 1):
        p_seq, info = fn(p_seq, jax.random.fold_in(base_key, r))
        voters.append(float(info["fault_voters"]))
    chained = make_chained_round_fn(cfg, model, norm, *arrays)
    p_chain, stacked = chained(params, base_key, jnp.arange(1, n + 1))
    for a, b in zip(jax.tree_util.tree_leaves(p_seq),
                    jax.tree_util.tree_leaves(p_chain), strict=True):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-6, rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(stacked["fault_voters"]),
                                  np.array(voters))


# ------------------------------------------------------------ e2e chaos ---

@pytest.mark.slow  # ~26s (ISSUE 12 budget rule: slow-gated behind
# cheap twins BEFORE the buffered-mode tests grew tier-1). Twins in
# tier-1: the masking/draw unit tests above pin every fault mechanism,
# test_driver's smoke runs the driver e2e, and the service chaos drills
# (tests/test_service.py) run the full faults+recovery composition.
def test_chaos_run_completes_and_logs_faults(tmp_path):
    """Acceptance E2E: a short fmnist-geometry run with 30% dropout plus a
    corrupt-payload agent completes every round, logs the Faults/* scalars,
    and stays within tolerance of the fault-free run's accuracy."""
    import json

    from defending_against_backdoors_with_robust_learning_rate_tpu.train import run
    from defending_against_backdoors_with_robust_learning_rate_tpu.utils.metrics import (
        run_name)

    base = Config(data="fmnist", data_dir=str(tmp_path / "nodata"),
                  num_agents=8, bs=32, local_ep=1, rounds=4, snap=2,
                  num_corrupt=1, poison_frac=0.5, robustLR_threshold=3,
                  synth_train_size=256, synth_val_size=64, eval_bs=64,
                  seed=9, log_dir=str(tmp_path), tensorboard=False)
    clean = run(base)
    chaos_cfg = base.replace(dropout_rate=0.3, corrupt_rate=0.15,
                             corrupt_mode="nan", faults_spare_corrupt=True)
    chaos = run(chaos_cfg)
    assert chaos["round"] == base.rounds, "chaos run did not finish"
    assert np.isfinite(chaos["val_acc"]) and np.isfinite(chaos["val_loss"])
    assert abs(chaos["val_acc"] - clean["val_acc"]) < 0.25
    tags = set()
    with open(tmp_path / run_name(chaos_cfg) / "metrics.jsonl") as f:
        for line in f:
            tags.add(json.loads(line)["tag"])
    assert {"Faults/Dropped", "Faults/Straggled",
            "Faults/Effective_Voters"} <= tags


def test_chaos_run_host_sampled_mode(tmp_path):
    """Host-sampled mode under faults: the driver computes the sampled
    slots' corrupt flags host-side and passes them per round (chaining is
    disabled — the chained host scan doesn't carry flags)."""
    from defending_against_backdoors_with_robust_learning_rate_tpu.train import run

    cfg = Config(data="synthetic", num_agents=4, bs=16, local_ep=1,
                 synth_train_size=128, synth_val_size=32, rounds=3, snap=3,
                 num_corrupt=1, seed=9, log_dir=str(tmp_path),
                 tensorboard=False, host_sampled="on", chain=2,
                 dropout_rate=0.3, corrupt_rate=0.2,
                 faults_spare_corrupt=True)
    s = run(cfg)
    assert s["round"] == cfg.rounds
    assert np.isfinite(s["val_loss"]) and np.isfinite(s["val_acc"])
