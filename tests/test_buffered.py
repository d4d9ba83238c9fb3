"""Buffered-async aggregation (ISSUE 12, fl/buffered.py).

The degenerate-case parity pins are the acceptance backbone: with K=m,
staleness 0 (no stragglers) and ``async_staleness_exp=0`` the buffered
tick's fold degenerates to the sync round's exact op sequence —
bit-identical for sign (integer sign-sums reduce exactly in any order),
ulp-close for avg — on the vmap path AND the 8-way shard_map mesh. On
top of that: commit cadence (K=2m commits every
other tick), the pending-arrival ladder (latencies land T ticks later
with staleness T, cross-checked against the host mirror draw), chained ==
per-round, the per-staleness Defense split, loud refusals, and the
family/fingerprint/run_name surface.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from defending_against_backdoors_with_robust_learning_rate_tpu.analysis.contracts import (
    base_check_config)
from defending_against_backdoors_with_robust_learning_rate_tpu.config import (
    Config)
from defending_against_backdoors_with_robust_learning_rate_tpu.data.registry import (
    get_federated_data)
from defending_against_backdoors_with_robust_learning_rate_tpu.fl import (
    buffered)
from defending_against_backdoors_with_robust_learning_rate_tpu.fl.common import (
    make_normalizer)
from defending_against_backdoors_with_robust_learning_rate_tpu.fl.rounds import (
    make_chained_round_fn, make_host_step, make_round_fn)
from defending_against_backdoors_with_robust_learning_rate_tpu.models.registry import (
    get_model, init_params)
from defending_against_backdoors_with_robust_learning_rate_tpu.parallel.mesh import (
    make_mesh)
from defending_against_backdoors_with_robust_learning_rate_tpu.parallel.rounds import (
    make_sharded_round_fn)
from defending_against_backdoors_with_robust_learning_rate_tpu.utils import (
    compile_cache)


def _build(cfg, mesh=None):
    fed = get_federated_data(cfg)
    model = get_model(cfg.data, cfg.model_arch, cfg.dtype)
    norm = make_normalizer(fed.mean, fed.std, fed.raw_is_normalized)
    arrays = (jnp.asarray(fed.train.images),
              jnp.asarray(fed.train.labels),
              jnp.asarray(fed.train.sizes))
    params = init_params(model, fed.train.images.shape[2:],
                         jax.random.PRNGKey(cfg.seed))
    if mesh is None:
        fn = make_round_fn(cfg, model, norm, *arrays)
    else:
        fn = make_sharded_round_fn(cfg, model, norm, mesh, *arrays)
    return fn, params, (model, norm, arrays)


def _carry(cfg, params, per_bin=False):
    return (params, buffered.init_state(cfg, params, per_bin=per_bin))


def _run_pair(cfg, rounds=3, mesh=None):
    """Run sync and buffered (K=m, staleness 0) side by side on the same
    keys; returns (sync_params, async_params, sync_info, async_info)."""
    fn_s, params, _ = _build(cfg, mesh)
    bcfg = cfg.replace(agg_mode="buffered")
    fn_a, params_b, _ = _build(bcfg, mesh)
    carry = _carry(bcfg, params_b)
    base = jax.random.PRNGKey(cfg.seed)
    info_s = info_a = None
    for r in range(1, rounds + 1):
        key = jax.random.fold_in(base, r)
        params, info_s = fn_s(params, key)
        carry, info_a = fn_a(carry, key)
    return params, carry[0], info_s, info_a


def _leaves(t):
    return [np.asarray(x) for x in jax.tree_util.tree_leaves(t)]


# ------------------------------------------------------------- parity ---

def test_vmap_parity_sign_bitwise():
    """K=m / staleness-0 / exp-0 buffered == sync, BITWISE, sign+RLR on
    the vmap path (integer sign-sums are order-free)."""
    cfg = base_check_config().replace(aggr="sign", server_lr=1.0)
    ps, pa, info_s, info_a = _run_pair(cfg)
    for a, b in zip(_leaves(ps), _leaves(pa), strict=True):
        np.testing.assert_array_equal(a, b)
    assert float(info_a["async_committed"]) == 1.0
    assert float(info_a["async_fill"]) == cfg.agents_per_round
    np.testing.assert_allclose(float(info_s["train_loss"]),
                               float(info_a["train_loss"]), rtol=1e-6)


def test_vmap_parity_avg_ulp():
    """Same pin for weighted FedAvg + RLR: the fold arithmetic mirrors
    the sync op sequence (measured bitwise on XLA:CPU; pinned at 1e-6
    for cross-toolchain headroom)."""
    cfg = base_check_config()
    ps, pa, _, _ = _run_pair(cfg)
    for a, b in zip(_leaves(ps), _leaves(pa), strict=True):
        np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-7)


def test_sharded_parity_sign_bitwise():
    """The 8-way shard_map pin, sign+RLR bitwise (the contribution sums
    ride the per-leaf psum plan; fl/buffered.fold_commit is shared)."""
    mesh = make_mesh(8)
    cfg = base_check_config().replace(aggr="sign", server_lr=1.0)
    ps, pa, _, info_a = _run_pair(cfg, mesh=mesh)
    for a, b in zip(_leaves(ps), _leaves(pa), strict=True):
        np.testing.assert_array_equal(a, b)
    assert float(info_a["async_committed"]) == 1.0


def test_sharded_parity_avg_ulp():
    """8-way avg+RLR parity at the ulp tier of a cross-device sum."""
    mesh = make_mesh(8)
    cfg = base_check_config()
    ps, pa, _, _ = _run_pair(cfg, mesh=mesh)
    for a, b in zip(_leaves(ps), _leaves(pa), strict=True):
        np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-7)


# ------------------------------------------------- cadence + staleness ---

def test_commit_cadence_k2m():
    """K=2m commits every other tick; off-tick params are bit-frozen."""
    cfg = base_check_config().replace(agg_mode="buffered",
                                      async_buffer_k=16)
    fn, params, _ = _build(cfg)
    carry = _carry(cfg, params)
    base = jax.random.PRNGKey(0)
    p_prev = _leaves(carry[0])
    for r in range(1, 5):
        carry, info = fn(carry, jax.random.fold_in(base, r))
        committed = float(info["async_committed"])
        assert committed == float(r % 2 == 0)
        assert float(info["async_fill"]) == 8.0 * (2 - r % 2)
        p_now = _leaves(carry[0])
        if not committed:
            for a, b in zip(p_prev, p_now, strict=True):
                np.testing.assert_array_equal(a, b)
        else:
            assert any(not np.array_equal(a, b)
                       for a, b in zip(p_prev, p_now, strict=True))
        p_prev = p_now


def test_pending_arrivals_match_host_mirror():
    """Arrival timing: a latency-T draw lands exactly T ticks later with
    staleness T. The emitted per-tick staleness histogram must equal the
    arrival schedule predicted from the host mirror draw
    (fl/buffered.host_latency_draw — the churn host-mirror idiom)."""
    cfg = base_check_config().replace(
        agg_mode="buffered", straggler_rate=0.7, async_max_staleness=3,
        async_buffer_k=10_000)   # never commits: hist accumulates
    fn, params, _ = _build(cfg)
    carry = _carry(cfg, params)
    base = jax.random.PRNGKey(cfg.seed)
    S = cfg.async_max_staleness
    n = 5
    # host-side arrival schedule: draws at tick t with latency T arrive
    # at tick t+T into staleness bin T
    expect = np.zeros((n + 1, S + 1))
    for t in range(1, n + 1):
        for T in buffered.host_latency_draw(cfg, t, seed=cfg.seed):
            if t + T <= n:
                expect[t + T, int(T)] += 1
    cum = np.zeros(S + 1)
    for r in range(1, n + 1):
        carry, info = fn(carry, jax.random.fold_in(base, r))
        cum += expect[r]
        np.testing.assert_array_equal(
            np.asarray(info["async_stale_hist"]), cum)
        assert float(info["async_fill"]) == cum.sum()


def test_staleness_weight_downweights():
    """1/(1+T)^a: exp 0 is exactly weight 1 (skipped multiply); larger
    exponents shrink stale contributions."""
    assert buffered._level_weights(base_check_config(), None) is None
    cfg = base_check_config().replace(async_staleness_exp=1.0)
    t = jnp.asarray([0, 1, 3])
    np.testing.assert_allclose(
        np.asarray(buffered._level_weights(cfg, t)),
        [1.0, 0.5, 0.25])


def test_chained_equals_per_round():
    """A chained async block (lax.scan over the carry) matches per-round
    dispatch — the buffer state threads the scan exactly like params."""
    cfg = base_check_config().replace(
        agg_mode="buffered", async_buffer_k=16, chain=4, snap=4,
        rounds=4)
    fn, params, (model, norm, arrays) = _build(cfg)
    carry = _carry(cfg, params)
    base = jax.random.PRNGKey(cfg.seed)
    per_round = carry
    infos = []
    for r in range(1, 5):
        per_round, info = fn(per_round, jax.random.fold_in(base, r))
        infos.append(info)
    chained = make_chained_round_fn(cfg, model, norm, *arrays)
    c2, stacked = chained(_carry(cfg, params), base, jnp.arange(1, 5))
    for a, b in zip(_leaves(per_round), _leaves(c2), strict=True):
        np.testing.assert_allclose(a, b, rtol=2e-5, atol=1e-6)
    np.testing.assert_array_equal(
        np.asarray(stacked["async_committed"]),
        [float(i["async_committed"]) for i in infos])
    np.testing.assert_array_equal(
        np.asarray(stacked["async_fill"]),
        [float(i["async_fill"]) for i in infos])


# ------------------------------------------------- per-staleness split ---

def test_per_bin_defense_split_vmap_full():
    """--telemetry full on the vmap path emits the per-staleness-bin
    flip-fraction/cosine split ([S+1] vectors, fractions in range; empty
    bins report cosine 0 per the telemetry NaN rule)."""
    cfg = base_check_config().replace(
        agg_mode="buffered", straggler_rate=0.5, telemetry="full",
        async_buffer_k=4, async_max_staleness=2)
    fn, params, _ = _build(cfg)
    carry = _carry(cfg, params, per_bin=True)
    base = jax.random.PRNGKey(0)
    for r in range(1, 4):
        carry, info = fn(carry, jax.random.fold_in(base, r))
    S = cfg.async_max_staleness
    flip = np.asarray(info["tel_stale_flip"])
    cos = np.asarray(info["tel_stale_cos"])
    hist = np.asarray(info["async_stale_hist"])
    assert flip.shape == cos.shape == hist.shape == (S + 1,)
    assert ((flip >= 0) & (flip <= 1)).all()
    assert ((cos >= -1.000001) & (cos <= 1.000001)).all()
    # an empty bin's cosine is exactly 0
    assert (cos[hist == 0] == 0.0).all()


# --------------------------------------------------------- refusals ---

def test_refusals_are_loud():
    ck = buffered.check
    ck(base_check_config())                        # sync: anything goes
    buf = base_check_config().replace(agg_mode="buffered")
    ck(buf)
    with pytest.raises(ValueError, match="order-statistic"):
        ck(buf.replace(aggr="comed"))
    with pytest.raises(ValueError, match="diagnostics"):
        ck(buf.replace(diagnostics=True))
    with pytest.raises(ValueError, match="async_buffer_k"):
        ck(buf.replace(async_buffer_k=-1))
    with pytest.raises(ValueError, match="async_max_staleness"):
        ck(buf.replace(async_max_staleness=0))
    with pytest.raises(ValueError, match="agg_mode"):
        buffered.is_buffered(buf.replace(agg_mode="bogus"))
    # the host-sampled step builder refuses at construction too
    with pytest.raises(ValueError, match="host-sampled"):
        make_host_step(buf, None, None)


# ------------------------------------- families / fingerprint / name ---

def test_family_suffix_and_fingerprint_split():
    cfg = Config(agg_mode="buffered")
    assert compile_cache.family_suffix(cfg) == "_async"
    assert compile_cache.family_suffix(
        cfg.replace(tenants=2)) == "_async_mt"
    assert compile_cache.family_suffix(Config()) == ""
    ex = (jnp.zeros(3),)
    assert compile_cache.fingerprint(cfg, "round_async", ex) != \
        compile_cache.fingerprint(Config(), "round_async", ex)
    # the async knobs are program provenance: each splits the key
    assert compile_cache.fingerprint(cfg, "round_async", ex) != \
        compile_cache.fingerprint(cfg.replace(async_buffer_k=4),
                                  "round_async", ex)


def test_run_name_cell():
    from defending_against_backdoors_with_robust_learning_rate_tpu.utils.metrics import (
        run_name)
    cfg = Config(agg_mode="buffered", async_buffer_k=5,
                 async_staleness_exp=0.5)
    assert "-agm:bufK5a0.5S4" in run_name(cfg)
    assert "-agm:" not in run_name(Config())
    # K=0 resolves to the cohort size in the cell (two different auto-K
    # populations must not collide)
    assert "-agm:bufK10a" in run_name(Config(agg_mode="buffered"))


def test_state_avals_match_init():
    """The planner's abstract carry must exactly match the engine's
    concrete init_state — drift here breaks every AOT hit."""
    cfg = base_check_config().replace(
        agg_mode="buffered", straggler_rate=0.3, telemetry="full")
    params = {"w": jnp.zeros((3, 2)), "b": jnp.zeros((2,))}
    for per_bin in (False, True):
        concrete = buffered.init_state(cfg, params, per_bin=per_bin)
        abstract = buffered.state_avals(cfg, params, per_bin=per_bin)
        ca = jax.tree_util.tree_map(
            lambda x: (x.shape, str(x.dtype)), concrete)
        aa = jax.tree_util.tree_map(
            lambda x: (x.shape, str(x.dtype)), abstract)
        assert ca == aa
    assert "bin_sign" in buffered.init_state(cfg, params, per_bin=True)
    assert "bin_sign" not in buffered.init_state(cfg, params)


def test_planner_emits_async_families():
    """plan_programs vocabulary: the async config plans round_async /
    chained_async with the (params, state) carry as the lead aval."""
    cfg = base_check_config().replace(agg_mode="buffered", chain=2,
                                      snap=2)
    fed = get_federated_data(cfg)
    model = get_model(cfg.data, cfg.model_arch, cfg.dtype)
    norm = make_normalizer(fed.mean, fed.std, fed.raw_is_normalized)
    specs = {s.family: s for s in compile_cache.plan_programs(
        cfg, model, norm, fed)}
    assert {"round_async", "chained_async", "eval_val",
            "eval_poison"} <= set(specs)
    lead = specs["round_async"].example_args[0]
    assert isinstance(lead, tuple) and len(lead) == 2   # (params, state)
    assert "count" in lead[1]
    # eval programs keep bare params (no buffer state)
    assert not isinstance(specs["eval_val"].example_args[0], tuple)


def test_chained_async_donates_carry():
    """Donation audit (contracts.DONATED_FAMILIES): the chained async
    scan aliases its whole carry — params AND buffer state — so no copy
    rides a dispatched block."""
    cfg = base_check_config().replace(agg_mode="buffered", chain=2,
                                      snap=2)
    fed = get_federated_data(cfg)
    model = get_model(cfg.data, cfg.model_arch, cfg.dtype)
    norm = make_normalizer(fed.mean, fed.std, fed.raw_is_normalized)
    specs = {s.family: s for s in compile_cache.plan_programs(
        cfg, model, norm, fed)}
    text = compile_cache.lower_program(
        specs["chained_async"].jit_obj,
        specs["chained_async"].example_args).as_text()
    assert "tf.aliasing_output" in text


def test_vote_range_widens_margin_bucketization():
    """The buffered electorate exceeds m between commits: vote_range is
    K + m, and a full-buffer margin histogram stays in-range (margin
    mean <= 1) instead of saturating the top bucket."""
    cfg = base_check_config().replace(agg_mode="buffered",
                                      async_buffer_k=4)
    assert buffered.vote_range(cfg) == 12            # K + m
    assert buffered.vote_range(
        cfg.replace(async_buffer_k=0)) == 16         # auto K = m
    tcfg = cfg.replace(telemetry="full", async_buffer_k=16)
    fn, params, _ = _build(tcfg)
    carry = _carry(tcfg, params, per_bin=True)
    base = jax.random.PRNGKey(0)
    for r in range(1, 3):   # two uncommitted ticks: electorate 2m > m
        carry, info = fn(carry, jax.random.fold_in(base, r))
    assert float(info["async_fill"]) == 16.0
    assert 0.0 <= float(info["tel_margin_mean"]) <= 1.0
    hist = np.asarray(info["tel_margin_hist"])
    np.testing.assert_allclose(hist.sum(), 1.0, rtol=1e-5)


def test_cohort_mirror_matches_cohort_program():
    """The host mirror's cohort key derivation (2-way round-key split)
    matches the cohort step's in-program arrival draw — the sweep's
    sim clock must charge cohort cells the latencies the program
    actually draws."""
    from defending_against_backdoors_with_robust_learning_rate_tpu.fl.rounds import (
        make_cohort_round_fn)
    cfg = base_check_config().replace(
        agg_mode="buffered", straggler_rate=0.7, async_max_staleness=2,
        async_buffer_k=10_000, cohort_sampled="on")
    fed = get_federated_data(cfg)
    model = get_model(cfg.data, cfg.model_arch, cfg.dtype)
    norm = make_normalizer(fed.mean, fed.std, fed.raw_is_normalized)
    fn = make_cohort_round_fn(cfg, model, norm)
    params = init_params(model, fed.train.images.shape[2:],
                         jax.random.PRNGKey(cfg.seed))
    carry = _carry(cfg, params)
    rows = (jnp.asarray(fed.train.images), jnp.asarray(fed.train.labels),
            jnp.asarray(fed.train.sizes))
    base = jax.random.PRNGKey(cfg.seed)
    S, n = cfg.async_max_staleness, 4
    from defending_against_backdoors_with_robust_learning_rate_tpu.data import (
        cohort as cohort_mod)
    expect = np.zeros((n + 1, S + 1))
    for t in range(1, n + 1):
        draws = buffered.host_latency_draw(cfg, t, seed=cfg.seed,
                                           cohort=True)
        # duplicate/shortfall padding slots are masked out of the fold
        # (the participation-mask protocol) — mirror the cohort's own
        # active mask too (data/cohort.sample_cohort_host)
        _ids, active = cohort_mod.sample_cohort_host(cfg, t)
        for T, a in zip(draws, np.asarray(active)):
            if a and t + T <= n:
                expect[t + T, int(T)] += 1
    cum = np.zeros(S + 1)
    for r in range(1, n + 1):
        carry, info = fn(carry, jax.random.fold_in(base, r),
                         jnp.int32(r), *rows)
        cum += expect[r]
        np.testing.assert_array_equal(
            np.asarray(info["async_stale_hist"]), cum)
