"""The folded round (ISSUE 27, ROADMAP R3): a scan over chunks of clients
that carries (sum of n_k u_k, sum of sign(u_k), sum of n_k) and never holds
the [m, n_params] stack. On the image CNN it must give the stacked round's
parameters to float32 round-off and its vote exactly; one rule picks stack
or fold from bytes the code can observe; what a fold cannot run is refused
with one sentence. CPU only: no number here is a device metric."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from defending_against_backdoors_with_robust_learning_rate_tpu.config import (
    Config, args_parser)
from defending_against_backdoors_with_robust_learning_rate_tpu.data.registry import (
    get_federated_data)
from defending_against_backdoors_with_robust_learning_rate_tpu.fl.common import (
    make_normalizer)
from defending_against_backdoors_with_robust_learning_rate_tpu.fl.rounds import (
    make_round_fn)
from defending_against_backdoors_with_robust_learning_rate_tpu.models.registry import (
    get_model, init_params)
from defending_against_backdoors_with_robust_learning_rate_tpu.ops import (
    aggregate)
from defending_against_backdoors_with_robust_learning_rate_tpu.utils import (
    compile_cache as cc)

V5E_LIMIT = int(15.75 * 2 ** 30)
RESNET9, LFM_CUT = 6_573_130, 507_820_160


@pytest.fixture(scope="module")
def env():
    cfg = Config(data="synthetic", num_agents=8, bs=16, local_ep=1,
                 synth_train_size=256, synth_val_size=32, num_corrupt=2,
                 poison_frac=1.0, robustLR_threshold=3, seed=3,
                 tensorboard=False)
    fed = get_federated_data(cfg)
    model = get_model(cfg.data, cfg.model_arch, cfg.dtype)
    params = init_params(model, cfg.image_shape, jax.random.PRNGKey(0))
    norm = make_normalizer(fed.mean, fed.std, fed.raw_is_normalized)
    arrays = tuple(map(jnp.asarray, (fed.train.images, fed.train.labels,
                                     fed.train.sizes)))
    return cfg, model, params, norm, arrays


def _round(env, **kw):
    cfg, model, params, norm, arrays = env
    fn = make_round_fn(cfg.replace(**kw), model, norm, *arrays)
    return fn(params, jax.random.PRNGKey(7))


@pytest.mark.parametrize("kw", [
    dict(agent_chunk=0),                       # one client at a time
    dict(agent_chunk=2),
    dict(agent_chunk=4, robustLR_threshold=0),  # plain FedAvg: no sign sum
    dict(agent_chunk=2, aggr="sign", server_lr=0.01),
    dict(agent_chunk=2, aggr="sign", server_lr=0.01, robustLR_threshold=0),
    dict(agent_chunk=2, attack="boost", attack_boost=4.0),
])
def test_folded_round_matches_stacked_round(env, kw):
    stack_p, stack_info = _round(env, agg_path="stack", **kw)
    fold_p, fold_info = _round(env, agg_path="fold", **kw)
    params = env[2]
    moved = 0
    for p0, a, b in zip(*(jax.tree_util.tree_leaves(t)
                          for t in (params, stack_p, fold_p)), strict=True):
        a, b, p0 = (np.asarray(x) for x in (a, b, p0))
        np.testing.assert_allclose(a, b, rtol=0, atol=2e-6)
        # the same vote: every coordinate moved the same way
        np.testing.assert_array_equal(np.sign(a - p0)[np.abs(a - p0) > 1e-5],
                                      np.sign(b - p0)[np.abs(a - p0) > 1e-5])
        moved += int(np.count_nonzero(a != p0))
    assert moved > 0
    np.testing.assert_allclose(float(stack_info["train_loss"]),
                               float(fold_info["train_loss"]), rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(stack_info["sampled"]),
                                  np.asarray(fold_info["sampled"]))
    for k in ("hlth_nonfinite", "hlth_params_finite", "hlth_update_normsq"):
        np.testing.assert_allclose(float(stack_info[k]), float(fold_info[k]),
                                   rtol=1e-5)
    np.testing.assert_array_equal(np.asarray(stack_info["hlth_agent_bad"]),
                                  np.asarray(fold_info["hlth_agent_bad"]))
    # lanes that need every update beside the vote stand down in a fold
    assert "rep_agree" not in fold_info
    votes = kw.get("robustLR_threshold", 3) > 0 or kw.get("aggr") == "sign"
    assert ("rep_agree" in stack_info) == votes


def test_fold_accumulators_against_the_stacked_rules():
    """`fold_updates` over chunks + `fold_finish` against `robust_lr` +
    `agg_avg` on the same stack; the sign sum of up to 127 clients is int8
    and the vote is equal coordinate for coordinate."""
    m = 12
    key = jax.random.PRNGKey(0)
    stack = {"a": jax.random.normal(key, (m, 33, 7)),
             "b": jnp.round(jax.random.normal(key, (m, 5)))}   # some zeros
    sizes = jax.random.randint(key, (m,), 3, 90)
    params = {"a": jnp.zeros((33, 7)), "b": jnp.zeros((5,))}
    cfg = Config(robustLR_threshold=4)
    acc = aggregate.fold_init(params, m, True, True)
    assert acc["ssum"]["a"].dtype == jnp.int8
    for i in range(0, m, 3):
        acc = aggregate.fold_updates(
            acc, jax.tree_util.tree_map(lambda u: u[i:i + 3], stack),
            sizes[i:i + 3])
    lr, agg = aggregate.fold_finish(acc, cfg, key, 4.0, 1.0)
    want_lr = aggregate.robust_lr(stack, 4.0, 1.0)
    want = aggregate.agg_avg(stack, sizes)
    for k in params:
        np.testing.assert_array_equal(np.asarray(lr[k]),
                                      np.asarray(want_lr[k]))
        np.testing.assert_allclose(np.asarray(agg[k]), np.asarray(want[k]),
                                   rtol=1e-5, atol=1e-7)
    big = aggregate.fold_init(params, 128, False, True)
    assert big["wsum"] is None and big["ssum"]["a"].dtype == jnp.float32


@pytest.mark.parametrize("stack,free,want", [
    (4 * RESNET9 * 40, V5E_LIMIT, "stack"),        # 1.05 GB of 16.9
    (4 * LFM_CUT * 10, V5E_LIMIT, "fold"),         # 20.3 GB
    (100, 200, "stack"),                           # exactly half: fits
    (100, 199, "fold"),
    (100, 0, "fold"),
    (100, -5, "fold"),
    (4 * LFM_CUT * 10, None, "stack"),             # no limit reported
])
def test_rule_on_numbers_alone(stack, free, want):
    assert cc.agg_path_for(stack, free) == want


@pytest.fixture
def limit(monkeypatch):
    def set_limit(n):
        monkeypatch.setattr(cc, "device_memory_limit", lambda: n)
    return set_limit


def test_the_benchmarks_resnet9_cells_keep_the_stack(limit):
    """`cifar-resnet9`'s flags at the v5e's limit: the 1.05 GB stack stays,
    on one chip and under --mesh=4 (a sharded round never folds)."""
    limit(V5E_LIMIT)
    cell = Config(data="cifar10", arch="resnet9", num_agents=40, bs=256,
                  num_corrupt=4, robustLR_threshold=8, remat=True,
                  agent_chunk=10, tensorboard=False)
    got = cc.resolved_agg(cell, RESNET9)
    assert (got.path, got.chosen, got.stack_bytes) == (
        "stack", True, 4 * RESNET9 * 40)
    assert got.limit_bytes == V5E_LIMIT - 4 * RESNET9 * 31
    assert cc.resolved_agg(cell.replace(mesh=4), RESNET9).path == "stack"
    # and a stack that does not fit folds, where the round can
    assert cc.resolved_agg(cell, LFM_CUT).path == "fold"
    assert cc.resolved_agg(cell.replace(aggr="comed"), LFM_CUT).path == \
        "stack"


def test_no_limit_keeps_the_stack_and_an_asked_path_is_honoured(limit):
    limit(None)
    cfg = Config(num_agents=10)
    assert cc.resolved_agg(cfg, LFM_CUT).path == "stack"
    got = cc.resolved_agg(cfg.replace(agg_path="fold"), 1000)
    assert (got.path, got.chosen) == ("fold", False)
    assert "as asked" in got.describe()
    with pytest.raises(ValueError, match="agg_path"):
        cc.resolved_agg(cfg.replace(agg_path="ring"), 1000)


def test_agg_path_has_no_flag():
    with pytest.raises(SystemExit):
        args_parser(["--agg_path=fold"])


TOKENS = dict(data="tokens", arch="lfm2_moe", agent_chunk=1)


@pytest.mark.parametrize("kw,word", [
    (dict(TOKENS, mesh=4), "--mesh"),
    (dict(TOKENS, chain=4), "--chain"),
    (dict(TOKENS, host_sampled="on"), "host-sampled"),
    (dict(TOKENS, cohort_sampled="on"), "cohort"),
    (dict(TOKENS, tenants=2), "--tenants"),
    (dict(TOKENS, agg_mode="buffered"), "buffer"),
    (dict(TOKENS, diagnostics=True), "--diagnostics"),
    (dict(TOKENS, arch="resnet9"), "--arch=lfm2_moe"),
    (dict(data="cifar10", arch="lfm2_moe"), "--data=tokens"),
    (dict(agg_path="fold", aggr="comed"), "--aggr=comed"),
    (dict(agg_path="fold", aggr="krum"), "--aggr=krum"),
    (dict(agg_path="fold", telemetry="full"), "--telemetry"),
    (dict(agg_path="fold", reputation="on", robustLR_threshold=2),
     "--reputation on"),
    (dict(agg_path="fold", dropout_rate=0.2), "participation mask"),
    (dict(agg_path="fold", mesh=4), "--mesh"),
    (dict(agg_path="fold", chain=2), "--chain"),
])
def test_unsupported_is_refused_with_one_sentence(kw, word):
    cfg = Config(tensorboard=False, compile_cache=False, **kw)
    said = cc.unsupported(cfg, cfg.agg_path == "fold")
    assert said and word in said[0] and said[0].endswith(".")
    from defending_against_backdoors_with_robust_learning_rate_tpu import (
        train)
    with pytest.raises(ValueError) as err:
        train.RoundEngine(cfg)
    assert str(err.value) == said[0]


def test_what_both_rounds_run_is_not_refused():
    assert cc.unsupported(Config(), False) == []
    assert cc.unsupported(Config(agg_path="fold", agent_chunk=2), True) == []
    assert cc.unsupported(Config(**TOKENS), False) == []
    assert cc.unsupported(Config(**TOKENS), True) == []


def test_engine_keeps_two_units_in_flight_where_parameters_are_large(
        monkeypatch, tmp_path):
    """A dispatch allocates its new parameters at enqueue: where few
    copies fit their share of the device the engine waits for the unit
    before last. (The CPU reports no limit: the rule is told one here.)"""
    from defending_against_backdoors_with_robust_learning_rate_tpu import (
        train)
    # the rule on numbers: the token cell's 2.03 GB on a v5e, ResNet-9's
    # 26 MB, a backend without a limit
    assert train.units_ahead(2_031_280_640, 16_909_336_064) == 2
    assert train.units_ahead(26_292_520, 16_909_336_064) is None
    assert train.units_ahead(2_031_280_640, None) is None
    assert train.units_ahead(500_000_000, 16_909_336_064) == 4
    cfg = args_parser([
        "--platform=cpu", "--data=synthetic", "--num_agents=4", "--bs=16",
        "--local_ep=1", "--rounds=4", "--synth_train_size=128",
        "--synth_val_size=32", "--eval_bs=32", "--snap=4",
        "--no_tensorboard", f"--log_dir={tmp_path}",
        "--data_dir=/nonexistent_use_synthetic"])
    plain = train.RoundEngine(cfg)
    plain.close()
    assert plain._units_ahead is None and not plain._in_flight
    monkeypatch.setattr(train, "units_ahead", lambda nbytes, limit: 2)
    eng = train.RoundEngine(cfg)
    try:
        for unit in eng.schedule():
            eng.dispatch(unit)
            assert len(eng._in_flight) <= 2
            eng.post_unit()
    finally:
        eng.close()
    waits = [s for s in eng.tracer.records() if s.name == "round/wait_room"]
    assert len(waits) == 2                  # before the third and fourth
