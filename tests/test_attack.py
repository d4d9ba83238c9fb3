"""Attack-registry subsystem tests (ISSUE 11, attack/).

Covers: registry resolution + validation, static's bitwise parity with
the legacy poison path, DBA trigger splitting, per-strategy
purity/determinism, schedule on/off round boundaries (host == traced),
the sign-flip strategy actually flipping the RLR vote on a toy
electorate, the boost-defeats-FedAvg / RLR-holds acceptance pair on a
quick CPU config, the host-mode refusals, run_name attack cells, the
scenario-matrix cell builder, and the online threshold-adaptation
policy/controller (attack/adapt.py)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from defending_against_backdoors_with_robust_learning_rate_tpu.attack import (
    adapt, dba, registry, schedule)
from defending_against_backdoors_with_robust_learning_rate_tpu.attack.patterns import (
    build_stamp)
from defending_against_backdoors_with_robust_learning_rate_tpu.attack.poison import (
    poison_agent_shards)
from defending_against_backdoors_with_robust_learning_rate_tpu.config import (
    Config)
from defending_against_backdoors_with_robust_learning_rate_tpu.ops.aggregate import (
    robust_lr)
from defending_against_backdoors_with_robust_learning_rate_tpu.utils.metrics import (
    run_name)


def tiny_cfg(**kw):
    base = dict(data="synthetic", num_agents=8, bs=16, local_ep=1,
                synth_train_size=256, synth_val_size=64, eval_bs=64,
                rounds=4, snap=2, num_corrupt=2, poison_frac=1.0,
                robustLR_threshold=3, seed=5, tensorboard=False,
                compile_cache=False,
                data_dir="/nonexistent_use_synthetic")
    base.update(kw)
    return Config(**base)


# ------------------------------------------------------------ registry ---

def test_registry_resolution_and_validation():
    cfg = tiny_cfg()
    assert registry.get(cfg).name == "static"
    registry.check(cfg)                       # default is valid
    assert not registry.in_jit(cfg)
    assert not registry.needs_round(cfg)

    with pytest.raises(ValueError, match="--attack must be one of"):
        registry.get(cfg.replace(attack="nope"))
    with pytest.raises(ValueError, match="attack_boost"):
        registry.check(cfg.replace(attack="boost", attack_boost=0.0))
    with pytest.raises(ValueError, match="attack_every"):
        registry.check(cfg.replace(attack="boost", attack_every=0))
    with pytest.raises(ValueError, match="attack_stop"):
        registry.check(cfg.replace(attack="boost", attack_start=5,
                                   attack_stop=5))
    # schedules only compose with the in-jit strategies
    for name in ("static", "dba"):
        with pytest.raises(ValueError, match="construction time"):
            registry.check(cfg.replace(attack=name, attack_start=2))
    # valid in-jit combos
    registry.check(cfg.replace(attack="signflip", attack_start=2,
                               attack_stop=6, attack_every=2))
    assert registry.in_jit(cfg.replace(attack="boost"))
    assert not registry.needs_round(cfg.replace(attack="boost"))
    assert registry.needs_round(cfg.replace(attack="boost",
                                            attack_start=1))


def test_static_update_hook_is_identity():
    cfg = tiny_cfg()   # attack=static
    ups = {"w": jnp.arange(12.0).reshape(4, 3)}
    assert registry.apply_update_attack(cfg, ups, None) is ups


def test_in_jit_attack_requires_flags():
    cfg = tiny_cfg(attack="boost")
    with pytest.raises(ValueError, match="corrupt-slot flags"):
        registry.apply_update_attack(cfg, {"w": jnp.ones((4, 3))}, None)


# ----------------------------------------------------- static parity ----

def test_static_poison_bitwise_legacy():
    """--attack static must stamp BITWISE what the pre-registry path
    stamped: poison_client_row's registry-routed stamp equals the legacy
    per-agent build_stamp on identical arrays."""
    cfg = tiny_cfg(data="fmnist", num_corrupt=2, poison_frac=0.5)
    rng = np.random.default_rng(0)
    imgs = rng.integers(0, 256, (4, 32, 28, 28, 1)).astype(np.uint8)
    lbls = rng.integers(0, 10, (4, 32)).astype(np.int32)
    sizes = np.full((4,), 32, np.int64)

    # registry-routed (stamp=None -> registry.stamp_for_agent)
    ia, la, ma = poison_agent_shards(imgs, lbls, sizes, cfg)
    # legacy stamps, forced explicitly
    from defending_against_backdoors_with_robust_learning_rate_tpu.attack.poison import (
        poison_client_row)
    ib, lb = imgs.copy(), lbls.copy()
    for aid in range(cfg.num_corrupt):
        legacy = build_stamp(cfg.data, cfg.pattern_type, agent_idx=aid,
                             data_dir=cfg.data_dir)
        poison_client_row(ib[aid], lb[aid], int(sizes[aid]), aid, cfg,
                          stamp=legacy)
    np.testing.assert_array_equal(ia, ib)
    np.testing.assert_array_equal(la, lb)
    assert ma[: cfg.num_corrupt].any()


def test_dba_split_partitions_full_pattern():
    for data, pat in (("fmnist", "plus"), ("fmnist", "square"),
                      ("cifar10", "plus"), ("synthetic", "plus")):
        full = build_stamp(data, pat, agent_idx=-1, data_dir="/none")
        cfg = tiny_cfg(data=data, pattern_type=pat, attack="dba",
                       num_corrupt=3)
        union = np.zeros_like(full.mask)
        total = 0
        for aid in range(3):
            st = registry.stamp_for_agent(cfg, aid)
            assert not (union & st.mask).any(), "shards overlap"
            union |= st.mask
            total += int(st.mask.sum())
        assert (union == full.mask).all() and total == full.mask.sum()


def test_dba_poisons_with_shard_and_flips_labels():
    cfg = tiny_cfg(data="fmnist", attack="dba", num_corrupt=2,
                   poison_frac=1.0, base_class=5, target_class=7)
    rng = np.random.default_rng(1)
    imgs = rng.integers(0, 256, (2, 16, 28, 28, 1)).astype(np.uint8)
    lbls = np.full((2, 16), 5, np.int32)
    sizes = np.full((2,), 16, np.int64)
    ia, la, ma = poison_agent_shards(imgs, lbls, sizes, cfg)
    assert ma.all(axis=1).all()                      # frac 1.0, all base
    assert (la == 7).all()                           # labels flipped
    # the two agents stamped DIFFERENT pixel sets (their shards)
    d0 = (ia[0] != imgs[0]).any(axis=(0, 3))
    d1 = (ia[1] != imgs[1]).any(axis=(0, 3))
    assert d0.any() and d1.any() and not (d0 & d1).any()


# ------------------------------------------- purity / determinism -------

def test_update_scale_pure_in_flags_round_seed():
    """The in-jit transform is a pure function of (corrupt flags,
    schedule round): repeated evaluation, jit, and different training
    seeds cannot change it."""
    cfg = tiny_cfg(attack="signflip", attack_boost=2.0, attack_start=2,
                   attack_every=2)
    flags = jnp.array([True, False, True, False])
    for rnd in (1, 2, 3, 4):
        act = schedule.active(cfg, rnd)
        a = registry.update_scale(cfg, flags, act)
        b = registry.update_scale(cfg, flags, schedule.active(cfg, rnd))
        c = jax.jit(lambda f, r: registry.update_scale(
            cfg, f, schedule.active(cfg, r)))(flags, jnp.int32(rnd))
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        np.testing.assert_array_equal(np.asarray(a), np.asarray(c))
    # seed never enters: the scale has no key argument at all — and two
    # configs differing only in seed build identical scales
    s1 = registry.update_scale(cfg.replace(seed=0), flags, None)
    s2 = registry.update_scale(cfg.replace(seed=99), flags, None)
    np.testing.assert_array_equal(np.asarray(s1), np.asarray(s2))


def test_schedule_round_boundaries():
    cfg = tiny_cfg(attack="boost", attack_start=3, attack_stop=6)
    on = [bool(schedule.active(cfg, r)) for r in range(1, 8)]
    assert on == [False, False, True, True, True, False, False]
    # one-shot
    one = tiny_cfg(attack="boost", attack_start=4, attack_stop=5)
    assert [bool(schedule.active(one, r)) for r in range(1, 7)] \
        == [False, False, False, True, False, False]
    # intermittent, phase-locked to attack_start
    inter = tiny_cfg(attack="boost", attack_start=2, attack_every=3)
    assert [bool(schedule.active(inter, r)) for r in range(1, 9)] \
        == [False, True, False, False, True, False, False, True]
    # traced == host (the churn purity property, same idiom)
    jit_active = jax.jit(lambda r: schedule.active(cfg, r))
    for r in range(1, 8):
        assert bool(jit_active(jnp.int32(r))) == on[r - 1]


# --------------------------------------------------- toy electorate -----

def test_signflip_flips_rlr_vote_on_toy_electorate():
    """8 voters, 3 corrupt, threshold 4: unanimous honest agreement
    (margin 8) survives; after the sign-flip the margin drops to
    8 - 2*3 = 2 < 4 and the RLR learning rate flips to -slr on every
    coordinate."""
    m, thr, slr = 8, 4.0, 1.0
    honest = {"w": jnp.ones((m, 5))}
    flags = jnp.arange(m) < 3
    lr_clean = robust_lr(honest, thr, slr)
    assert (np.asarray(lr_clean["w"]) == slr).all()
    cfg = tiny_cfg(attack="signflip", num_corrupt=3)
    attacked = registry.apply_update_attack(cfg, honest, flags)
    lr_att = robust_lr(attacked, thr, slr)
    assert (np.asarray(lr_att["w"]) == -slr).all()
    # and with only 1 corrupt voter the margin (6) still clears thr=4
    one = registry.apply_update_attack(
        cfg.replace(num_corrupt=1), honest, jnp.arange(m) < 1)
    assert (np.asarray(robust_lr(one, thr, slr)["w"]) == slr).all()


# ------------------------------------------------------ quick e2e -------

@pytest.mark.slow  # ~35s, the heaviest tier-1 test (ISSUE 12 budget
# rule: slow-gate BEFORE growing the suite). Cheap twins in tier-1: the
# toy-electorate vote tests above pin the boost/signflip mechanics
# per-round, and the CI scenario-smoke job asserts the exact
# boost-defeats-avg / RLR-holds separation end-to-end on every push.
def test_boost_defeats_avg_but_rlr_holds():
    """The acceptance pair on a quick CPU config: model-replacement
    boosting drives poison accuracy to ~1 through plain FedAvg, while
    the RLR defense at the paper-shape threshold holds it down (the
    vote is on signs, which boosting cannot buy)."""
    from defending_against_backdoors_with_robust_learning_rate_tpu.train import (
        run)
    base = tiny_cfg(local_ep=2, synth_train_size=512, synth_val_size=128,
                    eval_bs=128, rounds=10, snap=5, seed=1,
                    attack="boost", attack_boost=8.0)
    import tempfile
    with tempfile.TemporaryDirectory() as td:
        undefended = run(base.replace(robustLR_threshold=0, log_dir=td))
        defended = run(base.replace(robustLR_threshold=4, log_dir=td))
    assert undefended["poison_acc"] >= 0.8, undefended
    assert defended["poison_acc"] <= 0.1, defended


# ------------------------------------------------------- refusals -------

def test_host_mode_scheduled_attack_refused():
    from defending_against_backdoors_with_robust_learning_rate_tpu.fl.rounds import (
        make_host_step)
    cfg = tiny_cfg(attack="boost", attack_start=2)
    with pytest.raises(ValueError, match="host-sampled"):
        make_host_step(cfg, model=None, normalize=None)


def test_chained_host_in_jit_attack_refused():
    from defending_against_backdoors_with_robust_learning_rate_tpu.fl.rounds import (
        make_host_step)
    cfg = tiny_cfg(attack="boost")
    with pytest.raises(ValueError, match="flag"):
        make_host_step(cfg, model=None, normalize=None, take_flags=False)


def test_chain_budget_host_attack_disables_chaining():
    from defending_against_backdoors_with_robust_learning_rate_tpu.utils import (
        compile_cache)
    cfg = tiny_cfg(attack="boost", chain=4, snap=4)
    assert compile_cache.chain_budget(cfg, host_mode=True) == 1
    # cohort mode keeps its chain (flags re-derive in-program)
    assert compile_cache.chain_budget(cfg, host_mode=True, cohort=True) == 4
    # device-resident keeps its chain
    assert compile_cache.chain_budget(cfg) == 4
    # static host mode unaffected
    assert compile_cache.chain_budget(tiny_cfg(chain=4, snap=4),
                                      host_mode=True) == 4


def test_step_takes_round_single_source():
    from defending_against_backdoors_with_robust_learning_rate_tpu.fl.rounds import (
        step_takes_round)
    assert not step_takes_round(tiny_cfg())
    assert not step_takes_round(tiny_cfg(attack="boost"))
    assert step_takes_round(tiny_cfg(attack="boost", attack_start=2))
    assert step_takes_round(tiny_cfg(churn_available=0.5))


# -------------------------------------------------------- run_name ------

def test_run_name_attack_cells():
    base = tiny_cfg()
    assert "-atk:" not in run_name(base)            # static: legacy name
    b = run_name(base.replace(attack="boost", attack_boost=8.0))
    assert "-atk:boostb8.0p1.0" in b
    sched = run_name(base.replace(attack="signflip", attack_start=2,
                                  attack_stop=6, attack_every=2))
    assert "-atk:signflipb1.0p1.0s2e2t6" in sched
    # cells never collide across strategy/boost/poison-intensity/schedule
    names = {run_name(base.replace(attack="boost", attack_boost=x))
             for x in (2.0, 8.0)}
    names.add(run_name(base.replace(attack="signflip")))
    names.add(run_name(base.replace(attack="signflip", poison_frac=0.0)))
    names.add(run_name(base.replace(attack="dba")))
    assert len(names) == 5


# ----------------------------------------------- scenario matrix --------

def test_scenario_matrix_cell_builder():
    import importlib.util
    import os
    spec = importlib.util.spec_from_file_location(
        "sweep_scenarios",
        os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "scripts", "sweep_scenarios.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    cells = mod.build_cells(["static", "boost", "signflip"],
                            ["avg", "rlr"], ["none", "drop30"],
                            ["sync", "buf_k2"],
                            boost=8.0, rounds=20, thr=4, m=10)
    assert len(cells) == 24
    names = {c["name"] for c in cells}
    assert len(names) == 24
    rlr_cell = next(c for c in cells
                    if c["name"] == "boost|rlr|drop30|sync")
    assert rlr_cell["overrides"]["robustLR_threshold"] == 4
    assert rlr_cell["overrides"]["attack_boost"] == 8.0
    assert rlr_cell["overrides"]["dropout_rate"] == 0.3
    assert "agg_mode" not in rlr_cell["overrides"]
    buf_cell = next(c for c in cells
                    if c["name"] == "boost|rlr|drop30|buf_k2")
    assert buf_cell["overrides"]["agg_mode"] == "buffered"
    assert buf_cell["overrides"]["async_buffer_k"] == 5   # m // 2
    # every cell's overrides are real Config fields (the queue validates
    # too; catching vocabulary drift here is cheaper)
    import dataclasses
    fields = {f.name for f in dataclasses.fields(Config)}
    for c in cells:
        assert set(c["overrides"]) <= fields, c
    with pytest.raises(SystemExit, match="unknown attack"):
        mod.build_cells(["bogus"], ["avg"], ["none"], ["sync"],
                        8.0, 20, 4, 10)
    with pytest.raises(SystemExit, match="unknown agg regime"):
        mod.build_cells(["static"], ["avg"], ["none"], ["bogus"],
                        8.0, 20, 4, 10)


# ------------------------------------------- threshold adaptation -------

def test_adapt_policy_directions():
    split_hist = [0.5, 0.2, 0.1, 0.05, 0.05, 0.05, 0.03, 0.02]
    calm_hist = [0.01] * 4 + [0.1, 0.1, 0.2, 0.56]
    # electorate splitting + defense not biting -> raise
    assert adapt.recommend_threshold(4, 8, 0.02, split_hist) == 5
    # over-defense -> lower, regardless of the histogram
    assert adapt.recommend_threshold(4, 8, 0.6, split_hist) == 3
    assert adapt.recommend_threshold(4, 8, 0.6, calm_hist) == 3
    # calm electorate, moderate flips -> hold
    assert adapt.recommend_threshold(4, 8, 0.1, calm_hist) == 4
    # corrupt anti-alignment signature raises even with a calm histogram
    assert adapt.recommend_threshold(4, 8, 0.02, calm_hist,
                                     cos_honest=0.5,
                                     cos_corrupt=-0.5) == 5
    # clamped to [1, m-1]
    assert adapt.recommend_threshold(1, 8, 0.9, calm_hist) == 1
    assert adapt.recommend_threshold(7, 8, 0.0, split_hist) == 7


def test_adapt_controller_validation_and_cadence():
    good = tiny_cfg(robustLR_threshold=4, telemetry="full",
                    checkpoint_dir="/tmp/ck", rlr_adapt_every=2)
    with pytest.raises(ValueError, match="robustLR_threshold"):
        adapt.ThresholdController(good.replace(robustLR_threshold=0))
    with pytest.raises(ValueError, match="telemetry full"):
        adapt.ThresholdController(good.replace(telemetry="basic"))
    with pytest.raises(ValueError, match="checkpoint_dir"):
        adapt.ThresholdController(good.replace(checkpoint_dir=""))

    ctl = adapt.ThresholdController(good)
    split = {"tel_flip_frac": 0.0,
             "tel_margin_hist": [0.6, 0.2, 0.1, 0.1, 0, 0, 0, 0]}
    assert ctl.consider(None, 2) is None            # no telemetry yet
    assert ctl.consider(split, 2) is None           # cadence: 1st of 2
    assert ctl.consider(split, 4) == 5              # 2nd boundary: move
    assert ctl.thr == 5 and ctl.moves == [(4, 4, 5)]
    assert ctl.consider(split, 6) is None           # cadence resets
    assert ctl.consider(split, 8) == 6
