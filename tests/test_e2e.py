"""End-to-end FL integration on synthetic data (SURVEY.md section 4):
training learns, the backdoor succeeds without defense, and RLR collapses it."""

import jax
import jax.numpy as jnp
import numpy as np

from defending_against_backdoors_with_robust_learning_rate_tpu.config import Config
from defending_against_backdoors_with_robust_learning_rate_tpu.data.registry import (
    get_federated_data)
from defending_against_backdoors_with_robust_learning_rate_tpu.fl.common import (
    make_normalizer)
from defending_against_backdoors_with_robust_learning_rate_tpu.fl.evaluate import (
    make_eval_fn, pad_eval_set)
from defending_against_backdoors_with_robust_learning_rate_tpu.fl.rounds import (
    make_round_fn)
from defending_against_backdoors_with_robust_learning_rate_tpu.models.registry import (
    get_model, init_params)


def _run(cfg, rounds):
    fed = get_federated_data(cfg)
    model = get_model(cfg.data, cfg.model_arch, cfg.dtype)
    params = init_params(model, cfg.image_shape, jax.random.PRNGKey(cfg.seed))
    norm = make_normalizer(fed.mean, fed.std, fed.raw_is_normalized)
    round_fn = make_round_fn(cfg, model, norm,
                             jnp.asarray(fed.train.images),
                             jnp.asarray(fed.train.labels),
                             jnp.asarray(fed.train.sizes))
    eval_fn = make_eval_fn(model, norm)
    val = pad_eval_set(fed.val_images, fed.val_labels, cfg.eval_bs)
    pval = pad_eval_set(fed.pval_images, fed.pval_labels, cfg.eval_bs)

    key = jax.random.PRNGKey(cfg.seed)
    for _r in range(rounds):
        key, sub = jax.random.split(key)
        params, _ = round_fn(params, sub)
    _, val_acc, _ = eval_fn(params, *map(jnp.asarray, val))
    _, poison_acc, _ = eval_fn(params, *map(jnp.asarray, pval))
    return float(val_acc), float(poison_acc)


BASE = Config(data="synthetic", num_agents=4, bs=32, local_ep=1,
              synth_train_size=768, synth_val_size=256, eval_bs=256,
              client_lr=0.05, seed=3)


def test_clean_training_learns():
    val_acc, _ = _run(BASE, rounds=6)
    assert val_acc > 0.6, f"val_acc={val_acc}"


def test_backdoor_succeeds_without_defense_and_rlr_collapses_it():
    """2 of 8 corrupt, full poison: backdoor ~1.0 undefended; RLR at
    threshold 6 drives it to ~0 at a small clean-acc cost — the README's
    qualitative curve shape (reference README.md:30-34)."""
    attack = BASE.replace(num_agents=8, num_corrupt=2, poison_frac=1.0,
                          local_ep=2)
    val_a, poison_a = _run(attack, rounds=20)
    assert val_a > 0.8
    assert poison_a > 0.6, f"backdoor failed: {poison_a}"

    defended = attack.replace(robustLR_threshold=6)
    val_d, poison_d = _run(defended, rounds=20)
    assert val_d > 0.7
    assert poison_d < 0.2, (
        f"RLR did not collapse backdoor: {poison_d} vs undefended {poison_a}")


def test_host_sampled_mode_trains():
    """The host-sampled path (fedemnist: shard stacks too big for HBM; the
    driver gathers each round's sampled shards host-side) runs rounds with
    fixed [m, ...] shapes and learns."""
    from defending_against_backdoors_with_robust_learning_rate_tpu.fl.rounds import (
        make_round_fn_host)

    cfg = BASE
    fed = get_federated_data(cfg)
    model = get_model(cfg.data, cfg.model_arch, cfg.dtype)
    params = init_params(model, cfg.image_shape, jax.random.PRNGKey(cfg.seed))
    norm = make_normalizer(fed.mean, fed.std, fed.raw_is_normalized)
    host_fn = make_round_fn_host(cfg, model, norm)

    rng = np.random.default_rng(0)
    losses = []
    key = jax.random.PRNGKey(9)
    for _rnd in range(4):
        key, sub = jax.random.split(key)
        ids = rng.choice(cfg.num_agents, cfg.agents_per_round, replace=False)
        params, info = host_fn(params, sub,
                               jnp.asarray(fed.train.images[ids]),
                               jnp.asarray(fed.train.labels[ids]),
                               jnp.asarray(fed.train.sizes[ids]))
        losses.append(float(info["train_loss"]))
    assert np.isfinite(losses).all() and losses[-1] < losses[0]


def test_all_aggregators_run_a_round():
    # the sort/distance-based rules, end to end through the driver; avg and
    # sign run e2e in most other driver tests (and every rule's math is
    # parity-pinned in test_ops/test_parallel/test_faults), so this loop
    # covers only the aggregators no other e2e test dispatches
    for aggr in ("comed", "krum"):
        cfg = BASE.replace(aggr=aggr, rounds=1)
        val_acc, _ = _run(cfg, rounds=2)
        assert np.isfinite(val_acc)
