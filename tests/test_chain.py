"""Chained-round execution (lax.scan over rounds) must be bit-compatible
with per-round dispatch: round r's key is fold_in(base_key, r) in both paths
(fl/rounds.make_chained_round_fn, parallel/rounds.make_sharded_chained_round_fn)."""

import jax
import jax.numpy as jnp
import numpy as np

from defending_against_backdoors_with_robust_learning_rate_tpu.config import Config
from defending_against_backdoors_with_robust_learning_rate_tpu.data.registry import (
    get_federated_data)
from defending_against_backdoors_with_robust_learning_rate_tpu.fl.common import (
    make_normalizer)
from defending_against_backdoors_with_robust_learning_rate_tpu.fl.rounds import (
    make_chained_round_fn, make_round_fn)
from defending_against_backdoors_with_robust_learning_rate_tpu.models.registry import (
    get_model, init_params)
from defending_against_backdoors_with_robust_learning_rate_tpu.parallel.mesh import (
    make_mesh)
from defending_against_backdoors_with_robust_learning_rate_tpu.parallel.rounds import (
    make_sharded_chained_round_fn, make_sharded_round_fn)


def _setup(num_agents=4):
    cfg = Config(data="synthetic", num_agents=num_agents, bs=16, local_ep=1,
                 synth_train_size=128, synth_val_size=32, num_corrupt=1,
                 poison_frac=1.0, robustLR_threshold=2, seed=3)
    fed = get_federated_data(cfg)
    model = get_model(cfg.data, cfg.model_arch, cfg.dtype)
    params = init_params(model, cfg.image_shape, jax.random.PRNGKey(0))
    norm = make_normalizer(fed.mean, fed.std, fed.raw_is_normalized)
    arrays = (jnp.asarray(fed.train.images), jnp.asarray(fed.train.labels),
              jnp.asarray(fed.train.sizes))
    return cfg, model, params, norm, arrays


def _assert_trees_close(a, b, **kw):
    for x, y in zip(jax.tree_util.tree_leaves(a),
                    jax.tree_util.tree_leaves(b), strict=True):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y), **kw)


def test_chained_matches_per_round_dispatch():
    cfg, model, params, norm, arrays = _setup()
    base_key = jax.random.PRNGKey(7)
    n = 4

    round_fn = make_round_fn(cfg, model, norm, *arrays)
    p_seq = params
    losses_seq = []
    for r in range(1, n + 1):
        p_seq, info = round_fn(p_seq, jax.random.fold_in(base_key, r))
        losses_seq.append(float(info["train_loss"]))

    chained = make_chained_round_fn(cfg, model, norm, *arrays)
    p_chain, stacked = chained(params, base_key, jnp.arange(1, n + 1))

    _assert_trees_close(p_seq, p_chain, atol=1e-6, rtol=1e-6)
    np.testing.assert_allclose(np.asarray(stacked["train_loss"]),
                               np.array(losses_seq), rtol=1e-5)
    assert stacked["sampled"].shape == (n, cfg.agents_per_round)


def test_chained_matches_per_round_with_clip_and_noise():
    """The r4 clip+noise sweep row runs chained: per-batch PGD projection
    and the server's Gaussian noise (k_noise split from the round key) must
    derive identically inside the scan and in per-round dispatch."""
    cfg, model, params, norm, arrays = _setup()
    cfg = cfg.replace(clip=1.0, noise=0.01)
    base_key = jax.random.PRNGKey(11)
    n = 3

    round_fn = make_round_fn(cfg, model, norm, *arrays)
    p_seq = params
    for r in range(1, n + 1):
        p_seq, _ = round_fn(p_seq, jax.random.fold_in(base_key, r))

    chained = make_chained_round_fn(cfg, model, norm, *arrays)
    p_chain, _ = chained(params, base_key, jnp.arange(1, n + 1))

    _assert_trees_close(p_seq, p_chain, atol=1e-6, rtol=1e-6)


def test_sharded_chained_matches_sharded_per_round():
    cfg, model, params, norm, arrays = _setup(num_agents=8)
    mesh = make_mesh(4)
    base_key = jax.random.PRNGKey(5)
    n = 3

    round_fn = make_sharded_round_fn(cfg, model, norm, mesh, *arrays)
    p_seq = params
    for r in range(1, n + 1):
        p_seq, _ = round_fn(p_seq, jax.random.fold_in(base_key, r))

    chained = make_sharded_chained_round_fn(cfg, model, norm, mesh, *arrays)
    p_chain, stacked = chained(params, base_key, jnp.arange(1, n + 1))

    _assert_trees_close(p_seq, p_chain, atol=1e-5, rtol=1e-5)
    assert stacked["train_loss"].shape == (n,)


def test_host_chained_matches_per_round_host():
    """Host-sampled chained blocks (fl/rounds.make_chained_round_fn_host)
    must match per-round host dispatch on the same shard payloads + keys."""
    from defending_against_backdoors_with_robust_learning_rate_tpu.fl.rounds import (
        make_chained_round_fn_host, make_round_fn_host)

    cfg, model, params, norm, arrays = _setup()
    images, labels, sizes = map(np.asarray, arrays)
    m = cfg.agents_per_round
    base_key = jax.random.PRNGKey(11)
    n = 3
    rng = np.random.default_rng(0)
    ids = np.stack([rng.choice(cfg.num_agents, m, replace=False)
                    for _ in range(n)])                 # [n, m]

    round_fn = make_round_fn_host(cfg, model, norm)
    p_seq = params
    losses = []
    for i, r in enumerate(range(1, n + 1)):
        p_seq, info = round_fn(p_seq, jax.random.fold_in(base_key, r),
                               jnp.asarray(images[ids[i]]),
                               jnp.asarray(labels[ids[i]]),
                               jnp.asarray(sizes[ids[i]]))
        losses.append(float(info["train_loss"]))

    chained = make_chained_round_fn_host(cfg, model, norm)
    p_chain, stacked = chained(params, base_key, jnp.arange(1, n + 1),
                               jnp.asarray(images[ids]),
                               jnp.asarray(labels[ids]),
                               jnp.asarray(sizes[ids]))

    _assert_trees_close(p_seq, p_chain, atol=1e-6, rtol=1e-6)
    np.testing.assert_allclose(np.asarray(stacked["train_loss"]),
                               np.array(losses), rtol=1e-5)


def test_sharded_host_chained_matches_per_round():
    """Sharded host-chained blocks: [chain, m, ...] stacks sharded on the m
    axis (P(None, agents)), scan slices a round per step, collectives inside
    the scan (parallel/rounds.make_sharded_chained_round_fn_host)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from defending_against_backdoors_with_robust_learning_rate_tpu.parallel.mesh import (
        AGENTS_AXIS)
    from defending_against_backdoors_with_robust_learning_rate_tpu.parallel.rounds import (
        make_sharded_chained_round_fn_host, make_sharded_round_fn_host)

    cfg, model, params, norm, arrays = _setup(num_agents=8)
    images, labels, sizes = map(np.asarray, arrays)
    mesh = make_mesh(4)
    m = cfg.agents_per_round
    agents_sh = NamedSharding(mesh, P(AGENTS_AXIS))
    block_sh = NamedSharding(mesh, P(None, AGENTS_AXIS))
    base_key = jax.random.PRNGKey(13)
    n = 2
    rng = np.random.default_rng(1)
    ids = np.stack([rng.choice(cfg.num_agents, m, replace=False)
                    for _ in range(n)])

    round_fn = make_sharded_round_fn_host(cfg, model, norm, mesh)
    p_seq = params
    for i, r in enumerate(range(1, n + 1)):
        p_seq, _ = round_fn(p_seq, jax.random.fold_in(base_key, r),
                            jax.device_put(images[ids[i]], agents_sh),
                            jax.device_put(labels[ids[i]], agents_sh),
                            jax.device_put(sizes[ids[i]], agents_sh))

    chained = make_sharded_chained_round_fn_host(cfg, model, norm, mesh)
    p_chain, stacked = chained(params, base_key, jnp.arange(1, n + 1),
                               jax.device_put(images[ids], block_sh),
                               jax.device_put(labels[ids], block_sh),
                               jax.device_put(sizes[ids], block_sh))

    _assert_trees_close(p_seq, p_chain, atol=1e-5, rtol=1e-5)
    assert stacked["train_loss"].shape == (n,)


def test_dispatch_schedule_covers_rounds_in_order():
    """The precomputed prefetch schedule must make exactly the driver loop's
    decisions: all rounds once, in order; chained blocks never cross an eval
    boundary; a diagnostics run keeps its snap rounds unchained."""
    from defending_against_backdoors_with_robust_learning_rate_tpu.train import (
        dispatch_schedule)

    for start, total, snap, chain_n, diag in [
            (0, 20, 5, 3, False), (0, 20, 5, 3, True), (7, 23, 5, 4, False),
            (3, 7, 5, 3, True), (0, 10, 10, 10, False), (0, 9, 4, 2, True)]:
        units = dispatch_schedule(start, total, snap, chain_n, diag, True)
        flat = [r for u in units for r in u]
        assert flat == list(range(start + 1, total + 1)), (start, total)
        for u in units:
            assert len(u) in (1, chain_n)
            if len(u) > 1:
                # no eval boundary strictly inside the block
                assert all(r % snap != 0 for r in u[:-1])
                # diagnostics snap rounds stay unchained
                if diag:
                    assert u[-1] % snap != 0
        # unchained mode degenerates to singletons
        assert all(len(u) == 1 for u in dispatch_schedule(
            start, total, snap, chain_n, diag, False))


def test_run_host_chain_matches_unchained(tmp_path):
    """Driver-level: host-sampled mode with --chain must produce the same
    curve as unchained host-sampled mode (same sampling sequence, same keys),
    through the unit-based prefetcher."""
    from defending_against_backdoors_with_robust_learning_rate_tpu.train import run

    base = Config(data="synthetic", num_agents=4, bs=16, local_ep=1,
                  synth_train_size=128, synth_val_size=32, rounds=4, snap=2,
                  seed=9, log_dir=str(tmp_path), tensorboard=False,
                  host_sampled="on")
    s1 = run(base)
    s2 = run(base.replace(chain=2))
    np.testing.assert_allclose(s1["val_acc"], s2["val_acc"], rtol=1e-5)
    np.testing.assert_allclose(s1["val_loss"], s2["val_loss"], rtol=1e-4)
    # and the no-prefetch path takes the same schedule
    s3 = run(base.replace(chain=2, host_prefetch=0))
    np.testing.assert_allclose(s1["val_loss"], s3["val_loss"], rtol=1e-4)


def test_run_with_chain_matches_unchained(tmp_path):
    from defending_against_backdoors_with_robust_learning_rate_tpu.train import run

    base = Config(data="synthetic", num_agents=4, bs=16, local_ep=1,
                  synth_train_size=128, synth_val_size=32, rounds=4, snap=2,
                  seed=9, log_dir=str(tmp_path), tensorboard=False)
    s1 = run(base)
    s2 = run(base.replace(chain=2))
    np.testing.assert_allclose(s1["val_acc"], s2["val_acc"], rtol=1e-5)
    np.testing.assert_allclose(s1["val_loss"], s2["val_loss"], rtol=1e-4)


def test_dataset_stacks_are_arguments_not_hlo_constants():
    """The K-agent dataset stacks must be jit ARGUMENTS: a closed-over array
    is inlined into the lowered program as a dense constant — ~0.5 GiB of
    HLO for the fedemnist stacks, which every compile re-ships and the
    persistent cache re-hashes."""
    import jax

    from defending_against_backdoors_with_robust_learning_rate_tpu.data.registry import (
        get_federated_data)
    from defending_against_backdoors_with_robust_learning_rate_tpu.fl.common import (
        make_normalizer)
    from defending_against_backdoors_with_robust_learning_rate_tpu.fl.rounds import (
        make_chained_round_fn)
    from defending_against_backdoors_with_robust_learning_rate_tpu.models.registry import (
        get_model, init_params)

    # ~6.4 MB of image stacks (fmnist geometry, synthetic fallback): far
    # larger than any legitimate constant
    cfg = Config(data="fmnist", num_agents=8, bs=16, local_ep=1,
                 synth_train_size=8192, synth_val_size=32, chain=2, seed=0,
                 data_dir="/nonexistent_use_synthetic")
    fed = get_federated_data(cfg)
    model = get_model(cfg.data, cfg.model_arch, cfg.dtype)
    params = init_params(model, cfg.image_shape, jax.random.PRNGKey(0))
    norm = make_normalizer(fed.mean, fed.std, fed.raw_is_normalized)
    arrays = tuple(map(jnp.asarray, (fed.train.images, fed.train.labels,
                                     fed.train.sizes)))
    assert sum(a.nbytes for a in arrays) > 5_000_000
    fn = make_chained_round_fn(cfg, model, norm, *arrays)
    lowered = fn.jitted.lower(params, jax.random.PRNGKey(1),
                              jnp.arange(1, 3), *fn.data)
    text_mb = len(lowered.as_text()) / 1e6
    assert text_mb < 2.0, (
        f"lowered chained program is {text_mb:.1f} MB of StableHLO — the "
        f"dataset stacks are being embedded as constants again")
