"""Orbax checkpoint/resume roundtrip (the subsystem the reference lacks,
SURVEY.md section 5.4)."""

import jax
import jax.numpy as jnp
import numpy as np

from defending_against_backdoors_with_robust_learning_rate_tpu.utils import (
    checkpoint as ckpt)


def test_roundtrip(tmp_path):
    d = str(tmp_path / "ck")
    params = {"a": jnp.arange(6.0).reshape(2, 3),
              "b": {"k": jnp.asarray([1.5, -2.5])}}
    key = jax.random.PRNGKey(123)
    ckpt.save(d, 7, params, key, 3.25, cum_net_mov=-1.5)
    ckpt.save(d, 9, params, key, 4.5, cum_net_mov=2.0)

    like = jax.tree_util.tree_map(jnp.zeros_like, params)
    rnd, p, k, cpa, cnm = ckpt.restore(d, like)
    assert rnd == 9 and cpa == 4.5 and cnm == 2.0
    np.testing.assert_array_equal(np.asarray(p["a"]), np.asarray(params["a"]))
    np.testing.assert_array_equal(np.asarray(jax.random.key_data(k)),
                                  np.asarray(jax.random.key_data(key)))


def test_restore_empty_dir_returns_none(tmp_path):
    assert ckpt.restore(str(tmp_path / "nope"), {}) is None


def test_legacy_checkpoint_without_cum_net_mov_restores(tmp_path):
    """Checkpoints written before cum_net_mov existed restore via the
    fallback branch, defaulting cum_net_mov to 0."""
    import os
    import orbax.checkpoint as ocp

    d = str(tmp_path / "ck")
    params = {"a": jnp.arange(4.0)}
    key = jax.random.PRNGKey(5)
    legacy = {
        "params": jax.device_get(params),
        "round": np.asarray(3, np.int64),
        "key": np.asarray(jax.device_get(jax.random.key_data(key))),
        "cum_poison_acc": np.asarray(1.25, np.float64),
    }
    ckptr = ocp.StandardCheckpointer()
    ckptr.save(os.path.join(d, "round_000003"), legacy, force=True)
    ckptr.wait_until_finished()

    rnd, p, k, cpa, cnm = ckpt.restore(
        d, jax.tree_util.tree_map(jnp.zeros_like, params))
    assert rnd == 3 and cpa == 1.25 and cnm == 0.0
    np.testing.assert_array_equal(np.asarray(p["a"]), np.asarray(params["a"]))


def test_restore_structure_mismatch_reraises(tmp_path):
    """A real structural mismatch (different param tree) is NOT swallowed by
    the legacy-cum_net_mov fallback."""
    import pytest

    d = str(tmp_path / "ck")
    ckpt.save(d, 1, {"a": jnp.arange(4.0)}, jax.random.PRNGKey(0), 0.0)
    with pytest.raises(ValueError):
        ckpt.restore(d, {"renamed": jnp.zeros(4)})


def test_cross_rng_impl_restore_fails_loudly(tmp_path):
    """train.py's apply_rng_impl docstring promises a checkpoint "resumes
    only under the impl that wrote it (restore fails loudly)": threefry key
    data is [2] uint32, rbg is [4], so a cross-impl restore is a structural
    mismatch orbax must reject — never a silent mis-resume."""
    import pytest

    prev = jax.config.jax_default_prng_impl
    d = str(tmp_path / "ck")
    params = {"a": jnp.arange(4.0)}
    like = jax.tree_util.tree_map(jnp.zeros_like, params)
    try:
        jax.config.update("jax_default_prng_impl", "threefry2x32")
        ckpt.save(d, 2, params, jax.random.PRNGKey(7), 1.0)
        jax.config.update("jax_default_prng_impl", "rbg")
        with pytest.raises(ValueError, match="rng_impl"):
            ckpt.restore(d, like)
        # and back under the writing impl it still restores fine
        jax.config.update("jax_default_prng_impl", "threefry2x32")
        rnd, _, k, _, _ = ckpt.restore(d, like)
        assert rnd == 2
        np.testing.assert_array_equal(
            np.asarray(jax.random.key_data(k)),
            np.asarray(jax.random.key_data(jax.random.PRNGKey(7))))
    finally:
        jax.config.update("jax_default_prng_impl", prev)


def test_latest_round_ignores_orbax_tmp_dirs(tmp_path):
    d = tmp_path / "ck"
    (d / "round_000005").mkdir(parents=True)
    (d / "round_000007.orbax-checkpoint-tmp-12345").mkdir()
    assert ckpt.latest_round(str(d)) == 5


def _resume_cfg(tmp_path, tag, **kw):
    from defending_against_backdoors_with_robust_learning_rate_tpu.config import (
        Config)

    return Config(data="synthetic", num_agents=4, bs=16, local_ep=1,
                  synth_train_size=128, synth_val_size=32, seed=21,
                  snap=5, chain=3, tensorboard=False,
                  log_dir=str(tmp_path / f"logs_{tag}"),
                  checkpoint_dir=str(tmp_path / f"ck_{tag}"), **kw)


def _restored_params(cfg):
    from defending_against_backdoors_with_robust_learning_rate_tpu.models.registry import (
        get_model, init_params)

    model = get_model(cfg.data, cfg.model_arch, cfg.dtype)
    like = init_params(model, cfg.image_shape, jax.random.PRNGKey(cfg.seed))
    rnd, params, *_ = ckpt.restore(cfg.checkpoint_dir, like)
    return rnd, params


import pytest  # noqa: E402


@pytest.mark.parametrize("host_sampled", ["auto", "on"])
def test_resume_mid_chain_continues_exact_sequence(tmp_path, host_sampled):
    """--resume restoring at a round where rnd % chain != 0 (round 5 with
    chain=3) must continue the exact sampling/key sequence through the next
    partial block: the budget logic re-enters a chained block (6-8), then
    singles (9, 10). Checked by bitwise-comparing the round-10 checkpoint of
    a resumed run against an uninterrupted one, for both the device-resident
    and host-sampled (unit-prefetched) paths."""
    from defending_against_backdoors_with_robust_learning_rate_tpu.train import (
        run)

    cfg_a = _resume_cfg(tmp_path, f"a_{host_sampled}", rounds=10,
                        host_sampled=host_sampled)
    run(cfg_a)
    rnd_a, p_a = _restored_params(cfg_a)
    assert rnd_a == 10

    cfg_b = _resume_cfg(tmp_path, f"b_{host_sampled}", rounds=5,
                        host_sampled=host_sampled)
    run(cfg_b)
    rnd_mid, _ = _restored_params(cfg_b)
    assert rnd_mid == 5 and rnd_mid % cfg_b.chain != 0
    run(cfg_b.replace(rounds=10, resume=True))
    rnd_b, p_b = _restored_params(cfg_b)
    assert rnd_b == 10

    for a, b in zip(jax.tree_util.tree_leaves(p_a),
                    jax.tree_util.tree_leaves(p_b), strict=True):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
