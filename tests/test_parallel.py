"""Sharded-vs-single-device parity on a faked 8-device CPU mesh
(SURVEY.md section 4: distributed-without-a-cluster)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from defending_against_backdoors_with_robust_learning_rate_tpu.config import Config
from defending_against_backdoors_with_robust_learning_rate_tpu.data.registry import (
    get_federated_data)
from defending_against_backdoors_with_robust_learning_rate_tpu.fl.common import (
    make_normalizer)
from defending_against_backdoors_with_robust_learning_rate_tpu.fl.rounds import (
    make_round_fn)
from defending_against_backdoors_with_robust_learning_rate_tpu.models.registry import (
    get_model, init_params)
from defending_against_backdoors_with_robust_learning_rate_tpu.parallel.mesh import (
    make_mesh, pick_agent_mesh_size)
from defending_against_backdoors_with_robust_learning_rate_tpu.parallel.rounds import (
    make_sharded_round_fn)


def test_pick_agent_mesh_size():
    assert pick_agent_mesh_size(8, 10, n_devices=8) == 5   # m=10 on v5e-8
    assert pick_agent_mesh_size(8, 8, n_devices=8) == 8
    assert pick_agent_mesh_size(0, 33, n_devices=8) == 3   # fedemnist m=33
    assert pick_agent_mesh_size(1, 7, n_devices=8) == 1


def _setup(aggr, num_corrupt=1, **kw):
    cfg = Config(data="synthetic", num_agents=8, bs=16, local_ep=1,
                 synth_train_size=256, synth_val_size=64, aggr=aggr,
                 num_corrupt=num_corrupt, poison_frac=1.0,
                 robustLR_threshold=3 if aggr in ("avg", "sign") else 0,
                 seed=11, **kw)
    fed = get_federated_data(cfg)
    model = get_model(cfg.data, cfg.model_arch, cfg.dtype)
    params = init_params(model, cfg.image_shape, jax.random.PRNGKey(0))
    norm = make_normalizer(fed.mean, fed.std, fed.raw_is_normalized)
    arrays = (jnp.asarray(fed.train.images), jnp.asarray(fed.train.labels),
              jnp.asarray(fed.train.sizes))
    return cfg, model, params, norm, arrays


def _sharded_and_vmap_round(cfg, model, params, norm, arrays):
    """One round of the vmap program and of the 8-way shard_map program
    from the same parameters and key: ((params, info), (params, info)),
    after the comparisons every case shares."""
    assert len(jax.devices()) == 8, "conftest must fake 8 CPU devices"
    key = jax.random.PRNGKey(42)

    single = make_round_fn(cfg, model, norm, *arrays)
    p1, info1 = single(params, key)

    mesh = make_mesh(8)
    sharded = make_sharded_round_fn(cfg, model, norm, mesh, *arrays)
    p2, info2 = sharded(params, key)

    np.testing.assert_array_equal(np.asarray(info1["sampled"]),
                                  np.asarray(info2["sampled"]))
    for a, b in zip(jax.tree_util.tree_leaves(p1),
                    jax.tree_util.tree_leaves(p2), strict=True):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(float(info1["train_loss"]),
                               float(info2["train_loss"]), rtol=1e-4)
    return info1, info2


# one case a collective pattern: psums of weighted sums and of sign sums
# (avg, sign), the all_to_all transpose with a local sort or distance
# matrix (comed, trmean, krum), per-iteration weighted psums (rfa).
# Value-level semantics of every rule are in tests/test_ops.py.
@pytest.mark.parametrize("aggr", ["avg", "comed", "sign", "trmean", "krum",
                                  "rfa"])
def test_sharded_round_matches_vmap_round(aggr):
    _sharded_and_vmap_round(*_setup(aggr))


LEAF_VARIANTS = {
    "avg_rlr": dict(aggr="avg"),
    "sign_rlr": dict(aggr="sign", server_lr=0.5),
    "avg_rlr_tel_full": dict(aggr="avg", telemetry="full"),
    "avg_rlr_faults": dict(aggr="avg", dropout_rate=0.3,
                           payload_norm_cap=100.0,
                           faults_spare_corrupt=True),
}

# series that are integer counts over coordinates or clients: the sum
# over devices is exact, so the per-leaf psum plan reads what vmap reads
_EXACT_SERIES = ("tel_flip_frac", "tel_margin_hist")


@pytest.mark.parametrize("name", sorted(LEAF_VARIANTS))
def test_leaf_round_matches_vmap_round(name):
    """The per-leaf psum plan against the single-device vmap round with
    the RLR vote on, two corrupt clients, and the lanes that ride the
    plan (full telemetry, the faults mask): parameters and loss as
    above, and every `tel_*` / `fault_*` series."""
    info1, info2 = _sharded_and_vmap_round(
        *_setup(num_corrupt=2, **LEAF_VARIANTS[name]))
    series = sorted(k for k in info1
                    if k.startswith(("tel_", "fault_")))
    assert series == sorted(k for k in info2
                            if k.startswith(("tel_", "fault_")))
    assert series or name in ("avg_rlr", "sign_rlr")
    for k in series:
        a, b = np.asarray(info1[k]), np.asarray(info2[k])
        if k in _EXACT_SERIES or k.startswith("fault_"):
            np.testing.assert_array_equal(a, b, err_msg=k)
        else:
            np.testing.assert_allclose(a, b, atol=1e-6, rtol=1e-5,
                                       err_msg=k)


def test_param_shard_transpose_roundtrip():
    """all_to_all param-sharding (SURVEY.md 7.3.1) is a lossless transpose:
    agents-sharded [m/d, ...] -> all-agents x param-chunk [m, c] -> back."""
    from jax.sharding import PartitionSpec as P
    from jax import shard_map
    from defending_against_backdoors_with_robust_learning_rate_tpu.parallel.rounds import (
        _from_param_shard, _to_param_shards)

    d = 8
    mesh = make_mesh(d)
    m, shape = 16, (3, 5, 7)   # flat length 105, not divisible by 8
    u = jnp.arange(m * 105, dtype=jnp.float32).reshape((m,) + shape)

    def body(ub):                      # ub: [m/d, ...] local block
        chunk, L = _to_param_shards(ub, d)
        assert chunk.shape == (m, -(-105 // d))
        med = jnp.sort(chunk, axis=0)[(m - 1) // 2]
        return _from_param_shard(med, L, shape)

    out = jax.jit(shard_map(
        body, mesh=mesh, in_specs=P("agents"), out_specs=P(),
        check_vma=False))(u)
    expect = jnp.sort(u, axis=0)[(m - 1) // 2]
    np.testing.assert_array_equal(np.asarray(out), np.asarray(expect))


def test_multihost_helpers_single_process_degrade():
    """multihost helpers must be transparent for single-process jobs: the
    global mesh equals the local mesh, put_replicated yields replicated
    global arrays the sharded round fn accepts."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from defending_against_backdoors_with_robust_learning_rate_tpu.parallel import (
        multihost)

    assert jax.process_count() == 1
    assert multihost.is_lead()
    mesh = multihost.global_agents_mesh(4)
    assert mesh.devices.size == 4 and mesh.axis_names == ("agents",)

    cfg, model, params, norm, arrays = _setup("avg", num_corrupt=0)
    g_params = multihost.put_replicated(mesh, params)
    leaf = jax.tree_util.tree_leaves(g_params)[0]
    assert leaf.sharding.is_equivalent_to(
        NamedSharding(mesh, P()), leaf.ndim)
    g_arrays = multihost.put_replicated(mesh, arrays)
    sharded = make_sharded_round_fn(cfg, model, norm, mesh, *g_arrays)
    p, info = sharded(g_params, jax.random.PRNGKey(0))
    assert np.isfinite(float(info["train_loss"]))


def test_sharded_multiround_trains():
    cfg, model, params, norm, arrays = _setup("avg", num_corrupt=0)
    mesh = make_mesh(4)
    sharded = make_sharded_round_fn(cfg, model, norm, mesh, *arrays)
    key = jax.random.PRNGKey(0)
    losses = []
    for _r in range(4):
        key, sub = jax.random.split(key)
        params, info = sharded(params, sub)
        losses.append(float(info["train_loss"]))
    assert losses[-1] < losses[0]


def test_sharded_host_round_matches_single_device_host():
    """Host-sampled sharded path (fedemnist-scale, VERDICT r1 #5): the
    shard_mapped round over host-gathered [m, ...] stacks must match the
    single-device host round bit-for-bit in sampling and closely in params."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from defending_against_backdoors_with_robust_learning_rate_tpu.fl.rounds import (
        make_round_fn_host)
    from defending_against_backdoors_with_robust_learning_rate_tpu.parallel.mesh import (
        AGENTS_AXIS)
    from defending_against_backdoors_with_robust_learning_rate_tpu.parallel.rounds import (
        make_sharded_round_fn_host)

    cfg, model, params, norm, arrays = _setup("avg")
    images, labels, sizes = arrays
    # the driver gathers m sampled shards host-side; emulate with a fixed
    # id set (m = agents_per_round = num_agents = 8 here)
    ids = np.array([3, 1, 7, 2, 5, 0, 6, 4])
    gathered = (images[ids], labels[ids], sizes[ids])
    key = jax.random.PRNGKey(9)

    single = make_round_fn_host(cfg, model, norm)
    p1, info1 = single(params, key, *gathered)

    mesh = make_mesh(8)
    sharding = NamedSharding(mesh, P(AGENTS_AXIS))
    sharded = make_sharded_round_fn_host(cfg, model, norm, mesh)
    p2, info2 = sharded(params, key,
                        *(jax.device_put(a, sharding) for a in gathered))

    for a, b in zip(jax.tree_util.tree_leaves(p1),
                    jax.tree_util.tree_leaves(p2), strict=True):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(float(info1["train_loss"]),
                               float(info2["train_loss"]), rtol=1e-4)


def test_guarded_sharded_round_runs():
    """--debug_nan over the shard_mapped path (ADVICE r1): checkify must
    accept the psum/all_to_all/all_gather collectives at trace time and the
    guarded fn must still raise on an injected NaN."""
    import pytest
    from jax.experimental import checkify
    from defending_against_backdoors_with_robust_learning_rate_tpu.utils.guards import (
        guard_round_fn)

    cfg, model, params, norm, arrays = _setup("comed")
    mesh = make_mesh(8)
    sharded = make_sharded_round_fn(cfg, model, norm, mesh, *arrays)
    guarded = guard_round_fn(sharded)
    p, info = guarded(params, jax.random.PRNGKey(3))
    assert np.isfinite(float(info["train_loss"]))

    bad = jax.tree_util.tree_map(lambda l: l.at[...].set(jnp.nan)
                                 if l.ndim else l, params)
    with pytest.raises(checkify.JaxRuntimeError):
        guarded(bad, jax.random.PRNGKey(4))
