"""Model parity: parameter counts match the reference architectures exactly
(SURVEY.md 7.2.3 'param-count parity checks').

Reference CNN_MNIST (src/models.py:11-31):
  conv1 1->32 3x3 (320) + conv2 32->64 3x3 (18,496)
  + fc1 9216->128 (1,179,776) + fc2 128->10 (1,290) = 1,199,882
Reference CNN_CIFAR (src/models.py:33-58):
  conv 3->64 (1,792) + conv 64->128 (73,856) + conv 128->256 (295,168)
  + fc1 1024->128 (131,200) + fc2 128->256 (33,024) + fc3 256->10 (2,570)
  = 537,610
"""

import jax
import jax.numpy as jnp
import pytest

from defending_against_backdoors_with_robust_learning_rate_tpu.models.registry import (
    get_model, init_params, param_count)


def _build(data, arch, shape):
    model = get_model(data, arch)
    params = init_params(model, shape, jax.random.PRNGKey(0))
    return model, params


def test_cnn_mnist_param_count_parity():
    model, params = _build("fmnist", "cnn", (28, 28, 1))
    assert param_count(params) == 1_199_882


def test_cnn_cifar_param_count_parity():
    model, params = _build("cifar10", "cnn", (32, 32, 3))
    assert param_count(params) == 537_610


def test_forward_shapes_and_dropout_determinism():
    for data, arch, shape in [("fmnist", "cnn", (28, 28, 1)),
                              ("cifar10", "cnn", (32, 32, 3)),
                              ("cifar10", "resnet9", (32, 32, 3))]:
        model, params = _build(data, arch, shape)
        x = jnp.zeros((4,) + shape, jnp.float32)
        out = model.apply({"params": params}, x, train=False)
        assert out.shape == (4, 10), (data, arch)
        assert out.dtype == jnp.float32
        # train mode with the same dropout key is deterministic
        rngs = {"dropout": jax.random.PRNGKey(7)}
        a = model.apply({"params": params}, x + 1.0, train=True, rngs=rngs)
        b = model.apply({"params": params}, x + 1.0, train=True, rngs=rngs)
        assert jnp.array_equal(a, b), (data, arch)


def test_bf16_compute_round_runs():
    """--dtype=bf16 (MXU compute dtype) trains a round with finite loss and
    f32 params (params/update math stays f32; only layer compute is bf16)."""
    import jax.numpy as jnp
    from defending_against_backdoors_with_robust_learning_rate_tpu.config import Config
    from defending_against_backdoors_with_robust_learning_rate_tpu.data.registry import (
        get_federated_data)
    from defending_against_backdoors_with_robust_learning_rate_tpu.fl.common import (
        make_normalizer)
    from defending_against_backdoors_with_robust_learning_rate_tpu.fl.rounds import (
        make_round_fn)

    cfg = Config(data="synthetic", num_agents=4, bs=16, local_ep=1,
                 synth_train_size=128, synth_val_size=32, dtype="bf16",
                 robustLR_threshold=2, num_corrupt=1, poison_frac=1.0)
    fed = get_federated_data(cfg)
    model = get_model(cfg.data, cfg.model_arch, cfg.dtype)
    params = init_params(model, cfg.image_shape, jax.random.PRNGKey(0))
    norm = make_normalizer(fed.mean, fed.std, fed.raw_is_normalized)
    rf = make_round_fn(cfg, model, norm, jnp.asarray(fed.train.images),
                       jnp.asarray(fed.train.labels),
                       jnp.asarray(fed.train.sizes))
    new_params, info = rf(params, jax.random.PRNGKey(1))
    assert jnp.isfinite(info["train_loss"])
    assert all(l.dtype == jnp.float32
               for l in jax.tree_util.tree_leaves(new_params))


def test_resnet9_is_the_north_star_default_for_cifar():
    """BASELINE.json configs[3-4] use ResNet-9 on cifar10; arch='auto'
    resolves cifar10 to the faithful CNN (parity) and 'resnet9' opts in."""
    assert type(get_model("cifar10", "cnn")).__name__ == "CNN_CIFAR"
    assert type(get_model("cifar10", "resnet9")).__name__ == "ResNet9"
    assert type(get_model("fmnist", "auto")).__name__ == "CNN_MNIST"


def test_resnet9_remat_matches_unremated():
    """Blockwise rematerialization (HBM lever for the 40-agent cifar
    configs) is exact: same param tree, same loss, same grads."""
    model = get_model("cifar10", "resnet9")
    model_r = get_model("cifar10", "resnet9", remat=True)
    params = init_params(model, (32, 32, 3), jax.random.PRNGKey(0))
    params_r = init_params(model_r, (32, 32, 3), jax.random.PRNGKey(0))
    assert (jax.tree_util.tree_structure(params)
            == jax.tree_util.tree_structure(params_r))
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 32, 32, 3))

    def loss(m):
        return lambda p: jnp.sum(
            jax.nn.log_softmax(m.apply({"params": p}, x, train=False)) ** 2)

    l1, g1 = jax.value_and_grad(loss(model))(params)
    l2, g2 = jax.value_and_grad(loss(model_r))(params)
    assert jnp.allclose(l1, l2, rtol=1e-6)
    for a, b in zip(jax.tree_util.tree_leaves(g1),
                    jax.tree_util.tree_leaves(g2), strict=True):
        assert jnp.allclose(a, b, rtol=1e-5, atol=1e-6)


def test_resnet9_selective_remat_matches_block():
    """The selective policy (save conv/MXU outputs, recompute only the
    elementwise tail — VERDICT r4 next #4) is exact like blockwise remat:
    identical param tree, loss, and grads, so checkpoints and sweep rows
    interchange freely across remat_policy settings."""
    model = get_model("cifar10", "resnet9")
    model_c = get_model("cifar10", "resnet9", remat=True,
                        remat_policy="conv")
    params = init_params(model, (32, 32, 3), jax.random.PRNGKey(0))
    params_c = init_params(model_c, (32, 32, 3), jax.random.PRNGKey(0))
    assert (jax.tree_util.tree_structure(params)
            == jax.tree_util.tree_structure(params_c))
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 32, 32, 3))

    def loss(m):
        return lambda p: jnp.sum(
            jax.nn.log_softmax(m.apply({"params": p}, x, train=False)) ** 2)

    l1, g1 = jax.value_and_grad(loss(model))(params)
    l2, g2 = jax.value_and_grad(loss(model_c))(params)
    assert jnp.allclose(l1, l2, rtol=1e-6)
    for a, b in zip(jax.tree_util.tree_leaves(g1),
                    jax.tree_util.tree_leaves(g2), strict=True):
        assert jnp.allclose(a, b, rtol=1e-5, atol=1e-6)


def _grad_fn(model, x):
    return jax.grad(lambda p: jnp.sum(jax.nn.log_softmax(
        model.apply({"params": p}, x, train=False)) ** 2))


def test_resnet9_remat_policy_none_is_the_unremated_model():
    """`remat=True, remat_policy="none"` (what `--remat_policy auto`
    settles where the whole backward's activations fit, ISSUE 30) builds
    the plain modules: the parameter tree of every other policy, gradients
    bit-equal to `remat=False`, and nothing to recompute in the jaxpr of
    its gradient, where the other two policies have a checkpoint."""
    from defending_against_backdoors_with_robust_learning_rate_tpu.utils.jaxprs import (
        iter_eqns)
    plain = get_model("cifar10", "resnet9")
    none = get_model("cifar10", "resnet9", remat=True, remat_policy="none")
    params = init_params(plain, (32, 32, 3), jax.random.PRNGKey(0))
    for policy in ("block", "conv", "none"):
        other = init_params(
            get_model("cifar10", "resnet9", remat=True, remat_policy=policy),
            (32, 32, 3), jax.random.PRNGKey(0))
        assert (jax.tree_util.tree_structure(params)
                == jax.tree_util.tree_structure(other))
        for a, b in zip(jax.tree_util.tree_leaves(params),
                        jax.tree_util.tree_leaves(other), strict=True):
            assert a.shape == b.shape and bool(jnp.all(a == b))
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 32, 32, 3))
    for a, b in zip(jax.tree_util.tree_leaves(_grad_fn(plain, x)(params)),
                    jax.tree_util.tree_leaves(_grad_fn(none, x)(params)),
                    strict=True):
        assert bool(jnp.all(a == b))

    def traced(model):
        jaxpr = jax.make_jaxpr(_grad_fn(model, x))(params)
        return str(jaxpr), {eqn.primitive.name for eqn in iter_eqns(jaxpr)
                            } & {"checkpoint", "remat", "remat2"}

    text, recomputed = traced(none)
    assert not recomputed and text == traced(plain)[0]
    for policy in ("block", "conv"):
        assert traced(get_model("cifar10", "resnet9", remat=True,
                                remat_policy=policy))[1]


def test_get_model_takes_resolved_policies_only():
    for asked in ("auto", "some"):
        with pytest.raises(ValueError, match="resolve 'auto'"):
            get_model("cifar10", "resnet9", remat=True, remat_policy=asked)
    # without remat the policy selects nothing, whatever it says
    for policy in ("block", "conv", "none"):
        model = get_model("cifar10", "resnet9", remat_policy=policy)
        assert (model.remat, model.remat_policy) == (False, policy)


def test_flops_per_example_analytic():
    """The registry's analytic FLOP model (bench.py's compile-free MFU
    source): positive, monotone in image size, and within 2x of XLA's
    own cost analysis of the compiled fwd+bwd step (the 3x-forward
    convention vs the compiler's exact count)."""
    from bench import bench_config, train_step_flops
    from defending_against_backdoors_with_robust_learning_rate_tpu.fl.common import (
        make_normalizer)
    from defending_against_backdoors_with_robust_learning_rate_tpu.models.registry import (
        flops_per_example)
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
