"""`correct` is C1 and C2 and C3: true on sixteen seeds at the tiny size,
false under each of three planted defects, and the plain references agree
with the program's models. All on the CPU: pass/fail, never a device
metric."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import check, harness
from benchmark.reference import cnn_mnist, evaluate, resnet9, server_step

from tiny_root import make_root

SEEDS = list(range(14)) + [2 ** 31 + 11, 3_000_000_019]


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    return make_root(tmp_path_factory.mktemp("tiny"))


@pytest.fixture(scope="module")
def tiny_uncached(tmp_path_factory):
    # a planted defect must reach the program: no banked executable
    return make_root(tmp_path_factory.mktemp("tiny_nc"),
                     extra_flags=["--no_compile_cache"])


def run(root, seed, lines=None):
    return harness.run_cell("tiny-cnn.round-eval", seed, 0.0, False,
                            platform="cpu", bench_path=root,
                            say=(lines.append if lines is not None
                                 else (lambda _ln: None)))


def checks(lines):
    return {tag: json.loads(next(ln for ln in lines if ln.startswith(
        f"[bench] {tag} ")).split(" ", 2)[2]) for tag in ("C1", "C2", "C3")}


@pytest.mark.parametrize("seed", SEEDS)
def test_correct_on_sixteen_seeds(tiny, seed):
    lines = []
    result = run(tiny, seed, lines)
    got = checks(lines)
    assert result["correct"] is True, got
    assert got["C1"]["lr_mismatched"] == 0
    assert got["C1"]["ulps_of_leaf_scale"] <= check.C1_ULPS
    assert result["failed"] == 0 and result["attempted"] == 3


def test_defect_vote_bypassed(tiny_uncached, monkeypatch):
    from defending_against_backdoors_with_robust_learning_rate_tpu.ops import (
        aggregate)

    def no_vote(updates, threshold, server_lr, mask=None):
        return jax.tree_util.tree_map(
            lambda u: jnp.full(u.shape[1:], server_lr, jnp.float32), updates)

    monkeypatch.setattr(aggregate, "robust_lr", no_vote)
    lines = []
    assert run(tiny_uncached, 1, lines)["correct"] is False
    got = checks(lines)
    assert not got["C1"]["ok"] and got["C1"]["lr_mismatched"] > 0
    assert got["C2"]["ok"] and got["C3"]["ok"]


def test_defect_normaliser_dropped_in_eval(tiny_uncached, monkeypatch):
    from defending_against_backdoors_with_robust_learning_rate_tpu import train
    real = train.make_eval_fn
    monkeypatch.setattr(
        train, "make_eval_fn",
        lambda model, _norm, n: real(model, lambda x: x.astype(jnp.float32),
                                     n))
    lines = []
    assert run(tiny_uncached, 2, lines)["correct"] is False
    got = checks(lines)
    assert got["C1"]["ok"] and got["C3"]["ok"] and not got["C2"]["ok"]


def test_defect_stamp_on_the_wrong_class(tiny_uncached, monkeypatch):
    from defending_against_backdoors_with_robust_learning_rate_tpu.attack import (
        poison)
    real = poison.build_poisoned_val
    monkeypatch.setattr(
        poison, "build_poisoned_val",
        lambda imgs, lbls, cfg: real(imgs, lbls, cfg.replace(
            base_class=(cfg.base_class + 1) % 10)))
    lines = []
    assert run(tiny_uncached, 3, lines)["correct"] is False
    got = checks(lines)
    assert got["C1"]["ok"] and got["C3"]["ok"] and not got["C2"]["ok"]
    assert got["C2"]["deviation"]["Validation/Loss"] < 1e-4   # clean side holds


def test_server_step_reference_by_hand():
    # three agents, two coordinates; sizes 1, 1, 2; threshold 3, lr 1
    u = np.array([[1.0, -2.0], [3.0, 4.0], [5.0, -6.0]], np.float32)
    p = np.array([10.0, 20.0], np.float32)
    lr, new = server_step.server_step(p, u, np.array([1, 1, 2]), 3.0, 1.0)
    assert lr.tolist() == [1.0, -1.0]              # votes |3| and |-1|
    # averages (1+3+10)/4 = 3.5 and (-2+4-12)/4 = -2.5
    assert new.tolist() == [13.5, 22.5]
    lr0, new0 = server_step.server_step(p, u, np.array([1, 1, 2]), 0.0, 1.0)
    assert lr0.tolist() == [1.0, 1.0] and new0.tolist() == [13.5, 17.5]


def test_stamp_and_poisoned_set_by_hand():
    imgs = np.zeros((3, 8, 8, 1), np.uint8)
    lbls = np.array([5, 1, 5], np.int32)
    backdoor = {"base_class": 5, "target_class": 7, "value": 255,
                "strokes": [{"rows": [1, 3], "cols": [2, 2]},
                            {"rows": [2, 2], "cols": [1, 3]}]}
    p_imgs, p_lbls = evaluate.poisoned_set(imgs, lbls, backdoor)
    assert p_lbls.tolist() == [7, 7] and p_imgs.shape == (2, 8, 8, 1)
    on = np.argwhere(p_imgs[0, :, :, 0] == 255).tolist()
    assert on == [[1, 2], [2, 1], [2, 2], [2, 3], [3, 2]]     # a plus
    assert imgs.max() == 0                                     # input untouched


@pytest.mark.parametrize("arch,data,ref,shape", [
    ("cnn", "fmnist", cnn_mnist, (28, 28, 1)),
    ("resnet9", "cifar10", resnet9, (32, 32, 3)),
])
def test_reference_forward_agrees_with_the_programs_model(arch, data, ref,
                                                          shape):
    from defending_against_backdoors_with_robust_learning_rate_tpu.models.registry import (
        get_model, init_params)
    model = get_model(data, arch, "f32", remat=(arch == "resnet9"))
    params = init_params(model, shape, jax.random.PRNGKey(1))
    leaves, treedef = jax.tree_util.tree_flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(2), len(leaves))
    params = jax.tree_util.tree_unflatten(treedef, [
        x + 0.05 * jax.random.normal(k, x.shape)
        for x, k in zip(leaves, keys, strict=True)])
    x = jax.random.normal(jax.random.PRNGKey(3), (3,) + shape)
    want = model.apply({"params": params}, x, train=False)
    got = ref.forward(jax.device_get(params), x)
    # float32 at the highest precision on both sides, another order of sums
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-5)
