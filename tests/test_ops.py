"""Unit tests for the numeric building blocks: SGD/clip/PGD parity with torch
semantics, aggregation rules, and the RLR defense (src/aggregation.py:48-75)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
import torch

from defending_against_backdoors_with_robust_learning_rate_tpu.config import Config
from defending_against_backdoors_with_robust_learning_rate_tpu.ops import tree
from defending_against_backdoors_with_robust_learning_rate_tpu.ops.aggregate import (
    agg_avg, agg_comed, agg_krum, agg_sign, agg_trmean, aggregate_updates,
    apply_aggregate, robust_lr)
from defending_against_backdoors_with_robust_learning_rate_tpu.ops.sgd import (
    clip_by_global_norm, pgd_project, sgd_momentum_step)


def _tree(*arrays):
    return {f"w{i}": jnp.asarray(a, jnp.float32) for i, a in enumerate(arrays)}


# ------------------------------------------------------------------- sgd ---

def test_clip_matches_torch_clip_grad_norm():
    rng = np.random.default_rng(0)
    g1, g2 = rng.normal(size=(5, 3)) * 4, rng.normal(size=(7,)) * 4
    ours = clip_by_global_norm(_tree(g1, g2), 2.0)

    t1 = torch.nn.Parameter(torch.zeros(5, 3))
    t2 = torch.nn.Parameter(torch.zeros(7))
    t1.grad = torch.tensor(g1, dtype=torch.float32)
    t2.grad = torch.tensor(g2, dtype=torch.float32)
    torch.nn.utils.clip_grad_norm_([t1, t2], 2.0)
    np.testing.assert_allclose(ours["w0"], t1.grad.numpy(), rtol=1e-5)
    np.testing.assert_allclose(ours["w1"], t2.grad.numpy(), rtol=1e-5)


def test_sgd_momentum_matches_torch_over_steps():
    """torch SGD(momentum, no dampening): buf = mu*buf + g; p -= lr*buf —
    fresh optimizer per round (src/agent.py:37-38)."""
    rng = np.random.default_rng(1)
    p0 = rng.normal(size=(4, 2))
    grads = [rng.normal(size=(4, 2)) for _ in range(5)]

    tp = torch.nn.Parameter(torch.tensor(p0, dtype=torch.float32))
    opt = torch.optim.SGD([tp], lr=0.1, momentum=0.9)
    for g in grads:
        opt.zero_grad()
        tp.grad = torch.tensor(g, dtype=torch.float32)
        opt.step()

    params = _tree(p0)
    mom = tree.zeros_like(params)
    for g in grads:
        params, mom = sgd_momentum_step(params, mom, _tree(g), 0.1, 0.9,
                                        jnp.bool_(True))
    np.testing.assert_allclose(params["w0"], tp.detach().numpy(), rtol=1e-5)


def test_sgd_masked_step_is_noop():
    params = _tree(np.ones((3,)))
    mom = _tree(np.full((3,), 0.5))
    p2, m2 = sgd_momentum_step(params, mom, _tree(np.ones((3,))), 0.1, 0.9,
                               jnp.bool_(False))
    np.testing.assert_array_equal(p2["w0"], params["w0"])
    np.testing.assert_array_equal(m2["w0"], mom["w0"])


def test_pgd_project():
    p0 = _tree(np.zeros((4,)))
    p = _tree(np.full((4,), 3.0))          # ||update|| = 6
    out = pgd_project(p, p0, 2.0)          # scaled to norm 2
    np.testing.assert_allclose(float(tree.norm(tree.sub(out, p0))), 2.0,
                               rtol=1e-5)
    out2 = pgd_project(out, p0, 2.0)       # inside the ball: no-op
    np.testing.assert_allclose(out2["w0"], out["w0"], rtol=1e-6)


# ----------------------------------------------------------- aggregation ---

def test_robust_lr_rule():
    """RLR (src/aggregation.py:48-54): |sum of signs| >= thr -> +lr else -lr."""
    u = jnp.asarray([[1.0, 1.0, -1.0, 0.0],
                     [2.0, -1.0, -3.0, 0.0],
                     [0.5, 1.0, -2.0, 0.0],
                     [4.0, -2.0, 5.0, 0.0]])
    lr = robust_lr({"w": u}, threshold=3.0, server_lr=1.0)["w"]
    # sums of signs: 4, -? (1-1+1-1=0), (-1-1-1+1=-2)->2, 0
    np.testing.assert_array_equal(np.asarray(lr), [1.0, -1.0, -1.0, -1.0])


def test_agg_avg_weighted():
    u = {"w": jnp.asarray([[1.0, 2.0], [3.0, 6.0]])}
    out = agg_avg(u, jnp.asarray([1.0, 3.0]))["w"]
    np.testing.assert_allclose(out, [(1 + 9) / 4, (2 + 18) / 4])


def test_agg_comed_matches_torch_median():
    rng = np.random.default_rng(2)
    for m in (3, 4, 7, 8):
        u = rng.normal(size=(m, 13)).astype(np.float32)
        ours = np.asarray(agg_comed({"w": jnp.asarray(u)})["w"])
        theirs = torch.median(torch.tensor(u), dim=0).values.numpy()
        np.testing.assert_allclose(ours, theirs, rtol=1e-6)


def test_agg_sign():
    u = {"w": jnp.asarray([[1.0, -2.0, 0.0], [3.0, -1.0, 0.0],
                           [-1.0, -5.0, 0.0]])}
    np.testing.assert_array_equal(np.asarray(agg_sign(u)["w"]),
                                  [1.0, -1.0, 0.0])


def test_agg_trmean_drops_extremes():
    """Trimmed mean (k=1) over [m, n]: per coordinate, min and max are
    dropped, the rest averaged — outliers cannot move the aggregate."""
    u = {"w": jnp.asarray([[100.0, -7.0], [1.0, 2.0],
                           [3.0, 4.0], [-50.0, 100.0]])}
    out = np.asarray(agg_trmean(u, trim_k=1)["w"])
    np.testing.assert_allclose(out, [(1 + 3) / 2, (2 + 4) / 2])
    # trim_k clamps so at least one value survives; k=0 is the plain mean
    out0 = np.asarray(agg_trmean(u, trim_k=0)["w"])
    np.testing.assert_allclose(out0, np.asarray(u["w"]).mean(0))
    out_big = np.asarray(agg_trmean(u, trim_k=99)["w"])
    np.testing.assert_allclose(out_big, np.sort(np.asarray(u["w"]),
                                                axis=0)[1:3].mean(0))


def test_agg_krum_drops_outlier():
    rng = np.random.default_rng(3)
    honest = rng.normal(0, 0.1, size=(5, 20)).astype(np.float32)
    outlier = np.full((1, 20), 50.0, np.float32)
    u = {"w": jnp.asarray(np.concatenate([outlier, honest]))}
    out = np.asarray(agg_krum(u, num_corrupt=1)["w"])
    # the selected update must be one of the honest ones
    assert np.abs(out).max() < 1.0


def _np_trimmed_mean(stack, k):
    """Yin et al. 2018, Definition 2 (coordinate-wise trimmed mean): per
    coordinate, remove the k largest and k smallest of the m values and
    average the remaining m-2k. Written directly from the paper's definition,
    independent of ops/aggregate.py."""
    srt = np.sort(np.asarray(stack, np.float64), axis=0)
    m = srt.shape[0]
    return srt[k:m - k].mean(axis=0)


def _np_krum_index(rows, f):
    """Blanchard et al. 2017, section 3 (Krum): each update i scores the sum
    of squared L2 distances to its m-f-2 closest OTHER updates; Krum selects
    the minimizer. Direct per-pair differences in float64, independent of the
    sq-norm-expansion path in ops/aggregate.py."""
    rows = np.asarray(rows, np.float64)
    m = rows.shape[0]
    d = ((rows[:, None, :] - rows[None, :, :]) ** 2).sum(-1)
    k = max(m - f - 2, 1)
    scores = [np.sort(np.delete(d[i], i))[:k].sum() for i in range(m)]
    return int(np.argmin(scores))


def test_agg_trmean_matches_paper_math_on_random_stacks():
    """Framework-extension parity bar (VERDICT r3 #8): agg_trmean must equal
    the straight-from-the-paper numpy trimmed mean on random multi-leaf
    stacks, across trim levels."""
    rng = np.random.default_rng(11)
    m = 9
    u = {"w": jnp.asarray(rng.normal(size=(m, 4, 3)).astype(np.float32)),
         "b": {"k": jnp.asarray(rng.normal(size=(m, 7)).astype(np.float32))}}
    for k in (0, 1, 2, 3):
        out = agg_trmean(u, trim_k=k)
        np.testing.assert_allclose(
            np.asarray(out["w"]), _np_trimmed_mean(u["w"], k), rtol=1e-5,
            err_msg=f"trim_k={k} leaf w")
        np.testing.assert_allclose(
            np.asarray(out["b"]["k"]), _np_trimmed_mean(u["b"]["k"], k),
            rtol=1e-5, err_msg=f"trim_k={k} leaf b.k")


def test_agg_krum_matches_paper_math_on_random_stacks():
    """agg_krum's selection must agree with the from-the-paper numpy Krum
    score (distances summed across all pytree leaves) on random stacks, for
    several seeds and corruption counts."""
    m = 8
    for seed in (0, 1, 2, 3, 4):
        rng = np.random.default_rng(seed)
        u = {"w": jnp.asarray(rng.normal(size=(m, 5, 2)).astype(np.float32)),
             "b": jnp.asarray(rng.normal(size=(m, 3)).astype(np.float32))}
        flat = np.concatenate(
            [np.asarray(u["w"]).reshape(m, -1), np.asarray(u["b"])], axis=1)
        for f in (0, 1, 2):
            want = _np_krum_index(flat, f)
            out = agg_krum(u, num_corrupt=f)
            np.testing.assert_array_equal(
                np.asarray(out["w"]), np.asarray(u["w"])[want],
                err_msg=f"seed={seed} f={f}: selected a different update "
                        f"than paper-Krum index {want}")
            np.testing.assert_array_equal(
                np.asarray(out["b"]), np.asarray(u["b"])[want])


def test_apply_aggregate_with_lr_tree():
    params = _tree(np.zeros((3,)))
    agg = _tree(np.asarray([1.0, 2.0, 3.0]))
    lr = _tree(np.asarray([1.0, -1.0, 1.0]))
    out = apply_aggregate(params, lr, agg)
    np.testing.assert_allclose(out["w0"], [1.0, -2.0, 3.0])
    out2 = apply_aggregate(params, 2.0, agg)
    np.testing.assert_allclose(out2["w0"], [2.0, 4.0, 6.0])


@pytest.mark.parametrize("aggr,m,n,thr", [
    ("avg", 4, 300, 3.0), ("avg", 10, 5000, 4.0), ("avg", 7, 1111, 0.0),
    ("sign", 6, 2222, 0.0), ("sign", 6, 2222, 3.0)])
def test_server_step_matches_numpy_reference(aggr, m, n, thr):
    """The three calls the round makes under `aggregate_rlr` (the vote,
    the rule, the apply) on a random [m, n] stack, against a NumPy
    transcription of compute_robustLR + FedAvg / signSGD majority
    (src/aggregation.py:48-75). Threshold 0 is the undefended step: a
    scalar server lr, as fl/rounds.py passes it."""
    rng = np.random.default_rng(0 if aggr == "avg" else 2)
    u = rng.normal(size=(m, n)).astype(np.float32)
    w = rng.uniform(1, 5, size=(m,)).astype(np.float32)
    p = rng.normal(size=(n,)).astype(np.float32)
    slr = 1.0 if aggr == "avg" else 0.05

    updates = {"w": jnp.asarray(u)}
    lr = robust_lr(updates, thr, slr) if thr > 0 else slr
    agg = (agg_avg(updates, jnp.asarray(w)) if aggr == "avg"
           else agg_sign(updates))
    got = np.asarray(apply_aggregate({"w": jnp.asarray(p)}, lr, agg)["w"])

    ssum = np.sign(u).sum(0)
    want_agg = ((u * (w / w.sum())[:, None]).sum(0) if aggr == "avg"
                else np.sign(ssum))
    want_lr = np.where(np.abs(ssum) >= thr, slr, -slr) if thr > 0 else slr
    tol = 1e-5 if aggr == "avg" else 1e-6
    np.testing.assert_allclose(got, p + want_lr * want_agg, atol=tol,
                               rtol=tol)


def test_noise_added_when_enabled():
    cfg = Config(aggr="avg", noise=1.0, clip=0.5)
    u = {"w": jnp.zeros((4, 100))}
    out = aggregate_updates(u, jnp.ones((4,)), cfg, jax.random.PRNGKey(0))
    std = float(jnp.std(out["w"]))
    assert 0.3 < std < 0.7      # N(0, noise*clip=0.5)


def _np_rfa(stack, iters, eps):
    """Pillutla et al. 2022, Algorithm 1 (smoothed Weiszfeld): start at the
    mean; reweight points by 1/max(||u_k - v||, eps) and take the weighted
    mean, a fixed number of iterations. Float64, independent of
    ops/aggregate.py."""
    rows = np.asarray(stack, np.float64)
    v = rows.mean(axis=0)
    for _ in range(iters):
        w = 1.0 / np.maximum(np.linalg.norm(rows - v[None], axis=1), eps)
        v = (rows * w[:, None]).sum(axis=0) / w.sum()
    return v


def test_agg_rfa_matches_paper_math_on_random_stacks():
    """agg_rfa (geometric median, smoothed Weiszfeld) held to the same
    extension parity bar as trmean/krum: equals the from-the-paper numpy
    implementation on random multi-leaf stacks (distances computed across
    ALL leaves jointly)."""
    from defending_against_backdoors_with_robust_learning_rate_tpu.ops.aggregate import (
        RFA_EPS, RFA_ITERS, agg_rfa)
    m = 7
    for seed in (0, 1, 2):
        rng = np.random.default_rng(seed)
        u = {"w": jnp.asarray(rng.normal(size=(m, 4, 3)).astype(np.float32)),
             "b": jnp.asarray(rng.normal(size=(m, 5)).astype(np.float32))}
        flat = np.concatenate(
            [np.asarray(u["w"]).reshape(m, -1), np.asarray(u["b"])], axis=1)
        want = _np_rfa(flat, RFA_ITERS, RFA_EPS)
        out = agg_rfa(u)
        got = np.concatenate([np.asarray(out["w"]).reshape(-1),
                              np.asarray(out["b"]).reshape(-1)])
        np.testing.assert_allclose(got, want.reshape(-1), rtol=1e-4,
                                   atol=1e-6, err_msg=f"seed={seed}")


def test_agg_rfa_resists_outlier():
    """The geometric median must stay near the honest cluster when one
    update is wildly corrupted (the property that makes it a defense)."""
    from defending_against_backdoors_with_robust_learning_rate_tpu.ops.aggregate import (
        agg_rfa)
    rng = np.random.default_rng(4)
    honest = rng.normal(0, 0.1, size=(6, 30)).astype(np.float32)
    outlier = np.full((1, 30), 100.0, np.float32)
    u = {"w": jnp.asarray(np.concatenate([honest, outlier]))}
    out = np.asarray(agg_rfa(u)["w"])
    mean = np.concatenate([honest, outlier]).mean(0)
    # the plain mean is dragged to ~14; RFA stays near the honest cloud
    assert np.abs(out).max() < 1.0 < np.abs(mean).max()


def test_aggregate_updates_dispatches_every_rule():
    """The dispatch table accepts every documented --aggr value and rejects
    unknown ones (config.py: avg|comed|sign|trmean|krum|rfa)."""
    import pytest
    rng = np.random.default_rng(9)
    u = {"w": jnp.asarray(rng.normal(size=(5, 12)).astype(np.float32))}
    sizes = jnp.asarray([3.0, 1.0, 2.0, 2.0, 4.0])
    for aggr in ("avg", "comed", "sign", "trmean", "krum", "rfa"):
        cfg = Config(aggr=aggr, num_corrupt=1)
        out = aggregate_updates(u, sizes, cfg, jax.random.PRNGKey(0))
        assert np.isfinite(np.asarray(out["w"])).all(), aggr
    with pytest.raises(ValueError, match="unknown aggr"):
        aggregate_updates(u, sizes, Config(aggr="bogus"),
                          jax.random.PRNGKey(0))
