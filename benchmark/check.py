"""What decides `correct`: C1 and C2, fixed work outside the window and a
function of the seed alone. (C3, the integrity of the window, is counted by
the harness as it runs.) Nothing here looks at accuracy after N rounds, at
parameters across the vote, or at a single logit: see PERF.md section 6."""

from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference import evaluate as ref_eval
from benchmark.reference import server_step as ref_server

# C1: new parameters agree with the float64 transcription to this many
# float32 ulps of the leaf's largest magnitude. The device sums m <= 40
# products of magnitude 1e-2/m in float32 and adds them to parameters of
# magnitude ~0.5, so the last rounding of that add is what is left: 0.50 ulps
# measured on the v5e on seeds 0-7 in both configurations (PERF.md section
# 6). A bf16 average would be off by ~1e-2 * 2**-8 = 4e-5, a thousand ulps.
C1_ULPS = 4.0
PARAM_SCALE, UPDATE_SCALE = 0.1, 0.01
EPS32 = float(np.finfo(np.float32).eps)


def c1_server_step(cfg, params_like, seed: int) -> Dict[str, Any]:
    """The three calls the round makes under scope `aggregate_rlr`
    (fl/rounds.py:380-395) on a seeded normal stack of the cell's real
    shape, against benchmark/reference/server_step.py, leaf by leaf."""
    from defending_against_backdoors_with_robust_learning_rate_tpu.ops import (
        aggregate)
    m = cfg.agents_per_round
    threshold = float(cfg.robustLR_threshold)
    server_lr = float(cfg.effective_server_lr)
    shapes = jax.tree_util.tree_map(lambda x: tuple(x.shape), params_like)
    leaves, treedef = jax.tree_util.tree_flatten(
        shapes, is_leaf=lambda x: isinstance(x, tuple))

    @jax.jit
    def draw(key):
        kp, ku, ks = jax.random.split(key, 3)
        pk = jax.random.split(kp, len(leaves))
        uk = jax.random.split(ku, len(leaves))
        params = [PARAM_SCALE * jax.random.normal(k, s, jnp.float32)
                  for k, s in zip(pk, leaves, strict=True)]
        updates = [UPDATE_SCALE * jax.random.normal(k, (m,) + s, jnp.float32)
                   for k, s in zip(uk, leaves, strict=True)]
        sizes = jax.random.randint(ks, (m,), 3000, 9000, jnp.int32)
        return (jax.tree_util.tree_unflatten(treedef, params),
                jax.tree_util.tree_unflatten(treedef, updates), sizes)

    @jax.jit
    def step(params, updates, sizes, key):
        lr = (aggregate.robust_lr(updates, threshold, server_lr)
              if threshold > 0 else server_lr)
        agg = aggregate.aggregate_updates(updates, sizes, cfg, key)
        return lr, aggregate.apply_aggregate(params, lr, agg)

    key = jax.random.PRNGKey(seed)
    params, updates, sizes = draw(key)
    lr, new = step(params, updates, sizes, key)
    sizes_h = np.asarray(jax.device_get(sizes))
    flat = jax.tree_util.tree_leaves
    lr_leaves = flat(lr) if threshold > 0 else [None] * len(leaves)
    mismatched, worst, coords = 0, 0.0, 0
    for p, u, l, n in zip(flat(params), flat(updates), lr_leaves, flat(new),
                          strict=True):
        p, u, n = (np.asarray(jax.device_get(x)) for x in (p, u, n))
        lr_ref, new_ref = ref_server.server_step(p, u, sizes_h, threshold,
                                                 server_lr)
        if l is not None:
            mismatched += int(np.count_nonzero(
                np.asarray(jax.device_get(l)) != lr_ref.astype(np.float32)))
        scale = float(np.max(np.abs(new_ref))) or 1.0
        worst = max(worst, float(np.max(np.abs(n - new_ref)))
                    / (EPS32 * scale))
        coords += p.size
    return {"ok": mismatched == 0 and worst <= C1_ULPS and bool(
                np.isfinite(worst)),
            "lr_mismatched": mismatched, "ulps_of_leaf_scale": worst,
            "coordinates": coords, "agents": m}


def _close(a: float, b: float, rtol: float, atol: float) -> bool:
    return bool(np.isfinite(a) and np.isfinite(b)
                and abs(a - b) <= rtol * max(abs(a), abs(b)) + atol)


def c2_model(rows: Dict[str, float], params, val, config: Dict[str, Any],
             forward) -> Dict[str, Any]:
    """The engine's own evaluation at the warm-up boundary (`rows`: its four
    scalars) against the plain reference on the same parameters and the
    same clean validation images; the poisoned set is the reference's own.
    Tolerances come from the configuration's file."""
    tol = config["check"]
    images, labels, weights = (np.asarray(jax.device_get(x)) for x in val)
    keep = weights.reshape(-1) > 0
    images = images.reshape((-1,) + images.shape[2:])[keep]
    labels = labels.reshape(-1)[keep]
    params = jax.device_get(params)
    v_loss, v_acc, v_n = ref_eval.loss_and_accuracy(
        forward, params, images, labels, config["normalise"])
    p_imgs, p_lbls = ref_eval.poisoned_set(images, labels, config["backdoor"])
    if not len(p_lbls):
        raise ValueError("the validation set has no image of the base class")
    p_loss, p_acc, p_n = ref_eval.loss_and_accuracy(
        forward, params, p_imgs, p_lbls, config["normalise"])
    ref = {"Validation/Loss": v_loss, "Validation/Accuracy": v_acc,
           "Poison/Poison_Loss": p_loss, "Poison/Poison_Accuracy": p_acc}
    counts = {"Validation/Accuracy": v_n, "Poison/Poison_Accuracy": p_n}
    out = {"ok": True, "n_val": v_n, "n_poison": p_n, "engine": rows,
           "reference": ref, "deviation": {}}
    for tag, r in ref.items():
        e = rows.get(tag, float("nan"))
        if tag in counts:
            dev = abs(e - r) * counts[tag]              # in images
            good = bool(np.isfinite(dev)) and dev <= tol["acc_images"]
        else:
            dev = abs(e - r) / max(abs(e), abs(r), 1e-30)   # relative
            good = _close(e, r, tol["loss_rtol"], tol["loss_atol"])
        out["deviation"][tag] = dev
        out["ok"] = out["ok"] and good
    return out
