"""The folded round's server step against a float64 transcription, a leaf
at a time: fixed work outside the window and a function of the seed alone.

For every leaf of the cell's real parameter tree, at its real shape: seeded
normal parameters, then `m` seeded normal updates folded one after another
through the program's own `ops/aggregate.fold_init` / `fold_updates` /
`fold_finish` and `apply_aggregate` (what `fl/rounds._fold_core` calls under
scope `aggregate_rlr`), and the new parameters compared with
`reference/server_step.py` (NumPy float64, `compute_robustLR` + FedAvg) on a
seeded sample of `check.c1_sample` coordinates of the leaf (all of a smaller
one): the learning rates equal, the new parameters to `check.c1_ulps` float32
ulps of the leaf's largest magnitude. The whole tree at once is 2 GB an
update beside a resident 12 GB engine and does not fit; neither does a
float64 pass over 5 G numbers fit a set-up (C1's took 7 s at 263M).

On the device: one leaf's parameters, accumulators, one update and the new
parameters, 17 bytes a coordinate: 0.50 GB at the largest leaf (8 x 2048 x
1792) of `lfm2-8b-a1b-ep4`; the samples, `m` x `c1_sample` float32, go to the
host. Seconds on the v5e: in PERF.md section 6 (PR 27)."""

from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference import server_step as ref_server

PHASE = "server"
PARAM_SCALE, UPDATE_SCALE = 0.1, 0.01
EPS32 = float(np.finfo(np.float32).eps)


def _leaf_fn(cfg, shape, m, sample):
    from defending_against_backdoors_with_robust_learning_rate_tpu.ops import (
        aggregate)
    threshold = float(cfg.robustLR_threshold)
    n = int(np.prod(shape))
    take = min(sample, n)

    @jax.jit
    def run(key):
        kp, ku, ks, ki = jax.random.split(key, 4)
        p = PARAM_SCALE * jax.random.normal(kp, shape, jnp.float32)
        sizes = jax.random.randint(ks, (m,), 3000, 9000, jnp.int32)
        idx = (jnp.arange(n) if take == n else
               jax.random.randint(ki, (take,), 0, n))

        def body(acc, xs):
            k, size = xs
            u = UPDATE_SCALE * jax.random.normal(k, shape, jnp.float32)
            acc = aggregate.fold_updates(acc, {"x": u[None]}, size[None])
            return acc, u.reshape(-1)[idx]

        acc, seen = jax.lax.scan(
            body, aggregate.fold_init({"x": p}, m, cfg.aggr == "avg",
                                      threshold > 0 or cfg.aggr == "sign"),
            (jax.random.split(ku, m), sizes))
        lr, agg = aggregate.fold_finish(
            acc, cfg, key, threshold if threshold > 0 else None,
            float(cfg.effective_server_lr))
        new = aggregate.apply_aggregate({"x": p}, lr, agg)["x"]
        lr_s = (lr["x"].reshape(-1)[idx] if threshold > 0
                else jnp.full((take,), lr, jnp.float32))
        return (p.reshape(-1)[idx], seen, sizes, lr_s, new.reshape(-1)[idx],
                jnp.max(jnp.abs(new)))

    return run


def run(ctx) -> Dict[str, Any]:
    cfg, tol = ctx["cfg"], ctx["config"]["check"]
    m = cfg.agents_per_round
    threshold = float(cfg.robustLR_threshold)
    server_lr = float(cfg.effective_server_lr)
    shapes = sorted({tuple(x.shape) for x in
                     jax.tree_util.tree_leaves(ctx["params"])})
    leaves = [tuple(x.shape) for x in
              jax.tree_util.tree_leaves(ctx["params"])]
    fns = {s: _leaf_fn(cfg, s, m, int(tol["c1_sample"])) for s in shapes}
    key = jax.random.PRNGKey(ctx["seed"])
    mismatched, worst, coords, folded = 0, 0.0, 0, 0
    for i, shape in enumerate(leaves):
        p, seen, sizes, lr, new, scale = (
            np.asarray(x) for x in jax.device_get(
                fns[shape](jax.random.fold_in(key, i))))
        lr_ref, new_ref = ref_server.server_step(p, seen, sizes, threshold,
                                                 server_lr)
        mismatched += int(np.count_nonzero(lr != lr_ref.astype(np.float32)))
        worst = max(worst, float(np.max(np.abs(new - new_ref)))
                    / (EPS32 * (float(scale) or 1.0)))
        coords += p.size
        folded += int(np.prod(shape))
    limit = float(tol["c1_ulps"])
    return {"ok": mismatched == 0 and worst <= limit and bool(
                np.isfinite(worst)),
            "lr_mismatched": mismatched, "ulps_of_leaf_scale": worst,
            "coordinates_compared": coords, "coordinates_folded": folded,
            "leaves": len(leaves), "agents": m,
            "compared": {"lr_mismatched": [mismatched, 0],
                         "ulps_of_leaf_scale": [worst, limit]}}


def contract(cfg, config) -> None:
    """The reference transcribes the sign vote and FedAvg and nothing
    else, and the fold's sign sum is exact in int8 up to 127 clients."""
    assert cfg.aggr == "avg" and cfg.noise == 0
    assert cfg.agents_per_round == config["agents"] <= 127
    assert config["check"]["c1_sample"] > 0 and config["check"]["c1_ulps"] > 0
