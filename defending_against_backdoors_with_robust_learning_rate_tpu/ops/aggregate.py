"""Server aggregation rules + the robust-learning-rate (RLR) defense.

Reference: src/aggregation.py. Updates arrive stacked on a leading agent axis
(`[m, ...]` per pytree leaf) instead of a Python dict of flat vectors
(src/federated.py:67-74); every rule is a `tree_map`ped reduction over axis 0,
which XLA lowers to the same math the flat-vector version computes.

- `robust_lr`   (src/aggregation.py:48-54): per coordinate,
    s = |sum_k sign(u_k)|; lr = +server_lr where s >= threshold else -server_lr.
  The vote is unweighted and runs over exactly the sampled agents
  (SURVEY.md 2.3.5) — callers pass the m sampled updates, so the effective
  vote count matches the reference's per-round participant count.
- `agg_avg`     (src/aggregation.py:57-64): data-size-weighted mean.
- `agg_comed`   (src/aggregation.py:66-69): per-coordinate median over agents.
- `agg_sign`    (src/aggregation.py:71-75): sign of the sum of signs (the
  reference double-signs; idempotent, SURVEY.md 2.3.6).
- `agg_krum`    : NOT in the reference (avg/comed/sign only) — required by
  BASELINE.json configs[4]; standard Krum (Blanchard et al., NeurIPS 2017):
  each update scores the sum of its m-f-2 smallest squared distances to the
  others; the minimizer is returned.
- `agg_rfa`     : NOT in the reference — geometric median via smoothed
  Weiszfeld (RFA, Pillutla et al., IEEE TSP 2022), the standard
  aggregation-robustness baseline alongside trmean/krum.
- server noise  (src/aggregation.py:34-35): N(0, noise*clip) added to the
  aggregate.
- `apply_aggregate` (src/aggregation.py:38-40): global += lr ⊙ aggregate.

Precision: the reference accumulates in float64 (src/agent.py:63); TPU has no
fast f64, we use f32 throughout (documented divergence, SURVEY.md 2.3.2).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from defending_against_backdoors_with_robust_learning_rate_tpu.ops import tree


def rlr_from_sign_sum(sign_sum, threshold, server_lr):
    """The RLR vote decision from a (raw or absolute) sign-sum array:
    +server_lr per coordinate where |sum_k sign(u_k)| >= threshold, else
    -server_lr (src/aggregation.py:48-54). THE single source of the vote
    arithmetic — shared by the vmap tree path (`robust_lr`) and the
    sharded per-leaf psum paths (parallel/rounds.py) — so both threshold
    identically.
    `threshold` may be a traced scalar (the mask-aware scaled value)."""
    return jnp.where(jnp.abs(sign_sum) >= threshold, server_lr,
                     -server_lr).astype(jnp.float32)


def robust_lr(stacked_updates, threshold, server_lr: float, mask=None):
    """Per-parameter learning-rate tree: +server_lr where the sign-agreement
    vote reaches `threshold`, else -server_lr (src/aggregation.py:48-54).

    With a participation `mask` ([m] bool, faults/masking.py) only masked-in
    agents vote (their rows are zeroed, contributing sign 0); `threshold`
    may then be a traced scalar (the mask-aware scaled threshold)."""
    if mask is not None:
        from defending_against_backdoors_with_robust_learning_rate_tpu.faults import (
            masking)
        stacked_updates = masking.zero_masked(stacked_updates, mask)

    def leaf(u):
        return rlr_from_sign_sum(jnp.sum(jnp.sign(u), axis=0), threshold,
                                 server_lr)
    return tree.map(leaf, stacked_updates)


def agg_avg(stacked_updates, data_sizes, mask=None):
    """Weighted FedAvg: sum_k n_k u_k / sum_k n_k (src/aggregation.py:57-64)."""
    if mask is not None:
        from defending_against_backdoors_with_robust_learning_rate_tpu.faults import (
            masking)
        return masking.masked_avg(stacked_updates, data_sizes, mask)
    w = data_sizes.astype(jnp.float32)
    total = jnp.sum(w)

    def leaf(u):
        wshape = (-1,) + (1,) * (u.ndim - 1)
        return jnp.sum(u * w.reshape(wshape), axis=0) / total
    return tree.map(leaf, stacked_updates)


def agg_comed(stacked_updates, mask=None):
    """Per-coordinate median over the agent axis (src/aggregation.py:66-69).

    With an even agent count this matches torch.median (lower of the two
    middle values), NOT numpy's midpoint interpolation."""
    if mask is not None:
        from defending_against_backdoors_with_robust_learning_rate_tpu.faults import (
            masking)
        return masking.masked_comed(stacked_updates, mask)
    m = jax.tree_util.tree_leaves(stacked_updates)[0].shape[0]

    def leaf(u):
        srt = jnp.sort(u, axis=0)
        return srt[(m - 1) // 2]
    return tree.map(leaf, stacked_updates)


def agg_sign(stacked_updates, mask=None):
    """Majority-sign update: sign(sum_k sign(u_k)) (src/aggregation.py:71-75)."""
    if mask is not None:
        from defending_against_backdoors_with_robust_learning_rate_tpu.faults import (
            masking)
        return masking.masked_sign(stacked_updates, mask)
    return tree.map(lambda u: jnp.sign(jnp.sum(jnp.sign(u), axis=0)),
                    stacked_updates)


def sq_dist_accum(dist, flat):
    """dist [m, m] += pairwise squared L2 distances of the rows of flat
    [m, c] (sq-norm expansion; callers clamp negatives after the last
    accumulation)."""
    flat = flat.astype(jnp.float32)
    sq = jnp.sum(flat * flat, axis=1)
    return dist + sq[:, None] + sq[None, :] - 2.0 * (flat @ flat.T)


def _pairwise_sq_dists(stacked_updates):
    """[m, m] matrix of squared L2 distances summed across all leaves."""
    leaves = jax.tree_util.tree_leaves(stacked_updates)
    m = leaves[0].shape[0]
    d = jnp.zeros((m, m), jnp.float32)
    for u in leaves:
        d = sq_dist_accum(d, u.reshape(m, -1))
    return jnp.maximum(d, 0.0)


def trmean_k(trim_k: int, m: int) -> int:
    """Clamp the per-end trim count so at least one value survives; shared
    by the vmap and sharded trmean paths (their parity depends on it)."""
    return max(0, min(int(trim_k), (m - 1) // 2))


def agg_trmean(stacked_updates, trim_k: int, mask=None):
    """Coordinate-wise trimmed mean: drop the trim_k smallest and largest
    values per coordinate, average the rest (framework extension; standard
    robust aggregation, Yin et al. 2018 — not in the reference, which has
    avg/comed/sign only). trim_k is clamped so at least one value remains;
    trim_k=0 degrades to the unweighted mean."""
    if mask is not None:
        from defending_against_backdoors_with_robust_learning_rate_tpu.faults import (
            masking)
        return masking.masked_trmean(stacked_updates, mask, trim_k)
    m = jax.tree_util.tree_leaves(stacked_updates)[0].shape[0]
    k = trmean_k(trim_k, m)

    def leaf(u):
        srt = jnp.sort(u, axis=0)
        return jnp.mean(srt[k:m - k], axis=0)
    return tree.map(leaf, stacked_updates)


def agg_krum(stacked_updates, num_corrupt: int = 0, mask=None):
    """Krum: select the update with the smallest sum of its m-f-2 nearest
    squared distances (framework extension; BASELINE.json configs[4])."""
    if mask is not None:
        from defending_against_backdoors_with_robust_learning_rate_tpu.faults import (
            masking)
        return masking.masked_krum(stacked_updates, mask, num_corrupt)
    d = _pairwise_sq_dists(stacked_updates)
    m = d.shape[0]
    k = max(m - num_corrupt - 2, 1)
    # distance to self is 0 and sorts first; take the next k columns
    srt = jnp.sort(d, axis=1)
    scores = jnp.sum(srt[:, 1:k + 1], axis=1)
    best = jnp.argmin(scores)
    return tree.map(lambda u: u[best], stacked_updates)


RFA_ITERS = 4       # fixed smoothed-Weiszfeld iterations (static for jit;
                    # the RFA paper reports 3-4 suffice to near-converge)
RFA_EPS = 1e-6      # smoothing floor on per-agent distances


def agent_sq_dists(stacked_updates, center):
    """[m] squared L2 distance of each stacked update to the `center` tree,
    summed across all leaves (shared by the vmap and sharded RFA paths)."""
    per_leaf = jax.tree_util.tree_leaves(tree.map(
        lambda u, c: jnp.sum(
            jnp.square(u.astype(jnp.float32) - c[None].astype(jnp.float32)),
            axis=tuple(range(1, u.ndim))),
        stacked_updates, center))
    total = per_leaf[0]
    for x in per_leaf[1:]:
        total = total + x
    return total


def agg_rfa(stacked_updates, iters: int = RFA_ITERS, eps: float = RFA_EPS,
            mask=None):
    """Geometric median of the updates via the smoothed Weiszfeld algorithm
    (RFA, Pillutla et al., IEEE TSP 2022 — framework extension; the
    reference ships avg/comed/sign only, src/aggregation.py:57-75).

    Starts from the unweighted mean; each of the `iters` fixed iterations
    reweights agents by 1/max(||u_k - v||, eps) and recomputes the weighted
    mean. Fixed iteration count keeps the compiled program static."""
    if mask is not None:
        from defending_against_backdoors_with_robust_learning_rate_tpu.faults import (
            masking)
        return masking.masked_rfa(stacked_updates, mask, iters, eps)
    v = tree.map(lambda u: jnp.mean(u.astype(jnp.float32), axis=0),
                 stacked_updates)
    for _ in range(iters):
        w = 1.0 / jnp.maximum(jnp.sqrt(agent_sq_dists(stacked_updates, v)),
                              eps)
        wsum = jnp.sum(w)

        def leaf(u, w=w, wsum=wsum):
            wshape = (-1,) + (1,) * (u.ndim - 1)
            return jnp.sum(u * w.reshape(wshape), axis=0) / wsum
        v = tree.map(leaf, stacked_updates)
    return v


def gaussian_noise_like(params_like, key, std: float):
    """Server DP noise N(0, std) per coordinate (src/aggregation.py:34-35)."""
    leaves, treedef = jax.tree_util.tree_flatten(params_like)
    keys = jax.random.split(key, len(leaves))
    noisy = [jax.random.normal(k, x.shape, jnp.float32) * std
             for k, x in zip(keys, leaves, strict=True)]
    return jax.tree_util.tree_unflatten(treedef, noisy)


def aggregate_updates(stacked_updates, data_sizes, cfg, key, mask=None):
    """Dispatch on cfg.aggr + optional noise (src/aggregation.py:26-35).

    `mask` ([m] bool participation mask, faults/masking.py) routes every
    rule through its masked variant; None is the dense path, bit-for-bit
    the pre-faults behavior."""
    if mask is not None:
        from defending_against_backdoors_with_robust_learning_rate_tpu.faults import (
            masking)
        agg = masking.masked_aggregate(stacked_updates, data_sizes, cfg, mask)
    elif cfg.aggr == "avg":
        agg = agg_avg(stacked_updates, data_sizes)
    elif cfg.aggr == "comed":
        agg = agg_comed(stacked_updates)
    elif cfg.aggr == "sign":
        agg = agg_sign(stacked_updates)
    elif cfg.aggr == "trmean":
        agg = agg_trmean(stacked_updates, cfg.num_corrupt)
    elif cfg.aggr == "krum":
        agg = agg_krum(stacked_updates, cfg.num_corrupt)
    elif cfg.aggr == "rfa":
        agg = agg_rfa(stacked_updates)
    else:
        raise ValueError(f"unknown aggr {cfg.aggr!r}")
    if cfg.noise > 0:
        agg = tree.add(agg, gaussian_noise_like(agg, key,
                                                cfg.noise * cfg.clip))
    return agg


# ---- the fold: the sum-shaped rules without the [m, ...] stack -----------
#
# avg, sign and the RLR vote are sums over clients, so a round may add each
# client's update to three accumulators as it arrives (weighted sum, sign
# sum, weight total) and never hold the stack. The accumulators are float32
# like the stack's reductions; chunks add in arrival order, so a folded
# round agrees with the stacked one to float32 round-off of the sums and
# exactly in the vote. The sign sum of up to 127 clients is held in int8 (a
# byte a parameter where float32 takes four; exact, since |sum| <= m).
SIGN_SUM_INT8_MAX_AGENTS = 127


def fold_init(params, agents: int, want_avg: bool, want_sign: bool):
    """Zeroed accumulators shaped like `params` for a round of `agents`
    clients; a rule that needs no weighted sum (sign) or no sign sum (avg
    without RLR) carries None."""
    sdt = jnp.int8 if agents <= SIGN_SUM_INT8_MAX_AGENTS else jnp.float32

    def zeros(dtype):
        return tree.map(lambda p: jnp.zeros(p.shape, dtype), params)
    return {"wsum": zeros(jnp.float32) if want_avg else None,
            "ssum": zeros(sdt) if want_sign else None,
            "n": jnp.float32(0.0)}


def fold_updates(acc, stacked_updates, data_sizes):
    """Add a chunk of clients ([c, ...] per leaf, sizes [c]) to the
    accumulators."""
    w = data_sizes.astype(jnp.float32)

    def wleaf(a, u):
        return a + jnp.sum(u * w.reshape((-1,) + (1,) * (u.ndim - 1)),
                           axis=0)
    return {
        "wsum": (None if acc["wsum"] is None
                 else tree.map(wleaf, acc["wsum"], stacked_updates)),
        "ssum": (None if acc["ssum"] is None
                 else tree.map(
                     lambda a, u: a + jnp.sum(jnp.sign(u), axis=0).astype(
                         a.dtype), acc["ssum"], stacked_updates)),
        "n": acc["n"] + jnp.sum(w)}


def fold_finish(acc, cfg, key, threshold, server_lr):
    """(lr tree or scalar, aggregate) from the accumulators: the same tail
    as the stacked round's `robust_lr` + `aggregate_updates`."""
    lr = (tree.map(lambda s: rlr_from_sign_sum(s, threshold, server_lr),
                   acc["ssum"]) if threshold is not None else server_lr)
    if cfg.aggr == "avg":
        agg = tree.map(lambda s: s / acc["n"], acc["wsum"])
    elif cfg.aggr == "sign":
        agg = tree.map(lambda s: jnp.sign(s).astype(jnp.float32),
                       acc["ssum"])
    else:
        raise ValueError(f"aggr {cfg.aggr!r} is not a sum over clients")
    if cfg.noise > 0:
        agg = tree.add(agg, gaussian_noise_like(agg, key,
                                                cfg.noise * cfg.clip))
    return lr, agg


def apply_aggregate(params, lr_tree_or_scalar, aggregated):
    """global <- global + lr ⊙ aggregate, f32 (src/aggregation.py:38-40)."""
    lr = lr_tree_or_scalar
    if isinstance(lr, (int, float)) or (hasattr(lr, "ndim") and lr.ndim == 0):
        new = tree.map(lambda p, a: p + lr * a, params, aggregated)
    else:
        new = tree.map(lambda p, l, a: p + l * a, params, lr, aggregated)
    return tree.astype(new, jnp.float32)
