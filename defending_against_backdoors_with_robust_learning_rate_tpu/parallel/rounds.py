"""Sharded FL round: `shard_map` over the `agents` mesh axis.

This is the distributed-communication backend the reference lacks entirely
(SURVEY.md 2.2: no torch.distributed/NCCL/MPI — updates travel as an
in-process Python dict, src/federated.py:67-74). Mapping, per SURVEY.md
section 5.8:

    agg_avg          -> psum of locally-weighted sums            (ICI)
    agg_sign / RLR   -> psum of per-coordinate sign sums         (ICI)
    agg_comed        -> all_to_all transpose to param-sharded layout,
                        local median, all_gather of median chunks
    agg_trmean       -> same transpose, local sort + trimmed-band mean
    agg_krum         -> all_to_all transpose, chunk-partial pairwise
                        distances psummed to the full [m, m] matrix,
                        winner's chunks re-assembled by all_gather
    agg_rfa          -> replicated Weiszfeld iterate; two psums per
                        iteration (local-block distances, no transpose)

comed/krum deliberately avoid `all_gather`ing the full [m, n_params]
update matrix (SURVEY.md 7.3.1: ~1 GiB/device at 256 agents x 1M params).
The `all_to_all` transpose repurposes the mesh axis from agents to
parameter chunks: each device ends up holding ALL m agents for 1/d of the
coordinates — memory AND interconnect traffic drop by the mesh factor d,
and the median/distance arithmetic is d-way parallel instead of
replicated.

Every device trains its block of m/d sampled agents (local `vmap`), then the
collective aggregation produces *replicated* new global params — one compiled
program per round, no host round-trips. Parity with the single-device vmap
path is asserted in tests/test_parallel.py on a faked 8-device CPU mesh.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from defending_against_backdoors_with_robust_learning_rate_tpu.fl import (
    buffered)
from defending_against_backdoors_with_robust_learning_rate_tpu.fl.rounds import (
    bind_data, make_block_trainer, make_chained)
from defending_against_backdoors_with_robust_learning_rate_tpu.health import (
    sentinel as health_sentinel)
from defending_against_backdoors_with_robust_learning_rate_tpu.ops import tree
from defending_against_backdoors_with_robust_learning_rate_tpu.ops.aggregate import (
    RFA_EPS, RFA_ITERS, agent_sq_dists, apply_aggregate, gaussian_noise_like,
    rlr_from_sign_sum, sq_dist_accum, trmean_k)
from defending_against_backdoors_with_robust_learning_rate_tpu.parallel import (
    buckets)
from defending_against_backdoors_with_robust_learning_rate_tpu.parallel.mesh import (
    AGENTS_AXIS)


def _to_param_shards(u, d):
    """[m/d, ...] local agent block -> ([m, c] all agents x local param chunk,
    flat length L). The all_to_all transposes the mesh axis from agents to
    parameter chunks; rows arrive in device order = global agent order."""
    mb = u.shape[0]
    flat = u.reshape(mb, -1)
    L = flat.shape[1]
    pad = -L % d
    if pad:
        flat = jnp.pad(flat, ((0, 0), (0, pad)))
    return jax.lax.all_to_all(flat, AGENTS_AXIS, split_axis=1, concat_axis=0,
                              tiled=True), L


def _from_param_shard(chunk, L, leaf_shape):
    """[c] local param chunk -> [...] full replicated leaf (all_gather)."""
    full = jax.lax.all_gather(chunk, AGENTS_AXIS, axis=0, tiled=True)
    return full[:L].reshape(leaf_shape)


def _sharded_aggregate(updates, sizes, cfg, d, key, mask_local=None,
                       mask_full=None, out=None):
    """Aggregation rules as collectives. `updates` leaves are the local block
    [m/d, ...]; `d` is the mesh size; returns the replicated aggregate.

    The faults path passes the participation mask twice: `mask_local`
    ([m/d] bool, this device's agent block) zeroes local rows/weights
    before the psums, and `mask_full` ([m] bool, replicated — every device
    derives the identical draw from the replicated fault key) drives the
    sentinel/index arithmetic on the all_to_all-transposed [m, c] chunks.
    None/None is the dense path, bit-for-bit the pre-faults behavior.

    `out` (optional dict): the sign branch stashes its raw per-leaf
    sign-sum psum results under ``"sign_sums"`` — the reputation lane
    (obs/reputation.py) re-reads the existing collective instead of
    issuing its own (the `_sharded_sign_shared` sharing discipline for
    the thresholdless sign aggregate)."""
    ax = AGENTS_AXIS
    masked = mask_local is not None
    if masked:
        from defending_against_backdoors_with_robust_learning_rate_tpu.faults import (
            masking)
        n_eff = masking.count(mask_full)
    if cfg.aggr == "avg":
        w = sizes.astype(jnp.float32)
        if masked:
            w = jnp.where(mask_local, w, 0.0)
            updates = masking.zero_masked(updates, mask_local)
        total = jax.lax.psum(jnp.sum(w), ax)

        def leaf(u):
            wshape = (-1,) + (1,) * (u.ndim - 1)
            return jax.lax.psum(jnp.sum(u * w.reshape(wshape), axis=0),
                                ax) / total
        agg = tree.map(leaf, updates)
    elif cfg.aggr == "sign":
        if masked:
            # zeroed rows vote sign(0) = 0 in the psum
            updates = masking.zero_masked(updates, mask_local)
        sums = tree.map(
            lambda u: jax.lax.psum(jnp.sum(jnp.sign(u), axis=0), ax),
            updates)
        if out is not None:
            out["sign_sums"] = sums
        agg = tree.map(jnp.sign, sums)
    elif cfg.aggr == "comed":
        m = cfg.agents_per_round

        def leaf(u):
            chunk, L = _to_param_shards(u, d)            # [m, c]
            if masked:
                med = masking.median_rows(chunk, mask_full, n_eff)
            else:
                med = jnp.sort(chunk, axis=0)[(m - 1) // 2]  # lower median
            return _from_param_shard(med, L, u.shape[1:])
        agg = tree.map(leaf, updates)
    elif cfg.aggr == "trmean":
        # coordinate-wise trimmed mean rides the same param-sharded
        # transpose as comed: sort the [m, c] chunk, mean the untrimmed
        # middle band (ops/aggregate.agg_trmean semantics)
        m = cfg.agents_per_round
        k = trmean_k(cfg.num_corrupt, m)

        def leaf(u):
            chunk, L = _to_param_shards(u, d)            # [m, c]
            if masked:
                band_mean = masking.trimmed_mean_rows(
                    chunk, mask_full, n_eff, cfg.num_corrupt)
            else:
                band_mean = jnp.mean(jnp.sort(chunk, axis=0)[k:m - k], axis=0)
            return _from_param_shard(band_mean, L, u.shape[1:])
        agg = tree.map(leaf, updates)
    elif cfg.aggr == "krum":
        m = cfg.agents_per_round
        if masked:
            # garbage payloads must not poison the distance matrix
            updates = masking.zero_masked(updates, mask_local)
        leaves, treedef = jax.tree_util.tree_flatten(updates)
        shards = [_to_param_shards(u, d) for u in leaves]
        # chunk-partial pairwise squared distances; psum over the mesh axis
        # (now indexing param chunks) completes the sum over coordinates
        dist = jnp.zeros((m, m), jnp.float32)
        for chunk, _ in shards:
            dist = sq_dist_accum(dist, chunk)
        dist = jnp.maximum(jax.lax.psum(dist, ax), 0.0)
        if masked:
            best = masking.krum_best(dist, mask_full, n_eff, cfg.num_corrupt)
        else:
            k = max(m - cfg.num_corrupt - 2, 1)
            srt = jnp.sort(dist, axis=1)
            best = jnp.argmin(jnp.sum(srt[:, 1:k + 1], axis=1))
        agg = jax.tree_util.tree_unflatten(treedef, [
            _from_param_shard(chunk[best], L, u.shape[1:])
            for (chunk, L), u in zip(shards, leaves, strict=True)])
    elif cfg.aggr == "rfa":
        # geometric median (smoothed Weiszfeld, ops/aggregate.agg_rfa
        # semantics): the iterate v is replicated; per-agent distances are
        # computed on each device's local block, so every iteration costs
        # exactly two psums (weighted sum + weight total) over ICI — no
        # transpose needed
        m = cfg.agents_per_round
        if masked:
            updates = masking.zero_masked(updates, mask_local)
            # reciprocal-multiply matches the dense divide-by-constant
            # after XLA strength reduction (faults/masking.py)
            denom = 1.0 / masking.count_f32(mask_full)
            w_base = mask_local.astype(jnp.float32)
        else:
            denom = 1.0 / m
            w_base = 1.0
        v = tree.map(
            lambda u: jax.lax.psum(jnp.sum(u.astype(jnp.float32), axis=0),
                                   ax) * denom, updates)
        for _ in range(RFA_ITERS):
            w = w_base / jnp.maximum(jnp.sqrt(agent_sq_dists(updates, v)),
                                     RFA_EPS)
            wsum = jax.lax.psum(jnp.sum(w), ax)

            def leaf(u, w=w, wsum=wsum):
                wshape = (-1,) + (1,) * (u.ndim - 1)
                return jax.lax.psum(
                    jnp.sum(u * w.reshape(wshape), axis=0), ax) / wsum
            v = tree.map(leaf, updates)
        agg = v
    else:
        raise ValueError(f"unknown aggr {cfg.aggr!r}")
    if cfg.noise > 0:
        # key is replicated across devices -> identical noise everywhere
        agg = tree.add(agg, gaussian_noise_like(agg, key,
                                                cfg.noise * cfg.clip))
    if masked:
        # all payloads dropped/rejected -> zero aggregate (noise included),
        # making the round a full no-op — matches the vmap path's guard
        agg = masking.guard_empty(agg, mask_full)
    return agg


def _sharded_sign_shared(updates, cfg, noise_key, mask_local=None,
                         mask_full=None, knobs=None):
    """aggr='sign' + RLR: ONE sign-sum psum per leaf, read twice — the
    vote takes |s| and the aggregate takes sign(s).

    The code used to issue the two textually-identical psums and rely on
    XLA CSE to merge them; the jaxpr contract checker measured that the
    partitioned all-reduces (distinct channel ids) never CSE — 20
    all-reduces where the plan promises 12 (analysis_baseline.json,
    sharded_rlr_sign). Sharing the collective here makes the documented
    budget true by construction; values are bit-identical (same
    arithmetic, same order). Returns (lr_tree, agg_tree, sign_sums_tree)
    with server noise + empty-electorate guard applied, mirroring
    _sharded_aggregate's tail; `sign_sums` is the raw per-leaf psum
    result, handed to full telemetry so its vote-margin histogram reads
    the SAME collective instead of issuing a third copy per leaf.
    `knobs` (fl/tenancy.TenantKnobs scalars, inside the tenant vmap)
    overrides the threshold/server-lr constants per tenant."""
    thr = (float(cfg.robustLR_threshold) if knobs is None
           else knobs.rlr_threshold)
    if mask_local is not None:
        from defending_against_backdoors_with_robust_learning_rate_tpu.faults import (
            masking)
        updates = masking.zero_masked(updates, mask_local)
        thr = masking.rlr_threshold(
            cfg, mask_full,
            base=None if knobs is None else knobs.rlr_threshold)
    slr = cfg.effective_server_lr if knobs is None else knobs.server_lr
    leaves, treedef = jax.tree_util.tree_flatten(updates)
    lr_leaves, agg_leaves, s_leaves = [], [], []
    for u in leaves:
        s = jax.lax.psum(jnp.sum(jnp.sign(u), axis=0), AGENTS_AXIS)
        lr_leaves.append(rlr_from_sign_sum(s, thr, slr))
        agg_leaves.append(jnp.sign(s))
        s_leaves.append(s)
    lr = jax.tree_util.tree_unflatten(treedef, lr_leaves)
    agg = jax.tree_util.tree_unflatten(treedef, agg_leaves)
    sign_sums = jax.tree_util.tree_unflatten(treedef, s_leaves)
    if cfg.noise > 0:
        agg = tree.add(agg, gaussian_noise_like(agg, noise_key,
                                                cfg.noise * cfg.clip))
    if mask_local is not None:
        from defending_against_backdoors_with_robust_learning_rate_tpu.faults import (
            masking)
        agg = masking.guard_empty(agg, mask_full)
    return lr, agg, sign_sums


def _sharded_robust_lr(updates, cfg, mask_local=None, mask_full=None,
                       knobs=None):
    """RLR sign-agreement vote as a psum (src/aggregation.py:48-54 semantics,
    vote over exactly the m sampled agents — minus masked-out voters on the
    faults path, where the threshold may also scale with the electorate).
    Returns (lr_tree, sign_sums_tree): the RAW signed per-leaf psums —
    `rlr_from_sign_sum` takes |s| internally and full telemetry's margin
    histogram takes |s| at the read site, so handing the raw sums out is
    value-identical to the historical |psum| hand-off while ALSO carrying
    the vote's direction, which the reputation lane (obs/reputation.py)
    compares per-client updates against. Zero extra psums either way
    (the same sharing `_sharded_sign_shared` does for the sign
    aggregate). `knobs` overrides the threshold/server-lr constants per
    tenant (fl/tenancy.py)."""
    thr = (float(cfg.robustLR_threshold) if knobs is None
           else knobs.rlr_threshold)
    if mask_local is not None:
        from defending_against_backdoors_with_robust_learning_rate_tpu.faults import (
            masking)
        updates = masking.zero_masked(updates, mask_local)
        thr = masking.rlr_threshold(
            cfg, mask_full,
            base=None if knobs is None else knobs.rlr_threshold)
    slr = cfg.effective_server_lr if knobs is None else knobs.server_lr
    leaves, treedef = jax.tree_util.tree_flatten(updates)
    lr_leaves, s_leaves = [], []
    for u in leaves:
        s = jax.lax.psum(jnp.sum(jnp.sign(u), axis=0), AGENTS_AXIS)
        lr_leaves.append(rlr_from_sign_sum(s, thr, slr))
        s_leaves.append(s)
    return (jax.tree_util.tree_unflatten(treedef, lr_leaves),
            jax.tree_util.tree_unflatten(treedef, s_leaves))


def _bucket_applicable(cfg) -> bool:
    """The bucketed reduce-scatter layout covers the psum-shaped rules
    (weighted FedAvg and signSGD, RLR on or off — the paper's headline
    configurations). The transpose rules (comed/trmean/krum) already run
    few large collectives (all_to_all + all_gather) and keep their plan;
    rfa's replicated Weiszfeld iterate keeps its per-iteration psums.
    Diagnostics need the full lr tree materialized, which the scattered
    vote never builds — `_build_sharded_body` refuses that combination
    loudly rather than silently mixing layouts across snap rounds."""
    return cfg.agg_layout == "bucket" and cfg.aggr in ("avg", "sign")


class _BucketInfo:
    """What the bucketed apply hands to telemetry: the post-noise/guard
    aggregate tree (full level only — reassembled from the same
    all_gather that carried the LR-scaled result), the globally-summed
    vote/flip stats vector that rode that gather (obs/telemetry.py
    shard_vote_stats; None when telemetry is off), the real (unpadded)
    coordinate count, and — when the reputation lane is on — this
    device's [m/d] rep_agree block (obs/reputation.py, computed against
    the full sign vote whose shard rode the same gather) plus its [m/d]
    rep_norm block (local: the flat block holds full coordinate rows)."""

    def __init__(self, agg=None, stats=None, total_coords=0,
                 rep_agree=None, rep_norm=None):
        self.agg = agg
        self.stats = stats
        self.total_coords = total_coords
        self.rep_agree = rep_agree
        self.rep_norm = rep_norm


def _bucketed_apply(params, updates, sizes, cfg, noise_key, d,
                    mask_local=None, mask_full=None, knobs=None):
    """avg/sign [+ RLR] aggregation on the bucketed flat layout
    (parallel/buckets.py): ONE reduce-scatter per bucket of the stacked
    partial sums (weighted sum and/or sign sum ride the SAME collective),
    the masked weighted-average AND the RLR sign-vote computed on the
    scattered shard, then ONE all_gather of the already-LR-scaled result.
    Collectives on the flagship (1 bucket): reduce-scatter + all-gather
    (+ the scalar weight-total psum for avg) — vs 2L+2 = 18 per-leaf
    psums on the leaf layout.

    Per-coordinate arithmetic is IDENTICAL to the leaf path (the flatten
    is a relayout, the local partial sums run over the same mb rows in
    the same order, noise is generated per leaf with the same key split,
    the empty-electorate guard multiplies the same replicated flag), so
    bucket-vs-leaf parity is pinned bitwise in fp32
    (tests/test_bucket_parity.py). Padding coordinates are explicit
    zeros: they vote margin 0 (=> lr -slr), aggregate 0, and are masked
    out of every statistic via `shard_coord_index`.

    Returns (new_params, _BucketInfo)."""
    ax = AGENTS_AXIS
    masked = mask_local is not None
    rlr = cfg.robustLR_threshold > 0
    thr = (float(cfg.robustLR_threshold) if knobs is None
           else knobs.rlr_threshold)
    if masked:
        from defending_against_backdoors_with_robust_learning_rate_tpu.faults import (
            masking)
        updates = masking.zero_masked(updates, mask_local)
        if rlr:
            thr = masking.rlr_threshold(
                cfg, mask_full,
                base=None if knobs is None else knobs.rlr_threshold)
    slr = cfg.effective_server_lr if knobs is None else knobs.server_lr
    layout = buckets.layout_for_stacked(updates, d)
    flat = buckets.flatten_stacked(layout, updates)       # [mb, padded]

    # the full level reads vote margins even without RLR (the leaf path
    # budgets its own per-leaf psums for that; here the sign sums ride
    # the one reduce-scatter for free)
    want_sign = rlr or cfg.aggr == "sign" or cfg.telemetry == "full"
    rows = []
    total = None
    if cfg.aggr == "avg":
        w = sizes.astype(jnp.float32)
        if masked:
            w = jnp.where(mask_local, w, 0.0)
        total = jax.lax.psum(jnp.sum(w), ax)              # scalar psum
        rows.append(jnp.sum(flat * w[:, None], axis=0))
    if want_sign:
        rows.append(jnp.sum(jnp.sign(flat), axis=0))
    stacked = jnp.stack(rows)                             # [r, padded]
    # one reduce-scatter per bucket; both quantities share each collective
    scat = jnp.concatenate([
        jax.lax.psum_scatter(
            stacked[:, b * layout.bucket:(b + 1) * layout.bucket],
            ax, scatter_dimension=1, tiled=True)
        for b in range(layout.n_buckets)], axis=1)        # [r, device_len]

    sign_s = scat[-1] if want_sign else None
    if cfg.aggr == "avg":
        agg_s = scat[0] / total
    else:
        agg_s = jnp.sign(sign_s)
    if cfg.noise > 0:
        # generated per leaf from the identical key split as the leaf
        # path (gaussian_noise_like over the same tree structure), then
        # relayed out through the flat space — bitwise the same noise
        noise = gaussian_noise_like(params, noise_key,
                                    cfg.noise * cfg.clip)
        pos = jax.lax.axis_index(ax)
        agg_s = agg_s + buckets.device_shard(
            layout, buckets.flatten_tree(layout, noise), pos)
    if masked:
        agg_s = masking.guard_empty(agg_s, mask_full)
    if rlr:
        lr_s = rlr_from_sign_sum(sign_s, thr, slr)
    else:
        lr_s = None
    delta_s = (lr_s if lr_s is not None else slr) * agg_s

    # ONE all_gather carries the LR-scaled result — plus, under
    # telemetry, the unscaled aggregate (full: the cosine split needs
    # the replicated agg tree) and the tiny vote/flip stats vector
    # (basic/full: summed across devices after the gather), so telemetry
    # adds ZERO collectives here
    from defending_against_backdoors_with_robust_learning_rate_tpu.obs import (
        reputation as rep_mod)
    rep_on = rep_mod.reputation_on(cfg)
    payload = [delta_s]
    stats_len = 0
    if cfg.telemetry == "full":
        payload.append(agg_s)
    if rep_on:
        # the reputation lane needs the FULL signed vote replicated to
        # compare each local client block against — the sign-sum shard
        # rides the SAME result all_gather (a widened payload, never a
        # new collective; the *_rep CheckSpecs pin the unchanged plan)
        payload.append(sign_s)
    if cfg.telemetry != "off":
        from defending_against_backdoors_with_robust_learning_rate_tpu.obs import (
            telemetry)
        pos = jax.lax.axis_index(ax)
        real = buckets.shard_coord_index(layout, pos) < layout.total
        stats = telemetry.shard_vote_stats(cfg, sign_s, real, lr_s,
                                           cfg.agents_per_round)
        if stats is not None:
            payload.append(stats)
            stats_len = stats.shape[0]
    gathered = jax.lax.all_gather(
        jnp.concatenate(payload) if len(payload) > 1 else payload[0],
        ax, axis=0, tiled=True).reshape(d, -1)

    dl = layout.device_len
    treedef = jax.tree_util.tree_structure(params)
    delta = buckets.unflatten(
        layout, buckets.gathered_to_flat(layout, gathered[:, :dl]),
        treedef)
    new_params = tree.astype(
        tree.map(lambda p, dlt: p + dlt, params, delta), jnp.float32)
    info = _BucketInfo(total_coords=layout.total)
    if cfg.telemetry == "full":
        info.agg = buckets.unflatten(
            layout, buckets.gathered_to_flat(layout, gathered[:, dl:2 * dl]),
            treedef)
    if rep_on:
        off = dl * (2 if cfg.telemetry == "full" else 1)
        sign_full = buckets.gathered_to_flat(layout,
                                             gathered[:, off:off + dl])
        real_full = jnp.arange(sign_full.shape[0]) < layout.total
        info.rep_agree = rep_mod.agree_rows_flat(flat, sign_full,
                                                 real_full, layout.total)
        # norm is local: flat's padding coordinates are explicit zeros,
        # so the row L2 over the padded block equals the real-coord norm
        info.rep_norm = rep_mod.norm_rows(flat)
    if stats_len:
        info.stats = jnp.sum(gathered[:, -stats_len:], axis=0)
    return new_params, info


def _bucket_async_contribs(cfg, params, updates, szs, mask_local, T_loc,
                           d, ax):
    """Buffered-async contributions through the bucketed collective shape
    (`--agg_mode buffered --agg_layout bucket`): the tick's per-level
    partial sums flatten into level-stacked rows of the bucket layout,
    ride ONE `psum_scatter` per bucket, and ONE `all_gather` reconstructs
    the globally-summed rows, which unflatten back into the contribution
    trees the shared replicated fold consumes (fl/buffered.fold_commit).

    Collective count: n_buckets reduce-scatters + 1 all_gather (+ the
    caller's packed scalar psum) — within the sync bucket plan's pinned
    budget (reduce-scatter 1, all_gather 1, psum 2 on the flagship). The
    gather carries `levels x quantities` rows instead of sync's one
    LR-scaled row; a real pod deployment would fold pending state on the
    scattered shard to keep wire bytes flat — simulation-side this keeps
    the buffer state layout-uniform with the leaf path (one checkpoint /
    carry shape per config), which the crash-exact drill depends on."""
    from defending_against_backdoors_with_robust_learning_rate_tpu.faults import (
        masking)
    avg = cfg.aggr == "avg"
    sgn = buffered.wants_sign(cfg)
    layout = buckets.layout_for_stacked(updates, d)
    if mask_local is not None:
        updates = masking.zero_masked(updates, mask_local)
    flat = buckets.flatten_stacked(layout, updates)      # [mb, padded]
    w = szs.astype(jnp.float32)
    sw = buffered._level_weights(cfg, T_loc)
    if sw is not None:
        w = w * sw
    sflat = jnp.sign(flat) if sgn else None
    avg_rows, sign_rows, cnt, wsum = [], [], [], []
    if T_loc is None:
        valid = (mask_local if mask_local is not None
                 else jnp.ones(w.shape, bool))
        wv = jnp.where(valid, w, 0.0)
        cnt.append(masking.count_f32(valid))
        if avg:
            wsum.append(jnp.sum(wv))
            avg_rows.append(jnp.sum(flat * wv[:, None], axis=0))
        if sgn:
            sign_rows.append(jnp.sum(sflat, axis=0))
    else:
        S = buffered.max_staleness(cfg)
        valid = (mask_local if mask_local is not None
                 else jnp.ones(T_loc.shape, bool))
        for s in range(S + 1):
            lvl = valid & (T_loc == s)
            wl = jnp.where(lvl, w, 0.0)
            cnt.append(masking.count_f32(lvl))
            if avg:
                wsum.append(jnp.sum(wl))
                avg_rows.append(jnp.sum(flat * wl[:, None], axis=0))
            if sgn:
                sign_rows.append(
                    jnp.sum(jnp.where(lvl[:, None], sflat, 0.0), axis=0))
    rows = jnp.stack(avg_rows + sign_rows)               # [R, padded]
    scat = jnp.concatenate([
        jax.lax.psum_scatter(
            rows[:, b * layout.bucket:(b + 1) * layout.bucket],
            ax, scatter_dimension=1, tiled=True)
        for b in range(layout.n_buckets)], axis=1)       # [R, device_len]
    gathered = jax.lax.all_gather(scat, ax, axis=0)      # [d, R, dl]
    treedef = jax.tree_util.tree_structure(params)

    def row_tree(r):
        return buckets.unflatten(
            layout, buckets.gathered_to_flat(layout, gathered[:, r, :]),
            treedef)

    n_lvl = len(avg_rows) if avg else len(sign_rows)
    trees = {}
    stack = jax.tree_util.tree_map
    if T_loc is None:
        if avg:
            trees["buf"] = row_tree(0)
        if sgn:
            trees["sign"] = row_tree(len(avg_rows))
        return (trees, cnt[0], wsum[0] if avg else None)
    if avg:
        trees["buf"] = stack(lambda *xs: jnp.stack(xs),
                             *[row_tree(s) for s in range(n_lvl)])
    if sgn:
        off = len(avg_rows)
        trees["sign"] = stack(lambda *xs: jnp.stack(xs),
                              *[row_tree(off + s) for s in range(n_lvl)])
    return (trees, jnp.stack(cnt), jnp.stack(wsum) if avg else None)


def _sharded_pallas_apply(params, updates, sizes, cfg):
    """Fused server step over the mesh: ONE Pallas pass per device over each
    local [m/d, leaf] update block (partial sign-sum + partial weighted sum,
    the leaf consumed in place — no ravel/concat staging, VERDICT r2 weak
    #4), psum of the partial trees, then an elementwise lr/apply that XLA
    fuses. HBM reads U exactly once per device — the single-device kernel's
    property (ops/pallas_rlr.py), composed with ICI collectives (XLA's
    collective-combiner batches the per-leaf psums)."""
    from defending_against_backdoors_with_robust_learning_rate_tpu.ops.pallas_rlr import (
        partial_vote_avg_flat)

    interp = jax.default_backend() != "tpu"
    w = sizes.astype(jnp.float32)
    total = jax.lax.psum(jnp.sum(w), AGENTS_AXIS)
    wn = w / total
    slr = cfg.effective_server_lr
    thr = float(cfg.robustLR_threshold)

    p_leaves, treedef = jax.tree_util.tree_flatten(params)
    u_leaves = jax.tree_util.tree_leaves(updates)
    new_leaves = []
    for p, u in zip(p_leaves, u_leaves, strict=True):
        mb = u.shape[0]
        ssum, wsum = partial_vote_avg_flat(u.reshape(mb, -1), wn,
                                           interpret=interp)
        ssum = jax.lax.psum(ssum, AGENTS_AXIS)
        if cfg.aggr == "sign":
            agg = jnp.sign(ssum)
        else:
            agg = jax.lax.psum(wsum, AGENTS_AXIS)
        if thr > 0:
            lr = jnp.where(jnp.abs(ssum) >= thr, slr, -slr)
        else:
            lr = slr
        new_leaves.append(
            (p.reshape(-1).astype(jnp.float32) + lr * agg).reshape(p.shape))
    return jax.tree_util.tree_unflatten(treedef, new_leaves)


def _loss_and_health(cfg, losses, updates_local, new_params, mask_local, d):
    """The shard body's loss reduction, with the health-sentinel lanes
    packed into the SAME collective when the lane is on
    (health/sentinel.py): pmean's scalar psum becomes one [3] vector
    psum — a shape change, never a new collective (the ``*_hlth``
    CheckSpecs pin the unchanged plan at 1/8/16-way). Lane 0 is exactly
    pmean's arithmetic (psum/d), so the loss is bitwise the health-off
    value."""
    if not health_sentinel.health_on(cfg):
        return jax.lax.pmean(jnp.mean(losses), AGENTS_AXIS), {}
    with jax.named_scope("health"):
        lanes = jnp.concatenate(
            [jnp.mean(losses)[None],
             health_sentinel.local_lanes(updates_local, mask_local)])
        packed = jax.lax.psum(lanes, AGENTS_AXIS)
        extras = health_sentinel.finish_sharded(packed[1], packed[2],
                                                new_params)
    return packed[0] / d, extras


def _build_sharded_body(cfg, model, normalize, mesh, take_flags=None,
                        take_active=None, mt=False):
    """The shard_mapped round body shared by the per-round and chained fns.

    With faults — or full telemetry — configured the body takes a trailing
    replicated [m] bool `corrupt_flags` input (`take_flags`; single source
    fl/rounds.host_takes_flags, overridable to False for the chained host
    scan, which has no per-round flag channel). Under faults every device
    derives the IDENTICAL fault draw from the replicated fault key
    (faults/model.py — no collective needed to agree on who failed),
    slices its local block of the draw by mesh position, and the only
    added communication is one tiny all_gather of the per-device
    payload-validation bits.

    `take_active` adds the trailing replicated [m] bool availability mask
    input (default: on iff churn is configured). The cohort-sampled
    builders force it on — their active mask (shortfall padding) rides
    the same input whether or not churn is configured — still with ZERO
    added collectives (the mask arrives replicated).

    An in-jit attack strategy (attack/registry.py) scales this device's
    corrupt rows right after local training — the flags arrive replicated
    and the transform is elementwise, so the collective plan is untouched
    on the leaf AND bucketed layouts (pinned by the *_atk_* contract
    specs). A *scheduled* attack adds one more trailing replicated input:
    the scalar schedule gate, computed OUTSIDE shard_map from the round
    index (like the churn mask — the body never needs the index itself).

    ``mt`` (ISSUE 13, fl/tenancy.py) builds the tenant-pack variant: the
    body is `jax.vmap`ped over a leading [E] tenant axis INSIDE the
    shard_map, a trailing replicated TenantKnobs input carries the
    per-tenant scalar knobs, and the in-jit attack gate input is forced
    on whenever the strategy is in-jit (every tenant carries its own
    schedule window). Collectives under vmap batch over the tenant axis
    — one psum of an [E, ...] payload, not E psums — so the leaf AND
    bucket collective plans are unchanged by construction (pinned by the
    *_mt CheckSpecs at 1/8/16-way)."""
    from defending_against_backdoors_with_robust_learning_rate_tpu.attack import (
        registry as attack_registry)
    from defending_against_backdoors_with_robust_learning_rate_tpu.fl.rounds import (
        _pallas_applicable, host_takes_flags)
    faults_on = cfg.faults_enabled
    # a quarantine set (health/monitor.py) rides the same replicated
    # availability-mask input as churn — the caller composes both masks
    # outside shard_map, so the body only sees one [m] bool channel
    churn_on = ((cfg.churn_enabled or health_sentinel.has_quarantine(cfg))
                if take_active is None else take_active)
    atk_on = attack_registry.in_jit(cfg)
    # tenant packs gate every in-jit attack per tenant (the trivial
    # schedule's traced gate is always-on); solo bodies only take the
    # gate input when a schedule actually needs the round index
    atk_sched = (atk_on if mt else attack_registry.needs_round(cfg))
    if take_flags is None:
        take_flags = host_takes_flags(cfg)
    if faults_on:
        from defending_against_backdoors_with_robust_learning_rate_tpu.faults import (
            model as fmodel)
    if churn_on:
        from defending_against_backdoors_with_robust_learning_rate_tpu.service import (
            churn as churn_mod)
    # layout-dispatched client-block trainer (ISSUE 10): under
    # --train_layout megabatch each device folds ITS m/d-client block
    # into one [mb*bs, ...] megabatch — the fold happens inside the
    # shard, so the collective plan is untouched by construction
    train_block = make_block_trainer(model, cfg, normalize)
    m = cfg.agents_per_round
    d = mesh.devices.size
    assert m % d == 0, f"agents_per_round={m} not divisible by mesh size {d}"
    mb = m // d
    if cfg.agg_layout not in ("leaf", "bucket"):
        raise ValueError(f"agg_layout must be 'leaf' or 'bucket', got "
                         f"{cfg.agg_layout!r}")
    if cfg.agg_layout == "bucket" and cfg.diagnostics:
        # the scattered vote never materializes the full lr tree the
        # diagnostics extras (lr_flat) read; mixing layouts between snap
        # and off-snap rounds would silently compare different programs
        raise ValueError(
            "--agg_layout bucket does not support --diagnostics (the "
            "lr tree is never materialized on the scattered path); "
            "re-run with --agg_layout leaf — the per-leaf psum plan "
            "keeps the full lr tree and supports every diagnostic")

    is_async = buffered.is_buffered(cfg)

    def shard_body(carry, imgs, lbls, szs, keys, noise_key, *rest):
        # trailing replicated inputs, in order: [m] corrupt flags (faults /
        # full telemetry / in-jit attack), the [m] churn availability
        # mask, then the scalar attack-schedule gate — the caller
        # computes the lifecycle draw and the schedule gate OUTSIDE
        # shard_map (they need the sampled ids / round index) and they
        # arrive replicated, so neither adds a collective (analysis
        # *_churn / *_atk_* specs pin this).
        # Buffered mode: the lead argument is the (params, buffer-state)
        # carry — both replicated; the fold is elementwise post-psum
        # (fl/buffered.py), so the collective plan is the sync family's.
        params, astate = carry if is_async else (carry, None)
        # tenant-pack mode: the LAST trailing input is the per-tenant
        # TenantKnobs (scalars here — the tenant vmap wraps this body)
        knobs = rest[-1] if mt else None
        idx = 0
        corrupt_full = churn_full = atk_active = None
        if take_flags:
            corrupt_full = rest[idx]
            idx += 1
        if churn_on:
            churn_full = rest[idx]
            idx += 1
        if atk_sched:
            atk_active = rest[idx]
        mask_local = mask_full = draw = ep_local = None
        if faults_on or churn_on or atk_on:
            pos = jax.lax.axis_index(AGENTS_AXIS) * mb

            def local(v):
                return jax.lax.dynamic_slice_in_dim(v, pos, mb, 0)
        if faults_on:
            # replicated draw: every device computes the same [m] pattern
            draw = fmodel.sample_faults(cfg, fmodel.fault_key(noise_key), m,
                                        corrupt_full)
            if cfg.straggler_rate > 0:
                ep_local = local(draw.ep_budget)
        # chunking applies to the per-device agent block (m/d agents)
        with jax.named_scope("local_train"):
            updates, losses = train_block(params, imgs, lbls, szs, keys,
                                          cfg.agent_chunk,
                                          ep_budget=ep_local)
        if atk_on:
            # each device scales ITS corrupt rows — elementwise on the
            # local block, replicated inputs, zero collectives
            updates = attack_registry.apply_update_attack(
                cfg, updates, local(corrupt_full), atk_active,
                boost=None if knobs is None else knobs.attack_boost)
        if faults_on:
            from defending_against_backdoors_with_robust_learning_rate_tpu.faults import (
                masking)
            if cfg.corrupt_rate > 0:
                updates = fmodel.inject_corrupt(updates, local(draw.corrupt),
                                                cfg.corrupt_mode)
            valid = jax.lax.all_gather(
                fmodel.payload_valid(updates, cfg.payload_norm_cap),
                AGENTS_AXIS, axis=0, tiled=True)
            mask_full = draw.participate & valid
            mask_local = local(mask_full)
        if churn_full is not None:
            # the replicated lifecycle mask joins the participation mask
            # exactly like a dropout draw — away clients are excluded
            # arithmetically, no shape changes, no collective
            mask_full = (churn_full if mask_full is None
                         else mask_full & churn_full)
            mask_local = local(mask_full)
        if is_async:
            # buffered-async tail: this tick's per-level contributions
            # ride the sync plan's collectives (per-leaf psums on the
            # leaf layout, per-bucket reduce-scatter + one all_gather on
            # the bucket layout; the tiny count/weight/loss lanes pack
            # into ONE vector psum), then the shared replicated fold
            # advances the carried buffer (fl/buffered.fold_commit —
            # zero collectives of its own, pinned by the *_async specs)
            with jax.named_scope("buffered_fold"):
                T_full = buffered.latency(
                    cfg, noise_key,
                    draw.straggler if draw is not None else None)
                T_loc = local(T_full) if T_full is not None else None
                loss_local = jnp.mean(losses)
                if _bucket_applicable(cfg):
                    g_trees, cnt_l, wsum_l = _bucket_async_contribs(
                        cfg, params, updates, szs, mask_local, T_loc, d,
                        AGENTS_AXIS)
                else:
                    c = buffered.tick_contributions(cfg, updates, szs,
                                                    mask_local, T_loc)
                    g_trees = {
                        k: tree.map(
                            lambda x: jax.lax.psum(x, AGENTS_AXIS), c[k])
                        for k in ("buf", "sign") if k in c}
                    cnt_l, wsum_l = c["cnt"], c.get("wsum")
                lanes = [jnp.atleast_1d(cnt_l)]
                if wsum_l is not None:
                    lanes.append(jnp.atleast_1d(wsum_l))
                lanes.append(loss_local[None])
                h_on = health_sentinel.health_on(cfg)
                if h_on:
                    # the health-sentinel lanes ride the SAME packed
                    # psum (health/sentinel.py — zero added collectives)
                    lanes.append(health_sentinel.local_lanes(updates,
                                                             mask_local))
                packed = jax.lax.psum(jnp.concatenate(lanes), AGENTS_AXIS)
                n1 = lanes[0].shape[0]
                contribs = dict(g_trees)
                contribs["cnt"] = packed[:n1] if n1 > 1 else packed[0]
                if wsum_l is not None:
                    contribs["wsum"] = (packed[n1:2 * n1] if n1 > 1
                                        else packed[1])
                # the loss lane rides the packed psum: psum/d is exactly
                # pmean's arithmetic, so the budget stays the sync plan's
                loss = (packed[-3] if h_on else packed[-1]) / d
                new_params, new_astate, lr, agg, a_extras, vote_sign = \
                    buffered.fold_commit(cfg, params, astate, contribs,
                                         noise_key, m, knobs=knobs)
            extras = dict(a_extras)
            if h_on:
                with jax.named_scope("health"):
                    extras.update(health_sentinel.finish_sharded(
                        packed[-2], packed[-1], new_params))
            if faults_on:
                extras.update(fmodel.fault_scalars(draw, mask_full))
                if churn_full is not None and cfg.churn_enabled:
                    extras["churn_away"] = churn_mod.churn_away(churn_full)
            elif churn_full is not None and cfg.churn_enabled:
                extras.update(churn_mod.churn_only_scalars(churn_full,
                                                           mask_full))
            if cfg.telemetry != "off":
                from defending_against_backdoors_with_robust_learning_rate_tpu.obs import (
                    telemetry)
                extras.update(telemetry.compute_sharded(
                    cfg, updates,
                    lr if cfg.robustLR_threshold > 0 else None, agg,
                    AGENTS_AXIS, mask_local=mask_local,
                    mask_full=mask_full, corrupt_full=corrupt_full,
                    sign_sums=vote_sign,
                    vote_range=buffered.vote_range(cfg)))
            from defending_against_backdoors_with_robust_learning_rate_tpu.obs import (
                reputation as rep_mod)
            if rep_mod.reputation_on(cfg):
                # agreement vs the BUFFER's replicated accumulated sign
                # vote (fold_commit's vote_sign) on the local block —
                # elementwise; shard_map's P(AGENTS_AXIS) out_spec
                # stitches the [m] row with zero collectives
                extras["rep_agree"] = rep_mod.agree_rows(
                    updates, vote_sign, mask=mask_local)
                extras["rep_norm"] = rep_mod.norm_rows(updates,
                                                       mask=mask_local)
            return (new_params, new_astate), loss, extras
        if _pallas_applicable(cfg):
            new_params = _sharded_pallas_apply(params, updates, szs, cfg)
            loss, hextras = _loss_and_health(cfg, losses, updates,
                                             new_params, None, d)
            return new_params, loss, hextras
        sign_sums = None
        bucket_info = None
        with jax.named_scope("aggregate_rlr"):
            if _bucket_applicable(cfg):
                # pod-shape plan: per-bucket reduce-scatter + one
                # all_gather of the LR-scaled result, vote + average on
                # the scattered shard (parallel/buckets.py)
                lr = agg = None
                new_params, bucket_info = _bucketed_apply(
                    params, updates, szs, cfg, noise_key, d,
                    mask_local, mask_full, knobs=knobs)
            elif cfg.robustLR_threshold > 0 and cfg.aggr == "sign":
                # vote + aggregate share one sign-sum psum per leaf (the
                # CSE XLA was measured not to do — see _sharded_sign_shared)
                lr, agg, sign_sums = _sharded_sign_shared(
                    updates, cfg, noise_key, mask_local, mask_full,
                    knobs=knobs)
                new_params = apply_aggregate(params, lr, agg)
            else:
                if cfg.robustLR_threshold > 0:
                    lr, sign_sums = _sharded_robust_lr(updates, cfg,
                                                       mask_local,
                                                       mask_full,
                                                       knobs=knobs)
                else:
                    lr = (cfg.effective_server_lr if knobs is None
                          else knobs.server_lr)
                agg_out = {}
                agg = _sharded_aggregate(updates, szs, cfg, d, noise_key,
                                         mask_local, mask_full,
                                         out=agg_out)
                if sign_sums is None:
                    # thresholdless sign aggregation: the sign branch's
                    # own psum results, re-read for the reputation lane
                    sign_sums = agg_out.get("sign_sums")
                new_params = apply_aggregate(params, lr, agg)
        loss, extras = _loss_and_health(cfg, losses, updates, new_params,
                                        mask_local, d)
        if faults_on:
            extras.update(fmodel.fault_scalars(draw, mask_full))
            if churn_full is not None and cfg.churn_enabled:
                extras["churn_away"] = churn_mod.churn_away(churn_full)
        elif churn_full is not None and cfg.churn_enabled:
            # emission gated on churn actually being configured: the
            # cohort builders force the active INPUT on (shortfall
            # padding joins the mask) without growing churn series
            extras.update(churn_mod.churn_only_scalars(churn_full,
                                                       mask_full))
        if cfg.telemetry != "off":
            from defending_against_backdoors_with_robust_learning_rate_tpu.obs import (
                telemetry)
            if bucket_info is not None:
                # the vote/flip stats and (full) the aggregate tree rode
                # the bucketed result all_gather — zero extra psums, the
                # leaf path's sign_sums sharing discipline on the new
                # layout
                extras.update(telemetry.compute_sharded_bucket(
                    cfg, updates, bucket_info, AGENTS_AXIS,
                    mask_local=mask_local, mask_full=mask_full,
                    corrupt_full=corrupt_full))
            else:
                # sign_sums: the vote's per-leaf psum results, so full
                # telemetry's margin histogram re-reads the existing
                # collective instead of duplicating it per leaf
                extras.update(telemetry.compute_sharded(
                    cfg, updates,
                    lr if cfg.robustLR_threshold > 0 else None, agg,
                    AGENTS_AXIS, mask_local=mask_local, mask_full=mask_full,
                    corrupt_full=corrupt_full, sign_sums=sign_sums))
        from defending_against_backdoors_with_robust_learning_rate_tpu.obs import (
            reputation as rep_mod)
        if rep_mod.reputation_on(cfg):
            if bucket_info is not None:
                # computed inside _bucketed_apply against the full vote
                # whose shard rode the existing result all_gather
                rep_local = bucket_info.rep_agree
                rep_nrm = bucket_info.rep_norm
                if mask_local is not None:
                    rep_local = jnp.where(mask_local, rep_local,
                                          rep_mod.MASKED)
                    rep_nrm = jnp.where(mask_local, rep_nrm,
                                        rep_mod.MASKED)
            else:
                # leaf layout: the vote's replicated sign-sum psums,
                # re-read — local [m/d] block, stitched to [m] by the
                # P(AGENTS_AXIS) out_spec, zero collectives
                rep_local = rep_mod.agree_rows(updates, sign_sums,
                                               mask=mask_local)
                rep_nrm = rep_mod.norm_rows(updates, mask=mask_local)
            extras["rep_agree"] = rep_local
            extras["rep_norm"] = rep_nrm
        if cfg.diagnostics:
            from defending_against_backdoors_with_robust_learning_rate_tpu.fl.diagnostics import (
                per_agent_norms)
            from jax.flatten_util import ravel_pytree
            extras["agent_norms"] = jax.lax.all_gather(
                per_agent_norms(updates), AGENTS_AXIS, axis=0, tiled=True)
            if cfg.robustLR_threshold > 0:
                extras["lr_flat"] = ravel_pytree(lr)[0]
        return new_params, loss, extras

    extras_specs = {}
    if is_async:
        extras_specs.update({k: P() for k in buffered.ASYNC_INFO_KEYS})
    if faults_on or (churn_on and cfg.churn_enabled):
        from defending_against_backdoors_with_robust_learning_rate_tpu.fl.rounds import (
            FAULT_INFO_KEYS)
        extras_specs.update({k: P() for k in FAULT_INFO_KEYS})
    if churn_on and cfg.churn_enabled:
        extras_specs["churn_away"] = P()
    if cfg.telemetry != "off":
        from defending_against_backdoors_with_robust_learning_rate_tpu.obs.telemetry import (
            telemetry_keys)
        extras_specs.update({k: P() for k in telemetry_keys(cfg)})
    if cfg.diagnostics:
        extras_specs["agent_norms"] = P()
        if cfg.robustLR_threshold > 0:
            extras_specs["lr_flat"] = P()
    # health-sentinel scalars (health/sentinel.py): replicated outputs
    # (the psummed lanes + the params-finite bit); the sharded key set
    # excludes the [m] suspect vector by construction
    extras_specs.update({k: P() for k in
                         health_sentinel.health_keys(cfg, sharded=True)})
    # reputation lane (obs/reputation.py): each device emits its LOCAL
    # [m/d] rep_agree + rep_norm blocks ([E, m/d] in a tenant pack) and
    # shard_map's out_spec stitches the full [m] rows — the free
    # materialization the health lane's hlth_agent_bad could not afford
    # (its value is replicated; the rep lanes are sharded by construction)
    from defending_against_backdoors_with_robust_learning_rate_tpu.obs.reputation import (
        rep_keys)
    extras_specs.update({k: (P(None, AGENTS_AXIS) if mt
                             else P(AGENTS_AXIS)) for k in rep_keys(cfg)})

    if mt:
        # tenant axis INSIDE the shard: every input grows a leading [E]
        # (the data stacks shard the AGENTS axis at position 1), the
        # knobs ride as one more replicated input, and jax.vmap batches
        # the body — collectives batch over the tenant axis instead of
        # multiplying, so the pinned plan is unchanged by construction
        agents = P(None, AGENTS_AXIS)
        in_specs = (P(), agents, agents, agents, agents, P()) \
            + ((P(),) if take_flags else ()) \
            + ((P(),) if churn_on else ()) \
            + ((P(),) if atk_sched else ()) + (P(),)
        return shard_map(
            jax.vmap(shard_body), mesh=mesh,
            in_specs=in_specs,
            out_specs=(P(), P(), extras_specs),
            check_vma=False)
    in_specs = (P(), P(AGENTS_AXIS), P(AGENTS_AXIS), P(AGENTS_AXIS),
                P(AGENTS_AXIS), P()) + ((P(),) if take_flags else ()) \
        + ((P(),) if churn_on else ()) + ((P(),) if atk_sched else ())
    return shard_map(
        shard_body, mesh=mesh,
        in_specs=in_specs,
        out_specs=(P(), P(), extras_specs),
        check_vma=False)


def _make_sample_step(cfg, model, normalize, mesh):
    """Shared sharded sample-and-step fn: step(params, key, images, labels,
    sizes).

    Samples the round's m agents, gathers their shards in-jit (partitioned
    over the mesh by shard_map's in_specs), and runs the shard_mapped body.
    Both the per-round and chained fns wrap THIS fn — chained execution
    stays bit-identical to per-round dispatch. The dataset stacks are jit
    ARGUMENTS, not closure captures (closure arrays get inlined into the
    lowered HLO as dense constants — see fl/rounds._make_sample_step)."""
    from defending_against_backdoors_with_robust_learning_rate_tpu.attack import (
        registry as attack_registry)
    from defending_against_backdoors_with_robust_learning_rate_tpu.fl.rounds import (
        host_takes_flags, step_takes_round)
    sharded = _build_sharded_body(cfg, model, normalize, mesh)
    K, m = cfg.num_agents, cfg.agents_per_round
    want_flags = host_takes_flags(cfg)

    def body(params, key, rnd, images, labels, sizes):
        k_sample, k_train, k_noise = jax.random.split(key, 3)
        with jax.named_scope("sample_gather"):
            sampled = jax.random.permutation(k_sample, K)[:m]
            imgs = jnp.take(images, sampled, axis=0)
            lbls = jnp.take(labels, sampled, axis=0)
            szs = jnp.take(sizes, sampled, axis=0)
        agent_keys = jax.random.split(k_train, m)
        extra = ((sampled < cfg.num_corrupt,) if want_flags else ())
        active = None
        if cfg.churn_enabled:
            from defending_against_backdoors_with_robust_learning_rate_tpu.service import (
                churn as churn_mod)
            # lifecycle draw computed OUTSIDE shard_map (it needs the
            # sampled ids + round index); enters the body replicated
            with jax.named_scope("churn_mask"):
                active = churn_mod.active_slots(cfg, sampled, rnd)
        if health_sentinel.has_quarantine(cfg):
            # quarantine membership composes into the same replicated
            # availability input (health/monitor.py QUARANTINE rung)
            qmask = health_sentinel.quarantine_mask(cfg, sampled)
            active = qmask if active is None else active & qmask
        if active is not None:
            extra = extra + (active,)
        if attack_registry.needs_round(cfg):
            # schedule gate computed OUTSIDE shard_map from the round
            # index; enters the body as a replicated scalar
            extra = extra + (attack_registry.schedule_active(cfg, rnd),)
        new_params, train_loss, extras = sharded(params, imgs, lbls, szs,
                                                 agent_keys, k_noise, *extra)
        return new_params, {"train_loss": train_loss, "sampled": sampled,
                            **extras}

    if step_takes_round(cfg):
        def step(params, key, rnd, images, labels, sizes):
            return body(params, key, rnd, images, labels, sizes)
        step.takes_round = True
        return step

    def step(params, key, images, labels, sizes):
        return body(params, key, jnp.int32(0), images, labels, sizes)
    step.takes_round = False
    return step


def make_sharded_round_fn(cfg, model, normalize, mesh,
                          images, labels, sizes):
    """Device-resident sharded round fn: round(params, key) -> (params, info).

    images/labels/sizes: full K-agent stacked arrays. The per-round gather of
    the m sampled shards happens in-jit; the gathered [m, ...] arrays are
    partitioned over the mesh by shard_map's in_specs.
    """
    from defending_against_backdoors_with_robust_learning_rate_tpu.utils import (
        compile_cache)
    return bind_data(jax.jit(_make_sample_step(cfg, model, normalize, mesh)),
                     (images, labels, sizes),
                     family=("round_sharded_diag" if cfg.diagnostics
                             else "round_sharded"
                             + compile_cache.family_suffix(cfg)))


def make_sharded_round_fn_mt(cfg, model, normalize, mesh,
                             images, labels, sizes):
    """Tenant-pack sharded round fn (ISSUE 13, fl/tenancy.py):
    round(params_E, keys_E, rnd, knobs) -> (params_E, info) with every
    carried array [E]-stacked and the tenant axis folded INSIDE the
    shard (each device trains its m/d-agent block for all E tenants; the
    per-leaf psums / bucketed reduce-scatters batch over the tenant axis
    instead of multiplying — the *_mt CheckSpecs pin the unchanged plan
    at 1/8/16-way). Per-tenant sampling, corrupt flags, churn masks and
    schedule gates are computed OUTSIDE shard_map from the per-tenant
    keys/knobs and enter replicated, the solo body's exact discipline.
    Buffered mode carries (params_E, astate_E) — both [E]-stacked,
    replicated across the mesh like the solo sharded-async carry — and
    each tenant runs on its EFFECTIVE clock rnd + knobs.rnd_offset (the
    scheduler's backfill skew; 0 on the FIFO path)."""
    from defending_against_backdoors_with_robust_learning_rate_tpu.attack import (
        registry as attack_registry, schedule as attack_schedule)
    from defending_against_backdoors_with_robust_learning_rate_tpu.fl.rounds import (
        host_takes_flags)
    from defending_against_backdoors_with_robust_learning_rate_tpu.utils import (
        compile_cache)
    sharded = _build_sharded_body(cfg, model, normalize, mesh, mt=True)
    K, m = cfg.num_agents, cfg.agents_per_round
    want_flags = host_takes_flags(cfg)
    atk_gated = attack_registry.in_jit(cfg)

    def step(carry_E, keys_E, rnd, knobs, images, labels, sizes):
        rnd_E = rnd + knobs.rnd_offset  # [E] effective round indices

        def sample(key):
            k_sample, k_train, k_noise = jax.random.split(key, 3)
            sampled = jax.random.permutation(k_sample, K)[:m]
            return sampled, jax.random.split(k_train, m), k_noise

        with jax.named_scope("sample_gather"):
            sampled_E, agent_keys_E, k_noise_E = jax.vmap(sample)(keys_E)
            imgs = jnp.take(images, sampled_E, axis=0)   # [E, m, ...]
            lbls = jnp.take(labels, sampled_E, axis=0)
            szs = jnp.take(sizes, sampled_E, axis=0)
        extra = ()
        if want_flags:
            extra += (sampled_E < cfg.num_corrupt,)
        active_E = None
        if cfg.churn_enabled:
            from defending_against_backdoors_with_robust_learning_rate_tpu.service import (
                churn as churn_mod)
            with jax.named_scope("churn_mask"):
                active_E = jax.vmap(
                    lambda s, r: churn_mod.active_slots(cfg, s, r))(
                        sampled_E, rnd_E)
        if health_sentinel.has_quarantine(cfg):
            q_E = jax.vmap(
                lambda s: health_sentinel.quarantine_mask(cfg, s))(
                    sampled_E)
            active_E = q_E if active_E is None else active_E & q_E
        if active_E is not None:
            extra += (active_E,)
        if atk_gated:
            # per-tenant schedule gates from the traced knob triples —
            # replicated [E] input, zero collectives (the solo gate
            # idiom); the gate reads each tenant's effective clock
            extra += (attack_schedule.active_traced(
                knobs.attack_start, knobs.attack_stop,
                knobs.attack_every, rnd_E),)
        new_carry, train_loss, extras = sharded(
            carry_E, imgs, lbls, szs, agent_keys_E, k_noise_E,
            *extra, knobs)
        return new_carry, {"train_loss": train_loss,
                           "sampled": sampled_E, **extras}

    jitted = jax.jit(step)

    def bound(params_E, keys_E, rnd, knobs):
        return jitted(params_E, keys_E, rnd, knobs, images, labels, sizes)

    bound.jitted, bound.data = jitted, (images, labels, sizes)
    bound.family = "round_sharded" + compile_cache.family_suffix(cfg)
    return bound


def make_sharded_host_step(cfg, model, normalize, mesh, take_flags=None):
    """Unjitted sharded host step(params, key, imgs, lbls, sizes) — shared
    body of the per-round and chained sharded host fns. Key derivation
    (split into k_train/k_noise, then m agent keys) matches
    fl/rounds.make_host_step bit-for-bit, so the sharded and single-device
    host paths are comparable round-for-round."""
    from defending_against_backdoors_with_robust_learning_rate_tpu.attack import (
        registry as attack_registry)
    from defending_against_backdoors_with_robust_learning_rate_tpu.fl.rounds import (
        host_takes_flags)
    if cfg.churn_enabled:
        # same contract as fl/rounds.make_host_step: the host-sampled
        # program never sees the sampled ids the lifecycle draw hashes
        raise ValueError(
            "client churn (--churn_available < 1) is not supported in "
            "host-sampled mode; run device-resident (--host_sampled off)")
    if buffered.is_buffered(cfg):
        # same contract as the single-device host step (fl/rounds)
        raise ValueError(
            "--agg_mode buffered is not supported in host-sampled mode; "
            "run device-resident (--host_sampled off) or cohort-sampled "
            "(--cohort_sampled on)")
    if attack_registry.needs_round(cfg):
        # same contract as the single-device host step: no round channel
        # for the schedule gate (fl/rounds.make_host_step)
        raise ValueError(
            f"--attack {cfg.attack} with a schedule is not supported in "
            f"host-sampled mode; run device-resident (--host_sampled "
            f"off) or cohort-sampled")
    if take_flags is False and attack_registry.in_jit(cfg):
        raise ValueError(
            f"--attack {cfg.attack} transforms updates in-jit and needs "
            f"the corrupt-slot flags, which the chained host scan does "
            f"not carry — the driver must dispatch host-sampled attack "
            f"rounds unchained (train.py disables --chain here)")
    if take_flags is None:
        take_flags = host_takes_flags(cfg)
    sharded = _build_sharded_body(cfg, model, normalize, mesh,
                                  take_flags=take_flags)
    m = cfg.agents_per_round

    if take_flags:
        # faults / full telemetry: the driver passes the sampled slots'
        # corrupt flags (it owns the host-side id sampling) — see
        # fl/rounds.make_host_step
        def step(params, key, imgs, lbls, szs, corrupt_flags):
            k_train, k_noise = jax.random.split(key)
            agent_keys = jax.random.split(k_train, m)
            new_params, train_loss, extras = sharded(
                params, imgs, lbls, szs, agent_keys, k_noise, corrupt_flags)
            return new_params, {"train_loss": train_loss, **extras}
        return step

    def step(params, key, imgs, lbls, szs):
        k_train, k_noise = jax.random.split(key)
        agent_keys = jax.random.split(k_train, m)
        new_params, train_loss, extras = sharded(params, imgs, lbls, szs,
                                                 agent_keys, k_noise)
        return new_params, {"train_loss": train_loss, **extras}

    return step


def make_sharded_round_fn_host(cfg, model, normalize, mesh):
    """Host-sampled sharded round fn: round(params, key, imgs, lbls, sizes).

    The fedemnist-scale path (3383 users, ref runner.sh:34-38): the full
    agent stack exceeds the device-resident budget, so the driver gathers the
    round's m sampled shards host-side and THIS fn partitions them over the
    `agents` mesh (m/d per device) before the shard_mapped body runs."""
    return jax.jit(make_sharded_host_step(cfg, model, normalize, mesh))


def make_sharded_chained_round_fn_host(cfg, model, normalize, mesh):
    """Chained sharded host rounds: chained(params, base_key, round_ids,
    imgs, lbls, sizes) over [chain, m, ...] blocks sharded on the m axis
    (P(None, agents)); `lax.scan` slices one round's [m, ...] stack per step
    and runs the shard_mapped body — collectives inside the scan, one XLA
    program per block."""
    from defending_against_backdoors_with_robust_learning_rate_tpu.fl.rounds import (
        make_chained_host)
    return make_chained_host(
        make_sharded_host_step(cfg.replace(diagnostics=False), model,
                               normalize, mesh, take_flags=False))


# ----------------------------------------------------------- cohort path ---

def make_sharded_cohort_step(cfg, model, normalize, mesh):
    """Unjitted sharded cohort step(params, key, rnd, imgs, lbls, szs):
    the cohort-sampled round (fl/rounds.make_cohort_step) over the agents
    mesh. The seeded cohort draw runs OUTSIDE shard_map (replicated — it
    needs no per-shard data) and its ids/active/corrupt-flags enter the
    body as replicated [m] inputs, so the whole population/cohort split
    adds ZERO collectives to the documented communication plan (pinned by
    the *_cohort specs in analysis/contracts.py)."""
    from defending_against_backdoors_with_robust_learning_rate_tpu.attack import (
        registry as attack_registry)
    from defending_against_backdoors_with_robust_learning_rate_tpu.data import (
        cohort as cohort_mod)
    from defending_against_backdoors_with_robust_learning_rate_tpu.fl.rounds import (
        host_takes_flags)
    want_flags = host_takes_flags(cfg)
    sharded = _build_sharded_body(cfg, model, normalize, mesh,
                                  take_flags=want_flags, take_active=True)
    m = cfg.agents_per_round

    def step(params, key, rnd, imgs, lbls, szs):
        with jax.named_scope("cohort_sample"):
            ids, active = cohort_mod.sample_cohort(cfg, rnd)
        if health_sentinel.has_quarantine(cfg):
            # quarantined members leave through the active mask, the
            # shortfall-padding / churn-absence protocol (fl/rounds
            # make_cohort_step does the same on the single-device path)
            active = active & health_sentinel.quarantine_mask(cfg, ids)
        k_train, k_noise = jax.random.split(key)
        agent_keys = jax.random.split(k_train, m)
        extra = (((ids < cfg.num_corrupt) & active,) if want_flags else ())
        extra = extra + (active,)
        if attack_registry.needs_round(cfg):
            extra = extra + (attack_registry.schedule_active(cfg, rnd),)
        new_params, train_loss, extras = sharded(params, imgs, lbls, szs,
                                                 agent_keys, k_noise, *extra)
        return new_params, {"train_loss": train_loss, "sampled": ids,
                            **extras}

    step.takes_round = True
    return step


def make_sharded_cohort_round_fn(cfg, model, normalize, mesh):
    """Sharded cohort round fn: round(params, key, rnd, imgs, lbls, szs) —
    the bank-gathered [m, ...] cohort stacks partitioned over the agents
    mesh (m/d per device), cohort ids recomputed in-program."""
    return jax.jit(make_sharded_cohort_step(cfg, model, normalize, mesh))


def make_sharded_chained_cohort_round_fn(cfg, model, normalize, mesh):
    """Chained sharded cohort rounds over [chain, m, ...] blocks sharded on
    the m axis; the scanned round index re-derives each round's cohort
    ids, flags and churn mask in-program (fl/rounds.make_chained_host)."""
    from defending_against_backdoors_with_robust_learning_rate_tpu.fl.rounds import (
        make_chained_host)
    return make_chained_host(
        make_sharded_cohort_step(cfg.replace(diagnostics=False), model,
                                 normalize, mesh))


def make_sharded_chained_round_fn(cfg, model, normalize, mesh,
                                  images, labels, sizes):
    """Chained sharded rounds: chained(params, base_key, round_ids).

    `lax.scan` over a block of rounds with the shard_mapped round body inside
    — one XLA program per block, collectives included; key derivation
    (`fold_in(base_key, r)`) matches the driver loop bit-for-bit (see
    fl/rounds.make_chained_round_fn). Diagnostics extras unsupported."""
    from defending_against_backdoors_with_robust_learning_rate_tpu.utils import (
        compile_cache)
    plain = cfg.replace(diagnostics=False)
    return make_chained(_make_sample_step(plain, model, normalize, mesh),
                        (images, labels, sizes),
                        family="chained_sharded"
                        + compile_cache.family_suffix(plain))
