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
    (pinned by the *_atk_* contract specs). A *scheduled* attack adds one
    more trailing replicated input: the scalar schedule gate, computed
    OUTSIDE shard_map from the round index (like the churn mask — the
    body never needs the index itself).

    ``mt`` (ISSUE 13, fl/tenancy.py) builds the tenant-pack variant: the
    body is `jax.vmap`ped over a leading [E] tenant axis INSIDE the
    shard_map, a trailing replicated TenantKnobs input carries the
    per-tenant scalar knobs, and the in-jit attack gate input is forced
    on whenever the strategy is in-jit (every tenant carries its own
    schedule window). Collectives under vmap batch over the tenant axis
    — one psum of an [E, ...] payload, not E psums — so the collective
    plan is unchanged by construction (pinned by the *_mt CheckSpecs at
    1/8/16-way)."""
    from defending_against_backdoors_with_robust_learning_rate_tpu.attack import (
        registry as attack_registry)
    from defending_against_backdoors_with_robust_learning_rate_tpu.fl.rounds import (
        host_takes_flags)
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
    train_block = make_block_trainer(model, cfg, normalize)
    m = cfg.agents_per_round
    d = mesh.devices.size
    assert m % d == 0, f"agents_per_round={m} not divisible by mesh size {d}"
    mb = m // d

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
            # ride the sync plan's per-leaf psums (the tiny count/weight/
            # loss lanes pack into ONE vector psum), then the shared
            # replicated fold advances the carried buffer
            # (fl/buffered.fold_commit — zero collectives of its own,
            # pinned by the *_async specs)
            with jax.named_scope("buffered_fold"):
                T_full = buffered.latency(
                    cfg, noise_key,
                    draw.straggler if draw is not None else None)
                T_loc = local(T_full) if T_full is not None else None
                loss_local = jnp.mean(losses)
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
        sign_sums = None
        with jax.named_scope("aggregate_rlr"):
            if cfg.robustLR_threshold > 0 and cfg.aggr == "sign":
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
            # the vote's replicated sign-sum psums, re-read — local
            # [m/d] block, stitched to [m] by the P(AGENTS_AXIS)
            # out_spec, zero collectives
            extras["rep_agree"] = rep_mod.agree_rows(updates, sign_sums,
                                                     mask=mask_local)
            extras["rep_norm"] = rep_mod.norm_rows(updates,
                                                   mask=mask_local)
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
    per-leaf psums batch over the tenant axis instead of multiplying — the
    *_mt CheckSpecs pin the unchanged plan at 1/8/16-way). Per-tenant sampling, corrupt flags, churn masks and
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
