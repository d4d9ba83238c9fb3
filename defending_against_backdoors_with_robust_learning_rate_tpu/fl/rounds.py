"""The FL round step — one jitted function per round.

Reference: the round loop body of src/federated.py:65-74 (sequential Python
loop over sampled agents, dict of updates, in-process aggregation). Here the
whole round is ONE compiled XLA program: client sampling
(`jax.random.permutation`, replacing the unseeded np.random.choice at
src/federated.py:68), a `vmap` over the m sampled agents' local training, the
aggregation rule + RLR defense, and the global parameter update. No snapshot/
restore dance (src/federated.py:66-72) is needed because local training is a
pure function of the global params.

Two data modes:
- device-resident (fmnist/cifar10): all K agent shards live in HBM; the
  sampled m shards are gathered *inside* jit.
- host-sampled (fedemnist, 3383 users): the driver gathers the sampled
  shards on host and feeds them as arguments (fixed [m, ...] shapes, so one
  compilation serves every round).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from defending_against_backdoors_with_robust_learning_rate_tpu.fl import (
    buffered, task)
from defending_against_backdoors_with_robust_learning_rate_tpu.fl.client import (
    make_local_train)
from defending_against_backdoors_with_robust_learning_rate_tpu.health import (
    sentinel as health_sentinel)
from defending_against_backdoors_with_robust_learning_rate_tpu.ops import loops
from defending_against_backdoors_with_robust_learning_rate_tpu.ops.aggregate import (
    aggregate_updates, apply_aggregate, fold_finish, fold_init, fold_updates,
    robust_lr)

# fault observability scalars (faults/model.fault_scalars) that chained
# blocks carry through their lax.scan alongside train_loss
FAULT_INFO_KEYS = ("fault_dropped", "fault_straggled", "fault_voters")
# everything a chained scan carries per-round besides train_loss/tel_*:
# the fault counters, the churn away count (service/churn.py) and the
# buffered-async fill/commit/staleness scalars (fl/buffered.py)
CHAINED_INFO_KEYS = (FAULT_INFO_KEYS + ("churn_away",)
                     + buffered.ASYNC_INFO_KEYS)


def host_takes_flags(cfg) -> bool:
    """Whether the host-sampled per-round step takes the trailing [m] bool
    corrupt-slot flags argument: the faults path needs them for
    --faults_spare_corrupt participation, full telemetry for the
    honest-vs-corrupt cosine split, and the in-jit attack strategies
    (attack/registry.py) to know which rows to transform. Single source
    for the driver, the AOT aval planner (utils/compile_cache.
    plan_programs) and the step builders — their signatures must agree."""
    from defending_against_backdoors_with_robust_learning_rate_tpu.attack import (
        registry as attack_registry)
    return (cfg.faults_enabled or cfg.telemetry == "full"
            or attack_registry.in_jit(cfg))


def step_takes_round(cfg) -> bool:
    """Whether the round step takes the round index as a traced int32
    lead argument: the churn lifecycle is a function of time
    (service/churn.py), so is diurnal traffic (data/traffic.py), and so
    is a scheduled in-jit attack (attack/schedule.py). Single source for
    the step builders here and in parallel/rounds.py, the driver's
    dispatch (train.py) and the AOT aval planner — their signatures must
    agree. (Cohort steps always take the round index regardless — their
    sampling consumes it.)"""
    from defending_against_backdoors_with_robust_learning_rate_tpu.attack import (
        registry as attack_registry)
    return (cfg.churn_enabled or cfg.traffic_enabled
            or attack_registry.needs_round(cfg))


def vmap_agents(local_train, params, imgs, lbls, sizes, keys,
                chunk: int = 0, ep_budget=None):
    """vmap local training over the leading agents axis, optionally in
    sequential chunks of `chunk` agents (`lax.map` over chunk groups).

    Chunking is the HBM lever for big models: peak activation memory scales
    with the number of simultaneously-trained agents (40 agents x bs 256 of
    ResNet-9 stashes ~19 GB — over a v5e chip's 16 GB), so `--agent_chunk c`
    trades a factor m/c of round latency for a factor m/c of activation
    memory. Results are independent of the chunking (each agent's training
    is independent); chunk must divide the (per-device) agent count.

    `ep_budget` ([m] int32, faults/) rides the same agents axis when the
    straggler fault is configured — local_train then takes it as a sixth
    per-agent argument."""
    extra = () if ep_budget is None else (ep_budget,)
    if getattr(local_train, "sequential", False):
        # a model whose operations do not batch over a client axis (the
        # token model's grouped products) trains a block's clients one
        # after another; a block of one is traced flat (a rolled loop
        # keeps its carry in buffers of its own: ops/loops.py)
        def vt(params, *per_agent):
            if per_agent[0].shape[0] == 1:
                return jax.tree_util.tree_map(
                    lambda a: a[None],
                    local_train(params, *(a[0] for a in per_agent)))
            return jax.lax.map(lambda a: local_train(params, *a), per_agent)
    else:
        vt = jax.vmap(local_train,
                      in_axes=(None,) + (0,) * (4 + len(extra)))
    m = imgs.shape[0]
    if 0 < chunk < m and m % chunk != 0:
        # falling back to the full block would reproduce the exact
        # compile-time OOM this flag exists to prevent — fail loudly
        raise ValueError(
            f"--agent_chunk {chunk} does not divide the agent block of {m} "
            f"(per-device agent count); pick a divisor or 0 for the full "
            f"block")
    if chunk <= 0 or chunk >= m:
        return vt(params, imgs, lbls, sizes, keys, *extra)
    nc = m // chunk

    def resh(a):
        return a.reshape((nc, chunk) + a.shape[1:])

    def body(carry, args):
        return carry, vt(params, *args)

    # routed through maybe_unrolled_scan: XLA:CPU executes convs inside
    # while-loops via a slow reference path (ops/loops.py), so short chunk
    # loops are traced flat on the CPU backend
    _, (updates, losses) = loops.maybe_unrolled_scan(
        body, 0, tuple(resh(a) for a in (imgs, lbls, sizes, keys) + extra),
        loops.cpu_backend() and nc <= 16)
    flat = functools.partial(
        jax.tree_util.tree_map, lambda u: u.reshape((m,) + u.shape[2:]))
    # `losses` is [m], or the task's dict of per-client values (fl/task.py)
    return flat(updates), flat(losses)


def make_block_trainer(model, cfg, normalize):
    """The client-block trainer every round builder (vmap/sharded/host/
    cohort x per-round/chained) takes:
    train_block(params, imgs, lbls, sizes, keys, chunk=0, ep_budget=None)
    -> (updates [m, ...]-stacked, losses [m]), `vmap_agents` over the
    per-client local_train."""
    local_train = make_local_train(model, cfg, normalize)

    def train_block(params, imgs, lbls, sizes, keys, chunk=0,
                    ep_budget=None):
        return vmap_agents(local_train, params, imgs, lbls, sizes, keys,
                           chunk, ep_budget=ep_budget)
    return train_block


def _fold_core(params, k_train, k_noise, imgs, lbls, sizes, *, train_block,
               cfg, corrupt_flags=None, rnd=None):
    """The round as a fold (ROADMAP R3): a scan over chunks of
    `--agent_chunk` clients (one at a time where it is unset) that carries
    (sum of n_k u_k, sum of sign(u_k), sum of n_k) and the per-client lanes
    that are functions of one update, and never stacks; then the same tail
    as the stacked round. `local_train` and `aggregate_rlr` are siblings in
    the scan body, so a trace files the fold's adds under the server step.
    What a fold cannot do is refused before any build
    (utils/compile_cache.unsupported)."""
    from defending_against_backdoors_with_robust_learning_rate_tpu.attack import (
        registry as attack_registry)
    m = imgs.shape[0]
    chunk = cfg.agent_chunk if 0 < cfg.agent_chunk < m else 1
    if m % chunk:
        raise ValueError(
            f"--agent_chunk {chunk} does not divide the {m} clients of a "
            f"round")
    nc = m // chunk
    keys = jax.random.split(k_train, m)
    flags = (jnp.zeros((m,), bool) if corrupt_flags is None
             else corrupt_flags)
    want_sign = cfg.robustLR_threshold > 0 or cfg.aggr == "sign"
    health = health_sentinel.health_on(cfg)

    def body(acc, args):
        im, lb, sz, ky, fl = args
        with jax.named_scope("local_train"):
            updates, per = train_block(params, im, lb, sz, ky)
        if attack_registry.in_jit(cfg):
            updates = attack_registry.apply_update_attack(
                cfg, updates, fl, attack_registry.schedule_active(cfg, rnd))
        with jax.named_scope("aggregate_rlr"):
            acc = fold_updates(acc, updates, sz)
        lanes = ()
        if health:
            with jax.named_scope("health"):
                lanes = health_sentinel._row_stats(updates)
        return acc, (per, lanes)

    def resh(a):
        return a.reshape((nc, chunk) + a.shape[1:])

    acc, (per, lanes) = loops.maybe_unrolled_scan(
        body, fold_init(params, m, cfg.aggr == "avg", want_sign),
        tuple(resh(a) for a in (imgs, lbls, sizes, keys, flags)),
        loops.cpu_backend() and nc <= 16)
    per, lanes = jax.tree_util.tree_map(
        lambda a: a.reshape((m,) + a.shape[2:]), (per, lanes))
    losses, sums = task.split_per_client(per)
    with jax.named_scope("aggregate_rlr"):
        lr, agg = fold_finish(
            acc, cfg, k_noise,
            float(cfg.robustLR_threshold) if cfg.robustLR_threshold > 0
            else None, cfg.effective_server_lr)
        new_params = apply_aggregate(params, lr, agg)
    extras = task.round_counters(sums)
    if health:
        with jax.named_scope("health"):
            extras.update(health_sentinel.from_row_stats(*lanes, new_params))
    return new_params, jnp.mean(losses), extras


def _round_core(params, k_train, k_noise, imgs, lbls, sizes, *,
                train_block, cfg, corrupt_flags=None, churn_active=None,
                rnd=None, astate=None, knobs=None):
    """Shared round body: vmapped local training + aggregation + update.

    With faults configured (cfg.faults_enabled) the round additionally
    draws the per-agent fault pattern from the round key (faults/model.py),
    truncates stragglers' epochs, injects corrupt payloads, validates
    payloads server-side, and aggregates over the resulting participation
    mask (faults/masking.py). `corrupt_flags` marks which sampled slots
    hold malicious agents (for --faults_spare_corrupt).

    `churn_active` ([m] bool, service/churn.py: the sampled clients'
    lifecycle availability this round) ANDs into the same participation
    mask — an away client's update never reaches aggregation, exactly
    like a dropped one, with zero extra collectives. A churn-only round
    (no fault rates) routes through the masking path too; an all-away
    cohort degrades to a parameter-preserving no-op via guard_empty.

    An in-jit attack strategy (attack/registry.py) transforms the
    corrupt rows right after local training — BEFORE fault injection and
    server-side payload validation, so --payload_norm_cap and the robust
    aggregators see the attacker's payload the way a real server would.
    `rnd` (traced int32, or None when the step has no round channel)
    feeds the attack schedule gate.

    `astate` (fl/buffered.py carried buffer state) routes the aggregation
    tail through the buffered-async fold instead of the immediate
    aggregate+apply; the straggler draw then delays the upload (latency
    draw) instead of truncating epochs, and the return grows a fourth
    element (the advanced buffer state).

    `knobs` (fl/tenancy.TenantKnobs of traced scalars — this tenant's
    slice of the pack's [E]-vectors, arriving through the tenant vmap)
    overrides the per-experiment scalar constants the solo paths bake in:
    server_lr, the RLR threshold, the attack boost and the schedule
    window. None (every solo path) keeps the Python constants — the
    traced program is bit-for-bit the historical one."""
    if cfg.agg_path == "fold":
        # resolved by whoever built this cfg (the engine and the planner,
        # through compile_cache.resolved_agg); `auto` that reaches here
        # unresolved keeps the stack, as every round did before the fold
        if astate is not None or knobs is not None:
            raise ValueError("a folded round has no buffered or "
                             "tenant-packed family")
        return _fold_core(params, k_train, k_noise, imgs, lbls, sizes,
                          train_block=train_block, cfg=cfg,
                          corrupt_flags=corrupt_flags, rnd=rnd)
    m = imgs.shape[0]
    agent_keys = jax.random.split(k_train, m)
    draw = None
    ep_budget = None
    if cfg.faults_enabled:
        from defending_against_backdoors_with_robust_learning_rate_tpu.faults import (
            model as fmodel)
        draw = fmodel.sample_faults(cfg, fmodel.fault_key(k_noise), m,
                                    corrupt_flags)
        if cfg.straggler_rate > 0:
            # buffered mode repurposes the straggler flags as the arrival
            # latency draw — a slow client uploads LATE (full epochs)
            # instead of truncated; the builder's signature still takes
            # the budget, so hand it the full-epoch constant
            ep_budget = (draw.ep_budget if astate is None
                         else jnp.full((m,), cfg.local_ep, jnp.int32))
    with jax.named_scope("local_train"):
        updates, per = train_block(params, imgs, lbls, sizes,
                                   agent_keys, cfg.agent_chunk,
                                   ep_budget=ep_budget)
    losses, sums = task.split_per_client(per)
    from defending_against_backdoors_with_robust_learning_rate_tpu.attack import (
        registry as attack_registry)
    if attack_registry.in_jit(cfg):
        if knobs is not None:
            # tenant pack: every tenant carries its own schedule triple
            # and boost as traced knobs (attack/schedule.active_traced —
            # the trivial (0, 0, 1) triple evaluates to always-on, so
            # unscheduled tenants match the solo gate-free fast path)
            from defending_against_backdoors_with_robust_learning_rate_tpu.attack import (
                schedule as attack_schedule)
            gate = attack_schedule.active_traced(
                knobs.attack_start, knobs.attack_stop, knobs.attack_every,
                rnd)
            updates = attack_registry.apply_update_attack(
                cfg, updates, corrupt_flags, gate,
                boost=knobs.attack_boost)
        else:
            updates = attack_registry.apply_update_attack(
                cfg, updates, corrupt_flags,
                attack_registry.schedule_active(cfg, rnd))
    mask = None
    extras = task.round_counters(sums)
    if draw is not None:
        from defending_against_backdoors_with_robust_learning_rate_tpu.faults import (
            masking, model as fmodel)
        if cfg.corrupt_rate > 0:
            updates = fmodel.inject_corrupt(updates, draw.corrupt,
                                            cfg.corrupt_mode)
        mask = draw.participate & fmodel.payload_valid(
            updates, cfg.payload_norm_cap)
        extras.update(fmodel.fault_scalars(draw, mask))
    if churn_active is not None:
        from defending_against_backdoors_with_robust_learning_rate_tpu.faults import (
            masking)
        from defending_against_backdoors_with_robust_learning_rate_tpu.service import (
            churn as churn_mod)
        mask = churn_active if mask is None else mask & churn_active
        # the mask always joins aggregation (cohort shortfall padding
        # rides it too), but Churn/* and churn-shaped Faults/* series
        # are emitted only when churn is actually configured — a plain
        # cohort run must not grow series that make it read as a churn
        # or faults run (its padding already shows in fault_voters
        # whenever faults are on)
        if draw is not None:
            extras["fault_voters"] = masking.count_f32(mask)
            if cfg.churn_enabled:
                extras["churn_away"] = churn_mod.churn_away(churn_active)
        elif cfg.churn_enabled:
            extras.update(
                churn_mod.churn_only_scalars(churn_active, mask))
    if astate is not None:
        # buffered-async tail (fl/buffered.py): this tick's updates fold
        # into the carried buffer by arrival level; params advance only
        # when the commit gate fires. lr/agg are the buffer's current
        # vote — telemetry describes the commit decision either way.
        with jax.named_scope("buffered_fold"):
            T = buffered.latency(
                cfg, k_noise, draw.straggler if draw is not None else None)
            contribs = buffered.tick_contributions(cfg, updates, sizes,
                                                   mask, T)
            new_params, new_astate, lr, agg, a_extras, vote_sign = \
                buffered.fold_commit(cfg, params, astate, contribs,
                                     k_noise, m, knobs=knobs)
        extras.update(a_extras)
        if cfg.telemetry != "off":
            from defending_against_backdoors_with_robust_learning_rate_tpu.obs import (
                telemetry)
            extras.update(telemetry.compute(
                cfg, updates, lr if cfg.robustLR_threshold > 0 else None,
                agg, mask=mask, corrupt_flags=corrupt_flags,
                sign_sums=vote_sign,
                vote_range=buffered.vote_range(cfg)))
        from defending_against_backdoors_with_robust_learning_rate_tpu.obs import (
            reputation as rep_mod)
        if rep_mod.reputation_on(cfg):
            # agreement vs the BUFFER's accumulated sign vote (the
            # electorate the commit decision actually thresholds) —
            # elementwise vs the replicated vote_sign tree, zero
            # collectives
            from defending_against_backdoors_with_robust_learning_rate_tpu.faults import (
                masking)
            u_rep = (updates if mask is None
                     else masking.zero_masked(updates, mask))
            extras["rep_agree"] = rep_mod.agree_rows(u_rep, vote_sign,
                                                     mask=mask)
            extras["rep_norm"] = rep_mod.norm_rows(u_rep, mask=mask)
        if health_sentinel.health_on(cfg):
            with jax.named_scope("health"):
                extras.update(health_sentinel.sentinel(
                    cfg, updates, new_params, mask=mask))
        return new_params, jnp.mean(losses), extras, new_astate
    slr = (cfg.effective_server_lr if knobs is None
           else knobs.server_lr)
    with jax.named_scope("aggregate_rlr"):
        if cfg.robustLR_threshold > 0:
            thr_base = None if knobs is None else knobs.rlr_threshold
            thr = (masking.rlr_threshold(cfg, mask, base=thr_base)
                   if mask is not None
                   else (float(cfg.robustLR_threshold)
                         if knobs is None else knobs.rlr_threshold))
            lr = robust_lr(updates, thr, slr, mask=mask)
        else:
            lr = slr
        agg = aggregate_updates(updates, sizes, cfg, k_noise, mask=mask)
        if mask is not None:
            # all payloads dropped/rejected -> zero aggregate, no-op round
            agg = masking.guard_empty(agg, mask)
        new_params = apply_aggregate(params, lr, agg)
    if cfg.telemetry != "off":
        from defending_against_backdoors_with_robust_learning_rate_tpu.obs import (
            telemetry)
        extras.update(telemetry.compute(
            cfg, updates, lr if cfg.robustLR_threshold > 0 else None, agg,
            mask=mask, corrupt_flags=corrupt_flags))
    from defending_against_backdoors_with_robust_learning_rate_tpu.obs import (
        reputation as rep_mod)
    if rep_mod.reputation_on(cfg):
        # per-client agreement vs the committed sign vote: derived from
        # the SAME zero-masked updates the vote counted, so the
        # electorate matches robust_lr's — elementwise reductions only,
        # zero collectives (the *_rep CheckSpec pins)
        if mask is not None:
            from defending_against_backdoors_with_robust_learning_rate_tpu.faults import (
                masking)
            u_rep = masking.zero_masked(updates, mask)
        else:
            u_rep = updates
        extras["rep_agree"] = rep_mod.agree_rows(
            u_rep, rep_mod.sign_sums_from(u_rep), mask=mask)
        extras["rep_norm"] = rep_mod.norm_rows(u_rep, mask=mask)
    if cfg.diagnostics:
        from defending_against_backdoors_with_robust_learning_rate_tpu.fl.diagnostics import (
            per_agent_norms)
        from jax.flatten_util import ravel_pytree
        extras["agent_norms"] = per_agent_norms(updates)
        if cfg.robustLR_threshold > 0:
            extras["lr_flat"] = ravel_pytree(lr)[0]
    if health_sentinel.health_on(cfg):
        with jax.named_scope("health"):
            extras.update(health_sentinel.sentinel(
                cfg, updates, new_params, mask=mask))
    return new_params, jnp.mean(losses), extras


def make_chained(step, data, family: str = "chained"):
    """Wrap a step(params, key, *data) fn into chained(params, base_key,
    round_ids): a `lax.scan` over rounds, round r keyed by
    `fold_in(base_key, r)` (the driver loop's exact derivation — chained
    blocks match per-round dispatch to ~1 ulp — same ops and keys,
    fusion may round differently). Shared by the
    single-device and sharded paths; info is reduced to the scannable
    train_loss/sampled leaves.

    `data` (the K-agent dataset stacks) is bound OUTSIDE the jit and passed
    as arguments at call time: a jit-closed-over array is inlined into the
    lowered program as a dense constant — for fedemnist-scale stacks that
    is a ~0.5 GiB HLO no compile service should (or will) swallow."""
    # churn steps take the round index (the scan already carries it)
    takes_round = getattr(step, "takes_round", False)

    @functools.partial(jax.jit, donate_argnums=0)
    def chained(params, base_key, round_ids, *data_args):
        def body(params, rnd):
            lead = (rnd,) if takes_round else ()
            new_params, info = step(params, jax.random.fold_in(base_key, rnd),
                                    *lead, *data_args)
            out = {"train_loss": info["train_loss"],
                   "sampled": info["sampled"]}
            out.update({k: info[k] for k in CHAINED_INFO_KEYS if k in info})
            # telemetry, health-sentinel and reputation ([m] rep_agree)
            # values ride the scan stacked per-round, like the fault
            # counters
            out.update({k: v for k, v in info.items()
                        if k.startswith(("tel_", "hlth_", "rep_"))})
            return new_params, out

        # XLA:CPU conv-in-while slow path (ops/loops.py): unroll short
        # chains; each chain step is a whole round so the cap stays small
        py_loops = loops.cpu_backend() and round_ids.shape[0] <= 16
        return loops.maybe_unrolled_scan(body, params, round_ids, py_loops)

    def bound(params, base_key, round_ids):
        return chained(params, base_key, round_ids, *data)

    bound.jitted, bound.data = chained, data   # for lowering-size tests
    bound.family = family   # AOT manifest name (utils/compile_cache.py)
    return bound


def _make_sample_step(cfg, model, normalize):
    """Shared sample-and-step fn: step(params, key, images, labels, sizes).

    Samples the round's m agents, gathers their device-resident shards
    in-jit, and runs the round core. The key-derivation order (sample, train,
    noise) matches parallel/rounds.py so the sharded and single-device paths
    are comparable round-for-round — and both the per-round and chained fns
    wrap THIS fn, which is what makes chained execution match
    per-round dispatch (same ops/keys; ~1 ulp fusion differences).

    The dataset stacks are ARGUMENTS, not closure captures: jit inlines
    closed-over arrays into the lowered HLO as dense constants (measured
    ~1 GiB of StableHLO for the fedemnist stacks, rejected by remote
    compile services and re-shipped on every compile)."""
    train_block = make_block_trainer(model, cfg, normalize)
    K, m = cfg.num_agents, cfg.agents_per_round
    is_async = buffered.is_buffered(cfg)

    def body(carry, key, rnd, images, labels, sizes):
        # buffered mode: the step's first argument is the (params,
        # buffer-state) carry — one pytree the chained scan, the AOT
        # avals, checkpointing and donation all treat as "the params"
        params, astate = carry if is_async else (carry, None)
        k_sample, k_train, k_noise = jax.random.split(key, 3)
        with jax.named_scope("sample_gather"):
            sampled = jax.random.permutation(k_sample, K)[:m]
            imgs = jnp.take(images, sampled, axis=0)
            lbls = jnp.take(labels, sampled, axis=0)
            szs = jnp.take(sizes, sampled, axis=0)
        # faults need the corrupt-slot flags for participation; full
        # telemetry needs them for the honest/corrupt cosine split
        # (host_takes_flags is the single source of that condition)
        want_flags = host_takes_flags(cfg)
        churn_active = None
        if cfg.churn_enabled:
            from defending_against_backdoors_with_robust_learning_rate_tpu.service import (
                churn as churn_mod)
            with jax.named_scope("churn_mask"):
                churn_active = churn_mod.active_slots(cfg, sampled, rnd)
        if cfg.traffic_enabled:
            # diurnal traffic presence (data/traffic.py) composes into
            # the same participation mask as churn — an unreachable
            # client is excluded arithmetically, zero extra collectives
            from defending_against_backdoors_with_robust_learning_rate_tpu.data import (
                traffic as traffic_mod)
            with jax.named_scope("traffic_mask"):
                t_present = traffic_mod.present_slots(cfg, sampled, rnd)
            churn_active = (t_present if churn_active is None
                            else churn_active & t_present)
        if health_sentinel.has_quarantine(cfg):
            # quarantined clients (health/monitor.py QUARANTINE rung)
            # leave the electorate through the participation mask — a
            # traced-constant membership test, the churn protocol
            qmask = health_sentinel.quarantine_mask(cfg, sampled)
            churn_active = (qmask if churn_active is None
                            else churn_active & qmask)
        res = _round_core(
            params, k_train, k_noise, imgs, lbls, szs,
            train_block=train_block, cfg=cfg,
            corrupt_flags=(sampled < cfg.num_corrupt
                           if want_flags else None),
            churn_active=churn_active, rnd=rnd, astate=astate)
        if is_async:
            new_params, train_loss, extras, new_astate = res
            return ((new_params, new_astate),
                    {"train_loss": train_loss, "sampled": sampled,
                     **extras})
        new_params, train_loss, extras = res
        return new_params, {"train_loss": train_loss, "sampled": sampled,
                            **extras}

    if step_takes_round(cfg):
        # churn — and a scheduled in-jit attack — need the round index
        # in-program (the lifecycle phase / attack window is a function
        # of time, not of the round key): the step grows a traced int32
        # `rnd` argument, threaded by the driver / the chained scan
        def step(params, key, rnd, images, labels, sizes):
            return body(params, key, rnd, images, labels, sizes)
        step.takes_round = True
        return step

    def step(params, key, images, labels, sizes):
        return body(params, key, jnp.int32(0), images, labels, sizes)
    step.takes_round = False
    return step


def bind_data(step_jit, data, family: str = "round"):
    """(params, key[, rnd], *data) jitted fn -> (params, key[, rnd]) fn
    with the dataset stacks bound at call time (passed as jit arguments
    every call; one compilation serves every round since shapes never
    change). The optional `rnd` lead argument is the churn path's round
    index (service/churn.py)."""
    def bound(params, key, *lead):
        return step_jit(params, key, *lead, *data)

    bound.jitted, bound.data = step_jit, data   # for lowering-size tests
    bound.family = family   # AOT manifest name (utils/compile_cache.py)
    return bound


def make_round_fn(cfg, model, normalize, images, labels, sizes):
    """Device-resident round fn: round(params, key) -> (params, metrics).

    images/labels/sizes are the full K-agent stacked arrays (jnp, on device).
    """
    from defending_against_backdoors_with_robust_learning_rate_tpu.utils import (
        compile_cache)
    return bind_data(jax.jit(_make_sample_step(cfg, model, normalize)),
                     (images, labels, sizes),
                     family=("round_diag" if cfg.diagnostics
                             else "round"
                             + compile_cache.family_suffix(cfg)))


def make_chained_round_fn(cfg, model, normalize, images, labels, sizes):
    """Round-chained fn: chained(params, base_key, round_ids) -> (params, info).

    Fuses a whole block of FL rounds into ONE compiled program via `lax.scan`
    over the round ids — the per-round host dispatch of the reference loop
    (src/federated.py:65) disappears entirely. Round r's key is
    `fold_in(base_key, r)`, exactly the driver loop's derivation, so a chained
    block matches dispatching the same rounds one at a time (~1 ulp).

    info leaves are stacked per-round ([n_chain, ...]). Diagnostics extras are
    not supported here (the driver runs diagnostic snap rounds unchained).
    """
    from defending_against_backdoors_with_robust_learning_rate_tpu.utils import (
        compile_cache)
    plain = cfg.replace(diagnostics=False)
    return make_chained(_make_sample_step(plain, model, normalize),
                        (images, labels, sizes),
                        family="chained"
                        + compile_cache.family_suffix(plain))


def make_host_step(cfg, model, normalize, take_flags=None):
    """Unjitted host-sampled step(params, key, imgs, lbls, sizes) — the
    shared body of the per-round and chained host fns (key split into
    k_train/k_noise matches bit-for-bit between them).

    With faults — or full telemetry — configured the step takes a sixth
    argument: the [m] bool `corrupt_flags` for the sampled slots (the
    driver computes it from the host-sampled ids — in-jit sampling isn't
    available to derive it here; single source: `host_takes_flags`).
    `take_flags=False` forces the flag-free signature: the chained host
    scan has no per-round flag channel, so it degrades the telemetry
    cosine split to all-honest instead of changing its calling
    convention."""
    if cfg.churn_enabled:
        # the host-sampled program never sees the sampled client ids, so
        # the in-program lifecycle draw has nothing to hash; host-side
        # churn-aware cohorting is future work (ROADMAP). Fail loudly
        # rather than silently running a churn-free round.
        raise ValueError(
            "client churn (--churn_available < 1) is not supported in "
            "host-sampled mode; run device-resident (--host_sampled off)")
    if cfg.traffic_enabled:
        # same contract as churn: the diurnal presence draw needs the
        # sampled client ids, which the host-sampled program never sees
        raise ValueError(
            "diurnal traffic (--traffic diurnal) is not supported in "
            "host-sampled mode; run device-resident or cohort-sampled")
    if buffered.is_buffered(cfg):
        # same contract as churn: the buffered arrival draw and carried
        # buffer have no host-sampled channel (fl/buffered.check names
        # the remediation) — fail loudly rather than silently syncing
        raise ValueError(
            "--agg_mode buffered is not supported in host-sampled mode; "
            "run device-resident (--host_sampled off) or cohort-sampled "
            "(--cohort_sampled on)")
    if health_sentinel.has_quarantine(cfg):
        # same contract as churn: the host-sampled program never sees the
        # sampled client ids the quarantine membership test hashes
        raise ValueError(
            "--quarantine is not supported in host-sampled mode (the "
            "program never sees the sampled client ids); run "
            "device-resident (--host_sampled off) or cohort-sampled")
    from defending_against_backdoors_with_robust_learning_rate_tpu.attack import (
        registry as attack_registry)
    if attack_registry.needs_round(cfg):
        # same contract as churn: the per-round host step has no round
        # channel for the schedule gate to read. Fail loudly rather than
        # silently running the attack always-on (or never).
        raise ValueError(
            f"--attack {cfg.attack} with a schedule "
            f"(attack_start/attack_stop/attack_every) is not supported "
            f"in host-sampled mode; run device-resident "
            f"(--host_sampled off) or cohort-sampled")
    if take_flags is False and attack_registry.in_jit(cfg):
        # the chained host scan has no per-round flag channel; a silently
        # unapplied attack would corrupt every scenario row downstream
        raise ValueError(
            f"--attack {cfg.attack} transforms updates in-jit and needs "
            f"the corrupt-slot flags, which the chained host scan does "
            f"not carry — the driver must dispatch host-sampled attack "
            f"rounds unchained (train.py disables --chain here)")
    train_block = make_block_trainer(model, cfg, normalize)
    if take_flags is None:
        take_flags = host_takes_flags(cfg)

    if take_flags:
        def step(params, key, imgs, lbls, sizes, corrupt_flags):
            k_train, k_noise = jax.random.split(key)
            new_params, train_loss, extras = _round_core(
                params, k_train, k_noise, imgs, lbls, sizes,
                train_block=train_block, cfg=cfg,
                corrupt_flags=corrupt_flags)
            return new_params, {"train_loss": train_loss, **extras}
        return step

    def step(params, key, imgs, lbls, sizes):
        k_train, k_noise = jax.random.split(key)
        new_params, train_loss, extras = _round_core(
            params, k_train, k_noise, imgs, lbls, sizes,
            train_block=train_block, cfg=cfg)
        return new_params, {"train_loss": train_loss, **extras}

    return step


def make_round_fn_host(cfg, model, normalize):
    """Host-sampled round fn: round(params, key, imgs, lbls, sizes).

    The driver samples agent ids and gathers their shards host-side (the
    fedemnist path: 3383 users, 1% sampled per round, src/runner.sh:34)."""
    return jax.jit(make_host_step(cfg, model, normalize))


def make_chained_host(step):
    """Wrap an unjitted host step into chained(params, base_key, round_ids,
    imgs, lbls, sizes) over [chain, m, ...] shard-stack blocks: a `lax.scan`
    whose round r consumes block row r and key `fold_in(base_key, r)` — the
    driver loop's exact derivation, so a chained host block matches
    dispatching the same rounds one at a time (~1 ulp fusion differences).

    This lifts the r2 restriction that host-sampled mode pays one host
    dispatch + gather per round (the fedemnist-scale path, ref
    src/runner.sh:34-38 at 500 rounds): the driver prefetches a whole
    block's shard stacks and the TPU runs `chain` rounds per dispatch.
    Shared by the single-device and sharded host paths — and by the
    cohort-sampled steps (data/cohort.py), whose ``takes_round`` signature
    gets the scanned round index threaded through (the scan already
    carries it), so a chained cohort block recomputes its per-round
    cohort ids, corrupt flags and churn mask in-program."""
    takes_round = getattr(step, "takes_round", False)

    @functools.partial(jax.jit, donate_argnums=0)
    def chained(params, base_key, round_ids, imgs, lbls, sizes):
        def body(params, xs):
            rnd, im, lb, sz = xs
            lead = (rnd,) if takes_round else ()
            new_params, info = step(
                params, jax.random.fold_in(base_key, rnd), *lead, im, lb, sz)
            out = {"train_loss": info["train_loss"]}
            out.update({k: info[k] for k in CHAINED_INFO_KEYS if k in info})
            out.update({k: v for k, v in info.items()
                        if k.startswith(("tel_", "hlth_", "rep_"))})
            return new_params, out

        # XLA:CPU conv-in-while slow path (ops/loops.py): unroll short chains
        py_loops = loops.cpu_backend() and round_ids.shape[0] <= 16
        return loops.maybe_unrolled_scan(
            body, params, (round_ids, imgs, lbls, sizes), py_loops)

    return chained


def make_chained_round_fn_host(cfg, model, normalize):
    """Chained host-sampled rounds: chained(params, base_key, round_ids,
    imgs, lbls, sizes) with [chain, m, ...] blocks (diagnostics unsupported;
    the driver runs diagnostic snap rounds unchained). take_flags=False:
    the scan carries no per-round corrupt flags (under faults the driver
    disables host chaining entirely; under full telemetry the cosine
    split degrades to all-honest)."""
    return make_chained_host(
        make_host_step(cfg.replace(diagnostics=False), model, normalize,
                       take_flags=False))


# ------------------------------------------------------- cohort-sampled ---

def make_cohort_step(cfg, model, normalize):
    """Unjitted cohort-sampled step(params, key, rnd, imgs, lbls, sizes) —
    the population/cohort-split round body (ISSUE 7).

    Data arrives host-gathered like the host-sampled path (fixed [m, ...]
    stacks from the client bank, data/bank.py), but the cohort ids are
    recomputed IN-PROGRAM from the traced round index (data/cohort.py) —
    the same seeded draw the driver's gather mirrored — so:

    - corrupt flags are real client ids (``ids < num_corrupt``), making
      Defense/* cosine splits and Faults/* rates functions of cohort
      MEMBERSHIP (a round that samples no corrupt client reports a zero
      corrupt electorate, test-pinned);
    - the churn lifecycle mask composes (cohorts are sampled from the
      churn-present set — the host-sampled + churn refusal is retired);
    - the chained scan needs no flag side-channel: flags re-derive from
      the scanned round index, so chaining survives faults and full
      telemetry keeps its honest/corrupt split.

    The [m] ``active`` mask (False = duplicate / churn-absent shortfall
    padding) always joins the participation-mask protocol: padded slots
    are excluded from aggregation arithmetically, like dropped clients."""
    from defending_against_backdoors_with_robust_learning_rate_tpu.data import (
        cohort as cohort_mod)
    train_block = make_block_trainer(model, cfg, normalize)
    want_flags = host_takes_flags(cfg)
    is_async = buffered.is_buffered(cfg)

    def step(carry, key, rnd, imgs, lbls, sizes):
        params, astate = carry if is_async else (carry, None)
        with jax.named_scope("cohort_sample"):
            ids, active = cohort_mod.sample_cohort(cfg, rnd)
        if health_sentinel.has_quarantine(cfg):
            # quarantined cohort members join the shortfall-padding /
            # churn-absence protocol: excluded from aggregation through
            # the active mask, zero extra collectives
            active = active & health_sentinel.quarantine_mask(cfg, ids)
        k_train, k_noise = jax.random.split(key)
        res = _round_core(
            params, k_train, k_noise, imgs, lbls, sizes,
            train_block=train_block, cfg=cfg,
            corrupt_flags=((ids < cfg.num_corrupt) & active
                           if want_flags else None),
            churn_active=active, rnd=rnd, astate=astate)
        if is_async:
            new_params, train_loss, extras, new_astate = res
            return ((new_params, new_astate),
                    {"train_loss": train_loss, "sampled": ids, **extras})
        new_params, train_loss, extras = res
        return new_params, {"train_loss": train_loss, "sampled": ids,
                            **extras}

    step.takes_round = True
    return step


def make_cohort_round_fn(cfg, model, normalize):
    """Cohort-sampled round fn: round(params, key, rnd, imgs, lbls, sizes).
    The driver mirrors the in-program draw (data/cohort.sample_cohort) to
    gather the cohort's bank rows; one compilation serves every round."""
    return jax.jit(make_cohort_step(cfg, model, normalize))


def make_chained_cohort_round_fn(cfg, model, normalize):
    """Chained cohort rounds: chained(params, base_key, round_ids, imgs,
    lbls, sizes) over [chain, m, ...] bank-row blocks. Unlike the plain
    host chain, faults and the full-telemetry cosine split survive
    chaining — the scanned round index re-derives the flags in-program."""
    return make_chained_host(
        make_cohort_step(cfg.replace(diagnostics=False), model, normalize))
