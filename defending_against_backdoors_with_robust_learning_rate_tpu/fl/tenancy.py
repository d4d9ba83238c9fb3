"""Multi-tenant tenant-pack round programs — the EXPERIMENT axis folded
into one resident program (ISSUE 13).

The scenario matrix (scripts/sweep_scenarios.py) is thousands of small
cells, and the experiment queue used to run them strictly back-to-back:
one small CNN per dispatch leaves the chip idle. This module makes the
Podracer play (arXiv:2104.06272: saturate accelerators by stacking many
small workloads into one resident program): E independent experiment
replicas that SHARE program shapes (same dataset, model, aggregation
rule, fault/churn/attack structure) run as a leading tenant axis of ONE
jitted round program. Per-tenant
params advance as a stacked [E, ...] pytree; cohorts are sampled, locally
trained, fault-injected and aggregated together; metrics fan back out per
tenant through the existing MetricsDrain (service/tenancy.py).

What varies per tenant — the *scalar knobs* — enters as traced
[E]-vectors (`TenantKnobs`), so one compiled program serves the whole
pack AND every pack of the same shape:

    seed          per-tenant base key stream (params init + sampling +
                  training keys; keys are program ARGUMENTS, like solo)
    server_lr     the effective server LR (the aggr=='sign' rule is
                  resolved per tenant host-side)
    robustLR_threshold   the RLR vote threshold (a pack mixing defended
                  and undefended tenants builds the vote once; a tenant
                  with threshold 0 gets lr=+server_lr on every
                  coordinate — arithmetically the undefended update)
    attack_boost / attack_start / attack_stop / attack_every
                  the in-jit attack scale + schedule window
                  (attack/schedule.active_traced; the trivial (0, 0, 1)
                  triple evaluates to always-on)

Knobs that change SHAPES or program structure (dataset, m, bs, aggr,
telemetry level, fault rates, churn process, attack strategy)
stay queue-level: the pack key (utils/compile_cache.tenant_pack_key) is
derived from the AOT fingerprint's own field algebra, so shape- or
program-incompatible cells can never share a pack.

Exactness semantics: the tenant programs run the SAME ops with the same
keys as the solo paths — per-tenant metrics are ulp-close to solo runs
(vmap batching may re-associate reductions), and integer sign-vote
arithmetic is exact. Dataset
CONTENT is built once from the pack's base config: for disk-backed
datasets it is seed-free; the synthetic fallback draws from the base
seed, so per-tenant seeds vary the key streams, not the data
(tests/test_tenancy.py pins the parity contract).
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from defending_against_backdoors_with_robust_learning_rate_tpu.fl.rounds import (
    CHAINED_INFO_KEYS, _round_core, host_takes_flags, make_block_trainer)
from defending_against_backdoors_with_robust_learning_rate_tpu.ops import loops

# the per-tenant scalar knobs — Config fields a tenant pack vectorizes as
# traced [E]-arrays. Everything else must agree across the pack
# (utils/compile_cache.tenant_pack_key drops exactly this set, plus the
# runtime fields, from the grouping key).
TENANT_KNOB_FIELDS = ("seed", "server_lr", "robustLR_threshold",
                      "attack_boost", "attack_start", "attack_stop",
                      "attack_every")


class TenantKnobs(NamedTuple):
    """The traced per-tenant scalar knobs, one [E]-vector per field (a
    scalar per field inside the tenant vmap). A NamedTuple so it is a
    pytree with a FIXED structure — the AOT fingerprint's arg avals stay
    stable across packs of the same width.

    ``rnd_offset`` is not a Config field: it is the scheduler's slot
    clock (service/scheduler.py). A cell backfilled into slot e at pack
    round p runs with offset -p, so its EFFECTIVE round index
    (rnd + offset) counts 1..rounds exactly like its solo twin — key
    folds, churn lifecycle and attack schedules all consume the
    effective index, keeping backfilled streams solo-exact. Every
    FIFO-path pack runs offset 0, which is arithmetically the historical
    program."""
    server_lr: jnp.ndarray      # [E] f32, the EFFECTIVE server lr
    rlr_threshold: jnp.ndarray  # [E] f32 (0 = undefended tenant)
    attack_boost: jnp.ndarray   # [E] f32
    attack_start: jnp.ndarray   # [E] i32
    attack_stop: jnp.ndarray    # [E] i32
    attack_every: jnp.ndarray   # [E] i32
    rnd_offset: jnp.ndarray     # [E] i32, slot clock (0 = pack clock)


def knob_vectors(cells, rnd_offsets=None) -> TenantKnobs:
    """Stack the E cell configs' scalar knobs into the traced vectors.
    The aggr=='sign' server-LR rule (config.effective_server_lr) is
    resolved here, per tenant, host-side. ``rnd_offsets`` is the
    scheduler's per-slot clock skew (None = the FIFO pack's zeros)."""
    E = len(cells)
    if rnd_offsets is None:
        rnd_offsets = [0] * E
    return TenantKnobs(
        server_lr=np.asarray([c.effective_server_lr for c in cells],
                             np.float32),
        rlr_threshold=np.asarray([float(c.robustLR_threshold)
                                  for c in cells], np.float32),
        attack_boost=np.asarray([c.attack_boost for c in cells],
                                np.float32),
        attack_start=np.asarray([c.attack_start for c in cells], np.int32),
        attack_stop=np.asarray([c.attack_stop for c in cells], np.int32),
        attack_every=np.asarray([c.attack_every for c in cells], np.int32),
        rnd_offset=np.asarray(rnd_offsets, np.int32),
    )


def knob_avals(E: int) -> TenantKnobs:
    """Abstract avals of the knob vectors for the AOT planners."""
    f32 = lambda: jax.ShapeDtypeStruct((E,), jnp.float32)  # noqa: E731
    i32 = lambda: jax.ShapeDtypeStruct((E,), jnp.int32)    # noqa: E731
    return TenantKnobs(server_lr=f32(), rlr_threshold=f32(),
                       attack_boost=f32(), attack_start=i32(),
                       attack_stop=i32(), attack_every=i32(),
                       rnd_offset=i32())


def canonical_rep(cfg, cells=None):
    """Normalize a pack-representative config: the knob fields collapse to
    canonical values so two packs differing only in knob VALUES share one
    program (and one AOT fingerprint). The only structural bit a knob
    carries — is the RLR vote built at all — survives as threshold 0/1,
    derived from the pack's cells when given."""
    rlr_on = (cfg.robustLR_threshold > 0 if cells is None
              else any(c.robustLR_threshold > 0 for c in cells))
    return cfg.replace(seed=0, server_lr=1.0,
                       robustLR_threshold=1 if rlr_on else 0,
                       attack_boost=1.0, attack_start=0, attack_stop=0,
                       attack_every=1)


def check(cfg) -> None:
    """Validate a tenant-pack rep config once, loudly, at engine/planner
    construction. Every refusal names its remediation — the queue's
    grouping (service/tenancy.py) routes ineligible cells to the serial
    path instead of crashing the pack."""
    if cfg.tenants < 1:
        raise ValueError(f"a tenant pack needs --tenants >= 1, got "
                         f"{cfg.tenants}")
    # E=1 is the degenerate pack — bit-identity with the untenanted path
    # is test-pinned (tests/test_tenancy.py); the queue still routes
    # singletons through the serial path (no packing win to pay for)
    reason = ineligible_reason(cfg)
    if reason:
        raise ValueError(f"--tenants {cfg.tenants}: {reason}")


def ineligible_reason(cfg) -> str:
    """Why this config's PROGRAM cannot be tenant-packed ('' = eligible).
    The tenant programs cover the device-resident sync surface (faults,
    churn, attacks and telemetry included); everything else keeps its
    solo path. Runtime/driver knobs (host_sampled, mesh) are judged by
    the queue's routing (service/tenancy.serial_reason) — this module is
    in the fingerprint audit's program-read scope and only consults
    program-tagged fields."""
    if cfg.diagnostics:
        return ("--diagnostics needs the per-tenant research scalars the "
                "pack never materializes; run those cells solo")
    if cfg.debug_nan:
        return "--debug_nan (checkify) runs solo"
    # buffered (agg_mode) packs stack the carried (params, state) buffer
    # as a leading [E] axis (ISSUE 16); cohort-sampled packs share ONE
    # bank gather across tenants (the cohort draw is cohort_seed-driven,
    # identical for every tenant at the same effective round) — both are
    # pack-eligible now. The cohort constraint — rnd_offset must be 0 so
    # the shared draw stays shared — is a SCHEDULER admission rule
    # (service/scheduler.py never backfills a cohort pack mid-run), not a
    # program refusal.
    return ""


# --------------------------------------------------------------- programs ---

def make_tenant_step(cfg, model, normalize):
    """The per-tenant solo body the tenant vmap batches:
    step(carry, key, rnd, knobs, images, labels, sizes) ->
    (carry, info). Identical ops and key derivation as
    fl/rounds._make_sample_step's body — that is what makes per-tenant
    results ulp-close to solo runs — with the scalar knobs arriving
    traced instead of baked (fl/rounds._round_core `knobs`). Always takes
    the round index: the churn lifecycle and the per-tenant schedule
    gates consume it, and an unused lead argument is free.

    Two ISSUE-16 extensions, both no-ops on the historical path:
    * the tenant runs on its EFFECTIVE clock rnd + knobs.rnd_offset —
      churn lifecycle and attack schedule gates see the tenant's own
      round index, so a cell backfilled mid-pack is solo-exact
      (offset 0 is arithmetically the old program);
    * buffered mode carries (params, buffer state) as the step carry —
      fold_commit consumes the per-tenant knobs, and the vmapped carry
      stacks both halves along the tenant axis."""
    from defending_against_backdoors_with_robust_learning_rate_tpu.fl import (
        buffered)
    train_block = make_block_trainer(model, cfg, normalize)
    K, m = cfg.num_agents, cfg.agents_per_round
    want_flags = host_takes_flags(cfg)
    is_async = buffered.is_buffered(cfg)

    def step(carry, key, rnd, knobs, images, labels, sizes):
        params, astate = carry if is_async else (carry, None)
        rnd = rnd + knobs.rnd_offset  # the tenant's own round index
        k_sample, k_train, k_noise = jax.random.split(key, 3)
        with jax.named_scope("sample_gather"):
            sampled = jax.random.permutation(k_sample, K)[:m]
            imgs = jnp.take(images, sampled, axis=0)
            lbls = jnp.take(labels, sampled, axis=0)
            szs = jnp.take(sizes, sampled, axis=0)
        churn_active = None
        if cfg.churn_enabled:
            from defending_against_backdoors_with_robust_learning_rate_tpu.service import (
                churn as churn_mod)
            with jax.named_scope("churn_mask"):
                churn_active = churn_mod.active_slots(cfg, sampled, rnd)
        result = _round_core(
            params, k_train, k_noise, imgs, lbls, szs,
            train_block=train_block, cfg=cfg,
            corrupt_flags=(sampled < cfg.num_corrupt
                           if want_flags else None),
            churn_active=churn_active, rnd=rnd, astate=astate, knobs=knobs)
        if is_async:
            new_params, train_loss, extras, new_astate = result
            return (new_params, new_astate), {
                "train_loss": train_loss, "sampled": sampled, **extras}
        new_params, train_loss, extras = result
        return new_params, {"train_loss": train_loss, "sampled": sampled,
                            **extras}

    return step


def _vmap_step(step):
    """Batch the solo body over the leading tenant axis: params/key/knobs
    map per tenant, the round index and the dataset stacks broadcast."""
    return jax.vmap(step, in_axes=(0, 0, None, 0, None, None, None))


def make_tenant_round_fn(cfg, model, normalize, images, labels, sizes):
    """Tenant-pack per-round fn:
    round(params_E, keys_E, rnd, knobs) -> (params_E, info) with info
    leaves [E]-stacked. Dataset stacks are jit ARGUMENTS bound at call
    time (the fl/rounds.bind_data discipline — closure arrays inline into
    the lowered HLO as dense constants)."""
    from defending_against_backdoors_with_robust_learning_rate_tpu.utils import (
        compile_cache)
    vstep = jax.jit(_vmap_step(make_tenant_step(cfg, model, normalize)))

    def bound(params_E, keys_E, rnd, knobs):
        return vstep(params_E, keys_E, rnd, knobs, images, labels, sizes)

    bound.jitted, bound.data = vstep, (images, labels, sizes)
    bound.family = "round" + compile_cache.family_suffix(cfg)
    return bound


def make_tenant_chained_fn(cfg, model, normalize, images, labels, sizes):
    """Tenant-pack chained block:
    chained(params_E, base_keys_E, round_ids, knobs) — a `lax.scan` over
    rounds of the tenant-vmapped body; round r's per-tenant key is
    `fold_in(base_key_e, r + rnd_offset_e)`, the driver loop's exact
    derivation at the tenant's EFFECTIVE round, so a chained pack matches
    dispatching the same pack rounds one at a time (and a backfilled
    tenant's key stream matches its solo twin). The carry — params_E, or
    (params_E, astate_E) in buffered mode — is donated (the
    chained-family contract, analysis/contracts.DONATED_FAMILIES)."""
    from defending_against_backdoors_with_robust_learning_rate_tpu.utils import (
        compile_cache)
    vstep = _vmap_step(make_tenant_step(cfg, model, normalize))

    @functools.partial(jax.jit, donate_argnums=0)
    def chained(params_E, base_keys_E, round_ids, knobs,
                images, labels, sizes):
        def body(params_E, rnd):
            keys = jax.vmap(
                lambda k, off: jax.random.fold_in(k, rnd + off))(
                base_keys_E, knobs.rnd_offset)
            new_params, info = vstep(params_E, keys, rnd, knobs,
                                     images, labels, sizes)
            out = {"train_loss": info["train_loss"],
                   "sampled": info["sampled"]}
            out.update({k: info[k] for k in CHAINED_INFO_KEYS if k in info})
            out.update({k: v for k, v in info.items()
                        if k.startswith(("tel_", "hlth_", "rep_"))})
            return new_params, out

        # XLA:CPU conv-in-while slow path (ops/loops.py): unroll short
        # chains, same cap as the solo chained families
        py_loops = loops.cpu_backend() and round_ids.shape[0] <= 16
        return loops.maybe_unrolled_scan(body, params_E, round_ids,
                                         py_loops)

    def bound(params_E, base_keys_E, round_ids, knobs):
        return chained(params_E, base_keys_E, round_ids, knobs,
                       images, labels, sizes)

    bound.jitted, bound.data = chained, (images, labels, sizes)
    bound.family = "chained" + compile_cache.family_suffix(cfg)
    return bound


def make_tenant_cohort_step(cfg, model, normalize):
    """Per-tenant cohort-sampled body the tenant vmap batches:
    step(carry, key, rnd, knobs, imgs, lbls, sizes) -> (carry, info) —
    fl/rounds.make_cohort_step with the knobs traced (ISSUE 16 gap 3).

    Data arrives as the SHARED [m, ...] cohort stacks, host-gathered ONCE
    per round for the whole pack (vmap broadcasts them): the cohort draw
    (data/cohort.sample_cohort) is cohort_seed-driven — NOT a knob field —
    so every tenant at the same effective round draws the same ids, and
    one indexed bank gather on the prefetch thread serves all E tenants.
    That is also why cohort packs admit no mid-run backfill: a nonzero
    rnd_offset would skew one tenant's draw away from the shared gather
    (service/scheduler.py pins cohort-pack offsets to 0; the in-program
    draw still consumes the effective round so the invariant is 'offsets
    equal', degrading loudly in parity tests rather than silently)."""
    from defending_against_backdoors_with_robust_learning_rate_tpu.data import (
        cohort as cohort_mod)
    from defending_against_backdoors_with_robust_learning_rate_tpu.fl import (
        buffered)
    from defending_against_backdoors_with_robust_learning_rate_tpu.health import (
        sentinel as health_sentinel)
    train_block = make_block_trainer(model, cfg, normalize)
    want_flags = host_takes_flags(cfg)
    is_async = buffered.is_buffered(cfg)

    def step(carry, key, rnd, knobs, imgs, lbls, sizes):
        params, astate = carry if is_async else (carry, None)
        rnd = rnd + knobs.rnd_offset
        with jax.named_scope("cohort_sample"):
            ids, active = cohort_mod.sample_cohort(cfg, rnd)
        if health_sentinel.has_quarantine(cfg):
            active = active & health_sentinel.quarantine_mask(cfg, ids)
        k_train, k_noise = jax.random.split(key)
        res = _round_core(
            params, k_train, k_noise, imgs, lbls, sizes,
            train_block=train_block, cfg=cfg,
            corrupt_flags=((ids < cfg.num_corrupt) & active
                           if want_flags else None),
            churn_active=active, rnd=rnd, astate=astate, knobs=knobs)
        if is_async:
            new_params, train_loss, extras, new_astate = res
            return ((new_params, new_astate),
                    {"train_loss": train_loss, "sampled": ids, **extras})
        new_params, train_loss, extras = res
        return new_params, {"train_loss": train_loss, "sampled": ids,
                            **extras}

    step.takes_round = True
    return step


def make_tenant_cohort_round_fn(cfg, model, normalize):
    """Tenant-pack cohort round fn:
    round(carry_E, keys_E, rnd, knobs, imgs, lbls, sizes) with the
    cohort stacks broadcast across tenants (gathered once per round by
    the engine's prefetch thread). Data is NOT bound here — cohort rows
    change every round, so they stay call-time arguments exactly like the
    solo cohort path."""
    from defending_against_backdoors_with_robust_learning_rate_tpu.utils import (
        compile_cache)
    vstep = jax.jit(_vmap_step(make_tenant_cohort_step(cfg, model,
                                                       normalize)))

    def bound(carry_E, keys_E, rnd, knobs, imgs, lbls, sizes):
        return vstep(carry_E, keys_E, rnd, knobs, imgs, lbls, sizes)

    bound.jitted = vstep
    bound.family = "round_cohort" + compile_cache.family_suffix(cfg)
    return bound


def make_tenant_eval_fn(model, normalize, n_classes: int = 10):
    """Tenant-stacked eval: eval(params_E, images, labels, weights) ->
    ([E] loss, [E] acc, [E, n_classes] per-class) — ONE dispatch
    evaluates the whole pack on the shared (broadcast) eval set."""
    from defending_against_backdoors_with_robust_learning_rate_tpu.fl.evaluate import (
        make_eval_fn)
    eval_fn = make_eval_fn(model, normalize, n_classes)
    # vmap traces THROUGH the inner jit; the outer jit is the dispatch
    return jax.jit(jax.vmap(eval_fn, in_axes=(0, None, None, None)))


def stack_params(solo_params_list):
    """[E x solo pytree] -> one [E, ...]-stacked pytree (per-tenant params
    initialized from each tenant's own seed, bitwise the solo init)."""
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs),
                                  *solo_params_list)


def tenant_slice(tree, e: int):
    """Index one tenant's slice out of an [E, ...]-stacked pytree of
    host-fetched values (the metrics fan-out's counterpart to
    `stack_params`)."""
    return jax.tree_util.tree_map(lambda x: x[e], tree)
