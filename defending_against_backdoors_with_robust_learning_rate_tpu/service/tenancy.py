"""Tenant-pack execution for the experiment queue (ISSUE 13 + 16).

`service/queue.py` runs scenario cells back-to-back; this module runs up
to E shape-compatible cells AT ONCE as one resident `*_mt` program
(fl/tenancy.py): per-tenant params/metrics carried as a stacked [E, ...]
pytree, per-tenant scalar knobs (seed, server LR, RLR threshold, attack
boost/schedule, slot clock) as traced [E]-vectors, cohorts sampled/
trained/fault-injected/aggregated together, and every metrics boundary
fanned back out per tenant through ONE MetricsDrain into each tenant's
own run dir (the same run_name a solo run of that cell would use, so
rows join).

Three layers:

- `plan_packs` — group a queue's cells into shape-compatible tenant
  packs using the compile-cache fingerprint's own field algebra
  (utils/compile_cache.tenant_pack_key — never an ad-hoc key list), with
  ineligible or shape-incompatible cells falling back to the serial path
  (a printed note per fallback, never a crash);
- `PackEngine` — the resident engine: dataset/model/programs built ONCE
  for a shape class, AOT bank adoption for the `*_mt` families, the
  per-unit dispatch + eval-boundary fan-out, and the per-SLOT state a
  scheduler needs (load/finalize/fail a tenant slot mid-run). The engine
  covers the vmap, sharded-mesh and cohort-sampled pack paths (ISSUE 16
  gaps 1-3: buffered carry stacked [E, ...], the `*_mt` shard_map
  families on a live mesh, one shared bank gather per cohort round);
- `run_pack` — the FIFO wrapper: build an engine, run every tenant start
  to finish in lockstep (offsets 0), return per-tenant summaries — the
  PR-13 semantics, byte-for-byte.

The bin-packing scheduler (service/scheduler.py) drives the SAME engine
with per-slot `rnd_offset`s: a slot whose cell completed (or was
evicted on a health incident) is reloaded with the next queued cell at
offset = -pack_round, so its key streams and schedule gates replay the
solo program exactly while the rest of the pack keeps training.

Exactness: per-tenant results are parity-pinned against solo runs
(tests/test_tenancy.py — ulp-close floats, bitwise sign-rule params;
dataset content comes from the pack's FIRST cell, which only matters for
the seed-keyed synthetic fallback). Checkpointing/heartbeat/spans are per-run facilities the pack
deliberately skips — queue cells are one-shot; run such cells solo.
"""

from __future__ import annotations

import concurrent.futures
import time
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from defending_against_backdoors_with_robust_learning_rate_tpu.fl import (
    buffered, tenancy as ftenancy)
from defending_against_backdoors_with_robust_learning_rate_tpu.fl.rounds import (
    FAULT_INFO_KEYS)
from defending_against_backdoors_with_robust_learning_rate_tpu.health import (
    monitor as health_monitor, sentinel as health_sentinel)
from defending_against_backdoors_with_robust_learning_rate_tpu.obs import (
    attribution as obs_attribution, events as obs_events,
    reputation as obs_reputation, telemetry as obs_telemetry)
from defending_against_backdoors_with_robust_learning_rate_tpu.utils import (
    compile_cache)
from defending_against_backdoors_with_robust_learning_rate_tpu.utils.guards import (
    all_finite_device, finite_warn)
from defending_against_backdoors_with_robust_learning_rate_tpu.utils.metrics import (
    MetricsDrain, MetricsWriter, run_name)


class PackIneligible(ValueError):
    """A pack refusal discovered only at run_pack time, BEFORE any
    program build (e.g. the resolved host-sampled mode needs the
    dataset's byte size, which plan_packs never loads) — the queue
    catches it and routes the member cells to the serial path instead
    of recording a pack failure."""


def serial_reason(cfg) -> str:
    """Why a cell routes to the serial path instead of a tenant pack
    ('' = packable): the program-level refusals
    (fl/tenancy.ineligible_reason) plus the driver/runtime knobs that
    module deliberately does not read (it is in the fingerprint audit's
    program-read scope). The PR-13 mesh refusal is retired: the engine
    resolves --mesh like the solo driver and dispatches the sharded
    `*_mt` families (cohort packs ignore the mesh request — there is no
    sharded cohort tenant family — with a printed note)."""
    reason = ftenancy.ineligible_reason(cfg)
    if reason:
        return reason
    if cfg.host_sampled == "on":
        return "host-sampled mode gathers shards per run; runs solo"
    return ""


def plan_packs(base_cfg, cells: List[Dict[str, Any]], tenants: int,
               apply_overrides) -> List[Tuple[str, List[Dict[str, Any]]]]:
    """Group queue cells into ("pack", [cells...]) / ("serial", [cell])
    work items, preserving first-appearance order of each shape class.

    Cells are pack-eligible when fl/tenancy.ineligible_reason is empty
    AND their `tenant_pack_key` (the fingerprint-derived shape/program
    class) matches; groups chunk into packs of at most `tenants`, and a
    leftover singleton (or any incompatible cell) runs serial with a
    printed note. `apply_overrides(base_cfg, overrides)` is the queue's
    own cell->Config resolution, passed in so the two can never drift."""
    groups: Dict[str, List[Dict[str, Any]]] = {}
    order: List[str] = []
    items: List[Tuple[str, List[Dict[str, Any]]]] = []
    for cell in cells:
        try:
            cfg = apply_overrides(base_cfg, cell["overrides"])
            reason = serial_reason(cfg)
            key = None if reason else compile_cache.tenant_pack_key(cfg)
        except Exception as e:  # a broken cell still gets its queue row
            reason, key = f"{type(e).__name__}: {e}", None
        if key is None:
            print(f"[tenancy] cell {cell['name']!r} -> serial "
                  f"({reason})")
            items.append(("serial", [cell]))
            continue
        if key not in groups:
            groups[key] = []
            order.append(key)
        groups[key].append(cell)
    for key in order:
        group = groups[key]
        for i in range(0, len(group), tenants):
            pack = group[i:i + tenants]
            if len(pack) < 2:
                print(f"[tenancy] cell {pack[0]['name']!r} -> serial "
                      f"(no shape-compatible partner in this queue)")
                items.append(("serial", pack))
            else:
                items.append(("pack", pack))
    # keep queue-row order stable: sort items by their first cell's
    # position in the original list
    pos = {id(c): i for i, c in enumerate(cells)}
    items.sort(key=lambda it: pos[id(it[1][0])])
    return items


class _Slot:
    """One resident tenant slot's host-side state: the cell it is
    running, its clock offset, its metrics writer and the per-tenant
    emission state the solo twin would keep."""

    def __init__(self, cfg, name: str, offset: int = 0,
                 writer: Optional[MetricsWriter] = None):
        self.cfg = cfg
        self.name = name
        self.offset = int(offset)
        self.writer = writer
        self.active = writer is not None
        self.tel_allowed = (obs_telemetry.telemetry_keys(cfg)
                            if self.active else [])
        self.cum_poison = 0.0
        self.health_ema = None
        # per-tenant suspicion ledger (obs/reputation.py) — assigned by
        # the engine when the pack program carries the rep_agree lane
        self.rep_tracker = None
        self.summary: Dict[str, Any] = {}
        self.error: Optional[BaseException] = None


class PackEngine:
    """The resident tenant-pack engine (see module docstring).

    `run_pack` (FIFO) and `service/scheduler.py` (bin-packed, backfilled)
    both drive this object; everything built in __init__ — dataset,
    model, round/chained/eval programs, AOT adoption, the stacked carry —
    is built ONCE per shape class and survives slot reloads.

    `evict_on_anomaly=True` (the scheduler) turns a per-tenant health
    enforcement failure into a slot eviction (the boundary returns the
    failed slots) instead of failing the whole pack — the FIFO path
    keeps the historical fail-the-pack semantics."""

    def __init__(self, cfgs, names: Optional[List[str]] = None,
                 offsets: Optional[List[int]] = None,
                 evict_on_anomaly: bool = False):
        from defending_against_backdoors_with_robust_learning_rate_tpu.data.registry import (
            get_federated_data)
        from defending_against_backdoors_with_robust_learning_rate_tpu.fl.common import (
            make_normalizer)
        from defending_against_backdoors_with_robust_learning_rate_tpu.fl.evaluate import (
            pad_eval_set)
        from defending_against_backdoors_with_robust_learning_rate_tpu.models.registry import (
            get_model, init_params, param_count)
        from defending_against_backdoors_with_robust_learning_rate_tpu.train import (
            apply_rng_impl)

        E = len(cfgs)
        if names is None:
            names = [f"tenant{e}" for e in range(E)]
        if offsets is None:
            offsets = [0] * E
        keys = {compile_cache.tenant_pack_key(c) for c in cfgs}
        if len(keys) != 1:
            raise ValueError(
                f"tenant pack mixes {len(keys)} shape/program classes — "
                f"the queue grouping (plan_packs) must only hand over "
                f"cells with one tenant_pack_key")
        self.pack_key = next(iter(keys))
        rep = ftenancy.canonical_rep(cfgs[0].replace(tenants=E),
                                     cells=cfgs)
        ftenancy.check(rep)
        reason = serial_reason(cfgs[0])
        if reason:
            raise ValueError(f"tenant pack: {reason}")
        self.rep = rep
        self.width = E
        self.evict_on_anomaly = evict_on_anomaly
        # cells must agree on rounds/snap (pack-key pinned) — the pack
        # advances every tenant in lockstep on one dispatch schedule
        self.rounds, self.snap = rep.rounds, rep.snap
        apply_rng_impl(rep.rng_impl)
        bank = compile_cache.setup(rep)
        self.t0 = time.perf_counter()

        # dataset content comes from the pack's FIRST cell (seed-free for
        # disk-backed data; the synthetic fallback draws from its seed —
        # documented exactness semantics, README "Multi-tenant sweeps")
        self.cohort = compile_cache.is_cohort_mode(rep)
        if self.cohort:
            from defending_against_backdoors_with_robust_learning_rate_tpu.data.registry import (
                get_cohort_data)
            fed = get_cohort_data(cfgs[0])
        else:
            fed = get_federated_data(cfgs[0])
            if compile_cache.is_host_mode(rep, fed):
                # host_sampled='auto' resolves against the loaded data's
                # byte size — the solo driver would route these cells
                # through the host-sampled families, but the pack binds
                # the full train stacks as device-resident jit arguments
                raise PackIneligible(
                    f"host-sampled mode resolves ON for this dataset "
                    f"({fed.train.images.nbytes / 1e9:.2f} GB train "
                    f"stack exceeds the device-resident budget); "
                    f"running cells solo")
        self.fed = fed
        # the pack's E experiments train at once: E is in the rule, and
        # the resolved policy goes into `rep`, which keys the *_mt families
        remat = compile_cache.resolved_remat(rep, fed)
        self.rep = rep = rep.replace(remat_policy=remat.policy)
        if rep.remat:
            print(f"[tenancy] {remat.describe()}")
        self.model = model = get_model(
            rep.data, rep.model_arch, rep.dtype, remat=rep.remat,
            remat_policy=rep.remat_policy)
        norm = make_normalizer(fed.mean, fed.std, fed.raw_is_normalized)
        self.image_shape = fed.train.images.shape[2:]
        m = rep.agents_per_round

        # --- mesh resolution (the solo driver's rules) ---
        self.n_mesh = 1
        mesh = None
        if rep.mesh != 1 and not self.cohort:
            from defending_against_backdoors_with_robust_learning_rate_tpu.parallel.mesh import (
                make_mesh, pick_agent_mesh_size)
            self.n_mesh = pick_agent_mesh_size(rep.mesh, m)
            if self.n_mesh > 1:
                mesh = make_mesh(self.n_mesh)
                print(f"[tenancy] sharded pack: {self.n_mesh} devices on "
                      f"the `agents` axis ({m // self.n_mesh} "
                      f"agents/device), tenant axis folded in-shard")
            else:
                print(f"[tenancy] no device count <= "
                      f"{rep.mesh or 'all'} divides m={m}; --mesh "
                      f"request ignored")
        elif rep.mesh != 1 and self.cohort:
            print("[tenancy] cohort packs run the vmap tenant family; "
                  "--mesh request ignored (no sharded cohort tenant "
                  "family)")

        # --- per-slot device state ---
        self.is_async = buffered.is_buffered(rep)
        params_E = ftenancy.stack_params([
            init_params(model, self.image_shape,
                        jax.random.PRNGKey(c.seed))
            for c in cfgs])
        self.n_params = param_count(ftenancy.tenant_slice(params_E, 0))
        if self.is_async:
            astate_E = ftenancy.stack_params([
                buffered.init_state(
                    rep,
                    ftenancy.tenant_slice(jax.device_get(params_E), e),
                    per_bin=(self.n_mesh == 1))
                for e in range(E)])
            self.carry = (params_E, astate_E)
        else:
            self.carry = params_E
        self.base_keys_E = jnp.stack(
            [jax.random.PRNGKey(c.seed) for c in cfgs])
        self.knobs = jax.tree_util.tree_map(
            jnp.asarray, ftenancy.knob_vectors(cfgs, offsets))
        # per-tenant key fold at the EFFECTIVE round (the solo driver's
        # fold_in(base_key, rnd), on each slot's own clock)
        self._fold = jax.jit(jax.vmap(
            lambda k, off, r: jax.random.fold_in(k, r + off),
            in_axes=(0, 0, None)))

        # --- programs + AOT adoption (warm packs skip XLA) ---
        arrays = (jnp.asarray(fed.train.images),
                  jnp.asarray(fed.train.labels),
                  jnp.asarray(fed.train.sizes))
        self.chain_n = (compile_cache.chain_budget(rep)
                        if not self.cohort and self.n_mesh == 1 else 1)
        self.compile_s = 0.0
        ab = compile_cache.abstractify
        carryE_aval = ab(self.carry)
        pE_aval = carryE_aval[0] if self.is_async else carryE_aval
        kE_aval = ab(self.base_keys_E)
        knob_aval = ab(self.knobs)
        rnd_aval = jax.ShapeDtypeStruct((), jnp.int32)
        self.chained_fn = None
        self._gather_rows = None
        self._prefetch: Optional[Tuple[int, Any]] = None
        self._exec = None
        if self.cohort:
            # ONE shared bank gather per round serves the whole pack
            # (ISSUE 16 gap 3): the cohort draw is cohort_seed-driven and
            # identical across tenants — scheduler admission keeps every
            # offset 0 so the shared draw stays shared
            if any(o != 0 for o in offsets):
                raise ValueError(
                    "cohort packs admit no clock skew (the shared bank "
                    "gather serves one draw); offsets must all be 0")
            if getattr(fed, "bank", None) is not None:
                self._gather_rows = fed.gather_cohort
                print(f"[tenancy] cohort pack: population "
                      f"{rep.num_agents:,} -> {m}-client cohorts, one "
                      f"shared gather for {E} tenants/round")
            else:
                self._gather_rows = lambda ids: (
                    fed.train.images[ids], fed.train.labels[ids],
                    fed.train.sizes[ids])
            self._exec = concurrent.futures.ThreadPoolExecutor(
                max_workers=1, thread_name_prefix="pack-prefetch")
            round_fn = ftenancy.make_tenant_cohort_round_fn(rep, model,
                                                            norm)
            shard_avals = tuple(
                jax.ShapeDtypeStruct((m,) + a.shape[1:], a.dtype)
                for a in ab(arrays))
            fn, secs = compile_cache.adopt(
                bank, rep, round_fn.family, round_fn.jitted,
                (carryE_aval, kE_aval, rnd_aval, knob_aval) + shard_avals)
            self.compile_s += secs
            self.round_fn = (round_fn if fn is None else fn)
        elif self.n_mesh > 1:
            # mesh executables embed the live mesh — never AOT-banked
            # (the solo driver's rule); the persistent XLA cache still
            # warm-starts them
            from defending_against_backdoors_with_robust_learning_rate_tpu.parallel.rounds import (
                make_sharded_round_fn_mt)
            self.round_fn = make_sharded_round_fn_mt(rep, model, norm,
                                                     mesh, *arrays)
        else:
            round_fn = ftenancy.make_tenant_round_fn(rep, model, norm,
                                                     *arrays)
            data_avals = ab(arrays)
            fn, secs = compile_cache.adopt(
                bank, rep, round_fn.family, round_fn.jitted,
                (carryE_aval, kE_aval, rnd_aval, knob_aval) + data_avals)
            self.compile_s += secs
            if fn is not None:
                data = round_fn.data

                def round_fn(cE, kE, rnd, kn, _fn=fn, _data=data):
                    return _fn(cE, kE, rnd, kn, *_data)
            self.round_fn = round_fn
            if self.chain_n > 1:
                chained_fn = ftenancy.make_tenant_chained_fn(
                    rep, model, norm, *arrays)
                ids_aval = jax.ShapeDtypeStruct((self.chain_n,),
                                                jnp.int32)
                fn, secs = compile_cache.adopt(
                    bank, rep, chained_fn.family, chained_fn.jitted,
                    (carryE_aval, kE_aval, ids_aval, knob_aval)
                    + data_avals)
                self.compile_s += secs
                if fn is not None:
                    data = chained_fn.data

                    def chained_fn(cE, kE, ids, kn, _fn=fn, _data=data):
                        return _fn(cE, kE, ids, kn, *_data)
                self.chained_fn = chained_fn

        eval_fn = ftenancy.make_tenant_eval_fn(model, norm, rep.n_classes)
        self.val = tuple(map(jnp.asarray, pad_eval_set(
            fed.val_images, fed.val_labels, rep.eval_bs)))
        self.pval = tuple(map(jnp.asarray, pad_eval_set(
            fed.pval_images, fed.pval_labels, rep.eval_bs)))
        self.eval_val_fn = self.eval_pval_fn = eval_fn
        fn, secs = compile_cache.adopt(bank, rep, "eval_val_mt", eval_fn,
                                       (pE_aval,) + ab(self.val))
        self.compile_s += secs
        if fn is not None:
            self.eval_val_fn = fn
        fn, secs = compile_cache.adopt(bank, rep, "eval_poison_mt",
                                       eval_fn, (pE_aval,) + ab(self.pval))
        self.compile_s += secs
        if fn is not None:
            self.eval_pval_fn = fn

        # --- per-tenant metrics plumbing: one writer per cell's run dir
        self.slots = [
            _Slot(cfg, name, offsets[e],
                  MetricsWriter(cfg.log_dir, run_name(cfg),
                                cfg.tensorboard))
            for e, (cfg, name) in enumerate(zip(cfgs, names, strict=True))]
        self.drain = (MetricsDrain()
                      if rep.async_metrics and not evict_on_anomaly
                      else None)
        # scalar health lanes only — the solo twin's boundary_keys
        # discipline: the [E, m] hlth_agent_bad suspect vector is ladder
        # evidence and must never ride the per-boundary fetch
        self.hlth_boundary = set(health_sentinel.boundary_keys(cfgs[0]))
        # per-tenant suspicion ledgers: the pack program's [E, m]
        # rep_agree lane fans out one tracker per cell — the solo twin's
        # longitudinal state, sliced on the tenant axis at the boundary
        self._rep_on = obs_reputation.reputation_on(rep)
        self._rep_pending: List[Any] = []
        if self._rep_on:
            for slot in self.slots:
                if slot.active:
                    slot.rep_tracker = (
                        obs_reputation.ReputationTracker.for_config(
                            slot.cfg, population=slot.cfg.num_agents))
        self.t_steady = None
        self.r_steady = 0
        self.t_steady_end = None
        self.r_steady_end = 0

    # ---------------------------------------------------------- slots ---

    def active_slots(self) -> List[int]:
        return [e for e, s in enumerate(self.slots) if s.active]

    def _refresh_knobs(self) -> None:
        self.knobs = jax.tree_util.tree_map(
            jnp.asarray,
            ftenancy.knob_vectors([s.cfg for s in self.slots],
                                  [s.offset for s in self.slots]))

    def load_slot(self, e: int, cfg, name: str, offset: int) -> None:
        """Backfill slot e with a fresh cell at clock offset `offset`
        (= -pack_round, so the cell's effective round counts 1..rounds):
        per-tenant params/buffer re-initialized from the cell's own seed
        — bitwise the solo init — via a functional [e]-indexed update of
        the stacked carry; knobs rebuilt host-side."""
        from defending_against_backdoors_with_robust_learning_rate_tpu.models.registry import (
            init_params)
        if self.cohort:
            raise ValueError("cohort packs admit no mid-run backfill "
                             "(the shared gather serves one draw)")
        params = init_params(self.model, self.image_shape,
                             jax.random.PRNGKey(cfg.seed))
        set_e = lambda P, p: P.at[e].set(jnp.asarray(p, P.dtype))  # noqa: E731
        if self.is_async:
            pE, aE = self.carry
            astate = buffered.init_state(self.rep, params,
                                         per_bin=(self.n_mesh == 1))
            self.carry = (jax.tree_util.tree_map(set_e, pE, params),
                          jax.tree_util.tree_map(set_e, aE, astate))
        else:
            self.carry = jax.tree_util.tree_map(set_e, self.carry, params)
        self.base_keys_E = self.base_keys_E.at[e].set(
            jax.random.PRNGKey(cfg.seed))
        self.slots[e] = _Slot(cfg, name, offset,
                              MetricsWriter(cfg.log_dir, run_name(cfg),
                                            cfg.tensorboard))
        if self._rep_on:
            # a backfilled cell starts its suspicion ledger fresh — the
            # solo twin's state at its round 0
            self.slots[e].rep_tracker = (
                obs_reputation.ReputationTracker.for_config(
                    cfg, population=cfg.num_agents))
        self._refresh_knobs()

    def finalize_slot(self, e: int) -> Dict[str, Any]:
        """Close out a COMPLETED slot: memory rows + writer close, then
        the solo-schema summary (service/queue.SUMMARY_KEYS)."""
        slot = self.slots[e]
        mem = obs_attribution.memory_watermarks()
        mem.update(obs_attribution.host_watermarks())
        if mem:
            for tag, v in obs_attribution.memory_rows(mem):
                slot.writer.scalar(tag, v, self.rounds)
        slot.writer.close()
        slot.active = False
        summary = dict(slot.summary)
        summary.setdefault("round", self.rounds)
        summary["params"] = self.n_params
        return summary

    def fail_slot(self, e: int, error: BaseException) -> None:
        """Evict a slot on a health incident / per-tenant failure:
        record-and-skip (the queue rows the failure; pack-mates keep
        training)."""
        slot = self.slots[e]
        slot.error = error
        slot.active = False
        try:
            slot.writer.close()
        except Exception:
            pass

    def idle_slot(self, e: int) -> None:
        """Mark a slot idle (nothing left to backfill): it keeps
        computing masked garbage on the pack clock — the occupancy
        metric, not a mask, accounts for the waste."""
        self.slots[e].active = False

    # ------------------------------------------------------- dispatch ---

    def _cohort_data(self, rnd: int):
        """The round's shared [m, ...] cohort rows — host-mirrored draw
        (data/cohort.sample_cohort_host, bit-identical to the in-program
        draw) + ONE indexed gather for the whole pack, one round ahead on
        the prefetch thread."""
        from defending_against_backdoors_with_robust_learning_rate_tpu.data import (
            cohort as cohort_mod)

        def gather(r):
            ids, _active = cohort_mod.sample_cohort_host(self.rep, r)
            return tuple(map(jnp.asarray, self._gather_rows(ids)))

        if self._prefetch is not None and self._prefetch[0] == rnd:
            data = self._prefetch[1].result()
        else:
            data = gather(rnd)
        self._prefetch = (rnd + 1, self._exec.submit(gather, rnd + 1))
        return data

    def dispatch_unit(self, unit) -> Tuple[int, Dict[str, Any]]:
        """Advance the pack clock over one schedule unit (a chained
        block or a single round); returns (pack_round, last-round info)."""
        if len(unit) > 1:
            ids = jnp.arange(unit[0], unit[-1] + 1)
            self.carry, stacked = self.chained_fn(
                self.carry, self.base_keys_E, ids, self.knobs)
            if self._rep_on and "rep_agree" in stacked:
                # [chain, E, m] agreement + norm rows + the matching
                # stacked client ids — sliced per tenant at the boundary
                # fan-out
                self._rep_pending.append((tuple(unit), stacked["sampled"],
                                          stacked["rep_agree"],
                                          stacked["rep_norm"]))
            return unit[-1], {k: v[-1] for k, v in stacked.items()}
        rnd = unit[0]
        keys_E = self._fold(self.base_keys_E, self.knobs.rnd_offset, rnd)
        if self.cohort:
            self.carry, info = self.round_fn(
                self.carry, keys_E, jnp.int32(rnd), self.knobs,
                *self._cohort_data(rnd))
        else:
            self.carry, info = self.round_fn(self.carry, keys_E,
                                             jnp.int32(rnd), self.knobs)
        if self._rep_on and "rep_agree" in info:
            self._rep_pending.append(((rnd,), info["sampled"],
                                      info["rep_agree"],
                                      info["rep_norm"]))
        return rnd, info

    def params_E(self):
        return self.carry[0] if self.is_async else self.carry

    def eval_boundary(self, rnd: int, info, rounds_done: int,
                      elapsed: float) -> Dict[int, BaseException]:
        """One eval boundary: the tenant-stacked eval pair + per-slot
        fan-out. Returns {slot: error} for slots whose health enforcement
        failed (only ever non-empty with evict_on_anomaly; the FIFO path
        re-raises instead)."""
        params_E = self.params_E()
        vals = {"finite": all_finite_device(params_E)}
        val_loss_d, val_acc_d, per_class_d = self.eval_val_fn(
            params_E, *self.val)
        poison_loss_d, poison_acc_d, _ = self.eval_pval_fn(
            params_E, *self.pval)
        vals.update(val_loss=val_loss_d, val_acc=val_acc_d,
                    base_acc=per_class_d[:, self.rep.base_class],
                    poison_loss=poison_loss_d,
                    poison_acc=poison_acc_d,
                    train_loss=info["train_loss"])
        if "fault_voters" in info:
            vals.update({k: info[k] for k in FAULT_INFO_KEYS})
        if "churn_away" in info:
            vals["churn_away"] = info["churn_away"]
        vals.update({k: info[k] for k in info
                     if k.startswith("tel_") or k in self.hlth_boundary})
        if self._rep_pending:
            # per-pack-round (round_ids, client_ids, rep_agree, rep_norm)
            # stacks since the last boundary ride the same (async) fetch
            vals["rep_rows"] = self._rep_pending
            self._rep_pending = []
        if self.drain is not None:
            self.drain.submit(self._emit_all, vals, rnd, rounds_done,
                              elapsed)
            return {}
        vals = jax.device_get(vals)  # static: ok(host-sync)
        return self._emit_all(vals, rnd, rounds_done, elapsed)

    # ----------------------------------------------------------- emit ---

    def _emit_all(self, vals, pack_rnd: int, rounds_done_now: int,
                  elapsed: float) -> Dict[int, BaseException]:
        """One eval boundary's per-tenant fan-out — runs on the drain
        thread (async) or inline (sync/scheduler); mirrors the solo
        train._emit_eval_body row order so tenant streams byte-compare
        to solo runs modulo wall-clock rows."""
        lane_on = "hlth_nonfinite" in vals
        if not lane_on:
            # --health off keeps the historical pack-level endpoint
            finite_warn(vals["finite"], where=f"pack round {pack_rnd}")
        now = time.perf_counter()
        # popped ONCE so an evict/retry pass cannot double-fold the
        # per-tenant ledgers (the solo _emit_eval_body discipline)
        rep_rows = vals.pop("rep_rows", None)
        errors: Dict[int, BaseException] = {}
        for e, slot in enumerate(self.slots):
            if not slot.active:
                continue
            try:
                self._emit_slot(e, slot, vals, pack_rnd, rounds_done_now,
                                elapsed, now, lane_on, rep_rows)
            except Exception as err:
                if not self.evict_on_anomaly:
                    raise
                errors[e] = err
        if self.t_steady is None:
            self.t_steady = now
            self.r_steady = rounds_done_now
        else:
            self.t_steady_end = now
            self.r_steady_end = rounds_done_now
        return errors

    def _emit_slot(self, e: int, slot: _Slot, vals, pack_rnd: int,
                   rounds_done_now: int, elapsed: float, now: float,
                   lane_on: bool, rep_rows=None) -> None:
        writer, cfg = slot.writer, slot.cfg
        ernd = pack_rnd + slot.offset  # the slot's own round index
        report = None
        if lane_on:
            # per-tenant health lane: the solo twin's assess/emit/
            # enforce (train._emit_eval_body) sliced per tenant —
            # Health/* rows land BEFORE Validation/*, the solo row
            # order, so tenant streams keep byte-parity with solo
            # runs. Each tenant is judged on ITS OWN committed-params
            # bit, not the pack-wide one (one diverging tenant must
            # not flag its pack-mates).
            hvals = {"finite":
                     float(vals["hlth_params_finite"][e]) >= 1.0,
                     "train_loss": float(vals["train_loss"][e])}
            for k in health_sentinel.boundary_keys(cfg):
                if k in vals:
                    hvals[k] = float(vals[k][e])
            report = health_monitor.assess(cfg, slot.health_ema, hvals)
            health_monitor.emit_rows(writer, report, ernd)
            health_monitor.enforce(
                cfg, report, where=f"pack round {ernd} tenant {e}")
        val_loss = float(vals["val_loss"][e])
        val_acc = float(vals["val_acc"][e])
        poison_loss = float(vals["poison_loss"][e])
        poison_acc = float(vals["poison_acc"][e])
        slot.cum_poison += poison_acc
        writer.scalar("Validation/Loss", val_loss, ernd)
        writer.scalar("Validation/Accuracy", val_acc, ernd)
        writer.scalar("Poison/Base_Class_Accuracy",
                      float(vals["base_acc"][e]), ernd)
        writer.scalar("Poison/Poison_Accuracy", poison_acc, ernd)
        writer.scalar("Poison/Poison_Loss", poison_loss, ernd)
        writer.scalar("Poison/Cumulative_Poison_Accuracy_Mean",
                      slot.cum_poison / ernd, ernd)
        writer.scalar("Train/Loss", float(vals["train_loss"][e]), ernd)
        if "fault_voters" in vals:
            writer.scalar("Faults/Dropped",
                          float(vals["fault_dropped"][e]), ernd)
            writer.scalar("Faults/Straggled",
                          float(vals["fault_straggled"][e]), ernd)
            writer.scalar("Faults/Effective_Voters",
                          float(vals["fault_voters"][e]), ernd)
        if "churn_away" in vals:
            writer.scalar("Churn/Sampled_Away",
                          float(vals["churn_away"][e]), ernd)
        tel = obs_telemetry.tenant_rows(vals, e, allowed=slot.tel_allowed)
        obs_telemetry.emit_scalars(writer, tel, ernd)
        rep_pred = ((lambda cid: cid < cfg.num_corrupt)
                    if cfg.num_corrupt > 0 else None)
        if slot.rep_tracker is not None and rep_rows:
            # the tenant's slice of the pack's [.., E, m] agreement rows
            # folds into ITS ledger on ITS clock (ernd = pack + offset),
            # mirroring the solo fold order; rows land after Defense/*
            # and before Throughput/*, the solo row order
            tracker = slot.rep_tracker
            for rnds, ids_blk, agrees, norms in rep_rows:
                ids_blk, agrees = np.asarray(ids_blk), np.asarray(agrees)
                norms = np.asarray(norms)
                if agrees.ndim == 2:             # single round [E, m]
                    tracker.fold(rnds[0] + slot.offset, ids_blk[e],
                                 agrees[e], norms[e])
                else:                            # chained [chain, E, m]
                    for j, r in enumerate(rnds):
                        tracker.fold(r + slot.offset, ids_blk[j, e],
                                     agrees[j, e], norms[j, e])
            obs_reputation.emit_rows(writer, tracker, ernd, rep_pred)
            for ev in tracker.drain_events():
                obs_events.emit(obs_reputation.SUSPECT_EVENT,
                                severity="warn", tenant=e, **ev)
        writer.scalar("Throughput/Rounds_Per_Sec",
                      rounds_done_now / elapsed, ernd)
        if (self.t_steady is not None
                and rounds_done_now > self.r_steady):
            writer.scalar("Throughput/Steady_Rounds_Per_Sec",
                          (rounds_done_now - self.r_steady)
                          / (now - self.t_steady), ernd)
        summary = {
            "round": ernd, "val_loss": val_loss, "val_acc": val_acc,
            "poison_loss": poison_loss, "poison_acc": poison_acc,
            "rounds_per_sec": rounds_done_now / elapsed}
        if tel:
            summary["defense"] = obs_telemetry.host_summary(tel)
        if slot.rep_tracker is not None:
            # the suspicion verdict as data: the same per-cell summary
            # key the solo path records (train.py _emit_eval_body), so
            # queue/sweep rows stay structurally identical packed or
            # serial (service/queue.SUMMARY_KEYS "suspicion")
            rep_sum = slot.rep_tracker.summary(rep_pred)
            summary["suspicion"] = rep_sum
            if "defense" in summary:
                summary["defense"]["rep_suspects"] = float(
                    rep_sum["suspect_count"])
                if "auc" in rep_sum:
                    summary["defense"]["rep_auc"] = float(rep_sum["auc"])
        if report is not None and report["rows"]:
            # the lane's verdict as data: queue rows
            # (service/queue.SUMMARY_KEYS) record per-cell health —
            # the SAME schema as the solo path's summary (train.py
            # _emit_eval_body), so packed-vs-serial rows stay
            # structurally identical
            summary["health"] = {k: float(v)
                                 for k, v in report["rows"].items()}
            # EMA commits LAST (the solo twin's discipline)
            slot.health_ema = report["new_state"]
        slot.summary = summary
        writer.flush()

    # -------------------------------------------------------- close ---

    def steady_rps(self) -> Optional[float]:
        if (self.t_steady is not None and self.t_steady_end is not None
                and self.r_steady_end > self.r_steady):
            return ((self.r_steady_end - self.r_steady)
                    / max(self.t_steady_end - self.t_steady, 1e-9))
        return None

    def close(self, loop_ok: bool = True) -> None:
        if self.drain is not None:
            if loop_ok:
                self.drain.flush()
            self.drain.close(raise_errors=False)
        if self._exec is not None:
            self._exec.shutdown(wait=False, cancel_futures=True)
        if not loop_ok:
            # a failed pack still flushes+releases every tenant's
            # metrics handle (the queue records the failure and moves
            # on; the success path closes writers via finalize_slot —
            # close() is not re-entrant)
            for slot in self.slots:
                if slot.active:
                    try:
                        slot.writer.close()
                    except Exception:
                        pass


def run_pack(cfgs, names: Optional[List[str]] = None
             ) -> Tuple[List[Dict[str, Any]], Dict[str, Any]]:
    """Run E shape-compatible cell configs as ONE tenant pack, FIFO
    (every tenant starts and finishes together, offsets 0 — the PR-13
    semantics).

    Returns (per-tenant summary dicts in cell order, pack_info) where
    each summary matches the solo run-summary keys the queue consumes
    (service/queue.SUMMARY_KEYS) and pack_info carries the pack-level
    timing split (compile/AOT-acquisition vs steady seconds)."""
    from defending_against_backdoors_with_robust_learning_rate_tpu.train import (
        dispatch_schedule)
    engine = PackEngine(cfgs, names)
    E, rounds, snap = engine.width, engine.rounds, engine.snap
    print(f"[tenancy] pack of {E} tenants x {rounds} rounds "
          f"({', '.join(s.name for s in engine.slots)})")
    rounds_done = 0
    loop_ok = False
    t_loop = time.perf_counter()
    try:
        for unit in dispatch_schedule(0, rounds, snap, engine.chain_n,
                                      False,
                                      engine.chained_fn is not None):
            rnd, info = engine.dispatch_unit(unit)
            rounds_done += len(unit)
            if rnd % snap == 0:
                engine.eval_boundary(rnd, info, rounds_done,
                                     time.perf_counter() - t_loop)
        loop_ok = True
    finally:
        engine.close(loop_ok)

    elapsed = time.perf_counter() - t_loop
    wall = time.perf_counter() - engine.t0
    pack_rps = rounds_done / max(elapsed, 1e-9)
    steady_rps = engine.steady_rps()
    summaries = []
    for e in range(E):
        summary = engine.finalize_slot(e)
        summary["rounds_per_sec"] = pack_rps
        if steady_rps is not None:
            summary["steady_rounds_per_sec"] = steady_rps
        summaries.append(summary)
    pack_info = {"tenants": E, "rounds": rounds,
                 "wall_s": round(wall, 3),
                 "compile_s": round(engine.compile_s, 3),
                 "rounds_per_sec": round(pack_rps, 4)}
    if steady_rps is not None:
        pack_info["steady_rounds_per_sec"] = round(steady_rps, 4)
    print(f"[tenancy] pack done: {E} tenants x {rounds} rounds in "
          f"{wall:.1f}s ({pack_rps:.2f} pack-rounds/sec)")
    return summaries, pack_info
