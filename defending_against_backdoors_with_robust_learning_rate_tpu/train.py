"""The experiment driver — reference src/federated.py:21-95 re-built around
jitted round functions.

Round loop shape (reference src/federated.py:65-92): sample agents -> local
training -> aggregate -> eval every `snap` rounds, logging the reference's
exact TensorBoard scalar names. Differences: the whole round is one compiled
XLA program (vmap on one device, shard_map over the `agents` mesh axis when
--mesh > 1); client sampling is seeded; checkpoint/resume via Orbax
(SURVEY.md section 5.4 gap); rounds/sec throughput is measured (section 5.1
gap, and BASELINE.json's headline metric).

Structure (ISSUE 6): all driver state lives in `RoundEngine`, a *resumable
round engine* whose loop body is exposed as explicit steps —
``dispatch(unit)`` / ``eval_boundary(rnd)`` / ``save_checkpoint(rnd)`` /
``post_unit()`` — over engine state. ``run`` (the one-shot trainer) iterates
them exactly as the historical monolithic loop did; the continuous-service
driver (service/driver.py) iterates the same steps indefinitely with a
supervisor wrapped around each one. The factoring is what makes crash-exact
recovery possible: every step is re-enterable from restored state."""

from __future__ import annotations

import collections
import os
import time
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from defending_against_backdoors_with_robust_learning_rate_tpu.config import (
    Config, args_parser, print_exp_details)
from defending_against_backdoors_with_robust_learning_rate_tpu.data.registry import (
    get_federated_data)
from defending_against_backdoors_with_robust_learning_rate_tpu.fl.common import (
    make_normalizer)
from defending_against_backdoors_with_robust_learning_rate_tpu.fl.evaluate import (
    make_eval_fn, pad_eval_set)
from defending_against_backdoors_with_robust_learning_rate_tpu.attack import (
    registry as attack_registry)
from defending_against_backdoors_with_robust_learning_rate_tpu.fl import (
    buffered as buffered_mod, task as task_mod)
from defending_against_backdoors_with_robust_learning_rate_tpu.fl.rounds import (
    CHAINED_INFO_KEYS, FAULT_INFO_KEYS, host_takes_flags, make_round_fn,
    make_round_fn_host, step_takes_round)
from defending_against_backdoors_with_robust_learning_rate_tpu.health import (
    monitor as health_monitor, sentinel as health_sentinel)
from defending_against_backdoors_with_robust_learning_rate_tpu.obs import (
    Heartbeat, NullHeartbeat, SpanTracer, attribution as obs_attribution,
    events as obs_events, flight as obs_flight,
    reputation as obs_reputation, spans as obs_spans,
    telemetry as obs_telemetry)
from defending_against_backdoors_with_robust_learning_rate_tpu.models.registry import (
    abstract_params, get_model, init_params, param_count)
from defending_against_backdoors_with_robust_learning_rate_tpu.utils import (
    checkpoint as ckpt, compile_cache)
from defending_against_backdoors_with_robust_learning_rate_tpu.utils.guards import (
    all_finite_device, guard_round_fn)
from defending_against_backdoors_with_robust_learning_rate_tpu.utils.metrics import (
    MetricsDrain, MetricsWriter, NullWriter, run_name)

# above this many stacked-array bytes the driver switches to host-side
# per-round shard gathering (the fedemnist path: 3383 users, SURVEY.md 7.3.2)
DEVICE_RESIDENT_BYTES = compile_cache.DEVICE_RESIDENT_BYTES


def _adopt_aot(bank, cfg, family, jit_obj, example_args):
    """The banked (or freshly banked) AOT executable of one program
    family, or None to keep the plain jit (utils/compile_cache.adopt)."""
    return compile_cache.adopt(bank, cfg, family, jit_obj, example_args)[0]


def _bind_compiled(compiled, data):
    """Rebind an adopted executable to the bound-fn calling convention:
    (params, key[, round_idx]) with the dataset stacks appended."""
    def bound(params, key, *lead):
        return compiled(params, key, *lead, *data)
    return bound


def dispatch_schedule(start, total, snap, chain_n, diagnostics, chaining):
    """The driver's dispatch plan: a list of round-id tuples, one per
    dispatch — a chained block (len == chain_n) whenever the budget to the
    next eval boundary allows, else a single round. A chained block never
    crosses an eval boundary, and a diagnostics run keeps its snap rounds
    unchained (they need prev_params + the diag-compiled variant). This is
    the SINGLE source of truth: the run loop iterates these units directly
    and the host-mode prefetcher produces payloads against the same list."""
    units, rnd = [], start
    while rnd < total:
        to_eval = min(snap - rnd % snap, total - rnd)
        diag_boundary = diagnostics and (rnd + to_eval) % snap == 0
        budget = to_eval - (1 if diag_boundary else 0)
        if chaining and budget >= chain_n:
            units.append(tuple(range(rnd + 1, rnd + chain_n + 1)))
            rnd += chain_n
        else:
            units.append((rnd + 1,))
            rnd += 1
    return units


def device_record() -> Dict:
    """The device as JAX reports it. Every run prints and records it: with
    `--platform` unset a missing chip leaves JAX on the CPU, and a
    rounds/sec line reads the same either way."""
    devices = jax.devices()
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind, "count": len(devices)}


def apply_rng_impl(choice: str) -> str:
    """Resolve and install the PRNG bit generator BEFORE any key is made.

    'auto' picks the TPU's hardware RNG (rbg) on the tpu backend — measured
    +13% round throughput on v5e (threefry dropout-mask generation is 15%
    of the round, profile_round.py --ablate) — and threefry elsewhere, so
    CPU tests and cross-path parity are stream-identical to before. Streams
    differ between impls: a checkpoint resumes only under the impl that
    wrote it (key data shapes differ; restore fails loudly)."""
    impls = {"auto": ("rbg" if jax.default_backend() == "tpu"
                      else "threefry2x32"),
             "threefry": "threefry2x32", "rbg": "rbg"}
    if choice not in impls:
        raise ValueError(f"rng_impl must be one of {sorted(impls)}, "
                         f"got {choice!r}")
    impl = impls[choice]
    jax.config.update("jax_default_prng_impl", impl)
    return impl


# Each unit's new parameters are allocated at dispatch, so the parameters of
# units dispatched and not yet run may hold this share of the device's
# memory. The dispatch loop never waits below two units in flight, and keeps
# no count where more than UNCOUNTED_AHEAD units fit the share (ResNet-9: 81).
DISPATCH_AHEAD_SHARE = 8
UNCOUNTED_AHEAD = 8


def units_ahead(param_bytes: int, limit_bytes: Optional[int]
                ) -> Optional[int]:
    """How many units the dispatch loop lets the host run ahead of the
    device, from the bytes a unit's new parameters take against the
    device's `bytes_limit`; None where it keeps no count (the backend
    reports no limit, or the parameters are small)."""
    if not limit_bytes:
        return None
    fit = limit_bytes // (DISPATCH_AHEAD_SHARE * max(param_bytes, 1))
    return None if fit > UNCOUNTED_AHEAD else max(2, int(fit))


class RoundEngine:
    """Resumable round engine: program building, restored state, and the
    loop body as explicit re-enterable steps.

    Construction does everything up to (not including) the first dispatch:
    data/model/program building, AOT adoption, checkpoint restore, metrics
    plumbing. The caller then drives:

        for unit in engine.schedule():      # or its own unit stream
            engine.dispatch(unit)
            if engine.rnd % cfg.snap == 0:
                engine.eval_boundary(engine.rnd)
                engine.save_checkpoint(engine.rnd)   # if checkpointing
            engine.post_unit()
        ...
        engine.close()                      # in a finally
        summary = engine.finalize()

    ``run`` below is exactly that loop (the historical one-shot trainer);
    service/driver.py wraps each step in a supervisor and streams units
    indefinitely. State (params, base_key, rnd, cumulative metrics) lives
    on the engine, so a crash resumes by building a fresh engine from the
    journaled checkpoint (utils/checkpoint.py) and re-entering the loop —
    bit-identical to never having crashed."""

    def __init__(self, cfg: Config, writer: Optional[MetricsWriter] = None,
                 resume_upto: Optional[int] = None):
        # observability (obs/): host-side round-trace spans + the
        # status.json heartbeat, lead process only. The tracer comes first
        # so that `engine/build` spans the whole constructor; it is the
        # process's current one (obs/spans.current()) from here on, and
        # listens for every program the backend acquires until close().
        self.lead = jax.process_index() == 0
        self.hb = NullHeartbeat()
        self.tracer = SpanTracer(enabled=cfg.spans and self.lead,
                                 on_end=self._span_ended)
        obs_spans.set_current(self.tracer)
        self.tracer.watch_compiles()
        try:
            with self.tracer.span("engine/build"):
                self._build(cfg, writer, resume_upto)
        except BaseException:
            self.tracer.close()
            raise

    def _span_ended(self, name: str, dur_s: float) -> None:
        # the heartbeat rides the tracer's span-completion hook, so
        # `last_span` tracks without extra calls
        self.hb.span_hook(name, dur_s)

    def _build(self, cfg: Config, writer: Optional[MetricsWriter],
               resume_upto: Optional[int]) -> None:
        # resume_upto pins the newest checkpoint round restore may pick
        # (0 = none): the service driver passes its journal-agreed resume
        # round so a kill between ckpt.save and journal_record cannot make
        # the engine restore past the metrics splice point. None (the
        # one-shot trainer) keeps newest-valid semantics. The producer
        # (prepare_crash_exact_resume) has already digest-validated that
        # round, so restore skips re-hashing it.
        # what the token task, or a fold asked for by hand, cannot run is
        # refused here, before anything is built (the rule itself never
        # picks a fold the configuration could not run)
        refused = compile_cache.unsupported(cfg, cfg.agg_path == "fold")
        if refused:
            raise ValueError(refused[0])
        if cfg.tenants > 0:
            # the tenant axis is the experiment QUEUE's pack knob
            # (service/queue.py --tenants routes shape-compatible cells
            # through service/tenancy.run_pack); this engine runs ONE
            # experiment and must never half-adopt the *_mt families
            raise ValueError(
                f"--tenants {cfg.tenants} packs experiments in the "
                f"queue (service/queue.py --tenants E, or "
                f"scripts/sweep_scenarios.py --tenants E); train.run "
                f"runs a single experiment — drop --tenants here")
        self.cfg = cfg
        self._resume_upto = resume_upto
        print_exp_details(cfg)
        self.device = device_record()
        print("[device] platform={platform} kind={kind} n={count}"
              .format(**self.device))
        obs_telemetry.check_level(cfg.telemetry)
        # health-lane + policy validation (health/monitor.py), loudly
        # and before any build
        health_monitor.check(cfg)
        if health_sentinel.has_quarantine(cfg):
            print(f"[health] quarantined clients: "
                  f"{list(health_sentinel.quarantine_ids(cfg))} "
                  f"(excluded via the participation mask)")
        # attack-config validation, loudly and before any build
        # (attack/registry.py: unknown strategy, bad boost, schedule on a
        # data-side strategy)
        attack_registry.check(cfg)
        atk_banner = attack_registry.banner(cfg)
        if atk_banner:
            print(atk_banner)
        # buffered-async validation (fl/buffered.py: order-statistic
        # aggregators, diagnostics, host-sampled — each refusal names
        # its remediation)
        buffered_mod.check(cfg)
        self.async_mode = async_mode = buffered_mod.is_buffered(cfg)
        async_banner = buffered_mod.banner(cfg)
        if async_banner:
            print(async_banner)
        impl = apply_rng_impl(cfg.rng_impl)
        if impl != "threefry2x32":
            print(f"[rng] {impl} bit generator")
        lead, tracer = self.lead, self.tracer
        with tracer.span("setup/obs"):
            self.hb = hb = (Heartbeat(cfg.status_file
                                      or os.path.join(cfg.log_dir,
                                                      "status.json"))
                            if cfg.heartbeat and lead else NullHeartbeat())
            hb.update(phase="setup", rounds=cfg.rounds, force=True)
        if cfg.telemetry != "off":
            print(f"[telemetry] in-jit defense telemetry: {cfg.telemetry} "
                  f"(Defense/* scalars ride the metrics stream)")
        # reputation-plane validation (obs/reputation.py), loudly and
        # before any build
        obs_reputation.check(cfg)
        # persistent XLA cache + AOT executable bank — must be configured
        # before the first compile so every program family persists
        bank = compile_cache.setup(cfg)
        if cfg.compile_cache:
            print(f"[cache] persistent XLA cache at "
                  f"{compile_cache.cache_root(cfg)}"
                  + ("" if bank is not None
                     else " (AOT bank off: --debug_nan)"))
        # population/cohort split (ISSUE 7): the cfg-only decision comes
        # FIRST — a million-client population must never be materialized
        # densely just to decide not to materialize it. The client bank
        # (data/bank.py) holds the population offset-indexed on disk;
        # `fed` then carries a zero-client shape shim plus the eval sets.
        cohort_mode = compile_cache.is_cohort_mode(cfg)
        cohort_src = None
        if (not cohort_mode and cfg.cohort_sampled == "auto"
                and cfg.num_agents
                >= compile_cache.COHORT_AUTO_MIN_POPULATION):
            print(f"[cohort] population {cfg.num_agents:,} is above the "
                  f"auto threshold but the implied cohort of "
                  f"{cfg.agents_per_round} cannot be sampled "
                  f"(data/cohort.py MAX_CANDIDATES); staying on the dense "
                  f"path — set --cohort_size to decouple population from "
                  f"cohort")
        with tracer.span("setup/data"):
            if cohort_mode:
                from defending_against_backdoors_with_robust_learning_rate_tpu.data.registry import (
                    get_cohort_data)
                cohort_src = fed = get_cohort_data(cfg)
            else:
                fed = get_federated_data(cfg)
        tracer.count("data_bytes_host", fed.nbytes)
        if fed.synthetic and cfg.data not in ("synthetic", "tokens"):
            print(f"[data] {cfg.data} files not found under "
                  f"{cfg.data_dir!r}; using the deterministic synthetic "
                  f"fallback")

        with tracer.span("setup/model_init"):
            # what the backward pass recomputes under --remat is settled
            # here, from the examples one device trains at once and what
            # it has free (compile_cache.resolved_remat); from here on cfg
            # carries the resolved policy, never 'auto', so the bank's
            # fingerprint keys the program that is built
            remat = compile_cache.resolved_remat(
                cfg, fed, threshold=DEVICE_RESIDENT_BYTES)
            cfg = self.cfg = cfg.replace(remat_policy=remat.policy)
            model = get_model(cfg.data, cfg.model_arch, cfg.dtype,
                              remat=cfg.remat,
                              remat_policy=cfg.remat_policy, cfg=cfg)
            example_shape = task_mod.input_shape(cfg, fed)
            # what a model wants counted once at build (a token model: the
            # experts and vocabulary rows it holds, the rows a sparse
            # layer's first pass takes of a step's sorted pairs, and the
            # squares of a sequence's scores its attention forms, by layer
            # kind where some layers have a window, the attention layers
            # by the path their core takes: attn_path{path=}, the forward
            # operations of a step's cores by layer kind:
            # attn_core_flops{kind=}, the layers whose rotary step is
            # token_ops.rope_half's product: attn_rope_product, and of a
            # linear-attention model its layers, chunk, chunks a sequence,
            # the core's forward operations a step and its state's bytes:
            # gdn_layers, gdn_chunk, gdn_chunks, gdn_core_flops,
            # gdn_state_bytes, and its layers by the path their core
            # takes: gdn_path{path=})
            built = (model.build_counters(cfg.bs * example_shape[0],
                                          example_shape[0])
                     if task_mod.is_tokens(cfg) else {})
            attn_paths = built.pop("attn_path", {})
            attn_flops = built.pop("attn_core_flops", {})
            gdn_paths = built.pop("gdn_path", {})
            for name, value in built.items():
                tracer.count(name, value)
            for path, layers in attn_paths.items():
                tracer.count("attn_path", layers, path=path)
            for kind, flops in attn_flops.items():
                tracer.count("attn_core_flops", flops, kind=kind)
            for path, layers in gdn_paths.items():
                tracer.count("gdn_path", layers, path=path)
            # stack or fold, settled here from the stack's bytes and what
            # the device has free (compile_cache.resolved_agg), before a
            # parameter exists; from here on cfg carries the resolved
            # path, so the bank's fingerprint keys the program that is built
            agg = compile_cache.resolved_agg(
                cfg, param_count(abstract_params(model, example_shape)))
            cfg = self.cfg = cfg.replace(agg_path=agg.path)
            tracer.count("agg_path", path=agg.path)
            tracer.count("agg_stack_bytes", agg.stack_bytes)
            tracer.count("agg_limit_bytes", agg.limit_bytes or 0)
            print(f"[agg] {agg.describe()}")
            params = init_params(model, example_shape,
                                 jax.random.PRNGKey(cfg.seed))
            print(f"[model] {type(model).__name__}: "
                  f"{param_count(params):,} params")
            if "moe_rows" in built:
                print(f"[model] moe rows {built['moe_rows']} of "
                      f"{built['moe_rows_worst']}: the first pass over a "
                      f"step's sorted pairs, {built['experts_held']} "
                      f"experts held; what it cannot hold takes a second")
                print(f"[model] attention squares "
                      f"{built['attn_squares_computed']} of "
                      f"{built['attn_squares']}: the blocks of a sequence's "
                      f"scores at or below the diagonal, the only ones "
                      f"formed"
                      + (f" in {built['attn_full_layers']} full-attention "
                         f"layer(s); {built['attn_window_squares_computed']}"
                         f" of {built['attn_window_squares']}"
                         f" in {built['attn_window_layers']} layer(s) with "
                         f"a window of {built['attn_window']} keys, those "
                         f"below the band not formed either"
                         if "attn_window" in built else "")
                      + "; path " + ", ".join(
                          f"{path} in {layers} layer(s)"
                          for path, layers in sorted(attn_paths.items()))
                      + f"; rotary by product in "
                        f"{built['attn_rope_product']} layer(s)")
            if "gdn_layers" in built:
                print(f"[model] gdn {built['gdn_layers']} linear-attention "
                      f"layer(s): the delta rule in {built['gdn_chunks']} "
                      f"chunk(s) of {built['gdn_chunk']} tokens a sequence, "
                      f"a float32 state of {built['gdn_state_bytes']:,} "
                      f"bytes a layer and step, "
                      f"{built['gdn_core_flops']:.4g} core operations a "
                      f"step; core by kernel in "
                      f"{gdn_paths.get('kernel', 0)} layer(s)")
            if cfg.remat:
                tracer.count("remat", policy=remat.policy)
                tracer.count("remat_saved_bytes", remat.saved_bytes)
                tracer.count("remat_limit_bytes", remat.limit_bytes or 0)
                print("[model] remat: every block recomputed in backward"
                      if task_mod.is_tokens(cfg)
                      else f"[model] {remat.describe()}")
            norm = make_normalizer(fed.mean, fed.std,
                                   fed.raw_is_normalized)
        # the lane resolves after the aggregation path: a folded round
        # never holds the updates beside the committed vote
        self._rep_on = obs_reputation.reputation_on(cfg)
        if self._rep_on:
            print(f"[reputation] per-client suspicion lanes: rep_agree + "
                  f"rep_norm ride the round program (zero added "
                  f"collectives); host ledger keyed by real client ids "
                  f"(--reputation off disables)")
        elif agg.path == "fold" and obs_reputation.wants_vote(cfg) \
                and cfg.reputation == "auto":
            print("[reputation] off (auto): a folded round never holds the "
                  "updates beside the committed vote")

        # single source with the precompile planner
        # (compile_cache.is_host_mode) so banked families always match what
        # this loop dispatches; the threshold stays the module global for
        # test monkeypatching
        host_mode = (not cohort_mode) and compile_cache.is_host_mode(
            cfg, fed, threshold=DEVICE_RESIDENT_BYTES)
        if host_mode and (cfg.churn_enabled or cfg.traffic_enabled):
            # churn/traffic-aware cohorting (ROADMAP carry-over from PR
            # 6; diurnal traffic joins in ISSUE 17): a host-sampled run
            # under churn or diurnal traffic routes through the cohort
            # program — cohorts sampled in-program from the present set
            # over the dense host stacks — instead of the old loud
            # refusal. The decision defers to is_cohort_mode (the same
            # single source the planner and precompile consult), which
            # honors an explicit --cohort_sampled off AND requires the
            # implied cohort to be samplable; either way the refusal
            # stays loud rather than crashing mid-construction.
            what = "churn" if cfg.churn_enabled else "traffic"
            if compile_cache.is_cohort_mode(
                    cfg, fed, threshold=DEVICE_RESIDENT_BYTES):
                cohort_mode, host_mode = True, False
                print(f"[cohort] host-sampled + {what}: cohorts are "
                      f"sampled from the {what}-present set (the refusal "
                      "path is retired)")
            else:
                raise ValueError(
                    f"host-sampled + {what} needs the cohort program "
                    f"(cohorts sampled from the {what}-present set), but "
                    "this config cannot take it: --cohort_sampled is "
                    "'off', or the implied cohort of "
                    f"{cfg.agents_per_round} clients is not samplable "
                    "(data/cohort.py MAX_CANDIDATES) — set "
                    "--cohort_size, raise availability, or disable "
                    f"{what}")
        n_mesh = 1
        if cfg.mesh != 1 and not host_mode and not cohort_mode:
            from defending_against_backdoors_with_robust_learning_rate_tpu.parallel.mesh import (
                pick_agent_mesh_size)
            from defending_against_backdoors_with_robust_learning_rate_tpu.parallel.rounds import (
                make_sharded_round_fn)
            n_mesh = pick_agent_mesh_size(cfg.mesh, cfg.agents_per_round)

        # diagnostics extras (lr vector, agent norms) are only consumed on
        # snap rounds; off-snap rounds run a variant compiled without them
        plain_cfg = cfg.replace(diagnostics=False)
        host_sampler = None
        chained_fn = None
        host_chained_fn = None
        get_unit = None   # host-mode payload fetch, set in the host branch
        self._prefetcher = None   # host-mode RoundPrefetcher, created lazily
        self._sched_units = None  # set by set_schedule (prefetch order)
        # a diagnostic snap round always runs unchained, so it is excluded
        # from the per-boundary chain budget (single source:
        # utils/compile_cache — the precompile planner must agree with the
        # driver on chain length)
        chain_n = compile_cache.chain_budget(cfg)
        mesh = None
        if n_mesh > 1:
            from defending_against_backdoors_with_robust_learning_rate_tpu.parallel import (
                multihost)
            if jax.process_count() > 1:
                # multi-host: one global agents mesh, DCN-aware device
                # order. The mesh must span every host's devices, so the
                # blocking policy cannot shrink it — the participant count
                # has to divide over the full pod (global_agents_mesh
                # raises otherwise).
                n_mesh = multihost.require_pod_divisible(
                    cfg.agents_per_round, "multi-host")
            mesh = multihost.global_agents_mesh(n_mesh)
            # placed ONCE, replicated over the mesh: an uncommitted array
            # sits on device 0, and every dispatch would re-ship the whole
            # dataset stack from it to the other devices
            with tracer.span("setup/place"):
                arrays = multihost.put_replicated(
                    mesh, (fed.train.images, fed.train.labels,
                           fed.train.sizes))
                params = multihost.put_replicated(mesh, params)
            tracer.count("data_bytes_placed",
                         n_mesh * sum(int(a.nbytes) for a in arrays))
            print(f"[mesh] {n_mesh} devices on the `agents` axis "
                  f"({cfg.agents_per_round // n_mesh} agents/device), "
                  f"{jax.process_count()} process(es)")
            print(f"[agg] {multihost.agg_plan_note(cfg, params)}")
            with tracer.span("setup/build_programs"):
                round_fn = make_sharded_round_fn(plain_cfg, model, norm,
                                                 mesh, *arrays)
                diag_round_fn = (make_sharded_round_fn(cfg, model, norm,
                                                       mesh, *arrays)
                                 if cfg.diagnostics else round_fn)
                if chain_n > 1:
                    from defending_against_backdoors_with_robust_learning_rate_tpu.parallel.rounds import (
                        make_sharded_chained_round_fn)
                    chained_fn = make_sharded_chained_round_fn(
                        plain_cfg, model, norm, mesh, *arrays)
        elif cohort_mode:
            with tracer.span("setup/build_programs"):
                # ----------------------------------------------- cohort mode
                # population decoupled from cohort (ISSUE 7): the driver
                # mirrors the seeded in-program cohort draw (data/cohort.py)
                # to gather only the m sampled clients' rows — from the
                # memory-mapped client bank, or (churn-aware host mode) from
                # the dense host stacks — and the round program recomputes
                # the same ids from the traced round index to derive corrupt
                # and churn flags per cohort MEMBER. Host/HBM stay O(cohort).
                m = cfg.agents_per_round
                if jax.process_count() > 1:
                    raise NotImplementedError(
                        "cohort-sampled mode is single-process for now — "
                        "the pod-scale aggregation rework (ROADMAP) will "
                        "shard the cohort gather across hosts")
                if cohort_src is not None:
                    print(f"[cohort] population {cfg.num_agents:,} clients -> "
                          f"{m}-client cohorts ({cfg.partitioner} client "
                          f"bank, {cohort_src.max_n} rows/cohort member; "
                          f"in-program sampling, cohort_seed "
                          f"{cfg.cohort_seed})")
                    gather_rows = cohort_src.gather_cohort
                else:
                    print(f"[cohort] {cfg.num_agents} clients -> {m}-client "
                          f"cohorts sampled from the churn-present set over "
                          f"the host shard stacks")

                    def gather_rows(ids):
                        return (fed.train.images[ids], fed.train.labels[ids],
                                fed.train.sizes[ids])
                take = lambda a: jnp.asarray(a)  # noqa: E731
                take_block = take
                round_fn_host = None
                if cfg.mesh != 1:
                    from jax.sharding import NamedSharding, PartitionSpec as P
                    from defending_against_backdoors_with_robust_learning_rate_tpu.parallel.mesh import (
                        AGENTS_AXIS, make_mesh, pick_agent_mesh_size)
                    from defending_against_backdoors_with_robust_learning_rate_tpu.parallel.rounds import (
                        make_sharded_cohort_round_fn)
                    n_mesh = pick_agent_mesh_size(cfg.mesh, m)
                    if n_mesh > 1:
                        mesh = make_mesh(n_mesh)
                        print(f"[mesh] {n_mesh} devices on the `agents` axis "
                              f"({m // n_mesh} cohort members/device), "
                              f"cohort-sampled")
                        from defending_against_backdoors_with_robust_learning_rate_tpu.parallel import (
                            multihost as mh)
                        print(f"[agg] {mh.agg_plan_note(cfg, params)}")
                        agents_sharding = NamedSharding(mesh, P(AGENTS_AXIS))
                        block_sharding = NamedSharding(mesh,
                                                       P(None, AGENTS_AXIS))
                        take = lambda a: jax.device_put(  # noqa: E731
                            a, agents_sharding)
                        take_block = lambda a: jax.device_put(  # noqa: E731
                            a, block_sharding)
                        round_fn_host = make_sharded_cohort_round_fn(
                            plain_cfg, model, norm, mesh)
                        diag_round_fn_host = (
                            make_sharded_cohort_round_fn(cfg, model, norm,
                                                         mesh)
                            if cfg.diagnostics else round_fn_host)
                if round_fn_host is None:
                    from defending_against_backdoors_with_robust_learning_rate_tpu.fl.rounds import (
                        make_cohort_round_fn)
                    round_fn_host = make_cohort_round_fn(plain_cfg, model,
                                                         norm)
                    diag_round_fn_host = (
                        make_cohort_round_fn(cfg, model, norm)
                        if cfg.diagnostics else round_fn_host)
                if chain_n > 1:
                    # cohort chaining survives faults AND keeps the full-
                    # telemetry cosine split: the scanned round index
                    # re-derives flags in-program (fl/rounds.make_cohort_step)
                    if n_mesh > 1:
                        from defending_against_backdoors_with_robust_learning_rate_tpu.parallel.rounds import (
                            make_sharded_chained_cohort_round_fn)
                        host_chained_fn = make_sharded_chained_cohort_round_fn(
                            plain_cfg, model, norm, mesh)
                    else:
                        from defending_against_backdoors_with_robust_learning_rate_tpu.fl.rounds import (
                            make_chained_cohort_round_fn)
                        host_chained_fn = make_chained_cohort_round_fn(
                            plain_cfg, model, norm)

                from defending_against_backdoors_with_robust_learning_rate_tpu.data import (
                    cohort as cohort_mod)

                def sample_ids(rnd):
                    # the host mirror of the in-program draw — bit-identical
                    # ids (data/cohort.py), evaluated on the prefetch thread.
                    # static: ok(host-sync)
                    ids, _active = cohort_mod.sample_cohort_host(cfg, rnd)
                    return ids

                def gather_unit(unit, enqueued_by=None):
                    """One dispatch unit's cohort payload: a single round's
                    [m, ...] stacks or a chained block's [chain, m, ...]
                    stacks — O(cohort) gather riding the prefetch thread, so
                    bank reads + H2D overlap the running round program."""
                    with tracer.span("prefetch/gather",
                                     parent=enqueued_by and (
                                         enqueued_by[0], unit[-1]),
                                     rounds=len(unit)):
                        ids = np.stack([sample_ids(r) for r in unit])
                        if len(unit) == 1:
                            imgs, lbls, szs = gather_rows(ids[0])
                            return (ids[0], take(imgs), take(lbls), take(szs))
                        rows = [gather_rows(i) for i in ids]
                        return (ids,
                                take_block(np.stack([r[0] for r in rows])),
                                take_block(np.stack([r[1] for r in rows])),
                                take_block(np.stack([r[2] for r in rows])))

                if cfg.host_prefetch > 0:
                    print(f"[prefetch] cohort gather pipeline, depth "
                          f"{cfg.host_prefetch}")
                get_unit = self._unit_fetcher(gather_unit)

                def host_sampler(params, key, rnd, want_diag):
                    with tracer.span("round/data_prep"):
                        _ids, imgs, lbls, szs = get_unit((rnd,))
                    fn = diag_round_fn_host if want_diag else round_fn_host
                    tracer.count("dispatch", family=self._family[
                        "diag" if want_diag else "round"])
                    with tracer.span("round/dispatch"):
                        # the round index is a traced int32 lead argument —
                        # the program recomputes the cohort (ids, flags,
                        # churn mask) from it; `sampled` in the info dict is
                        # the program's own draw
                        new_params, info = fn(params, key, jnp.int32(rnd),
                                              imgs, lbls, szs)
                    return new_params, info
        elif host_mode:
            with tracer.span("setup/build_programs"):
                print(f"[data] host-sampled mode "
                      f"({fed.train.images.nbytes / 2**30:.1f} GiB of shards)")
                # take(base, ids) materializes the round's sampled [m, ...]
                # stack for this mode: the multi-process variant never gathers
                # rows this process's devices don't own. take_block is the
                # chained variant: ids [chain, m] -> [chain, m, ...] block in
                # one placement.
                take = lambda a, ids: jnp.asarray(a[ids])  # noqa: E731
                take_block = take
                round_fn_host = None
                if cfg.mesh != 1 and jax.process_count() > 1:
                    # multi-process host-sampled: every process runs the
                    # identical seeded sampling over its (replicated) host
                    # dataset, then materializes only its addressable shards
                    # of the global [m, ...] stacks
                    # (multihost.take_agents_sharded); the shard_mapped round
                    # runs over ONE global agents mesh exactly like the
                    # device-resident multi-host path
                    from defending_against_backdoors_with_robust_learning_rate_tpu.parallel import (
                        multihost)
                    from defending_against_backdoors_with_robust_learning_rate_tpu.parallel.rounds import (
                        make_sharded_round_fn_host)
                    n_mesh = multihost.require_pod_divisible(
                        cfg.agents_per_round, "multi-host host-sampled")
                    mesh = multihost.global_agents_mesh(0)
                    print(f"[mesh] {n_mesh} global devices on the `agents` "
                          f"axis ({cfg.agents_per_round // n_mesh} "
                          f"agents/device), host-sampled shards, "
                          f"{jax.process_count()} processes")
                    take = lambda a, ids: multihost.take_agents_sharded(  # noqa: E731
                        mesh, a, ids)
                    take_block = lambda a, ids: \
                        multihost.take_agents_sharded_block(  # noqa: E731
                            mesh, a, ids)
                    params = multihost.put_replicated(mesh, params)
                    round_fn_host = make_sharded_round_fn_host(
                        plain_cfg, model, norm, mesh)
                    diag_round_fn_host = (
                        make_sharded_round_fn_host(cfg, model, norm, mesh)
                        if cfg.diagnostics else round_fn_host)
                elif cfg.mesh != 1:
                    # the m sampled shards gathered each round are fixed-shape
                    # [m, ...] stacks — partition them over the agents mesh
                    # (m/d per device) and run the shard_mapped round body
                    from jax.sharding import NamedSharding, PartitionSpec as P
                    from defending_against_backdoors_with_robust_learning_rate_tpu.parallel.mesh import (
                        AGENTS_AXIS, make_mesh, pick_agent_mesh_size)
                    from defending_against_backdoors_with_robust_learning_rate_tpu.parallel.rounds import (
                        make_sharded_round_fn_host)
                    n_mesh = pick_agent_mesh_size(cfg.mesh,
                                                  cfg.agents_per_round)
                    if n_mesh > 1:
                        mesh = make_mesh(n_mesh)
                        print(f"[mesh] {n_mesh} devices on the `agents` axis "
                              f"({cfg.agents_per_round // n_mesh} "
                              f"agents/device), host-sampled shards")
                        agents_sharding = NamedSharding(mesh, P(AGENTS_AXIS))
                        block_sharding = NamedSharding(mesh,
                                                       P(None, AGENTS_AXIS))
                        # device_put on the host array splits host->devices in
                        # one step (no staging copy through device 0)
                        take = lambda a, ids: jax.device_put(  # noqa: E731
                            a[ids], agents_sharding)
                        take_block = lambda a, ids: jax.device_put(  # noqa: E731
                            a[ids], block_sharding)
                        round_fn_host = make_sharded_round_fn_host(
                            plain_cfg, model, norm, mesh)
                        diag_round_fn_host = (
                            make_sharded_round_fn_host(cfg, model, norm, mesh)
                            if cfg.diagnostics else round_fn_host)
                if round_fn_host is None:
                    round_fn_host = make_round_fn_host(plain_cfg, model, norm)
                    diag_round_fn_host = (make_round_fn_host(cfg, model, norm)
                                          if cfg.diagnostics else round_fn_host)
                # one site builds the chained-host variant for whichever round
                # fn was picked above (sharded single- or multi-process mesh,
                # or single-device); a multi-process job WITHOUT the global
                # mesh gets no chaining (it is the redundant-work warning case
                # below). Host-sampled chaining is also skipped under faults:
                # the host step then takes per-round corrupt flags the chained
                # scan doesn't carry (device-resident chaining computes them
                # in-jit and is unaffected).
                if chain_n > 1 and (cfg.faults_enabled
                                    or attack_registry.in_jit(cfg)):
                    chain_n = 1
                    tag, why = (("faults", "faults") if cfg.faults_enabled
                                else ("attack", f"--attack {cfg.attack}"))
                    print(f"[{tag}] host-sampled mode: --chain disabled "
                          f"({why} needs per-round corrupt flags riding "
                          f"each dispatch)")
                if chain_n > 1:
                    if n_mesh > 1:
                        from defending_against_backdoors_with_robust_learning_rate_tpu.parallel.rounds import (
                            make_sharded_chained_round_fn_host)
                        host_chained_fn = make_sharded_chained_round_fn_host(
                            plain_cfg, model, norm, mesh)
                    elif jax.process_count() == 1:
                        from defending_against_backdoors_with_robust_learning_rate_tpu.fl.rounds import (
                            make_chained_round_fn_host)
                        host_chained_fn = make_chained_round_fn_host(
                            plain_cfg, model, norm)

                def sample_ids(rnd):
                    # per-round generator so --resume continues the same
                    # sampling sequence the uninterrupted run would have used
                    rng = np.random.default_rng(cfg.seed * 100_003 + rnd)
                    return rng.choice(cfg.num_agents, cfg.agents_per_round,
                                      replace=False)

                def gather_unit(unit, enqueued_by=None):
                    """One dispatch unit's payload: a single round's [m, ...]
                    stacks or a chained block's [chain, m, ...] stacks (one
                    placement). The span lands on whichever thread runs the
                    gather — the prefetch worker in pipelined mode, so
                    trace.json shows the overlap."""
                    with tracer.span("prefetch/gather",
                                     parent=enqueued_by and (
                                         enqueued_by[0], unit[-1]),
                                     rounds=len(unit)):
                        ids = np.stack([sample_ids(r) for r in unit])
                        if len(unit) == 1:
                            return (ids[0], take(fed.train.images, ids[0]),
                                    take(fed.train.labels, ids[0]),
                                    take(fed.train.sizes, ids[0]))
                        return (ids, take_block(fed.train.images, ids),
                                take_block(fed.train.labels, ids),
                                take_block(fed.train.sizes, ids))

                # host gather + H2D transfer overlap the running round program
                # (data/prefetch.py); created lazily at the first dispatch so
                # a resumed run prefetches from its restored start round
                if cfg.host_prefetch > 0:
                    print(f"[prefetch] host->device pipeline, depth "
                          f"{cfg.host_prefetch}")

                get_unit = self._unit_fetcher(gather_unit)

                def host_sampler(params, key, rnd, want_diag):
                    with tracer.span("round/data_prep"):
                        ids, imgs, lbls, szs = get_unit((rnd,))
                    fn = diag_round_fn_host if want_diag else round_fn_host
                    tracer.count("dispatch", family=self._family[
                        "diag" if want_diag else "round"])
                    with tracer.span("round/dispatch"):
                        if host_takes_flags(cfg):
                            # faults: the host-sampled ids determine which
                            # slots hold malicious agents
                            # (--faults_spare_corrupt participation); full
                            # telemetry: the honest/corrupt cosine split needs
                            # the same flags
                            flags = jnp.asarray(ids < cfg.num_corrupt)
                            new_params, info = fn(params, key, imgs, lbls, szs,
                                                  flags)
                        else:
                            new_params, info = fn(params, key, imgs, lbls, szs)
                    info["sampled"] = ids
                    return new_params, info
        else:
            with tracer.span("setup/place"):
                arrays = (jnp.asarray(fed.train.images),
                          jnp.asarray(fed.train.labels),
                          jnp.asarray(fed.train.sizes))
            tracer.count("data_bytes_placed",
                         sum(int(a.nbytes) for a in arrays))
            with tracer.span("setup/build_programs"):
                round_fn = make_round_fn(plain_cfg, model, norm, *arrays)
                diag_round_fn = (make_round_fn(cfg, model, norm, *arrays)
                                 if cfg.diagnostics else round_fn)
                if chain_n > 1:
                    from defending_against_backdoors_with_robust_learning_rate_tpu.fl.rounds import (
                        make_chained_round_fn)
                    chained_fn = make_chained_round_fn(plain_cfg, model,
                                                       norm, *arrays)
        # the families' names, for the bank and the `dispatch` counter
        # (an adopted executable no longer carries its own)
        if host_sampler is None:
            self._family = {
                k: fn.family for k, fn in (
                    ("round", round_fn), ("diag", diag_round_fn),
                    ("chained", chained_fn)) if fn is not None}
        else:
            kind = "cohort" if cohort_mode else "host"
            sfx = compile_cache.family_suffix(cfg)
            self._family = {"round": f"round_{kind}{sfx}",
                            "diag": f"round_{kind}_diag",
                            "chained": f"chained_{kind}{sfx}"}
        if chained_fn is not None or host_chained_fn is not None:
            print(f"[chain] {chain_n} rounds per compiled dispatch "
                  f"(lax.scan"
                  + (", host-sampled blocks)" if host_chained_fn is not None
                     else ")"))

        if async_mode and host_mode:
            raise ValueError(
                "--agg_mode buffered is not supported in host-sampled "
                "mode (this dataset is above the device-resident budget "
                "and the host step has no channel for the arrival draw); "
                "run cohort-sampled (--cohort_sampled on) so the round "
                "program owns the cohort, or --agg_mode sync")
        if async_mode and jax.process_count() > 1:
            raise NotImplementedError(
                "--agg_mode buffered is single-process for now — the "
                "carried buffer state is not yet multi-host replicated; "
                "run --agg_mode sync on multi-process jobs")
        if async_mode:
            # the engine's "params" slot becomes the (params, buffer)
            # carry: checkpointing, AOT avals, donation and the chained
            # scan all treat it as one pytree, which is what makes a
            # mid-buffer kill recover crash-exactly — the buffer rides
            # the digest-verified checkpoint like params do. Per-bin
            # telemetry accumulators ride the vmap paths only
            # (fl/buffered.init_state; the sharded paths degrade the
            # per-staleness split rather than paying per-bin collectives).
            params = (params, buffered_mod.init_state(
                cfg, params, per_bin=(n_mesh == 1)))

        if cfg.faults_enabled:
            print(f"[faults] dropout={cfg.dropout_rate} "
                  f"straggler={cfg.straggler_rate}@{cfg.straggler_epochs}ep "
                  f"corrupt={cfg.corrupt_rate}/{cfg.corrupt_mode} "
                  f"norm_cap={cfg.payload_norm_cap} "
                  f"rlr_threshold={cfg.rlr_threshold_mode}"
                  + (" spare_corrupt" if cfg.faults_spare_corrupt else ""))
        if cfg.churn_enabled:
            print(f"[churn] client lifecycles: available "
                  f"{cfg.churn_available} of phases, period "
                  f"{cfg.churn_period} rounds, churn_seed {cfg.churn_seed} "
                  f"(service/churn.py; away clients ride the "
                  f"participation mask)")
        if cfg.traffic_enabled:
            from defending_against_backdoors_with_robust_learning_rate_tpu.data import (
                traffic as traffic_mod)
            print(f"[traffic] diurnal availability: peak "
                  f"{cfg.traffic_peak_frac} / trough "
                  f"{cfg.traffic_trough_frac} over "
                  f"{cfg.traffic_day_rounds}-round days (mean "
                  f"{traffic_mod.mean_available(cfg):.2f}), latency sigma "
                  f"{cfg.traffic_latency_sigma}, traffic_seed "
                  f"{cfg.traffic_seed} (data/traffic.py; present clients "
                  f"ride the participation mask)")

        if jax.process_count() > 1 and n_mesh <= 1:
            # no global-mesh SPMD path was taken: every process would run
            # the identical seeded program independently — N-way duplicated
            # work, not a distributed job (ADVICE r1)
            print("[WARN] multi-process job without the global agents "
                  f"mesh: {jax.process_count()} processes are training "
                  "REDUNDANTLY. Set --mesh=0 (all devices) to distribute "
                  "the round over the pod.")

        if cfg.debug_nan:
            # sanitizer mode (SURVEY.md section 5.2): float checks compiled
            # into every round variant; raises on the first NaN/inf
            print("[guards] checkify float checks enabled (--debug_nan)")
            if host_sampler is None:
                round_fn = guard_round_fn(round_fn)
                diag_round_fn = guard_round_fn(diag_round_fn)
            else:
                round_fn_host = guard_round_fn(round_fn_host)
                diag_round_fn_host = guard_round_fn(diag_round_fn_host)
            if chained_fn is not None:
                chained_fn = guard_round_fn(chained_fn)
            if host_chained_fn is not None:
                host_chained_fn = guard_round_fn(host_chained_fn)

        with tracer.span("setup/build_programs"):
            # the image task's builder stays this module's own name: the
            # benchmark's defect tests patch `train.make_eval_fn`
            eval_fn = (task_mod.make_eval_fn(model, norm, cfg)
                       if task_mod.is_tokens(cfg)
                       else make_eval_fn(model, norm, cfg.n_classes))
            self._fisher_fn = None
            if cfg.diagnostics:
                from defending_against_backdoors_with_robust_learning_rate_tpu.fl.diagnostics import (
                    make_fisher_fn)
                self._fisher_fn = make_fisher_fn(model, norm)
        with tracer.span("setup/place"):
            val = tuple(map(jnp.asarray, pad_eval_set(
                fed.val_images, fed.val_labels, cfg.eval_bs)))
            pval = tuple(map(jnp.asarray, pad_eval_set(
                fed.pval_images, fed.pval_labels, cfg.eval_bs)))
        tracer.count("data_bytes_placed",
                     sum(int(a.nbytes) for a in val + pval))

        if writer is None:
            with tracer.span("setup/obs"):
                writer = (MetricsWriter(cfg.log_dir, run_name(cfg),
                                        cfg.tensorboard,
                                        start_fields={"device": self.device})
                          if lead else NullWriter())
        self.writer = writer

        base_key = jax.random.PRNGKey(cfg.seed)

        start_round, cum_poison_acc, self.cum_net_mov = 0, 0.0, 0.0
        health_ema = None
        # per-client suspicion ledger (obs/reputation.py): the host fold
        # of the in-jit rep_agree lane — lead process only (the writer's
        # discipline); every process still COMPILES the lane so program
        # families match across the pod. Observe-only: quarantine stays
        # the health ladder's decision.
        self._rep_tracker = (obs_reputation.ReputationTracker.for_config(
            cfg, population=cfg.num_agents)
            if self._rep_on and lead else None)
        self._rep_pending = []
        # ground truth touches ONLY the AUC evaluation row — the ranking
        # itself never reads a corrupt flag (obs/reputation.py)
        self._rep_pred = ((lambda cid: cid < cfg.num_corrupt)
                          if cfg.num_corrupt > 0 else None)
        if self._rep_tracker is not None and self._rep_tracker.sketch_mode:
            print(f"[reputation] population {cfg.num_agents:,} > cap "
                  f"{cfg.rep_population_cap:,}: count-min sketch + "
                  f"top-{cfg.rep_topk} heavy-hitter ledger "
                  f"(O(cohort + k) RSS)")
        if cfg.resume and cfg.checkpoint_dir:
            with tracer.span("setup/restore"):
                restored = ckpt.restore(
                    cfg.checkpoint_dir, params, upto=self._resume_upto,
                    upto_validated=self._resume_upto is not None)
                if restored is not None:
                    (start_round, params, base_key, cum_poison_acc,
                     self.cum_net_mov) = restored
                    if jax.process_count() > 1 and n_mesh > 1:
                        from defending_against_backdoors_with_robust_learning_rate_tpu.parallel import (
                            multihost)
                        params = multihost.put_replicated(mesh, params)
                    else:
                        params = jax.device_put(params)
                    # the health-EMA baseline rides the round journal
                    # (save_checkpoint writes it): restoring it is what keeps
                    # replayed Health/Loss_Z rows byte-identical across a
                    # crash-exact resume
                    for entry in ckpt.journal_read(cfg.checkpoint_dir):
                        if entry["round"] == start_round:
                            health_ema = entry.get("health") or None
                            # the suspicion ledger rides the same journal
                            # entry; restoring it is what keeps replayed
                            # Reputation/* rows byte-identical
                            if self._rep_tracker is not None:
                                self._rep_tracker.load_state(
                                    entry.get("reputation") or None)
                    print(f"[ckpt] resumed from round {start_round}")
                    # a per-life record (obs/events.PER_LIFE_PREFIXES): each
                    # process/segment that restores emits its own — a no-op
                    # outside the service plane (no ledger installed)
                    obs_events.emit("checkpoint/restore", round=start_round)

        # --- AOT adoption: swap jitted program families for banked
        # serialized executables (utils/compile_cache.py). A warm start
        # skips XLA entirely; a cold start compiles ahead-of-time and banks
        # the result. Scope: single-process, single-device programs only —
        # sharded round fns produce mesh-replicated params whose shardings
        # a Compiled lowered from plain avals rejects at call time, and
        # multi-process executables embed the local topology; both keep
        # plain jit, which still warm-starts through the persistent XLA
        # cache. Any per-family failure also falls back to jit.
        eval_val_fn = eval_pval_fn = eval_fn
        # a first-time compile is minutes of legitimate silence: flag the
        # compile window for the stall detectors until the first dispatch
        # unit has executed
        hb.update(phase="compile", compile_in_flight=True, force=True)
        if bank is not None and jax.process_count() == 1 and n_mesh == 1:
            ab = compile_cache.abstractify
            p_aval, k_aval = ab(params), ab(base_key)
            # eval programs take the BARE model params — in buffered mode
            # `params` is the (params, buffer-state) carry and handing
            # that aval to eval would lower model.apply over a tuple
            mp_aval = ab(params[0]) if async_mode else p_aval
            ids_aval = jax.ShapeDtypeStruct((chain_n,), jnp.int32)
            # churn — and scheduled-attack — round programs take the
            # round index as a traced int32 scalar (single source
            # fl/rounds.step_takes_round, with plan_programs)
            lead_avals = ((jax.ShapeDtypeStruct((), jnp.int32),)
                          if step_takes_round(cfg) else ())
            if cohort_mode or host_sampler is not None:
                # one adoption triad (round / diag / chained block) for
                # both [m, ...]-stack branches; they differ only in
                # family names and the per-round signature — cohort
                # takes the traced round index as a lead int32 and no
                # flag avals (flags derive in-program from the
                # recomputed cohort ids), host takes trailing corrupt
                # flags when faults/full telemetry need them
                m = cfg.agents_per_round
                shard_avals = tuple(
                    jax.ShapeDtypeStruct((m,) + a.shape[1:], a.dtype)
                    for a in (fed.train.images, fed.train.labels,
                              fed.train.sizes))
                fams = tuple(self._family[k]
                             for k in ("round", "diag", "chained"))
                if cohort_mode:
                    round_avals = (
                        (p_aval, k_aval,
                         jax.ShapeDtypeStruct((), jnp.int32))
                        + shard_avals)
                else:
                    flag_avals = ((jax.ShapeDtypeStruct((m,), jnp.bool_),)
                                  if host_takes_flags(cfg) else ())
                    round_avals = ((p_aval, k_aval) + shard_avals
                                   + flag_avals)
                shared = diag_round_fn_host is round_fn_host
                fn = _adopt_aot(bank, cfg, fams[0], round_fn_host,
                                round_avals)
                if fn is not None:
                    round_fn_host = fn
                    if shared:
                        diag_round_fn_host = fn
                if cfg.diagnostics:
                    fn = _adopt_aot(bank, cfg, fams[1],
                                    diag_round_fn_host, round_avals)
                    if fn is not None:
                        diag_round_fn_host = fn
                if host_chained_fn is not None:
                    block_avals = tuple(
                        jax.ShapeDtypeStruct((chain_n,) + a.shape, a.dtype)
                        for a in shard_avals)
                    fn = _adopt_aot(bank, cfg, fams[2], host_chained_fn,
                                    (p_aval, k_aval, ids_aval)
                                    + block_avals)
                    if fn is not None:
                        host_chained_fn = fn
            else:
                data_avals = ab(arrays)
                fn = _adopt_aot(bank, cfg, round_fn.family, round_fn.jitted,
                                (p_aval, k_aval) + lead_avals + data_avals)
                if fn is not None:
                    round_fn = _bind_compiled(fn, round_fn.data)
                    if not cfg.diagnostics:
                        diag_round_fn = round_fn
                if cfg.diagnostics:
                    fn = _adopt_aot(bank, cfg, diag_round_fn.family,
                                    diag_round_fn.jitted,
                                    (p_aval, k_aval) + lead_avals
                                    + data_avals)
                    if fn is not None:
                        diag_round_fn = _bind_compiled(fn,
                                                       diag_round_fn.data)
                if chained_fn is not None:
                    fn = _adopt_aot(bank, cfg, chained_fn.family,
                                    chained_fn.jitted,
                                    (p_aval, k_aval, ids_aval) + data_avals)
                    if fn is not None:
                        chained_fn = _bind_compiled(fn, chained_fn.data)
            fn = _adopt_aot(bank, cfg, "eval_val", eval_fn,
                            (mp_aval,) + ab(val))
            if fn is not None:
                eval_val_fn = fn
            fn = _adopt_aot(bank, cfg, "eval_poison", eval_fn,
                            (mp_aval,) + ab(pval))
            if fn is not None:
                eval_pval_fn = fn

        with tracer.span("setup/obs"):
            # sampled device-trace window (--profile_rounds N,
            # obs/attribution.py): opens at the first STEADY dispatch unit
            # (never the compile unit), closes after N rounds, and is parsed
            # into Device/* + Memory/* attribution rows after the loop. A bare
            # --profile_dir (without --profile_rounds) keeps its historical
            # whole-run trace semantics.
            self.prof = None
            if cfg.profile_rounds > 0 and lead:
                run_dir_hint = getattr(writer, "dir", None) or cfg.log_dir
                self.prof = obs_attribution.RoundProfiler(
                    cfg.profile_rounds,
                    cfg.profile_dir or os.path.join(run_dir_hint, "profile"))
            self._whole_run_trace = bool(cfg.profile_dir and lead
                                         and self.prof is None)
            if self._whole_run_trace:
                jax.profiler.start_trace(cfg.profile_dir)

            # incident flight recorder (obs/flight.py): a bounded per-round
            # ring + crash-exact flight.jsonl next to metrics.jsonl, lead
            # process only. A unit's span durations are the tracer's own
            # per-unit group — no extra timing calls on the hot path.
            self.flight = None
            if cfg.flight == "on" and lead:
                flight_dir = getattr(writer, "dir", None) or cfg.log_dir
                flight_run = run_name(cfg)
                self.flight = obs_flight.FlightRecorder(
                    os.path.join(flight_dir, obs_flight.STREAM_NAME),
                    run=flight_run, corr=obs_events.corr_id(flight_run),
                    slot=f"p{jax.process_index()}"
                         + (f"-E{cfg.tenants}" if cfg.tenants > 0 else ""),
                    span_source=tracer.unit_ms if tracer.enabled else None)

            # --- async metrics pipeline: per-round/eval scalars stay on device
            # and drain through a background thread's batched device_get, so
            # the round loop never blocks on a host sync (~24% of round time on
            # the small CNN, r3 flagship ladder). Diagnostics and --debug_nan
            # need inline host values; multi-process jobs keep the lead-only
            # writer synchronous.
            use_async = (cfg.async_metrics and not cfg.debug_nan
                         and not cfg.diagnostics and jax.process_count() == 1)
            self.drain = MetricsDrain(tracer=tracer) if use_async else None
            if self.drain is not None:
                print("[metrics] async drain: host syncs ride a background "
                      "thread (--sync_metrics restores the inline path)")
        # steady-state clock (VERDICT r1 #9): stamped in emit_eval, i.e.
        # when a boundary's values ARRIVE (post-execution) — in async mode
        # the dispatch timestamps would measure queueing, not compute
        self.mstate = {"cum_poison_acc": cum_poison_acc, "summary": {},
                       "t_steady": None, "r_steady": 0,
                       "t_steady_end": None, "r_steady_end": 0,
                       # health-EMA baseline (health/sentinel.py):
                       # journal-restored on resume so replayed Health/*
                       # rows are byte-identical
                       "health_ema": health_ema}

        # engine state the step methods advance
        self.params = params
        self.base_key = base_key
        self.start_round = start_round
        self.rnd = start_round
        self.rounds_done = 0
        self.first_unit = True
        self.chain_n = chain_n
        self.n_mesh = n_mesh
        self.host_mode = host_mode
        self.cohort_mode = cohort_mode
        self.val, self.pval = val, pval
        self._round_fn, self._diag_round_fn = (
            (round_fn, diag_round_fn) if host_sampler is None
            else (None, None))
        self._host_sampler = host_sampler
        self._get_unit_impl = get_unit
        self._chained_fn, self._host_chained_fn = chained_fn, host_chained_fn
        self._eval_val_fn, self._eval_pval_fn = eval_val_fn, eval_pval_fn
        self._last_info = {}
        # a dispatch allocates the unit's new parameters at once, however
        # many units the device still has ahead of it: the loop waits for
        # an earlier unit where one more would not fit `units_ahead`
        self._units_ahead = units_ahead(
            sum(x.size * x.dtype.itemsize for x in
                jax.tree_util.tree_leaves(self.model_params)),
            compile_cache.device_memory_limit())
        self._in_flight = collections.deque()
        self._last_unit_rounds = 1
        self._want_diag = False
        self._prev_params = None
        self.t_loop = time.perf_counter()

    # ------------------------------------------------------------- schedule

    @property
    def chaining(self) -> bool:
        return (self._chained_fn is not None
                or self._host_chained_fn is not None)

    @property
    def model_params(self):
        """The bare model parameters: in buffered-async mode the engine's
        ``params`` slot holds the (params, buffer-state) carry
        (fl/buffered.py) — eval, profiling and the summary read the model
        half through this property."""
        return self.params[0] if self.async_mode else self.params

    def schedule(self):
        """The one-shot dispatch plan from the engine's (restored) start
        round to cfg.rounds. ONE source of truth for chaining decisions:
        the loop consumes the same schedule the host-mode prefetcher
        produces against, so the two cannot desynchronize (code review
        r3)."""
        units = dispatch_schedule(
            self.start_round, self.cfg.rounds, self.cfg.snap, self.chain_n,
            self.cfg.diagnostics, self.chaining)
        self.set_schedule(units)
        return units

    def set_schedule(self, units) -> None:
        """Pin the unit stream the host-mode prefetcher will produce
        against (any iterable of round-id tuples; the service driver
        passes a generator). Must be called before the first dispatch."""
        self._sched_units = units

    # ------------------------------------------------------------- stepping

    def _round_lead(self, rnd):
        # churn — and scheduled-attack — round programs take the round
        # index as a traced lead argument (fl/rounds.step_takes_round is
        # the single source; the AOT aval planner agrees)
        return ((jnp.int32(rnd),)
                if step_takes_round(self.cfg) else ())

    def dispatch(self, unit, nonce: int = 0) -> None:
        """Run one dispatch unit (a single round or a chained block):
        advances params/rnd/rounds_done, records spans/heartbeat, feeds
        the profiler, and emits the snap-round diagnostics scalars.

        ``nonce`` (health/monitor.py DISCARD rung) folds a recovery
        nonce into the single-round key so a withdrawn round re-draws
        its stochastic choices deterministically; 0 (every normal
        dispatch) keeps the historical derivation bit-for-bit. Chained
        blocks never take a nonce (the service driver, the only ladder
        host, dispatches unchained)."""
        # every span from here to the unit's post_unit (and what the
        # drain thread does for its boundary) carries the unit's last round
        self.tracer.set_unit(unit[-1])
        with self.tracer.span("engine/dispatch"):
            self._dispatch(unit, nonce)

    def _dispatch(self, unit, nonce: int) -> None:
        cfg, tracer = self.cfg, self.tracer
        self.hb.update(phase="train", round=unit[-1])
        if self.flight is not None:
            self.flight.begin_unit()
        self._last_unit_rounds = len(unit)
        if self.prof is not None and not self.first_unit:
            # steady state: every hot-path program compiled during the
            # first unit, so the window never captures XLA working
            self.prof.maybe_start()
        while self._units_ahead and len(
                self._in_flight) >= self._units_ahead:
            with tracer.span("round/wait_room"):
                # static: ok(host-sync)
                self._in_flight.popleft().block_until_ready()
        if len(unit) > 1:
            # chained block: fixed length => one compilation per shape
            with tracer.span("round/data_prep"):
                ids = jnp.arange(unit[0], unit[-1] + 1)
                payload = (None if self._chained_fn is not None
                           else self._get_unit(unit))
            with tracer.span("round/dispatch", chain=len(unit)):
                tracer.count("dispatch", family=self._family["chained"])
                if self._chained_fn is not None:
                    self.params, stacked = self._chained_fn(
                        self.params, self.base_key, ids)
                else:
                    # host-sampled block: the prefetcher hands over the
                    # whole [chain, m, ...] shard-stack payload at once
                    _, imgs, lbls, szs = payload
                    self.params, stacked = self._host_chained_fn(
                        self.params, self.base_key, ids, imgs, lbls, szs)
            self.rnd = unit[-1]
            self.rounds_done += len(unit)
            info = {"train_loss": stacked["train_loss"][-1]}
            info.update({k: stacked[k][-1] for k in CHAINED_INFO_KEYS
                         if k in stacked})
            info.update({k: stacked[k][-1] for k in stacked
                         if k.startswith(("tel_", "hlth_"))})
            if self._rep_tracker is not None and "rep_agree" in stacked:
                # [chain, m] agreement rows + matching REAL client ids:
                # device-resident scans stack their in-program draw
                # ("sampled"); host/cohort blocks don't carry it through
                # the scan — the payload's id block is the bit-identical
                # host mirror. Rows stay on device until the boundary's
                # (async) drain fetch.
                ids_blk = stacked.get("sampled")
                if ids_blk is None and payload is not None:
                    ids_blk = payload[0]
                if ids_blk is not None:
                    self._rep_pending.append((tuple(unit), ids_blk,
                                              stacked["rep_agree"],
                                              stacked["rep_norm"]))
            self._want_diag, self._prev_params = False, None
        else:
            rnd = unit[0]
            with tracer.span("round/data_prep"):
                key = jax.random.fold_in(self.base_key, rnd)
                if nonce:
                    key = jax.random.fold_in(
                        key, health_monitor.RECOVERY_NONCE + nonce)
                snap_round = rnd % cfg.snap == 0
                self._want_diag = cfg.diagnostics and snap_round
                self._prev_params = self.params if self._want_diag else None
            if self._host_sampler is not None:
                # host_sampler opens its own data_prep/dispatch spans (the
                # gather is the interesting part there)
                self.params, info = self._host_sampler(
                    self.params, key, rnd, self._want_diag)
            else:
                with tracer.span("round/dispatch"):
                    fn = (self._diag_round_fn if self._want_diag
                          else self._round_fn)
                    tracer.count("dispatch", family=self._family[
                        "diag" if self._want_diag else "round"])
                    self.params, info = fn(self.params, key,
                                           *self._round_lead(rnd))
            self.rnd = rnd
            self.rounds_done += 1
            if (self._rep_tracker is not None and "rep_agree" in info
                    and "sampled" in info):
                if nonce:
                    # DISCARD-rung re-dispatch: the withdrawn attempt's
                    # evidence must not fold alongside the redrawn round
                    self._rep_pending = [p for p in self._rep_pending
                                         if p[0] != (rnd,)]
                self._rep_pending.append(((rnd,), info["sampled"],
                                          info["rep_agree"],
                                          info["rep_norm"]))
        self._last_info = info
        if self._units_ahead:
            # the unit's loss: an output of its program that nothing donates
            self._in_flight.append(info["train_loss"])
        if self.prof is not None:
            # accounts the unit toward the capture budget and polls the
            # HBM watermarks; closes the window (blocking on params first)
            # once the budget is reached
            self.prof.after_unit(self.params, len(unit))
        if self._want_diag:
            self._emit_diagnostics(info)

    def _unit_fetcher(self, gather_unit):
        """The payload-fetch closure shared by the host-sampled and
        cohort-sampled branches: direct gather, or the depth-bounded
        prefetch pipeline (data/prefetch.py) created lazily at the first
        dispatch. _sched_units is THE loop's schedule (set before the
        loop starts; the first get_unit call is its first entry), so
        production order provably matches consumption order."""
        cfg = self.cfg

        def get_unit(unit):
            if cfg.host_prefetch > 0:
                if self._prefetcher is None:
                    from defending_against_backdoors_with_robust_learning_rate_tpu.data.prefetch import (
                        RoundPrefetcher)
                    # the whole schedule is enqueued here, once: the
                    # worker's gathers name the span open now as parent
                    enqueued_by = self.tracer.handoff()
                    self._prefetcher = RoundPrefetcher(
                        lambda u: gather_unit(u, enqueued_by),
                        self._sched_units, depth=cfg.host_prefetch)
                return self._prefetcher.get(unit)
            return gather_unit(unit)

        return get_unit

    def _get_unit(self, unit):
        if self._get_unit_impl is None:
            raise RuntimeError("host payload requested outside host mode")
        # the host branch's get_unit closure (set in __init__)
        return self._get_unit_impl(unit)

    def _emit_diagnostics(self, info) -> None:
        cfg, writer, rnd = self.cfg, self.writer, self.rnd
        from defending_against_backdoors_with_robust_learning_rate_tpu.fl.diagnostics import (
            norm_scalars, sign_agreement)
        if "agent_norms" in info:
            for tag, v in norm_scalars(info["agent_norms"],
                                       info["sampled"],
                                       cfg.num_corrupt).items():
                writer.scalar(tag, v, rnd)
        if "lr_flat" in info:
            from jax.flatten_util import ravel_pytree
            pval = self.pval
            # Fisher at the pre-update params (aggregation.py:146-148)
            f_adv = ravel_pytree(self._fisher_fn(self._prev_params,
                                                 *pval))[0]
            hon_labels = jnp.full_like(pval[1], cfg.base_class)
            f_hon = ravel_pytree(
                self._fisher_fn(self._prev_params, pval[0], hon_labels,
                                pval[2]))[0]
            upd_flat = (ravel_pytree(self.params)[0]
                        - ravel_pytree(self._prev_params)[0])
            # --diagnostics is the synchronous research mode by design
            # (the async drain is disabled); these fetches happen at snap
            # cadence only.
            # static: ok(host-sync)
            scalars, self.cum_net_mov = sign_agreement(
                np.asarray(info["lr_flat"]), np.asarray(upd_flat),
                np.asarray(f_adv), np.asarray(f_hon),
                cfg.top_frac, cfg.effective_server_lr, self.cum_net_mov)
            for tag, v in scalars.items():
                writer.scalar(tag, v, rnd)

    def eval_boundary(self, rnd: int) -> None:
        """One eval boundary: dispatch the two eval programs on the
        (un-donated) params and route the values through the async drain
        (or emit inline in sync mode)."""
        with self.tracer.span("engine/eval_boundary"):
            self._eval_boundary(rnd)

    def _eval_boundary(self, rnd: int) -> None:
        cfg, tracer, info = self.cfg, self.tracer, self._last_info
        # HBM watermarks ride the heartbeat so the session stall detectors
        # see memory pressure, not just phase ({} on backends without
        # allocator stats)
        with tracer.span("obs/memory_poll"):
            mem = obs_attribution.memory_watermarks()
        self.hb.update(phase="eval", round=rnd, **mem)
        if self.flight is not None and mem:
            self.flight.note(**mem)
        # divergence aborts only under --debug_nan (sync mode); otherwise
        # the finite check rides the drain and warns, and the run keeps
        # recording its (NaN) metrics
        with tracer.span("eval/finite_dispatch"):
            vals = {"finite": all_finite_device(self.params)}
        # eval dispatches on the (un-donated) params BEFORE the next
        # dispatch unit runs: in async mode round r's eval executes
        # overlapped with the round r+1 training block
        with tracer.span("eval/val_dispatch"):
            val_loss_d, val_acc_d, per_class_d = self._eval_val_fn(
                self.model_params, *self.val)
        with tracer.span("eval/poison_dispatch"):
            poison_loss_d, poison_acc_d, _ = self._eval_pval_fn(
                self.model_params, *self.pval)
        vals.update(val_loss=val_loss_d, val_acc=val_acc_d,
                    poison_loss=poison_loss_d,
                    poison_acc=poison_acc_d,
                    train_loss=info["train_loss"])
        if task_mod.is_tokens(cfg):
            # the eval's third value is the pairs its tokens were routed
            # to, and the round's router counters ride the same fetch
            vals["moe_eval_pairs"] = per_class_d
            vals.update({k: info[k] for k in task_mod.MOE_ROUND_KEYS
                         + (task_mod.MTP_LOSS,) if k in info})
        else:
            vals["base_acc"] = per_class_d[cfg.base_class]
        if "fault_voters" in info:
            vals.update({k: info[k] for k in FAULT_INFO_KEYS})
        if "churn_away" in info:
            vals["churn_away"] = info["churn_away"]
        if "async_fill" in info:
            # buffered-aggregation observability (fl/buffered.py)
            vals.update({k: info[k]
                         for k in buffered_mod.ASYNC_INFO_KEYS})
        # in-jit defense telemetry rides the same (async) fetch
        vals.update({k: info[k] for k in info if k.startswith("tel_")})
        # health-sentinel scalars (health/sentinel.py): the [m] suspect
        # vector stays in the info dict — it is ladder evidence
        # (service/driver.py), not a metrics row
        vals.update({k: info[k]
                     for k in health_sentinel.boundary_keys(cfg)
                     if k in info})
        if self._rep_tracker is not None and self._rep_pending:
            # per-round (round_ids, client_ids, rep_agree, rep_norm) rows
            # since the last boundary ride the same (async) fetch; the
            # tracker fold happens host-side in _emit_eval_body, on the
            # drain thread in async mode
            vals["rep_rows"] = self._rep_pending
            self._rep_pending = []
        if self.drain is not None:
            elapsed = time.perf_counter() - self.t_loop
            self.drain.submit(self._emit_eval, vals, rnd, self.rounds_done,
                              elapsed, tracer.handoff())
        else:
            with tracer.span("metrics/host_sync"):
                # this IS the --sync_metrics fallback path; async mode
                # routes the same fetch through the MetricsDrain instead.
                # static: ok(host-sync)
                vals = jax.device_get(vals)  # THE per-round sync
            elapsed = time.perf_counter() - self.t_loop
            self._emit_eval(vals, rnd, self.rounds_done, elapsed)

    def _emit_eval(self, vals, ernd, rounds_done_now, elapsed,
                   enqueued_by=None):
        """One eval boundary's host side-effects, in the exact synchronous
        order. Sync mode calls it inline with fetched values; async mode
        runs it on the drain thread — one code path, so metrics.jsonl is
        bit-identical between the modes (tests/test_async_metrics.py).
        The cumulative poison mean accumulates HERE in host float64,
        matching the synchronous semantics exactly. `enqueued_by` is the
        boundary's span, handed over with the work in async mode."""
        with self.tracer.span("metrics/emit", parent=enqueued_by):
            self._emit_eval_body(vals, ernd, rounds_done_now, elapsed)

    def _emit_eval_body(self, vals, ernd, rounds_done_now, elapsed):
        # service/tenancy.run_pack's emit() mirrors this row schema
        # per tenant — a new scalar series added here must be fanned
        # out there too, or packed tenants' streams silently diverge
        # from their solo twins (the tenancy parity tests pin the
        # series they exercise, not future ones)
        cfg, writer, mstate = self.cfg, self.writer, self.mstate
        # unified divergence policy (health/monitor.py): the historical
        # finite_warn / --debug_nan endpoints AND the sentinel-lane
        # judgement (z-score, norm spike) route through ONE assessment;
        # `abort` raises here, `record`/`recover` warn and keep the
        # metrics flowing. The EMA state commits LAST (with
        # cum_poison_acc): a supervised retry of this body must not
        # double-fold the baseline.
        health_report = health_monitor.assess(cfg, mstate["health_ema"],
                                              vals)
        health_monitor.emit_rows(writer, health_report, ernd)
        health_monitor.enforce(cfg, health_report, where=f"round {ernd}")
        val_loss = float(vals["val_loss"])
        val_acc = float(vals["val_acc"])
        poison_loss = float(vals["poison_loss"])
        poison_acc = float(vals["poison_acc"])
        # computed into a local and committed to mstate only at the very
        # end: the service supervisor retries a transiently-failed eval
        # unit by re-running this body, and an accumulate-first ordering
        # would double-count poison_acc into the checkpointed cumulative
        cum_poison_acc = mstate["cum_poison_acc"] + poison_acc
        # scalar names preserved from src/federated.py:81-91
        writer.scalar("Validation/Loss", val_loss, ernd)
        writer.scalar("Validation/Accuracy", val_acc, ernd)
        if "base_acc" in vals:
            writer.scalar("Poison/Base_Class_Accuracy",
                          float(vals["base_acc"]), ernd)
        writer.scalar("Poison/Poison_Accuracy", poison_acc, ernd)
        writer.scalar("Poison/Poison_Loss", poison_loss, ernd)
        writer.scalar("Poison/Cumulative_Poison_Accuracy_Mean",
                      cum_poison_acc / ernd, ernd)
        writer.scalar("Train/Loss", float(vals["train_loss"]), ernd)
        if task_mod.MTP_LOSS in vals:
            # the auxiliary term alone, before its weight (fl/task.py)
            writer.scalar("Train/MTP_Loss", float(vals[task_mod.MTP_LOSS]),
                          ernd)
        if "moe_eval_pairs" in vals:
            # router load (fl/task.py): the validation tokens' pairs by
            # sparse layer and held expert (last column: experts not held
            # here), and the round's counters, which also enter the tracer
            for li, row in enumerate(np.asarray(vals["moe_eval_pairs"])):
                for e, c in enumerate(row[:-1]):
                    writer.scalar(f"Moe/Eval_Pairs/L{li}E{e}", float(c),
                                  ernd)
                writer.scalar(f"Moe/Eval_Pairs/L{li}Absent", float(row[-1]),
                              ernd)
            for k in task_mod.MOE_ROUND_KEYS:
                if k in vals:
                    writer.scalar("Moe/" + k[4:].title(), float(vals[k]),
                                  ernd)
                    self.tracer.count(k, float(vals[k]))
        if "fault_voters" in vals:
            # degradation observability (faults/ + service/churn.py): who
            # failed this round, and how thin the electorate got
            writer.scalar("Faults/Dropped",
                          float(vals["fault_dropped"]), ernd)
            writer.scalar("Faults/Straggled",
                          float(vals["fault_straggled"]), ernd)
            writer.scalar("Faults/Effective_Voters",
                          float(vals["fault_voters"]), ernd)
        if "churn_away" in vals:
            writer.scalar("Churn/Sampled_Away",
                          float(vals["churn_away"]), ernd)
        if "async_fill" in vals:
            # buffered-mode observability: how full the buffer ran, and
            # the staleness mix it accumulated since the last commit
            writer.scalar("Async/Buffer_Fill",
                          float(vals["async_fill"]), ernd)
            if self.flight is not None:
                # flight-record the fill on the same (possibly drain-)
                # thread that materialized it — note() is lock-guarded
                self.flight.note(buffer_fill=float(vals["async_fill"]))
            writer.scalar("Async/Committed",
                          float(vals["async_committed"]), ernd)
            for i, c in enumerate(vals["async_stale_hist"]):
                writer.scalar(f"Async/Staleness_Hist/{i}", float(c), ernd)
        # Defense/* telemetry scalars (obs/telemetry.py), shared emit path
        # so sync and async streams stay bit-identical
        obs_telemetry.emit_scalars(writer, vals, ernd)
        # suspicion ledger fold + Reputation/* rows (obs/reputation.py):
        # popped so a supervised retry of this body cannot double-fold
        # the longitudinal EMA/streak state
        rep_rows = vals.pop("rep_rows", None)
        if self._rep_tracker is not None and rep_rows is not None:
            tracker = self._rep_tracker
            for rnds, row_ids, agrees, norms in rep_rows:
                row_ids, agrees = np.asarray(row_ids), np.asarray(agrees)
                norms = np.asarray(norms)
                if agrees.ndim == 1:
                    tracker.fold(rnds[0], row_ids, agrees, norms)
                else:
                    for j, r in enumerate(rnds):
                        tracker.fold(r, row_ids[j], agrees[j], norms[j])
            obs_reputation.emit_rows(writer, tracker, ernd,
                                     self._rep_pred)
            for ev in tracker.drain_events():
                # typed ledger event on the streak crossing; replay-
                # deduped (obs/events.REPLAY_DEDUPE_EVENTS) so crash-
                # exact resumes don't re-announce the same suspect
                obs_events.emit(obs_reputation.SUSPECT_EVENT,
                                severity="warn", **ev)
        writer.scalar("Throughput/Rounds_Per_Sec",
                      rounds_done_now / elapsed, ernd)
        now = time.perf_counter()
        if (mstate["t_steady"] is not None
                and rounds_done_now > mstate["r_steady"]):
            writer.scalar("Throughput/Steady_Rounds_Per_Sec",
                          (rounds_done_now - mstate["r_steady"])
                          / (now - mstate["t_steady"]), ernd)
        print(f'| Rnd {ernd}: Val_Loss/Val_Acc: {val_loss:.3f} / '
              f'{val_acc:.3f} |')
        print(f'| Rnd {ernd}: Poison Loss/Poison Acc: {poison_loss:.3f} / '
              f'{poison_acc:.3f} |')
        mstate["summary"] = {
            "round": ernd, "val_loss": val_loss, "val_acc": val_acc,
            "poison_loss": poison_loss, "poison_acc": poison_acc,
            "rounds_per_sec": rounds_done_now / elapsed}
        if health_report["rows"]:
            # the lane's verdict as data: queue rows read it from the
            # run summary (service/queue.SUMMARY_KEYS "health"); the
            # service LADDER deliberately does not — it judges the raw
            # sentinel lanes synchronously from eng._last_info
            # (health/monitor.HealthLadder.check), ahead of this
            # (possibly async-drained) emit
            mstate["summary"]["health"] = {
                k: float(v) for k, v in health_report["rows"].items()}
        tel = obs_telemetry.host_summary(vals)
        if tel:
            # the mechanism's state as data: the scenario-matrix rows
            # (service/queue.py SUMMARY_KEYS) and the online threshold-
            # adaptation controller (attack/adapt.py — reads the stash
            # after the boundary's drain flush) both consume this
            mstate["summary"]["defense"] = tel
            mstate["defense"] = tel
            # freshness stamp: a skipped/degraded eval boundary must not
            # let the adaptation controller decide on the previous
            # boundary's snapshot (service/driver.py checks this)
            mstate["defense_round"] = ernd
        if self._rep_tracker is not None:
            rep_sum = self._rep_tracker.summary(self._rep_pred)
            # the queue/sweep cells read this key (service/queue.py
            # SUMMARY_KEYS "suspicion")
            mstate["summary"]["suspicion"] = rep_sum
            if tel:
                # scalar enrichment of the defense block — float values
                # only, so consumers that iterate the block's rows
                # (attack/adapt.py) stay type-stable
                tel["rep_suspects"] = float(rep_sum["suspect_count"])
                if "auc" in rep_sum:
                    tel["rep_auc"] = float(rep_sum["auc"])
        if mstate["t_steady"] is None:
            # first eval boundary done: every program variant on the hot
            # path has now compiled (or loaded) at least once
            mstate["t_steady"] = now
            mstate["r_steady"] = rounds_done_now
        else:
            # steady window always ends at a snap boundary: a final
            # partial segment (rounds % snap != 0) may fall back to the
            # never-yet-compiled unchained round fn, and that compile must
            # not pollute the compile-free metric
            mstate["t_steady_end"] = now
            mstate["r_steady_end"] = rounds_done_now
        writer.flush()
        mstate["cum_poison_acc"] = cum_poison_acc   # commit LAST (see top)
        mstate["health_ema"] = health_report["new_state"]

    def drain_flush(self, timeout: Optional[float] = None) -> None:
        """Surface queued metrics (and any drain-thread error) now."""
        if self.drain is not None:
            with self.tracer.span("drain/wait"):
                self.drain.flush(timeout=timeout)

    def save_checkpoint(self, rnd: int, journal: bool = True,
                        drain_timeout: Optional[float] = None) -> None:
        """Checkpoint at an eval boundary. Every process calls save: orbax
        runs cross-process barriers inside and writes replicated data from
        the primary only — lead-gating it would deadlock a multi-host job.
        The drain is flushed first (`drain_timeout` is the service
        supervisor's wedge budget — TimeoutError classifies as wedged):
        the saved cum_poison_acc must include every eval boundary up to
        this round. With `journal`, the metrics byte offset is recorded
        for crash-exact resume (utils/checkpoint.py round journal)."""
        cfg = self.cfg
        if not cfg.checkpoint_dir:
            return
        self.drain_flush(timeout=drain_timeout)
        self.hb.update(phase="checkpoint", round=rnd)
        with self.tracer.span("ckpt/save"):
            # -1 = auto: keep everything in the one-shot trainer (historic
            # behavior); serve() replaces it with its bounded default
            keep = max(cfg.service_keep_ckpts, 0)
            ckpt.save(cfg.checkpoint_dir, rnd, self.params, self.base_key,
                      self.mstate["cum_poison_acc"], self.cum_net_mov,
                      keep_last=keep)
        # replay-deduped (obs/events.REPLAY_DEDUPE_EVENTS): a crash-exact
        # resume that re-saves an already-ledgered boundary re-emits
        # nothing, so interrupted and uninterrupted twins stay
        # byte-identical; emitted BEFORE the journal write so a kill in
        # between leaves the dedupe mark, not a missing record
        obs_events.emit("checkpoint/save", round=rnd)
        if journal:
            offset = getattr(self.writer, "offset", None)
            if offset is not None:
                # the health-EMA baseline rides the journal entry: a
                # crash-exact resume restores it alongside the metrics
                # splice so replayed Health/* rows are byte-identical
                extra = {"health": self.mstate["health_ema"]}
                if self._rep_tracker is not None:
                    # the suspicion ledger rides the same entry
                    # (crash-exact Reputation/* rows); keyed only when
                    # the lane is on, so an off run's journal is
                    # byte-identical to the pre-plane format
                    extra["reputation"] = self._rep_tracker.state_dict()
                ckpt.journal_record(cfg.checkpoint_dir, rnd, offset(),
                                    keep_last=keep, **extra)

    def post_unit(self) -> None:
        """End-of-unit bookkeeping: flip the compile flag after the first
        unit (from here a silent heartbeat means a stall, not XLA working),
        close the flight record and flush the writer in sync mode."""
        with self.tracer.span("engine/post_unit"):
            self._post_unit()

    def _post_unit(self) -> None:
        if self.first_unit:
            self.first_unit = False
            self.hb.update(compile_in_flight=False, force=True)
        if self.flight is not None:
            self.flight.end_unit(
                self.rnd, unit_rounds=self._last_unit_rounds,
                drain_depth=(self.drain.pending
                             if self.drain is not None else None))
        if self.drain is None:
            self.writer.flush()

    # ------------------------------------------------------------- teardown

    def close(self) -> None:
        """Release threads/devices — the `finally` step. Any exception must
        still tear down the prefetch worker (it pins device arrays and
        would leak per failed run); the drain closes without raising, to
        not mask a loop exception with a secondary metrics error."""
        if self.drain is not None:
            self.drain.close(raise_errors=False)
        if self._prefetcher is not None:
            self._prefetcher.close()
            self._prefetcher = None
        if self.prof is not None:
            # a run shorter than the budget still flushes its window
            self.prof.close(self.params)
        if self.flight is not None:
            # stream handle only — the ring stays live so the driver can
            # still snapshot a post-teardown incident (recovery re-entry)
            self.flight.close()
        # the compile listener only: the records stay for finalize() and
        # for readers that come after (obs/spans.current())
        self.tracer.close()

    def finalize(self) -> Dict:
        """Post-loop summary: throughput, attribution, memory watermarks,
        span aggregates; closes the writer and the heartbeat."""
        cfg, writer, mstate = self.cfg, self.writer, self.mstate
        if self._whole_run_trace:
            jax.profiler.stop_trace()
            self._whole_run_trace = False
        elapsed = time.perf_counter() - self.t_loop
        summary = dict(mstate["summary"])
        summary.setdefault("round", cfg.rounds)
        summary["rounds_per_sec"] = self.rounds_done / max(elapsed, 1e-9)
        if (mstate["t_steady"] is not None
                and mstate["t_steady_end"] is not None
                and mstate["r_steady_end"] > mstate["r_steady"]):
            summary["steady_rounds_per_sec"] = (
                (mstate["r_steady_end"] - mstate["r_steady"])
                / max(mstate["t_steady_end"] - mstate["t_steady"], 1e-9))
        summary["params"] = param_count(self.model_params)
        summary["device"] = self.device
        print("Training has finished!")
        print(f"[throughput] {summary['rounds_per_sec']:.3f} rounds/sec "
              f"({self.rounds_done} rounds in {elapsed:.1f}s)"
              + (f"; steady-state "
                 f"{summary['steady_rounds_per_sec']:.3f} r/s"
                 if "steady_rounds_per_sec" in summary else ""))
        # device-time attribution (obs/attribution.py): the sampled capture
        # window parses into Device/* rows + the summary; HBM watermarks
        # (the per-captured-unit maxima, plus a final poll) land as
        # Memory/* rows and heartbeat fields. All of it is absent when
        # --profile_rounds=0 and the backend exposes no memory_stats — the
        # off path emits nothing.
        mem = obs_attribution.memory_watermarks()
        # host RSS rides the same Memory/* rows: the population-axis CI
        # job pins it flat across the client-population ladder (ISSUE 7)
        mem.update(obs_attribution.host_watermarks())
        if self.prof is not None:
            for key, val in self.prof.mem.items():
                mem[key] = max(mem.get(key, 0), val)
            attr = self.prof.result()
            if attr is not None:
                for tag, v in obs_attribution.scalar_rows(attr):
                    writer.scalar(tag, v, self.rnd)
                summary["attribution"] = attr
                if attr.get("device_present"):
                    pr = attr.get("per_round", {})
                    print(f"[profile] device time/round: "
                          f"{pr.get('compute_ms', 0.0):.1f} ms compute + "
                          f"{pr.get('collective_ms', 0.0):.1f} ms "
                          f"collective + {pr.get('gap_ms', 0.0):.1f} ms "
                          f"gap ({100 * attr['collective_frac']:.1f}% "
                          f"collective)")
                else:
                    print(f"[profile] {attr.get('note', 'no device track')}")
        if mem:
            # memory_rows values are host ints from device.memory_stats()
            for tag, val in obs_attribution.memory_rows(mem):
                writer.scalar(tag, val, self.rnd)
            summary["memory"] = mem
            self.hb.update(**mem)
        # per-span aggregates -> metrics.jsonl (Spans/*) and the summary;
        # the full event stream -> trace.json in the run dir
        # (Perfetto-loadable)
        if self.tracer.enabled:
            for tag, v in self.tracer.scalar_rows():
                writer.scalar(tag, v, self.rnd)
            summary["spans"] = self.tracer.aggregates()
            run_dir = getattr(writer, "dir", None)
            if run_dir:
                trace_path = self.tracer.write_trace(
                    os.path.join(run_dir, "trace.json"))
                if trace_path:
                    summary["trace_path"] = trace_path
                    print(f"[spans] {trace_path} "
                          f"(load in https://ui.perfetto.dev)")
        if self.flight is not None:
            # the clean-exit snapshot: flight.json always reflects the
            # run's final window, incident or not
            self.flight.snapshot("clean_exit", self.rnd)
        writer.close()
        self.hb.close("done")
        return summary


def run(cfg: Config, writer: Optional[MetricsWriter] = None) -> Dict:
    """The one-shot trainer: build the engine, iterate its schedule, emit
    the summary — exactly the historical loop, now over RoundEngine
    steps."""
    eng = RoundEngine(cfg, writer=writer)
    try:
        for unit in eng.schedule():
            eng.dispatch(unit)
            if eng.rnd % cfg.snap == 0:
                eng.eval_boundary(eng.rnd)
                eng.save_checkpoint(eng.rnd)
            eng.post_unit()
        # surface any drain-thread error while the run's state is intact
        # (close() below closes without raising, to not mask a loop
        # exception with a secondary metrics error)
        if eng.drain is not None:
            eng.hb.update(phase="drain", force=True)
            with eng.tracer.span("drain/wait"):
                eng.drain.flush()
    finally:
        eng.close()
    return eng.finalize()


def main(argv=None):
    cfg = args_parser(argv)
    if cfg.platform:
        # must land before any backend use; with a platform named, JAX
        # raises when it is absent instead of settling for the CPU
        jax.config.update("jax_platforms", cfg.platform)
    if cfg.num_processes > 1 or cfg.coordinator:
        from defending_against_backdoors_with_robust_learning_rate_tpu.parallel import (
            multihost)
        multihost.maybe_initialize(cfg.coordinator, cfg.num_processes,
                                   cfg.process_id)
    run(cfg)
    # entry-point contract: setuptools console scripts wrap this in
    # sys.exit(main()), so returning the summary dict would exit status 1
    return 0


if __name__ == "__main__":
    main()
