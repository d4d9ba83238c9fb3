#!/usr/bin/env python
"""Headline benchmark: FL rounds/sec on the flagship config.

Config = BASELINE.json configs[1]: fmnist-shaped data, 10 agents, 1 corrupt,
poison_frac=0.5, robustLR_threshold=4, local_ep=2, bs=256 (the paper's
FMNIST attack+defense setting, src/runner.sh:18). Real FMNIST is used when
present under ./data; otherwise the deterministic synthetic fallback with the
same 60k x 28x28 geometry.

Prints ONE JSON line:
  {"metric": "fl_rounds_per_sec", "value": N, "unit": "rounds/sec",
   "vs_baseline": N, ...} (vs_baseline only for the default fmnist config —
the resnet9 config has no reference counterpart to compare against)

value is STEADY-STATE rounds/sec (post-compile); `compile_s` records the
first-block compile separately (VERDICT r1 #9). Compile persistence
(utils/compile_cache.py) splits that further: `cache_hit` says whether the
round-block executable was loaded from the serialized-executable bank,
`compile_s_cold` is the full trace+lower+XLA cost (from this run, or from
the banking run's manifest on a hit) and `compile_s_warm` the deserialize
cost of a warm start; `host_sync` records the per-eval-boundary blocking
host sync the driver's async metrics drain removes. vs_baseline is the speedup
over the reference-semantics torch loop measured on this host
(BASELINE_MEASURED.json, scripts/measure_reference_baseline.py): the
reference trains sampled agents sequentially (src/federated.py:68-72), so
its round time is agents * local_ep * batches * sec_per_batch_step.

No fallback: this is a device benchmark. Without `--platform cpu` (the
explicit small-shape debugging mode) it fails when JAX's backend is not a
TPU, and a `device_kind` missing from the peak table is an error. One
process, no child: a chip belongs to one process at a time.
"""

import argparse
import json
import os
import sys
import time


def log(msg):
    print(msg, file=sys.stderr, flush=True)


# peak dense-matmul throughput by device_kind substring (TFLOP/s, bf16);
# Google Cloud TPU documentation — used to turn measured FLOP/s into an MFU
# figure. f32 inputs on the MXU run through the same bf16 pipeline under
# JAX's default matmul precision, so bf16 peak is the honest denominator
# either way
PEAK_BF16_TFLOPS = (
    ("v6", 918.0),        # v6e (Trillium)
    ("v5p", 459.0),
    ("v5", 197.0),        # v5e / "TPU v5 lite"
    ("v4", 275.0),
    ("v3", 123.0),
    ("v2", 45.0),
)


def peak_tflops(device_kind: str) -> float:
    """bf16 peak of a TPU `device_kind`. A chip that is not in the table is
    an error, not a default: an MFU against a guessed peak is not a
    measurement."""
    kind = device_kind.lower()
    if "tpu" in kind:
        for key, val in PEAK_BF16_TFLOPS:
            if key in kind:
                return val
    raise ValueError(
        f"device_kind {device_kind!r} is not in bench.PEAK_BF16_TFLOPS; "
        f"add the chip's published peak (with its source) before "
        f"benchmarking on it")


def bench_config(name: str, remat_policy: str = "block",
                 agent_chunk: int = -1, **extra):
    """The two benchmark configs, importable (scripts/precompile.py banks
    their program families offline from the very same construction).

    fmnist = the flagship paper config (BASELINE.json configs[1]);
    resnet9 = the north-star cifar10 ResNet-9 DBA+RLR config
    (BASELINE.json configs[3]: 40 agents, 4 corrupt, thr=8, remat +
    agent_chunk=10)."""
    from defending_against_backdoors_with_robust_learning_rate_tpu.config import (
        Config)
    if name == "resnet9":
        return Config(data="cifar10", num_agents=40, local_ep=2, bs=256,
                      num_corrupt=4, poison_frac=0.5, pattern_type="plus",
                      robustLR_threshold=8, arch="resnet9",
                      remat=True, remat_policy=remat_policy,
                      agent_chunk=(10 if agent_chunk < 0 else agent_chunk),
                      synth_train_size=50000,
                      synth_val_size=10000, seed=0, **extra)
    return Config(data="fmnist", num_agents=10, local_ep=2, bs=256,
                  num_corrupt=1, poison_frac=0.5, robustLR_threshold=4,
                  synth_train_size=60000,
                  synth_val_size=10000, seed=0, **extra)


def train_step_flops(model, params, norm, cfg, image_shape):
    """XLA's own FLOP count for ONE client fwd+bwd minibatch step (the
    compiler's cost analysis of the compiled program — no hand model).
    Multiplied out by the driver: agents x epochs x batches per round.

    Callers pass a NON-remat model instance: MFU is model-FLOPs utilization,
    so rematerialization's recompute work must not inflate the numerator
    (the timed program may still remat — that cost shows up in the wall
    clock, where it belongs)."""
    import jax
    import jax.numpy as jnp

    from defending_against_backdoors_with_robust_learning_rate_tpu.fl.common import (
        masked_ce)

    x = jnp.zeros((cfg.bs,) + tuple(image_shape), jnp.float32)
    y = jnp.zeros((cfg.bs,), jnp.int32)
    w = jnp.ones((cfg.bs,), bool)

    def loss_fn(p):
        logits = model.apply({"params": p}, norm(x), train=True,
                             rngs={"dropout": jax.random.PRNGKey(0)})
        return masked_ce(logits, y, w)

    compiled = jax.jit(jax.value_and_grad(loss_fn)).lower(params).compile()
    return float(compiled.cost_analysis().get("flops", 0.0))


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--platform", default="",
                    help="'cpu' = debug on XLA:CPU at the shapes you pass "
                         "(the JSON names the device; no device metric "
                         "may be quoted from it). Left empty the backend "
                         "must be a TPU or the benchmark fails")
    ap.add_argument("--bench_config", choices=("fmnist", "resnet9"),
                    default="fmnist",
                    help="fmnist = flagship paper config (BASELINE.json "
                         "configs[1], the default the driver records); "
                         "resnet9 = the north-star cifar10 ResNet-9 DBA+RLR "
                         "config (BASELINE.json configs[3]: 40 agents, 4 "
                         "corrupt, thr=8, remat + agent_chunk=10)")
    ap.add_argument("--chain", type=int, default=10,
                    help="rounds fused per lax.scan block")
    ap.add_argument("--blocks", type=int, default=3,
                    help="timed steady-state blocks")
    ap.add_argument("--dtype", default="",
                    help="override compute dtype (f32|bf16)")
    ap.add_argument("--rng_impl", choices=("auto", "threefry", "rbg"),
                    default="auto",
                    help="PRNG bit generator (auto = hardware rbg on TPU)")
    ap.add_argument("--faults", action="store_true",
                    help="also measure rounds/sec at 30%% client dropout "
                         "(faults/ masking path) and report the masking "
                         "overhead vs the dense 0%% run")
    ap.add_argument("--health", choices=("on", "off", "both"),
                    default="on",
                    help="in-program health sentinel lane "
                         "(health/sentinel.py, default on — the shipped "
                         "config). 'off' re-points the headline at the "
                         "lane-free program; 'both' keeps the on "
                         "headline and ALSO measures the off twin "
                         "(health_ab in the output JSON — the ISSUE-14 "
                         "<=1%% overhead acceptance A/B)")
    ap.add_argument("--reputation", choices=("auto", "on", "off", "both"),
                    default="auto",
                    help="in-program reputation lanes (obs/reputation.py: "
                         "per-sampled-client rep_agree + rep_norm rows, "
                         "default auto = on whenever a sign vote "
                         "exists). 'off' re-points the headline at the "
                         "lane-free program; 'both' keeps the auto headline and "
                         "ALSO measures the off twin (reputation_ab in "
                         "the output JSON — the ISSUE-20 <1%% overhead "
                         "acceptance A/B)")
    ap.add_argument("--telemetry", choices=("off", "basic", "full"),
                    default="off",
                    help="also measure rounds/sec with in-jit defense "
                         "telemetry (obs/telemetry.py) at this level and "
                         "report the overhead vs the off run (the "
                         "headline value stays the off number)")
    ap.add_argument("--events", choices=("off", "both"), default="off",
                    help="'both' re-measures the headline blocks with a "
                         "live event ledger + Prometheus textfile "
                         "exporter updated at block cadence (the service "
                         "plane's boundary cadence upper bound) and "
                         "reports the overhead (events_ab in the output "
                         "JSON — the ISSUE-15 <1%% acceptance A/B)")
    ap.add_argument("--population_ladder", default="",
                    help="comma-separated client populations (e.g. "
                         "10000,100000,1000000): measure cohort-sampled "
                         "(data/bank.py + data/cohort.py) rounds/sec at "
                         "each rung with the flagship's cohort size, "
                         "recording host-RSS/HBM watermarks per rung — the "
                         "constant-memory evidence (ISSUE 7). Also runs "
                         "the equal-cohort dense-vs-cohort A/B on the "
                         "flagship config (label_shards bank: identical "
                         "shards, the delta is pure cohort machinery)")
    ap.add_argument("--ladder_partitioner",
                    choices=("dirichlet", "pathological"),
                    default="dirichlet",
                    help="client-bank partitioner for the ladder rungs "
                         "(label_shards cannot reach these populations)")
    ap.add_argument("--ladder_spc", type=int, default=0,
                    help="samples per client on the ladder rungs (0 = "
                         "auto clamp; the SAME value lands on every rung, "
                         "so rung rounds/sec are compute-comparable)")
    ap.add_argument("--agg_mode", choices=("sync", "buffered", "both"),
                    default="sync",
                    help="aggregation mode (ISSUE 12, fl/buffered.py): "
                         "buffered runs the headline through the "
                         "buffered-async tick program; both ALSO "
                         "measures an A/B — buffered at K=m (the pure "
                         "mode overhead, acceptance <=3%%) plus sync "
                         "rounds/sec vs buffered ticks/sec at 30%%/50%% "
                         "straggler rates (agg_mode_ab in the output "
                         "JSON; BENCH_NOTES r13)")
    ap.add_argument("--tenants", type=int, default=0,
                    help=">=2: tenancy A/B (ISSUE 13, tenancy_ab in the "
                         "output JSON): an equal 16-cell shape-compatible "
                         "cell list through the serial experiment queue "
                         "vs the tenant-packed queue at this pack width — "
                         "cells/hour per arm + the packed/serial speedup "
                         "(service/tenancy.py)")
    ap.add_argument("--status_file", default="logs/status.json",
                    help="heartbeat path (obs/heartbeat.py) the session "
                         "stall detector reads; empty disables")
    ap.add_argument("--profile_rounds", type=int, default=0,
                    help=">0: after the timed steady blocks, capture a "
                         "jax.profiler window of (at least) this many "
                         "extra rounds and attribute device time "
                         "(obs/attribution.py: compute/collective/gap + "
                         "named-scope split as `attribution` in the "
                         "output JSON; the timed figure is unaffected)")
    ap.add_argument("--profile_trace_dir", default="logs/bench_profile",
                    help="where the --profile_rounds capture lands "
                         "(re-parse offline via scripts/trace_top_ops.py "
                         "--parse or python -m ...obs.report)")
    ap.add_argument("--remat_policy", choices=("block", "conv", "none"),
                    default="block",
                    help="resnet9 config only: block = full blockwise "
                         "remat (r4 baseline, +33%% fwd recompute), conv = "
                         "selective save-conv-outputs remat, none = "
                         "nothing recomputed (the program --remat left "
                         "out builds)")
    ap.add_argument("--agent_chunk", type=int, default=-1,
                    help="resnet9 config only: override the agent chunk "
                         "size (-1 keeps the config default of 10; 0 = "
                         "full 40-agent vmap)")
    ap.add_argument("--synth_train_size", type=int, default=0,
                    help="override the synthetic dataset size (forces the "
                         "synthetic generator; for CI verification of the "
                         "warm-start path on small shapes; 0 = config "
                         "default). The emitted value is NOT comparable "
                         "to full-shape rows (synth_override in the JSON)")
    ap.add_argument("--no_compile_cache", action="store_true",
                    help="disable the persistent XLA cache and the "
                         "serialized-executable AOT bank "
                         "(utils/compile_cache.py); every run compiles cold")
    ap.add_argument("--compile_cache_dir", default="",
                    help="compile-cache root (default: .compile_cache/ "
                         "in the checkout; $JAX_COMPILATION_CACHE_DIR, "
                         "where set, takes precedence)")
    args = ap.parse_args()

    # advisor r5 (bench.py:160): these knobs only exist on the resnet9
    # config — flag the silent no-op instead of swallowing it, and record
    # it in the output JSON so a sweep row can't be misread as an A/B
    ignored_flags = []
    if args.bench_config != "resnet9":
        if args.remat_policy != "block":
            ignored_flags.append("--remat_policy")
        if args.agent_chunk != -1:
            ignored_flags.append("--agent_chunk")
    if ignored_flags:
        log(f"[bench] WARNING: {', '.join(ignored_flags)} only apply to "
            f"--bench_config resnet9 and are IGNORED for "
            f"{args.bench_config!r} (recorded as ignored_flags in the "
            f"output JSON)")

    # observability (obs/): span-trace the bench phases and heartbeat
    # through them (status.json; compile_in_flight marks the legitimately
    # silent compile window)
    from defending_against_backdoors_with_robust_learning_rate_tpu.obs import (
        Heartbeat, SpanTracer)
    hb = Heartbeat(args.status_file, enabled=bool(args.status_file))
    tracer = SpanTracer(on_end=hb.span_hook)
    hb.update(phase="setup", force=True)

    import jax

    if args.platform:
        jax.config.update("jax_platforms", args.platform)

    from defending_against_backdoors_with_robust_learning_rate_tpu.train import (
        apply_rng_impl, device_record)

    device = device_record()   # every result names the device it ran on
    log(f"[bench] device: platform={device['platform']} "
        f"kind={device['kind']} n={device['count']}")
    if args.platform != "cpu":
        if device["platform"] != "tpu":
            raise SystemExit(
                f"[bench] backend is {device['platform']!r}, not a TPU: a "
                f"device benchmark does not fall back (pass --platform cpu "
                f"to debug at small shapes)")
        peak = peak_tflops(device["kind"])
    else:
        peak = None   # XLA:CPU has no MFU

    import jax.numpy as jnp

    rng_impl = apply_rng_impl(args.rng_impl)
    log(f"[bench] prng impl: {rng_impl}")

    from defending_against_backdoors_with_robust_learning_rate_tpu.data.registry import (
        get_federated_data)
    from defending_against_backdoors_with_robust_learning_rate_tpu.fl.common import (
        make_normalizer)
    from defending_against_backdoors_with_robust_learning_rate_tpu.fl.rounds import (
        make_chained_round_fn)
    from defending_against_backdoors_with_robust_learning_rate_tpu.models.registry import (
        get_model, init_params)

    extra = {"compile_cache": not args.no_compile_cache,
             "compile_cache_dir": args.compile_cache_dir}
    if args.dtype:
        extra["dtype"] = args.dtype
    if args.health == "off":
        # 'off' re-points the headline; 'both' keeps the (default-on)
        # headline and adds the health_ab block below
        extra["health"] = "off"
    if args.reputation in ("on", "off"):
        # a single setting re-points the HEADLINE; 'both' keeps the
        # auto headline and adds the reputation_ab block below
        extra["reputation"] = args.reputation
    # BASELINE.json configs[1] (fmnist flagship) or configs[3] (resnet9,
    # the MXU-bound north-star shape — VERDICT r3 next #1); shared with
    # scripts/precompile.py via bench_config so the banked program
    # families match what this benchmark dispatches
    cfg = bench_config(args.bench_config,
                       remat_policy=args.remat_policy,
                       agent_chunk=args.agent_chunk, **extra)
    if args.synth_train_size:
        cfg = cfg.replace(synth_train_size=args.synth_train_size,
                          synth_val_size=max(512,
                                             args.synth_train_size // 10),
                          data_dir="/nonexistent_use_synthetic_reduced")
    if args.agg_mode == "buffered":
        # headline through the buffered tick program (K=m by default —
        # the staleness-0 cadence that matches sync round-for-round)
        cfg = cfg.replace(agg_mode="buffered")
    from defending_against_backdoors_with_robust_learning_rate_tpu.utils import (
        compile_cache)

    # persistent XLA cache + AOT executable bank: a warm second run loads
    # the serialized round-block executable and skips XLA entirely
    bank = compile_cache.setup(cfg)
    if bank is not None:
        log(f"[bench] compile cache at {compile_cache.cache_root(cfg)}")

    hb.update(phase="data", force=True)
    with tracer.span("bench/data"):
        fed = get_federated_data(cfg)
    # as the engine does: every measure(cfg.replace(...)) below keys its
    # programs on the policy the model was built with
    cfg = cfg.replace(
        remat_policy=compile_cache.resolved_remat(cfg, fed).policy)
    model = get_model(cfg.data, cfg.model_arch, cfg.dtype, remat=cfg.remat,
                      remat_policy=cfg.remat_policy)
    norm = make_normalizer(fed.mean, fed.std, fed.raw_is_normalized)
    arrays = (jnp.asarray(fed.train.images), jnp.asarray(fed.train.labels),
              jnp.asarray(fed.train.sizes))
    chain = args.chain

    def measure(mcfg, label="", profile_dir=None, per_block=None):
        """Compile (or load the banked executable) + steady-state
        rounds/sec of mcfg's chained round fn. Returns (params,
        rounds_per_sec, compile_s, cache_info) where compile_s keeps its
        historical meaning (executable acquisition + first block) and
        cache_info carries the cold/warm split.

        Fresh params per call: the chained fn donates its params argument,
        so a prior measurement's buffer cannot be reused."""
        params = init_params(model, fed.train.images.shape[2:],
                             jax.random.PRNGKey(0))
        from defending_against_backdoors_with_robust_learning_rate_tpu.fl import (
            buffered as buffered_mod)
        if buffered_mod.is_buffered(mcfg):
            # buffered mode: the chained scan carries the (params,
            # buffer-state) pair; the AOT example aval below follows
            # automatically (params IS the carry)
            params = (params, buffered_mod.init_state(mcfg, params,
                                                      per_bin=True))
        # chained execution: blocks of rounds fused into one lax.scan
        # dispatch (bit-identical to per-round dispatch; see fl/rounds.py)
        chained = make_chained_round_fn(mcfg, model, norm, *arrays)
        base_key = jax.random.PRNGKey(0)
        call, cache_info = chained, None
        acquire_s = 0.0
        hb.update(phase="compile", compile_in_flight=True, force=True)
        if bank is not None:
            try:
                ab = compile_cache.abstractify
                example = (ab(params), ab(base_key),
                           jax.ShapeDtypeStruct((chain,), jnp.int32)
                           ) + ab(arrays)
                with tracer.span("bench/aot_acquire", label=label):
                    compiled, hit, acquire_s, entry = bank.get_or_compile(
                        chained.family, mcfg, chained.jitted, example)
                data = chained.data
                call = lambda p, k, ids: compiled(p, k, ids, *data)  # noqa: E731
                # cold time comes from THIS run on a miss, and from the
                # banking run's manifest record on a hit — so a warm run
                # can still report the cold/warm ratio it is beating
                cache_info = {
                    "cache_hit": hit,
                    "compile_s_cold": round(float(
                        entry.get("compile_s", acquire_s)), 2),
                    "compile_s_warm": (round(acquire_s, 2) if hit else None),
                }
                log(f"[bench]{label} aot "
                    + ("hit: executable loaded" if hit
                       else "miss: compiled+banked")
                    + f" in {acquire_s:.1f}s")
            except Exception as e:  # bank is an optimization, never fatal
                log(f"[bench]{label} aot unavailable "
                    f"({type(e).__name__}: {e}); jit path")
        # warmup / first block (post-AOT this is pure execution; on the
        # jit path it still includes the trace+compile)
        t0 = time.perf_counter()
        with tracer.span("bench/first_block", label=label):
            params, _ = call(params, base_key, jnp.arange(1, chain + 1))
            jax.block_until_ready(params)
        compile_s = time.perf_counter() - t0 + acquire_s
        log(f"[bench]{label} compile+first {chain}-round block: "
            f"{compile_s:.1f}s")
        hb.update(phase="measure", compile_in_flight=False, force=True)

        n_rounds = args.blocks * chain
        t0 = time.perf_counter()
        with tracer.span("bench/steady_blocks", label=label,
                         blocks=args.blocks):
            for b in range(args.blocks):
                ids = jnp.arange((b + 1) * chain + 1, (b + 2) * chain + 1)
                params, _ = call(params, base_key, ids)
                if per_block is not None:
                    # the events A/B hook: ledger emit + exporter flush
                    # at block cadence, INSIDE the timed window
                    per_block(b, (b + 1) * chain)
            jax.block_until_ready(params)
        elapsed = time.perf_counter() - t0
        rounds_per_sec = n_rounds / elapsed
        log(f"[bench]{label} {n_rounds} rounds in {elapsed:.2f}s "
            f"-> {rounds_per_sec:.3f} rounds/sec steady-state")

        if profile_dir and args.profile_rounds > 0:
            # device-time attribution window (obs/attribution.py): EXTRA
            # steady blocks under the profiler, after the timed ones, so
            # capture overhead never touches the headline figure
            from defending_against_backdoors_with_robust_learning_rate_tpu.obs import (
                attribution)
            p_blocks = -(-args.profile_rounds // chain)
            if jax.default_backend() != "tpu":
                # XLA:CPU's profiler records every op thunk of the
                # conv-in-loop path: full-shape CPU rounds serialize
                # multi-minute, multi-GB traces at stop_trace. Useful
                # only on reduced shapes (the CI smoke) — say so.
                log("[bench] WARNING: profiling a non-TPU backend — "
                    "stop_trace serialization can take minutes on "
                    "full-shape CPU rounds (fine on reduced shapes)")
            hb.update(phase="profile", force=True)
            with tracer.span("bench/profile_blocks", blocks=p_blocks):
                jax.profiler.start_trace(profile_dir)
                for b in range(args.blocks, args.blocks + p_blocks):
                    ids = jnp.arange((b + 1) * chain + 1,
                                     (b + 2) * chain + 1)
                    params, _ = call(params, base_key, ids)
                jax.block_until_ready(params)
                jax.profiler.stop_trace()
            attribution.write_capture_meta(profile_dir, {
                "rounds": p_blocks * chain,
                "backend": jax.default_backend(),
                "source": "bench --profile_rounds"})
            log(f"[bench]{label} profiled {p_blocks * chain} extra rounds "
                f"-> {profile_dir}")
        if buffered_mod.is_buffered(mcfg):
            # downstream consumers (eval, FLOP cost analysis) want the
            # bare model params, not the (params, buffer-state) carry
            params = params[0]
        return params, rounds_per_sec, compile_s, cache_info

    params, rounds_per_sec, compile_s, cache_info = measure(
        cfg, profile_dir=(args.profile_trace_dir
                          if args.profile_rounds > 0 else None))

    # device-time attribution of the profiled window + HBM watermarks
    # (obs/attribution.py) — the fields the run report and BENCH_NOTES r7
    # judge; hbm is polled regardless of profiling (None-stats backends
    # simply omit it)
    from defending_against_backdoors_with_robust_learning_rate_tpu.obs import (
        attribution as obs_attribution)
    attribution_out = None
    if args.profile_rounds > 0:
        attribution_out = obs_attribution.attribute(args.profile_trace_dir)
        if attribution_out is not None and \
                attribution_out.get("device_present"):
            log(f"[bench] attribution: "
                f"{attribution_out['compute_ms']:.1f} ms compute | "
                f"{attribution_out['collective_ms']:.1f} ms collective "
                f"({100 * attribution_out['collective_frac']:.1f}%) | "
                f"{attribution_out['gap_ms']:.1f} ms gap")
        elif attribution_out is not None:
            log(f"[bench] attribution: "
                f"{attribution_out.get('note', 'no device track')}")
    hbm = obs_attribution.memory_watermarks()

    faults_out = None
    if args.faults:
        # masking-overhead probe (faults/): the same config with 30% client
        # dropout exercises the participation-mask aggregation path; the
        # delta vs the dense 0% run is the cost of mask-aware aggregation
        # (dropped agents still train — shapes are static — so compute
        # doesn't shrink with the electorate)
        r0 = rounds_per_sec
        _, r30, c30, _ = measure(cfg.replace(dropout_rate=0.3),
                                 label="[faults dropout=0.3]")
        faults_out = {
            "dropout0_rounds_per_sec": round(r0, 4),
            "dropout30_rounds_per_sec": round(r30, 4),
            "masking_overhead_pct": round(100.0 * (1.0 - r30 / r0), 2),
            "dropout30_compile_s": round(c30, 1),
        }
        log(f"[bench] masking overhead at 30% dropout: "
            f"{faults_out['masking_overhead_pct']}%")

    telemetry_out = None
    if args.telemetry != "off":
        # telemetry-overhead probe (obs/telemetry.py): same config with
        # in-jit defense telemetry compiled into the round program; the
        # delta vs the off run is the cost of the extra on-device stats
        # (the headline `value` stays the off number)
        r_base = rounds_per_sec
        _, r_tel, c_tel, _ = measure(
            cfg.replace(telemetry=args.telemetry),
            label=f"[telemetry {args.telemetry}]")
        telemetry_out = {
            "level": args.telemetry,
            "off_rounds_per_sec": round(r_base, 4),
            "on_rounds_per_sec": round(r_tel, 4),
            "overhead_pct": round(100.0 * (1.0 - r_tel / r_base), 2),
            "compile_s": round(c_tel, 1),
        }
        log(f"[bench] telemetry={args.telemetry} overhead: "
            f"{telemetry_out['overhead_pct']}%")

    health_ab_out = None
    if args.health == "both":
        # health-lane overhead A/B (ISSUE 14): same config with the
        # in-jit sentinel compiled OUT of the round program; the on
        # headline vs the off twin is the cost of the lane's reductions
        # (acceptance: <=1% on steady rounds/sec — the sharded scalars
        # pack into the loss psum, so there is no collective delta to
        # pay, only the reduction arithmetic)
        hb.update(phase="health_ab", force=True)
        _, r_hoff, c_hoff, _ = measure(cfg.replace(health="off"),
                                       label="[health off]")
        health_ab_out = {
            "on_rounds_per_sec": round(rounds_per_sec, 4),
            "off_rounds_per_sec": round(r_hoff, 4),
            "overhead_pct": round(
                100.0 * (1.0 - rounds_per_sec / r_hoff), 2),
            "compile_s_off": round(c_hoff, 1),
        }
        log(f"[bench] health-lane overhead: "
            f"{health_ab_out['overhead_pct']}% "
            f"(on {rounds_per_sec:.3f} vs off {r_hoff:.3f} r/s)")

    reputation_ab_out = None
    if args.reputation == "both":
        # reputation-lane overhead A/B (ISSUE 20): same config with the
        # rep_agree + rep_norm client rows compiled OUT of the round
        # program; the on headline vs the off twin is the cost of the
        # two lanes (acceptance: <1% on steady rounds/sec — both rows
        # are device-local reductions riding the existing sign-sum tree
        # and update buffers, so there is no collective delta to pay)
        hb.update(phase="reputation_ab", force=True)
        _, r_roff, c_roff, _ = measure(cfg.replace(reputation="off"),
                                       label="[reputation off]")
        reputation_ab_out = {
            "on_rounds_per_sec": round(rounds_per_sec, 4),
            "off_rounds_per_sec": round(r_roff, 4),
            "overhead_pct": round(
                100.0 * (1.0 - rounds_per_sec / r_roff), 2),
            "compile_s_off": round(c_roff, 1),
        }
        log(f"[bench] reputation-lane overhead: "
            f"{reputation_ab_out['overhead_pct']}% "
            f"(on {rounds_per_sec:.3f} vs off {r_roff:.3f} r/s)")

    events_ab_out = None
    if args.events == "both":
        # ledger+exporter overhead A/B (ISSUE 15): the headline blocks
        # re-measured with a live event ledger and Prometheus textfile
        # exporter serviced once per block — the boundary-cadence cost a
        # service run would pay. Pure host-side IO: the traced program is
        # untouched, so the acceptance (<1% steady rounds/sec) is about
        # write+flush latency hiding under the dispatched block.
        from defending_against_backdoors_with_robust_learning_rate_tpu.obs import (
            events as obs_events, export as obs_export)
        hb.update(phase="events_ab", force=True)
        ev_path = "logs/bench_events.jsonl"
        if os.path.exists(ev_path):
            os.remove(ev_path)
        ledger = obs_events.EventLedger(ev_path, run="bench",
                                        corr=obs_events.corr_id("bench"))
        exporter = obs_export.MetricsExporter(
            textfile="logs/bench_metrics.prom", info={"run": "bench"})

        def _per_block(b, rounds_done):
            ledger.emit("bench/block", round=rounds_done, block=b)
            exporter.observe_rounds(rounds_done)
            exporter.set("round", rounds_done)
            exporter.flush()

        _, r_ev, _, _ = measure(cfg, label="[events on]",
                                per_block=_per_block)
        ledger.close()
        exporter.close()
        events_ab_out = {
            "off_rounds_per_sec": round(rounds_per_sec, 4),
            "on_rounds_per_sec": round(r_ev, 4),
            "overhead_pct": round(
                100.0 * (1.0 - r_ev / rounds_per_sec), 2),
        }
        log(f"[bench] ledger+exporter overhead: "
            f"{events_ab_out['overhead_pct']}% "
            f"(off {rounds_per_sec:.3f} vs on {r_ev:.3f} r/s)")

    population_out = None
    if args.population_ladder:
        # population-axis measurement (ISSUE 7): the cohort-sampled path
        # decouples population size from per-round cohort size. Two
        # claims go on the record here: (1) equal-cohort overhead — the
        # flagship config re-run through the cohort program over a
        # label_shards bank (bitwise-identical shards, same [m, ...]
        # shapes; the delta vs the dense headline is pure cohort
        # machinery: in-program sampling + per-round gather/H2D, within
        # 10% by acceptance); (2) the ladder — rounds/sec at each
        # population rung with the SAME cohort size and samples/client
        # (compute-comparable), with host peak RSS + HBM watermarks per
        # rung. ru_maxrss is monotone, so an ascending ladder whose
        # watermark stays flat IS the constant-memory proof.
        import numpy as np

        from defending_against_backdoors_with_robust_learning_rate_tpu.data import (
            cohort as cohort_mod)
        from defending_against_backdoors_with_robust_learning_rate_tpu.data.prefetch import (
            RoundPrefetcher)
        from defending_against_backdoors_with_robust_learning_rate_tpu.data.registry import (
            get_cohort_data)
        from defending_against_backdoors_with_robust_learning_rate_tpu.fl.rounds import (
            make_chained_cohort_round_fn, make_cohort_round_fn)

        def measure_cohort(mcfg, label):
            """Steady rounds/sec of mcfg's cohort-sampled program: the
            driver's own prefetch pipeline (data/prefetch.py, depth 1)
            overlaps the bank gather + H2D with the running block, so
            the figure reflects the real round pipeline, not a
            serialized gather."""
            hb.update(phase=f"population{label}", force=True)
            t0 = time.perf_counter()
            with tracer.span("bench/bank", label=label):
                src = get_cohort_data(mcfg)
            bank_s = time.perf_counter() - t0
            bank_bytes = sum(
                os.path.getsize(os.path.join(src.bank.dir, f))
                for f in os.listdir(src.bank.dir))
            params = init_params(model, fed.train.images.shape[2:],
                                 jax.random.PRNGKey(0))
            base_key = jax.random.PRNGKey(0)
            fn = (make_chained_cohort_round_fn(mcfg, model, norm)
                  if chain > 1 else make_cohort_round_fn(mcfg, model, norm))

            def gather_unit(unit):
                ids = [cohort_mod.sample_cohort_host(mcfg, r)[0]
                       for r in unit]
                rows = [src.gather_cohort(i) for i in ids]
                if len(unit) == 1:
                    return tuple(map(jnp.asarray, rows[0]))
                return tuple(jnp.asarray(np.stack([r[k] for r in rows]))
                             for k in range(3))

            n_blocks = args.blocks + 1   # block 0 = compile + warmup
            sched = [tuple(range(b * chain + 1, (b + 1) * chain + 1))
                     for b in range(n_blocks)]
            pre = RoundPrefetcher(gather_unit, sched, depth=1)
            try:
                def run_block(params, b):
                    payload = pre.get(sched[b])
                    if chain > 1:
                        ids = jnp.asarray(sched[b], jnp.int32)
                        return fn(params, base_key, ids, *payload)[0]
                    return fn(params, base_key, jnp.int32(sched[b][0]),
                              *payload)[0]

                hb.update(phase="compile", compile_in_flight=True,
                          force=True)
                t0 = time.perf_counter()
                with tracer.span("bench/cohort_first", label=label):
                    params = run_block(params, 0)
                    jax.block_until_ready(params)
                compile_s = time.perf_counter() - t0
                hb.update(phase="measure", compile_in_flight=False,
                          force=True)
                t0 = time.perf_counter()
                with tracer.span("bench/cohort_steady", label=label,
                                 blocks=args.blocks):
                    for b in range(1, n_blocks):
                        params = run_block(params, b)
                    jax.block_until_ready(params)
                elapsed = time.perf_counter() - t0
            finally:
                pre.close()
            r = args.blocks * chain / elapsed
            log(f"[bench]{label} {args.blocks * chain} rounds in "
                f"{elapsed:.2f}s -> {r:.3f} rounds/sec steady-state "
                f"(bank {bank_bytes / 2**20:.1f} MiB in {bank_s:.1f}s, "
                f"compile+first {compile_s:.1f}s)")
            return r, compile_s, bank_s, bank_bytes

        # (1) equal-cohort A/B on the flagship: same population, same
        # shards (label_shards), same shapes — cohort machinery only.
        r_dense = rounds_per_sec
        ab_cfg = cfg.replace(cohort_sampled="on",
                             cohort_size=cfg.agents_per_round,
                             partitioner="label_shards")
        r_ab, c_ab, _, _ = measure_cohort(
            ab_cfg, f"[cohort K={cfg.num_agents}]")
        population_out = {
            "cohort_size": cfg.agents_per_round,
            "dense_rounds_per_sec": round(r_dense, 4),
            "equal_cohort_rounds_per_sec": round(r_ab, 4),
            "cohort_overhead_pct": round(
                100.0 * (1.0 - r_ab / r_dense), 2),
            "equal_cohort_compile_s": round(c_ab, 1),
            "ladder": [],
        }
        log(f"[bench] equal-cohort overhead vs dense: "
            f"{population_out['cohort_overhead_pct']}%")

        # (2) the population ladder, ascending so the monotone RSS
        # watermark judges flatness. samples_per_client is resolved ONCE
        # (auto would resolve per rung — clip(n/K) shrinks with K — and
        # different max_n per rung would break the rungs'
        # compute-comparability the r9 template relies on); the largest
        # rung's auto value lands on every rung.
        from defending_against_backdoors_with_robust_learning_rate_tpu.data import (
            bank as bank_mod)
        from defending_against_backdoors_with_robust_learning_rate_tpu.data.registry import (
            get_datasets)
        def current_rss_bytes():
            # ru_maxrss is the PROCESS-lifetime peak — the dense headline
            # measured above may dominate it, making a flat peak ladder
            # vacuous. The instantaneous VmRSS per rung is the signal
            # that would actually expose O(population) growth in-process
            # (the CI population-smoke job measures each rung in its own
            # process for the rigorous watermark).
            try:
                with open("/proc/self/status") as f:
                    for line in f:
                        if line.startswith("VmRSS:"):
                            return int(line.split()[1]) * 1024
            except OSError:
                pass
            return None

        rungs = sorted(int(x) for x in
                       args.population_ladder.split(",") if x.strip())
        base_train, _, _ = get_datasets(cfg)
        if isinstance(base_train, list):
            raise ValueError(
                "the population ladder needs a single base dataset to "
                "index (pre-split per-user data cannot be re-partitioned)")
        ladder_spc = bank_mod.resolve_samples_per_client(
            args.ladder_spc, len(base_train.labels), max(rungs))
        population_out["ladder_samples_per_client"] = ladder_spc
        log(f"[bench] ladder samples/client: {ladder_spc} (same on "
            f"every rung)")
        for pop in rungs:
            rung_cfg = cfg.replace(
                num_agents=pop, cohort_sampled="on",
                cohort_size=cfg.agents_per_round,
                partitioner=args.ladder_partitioner,
                samples_per_client=ladder_spc)
            r, c_s, bank_s, bank_bytes = measure_cohort(
                rung_cfg, f"[population {pop}]")
            rss = obs_attribution.host_watermarks()
            cur = current_rss_bytes()
            if cur is not None:
                rss["host_rss_bytes"] = cur
            rung_hbm = obs_attribution.memory_watermarks()
            row = {"population": pop,
                   "rounds_per_sec": round(r, 4),
                   "compile_s": round(c_s, 1),
                   "bank_build_s": round(bank_s, 1),
                   "bank_bytes": bank_bytes,
                   **rss, **rung_hbm}
            population_out["ladder"].append(row)
            log(f"[bench] rung {pop:,}: {r:.3f} rounds/sec, host RSS "
                f"{(cur or 0) / 2**30:.2f} GiB now / "
                f"{rss.get('host_peak_rss_bytes', 0) / 2**30:.2f} GiB "
                f"peak")

    # analytic performance anatomy (ISSUE 10): FLOPs/round from the model
    # registry's arithmetic — no compile, works on every backend, so the
    # MFU trajectory is tracked on CPU before a TPU session ever runs.
    # One fwd+bwd step ~ 3x the forward (registry docstring convention).
    from defending_against_backdoors_with_robust_learning_rate_tpu.models.registry import (
        flops_per_example)
    analytic_round = None
    fwd_flops = flops_per_example(cfg.data, cfg.model_arch,
                                  fed.train.images.shape[2:], cfg.n_classes)
    if fwd_flops:
        nb_an = fed.train.images.shape[1] // cfg.bs
        analytic_round = (cfg.agents_per_round * cfg.local_ep * nb_an
                          * cfg.bs * 3.0 * fwd_flops)
        log(f"[bench] analytic {analytic_round/1e12:.2f} TFLOP/round "
            f"({cfg.agents_per_round}x{cfg.local_ep}x{nb_an}x{cfg.bs} "
            f"examples, 3x fwd)")

    # performance anatomy (VERDICT r2 weak #1): FLOPs/round from XLA's own
    # cost analysis of the compiled client step, and MFU against the chip's
    # bf16 peak — "actually fast, or just correct?" on the record
    flops_round = mfu = tflops_sec = None
    try:
        # non-remat twin for the FLOP count (see train_step_flops docstring)
        flops_model = (get_model(cfg.data, cfg.model_arch, cfg.dtype,
                                 remat=False) if cfg.remat else model)
        step_flops = train_step_flops(flops_model, params, norm, cfg,
                                      fed.train.images.shape[2:])
        if step_flops > 0:
            nb = fed.train.images.shape[1] // cfg.bs
            flops_round = (cfg.agents_per_round * cfg.local_ep * nb
                           * step_flops)
            tflops_sec = flops_round * rounds_per_sec / 1e12
            # `peak` computed once beside the analytic block above
            log(f"[bench] {flops_round/1e12:.2f} TFLOP/round (XLA cost "
                f"analysis, {cfg.agents_per_round}x{cfg.local_ep}x{nb} "
                f"steps) -> {tflops_sec:.1f} TFLOP/s")
            if peak:
                mfu = tflops_sec / peak
                log(f"[bench] MFU {100*mfu:.1f}% of {peak:.0f} TFLOP/s "
                    f"bf16 peak ({device['kind']})")
    except Exception as e:  # cost analysis is informative, never fatal
        log(f"[bench] cost analysis unavailable: {e}")

    # host-sync anatomy: the blocking time per eval boundary that train.py's
    # async metrics drain removes from the round loop's critical path
    # (eval_sync_s - eval_dispatch_s = host wait the driver no longer pays)
    host_sync = None
    hb.update(phase="eval_probe", force=True)
    try:
        from defending_against_backdoors_with_robust_learning_rate_tpu.fl.evaluate import (
            make_eval_fn, pad_eval_set)
        eval_fn = make_eval_fn(model, norm, cfg.n_classes)
        val = tuple(map(jnp.asarray, pad_eval_set(
            fed.val_images, fed.val_labels, cfg.eval_bs)))
        jax.block_until_ready(eval_fn(params, *val))  # compile outside timing
        t0 = time.perf_counter()
        vl, va, _ = eval_fn(params, *val)
        dispatch_s = time.perf_counter() - t0
        _ = (float(vl), float(va))   # the driver's old inline sync
        sync_s = time.perf_counter() - t0
        host_sync = {"eval_dispatch_s": round(dispatch_s, 4),
                     "eval_sync_s": round(sync_s, 4),
                     "removed_per_eval_s": round(sync_s - dispatch_s, 4)}
        log(f"[bench] eval dispatch {dispatch_s*1e3:.1f}ms vs sync "
            f"{sync_s*1e3:.1f}ms -> async metrics hide "
            f"{(sync_s - dispatch_s)*1e3:.1f}ms per eval boundary")
    except Exception as e:  # informative, never fatal
        log(f"[bench] host-sync probe unavailable: {e}")

    agg_mode_ab = None
    if args.agg_mode == "both":
        # buffered-async A/B (ISSUE 12): (1) buffered at K=m, staleness 0
        # — the pure mode overhead (acceptance: ticks/sec within 3% of
        # sync rounds/sec; the fold arithmetic is the only delta); (2) at
        # 30%/50% straggler rates, sync rounds/sec (the barrier pays the
        # latency on the simulated clock) vs buffered ticks/sec at
        # K=m/2 — the production-shape comparison the r13 notes judge.
        hb.update(phase="agg_mode_ab", force=True)
        _, r_buf, c_buf, _ = measure(cfg.replace(agg_mode="buffered"),
                                     label="[agg_mode buffered K=m]")
        agg_mode_ab = {
            "sync": {"rounds_per_sec": round(rounds_per_sec, 4)},
            "buffered": {"ticks_per_sec": round(r_buf, 4),
                         "compile_s": round(c_buf, 1)},
            "buffered_vs_sync": round(r_buf / rounds_per_sec, 4)}
        for rate in (0.3, 0.5):
            scfg = cfg.replace(straggler_rate=rate)
            _, r_s, _, _ = measure(scfg,
                                   label=f"[sync straggler={rate}]")
            _, r_b, _, _ = measure(
                scfg.replace(agg_mode="buffered",
                             async_buffer_k=max(
                                 1, cfg.agents_per_round // 2)),
                label=f"[buffered K=m/2 straggler={rate}]")
            agg_mode_ab[f"straggler_{rate}"] = {
                "sync_rounds_per_sec": round(r_s, 4),
                "buffered_ticks_per_sec": round(r_b, 4)}
        log(f"[bench] buffered/sync throughput ratio at K=m: "
            f"{agg_mode_ab['buffered_vs_sync']:.3f}x")

    tenancy_ab_out = None
    if args.tenants >= 2:
        # multi-tenant A/B (ISSUE 13, service/tenancy.py): the SAME
        # 16-cell shape-compatible cell list (seeds x RLR thresholds —
        # pure per-tenant knobs) through the serial queue and the
        # tenant-packed queue at --tenants E. Each arm reports wall +
        # cells/hour; the headline is the packed/serial speedup (the
        # ROADMAP target is >10x on TPU via the banked *_mt families).
        hb.update(phase="tenancy_ab", force=True)
        from defending_against_backdoors_with_robust_learning_rate_tpu.service.queue import (
            run_queue)
        thr_ab = cfg.robustLR_threshold or 4
        ab_cells = [{"name": f"s{s}_t{t}",
                     "overrides": {"seed": s, "robustLR_threshold": t}}
                    for t in (0, thr_ab) for s in range(8)]
        ab_cfg = cfg.replace(rounds=2 * chain, snap=chain,
                             tensorboard=False, profile_rounds=0)
        tenancy_ab_out = {"cells": len(ab_cells), "tenants": args.tenants,
                          "rounds_per_cell": ab_cfg.rounds}
        for arm, E in (("serial", 0), ("packed", args.tenants)):
            arm_cfg = ab_cfg.replace(log_dir=os.path.join(
                cfg.log_dir, "tenancy_ab", arm))
            t_arm = time.perf_counter()
            rows = run_queue(
                arm_cfg,
                [dict(c, overrides=dict(c["overrides"]))
                 for c in ab_cells],
                results_path=os.path.join(arm_cfg.log_dir,
                                          "queue_results.jsonl"),
                tenants=E)
            wall = time.perf_counter() - t_arm
            ok = sum(r["ok"] for r in rows)
            tenancy_ab_out[arm] = {
                "ok": ok, "wall_s": round(wall, 2),
                "cells_per_hour": round(3600.0 * ok / max(wall, 1e-9),
                                        2)}
        tenancy_ab_out["speedup"] = round(
            tenancy_ab_out["packed"]["cells_per_hour"]
            / max(tenancy_ab_out["serial"]["cells_per_hour"], 1e-9), 3)
        log(f"[bench] tenancy A/B: serial "
            f"{tenancy_ab_out['serial']['cells_per_hour']:.1f} vs packed "
            f"{tenancy_ab_out['packed']['cells_per_hour']:.1f} cells/hour"
            f" ({tenancy_ab_out['speedup']:.2f}x at E={args.tenants})")

    vs_baseline = None
    base_path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                             "BASELINE_MEASURED.json")
    if os.path.exists(base_path) and args.bench_config == "fmnist":
        # the measured torch baseline is the CNN_MNIST batch step; it does
        # not transfer to ResNet-9 (a model the reference doesn't have), so
        # the resnet9 config omits the key entirely rather than emitting a
        # fake 1.0x
        with open(base_path) as f:
            base = json.load(f)
        batches_per_agent = fed.train.images.shape[1] // cfg.bs
        ref_round_sec = (cfg.agents_per_round * cfg.local_ep *
                         batches_per_agent * base["sec_per_batch_step"])
        vs_baseline = rounds_per_sec * ref_round_sec
        log(f"[bench] reference-semantics round would take "
            f"{ref_round_sec:.1f}s on this host's CPU -> "
            f"speedup {vs_baseline:.1f}x")

    out = {"metric": "fl_rounds_per_sec",
           "value": round(rounds_per_sec, 4),
           "unit": "rounds/sec",
           "compile_s": round(compile_s, 1),
           "chain": chain,
           # blocks*chain = steady rounds: obs/explain.py normalizes the
           # span totals per round with it when diffing two artifacts
           "blocks": args.blocks,
           "rng_impl": rng_impl,
           "bench_config": args.bench_config,
           "dtype": cfg.dtype,
           "device": device}
    if cache_info is not None:
        # cold-vs-warm compile persistence (utils/compile_cache.py): a
        # second run on a populated cache reports cache_hit true and
        # compile_s_warm (executable deserialize) << compile_s_cold
        out["cache_hit"] = cache_info["cache_hit"]
        out["compile_s_cold"] = cache_info["compile_s_cold"]
        if cache_info["compile_s_warm"] is not None:
            out["compile_s_warm"] = cache_info["compile_s_warm"]
    if host_sync is not None:
        out["host_sync"] = host_sync
    if ignored_flags:
        out["ignored_flags"] = ignored_flags
    if vs_baseline is not None:
        # only when a comparable measured baseline exists (fmnist config);
        # resnet9 has no reference counterpart, so no 1.0x placeholder
        out["vs_baseline"] = round(vs_baseline, 2)
    if flops_round is not None:
        out["tflop_per_round"] = round(flops_round / 1e12, 4)
        out["tflops_per_sec"] = round(tflops_sec, 2)
    if analytic_round is not None:
        # the compile-free MFU trajectory (ISSUE 10): analytic FLOPs from
        # the model registry, trackable on CPU before any TPU session
        out["analytic_tflop_per_round"] = round(analytic_round / 1e12, 4)
        out["analytic_tflops_per_sec"] = round(
            analytic_round * rounds_per_sec / 1e12, 3)
        if mfu is None and peak:
            # cost analysis unavailable (some backends) — the analytic
            # count still yields the MFU figure
            mfu = analytic_round * rounds_per_sec / 1e12 / peak
    if mfu is not None:
        out["mfu"] = round(mfu, 4)
    if faults_out is not None:
        out["faults"] = faults_out
    if telemetry_out is not None:
        out["telemetry"] = telemetry_out
    out["health"] = cfg.health
    if health_ab_out is not None:
        out["health_ab"] = health_ab_out
    out["reputation"] = cfg.reputation
    if reputation_ab_out is not None:
        out["reputation_ab"] = reputation_ab_out
    if events_ab_out is not None:
        out["events_ab"] = events_ab_out
    if population_out is not None:
        out["population"] = population_out
    if attribution_out is not None:
        out["attribution"] = attribution_out
    out["agg_mode"] = cfg.agg_mode
    if agg_mode_ab is not None:
        out["agg_mode_ab"] = agg_mode_ab
    if tenancy_ab_out is not None:
        out["tenancy_ab"] = tenancy_ab_out
    if hbm:
        out["hbm"] = hbm
    # per-phase span aggregates (obs/spans.py): where this bench's wall
    # time actually went — data vs acquire vs blocks
    out["spans"] = tracer.aggregates()
    if args.synth_train_size:
        out["synth_override"] = args.synth_train_size
    hb.close("done")
    print(json.dumps(out))


if __name__ == "__main__":
    main()
