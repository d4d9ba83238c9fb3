"""Declared contracts for the static-analysis passes (analysis/).

Three kinds of declaration live here, one per pass:

1. **AST-rule scope + allowlist** (`analysis/ast_rules.py`): which modules
   count as round/eval hot paths for the host-sync rule, which functions
   are exempt from which rules (with the justification inline — an ALLOW
   entry without a reason is a review defect), and which cross-module
   callees donate their buffers.
2. **Jaxpr contracts** (`analysis/jaxpr_lint.py`): the named check
   configurations (tiny synthetic shapes — tracing cost, not training
   cost) and the per-family collective budgets they must hold. Budgets
   are ceilings derived from the implementation's documented communication
   plan (parallel/rounds.py module docstring); `analysis_baseline.json`
   records the exact measured counts so future PRs see *diffs*, not just
   pass/fail.
3. **Fingerprint provenance rules** (`analysis/fingerprint_audit.py`):
   which provenance classes may/must appear in the AOT-bank fingerprint
   (utils/compile_cache.EXCLUDED_FIELDS), and which package modules count
   as program-shaping for the cfg-read cross-check.

Adding a contract: append a `CheckSpec` to `check_specs()` (or widen a
budget with a comment saying why the communication plan changed) and
refresh `analysis_baseline.json` via
`python -m defending_against_backdoors_with_robust_learning_rate_tpu.analysis --write-baseline`.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Tuple

PKG = "defending_against_backdoors_with_robust_learning_rate_tpu"

# --------------------------------------------------------------------------
# AST-rule scope (analysis/ast_rules.py)
# --------------------------------------------------------------------------

# Modules whose code sits on the round/eval hot path: a host sync here
# either blocks the dispatch loop (driver files) or is flat-out wrong
# (traced files). Paths are repo-relative; trailing "/" means the subtree.
HOT_PATH_MODULES = (
    f"{PKG}/fl/",
    f"{PKG}/ops/",
    f"{PKG}/parallel/rounds.py",
    f"{PKG}/faults/",
    f"{PKG}/obs/telemetry.py",
    f"{PKG}/data/prefetch.py",
    f"{PKG}/train.py",
    "scripts/profile_round.py",
    # in-jit attack strategies (ISSUE 11): the update transform and its
    # schedule gate run inside every round program
    f"{PKG}/attack/registry.py",
    f"{PKG}/attack/schedule.py",
    f"{PKG}/attack/boost.py",
    f"{PKG}/attack/signflip.py",
    # in-jit health sentinel (ISSUE 14): its reductions run inside every
    # round program (the host-side half lives in health/monitor.py,
    # which is deliberately NOT hot-path scope)
    f"{PKG}/health/sentinel.py",
    # in-jit reputation lane (ISSUE 20): agree_rows/agree_rows_flat are
    # traced into every round program; the module's host half
    # (ReputationTracker) runs on the post-drain emit path and carries
    # ALLOW entries below
    f"{PKG}/obs/reputation.py",
)

# Function-level exemptions: (repo-relative path, function qualname prefix)
# -> {rule: justification}. Nested functions inherit their parent's entry.
# Every entry must say WHY the rule does not apply — these are the
# documented escape hatches, not a dumping ground.
ALLOW: Dict[Tuple[str, str], Dict[str, str]] = {
    (f"{PKG}/train.py", "_emit_eval_body"): {
        "host-sync": "RoundEngine._emit_eval_body runs on the MetricsDrain "
                     "thread (async mode) or at the eval boundary after an "
                     "explicit device_get (sync mode); values are already "
                     "host-side",
    },
    (f"{PKG}/obs/telemetry.py", "emit_scalars"): {
        "host-sync": "host emit path shared by the sync/async metrics "
                     "streams; called only with already-fetched values",
    },
    (f"{PKG}/obs/telemetry.py", "host_summary"): {
        "host-sync": "summary/adaptation snapshot builder on the same "
                     "post-drain host path as emit_scalars; called only "
                     "with already-fetched values",
    },
    # ReputationTracker methods (the AST pass keys bare method qualnames
    # — class names do not prefix): the longitudinal tracker folds
    # DRAINED rows on the post-drain emit path (train.py _emit_eval_body
    # / service tenancy _emit_slot); every value it touches is already
    # host-side
    (f"{PKG}/obs/reputation.py", "fold"): {
        "host-sync": "ReputationTracker.fold consumes drained numpy rows "
                     "on the post-drain emit path; values are already "
                     "host-side",
    },
    (f"{PKG}/obs/reputation.py", "boundary_rows"): {
        "host-sync": "ReputationTracker.boundary_rows renders host-side "
                     "Python EMA state into metrics rows on the emit "
                     "path; no device value is touched",
    },
    (f"{PKG}/obs/reputation.py", "load_state"): {
        "host-sync": "ReputationTracker.load_state converts JSON journal "
                     "scalars at resume time; no device value is touched",
    },
    (f"{PKG}/obs/reputation.py", "emit_rows"): {
        "host-sync": "host emit path shared by the sync/async metrics "
                     "streams and the tenant fan-out (the emit_scalars "
                     "discipline); called only with already-folded "
                     "host state",
    },
    (f"{PKG}/fl/diagnostics.py", "norm_scalars"): {
        "host-sync": "snap-cadence research diagnostics; --diagnostics "
                     "forces the synchronous metrics path by design",
    },
    (f"{PKG}/fl/diagnostics.py", "sign_agreement"): {
        "host-sync": "host-side set algebra on flat vectors at snap "
                     "cadence (--diagnostics is synchronous by design)",
    },
    (f"{PKG}/data/registry.py", "make_synthetic.gen"): {
        "jit-side-effect": "host-side numpy dataset synthesis; `gen` is "
                           "a data generator the builder calls eagerly, "
                           "never traced (the make_ builder convention "
                           "false-positives here)",
    },
    (f"{PKG}/fl/tenancy.py", "knob_vectors"): {
        "host-sync": "host-side knob-vector construction from Python "
                     "config scalars at pack-build time; no device "
                     "value is touched",
    },
    (f"{PKG}/fl/buffered.py", "host_latency_draw"): {
        "host-sync": "host MIRROR of the in-program arrival draw (the "
                     "churn/cohort mirror idiom): returns numpy for the "
                     "scenario sweep's simulated clock and the arrival-"
                     "timing tests; never called on the dispatch path",
    },
    (f"{PKG}/ops/loops.py", "maybe_unrolled_scan"): {
        "jit-side-effect": "RLR_SCAN_MODE/RLR_SCAN_UNROLL are deliberate "
                           "trace-time measurement overrides (module "
                           "docstring); NOTE they change the traced "
                           "program without entering the AOT fingerprint "
                           "— never set them outside profiling",
    },
    (f"{PKG}/data/prefetch.py", "_worker"): {
        "cross-thread-state": "_err is written exactly once, and the "
                              "sentinel put() that follows it publishes "
                              "the write — Queue's internal lock gives "
                              "the consuming get() the happens-before "
                              "edge before _raise_if_failed reads it",
    },
    (f"{PKG}/data/bank.py", "_write_range"): {
        "racy-file-write": "every shard + digest sidecar lands inside "
                           "the build's PRIVATE tmp directory (one per "
                           "worker range, non-overlapping shard ids); "
                           "the parent publishes the finished tree with "
                           "a single atomic os.replace after all "
                           "workers join",
    },
    (f"{PKG}/utils/metrics.py", "_stop_and_join"): {
        "cross-thread-state": "joining while holding _cond would "
                              "deadlock the worker's final drain; only "
                              "the owning submitter thread calls close/"
                              "_stop_and_join, and the worker never "
                              "touches _thread — the join() itself is "
                              "the synchronization edge",
    },
    (f"{PKG}/obs/export.py", "close"): {
        "cross-thread-state": "teardown runs on the owning driver "
                              "thread; holding _lock across shutdown()/"
                              "join() could deadlock a mid-scrape "
                              "render, and the scrape thread only READS "
                              "via render() — it never writes _server/"
                              "_thread; shutdown()+join() is the "
                              "synchronization edge",
    },
    (f"{PKG}/service/tenancy.py", "load_slot"): {
        "cross-thread-state": "slot replacement runs only in the "
                              "scheduler harness, which constructs the "
                              "pack with evict_on_anomaly=True and "
                              "therefore drain=None (tenancy.py) — no "
                              "drain thread exists to race the slots "
                              "write; the gather executor only runs "
                              "inside step(), never concurrently with "
                              "load_slot",
    },
    (f"{PKG}/service/tenancy.py", "_emit_all"): {
        "cross-thread-state": "the steady-state counters are folded "
                              "only inside _emit_all, which runs "
                              "serialized on the single MetricsDrain "
                              "worker (submits are queued); steady_rps "
                              "reads them only after close() has "
                              "flushed and joined the drain — the join "
                              "is the happens-before edge",
    },
}

# Cross-module donated-buffer callees the donate-reuse rule tracks: callee
# name -> donated positional-argument indices. In-module donation
# (functools.partial(jax.jit, donate_argnums=...)) is detected
# structurally; this covers names that cross a module boundary (train.py
# calls the chained fns built in fl/rounds.py, which donate params).
DONATED_CALLS: Dict[str, Tuple[int, ...]] = {
    "chained_fn": (0,),
    "host_chained_fn": (0,),
}

# Program families whose params argument MUST be donated (position 0):
# the chained lax.scan blocks are the throughput hot path, and without
# donation every dispatched block would hold two full parameter buffers
# (and XLA may insert a copy for the carry). The donation-audit pin
# (tests/test_compile_cache.py::test_chained_families_donate_params)
# lowers each family through the compile-cache planners and asserts the
# StableHLO input-output aliasing attribute on arg 0 — a regression (a
# refactor dropping donate_argnums) fails tier-1/CI. The per-round
# families deliberately do NOT donate: the diagnostics snap path reads
# prev_params after the call, parity tests dispatch several programs on
# one buffer, and the service supervisor may retry a dispatch whose
# donated input a partially-executed call already consumed.
DONATED_FAMILIES: Tuple[str, ...] = (
    "chained", "chained_host", "chained_cohort", "chained_sharded",
    # tenant-pack twins (ISSUE 13): the chained scan donates the whole
    # [E, ...]-stacked parameter carry — without it every dispatched
    # block would hold two copies of E experiments' params
    "chained_mt",
    # buffered-async twins (ISSUE 12): the chained scan donates the whole
    # (params, buffer) carry — without it every dispatched block would
    # hold two copies of the buffer state on top of the params pair
    "chained_async", "chained_cohort_async", "chained_sharded_async",
    # buffered tenant packs (ISSUE 16): the chained scan donates the
    # [E]-stacked (params, buffer) carry
    "chained_async_mt",
)

# --------------------------------------------------------------------------
# Jaxpr contracts (analysis/jaxpr_lint.py)
# --------------------------------------------------------------------------

# primitives that must never appear in a round/eval program: host
# callbacks stall the dispatch pipeline and are unserializable in the AOT
# bank; infeed/outfeed are not part of this design at all.
FORBIDDEN_PRIMITIVES = frozenset({
    "debug_callback", "debug_print", "pure_callback", "io_callback",
    "callback", "outside_call", "infeed", "outfeed",
    "host_local_array_to_global_array",
})

# collective primitive names counted against the budgets
COLLECTIVE_PRIMITIVES = ("psum", "all_gather", "all_to_all", "ppermute",
                         "pmin", "pmax", "reduce_scatter")

# mesh-axis sizes the sharded contracts are traced and judged at: single
# chip, the 8-way CI mesh, and a 16-way pod shape. Counts are the same at
# every topology BY DESIGN (the communication plans are topology-free);
# tracing each size proves it. The reference topology keeps the
# historical (unsuffixed) baseline keys; other sizes record as
# `<name>@<d>w`. Topologies above the process's faked device count are
# skipped (tier-1 runs under 8; scripts/check_static.py forces 16).
TOPOLOGIES = (1, 8, 16)
REFERENCE_TOPOLOGY = 8


@dataclasses.dataclass(frozen=True)
class CheckSpec:
    """One jaxpr-contract check: a named tiny config, the program family
    to trace, and the budgets its IR must hold.

    `collective_budget` is a jaxpr-level ceiling per collective primitive
    (traced eqn counts, pre-CSE — deterministic and compile-free).
    `hlo_all_reduce_max` additionally bounds post-optimization all-reduce
    ops in the compiled HLO (``--compiled`` mode): this is where the
    "sign psums CSE with the RLR vote" claim becomes a test, because the
    jaxpr-level count legitimately double-counts the shared vote."""
    name: str
    family: str
    sharded: bool
    cfg_overrides: Dict[str, object]
    collective_budget: Dict[str, int]
    hlo_all_reduce_max: Optional[int] = None
    forbid_f64: bool = True
    forbid_callbacks: bool = True
    host_mode: bool = False    # plan the host-sampled variant (the driver
                               # gathers shards host-side; [m, ...] args)


def base_check_config():
    """The tiny synthetic config every check derives from. 8 agents so the
    8-device CI mesh gets 1 agent/device; shapes small enough that tracing
    is milliseconds."""
    from defending_against_backdoors_with_robust_learning_rate_tpu.config import (
        Config)
    return Config(data="synthetic", num_agents=8, bs=16, local_ep=1,
                  synth_train_size=128, synth_val_size=32, eval_bs=32,
                  rounds=2, snap=1, num_corrupt=2, poison_frac=0.5,
                  robustLR_threshold=4, aggr="avg", seed=0,
                  compile_cache=False, tensorboard=False,
                  data_dir="/nonexistent_use_synthetic")


# The CNN parameter tree used by every check config (models/cnn.py):
# conv1/conv2 kernel+bias, dense1/dense2 kernel+bias = 8 leaves. The
# budget formulas below take it as a parameter so a model change shows up
# as a budget diff, not silent slack.
def collective_budgets(n_leaves: int) -> Dict[str, "CheckSpec"]:
    """The checked family matrix, keyed by spec name. Budget arithmetic
    mirrors parallel/rounds.py's documented communication plan:

    - loss pmean: 1 psum
    - RLR vote (_sharded_robust_lr): 1 sign psum per leaf
    - avg aggregate: 1 weighted-sum psum per leaf + 1 weight-total psum
    - sign + RLR: 1 SHARED sign psum per leaf (_sharded_sign_shared —
      the vote reads |s|, the aggregate sign(s); this pass measured that
      the old rely-on-XLA-CSE version never actually merged its
      channel-id'd all-reduces)
    - faults: exactly 1 [m]-bit validation all_gather, nothing else

    HLO ceilings add the partitioner's fixed overhead: on the measured
    toolchain (jax 0.4.37, XLA:CPU, 8 devices) GSPMD inserts 3
    all-reduces (+4 collective-permute, 1 all-gather) partitioning the
    outer in-jit sample gather around the shard_map — a constant, not a
    per-leaf term. A jax upgrade may shift it; re-measure via
    --compiled --write-baseline and review the diff.
    """
    spmd_overhead = 3
    zero = {p: 0 for p in COLLECTIVE_PRIMITIVES}
    specs = {}

    # vmap path: the whole point is NO collectives of any kind
    specs["vmap_rlr_avg"] = CheckSpec(
        name="vmap_rlr_avg", family="round", sharded=False,
        cfg_overrides={}, collective_budget=dict(zero))
    specs["vmap_eval"] = CheckSpec(
        name="vmap_eval", family="eval_val", sharded=False,
        cfg_overrides={}, collective_budget=dict(zero))
    # the folded round (ISSUE 27, ROADMAP R3): the same `round` family
    # with the update stack replaced by a scan over chunks of clients that
    # carries (weighted sum, sign sum, weight total). `agg_path` has no
    # flag: the engine resolves it from the device's memory
    # (compile_cache.resolved_agg); the spec sets it. Collective-free like
    # its stacked twin, and no f64, no callback in the scan body.
    specs["vmap_rlr_avg_fold"] = CheckSpec(
        name="vmap_rlr_avg_fold", family="round", sharded=False,
        cfg_overrides={"agg_path": "fold", "agent_chunk": 2},
        collective_budget=dict(zero))

    # flagship sharded defense: avg + RLR — psums only, no transposes
    specs["sharded_rlr_avg"] = CheckSpec(
        name="sharded_rlr_avg", family="round_sharded", sharded=True,
        cfg_overrides={},
        collective_budget={**zero, "psum": 2 * n_leaves + 2},
        hlo_all_reduce_max=2 * n_leaves + 2 + spmd_overhead)

    # sign + RLR: the vote and the aggregate SHARE one sign psum per leaf
    # (_sharded_sign_shared) — n_leaves + 1 total, at both IR levels
    specs["sharded_rlr_sign"] = CheckSpec(
        name="sharded_rlr_sign", family="round_sharded", sharded=True,
        cfg_overrides={"aggr": "sign", "server_lr": 1.0},
        collective_budget={**zero, "psum": n_leaves + 1},
        hlo_all_reduce_max=n_leaves + 1 + spmd_overhead)

    # faults on the sharded path: the ONLY added collective is the [m]-bit
    # payload-validation all_gather (parallel/rounds.py docstring claim)
    specs["sharded_rlr_avg_faults"] = CheckSpec(
        name="sharded_rlr_avg_faults", family="round_sharded", sharded=True,
        cfg_overrides={"dropout_rate": 0.3, "payload_norm_cap": 100.0,
                       "faults_spare_corrupt": True},
        collective_budget={**zero, "psum": 2 * n_leaves + 2,
                           "all_gather": 1},
        hlo_all_reduce_max=2 * n_leaves + 2 + spmd_overhead)

    # host-sampled sharded variant (the fedemnist-scale dispatch surface):
    # same body, no in-jit sample gather — identical collective budget
    specs["sharded_host_rlr_avg"] = CheckSpec(
        name="sharded_host_rlr_avg", family="round_sharded_host",
        sharded=True, host_mode=True, cfg_overrides={},
        collective_budget={**zero, "psum": 2 * n_leaves + 2},
        hlo_all_reduce_max=2 * n_leaves + 2 + spmd_overhead)

    # chained sharded block: same per-round body inside a lax.scan — the
    # static walk counts the body once, so the budget is unchanged
    specs["sharded_chained_rlr_avg"] = CheckSpec(
        name="sharded_chained_rlr_avg", family="chained_sharded",
        sharded=True, cfg_overrides={"chain": 2, "snap": 2},
        collective_budget={**zero, "psum": 2 * n_leaves + 2},
        hlo_all_reduce_max=2 * n_leaves + 2 + spmd_overhead)

    # --telemetry full families (ROADMAP REMAINING after PR 4): full
    # telemetry's vote-margin histogram needs the per-leaf sign psums the
    # RLR vote already issues — obs/telemetry.compute_sharded now takes
    # them as `sign_sums` (the PR-4 shared-psum fix applied to the
    # duplicate telemetry used to rely on XLA CSE'ing away, which
    # channel-id'd all-reduces never do). Net telemetry cost on every
    # sharded family: ZERO extra psums + exactly 3 tiny all_gathers
    # (norms, cosine dots, cosine usq).
    specs["vmap_rlr_avg_tel_full"] = CheckSpec(
        name="vmap_rlr_avg_tel_full", family="round", sharded=False,
        cfg_overrides={"telemetry": "full"},
        collective_budget=dict(zero))
    specs["sharded_rlr_avg_tel_full"] = CheckSpec(
        name="sharded_rlr_avg_tel_full", family="round_sharded",
        sharded=True, cfg_overrides={"telemetry": "full"},
        collective_budget={**zero, "psum": 2 * n_leaves + 2,
                           "all_gather": 3},
        hlo_all_reduce_max=2 * n_leaves + 2 + spmd_overhead)
    specs["sharded_rlr_sign_tel_full"] = CheckSpec(
        name="sharded_rlr_sign_tel_full", family="round_sharded",
        sharded=True,
        cfg_overrides={"aggr": "sign", "server_lr": 1.0,
                       "telemetry": "full"},
        collective_budget={**zero, "psum": n_leaves + 1,
                           "all_gather": 3},
        hlo_all_reduce_max=n_leaves + 1 + spmd_overhead)

    # client churn (ISSUE 6, service/churn.py): the lifecycle mask is a
    # replicated draw feeding the participation-mask protocol — the
    # acceptance claim is ZERO collectives beyond the plain family's plan
    # (vmap stays collective-free; the sharded budget is unchanged), and
    # churn + faults together still cost only the ONE [m]-bit validation
    # all_gather the faults path already pays.
    churn = {"churn_available": 0.75, "churn_period": 4}
    specs["vmap_rlr_avg_churn"] = CheckSpec(
        name="vmap_rlr_avg_churn", family="round", sharded=False,
        cfg_overrides=dict(churn), collective_budget=dict(zero))
    specs["sharded_rlr_avg_churn"] = CheckSpec(
        name="sharded_rlr_avg_churn", family="round_sharded", sharded=True,
        cfg_overrides=dict(churn),
        collective_budget={**zero, "psum": 2 * n_leaves + 2},
        hlo_all_reduce_max=2 * n_leaves + 2 + spmd_overhead)
    specs["sharded_rlr_avg_churn_faults"] = CheckSpec(
        name="sharded_rlr_avg_churn_faults", family="round_sharded",
        sharded=True,
        cfg_overrides={**churn, "dropout_rate": 0.3,
                       "payload_norm_cap": 100.0,
                       "faults_spare_corrupt": True},
        collective_budget={**zero, "psum": 2 * n_leaves + 2,
                           "all_gather": 1},
        hlo_all_reduce_max=2 * n_leaves + 2 + spmd_overhead)

    # adaptive-adversary attack registry (ISSUE 11, attack/registry.py):
    # the in-jit strategies (boost / signflip) are an elementwise per-row
    # scale on the stacked updates, with corrupt flags derived from real
    # client ids and the schedule gate a replicated pure function of the
    # traced round index — the acceptance claim is ZERO collectives
    # beyond the plain family's plan on EVERY dispatch surface. The
    # scheduled variants additionally exercise the takes_round signature
    # (the round index as a traced lead argument) through the planners.
    atk_b = {"attack": "boost", "attack_boost": 8.0}
    atk_s = {"attack": "signflip"}
    atk_sched = {"attack": "signflip", "attack_start": 2,
                 "attack_every": 2}
    specs["vmap_rlr_avg_atk_boost"] = CheckSpec(
        name="vmap_rlr_avg_atk_boost", family="round", sharded=False,
        cfg_overrides=dict(atk_b), collective_budget=dict(zero))
    specs["vmap_rlr_avg_atk_sched"] = CheckSpec(
        name="vmap_rlr_avg_atk_sched", family="round", sharded=False,
        cfg_overrides=dict(atk_sched), collective_budget=dict(zero))
    specs["sharded_rlr_avg_atk_boost"] = CheckSpec(
        name="sharded_rlr_avg_atk_boost", family="round_sharded",
        sharded=True, cfg_overrides=dict(atk_b),
        collective_budget={**zero, "psum": 2 * n_leaves + 2},
        hlo_all_reduce_max=2 * n_leaves + 2 + spmd_overhead)
    specs["sharded_rlr_sign_atk_signflip"] = CheckSpec(
        name="sharded_rlr_sign_atk_signflip", family="round_sharded",
        sharded=True,
        cfg_overrides={**atk_s, "aggr": "sign", "server_lr": 1.0},
        collective_budget={**zero, "psum": n_leaves + 1},
        hlo_all_reduce_max=n_leaves + 1 + spmd_overhead)
    specs["sharded_rlr_avg_atk_boost_faults"] = CheckSpec(
        name="sharded_rlr_avg_atk_boost_faults", family="round_sharded",
        sharded=True,
        cfg_overrides={**atk_b, "dropout_rate": 0.3,
                       "payload_norm_cap": 100.0,
                       "faults_spare_corrupt": True},
        collective_budget={**zero, "psum": 2 * n_leaves + 2,
                           "all_gather": 1},
        hlo_all_reduce_max=2 * n_leaves + 2 + spmd_overhead)
    specs["sharded_chained_rlr_avg_atk_sched"] = CheckSpec(
        name="sharded_chained_rlr_avg_atk_sched",
        family="chained_sharded", sharded=True,
        cfg_overrides={**atk_sched, "chain": 2, "snap": 2},
        collective_budget={**zero, "psum": 2 * n_leaves + 2},
        hlo_all_reduce_max=2 * n_leaves + 2 + spmd_overhead)
    specs["sharded_rlr_avg_cohort_atk_sched"] = CheckSpec(
        name="sharded_rlr_avg_cohort_atk_sched",
        family="round_sharded_cohort", sharded=True,
        cfg_overrides={**atk_sched, "cohort_sampled": "on"},
        collective_budget={**zero, "psum": 2 * n_leaves + 2},
        hlo_all_reduce_max=2 * n_leaves + 2 + spmd_overhead)

    # buffered-async aggregation (ISSUE 12, fl/buffered.py): the carried
    # buffer fold is elementwise on the replicated trees, and the
    # per-level contribution sums RIDE
    # the sync plan's collectives — per-leaf psums carry [S+1]-stacked
    # partials instead of plain leaves (a shape change, not a count
    # change), and the tiny count/weight/loss lanes pack into ONE vector
    # psum that replaces the sync plan's weight-total psum + loss pmean.
    # The acceptance claim is therefore ZERO collectives beyond each
    # mode's pinned plan: vmap stays collective-free, avg+RLR stays
    # within 2L+2 psums (measured 2L+1: the packing saves one), sign+RLR
    # within L+1, and faults still add exactly the one [m]-bit
    # validation all_gather. The `_stale` spec runs WITH
    # stragglers so the level-stacked (pending-ladder) shape is the one
    # being judged, not just the staleness-0 fast path.
    buf = {"agg_mode": "buffered"}
    specs["vmap_rlr_avg_async"] = CheckSpec(
        name="vmap_rlr_avg_async", family="round_async", sharded=False,
        cfg_overrides=dict(buf), collective_budget=dict(zero))
    specs["sharded_rlr_avg_async"] = CheckSpec(
        name="sharded_rlr_avg_async", family="round_sharded_async",
        sharded=True, cfg_overrides=dict(buf),
        collective_budget={**zero, "psum": 2 * n_leaves + 2},
        hlo_all_reduce_max=2 * n_leaves + 2 + spmd_overhead)
    specs["sharded_rlr_sign_async"] = CheckSpec(
        name="sharded_rlr_sign_async", family="round_sharded_async",
        sharded=True,
        cfg_overrides={**buf, "aggr": "sign", "server_lr": 1.0},
        collective_budget={**zero, "psum": n_leaves + 1},
        hlo_all_reduce_max=n_leaves + 1 + spmd_overhead)
    specs["sharded_rlr_avg_async_stale"] = CheckSpec(
        name="sharded_rlr_avg_async_stale", family="round_sharded_async",
        sharded=True,
        cfg_overrides={**buf, "straggler_rate": 0.5,
                       "async_buffer_k": 4},
        collective_budget={**zero, "psum": 2 * n_leaves + 2,
                           "all_gather": 1},
        hlo_all_reduce_max=2 * n_leaves + 2 + spmd_overhead)
    specs["sharded_rlr_avg_async_faults"] = CheckSpec(
        name="sharded_rlr_avg_async_faults", family="round_sharded_async",
        sharded=True,
        cfg_overrides={**buf, "dropout_rate": 0.3,
                       "payload_norm_cap": 100.0,
                       "faults_spare_corrupt": True},
        collective_budget={**zero, "psum": 2 * n_leaves + 2,
                           "all_gather": 1},
        hlo_all_reduce_max=2 * n_leaves + 2 + spmd_overhead)
    specs["sharded_chained_rlr_avg_async"] = CheckSpec(
        name="sharded_chained_rlr_avg_async",
        family="chained_sharded_async", sharded=True,
        cfg_overrides={**buf, "chain": 2, "snap": 2},
        collective_budget={**zero, "psum": 2 * n_leaves + 2},
        hlo_all_reduce_max=2 * n_leaves + 2 + spmd_overhead)
    specs["sharded_rlr_avg_cohort_async"] = CheckSpec(
        name="sharded_rlr_avg_cohort_async",
        family="round_sharded_cohort_async", sharded=True,
        cfg_overrides={**buf, "cohort_sampled": "on"},
        collective_budget={**zero, "psum": 2 * n_leaves + 2},
        hlo_all_reduce_max=2 * n_leaves + 2 + spmd_overhead)

    # cohort-sampled population axis (ISSUE 7, data/cohort.py): the
    # in-program cohort draw + active mask are replicated computations
    # feeding the participation-mask protocol — the acceptance claim is
    # ZERO collectives beyond the plain family's plan (the vmap cohort
    # family stays collective-free; the sharded budget is unchanged;
    # cohort + churn composes presence into the draw for free; cohort +
    # faults still costs only the one [m]-bit validation all_gather).
    # The HLO ceilings carry the same measured +3 GSPMD partitioner
    # constant as every sharded family (analysis_baseline.json pins 21
    # all-reduces = the 18-psum plan + 3).
    coh = {"cohort_sampled": "on"}
    specs["vmap_rlr_avg_cohort"] = CheckSpec(
        name="vmap_rlr_avg_cohort", family="round_cohort", sharded=False,
        cfg_overrides=dict(coh), collective_budget=dict(zero))
    specs["sharded_rlr_avg_cohort"] = CheckSpec(
        name="sharded_rlr_avg_cohort", family="round_sharded_cohort",
        sharded=True, cfg_overrides=dict(coh),
        collective_budget={**zero, "psum": 2 * n_leaves + 2},
        hlo_all_reduce_max=2 * n_leaves + 2 + spmd_overhead)
    specs["sharded_rlr_avg_cohort_churn"] = CheckSpec(
        name="sharded_rlr_avg_cohort_churn", family="round_sharded_cohort",
        sharded=True, cfg_overrides={**coh, **churn},
        collective_budget={**zero, "psum": 2 * n_leaves + 2},
        hlo_all_reduce_max=2 * n_leaves + 2 + spmd_overhead)
    specs["sharded_rlr_avg_cohort_faults"] = CheckSpec(
        name="sharded_rlr_avg_cohort_faults",
        family="round_sharded_cohort", sharded=True,
        cfg_overrides={**coh, "dropout_rate": 0.3,
                       "payload_norm_cap": 100.0,
                       "faults_spare_corrupt": True},
        collective_budget={**zero, "psum": 2 * n_leaves + 2,
                           "all_gather": 1},
        hlo_all_reduce_max=2 * n_leaves + 2 + spmd_overhead)

    # multi-tenant tenant packs (ISSUE 13, fl/tenancy.py): the
    # EXPERIMENT axis folds as a leading [E] dimension — vmap over
    # tenants INSIDE the shard_map body (parallel/rounds.py
    # make_sharded_round_fn_mt), so every collective batches over the
    # tenant axis instead of multiplying: ONE psum of an [E, ...]
    # payload, not E psums. The acceptance claim is ZERO collectives
    # beyond the pinned plan at 1/8/16-way — avg+RLR stays 2L+2 psums,
    # sign+RLR L+1, faults still exactly the one [m]-bit validation
    # all_gather; the vmap tenant family stays
    # collective-free. Per-tenant knobs are traced [E]-vector inputs and
    # add nothing to the communication plan.
    mt = {"tenants": 2}
    specs["vmap_rlr_avg_mt"] = CheckSpec(
        name="vmap_rlr_avg_mt", family="round_mt", sharded=False,
        cfg_overrides=dict(mt), collective_budget=dict(zero))
    specs["sharded_rlr_avg_mt"] = CheckSpec(
        name="sharded_rlr_avg_mt", family="round_sharded_mt",
        sharded=True, cfg_overrides=dict(mt),
        collective_budget={**zero, "psum": 2 * n_leaves + 2},
        hlo_all_reduce_max=2 * n_leaves + 2 + spmd_overhead)
    specs["sharded_rlr_sign_mt"] = CheckSpec(
        name="sharded_rlr_sign_mt", family="round_sharded_mt",
        sharded=True,
        cfg_overrides={**mt, "aggr": "sign", "server_lr": 1.0},
        collective_budget={**zero, "psum": n_leaves + 1},
        hlo_all_reduce_max=n_leaves + 1 + spmd_overhead)
    specs["sharded_rlr_avg_mt_faults"] = CheckSpec(
        name="sharded_rlr_avg_mt_faults", family="round_sharded_mt",
        sharded=True,
        cfg_overrides={**mt, "dropout_rate": 0.3,
                       "payload_norm_cap": 100.0,
                       "faults_spare_corrupt": True},
        collective_budget={**zero, "psum": 2 * n_leaves + 2,
                           "all_gather": 1},
        hlo_all_reduce_max=2 * n_leaves + 2 + spmd_overhead)

    # buffered tenant packs (ISSUE 16): the carried (params, buffer)
    # state stacks as a leading [E] axis and the async fold batches over
    # tenants under the vmap — the contribution sums still ride the sync
    # plan's collectives (per-leaf psums of [E, S+1, ...] payloads, one
    # packed lane psum), so the claim is the async budget UNCHANGED by
    # the tenant axis at 1/8/16-way: vmap collective-free, avg+RLR
    # within 2L+2 psums, sign+RLR within L+1. The cohort-tenant twin pins
    # gap 3 (one shared bank gather per round): the in-program cohort
    # draw batches over tenants collective-free.
    buf_mt = {**buf, **mt}
    specs["vmap_rlr_avg_async_mt"] = CheckSpec(
        name="vmap_rlr_avg_async_mt", family="round_async_mt",
        sharded=False, cfg_overrides=dict(buf_mt),
        collective_budget=dict(zero))
    specs["sharded_rlr_avg_async_mt"] = CheckSpec(
        name="sharded_rlr_avg_async_mt", family="round_sharded_async_mt",
        sharded=True, cfg_overrides=dict(buf_mt),
        collective_budget={**zero, "psum": 2 * n_leaves + 2},
        hlo_all_reduce_max=2 * n_leaves + 2 + spmd_overhead)
    specs["sharded_rlr_sign_async_mt"] = CheckSpec(
        name="sharded_rlr_sign_async_mt",
        family="round_sharded_async_mt", sharded=True,
        cfg_overrides={**buf_mt, "aggr": "sign", "server_lr": 1.0},
        collective_budget={**zero, "psum": n_leaves + 1},
        hlo_all_reduce_max=n_leaves + 1 + spmd_overhead)
    specs["vmap_rlr_avg_cohort_mt"] = CheckSpec(
        name="vmap_rlr_avg_cohort_mt", family="round_cohort_mt",
        sharded=False, cfg_overrides={**coh, **mt},
        collective_budget=dict(zero))

    # in-program health lane + quarantine mask (ISSUE 14, health/): the
    # sentinel is pure jnp reductions on data the body already holds, and
    # the sharded scalar lanes PACK into the loss psum the body already
    # pays (pmean's scalar psum becomes one [3]-vector psum — a shape
    # change, never a count change; the buffered mode appends to its
    # existing packed-lane psum the same way). The quarantine set is a
    # traced membership CONSTANT feeding the participation-mask protocol
    # (the churn idiom). The acceptance claim is therefore ZERO
    # collectives beyond each family's pinned plan on EVERY dispatch
    # surface, at 1/8/16-way (contracts.TOPOLOGIES), jaxpr + compiled
    # HLO. `health` defaults ON, so every spec above already traces the
    # lane — these `*_hlth` twins pin it EXPLICITLY (surviving a default
    # flip) and compose it with an armed quarantine set; the `_off` twin
    # pins that the bench A/B arm really removes the lane from the vmap
    # program.
    hlth = {"health": "on", "quarantine": "1,3"}
    specs["vmap_rlr_avg_hlth"] = CheckSpec(
        name="vmap_rlr_avg_hlth", family="round", sharded=False,
        cfg_overrides=dict(hlth), collective_budget=dict(zero))
    specs["vmap_rlr_avg_hlth_off"] = CheckSpec(
        name="vmap_rlr_avg_hlth_off", family="round", sharded=False,
        cfg_overrides={"health": "off"}, collective_budget=dict(zero))
    specs["sharded_rlr_avg_hlth"] = CheckSpec(
        name="sharded_rlr_avg_hlth", family="round_sharded",
        sharded=True, cfg_overrides=dict(hlth),
        collective_budget={**zero, "psum": 2 * n_leaves + 2},
        hlo_all_reduce_max=2 * n_leaves + 2 + spmd_overhead)
    specs["sharded_rlr_avg_cohort_hlth"] = CheckSpec(
        name="sharded_rlr_avg_cohort_hlth",
        family="round_sharded_cohort", sharded=True,
        cfg_overrides={**hlth, "cohort_sampled": "on"},
        collective_budget={**zero, "psum": 2 * n_leaves + 2},
        hlo_all_reduce_max=2 * n_leaves + 2 + spmd_overhead)
    specs["sharded_rlr_avg_async_hlth"] = CheckSpec(
        name="sharded_rlr_avg_async_hlth", family="round_sharded_async",
        sharded=True, cfg_overrides={**hlth, "agg_mode": "buffered"},
        collective_budget={**zero, "psum": 2 * n_leaves + 2},
        hlo_all_reduce_max=2 * n_leaves + 2 + spmd_overhead)
    specs["sharded_rlr_avg_mt_hlth"] = CheckSpec(
        name="sharded_rlr_avg_mt_hlth", family="round_sharded_mt",
        sharded=True, cfg_overrides={**hlth, "tenants": 2},
        collective_budget={**zero, "psum": 2 * n_leaves + 2},
        hlo_all_reduce_max=2 * n_leaves + 2 + spmd_overhead)

    # in-jit reputation lane (ISSUE 20, obs/reputation.py): per-sampled-
    # client sign-agreement vs the committed vote. The acceptance claim
    # is ZERO added collectives on every dispatch surface at 1/8/16-way:
    # the vmap/tenant paths compute rep_agree as collective-free
    # [m]/[E,m] reductions, the sharded paths re-read the vote's existing
    # sign-sum psums and stitch the sharded [m/d] row through the
    # P(AGENTS_AXIS) out_spec, and the buffered fold compares against the
    # replicated vote the commit already holds. Every `*_rep` twin
    # therefore pins its plain counterpart's budget UNCHANGED; the
    # `_off` twin pins that the A/B arm really removes the lane.
    rep = {"reputation": "on"}
    specs["vmap_rlr_avg_rep"] = CheckSpec(
        name="vmap_rlr_avg_rep", family="round", sharded=False,
        cfg_overrides=dict(rep), collective_budget=dict(zero))
    specs["vmap_rlr_avg_rep_off"] = CheckSpec(
        name="vmap_rlr_avg_rep_off", family="round", sharded=False,
        cfg_overrides={"reputation": "off"},
        collective_budget=dict(zero))
    specs["sharded_rlr_avg_rep"] = CheckSpec(
        name="sharded_rlr_avg_rep", family="round_sharded",
        sharded=True, cfg_overrides=dict(rep),
        collective_budget={**zero, "psum": 2 * n_leaves + 2},
        hlo_all_reduce_max=2 * n_leaves + 2 + spmd_overhead)
    specs["sharded_rlr_sign_rep"] = CheckSpec(
        name="sharded_rlr_sign_rep", family="round_sharded",
        sharded=True,
        cfg_overrides={**rep, "aggr": "sign", "server_lr": 1.0},
        collective_budget={**zero, "psum": n_leaves + 1},
        hlo_all_reduce_max=n_leaves + 1 + spmd_overhead)
    specs["sharded_rlr_avg_cohort_rep"] = CheckSpec(
        name="sharded_rlr_avg_cohort_rep",
        family="round_sharded_cohort", sharded=True,
        cfg_overrides={**rep, "cohort_sampled": "on"},
        collective_budget={**zero, "psum": 2 * n_leaves + 2},
        hlo_all_reduce_max=2 * n_leaves + 2 + spmd_overhead)
    specs["sharded_rlr_avg_async_rep"] = CheckSpec(
        name="sharded_rlr_avg_async_rep", family="round_sharded_async",
        sharded=True, cfg_overrides={**rep, "agg_mode": "buffered"},
        collective_budget={**zero, "psum": 2 * n_leaves + 2},
        hlo_all_reduce_max=2 * n_leaves + 2 + spmd_overhead)
    specs["sharded_rlr_avg_mt_rep"] = CheckSpec(
        name="sharded_rlr_avg_mt_rep", family="round_sharded_mt",
        sharded=True, cfg_overrides={**rep, "tenants": 2},
        collective_budget={**zero, "psum": 2 * n_leaves + 2},
        hlo_all_reduce_max=2 * n_leaves + 2 + spmd_overhead)

    # --diagnostics sharded twin (the coverage pass, analysis/coverage.py,
    # surfaced it as reachable-but-unpinned): the ONLY addition to the
    # plan is one all_gather collecting the per-client loss diagnostics
    # across shards — pinned so a diagnostics refactor cannot silently
    # grow the round program's communication
    specs["sharded_rlr_avg_diag"] = CheckSpec(
        name="sharded_rlr_avg_diag", family="round_sharded_diag",
        sharded=True, cfg_overrides={"diagnostics": True},
        collective_budget={**zero, "psum": 2 * n_leaves + 2,
                           "all_gather": 1},
        hlo_all_reduce_max=2 * n_leaves + 2 + spmd_overhead)
    return specs


def check_specs() -> Dict[str, CheckSpec]:
    """Budgeted family matrix for the current check model (CNN, 8 leaves)."""
    return collective_budgets(n_leaves=8)


# --------------------------------------------------------------------------
# Fingerprint-audit rules (analysis/fingerprint_audit.py)
# --------------------------------------------------------------------------

# Package modules whose cfg.<field> reads shape traced programs (builders
# included: a builder-body read bakes the value into the trace). The
# audit cross-checks every field read here against its provenance tag.
PROGRAM_READ_MODULES = (
    f"{PKG}/fl/",
    f"{PKG}/ops/",
    f"{PKG}/parallel/rounds.py",
    f"{PKG}/faults/",
    f"{PKG}/obs/telemetry.py",
    f"{PKG}/models/",
    # in-program cohort sampling (ISSUE 7): the traced draw reads
    # cohort_seed / num_agents / agents_per_round (+ churn fields via
    # service/churn.py) — all program provenance
    f"{PKG}/data/cohort.py",
    # attack schedule (ISSUE 11): the traced gate reads
    # attack_start/attack_stop/attack_every — program provenance.
    # (attack/registry.py itself is NOT in scope: its stamp_for_agent is
    # the host-side data hook and legitimately reads runtime fields like
    # data_dir; its traced reads — attack, attack_boost — are program-
    # tagged regardless.)
    f"{PKG}/attack/schedule.py",
    f"{PKG}/attack/boost.py",
    f"{PKG}/attack/signflip.py",
    # health lane (ISSUE 14): the traced sentinel reads cfg.health (the
    # lane is a program difference, like telemetry) and cfg.quarantine
    # (a traced membership constant, like churn_seed) — both program
    # provenance. (health/monitor.py is NOT in scope: the host-side
    # policy legitimately reads runtime fields like health_policy and
    # the EMA judgement knobs.)
    f"{PKG}/health/sentinel.py",
)

# Provenance classes (config.FIELD_PROVENANCE values) and their
# fingerprint rule:
#   program  -> MUST be fingerprinted (never in EXCLUDED_FIELDS)
#   shape    -> enters via example-arg avals; fingerprinting is harmless,
#               exclusion is fine when an aval provably pins it
#   data     -> changes dataset CONTENT only, never the program; either way
#   runtime  -> driver/IO knob; MUST be excluded (fingerprinting one
#               causes spurious recompiles — the drift this audit exists
#               to catch)
PROVENANCE_CLASSES = ("program", "shape", "data", "runtime")

# --------------------------------------------------------------------------
# Program-family coverage (analysis/coverage.py)
# --------------------------------------------------------------------------

# How to TURN ON each compile_cache.family_suffix token. The coverage
# pass derives the token list from family_suffix's own AST (never a
# duplicated list); this table only says which config overrides activate
# a token so the lattice can be enumerated through the real planners. A
# token the algebra emits with no driver here fails the gate loudly
# (rule `suffix-unmapped`) — adding a family_suffix branch REQUIRES
# teaching the coverage pass how to reach it.
SUFFIX_DRIVERS: Dict[str, Dict[str, object]] = {
    "_async": {"agg_mode": "buffered"},       # fl/buffered.is_buffered
    "_mt": {"tenants": 2},                    # tenant packs (fl/tenancy)
}

# Reachable families deliberately carrying NO CheckSpec. Every entry
# must say WHY no collective-budget pin is needed — a waiver without a
# reason is a review defect, and a waiver for a family that gains a
# spec (or stops being reachable) is flagged as stale.
_W_CHAINED_VMAP = (
    "vmap chained scan of a collective-free round body: iter_eqns counts "
    "the scan body once, so a spec here would re-pin exactly the round "
    "twin's zero collectives; the family's real contract is the donation "
    "pin (DONATED_FAMILIES + test_chained_families_donate_params)")
_W_VMAP_CROSS = (
    "vmap family — collective-free by construction (no mesh); every "
    "mechanism axis is pinned at zero by its vmap_rlr_avg* "
    "representative, and the suffix cross-terms compose the same "
    "collective-free bodies (the sharded twins of these cross-terms "
    "carry real budgets)")
_W_VMAP_DIAG = (
    "diagnostics adds host-visible per-client outputs to a vmap body — "
    "still collective-free; the sharded diag twin carries the real pin "
    "(sharded_rlr_avg_diag: +1 all_gather)")
_W_EVAL_TWIN = (
    "same traced eval body as the pinned vmap_eval family, on a "
    "different eval set (the _mt pair is that body vmapped over the "
    "tenant axis) — collective-free; a divergence would surface in "
    "vmap_eval's zero pin")
WAIVED_FAMILIES: Dict[str, str] = {
    **{f: _W_CHAINED_VMAP for f in (
        "chained", "chained_async", "chained_async_mt", "chained_cohort",
        "chained_cohort_async", "chained_host", "chained_mt")},
    **{f: _W_VMAP_CROSS for f in (
        "round_cohort_async", "round_cohort_async_mt", "round_host")},
    **{f: _W_VMAP_DIAG for f in (
        "round_diag", "round_cohort_diag", "round_host_diag")},
    **{f: _W_EVAL_TWIN for f in (
        "eval_poison", "eval_val_mt", "eval_poison_mt")},
}

# Program-provenance config fields deliberately absent from run_name.
# Every entry must say why two runs differing only in this field MAY
# share a run dir — the documented escape hatch for the run_name
# collision rule (the PR-3/11/13 bug class made static).
_X_REFERENCE_VOCAB = (
    "the run name reproduces the reference's hyperparameter vocabulary "
    "(src/federated.py:27-31) — the model/data/local-training axes were "
    "never in it; sweeps separate them by --log_dir root (scripts/ "
    "convention) and retro-adding them would orphan every historical "
    "run dir the curve-parity harness keys on")
_X_VALUE_PRESERVING = (
    "value-preserving re-lowering knob: results are bit-identical (or "
    "pinned ulp-equal by the parity tests), so runs differing only in "
    "it are the SAME experiment retuned — sharing the dir is the "
    "resume story, not a collision")
RUN_NAME_EXEMPT: Dict[str, str] = {
    "arch": _X_REFERENCE_VOCAB,
    "data": _X_REFERENCE_VOCAB,
    "dtype": _X_REFERENCE_VOCAB,
    "bs": _X_REFERENCE_VOCAB,
    "local_ep": _X_REFERENCE_VOCAB,
    "client_lr": _X_REFERENCE_VOCAB,
    "client_moment": _X_REFERENCE_VOCAB,
    "agent_chunk": _X_VALUE_PRESERVING,
    "agg_path": _X_VALUE_PRESERVING,
    "lm_config": _X_REFERENCE_VOCAB,
    "lm_layers": _X_REFERENCE_VOCAB,
    "lm_experts_held": _X_REFERENCE_VOCAB,
    "lm_expert_offset": _X_REFERENCE_VOCAB,
    "lm_vocab_held": _X_REFERENCE_VOCAB,
    "remat": _X_VALUE_PRESERVING,
    "remat_policy": _X_VALUE_PRESERVING,
    "debug_nan": (
        "checkify instrumentation only observes — values are identical, "
        "and a debugging rerun must land in the dir of the run it is "
        "debugging"),
    "diagnostics": (
        "the Norms/* and Sign/* research scalars only ADD outputs at snap "
        "rounds; the update math is the plain program's (the engine "
        "builds the plain/diag pair from one config) — a diagnostics "
        "rerun must land in the dir of the run it explains"),
    "telemetry": (
        "telemetry levels change which scalars are computed, never the "
        "model update (the telemetry-off bit-identity contract, pinned "
        "by jaxpr_lint's tripwire) — the metrics stream is "
        "self-describing about its level"),
    "health": (
        "the in-jit sentinel lane only ADDS monitoring reductions; the "
        "update math is untouched (health on/off value parity is a "
        "tier-1 pin) — the lane is observability, not experiment "
        "identity (quarantine, which DOES change results, is in the "
        "name)"),
    "tenants": (
        "the pack width is a scheduling decision: per-tenant metrics "
        "land under each tenant's OWN run_name (service/tenancy), and "
        "pack-vs-standalone parity is the acceptance contract — the "
        "same cell must resolve to the same dir either way"),
    "reputation": (
        "the in-jit agreement lane only ADDS monitoring reductions; the "
        "update math is untouched (--reputation off bit-identity is a "
        "tier-1 pin, the health precedent) — the lane is observability, "
        "not experiment identity, and the tracker it feeds is "
        "observe-only by contract"),
}
