"""Compile persistence + ahead-of-time (AOT) executable banking.

The flagship configs run hundreds of FL rounds per experiment row, yet a
process without persistence pays the full XLA compile cost again (tens
of seconds per TPU program family). FedJAX (arXiv:2108.02117) treats
cached compilation of the round program as a first-class requirement for
FL-simulation throughput; this module is that requirement, in two layers:

1. **Persistent XLA cache** (`enable_persistent_cache`): JAX's
   `jax_compilation_cache_dir`, so every `jit` compilation — including
   ones this module never sees — warm-starts from disk across processes.
2. **Executable bank** (`AotBank`): `lower().compile()` each program family
   the run will use ahead of time and serialize the *executable itself*
   (`jax.experimental.serialize_executable`), keyed by a fingerprint of
   (config, package source, jax version, backend, topology, arg shapes).
   A warm start deserializes the banked executable and skips XLA entirely
   — no trace, no lowering, no compile. `scripts/precompile.py` banks all
   families of the bench configs offline.

Where the caches live (`cache_root`): `$JAX_COMPILATION_CACHE_DIR` when the
machine sets it — XLA's cache is then that directory exactly (JAX reads
the variable itself; this module issues no `jax_compilation_cache_dir`
update) and the bank is its `aot/` subdirectory. Otherwise
`--compile_cache_dir`, otherwise `CHECKOUT_CACHE_ROOT`, a git-ignored
directory of the checkout; under those two XLA's cache is `<root>/xla`
and the bank `<root>/aot`. The path is part of XLA's cache key, so none
of them moves between runs.

Program families (the manifest vocabulary; see `plan_programs`):

    round / round_diag      device-resident per-round fn (fl/rounds.py)
    chained                 device-resident lax.scan round block
    round_host[_diag]       host-sampled per-round fn
    chained_host            host-sampled chained block
    round_cohort[_diag] /   cohort-sampled population path (ISSUE 7):
    chained_cohort /        in-program seeded cohort over the client
    round_sharded_cohort    bank (data/bank.py + data/cohort.py)
    round_sharded /         shard_map variants (parallel/rounds.py) —
    chained_sharded         adopted at runtime, banked best-effort;
                            the analysis passes plan them per topology
                            through `plan_sharded_programs`
    eval_val / eval_poison  the two eval-set program instances

Every entry is a pair of files in `<root>/aot/`: `<family>-<fp>.jex`
(pickled serialized executable + arg pytree defs + the ids of the devices
it was compiled for) and a `<family>-<fp>.json` sidecar (the manifest
record: fingerprint inputs, compile seconds, backend). Per-entry files make
concurrent writers safe without locking — the manifest IS the directory. A
changed config, source file, jax version, backend, topology or arg shape
changes the fingerprint, so stale executables are never loaded; they are
simply dead files.

Failure policy: every load path degrades to the plain jit path with a log
line — a corrupt or version-skewed bank can cost a recompile, never a run.
"""

from __future__ import annotations

import dataclasses
import functools
import hashlib
import json
import os
import pickle
import time
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

# config fields that do not change the compiled program (pure IO/driver
# knobs). `snap`/`rounds`/`seed`/`chain` only alter which/how many
# dispatches run; shapes (which DO change programs, e.g. the chained
# block's round_ids length) enter the fingerprint through the
# example-argument avals instead. This set is audited against
# config.FIELD_PROVENANCE by analysis/fingerprint_audit.py: every
# `runtime` field must be here, no `program` field may be — drift in
# either direction fails the static-analysis CI gate.
EXCLUDED_FIELDS = frozenset({
    "data_dir", "log_dir", "checkpoint_dir", "resume", "profile_dir",
    "tensorboard", "rounds", "snap", "seed", "chain", "host_prefetch",
    "compile_cache", "compile_cache_dir", "async_metrics",
    # obs/: spans + heartbeat are host-side IO; `telemetry` is NOT here —
    # it adds outputs to the traced program, so it must key the cache
    "spans", "heartbeat", "status_file",
    # fleet observability (ISSUE 15): ledger + exporter are host-side IO
    "events", "metrics_port", "metrics_textfile",
    # forensics (ISSUE 18): flight recorder + profile trigger are
    # host-side IO around the dispatch loop — neither shapes a program
    "flight", "trigger_profile",
    # fingerprint-drift fixes (ISSUE 4 audit): runtime-only fields that
    # used to split identical programs across cache keys. `platform`
    # (backend is fingerprinted directly), the multihost rendezvous
    # triplet (process/device counts are fingerprinted), `top_frac`
    # (host-side Sign/* set algebra), `rng_impl` (the RESOLVED impl keys
    # via jax_default_prng_impl — the unresolved 'auto' string must not
    # split from 'rbg' on TPU), `mesh` (sharded families are never
    # banked; eval/vmap programs are mesh-independent and should share),
    # `host_sampled` (family names already key the fingerprint).
    "platform", "coordinator", "num_processes", "process_id", "top_frac",
    "rng_impl", "mesh", "host_sampled",
    # sampled profiler window (obs/attribution.py): observation only
    "profile_rounds",
    # continuous-service driver knobs (service/): retry policy, streaming
    # budget, checkpoint retention and chaos injection are all host-side —
    # none shapes a traced program (churn_* fields by contrast DO and are
    # fingerprinted)
    "service_rounds", "service_retries", "service_backoff_s",
    "service_deadline_s", "service_keep_ckpts", "chaos",
    # health lane (ISSUE 14): the incident POLICY and its EMA judgement
    # knobs are host-side (health/monitor.py) and bank verification is
    # open-time IO — none shapes a traced program (`health` and
    # `quarantine` by contrast DO and are fingerprinted)
    "health_policy", "health_z_threshold", "health_spike_factor",
    "bank_verify",
    # population axis (ISSUE 7): `cohort_sampled` selects the cohort
    # program families (names key the fingerprint, like host_sampled);
    # bank storage location / IO shard layout / build parallelism never
    # shape a program (cohort_seed/cohort_size and the partitioner
    # fields by contrast DO shape programs or data and are
    # fingerprinted; the traffic_* fields are traced and stay in)
    "cohort_sampled", "bank_dir", "bank_shard_clients",
    "bank_build_workers",
    # online RLR-threshold adaptation (attack/adapt.py): a host-side
    # service policy — it ACTS by rebuilding programs with a different
    # robustLR_threshold (which is fingerprinted), never by changing a
    # trace itself. The attack/attack_* strategy fields by contrast ARE
    # traced (attack/registry.py update hook + schedule) and stay in the
    # fingerprint.
    "rlr_adapt", "rlr_adapt_every",
    # defense provenance plane (ISSUE 20): the host tracker's
    # representation knobs and the health ladder's promoted anomaly
    # thresholds are never read in a trace (`reputation` by contrast
    # selects whether the rep_* lanes are compiled in and stays in the
    # fingerprint, the `telemetry` rule)
    "rep_population_cap", "rep_topk", "rep_streak",
    "defense_flip_frac_hi", "defense_low_margin_hi",
})

# families built from cfg.replace(diagnostics=False) in the driver; their
# fingerprints normalize diagnostics off so a --diagnostics run still hits
# the same banked non-diag executables
_DIAG_FAMILIES = frozenset({"round_diag", "round_host_diag",
                            "round_sharded_diag"})

# the machine's own choice of XLA cache directory; JAX reads it at import
CACHE_DIR_ENV = "JAX_COMPILATION_CACHE_DIR"

_PACKAGE_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# the default root: one fixed, git-ignored directory of the checkout (the
# path keys XLA's cache, so it must not move between runs)
CHECKOUT_CACHE_ROOT = os.path.join(os.path.dirname(_PACKAGE_DIR),
                                   ".compile_cache")

# above this many stacked-array bytes the driver switches to host-side
# per-round shard gathering (the fedemnist path; train.py re-exports this)
DEVICE_RESIDENT_BYTES = 2 << 30


def cache_root(cfg=None) -> str:
    """Resolve the cache root: $JAX_COMPILATION_CACHE_DIR when set, else
    --compile_cache_dir, else CHECKOUT_CACHE_ROOT (see module docstring)."""
    env = os.environ.get(CACHE_DIR_ENV, "")
    if env:
        return env
    root = getattr(cfg, "compile_cache_dir", "") or ""
    return os.path.expanduser(root) if root else CHECKOUT_CACHE_ROOT


def _reset_jax_cache_state() -> None:
    """jax's persistent-cache module initializes AT MOST ONCE per process:
    after any compile, a later `jax_compilation_cache_dir` update is
    silently ignored. Reset to pristine so the next compile re-initializes
    against the current config. XLA:CPU only — the CPU-only bank-miss
    toggle (`AotBank.get_or_compile`) and a process that switches cache
    roots after its first compile (the test suite) need it; a TPU process
    sets the directory once, before its first compile."""
    if jax.default_backend() != "cpu":
        return
    from jax._src import compilation_cache as jax_cc
    jax_cc.reset_cache()


def enable_persistent_cache(cfg=None) -> str:
    """Turn on JAX's persistent compilation cache under `cache_root(cfg)`
    and return XLA's cache directory. Under $JAX_COMPILATION_CACHE_DIR JAX
    has already read the variable: the directory is used as it stands and
    never re-set in code.

    Thresholds are zeroed so every program family persists (the default
    1s/min-size gates would skip the small eval programs, which every
    process would then recompile). Safe to call more than once."""
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    root = cache_root(cfg)
    if os.environ.get(CACHE_DIR_ENV, ""):
        return root
    xla_dir = os.path.join(root, "xla")
    os.makedirs(xla_dir, exist_ok=True)
    if jax.config.jax_compilation_cache_dir != xla_dir:
        jax.config.update("jax_compilation_cache_dir", xla_dir)
        _reset_jax_cache_state()
    return xla_dir


@functools.lru_cache(maxsize=None)
def source_digest(package_dir: str = _PACKAGE_DIR) -> str:
    """sha256 over the package's .py files as they are on disk (relative
    path + bytes, sorted). Read from disk, not from git: a chip copy of
    the checkout is not a repository. Memoized — a process runs the code
    it imported, not later edits."""
    h = hashlib.sha256()
    for dirpath, dirnames, filenames in os.walk(package_dir):
        dirnames.sort()
        for name in sorted(filenames):
            if name.endswith(".py"):
                path = os.path.join(dirpath, name)
                h.update(os.path.relpath(path, package_dir).encode())
                with open(path, "rb") as f:
                    h.update(f.read())
    return h.hexdigest()


def abstractify(tree):
    """Pytree of arrays -> matching ShapeDtypeStructs (already-abstract
    leaves pass through), for zero-materialization `lower()` calls."""
    return jax.tree_util.tree_map(
        lambda x: x if isinstance(x, jax.ShapeDtypeStruct)
        else jax.ShapeDtypeStruct(jnp.shape(x), jnp.result_type(x)), tree)


def _arg_shapes(example_args) -> List[Tuple[str, str]]:
    return [(str(tuple(l.shape)), str(l.dtype))
            for l in jax.tree_util.tree_leaves(abstractify(example_args))]


# `--remat_policy auto`: the tagged convolution outputs of every example in
# flight may take this share of what the device has free, and no more.
# Beside them the program holds what `block` holds anyway. On the chip
# (PERF.md section 6, PR 25; ResNet-9, f32, 16.91 GB limit) the `conv`
# round took 1.95x the kept bytes of what was free at 10 agents of 256 a
# chunk (3.86 GB kept, peak 8.75 GB) and 1.83x at 20 (7.72 GB kept, peak
# 15.39 GB); XLA's memory analysis reads 2.3x for bf16. At a third the
# `conv` program therefore needs 0.65 (f32) to 0.77 (bf16) of what is
# free at the threshold. That errs towards `block`: chunks of 20 in f32 resolve
# `block` (13.42 GB, 0.2365 rounds/s) where `conv` forced by hand still
# fit, 1.5 GB under the limit, and ran 0.2566.
REMAT_CONV_SHARE_DIVISOR = 3
# The rung above it (ISSUE 30): nothing is recomputed where this multiple
# of the same bytes fits what is free. On the chip (PERF.md section 6,
# PR 30; the benchmark's shape, f32, ten agents of 256 at once, 3.86 GB of
# tagged outputs) the round with nothing recomputed reserved 9.51 GB where
# `conv` reserved 8.34, which with 0.40 GB in use beside the program is
# PR 25's reading by hand, a peak of 9.91 GB against 8.75: 2.25x the
# tagged bytes of what was free where `conv` took 1.95x. Per chip on four
# it reserved 7.83 GB against 6.67. At 3.8 it needs 0.59 of what is free at
# the threshold, under `conv`'s 0.65 at its own; the cells ask 14.66 GB of
# 15.67 free (16.46 a chip on four), so a GB more resident does not flip
# them. XLA's memory analysis for a described v5e (arguments + outputs +
# temporaries, GB of 16.91; it read 1.6 GB over the chip at the cell's
# shape) at the shapes no cell visits, none / conv / block:
#   f32  chunk 10  11.41 / 10.14 /  9.13   resolves none
#   f32  chunk 20  17.23 / 17.34 / 15.32   block (3 x 7.72 GB kept)
#   f32  chunk 40  refused at 18.59G / 28.61G / 16.10G of 15.75G: block
#   bf16 chunk 10   7.73 /  7.48           none
#   bf16 chunk 20  12.62 / 11.86           none (3.86 GB kept)
#   bf16 chunk 40  16.86 / 18.07 / 15.75   block
#   f32  tenants 2 19.15 / 19.17 / 16.46   block
#   bf16 tenants 2 18.76 / 13.22           conv: 3.8 x 3.86 = 14.66 GB of
#                                          14.62 free
# The last row sets the constant. A packed program keeps about 1.15 MB an
# example more than an unpacked one when nothing is recomputed (f32, two
# tenants of five agents: 14.32 against 11.41), 4.3x its tagged bytes in
# bf16: at 3.5, where the unpacked f32 round would already sit under
# `conv`'s share (2.64x by the analysis, 0.75 of what is free), the packed
# bf16 row resolved `none` and did not fit. 3.8 is the least that turns it
# down and the most that leaves the cells a GB. It does not cover every
# pack: at 4.3x, a packed bf16 shape just inside the threshold would ask
# for more than is free (PERF.md section 7; no cell packs).
REMAT_NONE_SHARE_DIVISOR = 3.8


@dataclasses.dataclass(frozen=True)
class RematChoice:
    """What `resolved_remat` settled, and on what."""
    policy: str                  # "block" | "conv" | "none": what
    #                              get_model receives
    saved_bytes: int             # conv outputs `conv` keeps on one device:
    #                              the rule's input under every policy
    limit_bytes: Optional[int]   # the device's limit less what is resident
    #                              there; None: the backend reports none
    chosen: bool = False         # by the rule, not by the user

    def describe(self) -> str:
        held = ("no memory limit reported by this backend"
                if self.limit_bytes is None else
                f"{self.limit_bytes / 1e9:.2f} GB free of the device's "
                f"limit (none where {REMAT_NONE_SHARE_DIVISOR:g}x fit "
                f"it, conv where {REMAT_CONV_SHARE_DIVISOR:g}x, else "
                f"block)")
        does = {"block": "every block is recomputed in backward",
                "conv": "the convolution outputs are kept, the "
                        "elementwise tail is recomputed",
                "none": "nothing is recomputed"}[self.policy]
        return (f"remat policy {self.policy} "
                f"({'auto' if self.chosen else 'as asked'}): {does}; the "
                f"convolution outputs of the examples in flight take "
                f"{self.saved_bytes / 1e9:.2f} GB; {held}")


def device_memory_limit() -> Optional[int]:
    """`bytes_limit` of this process's first device, or None where the
    backend keeps no such count (XLA:CPU)."""
    stats = jax.local_devices()[0].memory_stats()
    return stats.get("bytes_limit") if stats else None


def remat_policy_for(bytes_per_example: int, examples_in_flight: int,
                     free_bytes: Optional[int]) -> str:
    """The rule of `--remat_policy auto`, on numbers alone: a ladder over
    the convolution outputs of the examples in flight, held against
    `free_bytes`, the device's limit less what is resident. `none` where
    the whole backward's activations fit beside them
    (`REMAT_NONE_SHARE_DIVISOR` x their bytes), else `conv` where they
    fit themselves (`REMAT_CONV_SHARE_DIVISOR` x), else `block`. A backend
    that reports no limit (None) gets `block`: nothing there says they
    fit."""
    if free_bytes is None:
        return "block"
    saved = bytes_per_example * examples_in_flight
    if REMAT_NONE_SHARE_DIVISOR * saved <= free_bytes:
        return "none"
    if REMAT_CONV_SHARE_DIVISOR * saved <= free_bytes:
        return "conv"
    return "block"


@functools.lru_cache(maxsize=32)
def _remat_shapes(data: str, arch: str, dtype: str,
                  image_shape: Tuple[int, ...]) -> Tuple[int, int]:
    """(conv_out bytes per example, parameter count) of the model."""
    from defending_against_backdoors_with_robust_learning_rate_tpu.models import (
        registry)
    model = registry.get_model(data, arch, dtype)
    return (registry.named_activation_bytes(model, image_shape),
            registry.param_count(
                registry.abstract_params(model, image_shape)))


def resolved_remat(cfg, fed=None,
                   threshold: Optional[int] = None) -> RematChoice:
    """Single source of what the backward pass recomputes under `--remat`
    (ISSUE 25, ISSUE 30). `--remat_policy block|conv|none` is honoured;
    `auto` recomputes nothing (`none`) when the whole backward's
    activations fit the device, keeps the convolution outputs and
    recomputes the elementwise tail (`conv`) when only those fit, and
    recomputes whole blocks (`block`) when they do not, from what the
    program can observe without compiling anything:

    - bytes of the tagged outputs per example, from the model's own
      shapes in `--dtype`;
    - examples in flight on one device: the agents it trains at once
      (`agent_chunk`, else the device's share of the sampled agents under
      `--mesh`, by the driver's blocking policy) x `bs` x the tenants of a
      packed program;
    - the device's `bytes_limit` less what is resident beside the
      activations: the [agents, n_params] float32 update stack, and the
      dataset `fed` where the run places it on the device (a host- or
      cohort-sampled run keeps it on the host: `is_host_mode`,
      `is_cohort_mode`, with the driver's `threshold`).

    Every builder of a model (the engine, the pack engine, precompile,
    bench, the jaxpr lint) resolves through here with its `fed` and writes
    the policy back into its cfg, so that `get_model` and the bank's
    fingerprint see `block`, `conv` or `none`, never `auto`; `fingerprint`
    resolves a cfg still carrying `auto` through here as well, so that
    `auto` is never a key of its own. Without `--remat` the policy selects
    nothing and reads `block`."""
    from defending_against_backdoors_with_robust_learning_rate_tpu.models.registry import (
        REMAT_POLICIES)
    from defending_against_backdoors_with_robust_learning_rate_tpu.parallel.mesh import (
        agent_mesh_size)
    if cfg.remat_policy != "auto" and cfg.remat_policy not in REMAT_POLICIES:
        raise ValueError(
            f"remat_policy must be 'auto' or one of {REMAT_POLICIES}, got "
            f"{cfg.remat_policy!r}")
    if not cfg.remat or cfg.data == "tokens":
        # the token model tags no tensor: under --remat it recomputes block
        # by block, whatever the device has free
        return RematChoice("block", 0, None)
    per_example, n_params = _remat_shapes(
        cfg.data, cfg.model_arch, cfg.dtype, tuple(cfg.image_shape))
    tenants = max(1, cfg.tenants)
    cohort = is_cohort_mode(cfg, fed, threshold)
    agents = cfg.agents_per_round
    if cfg.mesh != 1 and not (cohort and cfg.tenants > 0):
        # a cohort pack has no sharded family and trains on one device
        agents //= agent_mesh_size(cfg.mesh, agents)
    at_once = cfg.agent_chunk if 0 < cfg.agent_chunk < agents else agents
    in_flight = at_once * cfg.bs * tenants
    limit = device_memory_limit()
    if limit is not None:
        limit -= 4 * n_params * agents * tenants
        if not (fed is None or cohort or is_host_mode(cfg, fed, threshold)):
            limit -= fed.nbytes
    chosen = cfg.remat_policy == "auto"
    policy = (remat_policy_for(per_example, in_flight, limit) if chosen
              else cfg.remat_policy)
    return RematChoice(policy, per_example * in_flight, limit, chosen)


# The round folds where the update stack would take more than this share of
# what the device has free beside one chunk of clients in training. A
# stack that takes half of it leaves the other half to the step's
# activations; `cifar-resnet9` (1.05 GB of 16 GB free) is far below it, a
# language model's stack (20 GB at 508M parameters and ten clients) far
# above.
AGG_STACK_SHARE_DIVISOR = 2
AGG_PATHS = ("stack", "fold")


@dataclasses.dataclass(frozen=True)
class AggChoice:
    """What `resolved_agg` settled, and on what."""
    path: str                    # "stack" | "fold"
    stack_bytes: int             # the [m, n_params] float32 update stack
    limit_bytes: Optional[int]   # the device's limit less what a step
    #                              holds; None: the backend reports none
    chosen: bool = False         # by the rule, not by the caller

    def describe(self) -> str:
        held = ("no memory limit reported by this backend"
                if self.limit_bytes is None else
                f"{self.limit_bytes / 1e9:.2f} GB free of the device's "
                f"limit, a 1/{AGG_STACK_SHARE_DIVISOR} share of it allowed")
        return (f"aggregation path {self.path} "
                f"({'auto' if self.chosen else 'as asked'}): the update "
                f"stack would take {self.stack_bytes / 1e9:.2f} GB; {held}")


def agg_path_for(stack_bytes: int, free_bytes: Optional[int]) -> str:
    """The rule on numbers alone: `fold` when the stack does not fit its
    share of `free_bytes`; a backend that reports no limit (None) keeps the
    stack, as it always did."""
    if free_bytes is None:
        return "stack"
    return ("stack" if AGG_STACK_SHARE_DIVISOR * stack_bytes <= free_bytes
            else "fold")


def resolved_agg(cfg, n_params: int) -> AggChoice:
    """Single source of whether a round holds the `[m, n_params]` update
    stack or folds each chunk of clients into the vote as it arrives
    (ROADMAP R3), from what the program can observe, as `resolved_remat`
    does: the stack's bytes against the device's `bytes_limit` less what a
    client step holds beside it (the global parameters, and parameters,
    gradient and momentum of each client trained at once). `cfg.agg_path`
    `stack` or `fold` is honoured (it has no flag: the engine writes the
    resolved path back, tests and the static gate's specs set it). `auto`
    never picks a fold the configuration could not run (`unsupported`):
    such a round keeps the stack and fits or fails as before."""
    if cfg.agg_path not in ("auto",) + AGG_PATHS:
        raise ValueError(f"agg_path must be 'auto' or one of {AGG_PATHS}, "
                         f"got {cfg.agg_path!r}")
    agents = cfg.agents_per_round
    stack = 4 * n_params * agents
    if cfg.agg_path != "auto":
        return AggChoice(cfg.agg_path, stack, None)
    limit = device_memory_limit()
    if limit is None or unsupported(cfg.replace(agg_path="fold"),
                                    folded=True):
        return AggChoice("stack", stack, limit, True)
    at_once = cfg.agent_chunk if 0 < cfg.agent_chunk < agents else agents
    free = limit - 4 * n_params * (1 + 3 * at_once)
    return AggChoice(agg_path_for(stack, free), stack, free, True)


def unsupported(cfg, folded: bool) -> List[str]:
    """One sentence for each thing `cfg` asks for that the token task or a
    folded round cannot do; empty where all of it runs. The engine raises
    on the first entry before it builds anything."""
    from defending_against_backdoors_with_robust_learning_rate_tpu.fl import (
        buffered, task)
    from defending_against_backdoors_with_robust_learning_rate_tpu.models import (
        registry as models)
    tokens = task.is_tokens(cfg)
    token_model = models.arch_takes_tokens(cfg.model_arch)
    if token_model and not tokens:
        return [f"--arch={cfg.model_arch} takes token ids: pass "
                f"--data=tokens."]
    if not (tokens or folded):
        return []
    who = "the token task (--data=tokens)" if tokens else "a folded round"
    fold = "a folded round"
    rules = [
        (tokens and not token_model, who,
         f"trains a token model: pass --arch={'|'.join(models.TOKEN_ARCHS)}."),
        (cfg.mesh != 1, who,
         "does not run under --mesh: the sharded body (parallel/rounds.py) "
         "takes the update stack and image shards."),
        (cfg.chain > 1, who,
         "is dispatched a round at a time: drop --chain (the chained scan "
         "carries the stack's per-round lanes)."),
        (cfg.host_sampled == "on" or is_cohort_mode(cfg), who,
         "runs device-resident only: the host-sampled and cohort surfaces "
         "gather image rows (--host_sampled off, no --cohort_size)."),
        (cfg.tenants > 0, who,
         "has no tenant-packed family: drop --tenants."),
        (buffered.is_buffered(cfg), who,
         "does not buffer: use --agg_mode sync."),
        (cfg.diagnostics, who,
         "writes no --diagnostics (they need the learning-rate vector and "
         "every client's norms against it)."),
        (folded and cfg.aggr not in task.FOLD_RULES, fold,
         f"sums clients as they arrive, and --aggr={cfg.aggr} needs every "
         f"update at once (comed, trmean, krum and rfa keep the stack)."),
        (folded and cfg.telemetry != "off", fold,
         "never holds the updates beside the committed vote: --telemetry's "
         "per-client rows need the stack."),
        (folded and cfg.reputation == "on", fold,
         "never holds the updates beside the committed vote: --reputation "
         "on needs the stack (auto resolves off)."),
        (folded and (cfg.faults_enabled or cfg.churn_enabled
                     or cfg.traffic_enabled or bool(cfg.quarantine)), fold,
         "has no participation mask yet: faults, churn, diurnal traffic "
         "and --quarantine ride the stacked round."),
    ]
    return [f"{subject} {sentence}" for cond, subject, sentence in rules
            if cond]


def family_suffix(cfg) -> str:
    """Program-family name suffix for the aggregation mode + tenancy:
    buffered-async families (`round_async`, ..., fl/buffered.py) and
    tenant-pack families (`round_mt`, ..., fl/tenancy.py) are DISTINCT
    programs with distinct names — and they compose (`round_async_mt`)
    — so manifests, contracts and driver logs never conflate them."""
    from defending_against_backdoors_with_robust_learning_rate_tpu.fl import (
        buffered)
    sfx = "_async" if buffered.is_buffered(cfg) else ""
    if getattr(cfg, "tenants", 0) > 0:
        sfx += "_mt"
    return sfx


def carry_aval(cfg, params_aval, sharded: bool = False):
    """The round program's lead-argument aval: bare params (sync), or the
    (params, buffer-state) carry (buffered mode, fl/buffered.py). The
    ``sharded`` flag mirrors the per-bin telemetry layout decision — the
    vmap paths carry the per-staleness accumulators under full telemetry,
    the sharded paths degrade that split and carry none."""
    from defending_against_backdoors_with_robust_learning_rate_tpu.fl import (
        buffered)
    if not buffered.is_buffered(cfg):
        return params_aval
    return (params_aval,
            buffered.state_avals(cfg, params_aval, per_bin=not sharded))


def fingerprint(cfg, family: str, example_args) -> str:
    """Cache key for one program family: config fields that shape the
    program + package source digest + jax version + backend + topology +
    PRNG impl + arg avals. Any mismatch is a different key — stale
    executables can't load."""
    fields = dataclasses.asdict(cfg)
    for name in EXCLUDED_FIELDS:
        fields.pop(name, None)
    if family not in _DIAG_FAMILIES:
        fields["diagnostics"] = False
    # the RESOLVED remat policy: `auto` shares the key of what it
    # resolves to, and is never a key of its own
    fields["remat_policy"] = resolved_remat(cfg).policy
    # and the RESOLVED aggregation path, from the parameters the program
    # takes (its lead argument; in buffered mode the carry's first half):
    # the engine writes `stack` or `fold` into its cfg, a planner may leave
    # `auto`, and both must ask for the same key
    from defending_against_backdoors_with_robust_learning_rate_tpu.fl import (
        buffered)
    lead = example_args[0] if example_args else ()
    if buffered.is_buffered(cfg) and isinstance(lead, tuple):
        lead = lead[0]
    fields["agg_path"] = resolved_agg(cfg, sum(
        int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(lead))).path
    if fields.get("tenants", 0) > 0:
        # tenant packs (fl/tenancy.py): the per-tenant scalar knobs are
        # traced [E]-vector ARGUMENTS of the *_mt programs, so their
        # config values must not split the cache — normalize them to the
        # canonical rep. The one structural bit a knob carries (is the
        # RLR vote built at all) survives as threshold 0/1.
        fields.update(
            server_lr=1.0,
            robustLR_threshold=1 if fields["robustLR_threshold"] > 0 else 0,
            attack_boost=1.0, attack_start=0, attack_stop=0,
            attack_every=1)
    meta = {
        "family": family,
        "cfg": {k: repr(v) for k, v in sorted(fields.items())},
        # the config cannot see an edit to the code that builds the
        # program: without this an entry banked by the parent commit
        # would be served to the change
        "source": source_digest(),
        "jax": jax.__version__,
        "backend": jax.default_backend(),
        "device_count": jax.device_count(),
        "process_count": jax.process_count(),
        "prng_impl": str(jax.config.jax_default_prng_impl),
        # compilation-shaping global config: the test harness runs at
        # matmul precision 'highest' while production runs at default —
        # same Config, different compiled math; they must not collide
        "matmul_precision": str(jax.config.jax_default_matmul_precision),
        "x64": bool(jax.config.jax_enable_x64),
        "arg_shapes": _arg_shapes(example_args),
    }
    blob = json.dumps(meta, sort_keys=True).encode()
    return hashlib.sha256(blob).hexdigest()[:20]


def tenant_pack_key(cfg) -> str:
    """Shape/program-compatibility key for tenant-pack grouping (ISSUE
    13): two cells may share a tenant pack IFF their keys match. Derived
    from the SAME field algebra as the AOT fingerprint — the config minus
    the runtime knobs (EXCLUDED_FIELDS) minus the per-tenant scalar
    knobs (fl/tenancy.TENANT_KNOB_FIELDS, which become traced
    [E]-vectors) — rather than an ad-hoc key list, so a new
    program-shaping field can never silently mix programs inside one
    pack. One addition on top of the fingerprint fields: the dispatch
    schedule (rounds/snap/chain) — runtime fields for the fingerprint,
    but a pack advances every tenant in lockstep, so cells must agree
    on it. The RLR threshold needs no structural split: a pack with ANY
    defended tenant builds the vote (fl/tenancy.canonical_rep derives
    the bit from its members), and a threshold-0 tenant's vote
    degenerates to +server_lr on every coordinate — arithmetically the
    undefended update. `tenants` itself is dropped — pack width is the
    queue's choice, not the cell's."""
    from defending_against_backdoors_with_robust_learning_rate_tpu.fl.tenancy import (
        TENANT_KNOB_FIELDS)
    fields = dataclasses.asdict(cfg)
    for name in EXCLUDED_FIELDS:
        fields.pop(name, None)
    for name in TENANT_KNOB_FIELDS:
        fields.pop(name, None)
    fields.pop("tenants", None)
    fields["_schedule"] = (cfg.rounds, cfg.snap, cfg.chain)
    meta = {"cfg": {k: repr(v) for k, v in sorted(fields.items())},
            "jax": jax.__version__,
            "backend": jax.default_backend()}
    blob = json.dumps(meta, sort_keys=True).encode()
    return hashlib.sha256(blob).hexdigest()[:16]


def _atomic_write(path: str, data: bytes) -> None:
    tmp = f"{path}.tmp.{os.getpid()}"
    with open(tmp, "wb") as f:
        f.write(data)
    os.replace(tmp, path)


class AotBank:
    """Serialized-executable store under `<root>/aot/`.

    `get_or_compile` is the single entry point: a fingerprint hit
    deserializes and returns the banked executable (no XLA); a miss
    compiles via `lower().compile()` and banks the result for the next
    process. Returns (compiled, cache_hit, seconds, entry)."""

    def __init__(self, root: str):
        self.dir = os.path.join(root, "aot")
        os.makedirs(self.dir, exist_ok=True)

    def _base(self, family: str, fp: str) -> str:
        return os.path.join(self.dir, f"{family}-{fp}")

    def lookup(self, family: str, fp: str) -> Optional[Dict[str, Any]]:
        try:
            with open(self._base(family, fp) + ".json") as f:
                return json.load(f)
        except (OSError, ValueError):
            return None

    def load(self, family: str, fp: str):
        """Deserialize a banked executable, or None (any failure = miss —
        logged, because a silently recompiling bank looks identical to a
        working one from the outside).

        `execution_devices` is pinned to the devices the program was
        compiled for: left to its default it is EVERY device of the
        backend, and a single-device executable reloaded on a host with
        more than one then dies at its first dispatch ("expected ... to
        have N shards")."""
        from jax.experimental import serialize_executable
        try:
            with open(self._base(family, fp) + ".jex", "rb") as f:
                payload, in_tree, out_tree, device_ids = pickle.load(f)
            by_id = {d.id: d for d in jax.devices()}
            return serialize_executable.deserialize_and_load(
                payload, in_tree, out_tree,
                execution_devices=[by_id[i] for i in device_ids])
        except Exception as e:
            print(f"[aot] {family}-{fp}: banked executable unloadable "
                  f"({type(e).__name__}: {e}); recompiling")
            return None

    # growth bound: fingerprint churn (config/jax-version changes) leaves
    # dead entries behind; keep the newest MAX_ENTRIES and reap the rest.
    # Sized above one full tier-1 suite's distinct program families (~64)
    # so a suite run never evicts entries a later test in the same run
    # (or the next run) would hit.
    MAX_ENTRIES = 128

    def _reap(self) -> None:
        entries = sorted(self.entries(), key=lambda e: e.get("created", 0.0))
        for e in entries[:-self.MAX_ENTRIES]:
            for ext in (".jex", ".json"):
                try:
                    os.remove(self._base(e["family"], e["fingerprint"])
                              + ext)
                except OSError:
                    pass

    def save(self, family: str, fp: str, compiled, compile_s: float,
             example_args) -> None:
        from jax.experimental import serialize_executable
        payload, in_tree, out_tree = serialize_executable.serialize(compiled)
        device_ids = [d.id for d in
                      compiled.runtime_executable().local_devices()]
        base = self._base(family, fp)
        _atomic_write(base + ".jex",
                      pickle.dumps((payload, in_tree, out_tree, device_ids)))
        entry = {"family": family, "fingerprint": fp,
                 "jax": jax.__version__,
                 "backend": jax.default_backend(),
                 "device_count": jax.device_count(),
                 "process_count": jax.process_count(),
                 "compile_s": round(compile_s, 2),
                 "created": time.time(),
                 "arg_shapes": _arg_shapes(example_args),
                 "file": os.path.basename(base) + ".jex"}
        _atomic_write(base + ".json",
                      json.dumps(entry, indent=1).encode())
        self._reap()

    def get_or_compile(self, family: str, cfg, jit_obj, example_args):
        """(compiled, cache_hit, seconds, entry). `seconds` is the pure
        executable-acquisition time: deserialize on a hit, trace+lower+
        compile on a miss (first-call execution is NOT included).

        On XLA:CPU the miss path compiles with the persistent XLA cache
        DISABLED: an executable whose compile was SERVED from that cache
        serializes to a payload missing its object code (it loads, then
        fails at first dispatch with "Function ... not found"; reproduced
        under jaxlib 0.9.0) — the bank must hold self-contained
        executables. On every other backend the compile goes through the
        persistent cache like any jit, so a family that cannot be banked
        still lands in XLA's cache. A verify-load after save catches an
        unloadable payload and deletes the broken artifacts."""
        from defending_against_backdoors_with_robust_learning_rate_tpu.obs import (
            events as obs_events)
        fp = fingerprint(cfg, family, example_args)
        entry = self.lookup(family, fp)
        if entry is not None:
            t0 = time.perf_counter()
            compiled = self.load(family, fp)
            if compiled is not None:
                obs_events.emit("aot/hit", family=family)
                return compiled, True, time.perf_counter() - t0, entry
        xla_cache_dir = (jax.config.jax_compilation_cache_dir
                         if jax.default_backend() == "cpu" else None)
        t0 = time.perf_counter()
        try:
            if xla_cache_dir:
                jax.config.update("jax_compilation_cache_dir", None)
                _reset_jax_cache_state()
            compiled = jit_obj.lower(*abstractify(example_args)).compile()
        finally:
            if xla_cache_dir:
                jax.config.update("jax_compilation_cache_dir",
                                  xla_cache_dir)
                _reset_jax_cache_state()
        secs = time.perf_counter() - t0
        try:
            self.save(family, fp, compiled, secs, example_args)
            if self.load(family, fp) is None:
                raise RuntimeError("verify-load of the banked executable "
                                   "failed")
            entry = self.lookup(family, fp)
        except Exception as e:  # unserializable backend: still usable AOT
            for ext in (".jex", ".json"):
                try:
                    os.remove(self._base(family, fp) + ext)
                except OSError:
                    pass
            entry = {"family": family, "fingerprint": fp,
                     "compile_s": round(secs, 2),
                     "unserializable": f"{type(e).__name__}: {e}"}
        obs_events.emit("aot/miss", family=family)
        return compiled, False, secs, entry

    def entries(self) -> List[Dict[str, Any]]:
        out = []
        for name in sorted(os.listdir(self.dir)):
            if name.endswith(".json"):
                try:
                    with open(os.path.join(self.dir, name)) as f:
                        out.append(json.load(f))
                except (OSError, ValueError):
                    continue
        return out


def adopt(bank, cfg, family, jit_obj, example_args):
    """Swap a jitted program for its banked (or freshly banked) AOT
    executable. Returns (Compiled, acquisition seconds), or (None, 0.0)
    when the bank can't serve this family — the caller keeps the plain jit
    path, which still warm-starts through the persistent XLA cache. The
    log line says what actually happened: a family that compiled but
    could not be banked must not read like one that was."""
    if bank is None:
        return None, 0.0
    from defending_against_backdoors_with_robust_learning_rate_tpu.obs import (
        spans)
    try:
        with spans.span(spans.ADOPT_PREFIX + family) as sp:
            compiled, hit, secs, entry = bank.get_or_compile(
                family, cfg, jit_obj, example_args)
    except Exception as e:
        print(f"[aot] {family}: falling back to jit "
              f"({type(e).__name__}: {e})")
        return None, 0.0
    # a bank miss compiles through XLA's persistent cache where the backend
    # allows it: the tracer's compile listener saw which it was
    served = sp is not None and any(src == "xla_cache_hit"
                                    for _p, src, _s in sp.acquired)
    spans.count(spans.PROGRAMS_COUNTER, family=family,
                source=("bank_hit" if hit else
                        "xla_cache_hit" if served else "compiled"))
    if hit:
        how = "loaded from cache"
    elif "unserializable" in entry:
        how = f"compiled, NOT banked ({entry['unserializable']})"
    else:
        how = "compiled+banked"
    print(f"[aot] {family}: {how} in {secs:.1f}s")
    return compiled, secs


def setup(cfg):
    """Driver/bench entry: enable the persistent XLA cache and return the
    executable bank, or None when --no_compile_cache (or --debug_nan —
    checkify-wrapped fns are not plain jits and AOT would bypass them)."""
    if not getattr(cfg, "compile_cache", True):
        return None
    enable_persistent_cache(cfg)
    if getattr(cfg, "debug_nan", False):
        return None
    return AotBank(cache_root(cfg))


def chain_budget(cfg, host_mode: bool = False, cohort: bool = False) -> int:
    """Rounds fused per dispatch — the driver's exact budget: capped at
    `snap` (minus the unchained diagnostic snap round), and 1 in
    host-sampled mode under faults OR an in-jit attack strategy
    (per-round corrupt flags ride each dispatch; train.py prints the
    reason). Cohort-sampled mode keeps its chain under both: the scanned
    round index re-derives the flags in-program
    (fl/rounds.make_cohort_step)."""
    from defending_against_backdoors_with_robust_learning_rate_tpu.attack import (
        registry as attack_registry)
    n = max(1, min(cfg.chain, cfg.snap - (1 if cfg.diagnostics else 0)))
    if (host_mode and not cohort
            and (cfg.faults_enabled or attack_registry.in_jit(cfg))):
        return 1
    return n


def is_host_mode(cfg, fed, threshold: Optional[int] = None) -> bool:
    """Single source of the driver's host-sampled decision — the
    precompile planner and train.run must agree on which program families
    a config dispatches. `threshold` lets the driver pass its own
    (monkeypatchable) byte budget."""
    if threshold is None:
        threshold = DEVICE_RESIDENT_BYTES
    return (cfg.host_sampled == "on"
            or (cfg.host_sampled == "auto"
                and fed.train.images.nbytes > threshold))


# populations at or above this auto-select the cohort-sampled path: a
# dense [K, max_n, ...] stack at 4096+ clients is already the wrong
# layout, and the paper-scale configs (K <= 40, fedemnist 3383) stay on
# their historical bit-exact paths
COHORT_AUTO_MIN_POPULATION = 4096


def is_cohort_mode(cfg, fed=None, threshold: Optional[int] = None) -> bool:
    """Single source of the driver's cohort-sampled decision (ISSUE 7) —
    train.run, the precompile planner and the jaxpr contracts must agree
    on which program families a config dispatches.

    Without `fed` this is the cfg-only decision (explicit on/off, or the
    auto population threshold) — callable before any data is built, which
    is the point: a 1M-client population must never be materialized
    densely just to decide not to materialize it. With `fed`, a
    host-sampled run under churn ALSO routes to the cohort program
    (cohorts sampled in-program from the churn-present set over the dense
    host stacks) — retiring the host-sampled + churn refusal."""
    if cfg.cohort_sampled == "on":
        return True
    if cfg.cohort_sampled == "off":
        return False
    if cfg.num_agents >= COHORT_AUTO_MIN_POPULATION:
        # auto additionally requires the implied cohort to be samplable
        # AND genuinely smaller than the population: with --cohort_size
        # unset, m = floor(K * agent_frac) can be population-sized — the
        # chunked draw could now sample it, but a population-sized
        # "cohort" is just the dense layout with extra steps, and
        # auto-rerouting it would silently change previously-working
        # dense runs. Such configs stay dense, with a hint printed by
        # the engine; an explicit `on` still wins above.
        from defending_against_backdoors_with_robust_learning_rate_tpu.data import (
            cohort as cohort_mod)
        return (cfg.agents_per_round < cfg.num_agents
                and cohort_mod.cohort_feasible(cfg))
    if fed is not None and (cfg.churn_enabled or cfg.traffic_enabled) \
            and is_host_mode(cfg, fed, threshold):
        # churn/traffic-aware cohorting for host-sampled runs — both
        # presence draws need the sampled client ids, which the
        # host-sampled program never sees. Only when the cohort is
        # actually samplable; the driver refuses loudly otherwise (the
        # PR-6 behavior)
        from defending_against_backdoors_with_robust_learning_rate_tpu.data import (
            cohort as cohort_mod)
        return cohort_mod.cohort_feasible(cfg)
    return False


@dataclasses.dataclass
class ProgramSpec:
    """One program family of a run: the jit object to lower and the
    abstract example arguments that pin its (single) instantiation."""
    family: str
    jit_obj: Any
    example_args: Tuple


def plan_programs(cfg, model, norm, fed,
                  host_mode: Optional[bool] = None) -> List[ProgramSpec]:
    """Enumerate the program families train.run would dispatch for `cfg`
    on a single process (the precompile surface). Mirrors the driver's
    mode selection; the mesh>1 shard_map variants are adopted at runtime
    only (their executables embed the live mesh) and are not planned here.
    """
    from defending_against_backdoors_with_robust_learning_rate_tpu.fl.evaluate import (
        pad_eval_set)
    from defending_against_backdoors_with_robust_learning_rate_tpu.fl.rounds import (
        host_takes_flags, make_chained_cohort_round_fn,
        make_chained_round_fn, make_chained_round_fn_host,
        make_cohort_round_fn, make_round_fn, make_round_fn_host,
        step_takes_round)
    from defending_against_backdoors_with_robust_learning_rate_tpu.models.registry import (
        init_params)

    sfx = family_suffix(cfg)
    cohort_mode = is_cohort_mode(cfg, fed)
    if host_mode is None:
        host_mode = (not cohort_mode) and is_host_mode(cfg, fed)
    from defending_against_backdoors_with_robust_learning_rate_tpu.fl import (
        task)
    params_aval = jax.eval_shape(
        lambda k: init_params(model, task.input_shape(cfg, fed), k),
        jax.random.PRNGKey(0))
    # buffered mode: round programs take the (params, buffer-state)
    # carry as their lead argument; eval programs keep bare params
    # stack or fold, resolved here as the engine resolves it (it hands its
    # cfg over resolved): the programs built below read `cfg.agg_path`
    cfg = cfg.replace(agg_path=resolved_agg(cfg, sum(
        int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(params_aval))
    ).path)
    lead_aval = carry_aval(cfg, params_aval)
    key_aval = abstractify(jax.random.PRNGKey(0))
    data_avals = abstractify((fed.train.images, fed.train.labels,
                              fed.train.sizes))
    chain_n = chain_budget(cfg, host_mode, cohort=cohort_mode)
    ids_aval = jax.ShapeDtypeStruct((chain_n,), jnp.int32)
    plain = cfg.replace(diagnostics=False)
    m = cfg.agents_per_round
    specs: List[ProgramSpec] = []

    if getattr(cfg, "tenants", 0) > 0:
        # tenant-pack families (ISSUE 13, fl/tenancy.py): the experiment
        # axis rides every carried array as a leading [E] dimension; the
        # per-tenant scalar knobs are traced [E]-vector arguments. In
        # buffered mode the stacked lead is the WHOLE (params, buffer
        # state) carry (ISSUE 16 — round_async_mt and friends)
        from defending_against_backdoors_with_robust_learning_rate_tpu.fl import (
            tenancy)
        rep = tenancy.canonical_rep(plain)
        tenancy.check(rep)
        E = rep.tenants
        stackE = functools.partial(
            jax.tree_util.tree_map,
            lambda a: jax.ShapeDtypeStruct((E,) + a.shape, a.dtype))
        pE_aval = stackE(params_aval)
        carryE_aval = stackE(carry_aval(rep, params_aval))
        keysE_aval = jax.ShapeDtypeStruct((E,) + key_aval.shape,
                                          key_aval.dtype)
        rnd_aval = jax.ShapeDtypeStruct((), jnp.int32)
        kavals = tenancy.knob_avals(E)
        if cohort_mode:
            # cohort tenant pack (ISSUE 16 gap 3): shared [m] cohort
            # stacks broadcast across tenants — one bank gather per round
            # serves the whole pack. No chained variant: the engine
            # dispatches cohort packs per-round (the host gather is
            # per-round by construction).
            shard_avals = tuple(
                jax.ShapeDtypeStruct((m,) + a.shape[1:], a.dtype)
                for a in data_avals)
            specs.append(ProgramSpec(
                "round_cohort" + sfx,
                tenancy.make_tenant_cohort_round_fn(rep, model,
                                                    norm).jitted,
                (carryE_aval, keysE_aval, rnd_aval, kavals)
                + shard_avals))
        else:
            specs.append(ProgramSpec(
                "round" + sfx,
                tenancy.make_tenant_round_fn(rep, model, norm,
                                             *data_avals).jitted,
                (carryE_aval, keysE_aval, rnd_aval, kavals) + data_avals))
            if chain_n > 1:
                specs.append(ProgramSpec(
                    "chained" + sfx,
                    tenancy.make_tenant_chained_fn(rep, model, norm,
                                                   *data_avals).jitted,
                    (carryE_aval, keysE_aval, ids_aval, kavals)
                    + data_avals))
        eval_mt = tenancy.make_tenant_eval_fn(model, norm, cfg.n_classes)
        for family, (imgs, lbls) in (
                ("eval_val_mt", (fed.val_images, fed.val_labels)),
                ("eval_poison_mt", (fed.pval_images, fed.pval_labels))):
            eval_avals = abstractify(pad_eval_set(imgs, lbls, cfg.eval_bs))
            specs.append(ProgramSpec(family, eval_mt,
                                     (pE_aval,) + eval_avals))
        return specs

    if cohort_mode:
        # cohort-sampled families (ISSUE 7): data arrives as [m, ...]
        # cohort stacks like host mode, plus the traced round index the
        # in-program sampling consumes (data/cohort.py) — no flag
        # arguments, the program derives them from real client ids
        rnd_aval = jax.ShapeDtypeStruct((), jnp.int32)
        shard_avals = tuple(
            jax.ShapeDtypeStruct((m,) + a.shape[1:], a.dtype)
            for a in data_avals)
        specs.append(ProgramSpec(
            "round_cohort" + sfx,
            make_cohort_round_fn(plain, model, norm),
            (lead_aval, key_aval, rnd_aval) + shard_avals))
        if cfg.diagnostics:
            specs.append(ProgramSpec(
                "round_cohort_diag",
                make_cohort_round_fn(cfg, model, norm),
                (lead_aval, key_aval, rnd_aval) + shard_avals))
        if chain_n > 1:
            block_avals = tuple(
                jax.ShapeDtypeStruct((chain_n,) + a.shape, a.dtype)
                for a in shard_avals)
            specs.append(ProgramSpec(
                "chained_cohort" + sfx,
                make_chained_cohort_round_fn(plain, model, norm),
                (lead_aval, key_aval, ids_aval) + block_avals))
    elif host_mode:
        shard_avals = tuple(
            jax.ShapeDtypeStruct((m,) + a.shape[1:], a.dtype)
            for a in data_avals)
        flags = ((jax.ShapeDtypeStruct((m,), jnp.bool_),)
                 if host_takes_flags(cfg) else ())
        specs.append(ProgramSpec(
            "round_host" + sfx, make_round_fn_host(plain, model, norm),
            (params_aval, key_aval) + shard_avals + flags))
        if cfg.diagnostics:
            specs.append(ProgramSpec(
                "round_host_diag", make_round_fn_host(cfg, model, norm),
                (params_aval, key_aval) + shard_avals + flags))
        if chain_n > 1:
            block_avals = tuple(
                jax.ShapeDtypeStruct((chain_n,) + a.shape, a.dtype)
                for a in shard_avals)
            specs.append(ProgramSpec(
                "chained_host" + sfx,
                make_chained_round_fn_host(plain, model, norm),
                (params_aval, key_aval, ids_aval) + block_avals))
    else:
        # churn — and scheduled-attack — round programs take the round
        # index as a traced int32 scalar (service/churn.py,
        # attack/schedule.py: functions of time, not of the round key;
        # single source fl/rounds.step_takes_round)
        lead = ((jax.ShapeDtypeStruct((), jnp.int32),)
                if step_takes_round(cfg) else ())
        specs.append(ProgramSpec(
            "round" + sfx,
            make_round_fn(plain, model, norm, *data_avals).jitted,
            (lead_aval, key_aval) + lead + data_avals))
        if cfg.diagnostics:
            specs.append(ProgramSpec(
                "round_diag",
                make_round_fn(cfg, model, norm, *data_avals).jitted,
                (lead_aval, key_aval) + lead + data_avals))
        if chain_n > 1:
            specs.append(ProgramSpec(
                "chained" + sfx,
                make_chained_round_fn(plain, model, norm,
                                      *data_avals).jitted,
                (lead_aval, key_aval, ids_aval) + data_avals))

    eval_fn = task.make_eval_fn(model, norm, cfg)
    for family, (imgs, lbls) in (
            ("eval_val", (fed.val_images, fed.val_labels)),
            ("eval_poison", (fed.pval_images, fed.pval_labels))):
        eval_avals = abstractify(pad_eval_set(imgs, lbls, cfg.eval_bs))
        specs.append(ProgramSpec(family, eval_fn,
                                 (params_aval,) + eval_avals))
    return specs


def plan_sharded_programs(cfg, model, norm, fed, mesh,
                          host_mode: bool = False) -> List[ProgramSpec]:
    """Enumerate the shard_map program families for an explicit `mesh`.

    The AOT bank never serves these (their executables embed the live
    mesh; train.run adopts them at runtime), but the static-analysis
    passes (analysis/jaxpr_lint.py) need the exact jit objects + avals the
    driver would dispatch, through the same planner vocabulary — this is
    the lowering hook that keeps the analysis surface and the dispatch
    surface from drifting."""
    from defending_against_backdoors_with_robust_learning_rate_tpu.parallel.rounds import (
        make_sharded_chained_round_fn, make_sharded_cohort_round_fn,
        make_sharded_round_fn, make_sharded_round_fn_host)
    from defending_against_backdoors_with_robust_learning_rate_tpu.fl.rounds import (
        host_takes_flags, step_takes_round)
    from defending_against_backdoors_with_robust_learning_rate_tpu.models.registry import (
        init_params)

    sfx = family_suffix(cfg)
    from defending_against_backdoors_with_robust_learning_rate_tpu.fl import (
        task)
    params_aval = jax.eval_shape(
        lambda k: init_params(model, task.input_shape(cfg, fed), k),
        jax.random.PRNGKey(0))
    # buffered mode: the sharded round programs take the (params,
    # buffer-state) carry — the sharded layout never carries the per-bin
    # telemetry accumulators (fl/buffered.init_state)
    lead_aval = carry_aval(cfg, params_aval, sharded=True)
    key_aval = abstractify(jax.random.PRNGKey(0))
    data_avals = abstractify((fed.train.images, fed.train.labels,
                              fed.train.sizes))
    chain_n = chain_budget(cfg, host_mode,
                           cohort=is_cohort_mode(cfg, fed))
    plain = cfg.replace(diagnostics=False)
    m = cfg.agents_per_round
    specs: List[ProgramSpec] = []
    if getattr(cfg, "tenants", 0) > 0:
        # sharded tenant pack (ISSUE 13): the tenant axis folds INSIDE
        # the shard (parallel/rounds.make_sharded_round_fn_mt) so the
        # collective plan is unchanged — the *_mt CheckSpecs pin that
        # at 1/8/16-way
        from defending_against_backdoors_with_robust_learning_rate_tpu.fl import (
            tenancy)
        from defending_against_backdoors_with_robust_learning_rate_tpu.parallel.rounds import (
            make_sharded_round_fn_mt)
        rep = tenancy.canonical_rep(plain)
        E = rep.tenants
        # buffered: the stacked lead is the whole (params, state) carry —
        # the sharded state shape (no per-bin accumulators), [E]-stacked
        carryE_aval = jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct((E,) + a.shape, a.dtype),
            carry_aval(rep, params_aval, sharded=True))
        keysE_aval = jax.ShapeDtypeStruct((E,) + key_aval.shape,
                                          key_aval.dtype)
        rnd_aval = jax.ShapeDtypeStruct((), jnp.int32)
        kavals = tenancy.knob_avals(E)
        specs.append(ProgramSpec(
            "round_sharded" + sfx,
            make_sharded_round_fn_mt(rep, model, norm, mesh,
                                     *data_avals).jitted,
            (carryE_aval, keysE_aval, rnd_aval, kavals) + data_avals))
        return specs
    if is_cohort_mode(cfg, fed):
        shard_avals = tuple(
            jax.ShapeDtypeStruct((m,) + a.shape[1:], a.dtype)
            for a in data_avals)
        rnd_aval = jax.ShapeDtypeStruct((), jnp.int32)
        specs.append(ProgramSpec(
            "round_sharded_cohort" + sfx,
            make_sharded_cohort_round_fn(plain, model, norm, mesh),
            (lead_aval, key_aval, rnd_aval) + shard_avals))
        return specs
    if host_mode:
        shard_avals = tuple(
            jax.ShapeDtypeStruct((m,) + a.shape[1:], a.dtype)
            for a in data_avals)
        flags = ((jax.ShapeDtypeStruct((m,), jnp.bool_),)
                 if host_takes_flags(cfg) else ())
        specs.append(ProgramSpec(
            "round_sharded_host" + sfx,
            make_sharded_round_fn_host(plain, model, norm, mesh),
            (params_aval, key_aval) + shard_avals + flags))
        return specs
    lead = ((jax.ShapeDtypeStruct((), jnp.int32),)
            if step_takes_round(cfg) else ())
    specs.append(ProgramSpec(
        "round_sharded" + sfx,
        make_sharded_round_fn(plain, model, norm, mesh,
                              *data_avals).jitted,
        (lead_aval, key_aval) + lead + data_avals))
    if cfg.diagnostics:
        specs.append(ProgramSpec(
            "round_sharded_diag",
            make_sharded_round_fn(cfg, model, norm, mesh,
                                  *data_avals).jitted,
            (lead_aval, key_aval) + lead + data_avals))
    if chain_n > 1:
        ids_aval = jax.ShapeDtypeStruct((chain_n,), jnp.int32)
        specs.append(ProgramSpec(
            "chained_sharded" + sfx,
            make_sharded_chained_round_fn(plain, model, norm, mesh,
                                          *data_avals).jitted,
            (lead_aval, key_aval, ids_aval) + data_avals))
    return specs


def trace_program(jit_obj, example_args):
    """ClosedJaxpr of a planned program — trace only, no lowering, no
    XLA. The analysis passes count primitives on this."""
    args = abstractify(example_args)
    if hasattr(jit_obj, "trace"):
        return jit_obj.trace(*args).jaxpr
    return jax.make_jaxpr(jit_obj)(*args)


def lower_program(jit_obj, example_args):
    """Lowered (StableHLO-level) program for a planned family; call
    `.compile()` on the result for post-optimization HLO."""
    return jit_obj.lower(*abstractify(example_args))


def precompile(cfg, model, norm, fed, bank: AotBank,
               log=print) -> List[Dict[str, Any]]:
    """Bank every planned program family for `cfg`. Idempotent: already-
    banked families are verified loadable and skipped. Returns the manifest
    rows (one per family, with cache_hit + seconds)."""
    rows = []
    for spec in plan_programs(cfg, model, norm, fed):
        compiled, hit, secs, entry = bank.get_or_compile(
            spec.family, cfg, spec.jit_obj, spec.example_args)
        del compiled
        verb = "loaded" if hit else "compiled+banked"
        log(f"[precompile] {spec.family}: {verb} in {secs:.1f}s "
            f"(fp {entry['fingerprint']})")
        rows.append({**entry, "cache_hit": hit,
                     "seconds": round(secs, 2)})
    return rows
