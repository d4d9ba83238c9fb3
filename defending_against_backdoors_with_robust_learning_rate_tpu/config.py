"""Experiment configuration: flag-compatible CLI over a frozen dataclass.

Flag-name/default parity with the reference CLI (reference: src/options.py:4-74,
20 flags). Differences, all deliberate and documented:

- ``--device`` (reference: src/options.py:67-68 picks cuda:0/cpu) is replaced by
  TPU-native placement flags ``--mesh`` and ``--platform``; ``--device`` is still
  accepted and ignored (with a warning) so reference command lines keep working.
- ``--num_workers`` (DataLoader threads, reference: src/options.py:70-71) is
  accepted and ignored: data is device-resident, there is no loader.
- New flags: ``--seed`` (the reference is unseeded, SURVEY.md 2.3.12; we add
  determinism), ``--arch`` (BASELINE.json configs[3-4] require ResNet-9 on
  cifar10 in addition to the faithful CNN), ``--dtype`` (bf16 compute on the
  MXU, f32 default for curve parity), ``--data_dir``, ``--log_dir``,
  ``--checkpoint_dir``/``--resume`` (SURVEY.md section 5.4: checkpointing is
  absent in the reference and added here), ``--mesh`` (number of devices on the
  ``agents`` mesh axis; 0 = all local devices, 1 = single-device vmap path).

Semantics preserved exactly (reference: src/federated.py:23): ``server_lr`` is
forced to 1.0 unless ``aggr == 'sign'``.
"""

from __future__ import annotations

import argparse
import dataclasses
from typing import Optional


@dataclasses.dataclass(frozen=True)
class Config:
    # --- reference flag surface (names + defaults match src/options.py) ---
    data: str = "fmnist"            # fmnist | cifar10 | fedemnist | synthetic
    num_agents: int = 10            # K
    agent_frac: float = 1.0         # C, fraction of agents sampled per round
    num_corrupt: int = 0            # first num_corrupt agent ids are malicious
    rounds: int = 200               # R communication rounds
    aggr: str = "avg"               # avg | comed | sign | trmean | krum | rfa
    local_ep: int = 2               # E local epochs
    bs: int = 256                   # B local batch size
    client_lr: float = 0.1
    client_moment: float = 0.9
    server_lr: float = 1.0          # only used as-is for aggr='sign'
    base_class: int = 5             # backdoor source class
    target_class: int = 7           # backdoor target class
    poison_frac: float = 0.0        # fraction of base-class samples to trojan
    pattern_type: str = "plus"      # plus | square | copyright | apple
    robustLR_threshold: int = 0     # >0 enables the RLR defense
    clip: float = 0.0               # >0 enables client-side PGD L2 projection
    noise: float = 0.0              # >0 adds N(0, noise*clip) server noise
    top_frac: int = 100             # sign-agreement diagnostic top-k params
    snap: int = 1                   # eval every `snap` rounds

    # --- TPU-native additions ---
    platform: str = ""              # "" = whatever JAX finds (the run's
                                    # [device] line says which); "cpu"/"tpu"
                                    # make JAX fail when it is absent
    seed: int = 0
    # multi-host (DCN) rendezvous — one process per host; all empty/0 means
    # single-process (or cloud auto-detection inside jax.distributed)
    coordinator: str = ""           # host:port of process 0
    num_processes: int = 0          # total processes in the job
    process_id: int = -1            # this process's id; -1 = auto
    arch: str = "auto"              # auto | cnn | resnet9, or a token model
                                    # of models/registry.TOKEN_ARCHS:
                                    # lfm2_moe | mla_moe | swa_moe |
                                    # gdn_moe
    dtype: str = "f32"              # f32 | bf16 (compute dtype on the MXU)
    rng_impl: str = "auto"          # auto: hardware RNG (rbg) on TPU,
                                    # threefry elsewhere; threefry | rbg
                                    # force. Measured +13% round throughput
                                    # on v5e (threefry dropout-mask bits
                                    # are 15% of the round). A checkpoint
                                    # must resume under the impl that
                                    # wrote it (key data shapes differ).
    mesh: int = 1                   # devices on the `agents` mesh axis; 0 = all
    chain: int = 1                  # rounds fused per dispatch via lax.scan
                                    # (capped at `snap`; >1 kills per-round
                                    # host dispatch overhead, bit-identical)
    host_prefetch: int = 2          # host-sampled mode: dispatch UNITS of
                                    # shard stacks gathered + device_put
                                    # ahead of the compute (0 = synchronous;
                                    # a unit is one round, or `chain` rounds
                                    # when chained — up to N+2 units
                                    # resident: N queued + 1 in the
                                    # worker's hand + 1 retained for
                                    # supervised retry)
    host_sampled: str = "auto"      # auto: shard stacks above the device-
                                    # resident budget (2 GiB) gather on host
                                    # per round; on/off forces the mode
    agent_chunk: int = 0            # >0: train agents in sequential chunks
                                    # of this size (lax.map) — divides peak
                                    # activation HBM by m/chunk for big
                                    # models; must divide the per-device
                                    # agent count (else full vmap)
    remat: bool = False             # rematerialization of the model's
                                    # forward (ResNet-9): backward
                                    # recomputes the activations that do
                                    # not fit the device instead of
                                    # stashing them (exact, saves HBM)
    remat_policy: str = "auto"      # what backward recomputes under remat.
                                    # block: everything in a block; conv:
                                    # only the elementwise tail, the conv
                                    # (MXU) outputs are kept; none:
                                    # nothing; auto: the least of the
                                    # three that fits the device, block
                                    # where it reports no limit
                                    # (compile_cache.resolved_remat)
    # --- fault injection & elastic participation (faults/) ---
    dropout_rate: float = 0.0       # per-round Bernoulli client dropout
    straggler_rate: float = 0.0     # per-round straggler probability
    straggler_epochs: int = 1       # local epochs a straggler completes
    corrupt_rate: float = 0.0       # per-round corrupt-payload probability
    corrupt_mode: str = "nan"       # nan | huge (1e30 finite constant)
    payload_norm_cap: float = 0.0   # >0: server rejects updates with L2
                                    # norm above the cap (validation mask)
    faults_spare_corrupt: bool = False  # attackers never drop out (the
                                    # adversarial participation model)
    rlr_threshold_mode: str = "abs"  # abs: paper's absolute vote count;
                                    # scaled: threshold * n_eff / m keeps
                                    # the required agreement fraction
                                    # invariant under churn
    # --- buffered-async aggregation (fl/buffered.py, FedBuff-shape) ---
    agg_mode: str = "sync"          # sync | buffered — sync barriers every
                                    # round on the slowest client (the
                                    # historical path, bit-identical);
                                    # buffered folds each arriving update
                                    # into a persistent staleness-weighted
                                    # buffer carried across ticks and
                                    # commits an aggregate only when
                                    # --async_buffer_k updates have
                                    # arrived. Arrival latency rides the
                                    # straggler draw: a straggling
                                    # client's update lands T ticks later
                                    # with staleness T (no epoch
                                    # truncation in buffered mode).
                                    # avg/sign (± RLR) only; refuses
                                    # --diagnostics/host-sampled.
    async_buffer_k: int = 0         # arrivals per commit (FedBuff's K);
                                    # 0 = auto: the cohort size m (then
                                    # staleness-0 runs commit every tick,
                                    # reproducing the sync path)
    async_staleness_exp: float = 0.0  # staleness-weight exponent a: an
                                    # arrival with staleness T folds with
                                    # weight 1/(1+T)^a; 0 = unweighted
                                    # (every arrival counts fully)
    async_max_staleness: int = 4    # max latency draw T (ticks) for a
                                    # straggling client; bounds the
                                    # carried pending-arrival state and
                                    # the staleness telemetry bins
    # --- adaptive-adversary attack registry (attack/registry.py) ---
    attack: str = "static"          # static | dba | boost | signflip —
                                    # the corrupt cohort's strategy:
                                    # static = the paper's trojan (data
                                    # poisoning only, bitwise the
                                    # pre-registry path); dba = the full
                                    # pattern dealt across corrupt agents
                                    # (attack/dba.py); boost / signflip =
                                    # in-jit update transforms applied
                                    # inside the round program
    attack_boost: float = 1.0       # model-replacement scale on corrupt
                                    # updates (boost: x+boost, signflip:
                                    # x-boost); 1.0 = magnitude-preserving
    attack_start: int = 0           # attack schedule (attack/schedule.py,
                                    # pure function of the traced round
                                    # index; rounds are 1-based): dormant
                                    # before this round
    attack_stop: int = 0            # 0 = never stop; start=k, stop=k+1
                                    # is the one-shot attack
    attack_every: int = 1           # intermittent: fire every n-th round
                                    # from attack_start
    # --- online RLR-threshold adaptation (attack/adapt.py) ---
    rlr_adapt: str = "off"          # off | on — the service driver
                                    # adapts --robustLR_threshold from
                                    # mid-run Defense/* telemetry at eval
                                    # boundaries (needs --telemetry full
                                    # + --checkpoint_dir; service mode)
    rlr_adapt_every: int = 2        # decide at most every N eval
                                    # boundaries (hysteresis)
    # --- client churn: arrive/depart/rejoin lifecycles (service/churn.py) ---
    churn_available: float = 1.0    # fraction of lifecycle phases a client
                                    # is present; 1.0 = always there (the
                                    # dense path, bit-identical); <1 routes
                                    # the round through the participation
                                    # mask with away clients excluded
    churn_period: int = 32          # rounds per lifecycle phase: a client's
                                    # stays/absences last whole phases, so
                                    # departures persist (unlike per-round
                                    # dropout) and rejoins happen on phase
                                    # boundaries
    churn_seed: int = 0             # seeds the lifecycle streams —
                                    # independent of --seed so the cohort
                                    # process can be re-drawn without
                                    # touching any training key stream
    # --- million-client population axis (data/bank.py + data/cohort.py) ---
    cohort_sampled: str = "auto"    # auto | on | off — decouple population
                                    # from cohort: the round program takes
                                    # the traced round index, recomputes
                                    # the seeded cohort ids in-program,
                                    # and trains only the gathered [m,...]
                                    # cohort stacks. auto turns on at
                                    # populations >= 4096 clients
                                    # (utils/compile_cache.is_cohort_mode)
    cohort_size: int = 0            # per-round cohort m; 0 = the legacy
                                    # floor(num_agents * agent_frac)
    cohort_seed: int = 0            # seeds the cohort stream — its own
                                    # program field (like churn_seed) so
                                    # cohorts can be re-drawn without
                                    # touching any training key stream
    partitioner: str = "label_shards"  # client-bank partitioner:
                                    # label_shards (the paper's exact
                                    # dealing scheme) | dirichlet |
                                    # pathological (per-client-seeded,
                                    # scale to millions of clients)
    dirichlet_alpha: float = 0.5    # Dir(alpha) class-mixture concentration
    classes_per_client: int = 2     # pathological: distinct classes/client
    samples_per_client: int = 0     # virtual-partitioner shard size;
                                    # 0 = auto clamp(n/K, 16, 4096)
    bank_dir: str = ""              # client-bank root ("" = auto under
                                    # data_dir, else log_dir)
    bank_shard_clients: int = 65536  # clients per bank index-shard file
                                    # (IO layout only — bank content is
                                    # provably layout-independent)
    bank_build_workers: int = 1     # parallel bank-build subprocesses
                                    # (data/bank.py): whole shard files
                                    # per worker, published bank bitwise
                                    # identical to the serial build —
                                    # a throughput knob like the shard
                                    # layout, never a content input
    # --- trace-shaped diurnal traffic (data/traffic.py, ISSUE 17) ---
    traffic: str = "flat"           # flat | diurnal — flat keeps every
                                    # path bit-identical; diurnal gives
                                    # each client a seeded timezone and a
                                    # raised-cosine daily availability
                                    # curve feeding the participation
                                    # mask, plus log-normal (heavy-tail)
                                    # buffered-mode latency
    traffic_seed: int = 0           # seeds the traffic streams —
                                    # independent of --seed (the
                                    # churn_seed idiom)
    traffic_peak_frac: float = 0.8  # availability at a client's local
                                    # daily peak
    traffic_trough_frac: float = 0.1  # availability at the local trough
                                    # (devices charging / offline at
                                    # night)
    traffic_day_rounds: int = 64    # rounds per simulated day (the
                                    # diurnal period; timezone offsets
                                    # spread client local time uniformly
                                    # over it)
    traffic_latency_sigma: float = 0.8  # log-normal sigma of the
                                    # buffered-mode staleness draw
                                    # (heavier tail = more very-late
                                    # uploads), clipped to max_staleness
    # --- multi-tenant packed sweeps (fl/tenancy.py, ISSUE 13) ---
    tenants: int = 0                # >0: this config is a TENANT PACK of E
                                    # independent experiment replicas run
                                    # as one resident program.
                                    # Per-tenant scalar knobs (seed,
                                    # server_lr, robustLR_threshold,
                                    # attack_boost, schedule gates) enter
                                    # as traced [E]-vectors; knobs that
                                    # change shapes stay queue-level.
                                    # 0 = the untenanted (solo) paths,
                                    # bit-for-bit the historical programs.
                                    # Normally set by the experiment queue
                                    # (service/queue.py --tenants), not by
                                    # hand.
    # --- in-program health lane + auto-recovery (health/, ISSUE 14) ---
    health: str = "on"              # on | off — the always-on in-jit
                                    # numerics sentinel (health/sentinel):
                                    # per-round nonfinite update counts,
                                    # committed-params finite bit and the
                                    # cohort update-norm mass emitted as
                                    # Health/* rows, with ZERO added
                                    # collectives (the sharded scalars
                                    # pack into the loss psum's lanes).
                                    # off removes the lane from the
                                    # traced program (the bench A/B arm)
    health_policy: str = "record"   # abort | recover | record — what a
                                    # numerics incident does
                                    # (health/monitor.py): abort raises
                                    # (--debug_nan forces this), record
                                    # warns loudly and keeps the metrics
                                    # flowing (the sweep default: a NaN
                                    # cell is recorded-and-skipped),
                                    # recover arms the service driver's
                                    # ladder (discard -> rollback ->
                                    # quarantine -> halt)
    health_z_threshold: float = 6.0  # loss z-score (vs the carried EMA
                                    # baseline) above which a boundary is
                                    # an incident
    health_spike_factor: float = 10.0  # update-norm spike trigger: norm >
                                    # factor x its EMA baseline
    defense_flip_frac_hi: float = 0.5  # Defense/Flip_Fraction above which
                                    # a boundary counts as a defense
                                    # anomaly (health/monitor.py). The
                                    # default is the PR-15 heuristic;
                                    # calibrate it from the reputation
                                    # plane's measured flip quantiles
                                    # (README "Defense observability")
    defense_low_margin_hi: float = 0.25  # low-vote-margin mass above which
                                    # a boundary counts as a defense
                                    # anomaly; same calibration source
                                    # (Reputation/* quantiles) as
                                    # defense_flip_frac_hi
    quarantine: str = ""            # comma-separated client ids excluded
                                    # from every round's participation
                                    # mask (the ladder's QUARANTINE rung
                                    # writes this; a traced program
                                    # constant — the churn protocol,
                                    # zero extra collectives)
    bank_verify: bool = False       # verify the client bank's per-shard
                                    # sha256 sidecars on open (data/bank):
                                    # a corrupted indices-*.bin fails
                                    # loudly naming the shard instead of
                                    # feeding garbage batches
    # --- continuous-service driver (service/driver.py) ---
    service_rounds: int = 0         # serve(): total rounds to stream; 0 =
                                    # indefinitely (until the stop file
                                    # <log_dir>/service.stop appears)
    service_retries: int = 3        # supervised retries per failed unit
    service_backoff_s: float = 0.25  # exponential-backoff base (doubles
                                    # per attempt)
    service_deadline_s: float = 0.0  # per-unit soft deadline; a unit past
                                    # it classifies as wedged (0 = off)
    service_keep_ckpts: int = -1    # checkpoints retained on disk (keep-K
                                    # pruning). -1 = auto: keep everything
                                    # in the one-shot trainer, 3 under
                                    # serve() (which checkpoints forever
                                    # and must bound the directory);
                                    # 0 = keep everything explicitly
    chaos: str = ""                 # deterministic fault-injection spec
                                    # (service/chaos.py), e.g.
                                    # "kill@7,corrupt_ckpt@4,wedge@3"
    # --- compile persistence & async dispatch (utils/compile_cache.py) ---
    compile_cache: bool = True      # persistent XLA cache + serialized-
                                    # executable AOT bank (warm starts skip
                                    # XLA entirely); --no_compile_cache
                                    # opts out
    compile_cache_dir: str = ""     # cache root ("" = the checkout's
                                    # .compile_cache/); ignored where
                                    # $JAX_COMPILATION_CACHE_DIR is set
    async_metrics: bool = True      # per-round scalars stay on device and
                                    # drain on a background thread (no
                                    # blocking host sync in the round
                                    # loop); --sync_metrics opts out.
                                    # Diagnostics/debug_nan/multi-process
                                    # runs are always synchronous.
    # --- observability (obs/) ---
    telemetry: str = "off"          # off | basic | full — in-jit defense
                                    # telemetry (obs/telemetry.py): norm
                                    # percentiles + RLR flip fraction
                                    # (basic), + vote-margin histogram and
                                    # honest/corrupt cosine split (full).
                                    # off adds NOTHING to the traced
                                    # program: training is bit-identical.
    reputation: str = "auto"        # auto | on | off — the per-client
                                    # defense-provenance lanes
                                    # (obs/reputation.py): every round the
                                    # traced program additionally emits
                                    # per-sampled-client rep_agree
                                    # (fraction of parameter coordinates
                                    # whose update sign matches the
                                    # committed sign vote) and rep_norm
                                    # (update L2 — the magnitude signal
                                    # the sign vote cannot carry) scalars,
                                    # mask-aware,
                                    # with ZERO added collectives, folded
                                    # host-side into a longitudinal
                                    # per-client suspicion ledger
                                    # (Reputation/* rows, rep/* events).
                                    # auto = on whenever a sign vote
                                    # exists (robustLR_threshold > 0 or
                                    # aggr='sign') and the round is not
                                    # a fold; off removes the lane —
                                    # training and every metrics
                                    # surface bit-identical
    rep_population_cap: int = 100000  # dense per-client dict up to this
                                    # population; above it the tracker
                                    # switches to a count-min sketch +
                                    # top-k heavy-hitter ledger so RSS
                                    # stays O(cohort + k) at 10M clients
    rep_topk: int = 64              # heavy-hitter ledger width (ranked
                                    # suspects surfaced per boundary)
    rep_streak: int = 3             # consecutive vote-losing boundaries
                                    # before a client crosses the
                                    # suspicion threshold (rep/suspect
                                    # ledger event; observe-only — the
                                    # health ladder owns quarantine)
    spans: bool = True              # host-side round-trace spans
                                    # (obs/spans.py): trace.json in the run
                                    # dir + Spans/* aggregates in
                                    # metrics.jsonl; --no_spans opts out
    heartbeat: bool = True          # atomically-rewritten status.json
                                    # (obs/heartbeat.py) for the session
                                    # stall detectors; --no_heartbeat
    status_file: str = ""           # heartbeat path ("" = <log_dir>/
                                    # status.json — a stable path the
                                    # watchers can find without knowing
                                    # the run name)
    events: str = "on"              # on | off — the service event ledger
                                    # (obs/events.py): every lifecycle
                                    # transition (retries, ladder rungs,
                                    # adaptation moves, chaos injections,
                                    # checkpoint save/restore, AOT bank
                                    # hit/miss) as one typed, seq-numbered
                                    # record in <run_dir>/events.jsonl;
                                    # off arms nothing and the metrics
                                    # stream is bit-identical
    flight: str = "on"              # on | off — the incident flight
                                    # recorder (obs/flight.py): a bounded
                                    # per-round ring of span durations /
                                    # dispatch gaps / drain depth / HBM
                                    # stats, streamed crash-exactly to
                                    # <run_dir>/flight.jsonl and dumped
                                    # atomically to flight.json on any
                                    # warn/error incident; host-side only,
                                    # training is bit-identical either way
    trigger_profile: str = "off"    # on | off — anomaly-triggered
                                    # profiling (obs/trigger.py): a flight-
                                    # window span z-score or a supervisor/
                                    # health incident arms the round
                                    # profiler for a bounded capture (max
                                    # 2/run) and ledgers the device split
                                    # as obs/trigger_* events. Off by
                                    # default: arming is timing-dependent,
                                    # so byte-identity drills keep it off
    metrics_port: int = 0           # >0: serve GET /metrics (Prometheus
                                    # exposition text, obs/export.py) on
                                    # this port from the service driver;
                                    # 0 = no HTTP exporter
    metrics_textfile: str = ""      # path for the atomically-rewritten
                                    # Prometheus textfile export
                                    # (node_exporter textfile-collector
                                    # format); "" = off
    data_dir: str = "./data"
    log_dir: str = "./logs"
    checkpoint_dir: str = ""        # "" disables checkpointing
    resume: bool = False
    eval_bs: int = 1024
    profile_dir: str = ""           # "" disables jax.profiler traces
    profile_rounds: int = 0         # >0: capture a jax.profiler window of
                                    # this many STEADY rounds (never the
                                    # compile unit) into <run_dir>/profile
                                    # (or --profile_dir), parse it into
                                    # Device/* + Memory/* attribution rows
                                    # (obs/attribution.py) and the run
                                    # report; 0 = off, bit-identical
    debug_nan: bool = False         # checkify float guards in the round fn
    diagnostics: bool = False       # Norms/* + Sign/* research scalars (C13)
    tensorboard: bool = True        # JSONL metrics always; TB optional
    # synthetic-data knobs (used when `data` is missing on disk or 'synthetic')
    synth_train_size: int = 2048
    synth_val_size: int = 512
    # --- the token task (--data=tokens
    # --arch=lfm2_moe|mla_moe|swa_moe|gdn_moe;
    # fl/task.py) ---
    seq_len: int = 2048             # tokens a packed sequence feeds the model
    lm_config: str = "lfm2-8b-a1b"  # the published widths: a name in the
                                    # arch's PUBLISHED (lfm2_moe:
                                    # lfm2-8b-a1b; mla_moe:
                                    # joyai-llm-flash; swa_moe:
                                    # laguna-xs.2; gdn_moe:
                                    # qwen3-next-80b-a3b), or a file
    lm_layers: str = ""             # the cut in depth: source layer indices
                                    # held, comma-separated ("" = all)
    lm_experts_held: int = 0        # experts of a sparse layer that live on
                                    # this chip (0 = all); the router keeps
                                    # its published width
    lm_expert_offset: int = 0       # the first held expert's index
    lm_vocab_held: int = 0          # vocabulary rows held (0 = all)
    agg_path: str = "auto"          # stack | fold: whether the round holds
                                    # the [m, n_params] update stack or folds
                                    # each client's update into the vote as
                                    # it arrives. No flag: auto is a rule
                                    # over the device's memory
                                    # (compile_cache.resolved_agg); tests and
                                    # the static gate's specs set it
    synth_hardness: float = 0.0     # 0 = easy separable prototypes; >0 mixes
                                    # a shared background into the prototypes,
                                    # raises pixel noise and adds label noise
                                    # so val_acc climbs over tens of rounds
                                    # instead of saturating immediately

    @property
    def faults_enabled(self) -> bool:
        """Any nonzero fault rate — or a payload-norm cap, which needs the
        server-side validation + participation mask to act — routes the
        round through the faults path (faults/); all-off keeps the dense
        path bit-for-bit."""
        return (self.dropout_rate > 0 or self.straggler_rate > 0
                or self.corrupt_rate > 0 or self.payload_norm_cap > 0)

    @property
    def churn_enabled(self) -> bool:
        """Client churn is on when availability is a real fraction. The
        lifecycle mask then joins the participation-mask protocol
        (faults/masking.py); 1.0 keeps the dense path bit-for-bit."""
        return self.churn_available < 1.0

    @property
    def traffic_enabled(self) -> bool:
        """Diurnal traffic is on when the model is not flat. The presence
        mask then joins the participation-mask protocol exactly like
        churn; "flat" keeps every path bit-for-bit."""
        return self.traffic != "flat"

    @property
    def effective_server_lr(self) -> float:
        """server_lr is forced to 1.0 unless aggr=='sign' (src/federated.py:23)."""
        return self.server_lr if self.aggr == "sign" else 1.0

    @property
    def agents_per_round(self) -> int:
        """The per-round cohort m: an explicit --cohort_size wins (the
        population/cohort decoupling knob, ISSUE 7); otherwise the
        reference's floor(K * C) (src/federated.py:68)."""
        import math

        if self.cohort_size > 0:
            return self.cohort_size
        return max(1, math.floor(self.num_agents * self.agent_frac))

    @property
    def n_classes(self) -> int:
        # the reference hardcodes 10 everywhere, incl. fedemnist eval
        # (src/utils.py:128, SURVEY.md 2.3.7); we keep 10 for parity.
        return 10

    @property
    def image_shape(self):
        if self.data in ("fmnist", "fedemnist"):
            return (28, 28, 1)
        if self.data in ("cifar10", "synthetic"):
            return (32, 32, 3) if self.data == "cifar10" else (8, 8, 1)
        if self.data == "tokens":
            raise ValueError("the token task has no image shape "
                             "(fl/task.input_shape gives a batch's)")
        raise ValueError(f"unknown dataset {self.data!r}")

    @property
    def model_arch(self) -> str:
        if self.arch != "auto":
            return self.arch
        return "cnn"

    def replace(self, **kw) -> "Config":
        return dataclasses.replace(self, **kw)


# --- field provenance (fingerprint audit, analysis/fingerprint_audit.py) ---
# Every Config field must declare where it lives; the static-analysis CI
# gate fails closed on a new field missing here. Classes
# (analysis/contracts.py):
#   program  shapes the traced round/eval program -> MUST be in the AOT
#            fingerprint (never in compile_cache.EXCLUDED_FIELDS)
#   shape    only changes array shapes -> pinned by the example-arg avals;
#            fingerprinting is harmless, exclusion allowed when an aval
#            provably carries it
#   data     changes dataset CONTENT, never the program
#   runtime  driver/IO knob -> MUST be excluded from the fingerprint
#            (fingerprinting one recompiles identical programs)
FIELD_PROVENANCE = {
    "data": "program",            # selects model family + image geometry
    "num_agents": "program",      # K: in-jit sampling range
    "agent_frac": "program",      # m = floor(K*C): vmap width
    "num_corrupt": "program",     # krum/trmean trim, corrupt-slot flags
    "rounds": "runtime",          # dispatch count only
    "aggr": "program",
    "local_ep": "program",        # scan trip count
    "bs": "program",              # batch shapes
    "client_lr": "program",       # baked into the SGD step
    "client_moment": "program",
    "server_lr": "program",
    "base_class": "data",         # poisoning source; host-side stamping
    "target_class": "data",
    "poison_frac": "data",
    "pattern_type": "data",
    "robustLR_threshold": "program",
    "clip": "program",
    "noise": "program",
    "top_frac": "runtime",        # host-side Sign/* set algebra only
    "snap": "runtime",            # eval cadence; schedule not program
    "platform": "runtime",        # backend is fingerprinted directly
    "seed": "runtime",            # keys are program ARGUMENTS
    "coordinator": "runtime",     # process_count is fingerprinted
    "num_processes": "runtime",
    "process_id": "runtime",
    "arch": "program",
    "dtype": "program",
    "rng_impl": "runtime",        # the RESOLVED impl is fingerprinted via
                                  # jax_default_prng_impl; 'auto' must not
                                  # split from its resolution
    "mesh": "runtime",            # sharded families are never banked; the
                                  # mesh-independent eval/vmap programs
                                  # should be shared across mesh settings
    "chain": "shape",             # round_ids aval pins the block length
    "host_prefetch": "runtime",
    "host_sampled": "runtime",    # selects the family; family names key
                                  # the fingerprint already
    "agent_chunk": "program",     # chunked lax.map vs full vmap
    "remat": "program",
    "remat_policy": "program",    # the fingerprint keys the RESOLVED
                                  # policy (compile_cache.resolved_remat:
                                  # `auto` is block, conv or none by
                                  # the device's memory limit)
    "dropout_rate": "program",    # faults path is traced
    "straggler_rate": "program",
    "straggler_epochs": "program",
    "corrupt_rate": "program",
    "corrupt_mode": "program",
    "payload_norm_cap": "program",
    "faults_spare_corrupt": "program",
    "rlr_threshold_mode": "program",
    "agg_mode": "program",         # selects the buffered-async round
                                   # program (fl/buffered.py carried
                                   # buffer state + fold/commit are
                                   # traced) — distinct *_async families
    "async_buffer_k": "program",   # baked into the traced commit gate
    "async_staleness_exp": "program",  # baked into the traced staleness
                                       # weight
    "async_max_staleness": "program",  # shapes the carried pending state
                                       # and the latency draw range
    "attack": "program",           # selects the in-jit update transform
                                   # (boost/signflip are traced; the
                                   # data-side strategies shape bank/shard
                                   # CONTENT — fingerprinting those too is
                                   # harmless, and one field can carry
                                   # only one class)
    "attack_boost": "program",     # baked into the traced row scale
    "attack_start": "program",     # baked into the traced schedule gate
    "attack_stop": "program",
    "attack_every": "program",
    "tenants": "program",          # E>0 selects the *_mt tenant-pack
                                   # program families (fl/tenancy.py):
                                   # the tenant axis is a traced leading
                                   # dimension of every carried array, so
                                   # the tenant count must split the AOT
                                   # cache (the [E, ...] avals pin it too)
    "rlr_adapt": "runtime",        # service-driver adaptation policy —
                                   # applied by REBUILDING programs with a
                                   # new robustLR_threshold, never read in
                                   # a trace
    "rlr_adapt_every": "runtime",
    "churn_available": "program",  # churn path is traced (service/churn.py
                                   # draws ride the round program)
    "churn_period": "program",
    "churn_seed": "program",       # baked into the traced lifecycle key
                                   # (PRNGKey(churn_seed) is a program
                                   # constant, unlike --seed whose keys are
                                   # program ARGUMENTS)
    "cohort_sampled": "runtime",   # selects the cohort program families;
                                   # family names key the fingerprint
    "cohort_size": "program",      # m: vmap width + in-program sampling
    "cohort_seed": "program",      # baked into the traced cohort draw
                                   # (data/cohort.py, like churn_seed)
    "partitioner": "data",         # shapes bank CONTENT, never the program
    "dirichlet_alpha": "data",
    "classes_per_client": "data",
    "samples_per_client": "shape",  # cohort-row length via the bank's
                                    # padded max_n -> pinned by the avals
    "bank_dir": "runtime",         # storage location only
    "bank_build_workers": "runtime",  # build throughput only — the
                                   # published bank is bitwise identical
                                   # at any worker count (data/bank.py)
    "traffic": "program",          # traffic path is traced
                                   # (data/traffic.py draws ride the
                                   # round program, like churn)
    "traffic_seed": "program",     # baked into the traced traffic key
                                   # (the churn_seed idiom)
    "traffic_peak_frac": "program",    # availability-curve shape enters
    "traffic_trough_frac": "program",  # the traced presence draw
    "traffic_day_rounds": "program",   # diurnal period (traced modulus)
    "traffic_latency_sigma": "program",  # traced buffered staleness draw
    "bank_shard_clients": "runtime",  # IO shard layout; bank content is
                                      # layout-independent (test-pinned)
    "health": "program",           # the in-jit sentinel adds outputs to
                                   # (and packs lanes into) the traced
                                   # round program — a program difference
                                   # like telemetry
    "health_policy": "runtime",    # host-side incident policy; never
                                   # read in a trace
    "health_z_threshold": "runtime",   # host-side EMA judgement knobs
    "health_spike_factor": "runtime",  # (health/monitor.py)
    "defense_flip_frac_hi": "runtime",   # host-side defense-anomaly
    "defense_low_margin_hi": "runtime",  # judgement thresholds
                                         # (health/monitor.py), calibrated
                                         # from Reputation/* quantiles —
                                         # never read in a trace
    "quarantine": "program",       # the quarantined-id set is a traced
                                   # membership constant (the churn_seed
                                   # idiom: baked in, keys the cache)
    "bank_verify": "runtime",      # open-time IO verification only
    "service_rounds": "runtime",   # service/driver.py streaming budget
    "service_retries": "runtime",  # supervisor policy (service/supervisor)
    "service_backoff_s": "runtime",
    "service_deadline_s": "runtime",
    "service_keep_ckpts": "runtime",
    "chaos": "runtime",            # fault injection is host-side only
    "compile_cache": "runtime",
    "compile_cache_dir": "runtime",
    "async_metrics": "runtime",
    "telemetry": "program",       # adds outputs to the traced program
    "reputation": "program",      # the per-client agreement lane adds
                                  # outputs to (and rides the existing
                                  # reductions of) the traced round
                                  # program — a program difference like
                                  # telemetry/health
    "rep_population_cap": "runtime",  # host-side tracker representation
    "rep_topk": "runtime",            # knobs (obs/reputation.py) — never
    "rep_streak": "runtime",          # read in a trace
    "spans": "runtime",
    "heartbeat": "runtime",
    "status_file": "runtime",
    "events": "runtime",          # ledger IO only; never read in a trace
    "flight": "runtime",          # ring buffer + stream IO only
    "trigger_profile": "runtime",  # arms the profiler; never in a trace
    "metrics_port": "runtime",    # exporter transport knobs
    "metrics_textfile": "runtime",
    "data_dir": "runtime",
    "log_dir": "runtime",
    "checkpoint_dir": "runtime",
    "resume": "runtime",
    "eval_bs": "shape",           # eval batch geometry via pad_eval_set
    "profile_dir": "runtime",
    "profile_rounds": "runtime",  # sampled profiler window; observation
                                  # only, never shapes the program
    "debug_nan": "program",       # checkify instruments the program (AOT
                                  # bank is off, but the XLA cache is not)
    "diagnostics": "program",     # per-family normalization in fingerprint()
    "tensorboard": "runtime",
    "synth_train_size": "shape",
    "synth_val_size": "shape",
    "synth_hardness": "data",
    "seq_len": "shape",           # pinned by the dataset's avals
    "lm_config": "program",       # the model's widths
    "lm_layers": "program",       # the cut: depth, experts, vocabulary
    "lm_experts_held": "program",
    "lm_expert_offset": "program",
    "lm_vocab_held": "program",
    "agg_path": "program",        # the fingerprint keys it as resolved
                                  # by the engine (stack | fold)
}


def _add_reference_flags(p: argparse.ArgumentParser) -> None:
    d = Config()
    p.add_argument("--data", type=str, default=d.data,
                   help="dataset we want to train on")
    p.add_argument("--num_agents", type=int, default=d.num_agents,
                   help="number of agents:K")
    p.add_argument("--agent_frac", type=float, default=d.agent_frac,
                   help="fraction of agents per round:C")
    p.add_argument("--num_corrupt", type=int, default=d.num_corrupt,
                   help="number of corrupt agents")
    p.add_argument("--rounds", type=int, default=d.rounds,
                   help="number of communication rounds:R")
    p.add_argument("--aggr", type=str, default=d.aggr,
                   help="aggregation function "
                        "(avg|comed|sign|trmean|krum|rfa)")
    p.add_argument("--local_ep", type=int, default=d.local_ep,
                   help="number of local epochs:E")
    p.add_argument("--bs", type=int, default=d.bs, help="local batch size: B")
    p.add_argument("--client_lr", type=float, default=d.client_lr,
                   help="clients learning rate")
    p.add_argument("--client_moment", type=float, default=d.client_moment,
                   help="clients momentum")
    p.add_argument("--server_lr", type=float, default=d.server_lr,
                   help="servers learning rate for signSGD")
    p.add_argument("--base_class", type=int, default=d.base_class,
                   help="base class for backdoor attack")
    p.add_argument("--target_class", type=int, default=d.target_class,
                   help="target class for backdoor attack")
    p.add_argument("--poison_frac", type=float, default=d.poison_frac,
                   help="fraction of dataset to corrupt for backdoor attack")
    p.add_argument("--pattern_type", type=str, default=d.pattern_type,
                   help="shape of bd pattern")
    p.add_argument("--robustLR_threshold", type=int, default=d.robustLR_threshold,
                   help="break ties when votes sum to 0")
    p.add_argument("--clip", type=float, default=d.clip,
                   help="weight clip to -clip,+clip")
    p.add_argument("--noise", type=float, default=d.noise,
                   help="server-side gaussian noise std multiplier (times clip)")
    p.add_argument("--top_frac", type=int, default=d.top_frac,
                   help="compare fraction of signs")
    p.add_argument("--snap", type=int, default=d.snap,
                   help="do inference in every num of snap rounds")
    # accepted-and-ignored reference flags (GPU-loop specific)
    p.add_argument("--device", type=str, default=None,
                   help="[ignored] reference GPU selector; use --mesh/--platform")
    p.add_argument("--num_workers", type=int, default=0,
                   help="[ignored] reference DataLoader workers; data is device-resident")


def _add_tpu_flags(p: argparse.ArgumentParser) -> None:
    d = Config()
    p.add_argument("--platform", type=str, default=d.platform,
                   help="jax platform override (cpu|tpu); empty = default")
    p.add_argument("--seed", type=int, default=d.seed)
    p.add_argument("--arch", type=str, default=d.arch,
                   help="auto|cnn|resnet9 (BASELINE.json configs[3-4]); "
                        "with --data=tokens a token model: lfm2_moe (short "
                        "convolutions + GQA) | mla_moe (latent attention + "
                        "MTP) | swa_moe (sliding-window and full attention "
                        "mixed by layer) | gdn_moe (Gated DeltaNet linear "
                        "attention beside gated full attention)")
    p.add_argument("--dtype", type=str, default=d.dtype, help="f32|bf16")
    p.add_argument("--rng_impl", choices=("auto", "threefry", "rbg"),
                   default=d.rng_impl,
                   help="PRNG bit generator: auto = hardware RNG (rbg) on "
                        "TPU (+13%% measured round throughput), threefry "
                        "elsewhere; checkpoints must resume under the impl "
                        "that wrote them")
    p.add_argument("--coordinator", type=str, default=d.coordinator,
                   help="multi-host: host:port of process 0 "
                        "(jax.distributed rendezvous)")
    p.add_argument("--num_processes", type=int, default=d.num_processes,
                   help="multi-host: total processes (one per host)")
    p.add_argument("--process_id", type=int, default=d.process_id,
                   help="multi-host: this process's id; -1 = auto")
    p.add_argument("--mesh", type=int, default=d.mesh,
                   help="devices on the `agents` mesh axis (0=all local devices)")
    p.add_argument("--chain", type=int, default=d.chain,
                   help="rounds fused into one compiled lax.scan dispatch "
                        "(capped at --snap so eval cadence is unchanged)")
    p.add_argument("--host_prefetch", type=int, default=d.host_prefetch,
                   help="host-sampled mode: dispatch units (1 round, or "
                        "--chain rounds when chained) of shard stacks "
                        "gathered + device_put ahead of the compute "
                        "(0=synchronous; device memory holds up to N+2 "
                        "units in flight)")
    p.add_argument("--host_sampled", choices=("auto", "on", "off"),
                   default=d.host_sampled,
                   help="force host-sampled shard gathering on/off "
                        "(auto: stacks above the 2 GiB device-resident "
                        "budget gather on host per round)")
    p.add_argument("--agent_chunk", type=int, default=d.agent_chunk,
                   help="train agents in sequential chunks of this size "
                        "(divides peak activation HBM; must divide the "
                        "per-device agent count)")
    p.add_argument("--remat", action="store_true",
                   help="rematerialization of the model forward "
                        "(ResNet-9): backward recomputes the activations "
                        "that do not fit the device instead of stashing "
                        "them — exact, saves HBM; how much is recomputed "
                        "is --remat_policy's, and nothing is where all of "
                        "it fits")
    p.add_argument("--remat_policy", type=str, default=d.remat_policy,
                   choices=("auto", "block", "conv", "none"),
                   help="what backward recomputes under --remat: block = "
                        "everything in a block; conv = only the "
                        "elementwise tail (GroupNorm, relu, pool), the "
                        "conv (MXU) outputs are kept; none = nothing "
                        "(the program --remat left out builds); auto = "
                        "by the conv outputs' bytes (examples in flight "
                        "on a device x the model's conv outputs) against "
                        "what the device has free: none where 3.8x fit, "
                        "conv where 3x fit, else block; block on a "
                        "backend that reports no memory limit (the CPU)")
    p.add_argument("--dropout_rate", type=float, default=d.dropout_rate,
                   help="per-round Bernoulli client dropout probability "
                        "(faults/: dropped agents are masked out of "
                        "aggregation; at least one agent always survives)")
    p.add_argument("--straggler_rate", type=float, default=d.straggler_rate,
                   help="per-round straggler probability; a straggler's "
                        "local training truncates to --straggler_epochs")
    p.add_argument("--straggler_epochs", type=int, default=d.straggler_epochs,
                   help="local epochs a straggler completes (capped at "
                        "--local_ep)")
    p.add_argument("--corrupt_rate", type=float, default=d.corrupt_rate,
                   help="per-round corrupt-payload probability; garbage "
                        "updates are caught by server-side payload "
                        "validation and masked out")
    p.add_argument("--corrupt_mode", choices=("nan", "huge"),
                   default=d.corrupt_mode,
                   help="corrupt-payload flavor: nan (caught by the finite "
                        "check) or huge (1e30 finite — needs "
                        "--payload_norm_cap or a robust aggregator)")
    p.add_argument("--payload_norm_cap", type=float,
                   default=d.payload_norm_cap,
                   help=">0: server rejects updates whose L2 norm exceeds "
                        "the cap (joins the participation mask)")
    p.add_argument("--faults_spare_corrupt", action="store_true",
                   help="malicious agents (id < num_corrupt) never drop "
                        "out: the adversarial participation model that "
                        "thins the RLR defense's honest majority")
    p.add_argument("--rlr_threshold_mode", choices=("abs", "scaled"),
                   default=d.rlr_threshold_mode,
                   help="RLR vote threshold under faults: abs = paper's "
                        "absolute count; scaled = threshold * n_eff / m")
    p.add_argument("--agg_mode", choices=("sync", "buffered"),
                   default=d.agg_mode,
                   help="aggregation mode (fl/buffered.py): sync = every "
                        "round barriers on the slowest client (the "
                        "historical path); buffered = FedBuff-shape — "
                        "arriving updates fold into a persistent "
                        "staleness-weighted buffer carried across ticks, "
                        "the server commits when --async_buffer_k have "
                        "arrived, and a straggling client's update lands "
                        "T ticks later with staleness T (avg/sign ± RLR "
                        "only)")
    p.add_argument("--async_buffer_k", type=int, default=d.async_buffer_k,
                   help="buffered mode: arrivals per commit (0 = auto: "
                        "the cohort size m — staleness-0 then reproduces "
                        "the sync path)")
    p.add_argument("--async_staleness_exp", type=float,
                   default=d.async_staleness_exp,
                   help="buffered mode: staleness-weight exponent a — an "
                        "arrival with staleness T folds with weight "
                        "1/(1+T)^a (0 = unweighted)")
    p.add_argument("--async_max_staleness", type=int,
                   default=d.async_max_staleness,
                   help="buffered mode: max latency draw in ticks for a "
                        "straggling client (bounds the carried pending "
                        "state and the staleness telemetry bins)")
    p.add_argument("--attack", choices=("static", "dba", "boost",
                                        "signflip"),
                   default=d.attack,
                   help="adaptive-adversary strategy (attack/registry.py):"
                        " static = the paper's trojan (bitwise the legacy "
                        "poison path); dba = distributed trigger split "
                        "across corrupt agents; boost = model-replacement "
                        "scaling of corrupt updates; signflip = RLR-aware "
                        "anti-vote (corrupt updates negated)")
    p.add_argument("--attack_boost", type=float, default=d.attack_boost,
                   help="corrupt-update scale for the in-jit strategies "
                        "(boost applies +x, signflip applies -x)")
    p.add_argument("--attack_start", type=int, default=d.attack_start,
                   help="attack schedule: dormant before this round "
                        "(late-start; rounds are 1-based; in-jit "
                        "strategies only)")
    p.add_argument("--attack_stop", type=int, default=d.attack_stop,
                   help="attack schedule: inactive from this round on "
                        "(0 = never; start=k stop=k+1 is one-shot)")
    p.add_argument("--attack_every", type=int, default=d.attack_every,
                   help="attack schedule: fire every n-th round from "
                        "--attack_start (intermittent)")
    p.add_argument("--rlr_adapt", choices=("off", "on"),
                   default=d.rlr_adapt,
                   help="service mode: adapt --robustLR_threshold online "
                        "from mid-run Defense/* telemetry at eval "
                        "boundaries (attack/adapt.py; needs --telemetry "
                        "full and --checkpoint_dir)")
    p.add_argument("--rlr_adapt_every", type=int, default=d.rlr_adapt_every,
                   help="threshold-adaptation cadence: decide at most "
                        "every N eval boundaries")
    p.add_argument("--churn_available", type=float, default=d.churn_available,
                   help="client-churn availability: fraction of lifecycle "
                        "phases a client is present (service/churn.py); "
                        "1.0 = no churn (bit-identical dense path)")
    p.add_argument("--churn_period", type=int, default=d.churn_period,
                   help="rounds per churn lifecycle phase — stays/absences "
                        "last whole phases, so departures persist and "
                        "rejoins land on phase boundaries")
    p.add_argument("--churn_seed", type=int, default=d.churn_seed,
                   help="seeds the client lifecycle streams (independent "
                        "of --seed)")
    p.add_argument("--cohort_sampled", choices=("auto", "on", "off"),
                   default=d.cohort_sampled,
                   help="population/cohort decoupling (data/bank.py + "
                        "data/cohort.py): the round trains a seeded "
                        "per-round cohort gathered from a sharded "
                        "memory-mapped client bank — host/HBM memory is "
                        "constant in population size (auto: on at >= "
                        "4096 clients)")
    p.add_argument("--cohort_size", type=int, default=d.cohort_size,
                   help="per-round cohort size m (0 = the legacy "
                        "floor(num_agents * agent_frac))")
    p.add_argument("--cohort_seed", type=int, default=d.cohort_seed,
                   help="seeds the per-round cohort draw (independent of "
                        "--seed; a program constant like --churn_seed)")
    p.add_argument("--partitioner",
                   choices=("label_shards", "dirichlet", "pathological"),
                   default=d.partitioner,
                   help="client-bank partitioner: label_shards = the "
                        "paper's dealing scheme (exact, small K); "
                        "dirichlet / pathological = per-client-seeded "
                        "non-IID draws that scale to millions of clients")
    p.add_argument("--dirichlet_alpha", type=float,
                   default=d.dirichlet_alpha,
                   help="Dirichlet class-mixture concentration (smaller = "
                        "more skewed clients)")
    p.add_argument("--classes_per_client", type=int,
                   default=d.classes_per_client,
                   help="pathological partitioner: distinct classes each "
                        "client sees")
    p.add_argument("--samples_per_client", type=int,
                   default=d.samples_per_client,
                   help="virtual-partitioner shard size (0 = auto "
                        "clamp(n_samples/population, 16, 4096))")
    p.add_argument("--bank_dir", type=str, default=d.bank_dir,
                   help="client-bank root (default: "
                        "<data_dir>/client_banks/, else under log_dir)")
    p.add_argument("--bank_shard_clients", type=int,
                   default=d.bank_shard_clients,
                   help="clients per bank index-shard file (IO layout "
                        "only; content is layout-independent)")
    p.add_argument("--bank_build_workers", type=int,
                   default=d.bank_build_workers,
                   help="parallel bank-build subprocesses (data/bank.py; "
                        "whole shard files per worker — the published "
                        "bank is bitwise identical at any worker count)")
    p.add_argument("--traffic", choices=("flat", "diurnal"),
                   default=d.traffic,
                   help="traffic model (data/traffic.py): flat = every "
                        "path bit-identical; diurnal = seeded per-client "
                        "timezones + raised-cosine daily availability "
                        "into the participation mask, log-normal "
                        "buffered latency")
    p.add_argument("--traffic_seed", type=int, default=d.traffic_seed,
                   help="seeds the traffic streams (independent of "
                        "--seed; a program constant like --churn_seed)")
    p.add_argument("--traffic_peak_frac", type=float,
                   default=d.traffic_peak_frac,
                   help="diurnal availability at a client's local daily "
                        "peak")
    p.add_argument("--traffic_trough_frac", type=float,
                   default=d.traffic_trough_frac,
                   help="diurnal availability at the local trough")
    p.add_argument("--traffic_day_rounds", type=int,
                   default=d.traffic_day_rounds,
                   help="rounds per simulated day (the diurnal period)")
    p.add_argument("--traffic_latency_sigma", type=float,
                   default=d.traffic_latency_sigma,
                   help="log-normal sigma of the buffered-mode staleness "
                        "draw (clipped to [1, max_staleness])")
    p.add_argument("--tenants", type=int, default=d.tenants,
                   help="multi-tenant pack width E (fl/tenancy.py): >0 "
                        "runs E independent experiment replicas as one "
                        "resident *_mt program with per-tenant seeds/"
                        "thresholds/LRs as traced [E]-vectors; normally "
                        "driven by the experiment queue "
                        "(service/queue.py --tenants), 0 = solo paths")
    p.add_argument("--health", choices=("on", "off"), default=d.health,
                   help="in-program numerics health lane "
                        "(health/sentinel.py): per-round nonfinite "
                        "counts + committed-params finite bit + update-"
                        "norm mass as Health/* rows, zero added "
                        "collectives; off removes the lane (bench A/B)")
    p.add_argument("--health_policy", choices=("abort", "recover",
                                               "record"),
                   default=d.health_policy,
                   help="numerics-incident policy (health/monitor.py): "
                        "abort raises (--debug_nan forces it), record "
                        "warns and keeps recording (sweep default), "
                        "recover arms the service driver's recovery "
                        "ladder (discard -> rollback -> quarantine -> "
                        "halt)")
    p.add_argument("--health_z_threshold", type=float,
                   default=d.health_z_threshold,
                   help="loss z-score vs the carried EMA above which a "
                        "boundary counts as a health incident")
    p.add_argument("--health_spike_factor", type=float,
                   default=d.health_spike_factor,
                   help="update-norm spike trigger: norm > factor x its "
                        "EMA baseline")
    p.add_argument("--defense_flip_frac_hi", type=float,
                   default=d.defense_flip_frac_hi,
                   help="Defense/Flip_Fraction above which a boundary is "
                        "a defense anomaly (health/monitor.py); calibrate "
                        "from the reputation plane's measured quantiles")
    p.add_argument("--defense_low_margin_hi", type=float,
                   default=d.defense_low_margin_hi,
                   help="low-vote-margin mass above which a boundary is a "
                        "defense anomaly; same Reputation/* calibration "
                        "source as --defense_flip_frac_hi")
    p.add_argument("--reputation", choices=("auto", "on", "off"),
                   default=d.reputation,
                   help="per-client defense-provenance lanes "
                        "(obs/reputation.py): rep_agree + rep_norm per "
                        "sampled client with zero added collectives, "
                        "folded into a longitudinal suspicion ledger "
                        "(Reputation/* rows, rep/* events). auto = on "
                        "when a sign vote exists and the round is not a "
                        "fold; off is bit-identical")
    p.add_argument("--rep_population_cap", type=int,
                   default=d.rep_population_cap,
                   help="population above which the reputation tracker "
                        "switches from a dense per-client dict to a "
                        "count-min sketch + top-k heavy-hitter ledger")
    p.add_argument("--rep_topk", type=int, default=d.rep_topk,
                   help="reputation heavy-hitter ledger width (ranked "
                        "suspects surfaced per eval boundary)")
    p.add_argument("--rep_streak", type=int, default=d.rep_streak,
                   help="consecutive vote-losing boundaries before a "
                        "client crosses the suspicion threshold "
                        "(rep/suspect event; observe-only)")
    p.add_argument("--quarantine", type=str, default=d.quarantine,
                   help="comma-separated client ids excluded from every "
                        "round's participation mask (the recovery "
                        "ladder's QUARANTINE rung; zero extra "
                        "collectives — the churn protocol)")
    p.add_argument("--bank_verify", action="store_true",
                   help="verify the client bank's per-shard sha256 "
                        "sidecars on open; a corrupted indices-*.bin "
                        "fails loudly naming the shard")
    p.add_argument("--service_rounds", type=int, default=d.service_rounds,
                   help="service mode: total rounds to stream (0 = run "
                        "until <log_dir>/service.stop appears)")
    p.add_argument("--service_retries", type=int, default=d.service_retries,
                   help="service mode: supervised retries per failed "
                        "dispatch/eval/checkpoint unit")
    p.add_argument("--service_backoff_s", type=float,
                   default=d.service_backoff_s,
                   help="service mode: exponential-backoff base seconds "
                        "(doubles per retry)")
    p.add_argument("--service_deadline_s", type=float,
                   default=d.service_deadline_s,
                   help="service mode: per-unit soft deadline in seconds; "
                        "a unit exceeding it is classified wedged (0=off)")
    p.add_argument("--service_keep_ckpts", type=int,
                   default=d.service_keep_ckpts,
                   help="checkpoints retained on disk (keep-K pruning; "
                        "-1 = auto: keep everything one-shot, 3 in "
                        "service mode; 0 = keep everything)")
    p.add_argument("--chaos", type=str, default=d.chaos,
                   help="deterministic fault-injection spec for the "
                        "service driver (service/chaos.py), e.g. "
                        "'kill@7,corrupt_ckpt@4,wedge@3,slow_eval@2'")
    p.add_argument("--no_compile_cache", action="store_true",
                   help="disable the persistent XLA compilation cache and "
                        "the serialized-executable AOT bank "
                        "(utils/compile_cache.py)")
    p.add_argument("--compile_cache_dir", type=str, default=d.compile_cache_dir,
                   help="compile-cache root (default: .compile_cache/ in "
                        "the checkout; $JAX_COMPILATION_CACHE_DIR, where "
                        "set, takes precedence)")
    p.add_argument("--telemetry", choices=("off", "basic", "full"),
                   default=d.telemetry,
                   help="in-jit defense telemetry (obs/telemetry.py): "
                        "basic = update-norm percentiles + RLR flip "
                        "fraction; full adds the vote-margin histogram "
                        "and honest/corrupt cosine split. Scalars stay "
                        "on device and ride the async metrics drain; "
                        "off is bit-identical to a build without it")
    p.add_argument("--no_spans", action="store_true",
                   help="disable the host-side round-trace spans "
                        "(obs/spans.py: trace.json + Spans/* aggregates)")
    p.add_argument("--no_heartbeat", action="store_true",
                   help="disable the status.json heartbeat "
                        "(obs/heartbeat.py)")
    p.add_argument("--status_file", type=str, default=d.status_file,
                   help="heartbeat path (default <log_dir>/status.json)")
    p.add_argument("--events", choices=("on", "off"), default=d.events,
                   help="service event ledger (obs/events.py): every "
                        "lifecycle transition as a typed, seq-numbered "
                        "record in <run_dir>/events.jsonl (off arms "
                        "nothing; the metrics stream is bit-identical)")
    p.add_argument("--flight", choices=("on", "off"), default=d.flight,
                   help="incident flight recorder (obs/flight.py): "
                        "bounded per-round ring streamed crash-exactly "
                        "to <run_dir>/flight.jsonl, snapshotted to "
                        "flight.json on any incident")
    p.add_argument("--trigger_profile", choices=("on", "off"),
                   default=d.trigger_profile,
                   help="anomaly-triggered profiling (obs/trigger.py): "
                        "a flight-window z-score or an incident arms "
                        "the round profiler for a bounded capture "
                        "(max 2/run) and ledgers the device split")
    p.add_argument("--metrics_port", type=int, default=d.metrics_port,
                   help=">0: serve GET /metrics (Prometheus exposition "
                        "text) on this port from the service driver "
                        "(obs/export.py)")
    p.add_argument("--metrics_textfile", type=str,
                   default=d.metrics_textfile,
                   help="path for the atomically-rewritten Prometheus "
                        "textfile export (node_exporter "
                        "textfile-collector format)")
    p.add_argument("--sync_metrics", action="store_true",
                   help="force the synchronous metrics path (float() host "
                        "sync every eval boundary) instead of the async "
                        "background drain")
    p.add_argument("--data_dir", type=str, default=d.data_dir)
    p.add_argument("--log_dir", type=str, default=d.log_dir)
    p.add_argument("--checkpoint_dir", type=str, default=d.checkpoint_dir)
    p.add_argument("--resume", action="store_true")
    p.add_argument("--eval_bs", type=int, default=d.eval_bs)
    p.add_argument("--profile_dir", type=str, default=d.profile_dir)
    p.add_argument("--profile_rounds", type=int, default=d.profile_rounds,
                   help=">0: sample a jax.profiler capture window of this "
                        "many steady rounds and attribute device time "
                        "(obs/attribution.py: Device/* + Memory/* rows, "
                        "run report input); 0 = off")
    p.add_argument("--debug_nan", action="store_true",
                   help="instrument the round program with checkify float "
                        "checks (raises on the first NaN/inf)")
    p.add_argument("--diagnostics", action="store_true",
                   help="log Norms/* and Sign/* research scalars "
                        "(the reference's dead-code diagnostics, C13)")
    p.add_argument("--no_tensorboard", action="store_true")
    p.add_argument("--synth_train_size", type=int, default=d.synth_train_size)
    p.add_argument("--synth_val_size", type=int, default=d.synth_val_size)
    p.add_argument("--seq_len", type=int, default=d.seq_len,
                   help="--data=tokens: tokens of a packed sequence")
    p.add_argument("--lm_config", type=str, default=d.lm_config,
                   help="a token model's published widths, a name "
                        "(--arch=lfm2_moe: lfm2-8b-a1b; --arch=mla_moe: "
                        "joyai-llm-flash; --arch=swa_moe: laguna-xs.2; "
                        "--arch=gdn_moe: qwen3-next-80b-a3b) or a "
                        "JSON file")
    p.add_argument("--lm_layers", type=str, default=d.lm_layers,
                   help="the cut in depth: source layer indices held, "
                        "comma-separated (empty = all)")
    p.add_argument("--lm_experts_held", type=int, default=d.lm_experts_held,
                   help="experts of a sparse layer held here (0 = all); "
                        "the router keeps its published width and top-k")
    p.add_argument("--lm_expert_offset", type=int,
                   default=d.lm_expert_offset,
                   help="index of the first held expert")
    p.add_argument("--lm_vocab_held", type=int, default=d.lm_vocab_held,
                   help="vocabulary rows held (0 = all); ids, logits and "
                        "loss are over the slice")
    p.add_argument("--synth_hardness", type=float, default=d.synth_hardness,
                   help="0=easy separable synthetic task; 0..1 mixes "
                        "prototypes toward a shared background, raises pixel "
                        "noise and adds label noise (learning curves become "
                        "non-trivial)")


def args_parser(argv: Optional[list] = None) -> Config:
    """Parse CLI flags into a Config (reference: src/options.py:4-74)."""
    p = argparse.ArgumentParser(
        description="TPU-native robust-learning-rate federated learning")
    _add_reference_flags(p)
    _add_tpu_flags(p)
    ns = p.parse_args(argv)
    if ns.device is not None:
        print(f"[config] --device={ns.device} ignored: placement is TPU-mesh "
              f"native, use --mesh / JAX_PLATFORMS")
    fields = {f.name for f in dataclasses.fields(Config)}
    kw = {k: v for k, v in vars(ns).items() if k in fields}
    kw["tensorboard"] = not ns.no_tensorboard
    kw["compile_cache"] = not ns.no_compile_cache
    kw["async_metrics"] = not ns.sync_metrics
    kw["spans"] = not ns.no_spans
    kw["heartbeat"] = not ns.no_heartbeat
    return Config(**kw)


def print_exp_details(cfg: Config) -> None:
    """Banner matching the reference (src/utils.py:287-303)."""
    print("======================================")
    print(f"    Dataset: {cfg.data}")
    print(f"    Global Rounds: {cfg.rounds}")
    print(f"    Aggregation Function: {cfg.aggr}")
    print(f"    Number of agents: {cfg.num_agents}")
    print(f"    Fraction of agents: {cfg.agent_frac}")
    print(f"    Batch size: {cfg.bs}")
    print(f"    Client_LR: {cfg.client_lr}")
    print(f"    Server_LR: {cfg.effective_server_lr}")
    print(f"    Client_Momentum: {cfg.client_moment}")
    print(f"    RobustLR_threshold: {cfg.robustLR_threshold}")
    print(f"    Noise Ratio: {cfg.noise}")
    print(f"    Number of corrupt agents: {cfg.num_corrupt}")
    print(f"    Poison Frac: {cfg.poison_frac}")
    print(f"    Clip: {cfg.clip}")
    print(f"    Seed: {cfg.seed}  Arch: {cfg.model_arch}  Dtype: {cfg.dtype}"
          f"  Mesh: {cfg.mesh}")
    print("======================================")
