"""Defense provenance plane: per-client longitudinal suspicion ledger.

The RLR defense (PAPER.md) is a per-parameter sign VOTE, yet every
Defense/* series is aggregate-level (flip fraction, margin histogram) or
cheats with ground-truth corrupt flags (the cosine split). This module
answers the operator question those series cannot: WHICH clients is the
vote voting against, and are they the same ones round after round?

Two halves:

**In-jit** — every round program additionally emits two per-sampled-
client [m] scalars: ``rep_agree``, the fraction of parameter coordinates
where the client's update sign matches the committed sign vote, and
``rep_norm``, the client's update L2 norm (mask-aware: faulted/padded
slots carry the ``MASKED`` sentinel ``-1.0`` so one lane transports both
value and validity). Two signals because the sign vote is MAGNITUDE-
BLIND by construction: a sign-flipping client loses the vote (low
agreement), but a boosting client scales its update without changing a
single sign — and a coordinated boosted pair WINS contested coordinates,
so its agreement is indistinguishable-to-anticorrelated. The norm lane
is what sees it. Collective cost is ZERO everywhere — the
vmap/cohort/host/buffered paths compute both as collective-free [m]
reductions (the tenant pack as [E, m]); the sharded path compares each
device's local agent block against the REPLICATED sign-sum tree the
vote's own psums already produced and lets shard_map's
``P(AGENTS_AXIS)`` out_spec stitch the [m] rows. Pinned by the ``*_rep``
CheckSpecs in analysis/contracts.py at 1/8/16-way.

**Host** — ``ReputationTracker`` folds the drained [m] rows into
longitudinal per-client state keyed by REAL client ids. Each fold turns
a client's round into one ground-truth-free SUSPICION observation::

    susp = max(1 - agree,  1 - med_norm / norm)     # 0 when norm <= med

where ``med_norm`` is the median update norm of THAT round's sampled
row — a scale-free reference that tracks the natural norm decay of a
converging run, so the norm term reads "how many times louder than the
cohort is this client shouting" (a 5x boost scores 0.8) while the
agreement term reads "how often is it outvoted". The tracker keeps an
agreement EMA (the Mean/Min_Agree rows), a suspicion EMA (the ranking),
and a vote-loss streak (consecutive rounds with ``susp >= 0.5`` — the
client either lost the vote outright or out-shouted the cohort 2x).
Below ``rep_population_cap`` the state is a dense per-client dict; above
it (planet-scale cohort runs) it switches to a count-min sketch over
suspicion mass plus an exact top-k heavy-hitter ledger, so a 10M-client
run's RSS stays O(cohort + k). The state is a tiny JSON-able dict
journaled with each checkpoint (train.py), which is what keeps replayed
``Reputation/*`` rows byte-identical across a crash-exact resume.

The ranking is ground-truth-free by construction. The ONLY consumer of
corrupt flags here is the AUC row (``Reputation/Suspicion_AUC``), which
*evaluates* the ranking against ground truth; the ranking itself never
reads a flag. The tracker is observe-only: quarantine remains the
health ladder's decision (health/monitor.py), with this plane's
measured quantiles documented as the calibration source for the
ladder's defense-anomaly thresholds (``--defense_flip_frac_hi`` /
``--defense_low_margin_hi``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

PREFIX = "rep_"
MODES = ("auto", "on", "off")
# host-side EMA decay for the per-client agreement baseline (boundary
# cadence, deterministic Python-float arithmetic — byte-identical rows
# on every replay, the health/sentinel discipline)
EMA_DECAY = 0.9
# a round with per-round suspicion (max of disagreement and relative
# norm excess — see the module doc) at or above this is a LOSS for the
# client — feeds the streak counter. 0.5 means "outvoted on a majority
# of coordinates" on the agreement side and "2x the cohort's median
# update norm" on the magnitude side
LOSE_THRESHOLD = 0.5
# masked/padded slot sentinel: the [m] lane carries value AND validity
MASKED = -1.0
# count-min sketch geometry (population > rep_population_cap). 4 x 4096
# f64 cells ~= 256 KiB — constant regardless of population
SKETCH_DEPTH = 4
SKETCH_WIDTH = 4096
# fixed affine-mix salts per sketch row (NEVER derived from hash(): the
# sketch must be deterministic across interpreters and resumes)
_SKETCH_SALTS = ((0x9E3779B1, 0x85EBCA77), (0xC2B2AE3D, 0x27D4EB2F),
                 (0x165667B1, 0xD3A2646C), (0xFD7046C5, 0xB55A4F09))
# Top_Suspects rows emitted per boundary (metrics.jsonl width); the full
# ranked ledger (rep_topk wide) goes to the run summary, not the stream
N_SUSPECT_ROWS = 8
# typed ledger event on a streak-threshold crossing; replay-deduped
# (obs/events.REPLAY_DEDUPE_EVENTS names the same literal — events.py
# must not import this module)
SUSPECT_EVENT = "rep/suspect"

TAGS = {
    "clients": "Reputation/Clients_Tracked",
    "mean_agree": "Reputation/Mean_Agree",
    "min_agree": "Reputation/Min_Agree",
    "suspect_count": "Reputation/Suspect_Count",
    "top_score": "Reputation/Top_Suspect_Score",
    "top_suspects": "Reputation/Top_Suspects",
    "auc": "Reputation/Suspicion_AUC",
}


def wants_vote(cfg) -> bool:
    """A committed sign vote exists to agree with (the paper's RLR
    threshold vote, or sign aggregation — ops/aggregate.py)."""
    return cfg.robustLR_threshold > 0 or cfg.aggr == "sign"


def check(cfg) -> None:
    """Loud config validation (the health/monitor.check discipline)."""
    if cfg.reputation not in MODES:
        raise ValueError(
            f"--reputation must be one of {MODES}, got {cfg.reputation!r}")
    if cfg.reputation == "on" and not wants_vote(cfg):
        raise ValueError(
            "--reputation on needs a sign vote to measure agreement "
            "against (set robustLR_threshold > 0 or --aggr sign), or use "
            "--reputation auto to resolve off without one")
    if cfg.rep_topk < 1:
        raise ValueError(f"--rep_topk must be >= 1, got {cfg.rep_topk}")
    if cfg.rep_streak < 1:
        raise ValueError(f"--rep_streak must be >= 1, got {cfg.rep_streak}")


def reputation_on(cfg) -> bool:
    """Is the lane compiled into cfg's round program? ``on`` forces it;
    ``auto`` resolves on exactly when a sign vote exists and the round
    holds the updates beside it (not a fold)."""
    if cfg.reputation == "off" or not wants_vote(cfg):
        return False
    # a folded round never holds the updates beside the committed vote
    # (fl/rounds._fold_core): `auto` stands down, `on` is refused
    return cfg.reputation == "on" or cfg.agg_path != "fold"


def rep_keys(cfg):
    """The static rep_* key set cfg's round program emits — chained
    scans and shard_map out_specs need it ahead of tracing (the
    telemetry_keys discipline)."""
    return ("rep_agree", "rep_norm") if reputation_on(cfg) else ()


# --- in-jit pieces --------------------------------------------------------

def sign_sums_from(updates):
    """Per-coordinate signed vote sums derived from the (already
    masked/zeroed) stacked updates — the vmap paths' fallback when the
    aggregation call did not expose its own sign-sum tree. Elementwise
    reductions over the leading agent axis: zero collectives."""
    return jax.tree_util.tree_map(
        lambda u: jnp.sum(jnp.sign(u.astype(jnp.float32)), axis=0), updates)


def agree_rows(updates, sign_sums, mask=None):
    """[rows] rep_agree: per-slot fraction of coordinates whose update
    sign matches the committed vote sign (``sign(u) * sign(vote) > 0``;
    a zero on either side is a non-match — ties never count as
    agreement). ``updates`` leaves are [rows, ...]; ``sign_sums`` the
    RAW (signed) per-coordinate vote sums, replicated — the vote's own
    psum results on the sharded leaf path, a local reduction elsewhere.
    Masked slots read the ``MASKED`` sentinel. Pure elementwise jnp:
    zero collectives on every path."""
    with jax.named_scope("reputation"):
        u_leaves = jax.tree_util.tree_leaves(updates)
        s_leaves = jax.tree_util.tree_leaves(sign_sums)
        rows = u_leaves[0].shape[0]
        total = sum(u.size // rows for u in u_leaves)
        match = jnp.zeros((rows,), jnp.float32)
        for u, s in zip(u_leaves, s_leaves, strict=True):
            uf = u.reshape(rows, -1).astype(jnp.float32)
            sf = jnp.sign(s.reshape(-1).astype(jnp.float32))
            hit = (jnp.sign(uf) * sf[None, :]) > 0
            match = match + jnp.sum(hit.astype(jnp.float32), axis=1)
        agree = match / total
        if mask is not None:
            agree = jnp.where(mask, agree, MASKED)
        return agree


def norm_rows(updates, mask=None):
    """[rows] rep_norm: each slot's update L2 norm over every parameter
    coordinate — the magnitude signal the sign vote cannot carry
    (``sign(5u) == sign(u)``: a boosting attacker is invisible to
    agreement but 5x the cohort's norm). ``updates`` is a pytree of
    [rows, ...] leaves. Masked slots read the ``MASKED`` sentinel. Pure
    local reductions: zero collectives on every path — on the sharded
    path each device's block holds its clients' full coordinate set."""
    with jax.named_scope("reputation"):
        leaves = jax.tree_util.tree_leaves(updates)
        rows = leaves[0].shape[0]
        sq = jnp.zeros((rows,), jnp.float32)
        for u in leaves:
            uf = u.reshape(rows, -1).astype(jnp.float32)
            sq = sq + jnp.sum(uf * uf, axis=1)
        norm = jnp.sqrt(sq)
        if mask is not None:
            norm = jnp.where(mask, norm, MASKED)
        return norm


# --- host-side longitudinal tracker ---------------------------------------

def _sketch_cols(cid: int):
    """The client's cell column per sketch row — fixed affine+xorshift
    mixing, deterministic across interpreters (no built-in hash())."""
    cols = []
    for a, b in _SKETCH_SALTS:
        h = (a * (cid + 1) + b) & 0xFFFFFFFF
        h ^= h >> 15
        h = (h * 0x2C1B3C6D) & 0xFFFFFFFF
        h ^= h >> 12
        cols.append(h % SKETCH_WIDTH)
    return cols


def rank_auc(scores, labels):
    """Mann-Whitney AUC of ``scores`` (higher = more suspect) against
    boolean ``labels`` (True = actually corrupt), average ranks on ties.
    None when either class is empty. Pure deterministic Python — the
    row must be byte-identical on replay."""
    pairs = sorted(zip(scores, labels))
    n_pos = sum(1 for _, y in pairs if y)
    n_neg = len(pairs) - n_pos
    if n_pos == 0 or n_neg == 0:
        return None
    rank_sum, i = 0.0, 0
    while i < len(pairs):
        j = i
        while j < len(pairs) and pairs[j][0] == pairs[i][0]:
            j += 1
        avg_rank = (i + 1 + j) / 2.0  # average of ranks i+1..j
        rank_sum += avg_rank * sum(1 for k in range(i, j) if pairs[k][1])
        i = j
    return (rank_sum - n_pos * (n_pos + 1) / 2.0) / (n_pos * n_neg)


class ReputationTracker:
    """Longitudinal per-client suspicion state folded from drained [m]
    rep_agree + rep_norm rows, keyed by REAL client ids.

    Each fold scores every valid slot with the module-doc suspicion
    observation ``max(1 - agree, 1 - med_norm / norm)`` (``med_norm``
    the row's own median — scale-free, so converging-run norm decay
    cancels) and EMA-folds it per client; the agreement EMA rides along
    for the Mean/Min_Agree rows.

    Dense mode (population <= cap): one dict entry per ever-seen client
    — exact EMAs, exact streaks, full-population AUC. Sketch mode
    (population > cap): a count-min sketch accumulates each client's
    suspicion mass and fold count (O(1) memory in the population);
    an exact ledger tracks the ``topk`` current heavy hitters (EMAs +
    streak start at admission — pre-admission history is the sketch's
    estimate, the documented approximation tests bound). AUC rows are
    dense-mode only: ranking 10M clients would need the O(population)
    state the sketch exists to avoid.

    All state is JSON-able (``state_dict``/``load_state``) and rides the
    checkpoint journal, so a crash-exact resume replays byte-identical
    Reputation/* rows. Folds are deterministic: slots in row order,
    ties broken by client id. Observe-only — nothing here feeds the
    participation mask."""

    def __init__(self, population: int, cap: int, topk: int,
                 streak_thr: int, decay: float = EMA_DECAY):
        self.population = int(population)
        self.cap = int(cap)
        self.topk = int(topk)
        self.streak_thr = int(streak_thr)
        # construction-time Python scalar, never a device value
        self.decay = float(decay)  # static: ok(host-sync)
        self.sketch_mode = self.population > self.cap
        self.rounds_folded = 0
        # dense: {cid: [agree_ema, n, streak, susp_ema]}; ledger (sketch
        # mode): same shape, capped at topk entries
        self.clients = {}
        self.mass = ([[0.0] * SKETCH_WIDTH for _ in range(SKETCH_DEPTH)]
                     if self.sketch_mode else None)
        self.count = ([[0.0] * SKETCH_WIDTH for _ in range(SKETCH_DEPTH)]
                      if self.sketch_mode else None)
        self._pending_events = []

    @classmethod
    def for_config(cls, cfg, population: int):
        return cls(population, cfg.rep_population_cap, cfg.rep_topk,
                   cfg.rep_streak)

    # -- folding ----------------------------------------------------------

    def fold(self, round_id: int, ids, agrees, norms=None) -> None:
        """Fold one drained round row: ``ids`` the [m] sampled REAL
        client ids, ``agrees``/``norms`` the matching rep_agree and
        rep_norm values (MASKED sentinel slots — faulted/padded — are
        skipped: an absent client neither wins nor loses the vote).
        ``norms=None`` degrades to agreement-only suspicion (every norm
        deviation reads 0) — the oracle tests' single-signal mode."""
        vals = [(int(cid), float(a),
                 None if norms is None else float(r))
                for cid, a, r in zip(
                    ids, agrees,
                    agrees if norms is None else norms)
                if float(a) >= 0.0]
        # the row's own median norm: the scale-free magnitude reference
        # (sorted() on floats — deterministic, replay-identical)
        med = None
        if norms is not None and vals:
            ns = sorted(r for _, _, r in vals)
            mid = len(ns) // 2
            med = (ns[mid] if len(ns) % 2
                   else 0.5 * (ns[mid - 1] + ns[mid]))
        for cid, a, r in vals:
            dev = 0.0
            if med is not None and r > med:
                dev = 1.0 if med <= 0.0 else 1.0 - med / r
            self._fold_one(cid, a, max(1.0 - a, dev), int(round_id))
        self.rounds_folded += 1

    def _fold_one(self, cid: int, agree: float, susp: float,
                  round_id: int) -> None:
        if self.sketch_mode:
            est = self._sketch_add(cid, susp)
            if cid not in self.clients and not self._admit(cid, est):
                return
        ent = self.clients.get(cid)
        if ent is None:
            ent = [agree, 1, 1 if susp >= LOSE_THRESHOLD else 0, susp]
            self.clients[cid] = ent
        else:
            ent[0] = self.decay * ent[0] + (1.0 - self.decay) * agree
            ent[1] += 1
            ent[2] = ent[2] + 1 if susp >= LOSE_THRESHOLD else 0
            ent[3] = self.decay * ent[3] + (1.0 - self.decay) * susp
        if ent[2] == self.streak_thr:
            # exact crossing (== not >=: one event per streak, the
            # checkpoint/save dedupe idiom handles crash replays)
            self._pending_events.append({
                "client": cid, "streak": ent[2], "round": round_id,
                "score": round(ent[3], 6)})

    def _sketch_add(self, cid: int, susp: float) -> float:
        """Add one suspicion observation; return the count-min estimate
        of the client's MEAN suspicion so far."""
        est = float("inf")
        for row, col in enumerate(_sketch_cols(cid)):
            self.mass[row][col] += susp
            self.count[row][col] += 1.0
            est = min(est, self.mass[row][col]
                      / max(self.count[row][col], 1.0))
        return est

    def _admit(self, cid: int, est: float) -> bool:
        """Heavy-hitter ledger admission: always while below capacity;
        at capacity, only past the current minimum suspicion (evicting
        that member — deterministic tie-break by id)."""
        if len(self.clients) < self.topk:
            return True
        worst_id, worst = None, None
        for k, ent in self.clients.items():
            score = ent[3]
            if worst is None or score < worst or (score == worst
                                                  and k > worst_id):
                worst_id, worst = k, score
        if est <= worst:
            return False
        del self.clients[worst_id]
        return True

    # -- read side --------------------------------------------------------

    def suspicion(self, cid: int) -> float:
        """The client's suspicion score in [0, 1] (the suspicion EMA —
        module doc); sketch estimate for non-ledger clients in sketch
        mode, 0.0 for a never-seen client in dense mode."""
        ent = self.clients.get(cid)
        if ent is not None:
            return ent[3]
        if not self.sketch_mode:
            return 0.0
        est = float("inf")
        for row, col in enumerate(_sketch_cols(cid)):
            c = self.count[row][col]
            est = min(est, (self.mass[row][col] / c) if c else 0.0)
        return est

    def ranked(self):
        """[(cid, score)] best-suspect-first, ties broken by id —
        deterministic for the Top_Suspects rows and the summary."""
        return sorted(((cid, ent[3])
                       for cid, ent in self.clients.items()),
                      key=lambda t: (-t[1], t[0]))

    def suspect_count(self) -> int:
        return sum(1 for ent in self.clients.values()
                   if ent[2] >= self.streak_thr)

    def drain_events(self):
        """Streak-crossing events accumulated since the last drain —
        the caller emits them through obs/events (keeping ledger writes
        on the metrics thread's already-serialized emit path)."""
        out, self._pending_events = self._pending_events, []
        return out

    def boundary_rows(self, corrupt_pred=None):
        """Ordered [(tag, value)] Reputation/* rows for one eval
        boundary. ``corrupt_pred`` (cid -> bool, the GROUND TRUTH) adds
        the AUC row that evaluates the ranking — the ranking itself
        never read it. Dense mode ranks the whole seen population;
        sketch mode ranks the ledger (and skips AUC, see class doc)."""
        rows = [(TAGS["clients"], float(len(self.clients)))]
        if self.clients:
            emas = [ent[0] for ent in self.clients.values()]
            rows.append((TAGS["mean_agree"], sum(emas) / len(emas)))
            rows.append((TAGS["min_agree"], min(emas)))
        rows.append((TAGS["suspect_count"], float(self.suspect_count())))
        ranked = self.ranked()
        if ranked:
            rows.append((TAGS["top_score"], ranked[0][1]))
            for i, (cid, _) in enumerate(ranked[:N_SUSPECT_ROWS]):
                rows.append((f"{TAGS['top_suspects']}/{i}", float(cid)))
        if corrupt_pred is not None and not self.sketch_mode and ranked:
            auc = rank_auc([s for _, s in ranked],
                           [bool(corrupt_pred(c)) for c, _ in ranked])
            if auc is not None:
                rows.append((TAGS["auc"], auc))
        return rows

    def summary(self, corrupt_pred=None) -> dict:
        """JSON-able snapshot for the run summary's ``suspicion`` key
        (and through it every queue/sweep JSONL cell)."""
        ranked = self.ranked()
        out = {
            "clients": len(self.clients),
            "rounds": self.rounds_folded,
            "suspect_count": self.suspect_count(),
            "suspects": [cid for cid, _ in ranked[:self.topk]],
            "scores": [round(s, 6) for _, s in ranked[:self.topk]],
            "mode": "sketch" if self.sketch_mode else "dense",
        }
        if corrupt_pred is not None and not self.sketch_mode and ranked:
            auc = rank_auc([s for _, s in ranked],
                           [bool(corrupt_pred(c)) for c, _ in ranked])
            if auc is not None:
                out["auc"] = round(auc, 6)
        return out

    # -- journal ----------------------------------------------------------

    def state_dict(self) -> dict:
        """JSON-able state for the checkpoint journal (keys stringified:
        JSON objects cannot carry int keys). Sketch arrays ride along —
        256 KiB of f64 cells, constant in the population."""
        out = {"rounds": self.rounds_folded,
               "clients": {str(cid): ent
                           for cid, ent in self.clients.items()}}
        if self.sketch_mode:
            out["mass"] = self.mass
            out["count"] = self.count
        return out

    def load_state(self, state: dict) -> None:
        """Restore from a journal entry (crash-exact resume): replayed
        rounds re-fold the same drained rows on top of this state, so
        the replayed Reputation/* rows are byte-identical."""
        if not state:
            return
        self.rounds_folded = int(state.get("rounds", 0))
        self.clients = {
            int(cid): [float(e[0]), int(e[1]), int(e[2]), float(e[3])]
            for cid, e in state.get("clients", {}).items()}
        if self.sketch_mode and "mass" in state:
            self.mass = [[float(x) for x in row] for row in state["mass"]]
            self.count = [[float(x) for x in row] for row in state["count"]]


def emit_rows(writer, tracker, step: int, corrupt_pred=None) -> None:
    """Write one boundary's Reputation/* rows. Shared by the sync and
    async metrics paths AND the tenant fan-out, so every stream is
    bit-identical between them (the telemetry emit_scalars discipline)."""
    for tag, val in tracker.boundary_rows(corrupt_pred):
        writer.scalar(tag, float(val), step)
