"""Buffered-asynchronous aggregation (FedBuff-shape): stop paying the
straggler barrier.

Every sync round program barriers on the slowest client — stragglers are
*modeled* (faults/) but their latency is still fully paid, the opposite of
the production shape the ROADMAP targets. ``--agg_mode buffered`` turns the
round loop into a stream of *ticks*: each tick trains the sampled cohort
against the CURRENT committed params, but an update only *arrives* at the
server after its seeded latency draw elapses — a straggling client's
update lands T ticks later with staleness T (the arrival draw rides the
straggler machinery: the same Bernoulli ``--straggler_rate`` draw selects
who is slow; in buffered mode it delays the upload instead of truncating
epochs). The server folds each arrival into a persistent
staleness-weighted buffer (weight ``1/(1+T)^a``, ``--async_staleness_exp``)
plus per-staleness counters and sign-vote accumulators, and commits an
aggregate — avg/sign ± RLR via the shared
``ops/aggregate.rlr_from_sign_sum`` — only once ``--async_buffer_k``
updates have arrived. Params advance ONLY at commits, so an update drawn
in commit window v and arriving in window v+1 was genuinely computed
against stale params: the electorate of every commit mixes staleness
levels, which is exactly the regime the RLR sign vote has never been
measured under (the per-staleness Defense/* split answers it).

Design properties, inherited from the faults/churn idiom:

- **pure function of (client, round)**: the latency draw derives from the
  round's fault key (``faults/model.fault_key`` + its own fold_in tag), so
  arrivals are reproducible under --seed, identical between per-round and
  chained dispatch, identical across every device of a mesh (replicated
  keys — no collective to agree on who is late), and exactly mirrorable
  on host (``host_latency_draw``, the churn/cohort host-mirror idiom).
- **fixed shapes, carried state**: not-yet-arrived contributions live in a
  bounded pending ladder (``async_max_staleness`` stacked partial sums —
  summation is commutative, so per-(remaining-ticks) partial sums lose no
  information the fold needs); the whole buffer state is ONE pytree
  carried through the chained scan and through the digest-verified
  checkpoint (crash-exact recovery of a mid-buffer kill is the chaos
  drill's acceptance).
- **zero extra collectives**: the fold is elementwise on the replicated
  trees; the sharded paths reuse the sync plan's psums on the per-level
  stacked partial sums and pack the tiny count/weight/loss lanes into
  one vector psum, so the ``*_async`` contract specs pin the SAME
  budgets as the sync families.
- **degenerate-case parity**: with K=m, staleness 0 (no stragglers) and
  ``async_staleness_exp=0``, every tick's arrivals are the full cohort,
  the commit gate fires every tick, and the fold arithmetic degenerates
  to the sync path's exact op sequence — bit-identical for sign (integer
  sign-sums are order-free), ulp-close for avg (tests/test_buffered.py).

Unsupported compositions refuse loudly (``check``): the order-statistic
aggregators (comed/trmean/krum/rfa) need the individual updates a running
sum cannot reconstruct; ``--diagnostics`` needs per-round lr/update trees
of a committed round; host-sampled mode has no cohort-id channel for the
arrival draw (cohort-sampled mode is the supported large-population
surface).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from defending_against_backdoors_with_robust_learning_rate_tpu.ops import tree
from defending_against_backdoors_with_robust_learning_rate_tpu.ops.aggregate import (
    apply_aggregate, gaussian_noise_like, rlr_from_sign_sum)

# fold_in tag separating the arrival-latency stream from the fault draws
# it rides next to (faults/model.FAULTS_KEY_TAG idiom)
ASYNC_KEY_TAG = 0xA51C

# info-dict keys every buffered tick emits (train.py writes them as
# Async/* rows; the chained scan carries them like the fault counters)
ASYNC_INFO_KEYS = ("async_fill", "async_committed", "async_stale_hist")


def is_buffered(cfg) -> bool:
    """Single source of the mode decision (config validation happens in
    ``check``; this predicate must stay cheap — it gates every builder)."""
    mode = getattr(cfg, "agg_mode", "sync")
    if mode not in ("sync", "buffered"):
        raise ValueError(f"agg_mode must be 'sync' or 'buffered', "
                         f"got {mode!r}")
    return mode == "buffered"


def buffer_k(cfg) -> int:
    """The commit threshold K (FedBuff's buffer size). 0 = auto: the
    cohort size m, so a staleness-0 run commits every tick and reproduces
    the sync cadence."""
    return int(cfg.async_buffer_k) or cfg.agents_per_round


def wants_sign(cfg) -> bool:
    """Whether the buffer carries sign-vote accumulators: the RLR vote
    and the sign aggregate consume them, and the full-telemetry
    per-staleness split votes over them."""
    return (cfg.robustLR_threshold > 0 or cfg.aggr == "sign"
            or cfg.telemetry == "full")


def max_staleness(cfg) -> int:
    return int(cfg.async_max_staleness)


def vote_range(cfg) -> int:
    """Margin-bucketization range for the buffered electorate: between
    commits the accumulated sign-sum magnitude can exceed the cohort
    size m (it approaches the commit gate K plus a tick's arrivals), so
    the vote-margin histograms bucketize over [0, K + m] instead of the
    sync path's [0, m] — without this a full buffer saturates the top
    bucket and the margin mean leaves [0, 1]."""
    return buffer_k(cfg) + cfg.agents_per_round


def has_pending(cfg) -> bool:
    """Whether arrivals can be delayed at all: without stragglers every
    draw is latency 0 and the pending ladder (and the per-level stacking)
    is never materialized — the parity fast path."""
    return cfg.straggler_rate > 0


def check(cfg) -> None:
    """Loud refusals for unsupported compositions, before any build
    (each names its remediation)."""
    if not is_buffered(cfg):
        return
    if cfg.aggr not in ("avg", "sign"):
        raise ValueError(
            f"--agg_mode buffered folds running sums; the order-statistic "
            f"aggregator --aggr {cfg.aggr} needs the individual updates "
            f"a buffer cannot reconstruct — use --aggr avg|sign (± RLR) "
            f"or --agg_mode sync")
    if cfg.diagnostics:
        raise ValueError(
            "--agg_mode buffered does not support --diagnostics (the "
            "Norms/Sign research scalars describe one committed round's "
            "lr/update trees, which a partially-filled buffer never "
            "has); re-run with --agg_mode sync, or drop --diagnostics")
    # (host-sampled mode is refused by the step builders and the engine
    # — fl/rounds.make_host_step, parallel/rounds.make_sharded_host_step,
    # train.RoundEngine — which own the host_sampled resolution; reading
    # the runtime-provenance field here would trip the fingerprint audit)
    if int(cfg.async_buffer_k) < 0:
        raise ValueError(f"--async_buffer_k must be >= 0 "
                         f"(0 = auto: the cohort size), got "
                         f"{cfg.async_buffer_k}")
    if cfg.async_staleness_exp < 0:
        raise ValueError(f"--async_staleness_exp must be >= 0, got "
                         f"{cfg.async_staleness_exp}")
    if max_staleness(cfg) < 1:
        raise ValueError(f"--async_max_staleness must be >= 1, got "
                         f"{cfg.async_max_staleness}")


def banner(cfg) -> str:
    if not is_buffered(cfg):
        return ""
    return (f"[async] buffered aggregation: commit every "
            f"{buffer_k(cfg)} arrivals, staleness weight "
            f"1/(1+T)^{cfg.async_staleness_exp}, max latency "
            f"{max_staleness(cfg)} tick(s) "
            f"(straggler_rate {cfg.straggler_rate} drives the arrival "
            f"draw; fl/buffered.py)")


# --------------------------------------------------------------- the draw ---

def latency(cfg, k_noise, straggler):
    """[m] int32 arrival latency in ticks, or None when no client can be
    late. Rides the straggler machinery: ``straggler`` is the fault
    draw's Bernoulli straggler flags ([m] bool, faults/model.py); a slow
    client's latency is uniform in [1, async_max_staleness] — or, under
    --traffic diurnal, heavy-tailed log-normal (data/traffic.py
    latency_quantile, same clip range) — uploads mostly land next tick
    with a genuine tail of very-late arrivals. Keyed off the round's
    fault stream with its own fold_in tag, so existing fault draws are
    untouched and the draw replicates across a mesh; the flat path keeps
    the historical randint bit-for-bit."""
    from defending_against_backdoors_with_robust_learning_rate_tpu.faults import (
        model as fmodel)
    if not has_pending(cfg) or straggler is None:
        return None
    k = jax.random.fold_in(fmodel.fault_key(k_noise), ASYNC_KEY_TAG)
    if cfg.traffic_enabled:
        from defending_against_backdoors_with_robust_learning_rate_tpu.data import (
            traffic as traffic_mod)
        u = jax.random.uniform(k, straggler.shape)
        t = traffic_mod.latency_quantile(cfg, u, max_staleness(cfg))
    else:
        t = jax.random.randint(k, straggler.shape, 1,
                               max_staleness(cfg) + 1)
    return jnp.where(straggler, t, 0)


def host_latency_draw(cfg, rnd, seed=None, m=None, cohort=False):
    """Host mirror of the (straggler, latency) draw the round program
    makes at round ``rnd`` — the same jax ops the traced path runs, so
    the answer is bit-identical (the churn / cohort host-mirror idiom).
    Returns an [m] numpy int32 vector of latencies. ``seed`` is the
    run's --seed, passed explicitly by the caller: the round keys are
    program ARGUMENTS (runtime provenance), so the mirror takes the seed
    the same way the program takes its key. ``cohort`` selects the
    cohort-step key derivation — those steps split the round key 2-ways
    (k_train, k_noise) where the device-resident sample step splits it
    3-ways (k_sample, k_train, k_noise); mirroring the wrong one would
    silently draw a different stream.

    The scenario sweep charges a sync round a simulated duration of
    ``1 + max(T)`` ticks from this draw (the barrier pays the slowest
    client's latency) vs a buffered tick's 1 — the sim clock that makes
    'buffered makes progress where sync waits' a measured number."""
    from defending_against_backdoors_with_robust_learning_rate_tpu.faults import (
        model as fmodel)
    m = m or cfg.agents_per_round
    key = jax.random.fold_in(jax.random.PRNGKey(seed or 0), rnd)
    k_noise = (jax.random.split(key)[1] if cohort
               else jax.random.split(key, 3)[2])
    k_strag = jax.random.split(fmodel.fault_key(k_noise), 3)[1]
    strag = jax.random.uniform(k_strag, (m,)) < cfg.straggler_rate
    t = latency(cfg, k_noise, strag)
    if t is None:
        return np.zeros((m,), np.int32)
    return np.asarray(t, np.int32)


# ----------------------------------------------------------- carried state ---

def _zeros_like_tree(params):
    return tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)


def _stacked_zeros(params, n: int):
    return tree.map(lambda p: jnp.zeros((n,) + p.shape, jnp.float32),
                    params)


def init_state(cfg, params, per_bin: bool = False):
    """The carried buffer state (a plain dict pytree), zero-initialized.
    Structure is a pure function of the config (the AOT fingerprint keys
    every field that shapes it):

      count        f32 []        arrivals since the last commit
      stale        f32 [S+1]     arrivals per staleness bin since commit
      buf          tree          staleness-weighted update sum   (avg)
      wsum         f32 []        staleness-weighted weight sum   (avg)
      sign         tree          sign-vote accumulator           (vote)
      pend_*       stacked       not-yet-arrived partial sums, indexed by
                                 ticks-until-arrival              (stragglers)
      bin_sign     [S+1]-stacked per-staleness sign accumulators
                                 (``per_bin``: the vmap full-telemetry
                                 Defense split)

    ``per_bin`` is the caller's layout decision: the vmap path carries the
    per-staleness accumulators under --telemetry full; the sharded paths
    degrade the per-bin split (a documented degradation like the chained
    host cosine split) rather than paying per-bin collectives."""
    S = max_staleness(cfg)
    state = {"count": jnp.float32(0.0),
             "stale": jnp.zeros((S + 1,), jnp.float32)}
    if cfg.aggr == "avg":
        state["buf"] = _zeros_like_tree(params)
        state["wsum"] = jnp.float32(0.0)
    if wants_sign(cfg):
        state["sign"] = _zeros_like_tree(params)
    if has_pending(cfg):
        if cfg.aggr == "avg":
            state["pend_buf"] = _stacked_zeros(params, S)
            state["pend_wsum"] = jnp.zeros((S,), jnp.float32)
        if wants_sign(cfg):
            state["pend_sign"] = _stacked_zeros(params, S)
        state["pend_cnt"] = jnp.zeros((S, S + 1), jnp.float32)
    if per_bin and cfg.telemetry == "full":
        state["bin_sign"] = _stacked_zeros(params, S + 1)
    return state


def state_avals(cfg, params_aval, per_bin: bool = False):
    """ShapeDtypeStruct twin of ``init_state`` for the AOT planners."""
    shaped = jax.eval_shape(
        lambda p: init_state(cfg, p, per_bin=per_bin), params_aval)
    return shaped


# ------------------------------------------------------- tick contributions ---

def _level_weights(cfg, T):
    """Per-slot staleness weight 1/(1+T)^a; None when a == 0 (the weight
    is then exactly 1 and the multiply is skipped — parity fast path)."""
    a = float(cfg.async_staleness_exp)
    if a == 0.0 or T is None:
        return None
    return (1.0 + T.astype(jnp.float32)) ** jnp.float32(-a)


def tick_contributions(cfg, updates, sizes, mask, T):
    """One tick's arrival contributions from the trained block.

    ``updates`` leaves are [mb, ...] (the full cohort, or a device's
    local block on the sharded paths); ``sizes`` [mb]; ``mask`` the [mb]
    participation mask or None; ``T`` the [mb] latency draw or None.

    Returns a dict of partial sums — plain leaf shapes when ``T`` is None
    (everything arrives now: the parity fast path whose op sequence is
    exactly the sync aggregation's), else [S+1]-stacked by latency level:

      buf   staleness-weighted update sums      (avg)
      sign  sign sums                            (vote)
      wsum  weighted counts  [S+1] / scalar      (avg)
      cnt   arrival counts   [S+1] / scalar

    Pure local compute — the sharded callers psum these (same collective
    count as the sync plan: the stacking rides the existing psums)."""
    from defending_against_backdoors_with_robust_learning_rate_tpu.faults import (
        masking)
    avg = cfg.aggr == "avg"
    sgn = wants_sign(cfg)
    w = sizes.astype(jnp.float32)
    sw = _level_weights(cfg, T)
    if sw is not None:
        w = w * sw
    out = {}
    if T is None:
        if mask is not None:
            updates = masking.zero_masked(updates, mask)
            w = jnp.where(mask, w, 0.0)
            out["cnt"] = masking.count_f32(mask)
        else:
            out["cnt"] = jnp.float32(updates_m(updates))
        if avg:
            out["wsum"] = jnp.sum(w)

            def leaf_avg(u):
                wshape = (-1,) + (1,) * (u.ndim - 1)
                return jnp.sum(u * w.reshape(wshape), axis=0)
            out["buf"] = tree.map(leaf_avg, updates)
        if sgn:
            out["sign"] = tree.map(
                lambda u: jnp.sum(jnp.sign(u), axis=0), updates)
        return out

    S = max_staleness(cfg)
    valid = mask if mask is not None else jnp.ones(T.shape, bool)
    cnt, wsum, bufs, signs = [], [], [], []
    for s in range(S + 1):
        lvl = valid & (T == s)
        wl = jnp.where(lvl, w, 0.0)
        cnt.append(masking.count_f32(lvl))
        if avg:
            wsum.append(jnp.sum(wl))
        zeroed = masking.zero_masked(updates, lvl)
        if avg:
            def leaf_avg(u, wl=wl):
                wshape = (-1,) + (1,) * (u.ndim - 1)
                return jnp.sum(u * wl.reshape(wshape), axis=0)
            bufs.append(tree.map(leaf_avg, zeroed))
        if sgn:
            signs.append(tree.map(
                lambda u: jnp.sum(jnp.sign(u), axis=0), zeroed))
    out["cnt"] = jnp.stack(cnt)
    if avg:
        out["wsum"] = jnp.stack(wsum)
        out["buf"] = _stack_trees(bufs)
    if sgn:
        out["sign"] = _stack_trees(signs)
    return out


def updates_m(updates) -> int:
    return jax.tree_util.tree_leaves(updates)[0].shape[0]


def _stack_trees(trees):
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *trees)


# ------------------------------------------------------------- fold + commit ---

def _roll_pend(pend, contrib_tail):
    """pend [S, ...] advances one tick: slot i holds what arrives i+1
    ticks from now. The head (arriving now) was consumed by the caller;
    the freshly-drawn level-(i+1) contribution joins slot i."""
    return tree.map(
        lambda p, c: jnp.concatenate([p[1:], jnp.zeros_like(p[:1])]) + c,
        pend, contrib_tail)


def fold_commit(cfg, params, state, contribs, k_noise, m, knobs=None):
    """Fold one tick's (global) contributions into the carried buffer,
    commit when the gate fires, return the advanced carry.

    Purely elementwise/replicated — the sharded callers hand over
    already-psum'd contributions, so this function adds ZERO collectives
    on any layout. Returns ``(new_params, new_state, lr, agg, extras,
    vote_sign)``; ``lr``/``agg`` are the commit decision's trees (the
    hypothetical commit on non-commit ticks — telemetry reads the
    buffer's current vote either way), ``extras`` the Async/* scalars
    plus (per-bin state present) the per-staleness Defense split, and
    ``vote_sign`` the buffer's accumulated sign-sum tree (None without a
    vote) — handed to telemetry so the margin histogram describes the
    BUFFERED electorate without issuing any collective of its own.

    ``knobs`` (fl/tenancy.TenantKnobs — this tenant's slice of the
    pack's traced [E]-vectors) overrides the server-LR and RLR-threshold
    scalars the solo paths bake in as Python constants; the STRUCTURAL
    decisions (is the vote built, is the threshold scaled) stay on
    ``cfg``, which the pack canonicalizes (fl/tenancy.canonical_rep
    collapses thresholds to the 0/1 vote bit) — everything the overrides
    touch is elementwise, so the collective plan is knob-free."""
    S = max_staleness(cfg)
    avg = cfg.aggr == "avg"
    sgn = wants_sign(cfg)
    pend = has_pending(cfg)
    stacked = "cnt" in contribs and getattr(contribs["cnt"], "ndim", 0) > 0
    if pend and not stacked:
        # stragglers always draw latencies, so pending state implies
        # level-stacked contributions; an unstacked caller would
        # silently strand the pending head — refuse instead
        raise ValueError(
            "buffered fold: pending state requires level-stacked "
            "contributions (a caller passed single-level sums on a "
            "straggler_rate > 0 config)")

    # ---- arrivals: this tick's level-0 contribution + the pending head
    if stacked:
        arr_bins = jnp.zeros((S + 1,), jnp.float32).at[0].set(
            contribs["cnt"][0])
        arr_wsum = contribs["wsum"][0] if avg else None
        arr_buf = (tree.map(lambda c: c[0], contribs["buf"])
                   if avg else None)
        arr_sign = (tree.map(lambda c: c[0], contribs["sign"])
                    if sgn else None)
    else:
        arr_bins = jnp.zeros((S + 1,), jnp.float32).at[0].set(
            contribs["cnt"])
        arr_wsum = contribs.get("wsum")
        arr_buf = contribs.get("buf")
        arr_sign = contribs.get("sign")
    new_state = {}
    if pend and stacked:
        arr_bins = arr_bins + state["pend_cnt"][0]
        if avg:
            arr_wsum = arr_wsum + state["pend_wsum"][0]
            arr_buf = tree.map(lambda a, p: a + p[0], arr_buf,
                               state["pend_buf"])
            new_state["pend_buf"] = _roll_pend(
                state["pend_buf"], tree.map(lambda c: c[1:],
                                            contribs["buf"]))
            new_state["pend_wsum"] = (jnp.concatenate(
                [state["pend_wsum"][1:], jnp.zeros((1,), jnp.float32)])
                + contribs["wsum"][1:])
        if sgn:
            arr_sign = tree.map(lambda a, p: a + p[0], arr_sign,
                                state["pend_sign"])
            new_state["pend_sign"] = _roll_pend(
                state["pend_sign"], tree.map(lambda c: c[1:],
                                             contribs["sign"]))
        # per-(remaining, staleness-bin) counts: a level-s draw arrives s
        # ticks out into bin s — jnp.eye's superdiagonal routes it
        route = jnp.eye(S + 1, dtype=jnp.float32)[1:] \
            * contribs["cnt"][1:, None]
        new_state["pend_cnt"] = (jnp.concatenate(
            [state["pend_cnt"][1:], jnp.zeros((1, S + 1), jnp.float32)])
            + route)

    # ---- fold
    count1 = state["count"] + jnp.sum(arr_bins)
    stale1 = state["stale"] + arr_bins
    if avg:
        buf1 = tree.add(state["buf"], arr_buf)
        wsum1 = state["wsum"] + arr_wsum
    if sgn:
        sign1 = tree.add(state["sign"], arr_sign)
    bin1 = None
    if "bin_sign" in state:
        # per-staleness vote accumulators (the Defense split): a
        # contribution's bin is its latency level, known at draw time —
        # accumulated here (at draw) so the split needs no per-bin
        # pending ladder; the buffer itself still folds at arrival.
        # Unstacked contributions are all level 0 — pad into bin 0.
        if stacked:
            contrib_sign = contribs["sign"]
        else:
            contrib_sign = tree.map(
                lambda c: jnp.pad(c[None], [(0, S)] + [(0, 0)] * c.ndim),
                arr_sign)
        bin1 = tree.map(lambda b, c: b + c, state["bin_sign"],
                        contrib_sign)

    # ---- commit decision (computed every tick, applied via `where` — one
    # compiled program serves every fill level)
    K = buffer_k(cfg)
    commit = count1 >= K
    slr = (cfg.effective_server_lr if knobs is None
           else knobs.server_lr)
    thr = (float(cfg.robustLR_threshold) if knobs is None
           else knobs.rlr_threshold)
    if cfg.robustLR_threshold > 0 and cfg.rlr_threshold_mode == "scaled":
        # the buffered electorate is the buffer, not the cohort: scale
        # against the arrivals actually voting
        thr = thr * count1 / jnp.float32(m)
    lr = (tree.map(lambda s: rlr_from_sign_sum(s, thr, slr), sign1)
          if cfg.robustLR_threshold > 0 else slr)
    if avg:
        # guard the empty buffer (0/0) exactly like masking.guard_empty:
        # a zero aggregate makes the commit a parameter-preserving no-op
        agg = tree.map(
            lambda b: jnp.where(count1 > 0, b / wsum1,
                                jnp.zeros_like(b)), buf1)
    else:
        agg = tree.map(lambda s: jnp.where(count1 > 0, jnp.sign(s),
                                           jnp.zeros_like(s)), sign1)
    if cfg.noise > 0:
        agg = tree.add(agg, gaussian_noise_like(agg, k_noise,
                                                cfg.noise * cfg.clip))
    committed = apply_aggregate(params, lr, agg)
    new_params = tree.map(lambda c, p: jnp.where(commit, c, p),
                          committed, params)

    # ---- reset-on-commit
    def z(x):
        return jnp.where(commit, jnp.zeros_like(x), x)

    new_state["count"] = z(count1)
    new_state["stale"] = z(stale1)
    if avg:
        new_state["buf"] = tree.map(z, buf1)
        new_state["wsum"] = z(wsum1)
    if sgn:
        new_state["sign"] = tree.map(z, sign1)

    extras = {"async_fill": count1,
              "async_committed": commit.astype(jnp.float32),
              "async_stale_hist": stale1}
    if bin1 is not None:
        extras.update(_per_bin_split(cfg, bin1, sign1, agg, count1,
                                     stale1, thr))
        new_state["bin_sign"] = tree.map(z, bin1)
    return (new_params, new_state, lr, agg, extras,
            sign1 if sgn else None)


def _per_bin_split(cfg, bin_sign, sign_total, agg, count1, stale1, thr):
    """The per-staleness-bin Defense split (vmap, --telemetry full):

    - ``tel_stale_flip``  [S+1]: fraction of coordinates the RLR vote
      would flip if bin b voted ALONE, at the threshold scaled to the
      bin's electorate (thr * n_b / n) — how much of the defense's bite
      each staleness level would draw by itself;
    - ``tel_stale_cos``   [S+1]: cosine of bin b's accumulated sign vote
      to the committed aggregate — whether stale voters still point where
      the commit goes (0 for an empty bin, the telemetry NaN rule).
    """
    S = max_staleness(cfg)
    leaves_bin = jax.tree_util.tree_leaves(bin_sign)
    leaves_agg = jax.tree_util.tree_leaves(agg)
    total_coords = sum(x.size // (S + 1) for x in leaves_bin)
    n_eff = jnp.maximum(count1, 1.0)
    thr_b = thr * stale1 / n_eff            # [S+1]
    flips = jnp.zeros((S + 1,), jnp.float32)
    dots = jnp.zeros((S + 1,), jnp.float32)
    bsq = jnp.zeros((S + 1,), jnp.float32)
    asq = jnp.float32(0.0)
    for b, a in zip(leaves_bin, leaves_agg, strict=True):
        bf = b.reshape(S + 1, -1)
        af = a.reshape(-1).astype(jnp.float32)
        flips = flips + jnp.sum(
            (jnp.abs(bf) < thr_b[:, None]).astype(jnp.float32), axis=1)
        dots = dots + bf @ af
        bsq = bsq + jnp.sum(bf * bf, axis=1)
        asq = asq + jnp.sum(af * af)
    cos = dots * jax.lax.rsqrt(bsq * asq + 1e-12)
    return {"tel_stale_flip": flips / total_coords,
            "tel_stale_cos": jnp.where(stale1 > 0, cos, 0.0)}
