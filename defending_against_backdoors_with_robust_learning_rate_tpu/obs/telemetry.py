"""In-jit defense telemetry — cheap scalars computed INSIDE the round fn.

The RLR defense (PAPER.md) is a per-coordinate sign vote, yet the driver
only logs outcome scalars: you can see *that* poison accuracy fell, never
*why*. This module computes the mechanism's state each round, on device,
as part of the compiled round program:

- ``tel_upd_norm_p50/p95/max``  percentiles of the m per-agent update L2
  norms (attack payloads routinely separate by magnitude first);
- ``tel_flip_frac``             fraction of coordinates the RLR vote
  flipped to -server_lr (the defense's actual bite, per round);
- ``tel_margin_mean``           mean sign-vote margin |sum sign(u)|/m;
- ``tel_margin_hist``           [N_MARGIN_BUCKETS] fraction of coordinates
  per bucketized vote margin in [0, m] (a margin distribution collapsing
  toward 0 = the electorate is splitting — the adaptive-attack signature,
  arXiv:2303.03320);
- ``tel_cos_honest/corrupt``    mean cosine of honest (resp. corrupt)
  agent updates to the aggregate — the separability the defense relies on.

Ladder (``--telemetry``): ``off`` adds NOTHING to the traced program —
training is bit-identical to a build without this module; ``basic`` = the
norm percentiles + flip fraction; ``full`` adds the margin histogram and
cosine split. All outputs are device scalars that ride the existing
``MetricsDrain`` (no host syncs on the round loop's critical path) and
surface as ``Defense/*`` rows in metrics.jsonl.

Masked rounds (faults/): masked-out agents are zeroed before the stats,
so the margins/cosines describe the actual electorate; their norms read
as 0 in the percentile scan. Corrupt-vs-honest split needs the sampled
slots' corrupt flags: the device-resident path derives them in-jit, the
host-sampled per-round path takes them as an argument (see
``fl.rounds.host_takes_flags``); the host-sampled *chained* path has no
flag channel, so there the cosine split degrades to all-honest.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from defending_against_backdoors_with_robust_learning_rate_tpu.fl.diagnostics import (
    per_agent_norms)

LEVELS = ("off", "basic", "full")
N_MARGIN_BUCKETS = 8
PREFIX = "tel_"
_EPS = 1e-12

# metrics.jsonl tag per telemetry key; tel_margin_hist expands to one
# Defense/Vote_Margin_Hist/<i> row per bucket (emit_scalars)
TAGS = {
    "tel_upd_norm_p50": "Defense/Update_Norm_P50",
    "tel_upd_norm_p95": "Defense/Update_Norm_P95",
    "tel_upd_norm_max": "Defense/Update_Norm_Max",
    "tel_flip_frac": "Defense/LR_Flip_Fraction",
    "tel_margin_mean": "Defense/Vote_Margin_Mean",
    "tel_margin_hist": "Defense/Vote_Margin_Hist",
    "tel_cos_honest": "Defense/Cosine_Honest_To_Agg",
    "tel_cos_corrupt": "Defense/Cosine_Corrupt_To_Agg",
    # per-staleness-bin split (fl/buffered.py, --agg_mode buffered +
    # --telemetry full on the vmap paths): one row per staleness bin
    "tel_stale_flip": "Defense/Stale_Flip_Fraction",
    "tel_stale_cos": "Defense/Stale_Cosine_To_Agg",
}


def check_level(level: str) -> str:
    if level not in LEVELS:
        raise ValueError(f"telemetry must be one of {LEVELS}, got {level!r}")
    return level


def telemetry_keys(cfg):
    """The static key set cfg's round program emits — the chained scans and
    shard_map out_specs need it ahead of tracing."""
    if cfg.telemetry == "off":
        return ()
    keys = ["tel_upd_norm_p50", "tel_upd_norm_p95", "tel_upd_norm_max"]
    if cfg.robustLR_threshold > 0:
        keys.append("tel_flip_frac")
    if cfg.telemetry == "full":
        keys += ["tel_margin_mean", "tel_margin_hist",
                 "tel_cos_honest", "tel_cos_corrupt"]
    return tuple(keys)


# --- pure pieces (shared by the vmap and shard_map paths) ----------------

def _norm_percentiles(norms):
    """Nearest-rank p50/p95/max of the [m] per-agent norms."""
    m = norms.shape[0]
    srt = jnp.sort(norms)
    return {"tel_upd_norm_p50": srt[(m - 1) // 2],
            "tel_upd_norm_p95": srt[min(m - 1, round(0.95 * (m - 1)))],
            "tel_upd_norm_max": srt[m - 1]}


def _flip_fraction(lr_tree):
    """Fraction of coordinates whose robust lr went negative."""
    neg, total = 0.0, 0
    for leaf in jax.tree_util.tree_leaves(lr_tree):
        neg = neg + jnp.sum((leaf < 0).astype(jnp.float32))
        total += leaf.size
    return neg / total


def _bucketize_margins(s, m: int):
    """[B] coordinate counts of the vote margins s (values in [0, m]),
    plus their sum (for the mean): bucket i covers margins in
    [i*(m+1)/B, (i+1)*(m+1)/B). THE single source of the bucketing
    formula."""
    flat = s.reshape(-1)
    idx = jnp.clip((flat.astype(jnp.int32) * N_MARGIN_BUCKETS) // (m + 1),
                   0, N_MARGIN_BUCKETS - 1)
    counts = jnp.bincount(idx, length=N_MARGIN_BUCKETS).astype(jnp.float32)
    return counts, jnp.sum(flat.astype(jnp.float32))


def _cosine_accumulators(updates_leaves, agg_leaves, mb: int):
    """([mb] dot(u_k, agg), [mb] ||u_k||^2) accumulated leaf-by-leaf —
    the shared cosine-split arithmetic of the vmap and sharded paths
    (their parity depends on accumulating in the same order)."""
    dots = jnp.zeros((mb,), jnp.float32)
    usq = jnp.zeros((mb,), jnp.float32)
    for u, a in zip(updates_leaves, agg_leaves, strict=True):
        uf = u.reshape(mb, -1).astype(jnp.float32)
        af = a.reshape(-1).astype(jnp.float32)
        dots = dots + uf @ af
        usq = usq + jnp.sum(uf * uf, axis=1)
    return dots, usq


def _finish_margins(counts, margin_sum, total_coords: int, m: int):
    return {"tel_margin_hist": counts / total_coords,
            "tel_margin_mean": margin_sum / (total_coords * m)}


def _finish_cosine(dots, usq, asq, corrupt, valid):
    """Mean cosine-to-aggregate over the honest and corrupt slots of the
    `valid` electorate (zero when a group is empty — NaN would poison the
    JSONL stream)."""
    cos = dots * jax.lax.rsqrt(usq * asq + _EPS)
    out = {}
    for key, sel in (("tel_cos_honest", valid & ~corrupt),
                     ("tel_cos_corrupt", valid & corrupt)):
        n = jnp.sum(sel.astype(jnp.float32))
        out[key] = jnp.where(n > 0,
                             jnp.sum(jnp.where(sel, cos, 0.0))
                             / jnp.maximum(n, 1.0), 0.0)
    return out


def _agg_sqnorm(agg):
    return sum(jnp.sum(jnp.square(a.astype(jnp.float32)))
               for a in jax.tree_util.tree_leaves(agg))


def _total_coords(updates) -> int:
    leaves = jax.tree_util.tree_leaves(updates)
    m = leaves[0].shape[0]
    return sum(u.size // m for u in leaves)


# --- single-device (vmap) path -------------------------------------------

def compute(cfg, updates, lr, agg, mask=None, corrupt_flags=None,
            sign_sums=None, vote_range=None):
    """Telemetry dict for the vmap round path. `updates` leaves are
    [m, ...]; `lr` is the robust-lr tree or None (RLR disabled); `agg` the
    aggregate tree; `mask` the [m] participation mask or None;
    `corrupt_flags` the [m] corrupt-slot flags or None (no split known).
    `sign_sums` (optional): an already-accumulated sign-sum tree whose
    margins the vote actually thresholds — the buffered-async path
    (fl/buffered.py) hands over its buffer accumulators so the margin
    histogram describes the BUFFERED electorate, not just this tick's;
    `vote_range` then widens the bucketization range to that
    electorate's maximum (fl/buffered.vote_range — default: m)."""
    with jax.named_scope("telemetry"):
        m = jax.tree_util.tree_leaves(updates)[0].shape[0]
        vr = vote_range or m
        if mask is not None:
            from defending_against_backdoors_with_robust_learning_rate_tpu.faults import (
                masking)
            updates = masking.zero_masked(updates, mask)
        out = _norm_percentiles(per_agent_norms(updates))
        if lr is not None:
            out["tel_flip_frac"] = _flip_fraction(lr)
        if cfg.telemetry != "full":
            return out
        counts = jnp.zeros((N_MARGIN_BUCKETS,), jnp.float32)
        margin_sum = jnp.float32(0.0)
        if sign_sums is not None:
            for s_leaf in jax.tree_util.tree_leaves(sign_sums):
                c, ms = _bucketize_margins(jnp.abs(s_leaf), vr)
                counts, margin_sum = counts + c, margin_sum + ms
        else:
            for u in jax.tree_util.tree_leaves(updates):
                uf = u.reshape(m, -1).astype(jnp.float32)
                s = jnp.abs(jnp.sum(jnp.sign(uf), axis=0))
                c, ms = _bucketize_margins(s, vr)
                counts, margin_sum = counts + c, margin_sum + ms
        dots, usq = _cosine_accumulators(
            jax.tree_util.tree_leaves(updates),
            jax.tree_util.tree_leaves(agg), m)
        out.update(_finish_margins(counts, margin_sum,
                                   _total_coords(updates), vr))
        corrupt = (jnp.zeros((m,), bool) if corrupt_flags is None
                   else corrupt_flags)
        valid = jnp.ones((m,), bool) if mask is None else mask
        out.update(_finish_cosine(dots, usq, _agg_sqnorm(agg),
                                  corrupt, valid))
        return out


# --- sharded (shard_map) path --------------------------------------------

def compute_sharded(cfg, updates_local, lr, agg, axis_name,
                    mask_local=None, mask_full=None, corrupt_full=None,
                    sign_sums=None, vote_range=None):
    """Telemetry dict inside the shard_mapped round body. `updates_local`
    leaves are this device's [m/d, ...] agent block; `lr`/`agg` are
    replicated trees. Collective cost: three tiny all_gathers under
    ``full`` (norms + the two cosine accumulators) and ZERO extra psums
    when the caller hands over `sign_sums` — the RLR vote's per-leaf psum
    results (raw or absolute; the margins take |s| either way). The
    pre-PR-5 version issued its own textually-identical psums and relied
    on XLA CSE, which the jaxpr contract checker measured never happens
    across channel-id'd all-reduces (the same finding the vote/aggregate
    sharing fixed in PR 4). Without `sign_sums` (RLR off) the psums are
    issued here and budgeted accordingly. `vote_range` widens the
    margin bucketization for the buffered electorate (see `compute`)."""
    with jax.named_scope("telemetry"):
        m = cfg.agents_per_round
        vr = vote_range or m
        if mask_local is not None:
            from defending_against_backdoors_with_robust_learning_rate_tpu.faults import (
                masking)
            updates_local = masking.zero_masked(updates_local, mask_local)
        norms = jax.lax.all_gather(per_agent_norms(updates_local),
                                   axis_name, axis=0, tiled=True)
        out = _norm_percentiles(norms)
        if lr is not None:
            out["tel_flip_frac"] = _flip_fraction(lr)  # replicated, no comm
        if cfg.telemetry != "full":
            return out
        mb = jax.tree_util.tree_leaves(updates_local)[0].shape[0]
        counts = jnp.zeros((N_MARGIN_BUCKETS,), jnp.float32)
        margin_sum = jnp.float32(0.0)
        sign_leaves = (None if sign_sums is None
                       else jax.tree_util.tree_leaves(sign_sums))
        for i, u in enumerate(jax.tree_util.tree_leaves(updates_local)):
            if sign_leaves is not None:
                # the vote's own psum result, re-read — no new collective
                s = jnp.abs(sign_leaves[i].reshape(-1))
            else:
                uf = u.reshape(mb, -1).astype(jnp.float32)
                s = jnp.abs(jax.lax.psum(jnp.sum(jnp.sign(uf), axis=0),
                                         axis_name))
            c, ms = _bucketize_margins(s, m)
            counts, margin_sum = counts + c, margin_sum + ms
        dots_l, usq_l = _cosine_accumulators(
            jax.tree_util.tree_leaves(updates_local),
            jax.tree_util.tree_leaves(agg), mb)
        out.update(_finish_margins(counts, margin_sum,
                                   _total_coords(updates_local), m))
        dots = jax.lax.all_gather(dots_l, axis_name, axis=0, tiled=True)
        usq = jax.lax.all_gather(usq_l, axis_name, axis=0, tiled=True)
        corrupt = (jnp.zeros((m,), bool) if corrupt_full is None
                   else corrupt_full)
        valid = jnp.ones((m,), bool) if mask_full is None else mask_full
        out.update(_finish_cosine(dots, usq, _agg_sqnorm(agg),
                                  corrupt, valid))
        return out


# --- host side -----------------------------------------------------------

def tenant_rows(vals, e: int, allowed=None) -> dict:
    """One tenant's slice of [E]-stacked telemetry values (host-fetched,
    the multi-tenant pack fan-out — service/tenancy.py): every tel_*
    leaf indexed at ``e`` on its leading tenant axis. ``allowed``
    (optional iterable of tel_* keys — telemetry_keys of the TENANT's
    own config) filters series the pack computes but this tenant's solo
    twin would not emit (e.g. tel_flip_frac on an undefended tenant in a
    pack that builds the RLR vote), so per-tenant streams stay
    row-compatible with solo runs."""
    out = {}
    keep = None if allowed is None else set(allowed)
    for key in sorted(vals):
        if not key.startswith(PREFIX):
            continue
        if keep is not None and key not in keep:
            continue
        out[key] = vals[key][e]
    return out


def host_summary(vals) -> dict:
    """JSON-able snapshot of the telemetry values in `vals`
    (host-fetched): tel_* scalars as floats, tel_margin_hist as a float
    list. One source for everything downstream of the drain that wants
    the mechanism's state as data rather than metrics rows — the run
    summary's ``defense`` block (train.py, and through it every
    scenario-matrix JSONL cell, scripts/sweep_scenarios.py) and the
    online threshold-adaptation controller (attack/adapt.py)."""
    out = {}
    for key in sorted(vals):
        if not key.startswith(PREFIX):
            continue
        v = vals[key]
        if getattr(v, "ndim", 0) or isinstance(v, (list, tuple)):
            out[key] = [float(x) for x in v]
        else:
            out[key] = float(v)
    return out


def emit_scalars(writer, vals, step: int) -> None:
    """Write every telemetry value in `vals` (host-fetched) as Defense/*
    scalars. Shared by the sync and async metrics paths, so the jsonl
    stream is bit-identical between them."""
    for key in sorted(vals):
        if not key.startswith(PREFIX):
            continue
        tag = TAGS.get(key, f"Defense/{key[len(PREFIX):]}")
        v = vals[key]
        if getattr(v, "ndim", 0) or isinstance(v, (list, tuple)):
            # vector series (margin histogram, per-staleness split):
            # one row per bin, the margin-hist idiom
            for i, x in enumerate(v):
                writer.scalar(f"{tag}/{i}", float(x), step)
        else:
            writer.scalar(tag, float(v), step)
