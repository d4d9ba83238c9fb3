"""The in-jit health lane: numerics sentinels computed inside the round.

The round program already computes everything a numerics-health verdict
needs — the stacked per-agent updates, the committed params, the mean
loss — so the sentinel is a handful of reductions riding the existing
program, not a new dispatch:

- ``hlth_nonfinite``       f32 count of PARTICIPATING agents whose update
                           carries any NaN/inf coordinate (masked-out
                           rows — injected corrupt payloads the faults
                           path already rejects — do not count: they are
                           handled, not a health incident);
- ``hlth_params_finite``   the committed-params finite bit (1.0/0.0),
                           per ROUND — unlike the boundary-only
                           ``all_finite_device`` eval check, a chained
                           block carries it for every scanned round;
- ``hlth_update_normsq``   the cohort's summed squared update norm over
                           FINITE coordinates (a magnitude burst shows
                           here, a NaN burst in the nonfinite lane; the
                           host-side EMA turns it into the spike bit);
- ``hlth_agent_bad``       [m] per-slot nonfinite bits — the QUARANTINE
                           rung's suspect evidence. Single-device paths
                           only: the sharded body would need an
                           all_gather to materialize it, and the health
                           lane's contract is ZERO added collectives
                           (the sharded ladder falls back to the whole
                           sampled cohort as the suspect set).

Collective cost: zero everywhere. The vmap paths are collective-free by
construction; the sharded paths pack the two scalar lanes into the loss
psum the body already pays (a shape change from scalar to [3], not a
count change — the buffered mode's packed-lane idiom), pinned by the
``*_hlth`` CheckSpecs in analysis/contracts.py at 1/8/16-way.

The host-side half (EMA, z-score, spike bit) lives as pure functions
here so health/monitor.py, the service ladder and the tests share one
formula; state is a tiny JSON-able dict the driver journals alongside
each checkpoint, which is what keeps replayed ``Health/*`` rows
byte-identical across a crash-exact resume.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

PREFIX = "hlth_"
LEVELS = ("on", "off")
# EMA decay for the loss / update-norm baselines (host-side, boundary
# cadence). Deterministic Python-float arithmetic: the same stream of
# boundary values produces bit-identical Health/* rows on every replay.
EMA_DECAY = 0.9
# boundaries of warmup before the z-score / spike bit may fire (the first
# boundaries ARE the distribution being learned)
WARMUP_BOUNDARIES = 3
_EPS = 1e-12


def health_on(cfg) -> bool:
    return cfg.health == "on"


def has_quarantine(cfg) -> bool:
    # judged on the PARSED id set, not string truthiness: a value like
    # "," parses to zero ids and must not arm the mask path (whose
    # composition would crash on the None mask) — monitor.check
    # additionally rejects such a value loudly before any build
    return bool(cfg.quarantine) and bool(quarantine_ids(cfg))


def quarantine_ids(cfg):
    """The quarantined client ids as a sorted int tuple (program
    constants — the set is baked into the traced membership test)."""
    try:
        ids = sorted({int(tok) for tok in cfg.quarantine.split(",") if tok})
    except ValueError as e:
        raise ValueError(
            f"--quarantine must be a comma-separated client-id list, "
            f"got {cfg.quarantine!r}") from e
    if any(i < 0 for i in ids):
        raise ValueError(f"--quarantine ids must be >= 0, got {ids}")
    return tuple(ids)


def quarantine_mask(cfg, sampled):
    """[m] bool: True = this sampled slot's client is NOT quarantined.

    The quarantine set is a traced CONSTANT (program provenance, like
    churn_seed), so membership is one broadcast compare — elementwise,
    replicated, zero collectives. The mask joins the participation-mask
    protocol exactly like a churn absence: a quarantined client's update
    never reaches aggregation."""
    ids = quarantine_ids(cfg)
    if not ids:
        return None
    q = jnp.asarray(ids, dtype=sampled.dtype)
    return ~jnp.any(sampled[:, None] == q[None, :], axis=1)


def health_keys(cfg, sharded: bool = False):
    """The static hlth_* key set cfg's round program emits — chained
    scans and shard_map out_specs need it ahead of tracing (the
    telemetry_keys discipline)."""
    if not health_on(cfg):
        return ()
    keys = ("hlth_nonfinite", "hlth_params_finite", "hlth_update_normsq")
    if not sharded:
        keys = keys + ("hlth_agent_bad",)
    return keys


def boundary_keys(cfg):
    """The scalar subset the eval boundary fetches into ``vals`` (the
    [m] suspect vector stays in the info dict for the ladder — it is
    evidence, not a metrics row)."""
    return tuple(k for k in health_keys(cfg) if k != "hlth_agent_bad")


# --- in-jit pieces --------------------------------------------------------

def params_finite_bit(params):
    """1.0 iff every committed-params coordinate is finite (f32 scalar;
    replicated inputs -> replicated bit, no collective)."""
    ok = jnp.all(jnp.stack([jnp.isfinite(leaf).all()
                            for leaf in jax.tree_util.tree_leaves(params)]))
    return ok.astype(jnp.float32)


def _row_stats(updates, mask=None):
    """([rows] bad bits, [rows] finite-coordinate squared norms) over the
    stacked update leaves — the shared arithmetic of the vmap sentinel
    and the sharded local partials (their cross-path parity depends on
    accumulating leaves in the same order)."""
    leaves = jax.tree_util.tree_leaves(updates)
    rows = leaves[0].shape[0]
    bad = jnp.zeros((rows,), bool)
    nsq = jnp.zeros((rows,), jnp.float32)
    for u in leaves:
        uf = u.reshape(rows, -1).astype(jnp.float32)
        finite = jnp.isfinite(uf)
        bad = bad | ~jnp.all(finite, axis=1)
        safe = jnp.where(finite, uf, 0.0)
        nsq = nsq + jnp.sum(safe * safe, axis=1)
    if mask is not None:
        bad = bad & mask
        nsq = jnp.where(mask, nsq, 0.0)
    return bad, nsq


def sentinel(cfg, updates, new_params, mask=None, agent_bad: bool = True):
    """The vmap-path sentinel dict (single-device, cohort, host, buffered
    — every path whose updates hold the full [m] cohort). Pure jnp
    reductions, zero collectives."""
    return from_row_stats(*_row_stats(updates, mask), new_params, agent_bad)


def from_row_stats(bad, nsq, new_params, agent_bad: bool = True):
    """The sentinel dict from per-client (bad bits, squared norms): what
    `sentinel` reads off a stack, and what a folded round
    (fl/rounds._fold_core) gathers chunk by chunk with `_row_stats`."""
    out = {"hlth_nonfinite": jnp.sum(bad.astype(jnp.float32)),
           "hlth_update_normsq": jnp.sum(nsq),
           "hlth_params_finite": params_finite_bit(new_params)}
    if agent_bad:
        out["hlth_agent_bad"] = bad
    return out


def local_lanes(updates_local, mask_local=None):
    """[2] f32 (bad count, normsq) partials of THIS device's agent block —
    the sharded body stacks them into the loss psum's lanes (a shape
    change on an existing collective, never a new one)."""
    bad, nsq = _row_stats(updates_local, mask_local)
    return jnp.stack([jnp.sum(bad.astype(jnp.float32)), jnp.sum(nsq)])


def finish_sharded(bad_count, normsq, new_params):
    """Assemble the sharded sentinel dict from the psummed lanes + the
    replicated committed params (no hlth_agent_bad: materializing the
    [m] vector would cost the all_gather the lane's zero-collective
    contract forbids — the ladder's suspect set degrades to the whole
    sampled cohort, documented in health/monitor.py)."""
    return {"hlth_nonfinite": bad_count,
            "hlth_update_normsq": normsq,
            "hlth_params_finite": params_finite_bit(new_params)}


# --- host-side pure math (EMA / z-score / spike bit) ----------------------

def ema_init():
    """Fresh EMA state (JSON-able — it rides the round journal so a
    crash-exact resume replays identical Health/* rows). ``delta_ema``
    (the committed-delta norm baseline) is only ever fed by the service
    ladder's boundary check — the metrics-path EMA never folds it, so
    Health/* rows are identical whether or not a ladder is armed."""
    return {"n": 0, "loss_ema": 0.0, "loss_var": 0.0, "norm_ema": 0.0,
            "delta_ema": 0.0}


def loss_z(state, loss: float) -> float:
    """z-score of this boundary's train loss against the carried EMA
    baseline; 0.0 during warmup or when the loss is nonfinite (a
    nonfinite loss already trips the nonfinite lane — the z lane must
    stay a readable number)."""
    if state["n"] < WARMUP_BOUNDARIES or not math.isfinite(loss):
        return 0.0
    return (loss - state["loss_ema"]) / math.sqrt(state["loss_var"] + _EPS)


def norm_spike(state, norm: float, factor: float) -> bool:
    """True when the update norm exceeds ``factor`` x its EMA baseline
    (post-warmup, finite values only)."""
    return (state["n"] >= WARMUP_BOUNDARIES and math.isfinite(norm)
            and norm > factor * max(state["norm_ema"], _EPS))


def delta_spike(state, delta: float, factor: float) -> bool:
    """True when the COMMITTED-delta norm (this boundary's params minus
    the previous round's — the service ladder computes it host-side,
    health/monitor.HealthLadder.check) bursts past ``factor`` x its own
    EMA baseline. This is the detector that catches a magnitude fault in
    the commit itself AT the boundary it happened — the loss z-score
    only sees such damage one boundary later, after the bad params have
    reached a checkpoint the ROLLBACK rung would then restore."""
    return (state["n"] >= WARMUP_BOUNDARIES and math.isfinite(delta)
            and state.get("delta_ema", 0.0) > 0.0
            and delta > factor * max(state.get("delta_ema", 0.0), _EPS))


def ema_update(state, loss: float, norm: float,
               delta: float = float("nan")):
    """Fold one HEALTHY boundary into the EMA baselines (incident
    boundaries are deliberately not folded: a NaN or a spike must not
    move the baseline it was judged against). Returns a new dict.
    ``delta`` (the committed-delta norm) is only passed by the service
    ladder; the metrics path leaves it NaN so its baseline stays 0.0
    there."""
    s = dict(state)
    if math.isfinite(delta):
        s["delta_ema"] = (delta if s.get("delta_ema", 0.0) == 0.0
                          else EMA_DECAY * s.get("delta_ema", 0.0)
                          + (1.0 - EMA_DECAY) * delta)
    if math.isfinite(loss):
        if s["n"] == 0:
            s["loss_ema"], s["loss_var"] = loss, 0.0
        else:
            d = loss - s["loss_ema"]
            s["loss_ema"] = s["loss_ema"] + (1.0 - EMA_DECAY) * d
            s["loss_var"] = (EMA_DECAY * s["loss_var"]
                             + (1.0 - EMA_DECAY) * d * d)
    if math.isfinite(norm):
        s["norm_ema"] = (norm if s["n"] == 0
                         else EMA_DECAY * s["norm_ema"]
                         + (1.0 - EMA_DECAY) * norm)
    s["n"] = s["n"] + 1
    return s
