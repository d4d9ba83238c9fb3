"""Health lane + auto-recovery ladder (ISSUE 14, health/).

Three layers, mirroring the module split:

- sentinel math: the in-jit reductions (nonfinite counts, params-finite
  bit, update-norm mass) against numpy host oracles, including the
  sharded packed-lane assembly; the host-side EMA / z-score / spike
  formulas as pure functions.
- policy: the unified divergence policy (abort|recover|record,
  --debug_nan forces abort), the quarantine mask's bitwise construction
  (the churn participation-mask protocol), and the deterministic ladder
  walk (budgets, skips, episode lifecycle, state persistence).
- drills: in-process serve() runs — nan@N heals via DISCARD->ROLLBACK
  with a byte-identical stream vs the uninjected twin; a persistent
  fault escalates to QUARANTINE then HALT loudly; `record` keeps the
  metrics flowing through a NaN; a resume from mid-rollback on-disk
  state picks the LADDER up, not the failure (the cheap twin of the
  slow-gated true-SIGKILL kill_mid_rollback drill); the 8-way shard_map
  acceptance drill runs the vmap drill's machinery over the mesh.

Data-plane integrity (bank sha256 sidecars + the bank_corrupt chaos
drill) closes the file.
"""

import json
import os
import signal
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from defending_against_backdoors_with_robust_learning_rate_tpu.config import (
    Config)
from defending_against_backdoors_with_robust_learning_rate_tpu.data import (
    bank as bank_mod)
from defending_against_backdoors_with_robust_learning_rate_tpu.health import (
    monitor, sentinel)
from defending_against_backdoors_with_robust_learning_rate_tpu.service import (
    chaos as chaos_mod, churn as churn_mod)
from defending_against_backdoors_with_robust_learning_rate_tpu.service.driver import (
    serve)
from defending_against_backdoors_with_robust_learning_rate_tpu.service.supervisor import (
    UnitFailure)
from defending_against_backdoors_with_robust_learning_rate_tpu.utils.metrics import (
    run_name)

# --- sentinel math vs host oracles ---------------------------------------


def _updates(m=6, bad_rows=(1, 4), inf_row=None):
    """A two-leaf stacked-update pytree with NaN/inf planted per row."""
    rng = np.random.RandomState(0)
    a = rng.randn(m, 3, 2).astype(np.float32)
    b = rng.randn(m, 5).astype(np.float32)
    for r in bad_rows:
        a[r, 1, 0] = np.nan
    if inf_row is not None:
        b[inf_row, 2] = np.inf
    return {"a": jnp.asarray(a), "b": jnp.asarray(b)}


def _oracle(updates, mask=None):
    """Numpy reference: per-row bad bits + finite-coordinate normsq."""
    leaves = [np.asarray(updates["a"]), np.asarray(updates["b"])]
    m = leaves[0].shape[0]
    bad = np.zeros(m, bool)
    nsq = np.zeros(m, np.float64)
    for u in leaves:
        flat = u.reshape(m, -1).astype(np.float64)
        fin = np.isfinite(flat)
        bad |= ~fin.all(axis=1)
        nsq += np.where(fin, flat, 0.0).__pow__(2).sum(axis=1)
    if mask is not None:
        bad &= mask
        nsq = np.where(mask, nsq, 0.0)
    return bad, nsq


def test_sentinel_vmap_matches_host_oracle():
    cfg = Config(health="on")
    upd = _updates(bad_rows=(1, 4), inf_row=2)
    params = {"w": jnp.ones((2, 2)), "b": jnp.zeros(3)}
    out = jax.jit(lambda u: sentinel.sentinel(cfg, u, params))(upd)
    bad, nsq = _oracle(upd)
    assert float(out["hlth_nonfinite"]) == bad.sum() == 3
    assert np.allclose(float(out["hlth_update_normsq"]), nsq.sum(),
                       rtol=1e-5)
    assert float(out["hlth_params_finite"]) == 1.0
    np.testing.assert_array_equal(np.asarray(out["hlth_agent_bad"]), bad)

    # masked-out rows are handled faults, not health incidents
    mask = np.array([True, False, True, True, True, True])
    out_m = sentinel.sentinel(cfg, upd, params, mask=jnp.asarray(mask))
    bad_m, nsq_m = _oracle(upd, mask)
    assert float(out_m["hlth_nonfinite"]) == bad_m.sum() == 2
    assert np.allclose(float(out_m["hlth_update_normsq"]), nsq_m.sum(),
                       rtol=1e-5)

    # a NaN in the committed params flips the finite bit
    bad_params = {"w": jnp.ones((2, 2)).at[0, 0].set(jnp.nan),
                  "b": jnp.zeros(3)}
    assert float(sentinel.params_finite_bit(bad_params)) == 0.0


def test_sentinel_sharded_lanes_match_vmap():
    """local_lanes summed across fake shards (the psum's arithmetic) +
    finish_sharded reproduces the vmap sentinel's scalars exactly."""
    cfg = Config(health="on")
    upd = _updates(m=8, bad_rows=(0, 5), inf_row=6)
    params = {"w": jnp.ones(4)}
    full = sentinel.sentinel(cfg, upd, params)
    lanes = jnp.zeros(2)
    for s in range(4):   # 4 shards x 2 agents, the shard_map row split
        shard = {k: v[2 * s: 2 * s + 2] for k, v in upd.items()}
        lanes = lanes + sentinel.local_lanes(shard)
    packed = sentinel.finish_sharded(lanes[0], lanes[1], params)
    assert float(packed["hlth_nonfinite"]) == float(full["hlth_nonfinite"])
    assert np.allclose(float(packed["hlth_update_normsq"]),
                       float(full["hlth_update_normsq"]), rtol=1e-6)
    assert "hlth_agent_bad" not in packed   # sharded set excludes it


def test_health_keys_static_sets():
    on = Config(health="on")
    assert sentinel.health_keys(on) == (
        "hlth_nonfinite", "hlth_params_finite", "hlth_update_normsq",
        "hlth_agent_bad")
    assert sentinel.health_keys(on, sharded=True) == (
        "hlth_nonfinite", "hlth_params_finite", "hlth_update_normsq")
    assert "hlth_agent_bad" not in sentinel.boundary_keys(on)
    assert sentinel.health_keys(Config(health="off")) == ()


def test_ema_z_spike_host_math():
    s = sentinel.ema_init()
    # warmup: no z, no spike, whatever the values
    assert sentinel.loss_z(s, 100.0) == 0.0
    assert not sentinel.norm_spike(s, 1e9, 10.0)
    for loss, norm in ((2.0, 1.0), (1.9, 1.1), (1.8, 1.0)):
        s = sentinel.ema_update(s, loss, norm)
    assert s["n"] == 3
    # post-warmup z matches the closed form
    want = (5.0 - s["loss_ema"]) / np.sqrt(s["loss_var"] + 1e-12)
    assert np.isclose(sentinel.loss_z(s, 5.0), want)
    assert sentinel.loss_z(s, float("nan")) == 0.0   # stays readable
    assert sentinel.norm_spike(s, 20 * s["norm_ema"], 10.0)
    assert not sentinel.norm_spike(s, 5 * s["norm_ema"], 10.0)
    # delta lane: fed only by the ladder; baseline 0.0 never fires
    assert not sentinel.delta_spike(s, 1e9, 10.0)
    s2 = sentinel.ema_update(s, 1.8, 1.0, delta=2.0)
    assert s2["delta_ema"] == 2.0
    assert sentinel.delta_spike(s2, 50.0, 10.0)
    assert not sentinel.delta_spike(s2, 10.0, 10.0)


def test_assess_judges_and_incident_does_not_move_baseline():
    cfg = Config(health="on")
    state = sentinel.ema_init()
    base = {"hlth_nonfinite": 0.0, "hlth_params_finite": 1.0,
            "hlth_update_normsq": 4.0, "train_loss": 2.0, "finite": True}
    for _ in range(4):
        r = monitor.assess(cfg, state, base)
        assert r["healthy"]
        state = r["new_state"]
    # nonfinite updates are an incident; the EMA must not fold it
    r = monitor.assess(cfg, state, {**base, "hlth_nonfinite": 3.0})
    assert not r["healthy"] and "3 nonfinite" in r["why"]
    assert r["new_state"] == state
    assert r["rows"]["nonfinite"] == 3.0
    # params-finite bit drop
    r = monitor.assess(cfg, state, {**base, "hlth_params_finite": 0.0})
    assert not r["healthy"] and not r["finite"]
    # loss z breach
    r = monitor.assess(cfg, state, {**base, "train_loss": 500.0})
    assert not r["healthy"] and "z-score" in r["why"]
    # committed-delta spike (the ladder-only lane)
    state_d = dict(state)
    for _ in range(2):
        state_d = monitor.assess(
            cfg, state_d, {**base, "hlth_delta_norm": 1.0})["new_state"]
    r = monitor.assess(cfg, state_d,
                       {**base, "hlth_delta_norm": 100.0})
    assert not r["healthy"] and "committed-delta" in r["why"]
    # a finite-coordinate burst that OVERFLOWS the squared-norm mass to
    # inf carries zero nonfinite rows and an isfinite-gated spike bit —
    # it must still be an incident, not a silent pass
    r = monitor.assess(cfg, state,
                       {**base, "hlth_update_normsq": float("inf")})
    assert not r["healthy"] and "overflow" in r["why"]
    r = monitor.assess(cfg, state,
                       {**base, "hlth_delta_norm": float("inf")})
    assert not r["healthy"] and "committed-delta" in r["why"]
    # --health off: only the boundary finite bit is judged, no rows
    r_off = monitor.assess(Config(health="off"), None, {"finite": False})
    assert not r_off["healthy"] and r_off["rows"] == {}


def test_policy_resolution_and_enforce():
    assert monitor.resolve_policy(Config(health_policy="record")) == \
        "record"
    # --debug_nan keeps its historical hard-abort contract
    assert monitor.resolve_policy(
        Config(health_policy="record", debug_nan=True)) == "abort"
    bad = {"rows": {}, "healthy": False, "finite": False, "why": "nan"}
    with pytest.raises(FloatingPointError):
        monitor.enforce(Config(health_policy="abort"), bad)
    assert monitor.enforce(Config(health_policy="record"), bad) is False
    # a soft incident (finite but unhealthy) aborts only under abort
    soft = {"rows": {}, "healthy": False, "finite": True, "why": "z"}
    with pytest.raises(monitor.HealthIncident):
        monitor.enforce(Config(health_policy="abort"), soft)
    assert monitor.enforce(Config(health_policy="recover"), soft) is False
    with pytest.raises(ValueError, match="health_policy"):
        monitor.check(Config(health_policy="bogus"))
    with pytest.raises(ValueError, match="comma-separated"):
        monitor.check(Config(quarantine="1,x"))
    # non-empty but zero ids ("," etc.) is an operator mistake: check
    # refuses it, and has_quarantine never half-arms the mask path
    with pytest.raises(ValueError, match="no client ids"):
        monitor.check(Config(quarantine=","))
    assert not sentinel.has_quarantine(Config(quarantine=","))


# --- quarantine mask: the churn participation-mask protocol ---------------


def test_quarantine_mask_bitwise_vs_membership_oracle():
    cfg = Config(quarantine="3,11,5")
    assert sentinel.quarantine_ids(cfg) == (3, 5, 11)
    sampled = jnp.asarray([7, 3, 5, 0, 11, 3], dtype=jnp.int32)
    mask = sentinel.quarantine_mask(cfg, sampled)
    oracle = ~np.isin(np.asarray(sampled), [3, 5, 11])
    np.testing.assert_array_equal(np.asarray(mask), oracle)
    # jit parity (it runs inside the traced round program)
    np.testing.assert_array_equal(
        np.asarray(jax.jit(
            lambda s: sentinel.quarantine_mask(cfg, s))(sampled)), oracle)
    # joins the churn protocol bitwise: same dtype/shape, composed by &
    ccfg = Config(churn_available=0.6, churn_period=3, num_agents=64,
                  quarantine="3,11,5")
    active = churn_mod.active_slots(ccfg, sampled, 4)
    composed = np.asarray(active & mask)
    np.testing.assert_array_equal(
        composed, np.asarray(active) & oracle)
    assert composed.dtype == np.asarray(active).dtype
    assert sentinel.quarantine_mask(Config(), sampled) is None


def test_quarantine_refused_in_host_sampled_mode():
    from defending_against_backdoors_with_robust_learning_rate_tpu.fl import (
        rounds as fl_rounds)
    cfg = Config(host_sampled="on", quarantine="2", num_agents=64)
    with pytest.raises(ValueError, match="host-sampled"):
        fl_rounds.make_host_step(cfg, None, None)


# --- the ladder: deterministic walk + persistence -------------------------


def test_ladder_walk_is_deterministic(tmp_path):
    cfg = Config(health_policy="recover",
                 checkpoint_dir=str(tmp_path / "ck"))
    lad = monitor.HealthLadder(cfg)
    assert lad.next_rung(cfg) == "discard"
    lad.record("discard", 5)
    assert lad.next_rung(cfg) == "rollback"
    lad.record("rollback", 5)
    assert lad.next_rung(cfg) == "quarantine"
    # the host-sampled path cannot quarantine -> the walk skips to halt
    assert lad.next_rung(cfg, quarantine_ok=False) == "halt"
    lad.record("quarantine", 5)
    assert lad.next_rung(cfg) == "halt"
    # no checkpoint dir: rollback AND quarantine are unreachable (both
    # re-enter through the checkpoint-restore machinery — without it a
    # re-entry would silently restart from round 0)
    nock = Config(health_policy="recover")
    lad2 = monitor.HealthLadder(nock)
    lad2.record("discard", 1)
    assert lad2.next_rung(nock) == "halt"
    # a healthy boundary closes the episode; cumulative counters persist
    r = monitor.assess(cfg, None, {"finite": True})
    lad.note_healthy(r)
    assert lad.state["episode"]["open"] is False
    assert lad.next_rung(cfg) == "discard"
    assert lad.counters == {"discard": 1, "rollback": 1,
                            "quarantine": 1, "halt": 0}


def test_ladder_state_persists_across_instances(tmp_path):
    path = str(tmp_path / "health_state.json")
    cfg = Config(health_policy="recover")
    lad = monitor.HealthLadder(cfg, state_path=path)
    lad.record("discard", 3)
    lad.record("rollback", 3)
    # a new instance (= a new process life) resumes the ladder mid-walk
    lad2 = monitor.HealthLadder(cfg, state_path=path)
    assert lad2.state["episode"] == {"discards": 1, "rollbacks": 1,
                                     "quarantines": 0, "open": True}
    assert lad2.next_rung(cfg.replace(checkpoint_dir="ck")) == "quarantine"
    # a prior QUARANTINE re-entry's --quarantine joins the record
    # (run_name ignores --quarantine, so the stamp still matches)
    lad3 = monitor.HealthLadder(cfg.replace(quarantine="7,2"),
                                state_path=path)
    assert set(lad3.state["quarantined"]) == {2, 7}
    # a DIFFERENT run sharing the log_dir must NOT inherit this ladder's
    # EMA/budgets/quarantine record — the run stamp discards it
    other = monitor.HealthLadder(cfg.replace(seed=99), state_path=path)
    assert other.state["episode"]["open"] is False
    assert other.state["quarantined"] == []


def test_chaos_numerics_grammar():
    inj = chaos_mod.parse_spec(
        "nan@5x2,spike@3:25,bank_corrupt@0,kill_recover@4")
    assert [(i.action, i.rnd, i.count, i.arg) for i in inj] == [
        ("nan", 5, 2, 0.0), ("spike", 3, 1, 25.0),
        ("bank_corrupt", 0, 1, 0.0), ("kill_recover", 4, 1, 0.0)]


# --- serve() drills -------------------------------------------------------

SVC = Config(data="synthetic", num_agents=8, bs=16, local_ep=1,
             synth_train_size=256, synth_val_size=64, eval_bs=64,
             snap=2, seed=5, tensorboard=False, num_corrupt=2,
             poison_frac=1.0, robustLR_threshold=3,
             service_backoff_s=0.01)

# single source (ISSUE 15 satellite): obs/constants.py owns the list
from defending_against_backdoors_with_robust_learning_rate_tpu.obs.constants import (  # noqa: E402
    NON_TIMING_PREFIXES as EXCLUDE)


def _cfg(tmp_path, tag, **kw):
    return SVC.replace(log_dir=str(tmp_path / f"{tag}_logs"),
                       checkpoint_dir=str(tmp_path / f"{tag}_ck"), **kw)


def _lines(cfg):
    path = os.path.join(cfg.log_dir, run_name(cfg), "metrics.jsonl")
    return [l for l in open(path)
            if not any(json.loads(l)["tag"].startswith(p)
                       for p in EXCLUDE)]


def _tags(cfg):
    return {json.loads(l)["tag"] for l in _lines(cfg)}


def test_serve_refuses_recover_with_rlr_adapt(tmp_path):
    """An adapted segment's live stream sits at the ORIGINAL threshold's
    run_name; a ladder re-entry inside it would splice a phantom path —
    the combination is refused loudly before any build."""
    cfg = SVC.replace(log_dir=str(tmp_path / "logs"),
                      checkpoint_dir=str(tmp_path / "ck"),
                      service_rounds=2, health_policy="recover",
                      rlr_adapt="on", telemetry="full")
    with pytest.raises(ValueError, match="rlr_adapt"):
        serve(cfg)


def test_serve_nan_recovers_via_rollback_byte_identical(tmp_path):
    """THE ladder drill (vmap twin of the 8-way one): a seeded NaN
    burst DISCARDs, escalates to ROLLBACK (the restored prev_params were
    poisoned too), replays clean — rc 0, journaled phases, and a final
    stream byte-identical to the uninjected twin."""
    cfg_a = _cfg(tmp_path, "a", service_rounds=6)
    serve(cfg_a)
    cfg_b = _cfg(tmp_path, "b", service_rounds=6,
                 chaos="nan@3", health_policy="recover")
    summary = serve(cfg_b)
    hs = summary["service"]["health"]
    assert hs["health_discards"] == 1 and hs["health_rollbacks"] == 1
    assert hs["health_quarantines"] == 0 and hs["incidents"] == 2
    # DISTINCT rounds: the rollback replay must not double-count the
    # replayed window (outer served 1-4, inner resumed from 2 -> 3-6)
    assert summary["service"]["rounds_served"] == 6
    assert _lines(cfg_b) == _lines(cfg_a)   # includes the Health/* rows
    assert "Health/Params_Finite" in _tags(cfg_b)
    status = json.load(open(os.path.join(cfg_b.log_dir, "status.json")))
    assert ["health_discard", "health_rollback", "recover"] == [
        p for p in status["service_phases"]
        if p.startswith(("health_", "recover"))]
    state = json.load(open(os.path.join(cfg_b.log_dir,
                                        "health_state.json")))
    assert state["episode"]["open"] is False   # healthy boundary closed it


def test_serve_persistent_fault_escalates_to_quarantine_then_halt(
        tmp_path):
    """A fault with fire budget left re-poisons every replay: the walk
    must spend DISCARD -> ROLLBACK -> QUARANTINE and HALT loudly with
    the journal intact and every transition counted."""
    cfg = _cfg(tmp_path, "h", service_rounds=6,
               chaos="nan@3x9", health_policy="recover")
    with pytest.raises(UnitFailure, match="health ladder exhausted"):
        serve(cfg)
    state = json.load(open(os.path.join(cfg.log_dir,
                                        "health_state.json")))
    assert state["counters"] == {"discard": 1, "rollback": 1,
                                 "quarantine": 1, "halt": 1}
    assert state["quarantined"]   # suspect evidence reached the record
    status = json.load(open(os.path.join(cfg.log_dir, "status.json")))
    assert {"health_discard", "health_rollback", "health_quarantine",
            "health_halt"} <= set(status["service_phases"])


def test_serve_record_policy_keeps_metrics_flowing(tmp_path):
    """The sweep default: a NaN cell is recorded-and-skipped — the run
    COMPLETES, Health/* rows mark the damage, no ladder arms."""
    cfg = _cfg(tmp_path, "r", service_rounds=6,
               chaos="nan@3", health_policy="record")
    summary = serve(cfg)
    assert "health" not in summary["service"]   # no ladder under record
    rows = {(json.loads(l)["tag"], json.loads(l)["step"]):
            json.loads(l)["value"] for l in _lines(cfg)}
    assert rows[("Health/Params_Finite", 2)] == 1.0
    assert rows[("Health/Params_Finite", 4)] == 0.0   # damage recorded
    assert rows[("Health/Params_Finite", 6)] == 0.0   # ...and kept going
    # the boundary verdict rides the engine summary for queue rows
    assert summary["health"]["params_finite"] == 0.0


def test_serve_spike_heals_in_place_at_discard(tmp_path):
    """A finite magnitude burst in the COMMIT (chaos spike@N) trips the
    ladder's committed-delta lane at the same boundary — before the
    checkpoint — and heals at the DISCARD rung (re-dispatch with the
    recovery nonce; the injection's fire budget is spent)."""
    cfg = _cfg(tmp_path, "s", service_rounds=10, snap=1,
               chaos="spike@6:40", health_policy="recover")
    summary = serve(cfg)
    hs = summary["service"]["health"]
    assert hs["health_discards"] == 1 and hs["health_rollbacks"] == 0
    state = json.load(open(os.path.join(cfg.log_dir,
                                        "health_state.json")))
    assert state["episode"]["open"] is False


def test_resume_from_mid_rollback_state_resumes_ladder(tmp_path):
    """Kill-mid-rollback, the cheap in-process twin (true-SIGKILL twin
    below is slow-gated): reproduce on disk exactly what a kill between
    the ladder's rollback RECORD and the completed re-entry leaves —
    rung counted, episode open, injection spent — then serve. The
    resumed process must pick the LADDER up (close the episode at the
    first healthy boundary), not re-meet the failure, and the stream
    must stay byte-identical to the uninjected twin."""
    cfg_a = _cfg(tmp_path, "a", service_rounds=6)
    serve(cfg_a)
    cfg_b = _cfg(tmp_path, "b", service_rounds=6,
                 chaos="nan@3", health_policy="recover")
    # life 1 equivalent, up to the kill: rounds 1-2 served + checkpointed
    serve(cfg_b.replace(chaos=""), max_rounds=2)
    os.makedirs(cfg_b.log_dir, exist_ok=True)
    with open(os.path.join(cfg_b.log_dir, "health_state.json"),
              "w") as f:
        # the run stamp is what a real kill leaves: state from a
        # DIFFERENT run would be discarded, not resumed
        json.dump({"run": run_name(cfg_b),
                   "ema": sentinel.ema_update(
                       sentinel.ema_init(), 2.2, 2.2),
                   "episode": {"discards": 1, "rollbacks": 1,
                               "quarantines": 0, "open": True},
                   "counters": {"discard": 1, "rollback": 1,
                                "quarantine": 0, "halt": 0},
                   "quarantined": [], "incidents": 2}, f)
    with open(os.path.join(cfg_b.log_dir, "chaos_state.json"),
              "w") as f:
        json.dump({"nan@3": 1}, f)   # the injection is spent
    summary = serve(cfg_b)                      # life 2
    hs = summary["service"]["health"]
    assert hs["health_rollbacks"] == 1          # carried, not re-walked
    assert _lines(cfg_b) == _lines(cfg_a)
    state = json.load(open(os.path.join(cfg_b.log_dir,
                                        "health_state.json")))
    assert state["episode"]["open"] is False


def test_serve_rearms_journaled_quarantine_set(tmp_path):
    """A kill AFTER a QUARANTINE rung was recorded but BEFORE its
    re-entry completed leaves the suspect set only in health_state.json
    — a fresh serve must re-arm it (the suspects stay out of the
    electorate; the ladder resumes, not the failure)."""
    cfg = _cfg(tmp_path, "q", service_rounds=2,
               health_policy="recover")
    os.makedirs(cfg.log_dir, exist_ok=True)
    with open(os.path.join(cfg.log_dir, "health_state.json"),
              "w") as f:
        json.dump({"run": run_name(cfg),
                   "ema": sentinel.ema_init(),
                   "episode": {"discards": 1, "rollbacks": 1,
                               "quarantines": 1, "open": True},
                   "counters": {"discard": 1, "rollback": 1,
                                "quarantine": 1, "halt": 0},
                   "quarantined": [5], "incidents": 3}, f)
    summary = serve(cfg)
    assert summary["service"]["health"]["quarantined"] == [5]


def test_serve_nan_recovers_on_8way_shard_map(tmp_path):
    base = dict(service_rounds=6, mesh=8)
    cfg_a = _cfg(tmp_path, "a", **base)
    serve(cfg_a)
    cfg_b = _cfg(tmp_path, "b", chaos="nan@3",
                 health_policy="recover", **base)
    summary = serve(cfg_b)
    hs = summary["service"]["health"]
    assert hs["health_rollbacks"] == 1
    assert _lines(cfg_b) == _lines(cfg_a)
    status = json.load(open(os.path.join(cfg_b.log_dir, "status.json")))
    assert {"health_discard", "health_rollback"} <= \
        set(status["service_phases"])


@pytest.mark.slow  # three cold subprocess interpreters; the in-process
# mid-rollback resume above pins the same state machinery in tier-1
def test_service_kill_mid_rollback_subprocess_drill(tmp_path):
    """True SIGKILL in the rollback window (--chaos kill_recover@4):
    life 1 dies with the rung recorded and the episode open; life 2 must
    resume the ladder, replay clean and match the uninjected twin."""
    pkg = "defending_against_backdoors_with_robust_learning_rate_tpu"
    args = [sys.executable, "-m", f"{pkg}.service.driver",
            "--data", "synthetic", "--num_agents", "8", "--bs", "16",
            "--local_ep", "1", "--synth_train_size", "256",
            "--synth_val_size", "64", "--eval_bs", "64", "--snap", "2",
            "--num_corrupt", "2", "--poison_frac", "1.0",
            "--robustLR_threshold", "3", "--seed", "5",
            "--no_tensorboard", "--service_rounds", "6",
            "--service_backoff_s", "0.01"]
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}

    def drill(tag, extra):
        cmd = args + ["--log_dir", str(tmp_path / f"{tag}_logs"),
                      "--checkpoint_dir", str(tmp_path / f"{tag}_ck")] \
            + extra
        return subprocess.run(cmd, env=env, capture_output=True,
                              text=True, timeout=600)

    assert drill("a", []).returncode == 0
    chaos = ["--chaos", "nan@3,kill_recover@4",
             "--health_policy", "recover"]
    first = drill("b", chaos)
    assert first.returncode == -signal.SIGKILL
    mid = json.load(open(tmp_path / "b_logs" / "health_state.json"))
    assert mid["episode"]["open"] and mid["counters"]["rollback"] == 1
    second = drill("b", chaos)
    assert second.returncode == 0, second.stderr[-2000:]

    def lines(tag):
        cfg = SVC.replace(log_dir=str(tmp_path / f"{tag}_logs"),
                          service_rounds=6)
        return _lines(cfg)

    assert lines("b") == lines("a")
    final = json.load(open(tmp_path / "b_logs" / "health_state.json"))
    assert final["episode"]["open"] is False
    assert final["counters"]["rollback"] == 1


# --- data-plane integrity: bank sha256 sidecars ---------------------------


def _small_bank(tmp_path, tag="bank"):
    labels = np.tile(np.arange(10), 40)   # 400 rows
    d = str(tmp_path / tag)
    bank_mod.build_bank(d, labels, population=64, partitioner="dirichlet",
                        samples_per_client=12, seed=3, shard_clients=16,
                        log=lambda *a, **k: None)
    return d


def test_bank_digest_sidecars_written_and_verified(tmp_path):
    d = _small_bank(tmp_path)
    shards = sorted(n for n in os.listdir(d)
                    if n.startswith("indices-") and n.endswith(".bin"))
    assert len(shards) == 4           # 64 clients / 16 per shard
    for n in shards:                  # one sidecar per shard, published
        assert os.path.exists(os.path.join(d, n + ".sha256"))
    assert bank_mod.verify_digests(d, log=lambda *a, **k: None) == 4
    # sidecar content is the real file hash (the build streamed it)
    want = open(os.path.join(d, shards[0] + ".sha256")).read().strip()
    assert bank_mod._file_sha256(os.path.join(d, shards[0])) == want


def test_bank_corruption_detected_loudly_naming_the_shard(tmp_path):
    d = _small_bank(tmp_path)
    victim = os.path.join(d, "indices-00002.bin")
    with open(victim, "r+b") as f:
        f.seek(os.path.getsize(victim) // 2)
        f.write(b"\xde\xad\xbe\xef")
    with pytest.raises(bank_mod.BankCorrupted) as e:
        bank_mod.verify_digests(d, log=lambda *a, **k: None)
    assert "indices-00002.bin" in str(e.value)   # names the shard
    # get_or_build(verify=True) must stay loud, never silently rebuild
    labels = np.tile(np.arange(10), 40)
    key = json.load(open(os.path.join(d, "meta.json")))["key"]
    with pytest.raises(bank_mod.BankCorrupted):
        bank_mod.get_or_build(
            d, labels, population=64, partitioner="dirichlet",
            samples_per_client=12, dirichlet_alpha=0.5,
            classes_per_client=2, seed=3, n_classes=10,
            shard_clients=16, key=key, verify=True,
            log=lambda *a, **k: None)
    # without --bank_verify the open trusts the bytes (status quo)
    bank, built = bank_mod.get_or_build(
        d, labels, population=64, partitioner="dirichlet",
        samples_per_client=12, dirichlet_alpha=0.5,
        classes_per_client=2, seed=3, n_classes=10,
        shard_clients=16, key=key, verify=False,
        log=lambda *a, **k: None)
    assert not built


def test_chaos_bank_corrupt_drill_pins_detection(tmp_path):
    """The chaos injector flips bytes in the @N-th shard; a verifying
    open must then fail naming that shard — and the injection's fire
    count persists (a resumed life does not re-corrupt)."""
    d = _small_bank(tmp_path)
    ch = chaos_mod.Chaos("bank_corrupt@1",
                         state_path=str(tmp_path / "chaos_state.json"))
    assert ch.corrupt_bank(str(tmp_path))
    with pytest.raises(bank_mod.BankCorrupted, match="indices-00001"):
        bank_mod.verify_digests(d, log=lambda *a, **k: None)
    ch2 = chaos_mod.Chaos("bank_corrupt@1",
                          state_path=str(tmp_path / "chaos_state.json"))
    assert not ch2.corrupt_bank(str(tmp_path))   # spent
