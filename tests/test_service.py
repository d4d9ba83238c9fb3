"""Continuous-service subsystem tests (ISSUE 6): churn lifecycles,
supervised retry/backoff, chaos injection, checkpoint hardening, and the
crash-exact resume drills.

The acceptance drills: an interrupted-and-resumed service run produces a
metrics.jsonl byte-identical (modulo wall-clock rows) to an uninterrupted
run's, on both the vmap and the 8-device shard_map paths. Tier-1 drives
the interruption in-process (abandon mid-round after un-journaled rows —
exactly the on-disk state a kill -9 leaves); the true SIGKILL drill runs
as a subprocess test and in the CI service-mode smoke job.
"""

import itertools
import json
import os
import signal
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from defending_against_backdoors_with_robust_learning_rate_tpu.config import Config
from defending_against_backdoors_with_robust_learning_rate_tpu.service import (
    chaos as chaos_mod)
from defending_against_backdoors_with_robust_learning_rate_tpu.service import (
    churn as churn_mod)
from defending_against_backdoors_with_robust_learning_rate_tpu.service.driver import (
    prepare_crash_exact_resume, serve)
from defending_against_backdoors_with_robust_learning_rate_tpu.service.queue import (
    load_cells, run_queue)
from defending_against_backdoors_with_robust_learning_rate_tpu.service.supervisor import (
    POISONED, TRANSIENT, WEDGED, Supervisor, UnitFailure, classify)
from defending_against_backdoors_with_robust_learning_rate_tpu.train import (
    RoundEngine)
from defending_against_backdoors_with_robust_learning_rate_tpu.utils import (
    checkpoint as ckpt)
from defending_against_backdoors_with_robust_learning_rate_tpu.utils.metrics import (
    MetricsWriter, run_name)

# --- churn lifecycles ----------------------------------------------------


def _churn_cfg(**kw):
    return Config(**{"data": "synthetic", "num_agents": 8,
                     "churn_available": 0.7, "churn_period": 4, **kw})


def test_churn_mask_pure_and_jit_parity():
    """active_slots is a pure function of (cfg, ids, round): repeated and
    traced evaluations agree bit-for-bit — the property that makes crash
    recovery exact (a resumed run reconstructs the identical lifecycle
    history from the config alone)."""
    cfg = _churn_cfg()
    ids = jnp.arange(cfg.num_agents)
    host = np.asarray(churn_mod.active_slots(cfg, ids, 7))
    again = np.asarray(churn_mod.active_slots(cfg, ids, 7))
    traced = np.asarray(
        jax.jit(lambda r: churn_mod.active_slots(cfg, ids, r))(
            jnp.int32(7)))
    np.testing.assert_array_equal(host, again)
    np.testing.assert_array_equal(host, traced)


def test_churn_departures_persist_for_whole_phases():
    """Unlike the memoryless per-round fault dropout, a churn
    absence/presence lasts a whole lifecycle phase: over R rounds each
    client flips availability at most ceil(R/period)+1 times (only at its
    phase boundaries)."""
    cfg = _churn_cfg(churn_available=0.5, churn_period=8)
    rounds = 32
    ids = jnp.arange(cfg.num_agents)
    tl = np.stack([np.asarray(churn_mod.active_slots(cfg, ids, r))
                   for r in range(rounds)])          # [rounds, K]
    flips = (tl[1:] != tl[:-1]).sum(axis=0)
    assert (flips <= rounds // cfg.churn_period + 1).all(), flips
    # and the population actually churns (some client flips at least once)
    assert flips.sum() > 0


def test_churn_availability_fraction_and_seed():
    """Presence frequency tracks churn_available, and churn_seed re-draws
    the lifecycles without touching any training stream (it keys an
    independent PRNG stream)."""
    cfg = _churn_cfg(num_agents=64, churn_available=0.7, churn_period=2)
    ids = jnp.arange(cfg.num_agents)
    tl = np.stack([np.asarray(churn_mod.active_slots(cfg, ids, r))
                   for r in range(0, 64, 2)])
    frac = tl.mean()
    assert 0.55 < frac < 0.85, frac
    other = np.stack([np.asarray(churn_mod.active_slots(
        cfg.replace(churn_seed=1), ids, r)) for r in range(0, 64, 2)])
    assert (tl != other).any()
    # availability 1.0 is structurally dense: every draw clears p
    all_on = churn_mod.active_slots(
        cfg.replace(churn_available=1.0), ids, 3)
    assert bool(jnp.all(all_on))
    assert not cfg.replace(churn_available=1.0).churn_enabled


def test_churn_full_cohort_round_matches_dense_bitwise():
    """The zero-overhead claim at the round level: at a round where every
    sampled client happens to be present, the churn round program's output
    is bit-identical to the dense (churn-free) program's."""
    from defending_against_backdoors_with_robust_learning_rate_tpu.data.registry import (
        get_federated_data)
    from defending_against_backdoors_with_robust_learning_rate_tpu.fl.common import (
        make_normalizer)
    from defending_against_backdoors_with_robust_learning_rate_tpu.fl.rounds import (
        make_round_fn)
    from defending_against_backdoors_with_robust_learning_rate_tpu.models.registry import (
        get_model, init_params)

    cfg = Config(data="synthetic", num_agents=8, bs=16, local_ep=1,
                 synth_train_size=256, synth_val_size=64, num_corrupt=2,
                 poison_frac=1.0, robustLR_threshold=3,
                 churn_available=0.85, churn_period=3)
    # a round where the whole population is present (the census is the
    # host-side mirror of the in-program draw, so this is exact)
    full = next(r for r in range(1, 200)
                if churn_mod.active_count(cfg, r) == cfg.num_agents)
    fed = get_federated_data(cfg)
    model = get_model(cfg.data, cfg.model_arch, cfg.dtype)
    norm = make_normalizer(fed.mean, fed.std, fed.raw_is_normalized)
    arrays = tuple(map(jnp.asarray, (fed.train.images, fed.train.labels,
                                     fed.train.sizes)))
    params = init_params(model, fed.train.images.shape[2:],
                         jax.random.PRNGKey(0))
    key = jax.random.fold_in(jax.random.PRNGKey(cfg.seed + 1), full)
    p_churn, info = make_round_fn(cfg, model, norm, *arrays)(
        params, key, jnp.int32(full))
    p_dense, _ = make_round_fn(cfg.replace(churn_available=1.0), model,
                               norm, *arrays)(params, key)
    assert float(info["churn_away"]) == 0.0
    for a, b in zip(jax.tree_util.tree_leaves(p_churn),
                    jax.tree_util.tree_leaves(p_dense), strict=True):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_churn_host_sampled_refused():
    """Churn + host-sampled mode fails loudly (the host step has no round
    lead; silently running churn-free would corrupt the experiment)."""
    from defending_against_backdoors_with_robust_learning_rate_tpu.fl.common import (
        make_normalizer)
    from defending_against_backdoors_with_robust_learning_rate_tpu.fl.rounds import (
        make_round_fn_host)
    from defending_against_backdoors_with_robust_learning_rate_tpu.models.registry import (
        get_model)

    cfg = _churn_cfg(bs=16, local_ep=1)
    model = get_model(cfg.data, cfg.model_arch, cfg.dtype)
    norm = make_normalizer(np.zeros(1), np.ones(1), True)
    with pytest.raises(ValueError, match="churn"):
        make_round_fn_host(cfg, model, norm)


# --- supervisor ----------------------------------------------------------


def test_classify_failure_classes():
    assert classify(TimeoutError("x")) == WEDGED
    assert classify(RuntimeError("UNAVAILABLE: backend")) == TRANSIENT
    assert classify(RuntimeError("please retry later")) == TRANSIENT
    # status names match case-sensitively: lowercase prose "unavailable"
    # alone is not the gRPC constant, and carries no other signature
    assert classify(ValueError("service momentarily unavailabl_")) \
        == POISONED
    assert classify(ValueError("shape mismatch [8] vs [4]")) == POISONED


def test_supervisor_transient_retries_with_exponential_backoff():
    sleeps = []
    sup = Supervisor(retries=3, backoff_s=0.25, sleep=sleeps.append)
    calls = itertools.count()

    def flaky():
        if next(calls) < 2:
            raise RuntimeError("UNAVAILABLE: injected")
        return 42

    assert sup.run("dispatch", flaky, unit=5) == 42
    assert sleeps == [0.25, 0.5]        # deterministic, doubling
    assert sup.counters["retries"] == 2
    assert sup.counters["transient"] == 2
    assert sup.counters["gave_up"] == 0
    assert "retry" in sup.phases_seen and "backoff" in sup.phases_seen


def test_supervisor_poisoned_fails_fast():
    sleeps = []
    sup = Supervisor(retries=3, sleep=sleeps.append)
    with pytest.raises(UnitFailure) as ei:
        sup.run("dispatch", lambda: (_ for _ in ()).throw(
            ValueError("NaN divergence")), unit=2)
    assert ei.value.classification == POISONED
    assert ei.value.attempts == 1       # no retry of a deterministic error
    assert sleeps == []
    assert sup.counters["gave_up"] == 1
    assert "degraded" in sup.phases_seen


def test_supervisor_retry_budget_exhausts():
    sup = Supervisor(retries=2, backoff_s=0.0, sleep=lambda s: None)

    def always_wedged():
        raise TimeoutError("drain stalled")

    with pytest.raises(UnitFailure) as ei:
        sup.run("checkpoint", always_wedged, unit=4)
    assert ei.value.classification == WEDGED
    assert ei.value.attempts == 3       # 1 + retries
    assert sup.counters["wedged"] == 3
    assert sup.counters["retries"] == 2


def test_supervisor_flags_slow_units_without_retrying():
    """A unit that COMPLETES past its deadline is recorded as slow (the
    degradation signal), not re-run — the work is done."""
    clock = iter([0.0, 5.0]).__next__
    sup = Supervisor(retries=3, deadline_s=1.0, clock=clock,
                     sleep=lambda s: None)
    assert sup.run("eval", lambda: "ok", unit=1) == "ok"
    assert sup.counters["slow_units"] == 1
    assert sup.counters["retries"] == 0
    assert "slow" in sup.phases_seen


def test_supervisor_keyboard_interrupt_propagates():
    """^C is the operator, not a failure: no classification, no retry."""
    sup = Supervisor(retries=3, sleep=lambda s: None)
    with pytest.raises(KeyboardInterrupt):
        sup.run("dispatch",
                lambda: (_ for _ in ()).throw(KeyboardInterrupt()))
    assert sup.counters["retries"] == 0
    assert sup.counters["gave_up"] == 0


def test_supervisor_stall_budget_matches_heartbeat_constant():
    from defending_against_backdoors_with_robust_learning_rate_tpu.obs import (
        heartbeat as hb_mod)
    assert Supervisor().stall_budget() == hb_mod.DEFAULT_STALE_S
    assert Supervisor(deadline_s=2.5).stall_budget() == 2.5


# --- chaos injector ------------------------------------------------------


def test_chaos_spec_grammar():
    inj = chaos_mod.parse_spec("kill@7,wedge@3x2,slow_eval@2:0.4")
    assert [(i.action, i.rnd, i.count, i.arg) for i in inj] == [
        ("kill", 7, 1, 0.0), ("wedge", 3, 2, 0.0),
        ("slow_eval", 2, 1, 0.4)]
    assert chaos_mod.parse_spec("") == []
    with pytest.raises(ValueError, match="bad chaos term"):
        chaos_mod.parse_spec("explode@3")
    with pytest.raises(ValueError, match="bad chaos term"):
        chaos_mod.parse_spec("kill")


def test_chaos_fire_counts_persist_across_lives(tmp_path):
    """A fired injection stays fired after a crash: the resumed process
    reads the state file and must NOT re-fire while replaying the round —
    the whole point of the kill drill."""
    state = str(tmp_path / "chaos_state.json")
    c1 = chaos_mod.Chaos("wedge@3x2", state_path=state)
    for _ in range(2):
        with pytest.raises(chaos_mod.ChaosError, match="UNAVAILABLE"):
            c1.on_dispatch(3)
    c1.on_dispatch(3)                   # count exhausted: clean
    c2 = chaos_mod.Chaos("wedge@3x2", state_path=state)  # "next life"
    c2.on_dispatch(3)                   # persisted: still exhausted
    c2.on_dispatch(2)                   # other rounds never fire


def test_chaos_poison_refires_every_attempt(tmp_path):
    """A poisoned unit is deterministic: every retry reproduces it (the
    supervisor must fail fast, not burn the budget)."""
    c = chaos_mod.Chaos("poison@5",
                        state_path=str(tmp_path / "state.json"))
    for _ in range(3):
        with pytest.raises(chaos_mod.ChaosError):
            c.on_dispatch(5)


# --- checkpoint hardening ------------------------------------------------


def _tiny_state():
    params = {"w": np.arange(12, dtype=np.float32).reshape(3, 4),
              "b": np.ones(4, np.float32)}
    return params, jax.random.PRNGKey(7)


def _corrupt_newest(ckpt_dir):
    rnd = ckpt.saved_rounds(ckpt_dir)[-1]
    path = os.path.join(os.path.abspath(ckpt_dir), f"round_{rnd:06d}")
    victim = max((os.path.join(b, f) for b, _d, fs in os.walk(path)
                  for f in fs), key=os.path.getsize)
    with open(victim, "r+b") as f:
        f.seek(0, os.SEEK_END)
        size = f.tell()
        f.seek(max(0, size // 2))
        f.write(b"\xde\xad\xbe\xef")
    return rnd


def test_restore_falls_back_to_newest_digest_valid(tmp_path):
    """ISSUE-6 satellite: a truncated/corrupt latest checkpoint costs one
    snap interval, never the run."""
    d = str(tmp_path / "ck")
    params, key = _tiny_state()
    ckpt.save(d, 2, params, key, 0.25)
    ckpt.save(d, 4, {"w": params["w"] + 1, "b": params["b"]}, key, 0.5)
    assert ckpt.newest_valid_round(d) == 4
    bad = _corrupt_newest(d)
    assert bad == 4
    assert ckpt.digest_valid(d, 4) is False
    assert ckpt.digest_valid(d, 2) is True
    assert ckpt.newest_valid_round(d) == 2
    rnd, got, _key, cum, _nm = ckpt.restore(d, params)
    assert rnd == 2 and cum == 0.25
    np.testing.assert_array_equal(got["w"], params["w"])


def test_restore_without_sidecar_uses_legacy_trust_path(tmp_path):
    """Checkpoints written before digests existed (no sidecar) restore on
    the legacy trust-the-directory path."""
    d = str(tmp_path / "ck")
    params, key = _tiny_state()
    ckpt.save(d, 2, params, key, 0.75)
    os.remove(os.path.join(d, "round_000002.digest"))
    assert ckpt.digest_valid(d, 2) is None
    assert ckpt.newest_valid_round(d) == 2
    rnd, _p, _k, cum, _nm = ckpt.restore(d, params)
    assert rnd == 2 and cum == 0.75


def test_keep_k_prunes_checkpoints_and_sidecars(tmp_path):
    d = str(tmp_path / "ck")
    params, key = _tiny_state()
    for rnd in (2, 4, 6):
        ckpt.save(d, rnd, params, key, 0.0, keep_last=2)
    assert ckpt.saved_rounds(d) == [4, 6]
    names = set(os.listdir(d))
    assert "round_000002" not in names
    assert "round_000002.digest" not in names
    assert "round_000006.digest" in names


def test_round_journal_roundtrip_and_bounds(tmp_path):
    d = str(tmp_path / "ck")
    ckpt.journal_record(d, 2, 100)
    ckpt.journal_record(d, 4, 250)
    ckpt.journal_record(d, 4, 260)      # replace, not duplicate
    assert ckpt.journal_offset_for(d, 2) == 100
    assert ckpt.journal_offset_for(d, 4) == 260
    assert ckpt.journal_offset_for(d, 99) == 0   # unjournaled
    assert [e["round"] for e in ckpt.journal_read(d)] == [2, 4]
    ckpt.journal_record(d, 6, 400, keep_last=2)
    assert [e["round"] for e in ckpt.journal_read(d)] == [4, 6]
    # a hand-mangled journal degrades to empty, never raises
    with open(ckpt.journal_path(d), "w") as f:
        f.write("{not json")
    assert ckpt.journal_read(d) == []


def test_chaos_corrupt_checkpoint_is_detected(tmp_path):
    """service/chaos.py's corrupt_ckpt flips bytes but leaves the sidecar:
    the restore path must DETECT it (digest mismatch) and fall back."""
    d = str(tmp_path / "ck")
    params, key = _tiny_state()
    ckpt.save(d, 2, params, key, 0.0)
    ckpt.save(d, 4, params, key, 0.0)
    c = chaos_mod.Chaos("corrupt_ckpt@4")
    assert c.corrupt_checkpoint(d, 4) is True
    assert ckpt.digest_valid(d, 4) is False
    assert ckpt.restore(d, params)[0] == 2


# --- metrics writer splice + run_name cells ------------------------------


def test_writer_offset_and_spliced_resume_stream(tmp_path):
    w = MetricsWriter(str(tmp_path), tensorboard=False)
    start = w.offset()
    assert start > 0                    # the _run/start boundary record
    w.scalar("X/Y", 1.0, 1)
    mid = w.offset()
    assert mid > start
    w.close()
    # crash-exact resume reopens with boundary=False: NO extra record, the
    # continued rows splice at the truncated offset
    w2 = MetricsWriter(str(tmp_path), tensorboard=False, boundary=False)
    assert w2.offset() == mid
    w2.close()
    tags = [json.loads(line)["tag"]
            for line in open(tmp_path / "metrics.jsonl")]
    assert tags.count("_run/start") == 1


def test_run_name_churn_cells():
    base = Config()
    assert run_name(base) == run_name(base.replace(churn_period=7,
                                                   churn_seed=3))
    a = run_name(base.replace(churn_available=0.8))
    b = run_name(base.replace(churn_available=0.8, churn_seed=3))
    assert a != run_name(base) and a != b and "chrn" in a


# --- experiment queue ----------------------------------------------------


def test_queue_load_cells_formats(tmp_path):
    p = tmp_path / "cells.json"
    p.write_text(json.dumps([{"aggr": "avg"}, {"name": "b",
                                               "overrides": {"seed": 3}}]))
    cells = load_cells(str(p))
    assert cells[0] == {"name": "cell000", "overrides": {"aggr": "avg"}}
    assert cells[1] == {"name": "b", "overrides": {"seed": 3}}
    p.write_text(json.dumps({"cells": [{"name": "x", "seed": 1}]}))
    assert load_cells(str(p))[0]["overrides"] == {"seed": 1}
    p.write_text(json.dumps({"cells": 3}))
    with pytest.raises(ValueError, match="list of cells"):
        load_cells(str(p))


def test_queue_runs_cells_and_survives_a_poisoned_one(tmp_path,
                                                      monkeypatch):
    """One poisoned cell must not abort the matrix: its row records the
    error and the queue moves on. Rows are flushed per cell (a mid-queue
    kill keeps completed rows)."""
    from defending_against_backdoors_with_robust_learning_rate_tpu import (
        train)

    def fake_run(cfg, writer=None):
        if cfg.seed == 13:
            raise RuntimeError("injected cell failure")
        return {"round": cfg.rounds, "val_acc": 0.5, "params": 10}

    monkeypatch.setattr(train, "run", fake_run)
    base = Config(log_dir=str(tmp_path))
    rows = run_queue(base, [{"name": "good", "overrides": {"seed": 1}},
                            {"name": "bad", "overrides": {"seed": 13}},
                            {"name": "tail", "overrides": {"seed": 2}}])
    assert [r["ok"] for r in rows] == [True, False, True]
    assert "injected cell failure" in rows[1]["error"]
    disk = [json.loads(line)
            for line in open(tmp_path / "queue_results.jsonl")]
    # the FINAL row is the queue-level throughput summary (ISSUE 13);
    # every cell row precedes it and carries the resolved run_name
    assert disk[-1]["queue_summary"] is True
    assert disk[-1]["cells"] == 3 and disk[-1]["ok"] == 2
    cell_rows = disk[:-1]
    assert [r["cell"] for r in cell_rows] == ["good", "bad", "tail"]
    assert all("run_name" in r for r in cell_rows)
    assert cell_rows[0]["summary"]["val_acc"] == 0.5
    with pytest.raises(ValueError, match="unknown Config fields"):
        run_queue(base, [{"name": "x", "overrides": {"nope": 1}}])


def test_queue_isolates_checkpoint_dirs_per_cell(tmp_path, monkeypatch):
    """Cells must not resume each other's checkpoints: a shared base
    checkpoint_dir gets a per-cell subdir (an explicit override wins)."""
    from defending_against_backdoors_with_robust_learning_rate_tpu import (
        train)
    seen = []

    def fake_run(cfg, writer=None):
        seen.append(cfg.checkpoint_dir)
        return {"round": cfg.rounds}

    monkeypatch.setattr(train, "run", fake_run)
    ck = str(tmp_path / "ck")
    base = Config(log_dir=str(tmp_path), checkpoint_dir=ck)
    run_queue(base, [{"name": "a", "overrides": {"seed": 1}},
                     {"name": "b", "overrides": {"seed": 2}},
                     {"name": "c", "overrides":
                         {"checkpoint_dir": str(tmp_path / "own")}}])
    assert seen == [os.path.join(ck, "a"), os.path.join(ck, "b"),
                    str(tmp_path / "own")]


# --- service driver: degradation + crash-exact resume --------------------

SVC = Config(data="synthetic", num_agents=8, bs=16, local_ep=1,
             synth_train_size=256, synth_val_size=64, eval_bs=64,
             snap=2, seed=5, tensorboard=False, num_corrupt=2,
             poison_frac=1.0, robustLR_threshold=3,
             churn_available=0.75, churn_period=3,
             service_backoff_s=0.01)

# single source (ISSUE 15 satellite): the exclusion list lives in
# obs/constants.py — it drifted once per PR while hand-duplicated here
from defending_against_backdoors_with_robust_learning_rate_tpu.obs.constants import (  # noqa: E402
    NON_TIMING_PREFIXES as EXCLUDE)


def _svc_cfg(tmp_path, tag, **kw):
    return SVC.replace(log_dir=str(tmp_path / f"{tag}_logs"),
                       checkpoint_dir=str(tmp_path / f"{tag}_ck"), **kw)


def _metric_lines(cfg):
    """metrics.jsonl lines minus the wall-clock rows — the crash-exact
    comparison set (raw strings: byte identity, not approximate)."""
    path = os.path.join(cfg.log_dir, run_name(cfg), "metrics.jsonl")
    keep = []
    for line in open(path):
        tag = json.loads(line)["tag"]
        if not any(tag.startswith(p) for p in EXCLUDE):
            keep.append(line)
    return keep


def _interrupt_mid_service(cfg, rounds, last_ckpt):
    """Reproduce on disk exactly what a kill -9 mid-service leaves: rows
    and checkpoints through `last_ckpt` journaled, then MORE eval rows
    written past it (un-journaled), then death — no finalize, no span
    rows, no clean writer close."""
    cfg = cfg.replace(chain=1, rounds=rounds, resume=True)
    writer = MetricsWriter(cfg.log_dir, run_name(cfg), tensorboard=False)
    eng = RoundEngine(cfg, writer=writer)
    units = [(r,) for r in range(1, rounds + 1)]
    eng.set_schedule(iter(units))
    for (rnd,) in units:
        eng.dispatch((rnd,))
        if rnd % cfg.snap == 0:
            eng.eval_boundary(rnd)
            if rnd <= last_ckpt:
                eng.save_checkpoint(rnd)
        eng.post_unit()
    if eng.drain is not None:
        eng.drain.flush()
    eng.close()
    eng.writer.close()                  # flushed file, no summary rows


def test_serve_crash_exact_resume_vmap(tmp_path):
    """THE acceptance drill (vmap path): interrupted-at-an-unjournaled-
    boundary + resumed == uninterrupted, byte-for-byte modulo wall-clock
    rows; the resume truncates the orphaned rows and replays them."""
    cfg_a = _svc_cfg(tmp_path, "a", service_rounds=8)
    sum_a = serve(cfg_a)
    assert sum_a["service"]["rounds_served"] == 8

    cfg_b = _svc_cfg(tmp_path, "b", service_rounds=8)
    # first life dies after round 6's eval rows landed but BEFORE round
    # 6's checkpoint: the newest journaled boundary is round 4
    _interrupt_mid_service(cfg_b, rounds=6, last_ckpt=4)
    sum_b = serve(cfg_b)
    assert sum_b["service"]["resumed_from"] == 4
    assert sum_b["service"]["truncated_bytes"] > 0   # orphans dropped
    assert sum_b["service"]["rounds_served"] == 4    # replayed 5..8
    assert _metric_lines(cfg_b) == _metric_lines(cfg_a)
    # the recovered heartbeat recorded the recovery phase
    status = json.load(open(os.path.join(cfg_b.log_dir, "status.json")))
    assert "recover" in status["service_phases"]
    assert status["phase"] == "done"


def test_resume_reenters_aot_bank(tmp_path):
    """ISSUE-16 pin: a recovered service re-enters the AOT bank as a HIT.

    The restored PRNG key used to come back as a typed ``key<fry>``
    array while a fresh life holds raw ``uint32[2]`` key data, so the
    program fingerprint split and every resume recompiled the fleet's
    programs (utils/checkpoint._restore_state now normalises the
    representation). The interrupted life runs without a ledger
    (RoundEngine directly), so every aot/* record in events.jsonl
    belongs to the resumed life."""
    from defending_against_backdoors_with_robust_learning_rate_tpu.obs import (
        events as obs_events)
    cfg = _svc_cfg(tmp_path, "aot", service_rounds=6)
    # warm the bank AND leave a crash-exact interruption behind
    _interrupt_mid_service(cfg, rounds=4, last_ckpt=2)
    summary = serve(cfg)
    assert summary["service"]["resumed_from"] == 2
    events = obs_events.read_events(
        os.path.join(cfg.log_dir, run_name(cfg), "events.jsonl"))
    aot = [r["event"] for r in events if r["event"].startswith("aot/")]
    assert aot and all(e == "aot/hit" for e in aot), aot


def test_serve_crash_exact_resume_sharded(tmp_path):
    """The same drill over the 8-device shard_map path (faked CPU mesh):
    churn + masked collectives + crash recovery compose."""
    base = dict(mesh=0, service_rounds=4)
    cfg_a = _svc_cfg(tmp_path, "a", **base)
    serve(cfg_a)
    cfg_b = _svc_cfg(tmp_path, "b", **base)
    _interrupt_mid_service(cfg_b, rounds=4, last_ckpt=2)
    sum_b = serve(cfg_b)
    assert sum_b["service"]["resumed_from"] == 2
    assert sum_b["service"]["truncated_bytes"] > 0
    assert _metric_lines(cfg_b) == _metric_lines(cfg_a)


def test_serve_wedged_dispatch_retries_and_completes(tmp_path):
    """Acceptance: an injected wedged dispatch triggers backoff + retry
    and the run completes, with Service/* retry counters recorded."""
    cfg = _svc_cfg(tmp_path, "w", service_rounds=4,
                   chaos="wedge@3x2")
    summary = serve(cfg)
    svc = summary["service"]
    assert svc["rounds_served"] == 4 and svc["retries"] >= 2
    assert svc["transient"] >= 2 and svc["gave_up"] == 0
    rows = {(r["tag"], r["step"]): r["value"]
            for line in open(os.path.join(cfg.log_dir, run_name(cfg),
                                          "metrics.jsonl"))
            for r in [json.loads(line)]}
    assert rows[("Service/Retries", 4)] >= 2
    assert rows[("Service/Transient_Failures", 4)] >= 2
    status = json.load(open(os.path.join(cfg.log_dir, "status.json")))
    assert {"retry", "backoff"} <= set(status["service_phases"])


def test_serve_poisoned_eval_skipped_training_continues(tmp_path):
    """Degradation policy: a deterministically failing eval is skipped
    (counted), training continues to completion."""
    cfg = _svc_cfg(tmp_path, "pe", service_rounds=4,
                   chaos="poison_eval@2")
    summary = serve(cfg)
    svc = summary["service"]
    assert svc["rounds_served"] == 4
    assert svc["evals_skipped"] == 1 and svc["poisoned"] >= 1
    steps = {json.loads(line)["step"]
             for line in open(os.path.join(cfg.log_dir, run_name(cfg),
                                           "metrics.jsonl"))
             if json.loads(line)["tag"] == "Validation/Accuracy"}
    assert steps == {4}                 # round-2 eval skipped, round-4 ran


def test_serve_wedged_drain_degrades_to_sync_metrics(tmp_path):
    """A stalled metrics drain wedges the checkpoint flush; the driver
    closes the drain (bounded) and finishes on synchronous metrics — no
    boundary rows lost."""
    cfg = _svc_cfg(tmp_path, "wd", service_rounds=4,
                   chaos="wedge_drain@2:0.8", service_deadline_s=0.1,
                   service_retries=1)
    summary = serve(cfg)
    svc = summary["service"]
    assert svc["rounds_served"] == 4 and svc["wedged"] >= 1
    steps = {json.loads(line)["step"]
             for line in open(os.path.join(cfg.log_dir, run_name(cfg),
                                           "metrics.jsonl"))
             if json.loads(line)["tag"] == "Validation/Accuracy"}
    assert steps == {2, 4}              # both boundaries recorded


def test_serve_poisoned_dispatch_fails_loud_then_resumes(tmp_path):
    """A poisoned dispatch is non-degradable: the service exits loudly
    with the journal intact, and the next serve resumes crash-exactly and
    completes."""
    cfg = _svc_cfg(tmp_path, "pd", service_rounds=4,
                   chaos="poison@3")
    with pytest.raises(UnitFailure) as ei:
        serve(cfg)
    assert ei.value.classification == POISONED
    status = json.load(open(os.path.join(cfg.log_dir, "status.json")))
    assert status["phase"] == "failed"
    summary = serve(cfg.replace(chaos=""))
    assert summary["service"]["resumed_from"] == 2
    assert summary["round"] == 4


def test_serve_stop_file_ends_indefinite_service(tmp_path):
    """service_rounds=0 streams until <log_dir>/service.stop appears."""
    cfg = _svc_cfg(tmp_path, "stop", service_rounds=0)
    os.makedirs(cfg.log_dir, exist_ok=True)
    open(os.path.join(cfg.log_dir, "service.stop"), "w").close()
    summary = serve(cfg)
    assert summary["service"]["rounds_served"] == 0


def test_prepare_crash_exact_resume_fresh_start(tmp_path):
    cfg = SVC.replace(log_dir=str(tmp_path / "logs"), checkpoint_dir="")
    assert prepare_crash_exact_resume(cfg) == {
        "resumed_from": 0, "metrics_offset": 0, "truncated_bytes": 0,
        "resume_upto": None, "boundary": True}


def test_prepare_resume_preserves_prior_runs_rows(tmp_path):
    """A fresh checkpoint dir must never wipe rows earlier runs appended to
    the shared metrics.jsonl: the first prepare journals the file's end as
    the round-0 splice base, and a kill before the first checkpoint
    truncates back to that base — not to 0."""
    from defending_against_backdoors_with_robust_learning_rate_tpu.service.driver import (
        _metrics_path)
    cfg = SVC.replace(log_dir=str(tmp_path / "logs"),
                      checkpoint_dir=str(tmp_path / "ck"))
    path = _metrics_path(cfg)
    os.makedirs(os.path.dirname(path))
    prior = b'{"tag": "Validation/Loss", "value": 1.0, "step": 2}\n'
    with open(path, "wb") as f:
        f.write(prior)
    info = prepare_crash_exact_resume(cfg)
    assert (info["metrics_offset"], info["boundary"]) == (len(prior), True)
    assert open(path, "rb").read() == prior          # nothing truncated
    assert ckpt.journal_offset_for(cfg.checkpoint_dir, 0) == len(prior)
    # the service dies before its first checkpoint, having appended rows
    with open(path, "ab") as f:
        f.write(b'{"tag": "Validation/Loss", "value": 0.9, "step": 4}\n')
    info = prepare_crash_exact_resume(cfg)
    assert info["resumed_from"] == 0 and info["boundary"] is True
    assert info["truncated_bytes"] > 0
    assert open(path, "rb").read() == prior          # base kept, tail cut


# --- the true kill -9 drill (subprocess; CI runs it in the service job) --


def test_service_kill9_subprocess_drill(tmp_path):
    pkg = "defending_against_backdoors_with_robust_learning_rate_tpu"
    args = [sys.executable, "-m", f"{pkg}.service.driver",
            "--data", "synthetic", "--num_agents", "8", "--bs", "16",
            "--local_ep", "1", "--synth_train_size", "256",
            "--synth_val_size", "64", "--eval_bs", "64", "--snap", "2",
            "--num_corrupt", "2", "--poison_frac", "1.0",
            "--robustLR_threshold", "3", "--seed", "5",
            "--no_tensorboard", "--churn_available", "0.75",
            "--churn_period", "3", "--service_rounds", "6",
            "--service_backoff_s", "0.01"]
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}

    def drill(tag, extra):
        cmd = args + ["--log_dir", str(tmp_path / f"{tag}_logs"),
                      "--checkpoint_dir", str(tmp_path / f"{tag}_ck")] \
            + extra
        return subprocess.run(cmd, env=env, capture_output=True,
                              text=True, timeout=600)

    assert drill("a", []).returncode == 0
    first = drill("b", ["--chaos", "kill@5"])
    assert first.returncode == -signal.SIGKILL
    second = drill("b", ["--chaos", "kill@5"])   # must not re-fire
    assert second.returncode == 0, second.stderr[-2000:]

    def lines(tag):
        cfg = SVC.replace(log_dir=str(tmp_path / f"{tag}_logs"),
                          service_rounds=6)
        return _metric_lines(cfg)

    assert lines("b") == lines("a")


# ------------------------------------------------ buffered-async drills ---

def test_chaos_kill_midbuf_grammar_and_gate(tmp_path):
    """kill_midbuf parses like kill, and serve refuses the drill on a
    sync run (a 'mid-buffer' kill without a buffer tests nothing)."""
    inj = chaos_mod.parse_spec("kill_midbuf@4")
    assert inj[0].action == "kill_midbuf" and inj[0].rnd == 4
    assert chaos_mod.Chaos("kill_midbuf@4").requires_buffered()
    assert not chaos_mod.Chaos("kill@4").requires_buffered()
    cfg = SVC.replace(log_dir=str(tmp_path / "logs"),
                      checkpoint_dir=str(tmp_path / "ck"),
                      service_rounds=2, chaos="kill_midbuf@1")
    with pytest.raises(ValueError, match="agg_mode buffered"):
        serve(cfg)


def test_serve_buffered_midbuffer_recovery(tmp_path):
    """The ISSUE-12 chaos acceptance, in-process: a service interrupted
    at a checkpoint whose carried buffer is NON-EMPTY (K=2m, odd snap:
    commits land on even ticks, checkpoints on odd) resumes to
    byte-identical non-timing rows — the buffer + staleness counters
    round-trip through the digest-verified checkpoint exactly like
    params (the true-SIGKILL twin is the subprocess drill via --chaos
    kill_midbuf)."""
    base = dict(agg_mode="buffered", async_buffer_k=16,
                straggler_rate=0.4, snap=3, service_rounds=9,
                churn_available=1.0)
    cfg_a = _svc_cfg(tmp_path, "a", **base)
    sum_a = serve(cfg_a)
    assert sum_a["service"]["rounds_served"] == 9

    cfg_b = _svc_cfg(tmp_path, "b", **base)
    # die after round 6's eval rows landed but BEFORE round 6's
    # checkpoint: the newest journaled boundary is round 3 — whose
    # buffer held round 3's uncommitted arrivals (fill > 0 at the
    # boundary, asserted below from the rows) — and round 6's orphaned
    # rows must be truncated and replayed
    _interrupt_mid_service(cfg_b, rounds=6, last_ckpt=3)
    sum_b = serve(cfg_b)
    assert sum_b["service"]["resumed_from"] == 3
    assert sum_b["service"]["truncated_bytes"] > 0
    assert _metric_lines(cfg_b) == _metric_lines(cfg_a)
    rows = {(json.loads(l)["tag"], json.loads(l)["step"]):
            json.loads(l)["value"] for l in _metric_lines(cfg_b)}
    assert rows[("Async/Buffer_Fill", 3)] > 0   # the kill WAS mid-buffer


def test_service_kill_midbuf_subprocess_drill(tmp_path):
    """True SIGKILL mid-buffer (--chaos kill_midbuf@4 on a buffered
    service): the killed life dies with uncommitted arrivals in the
    carried buffer; the resumed life replays to byte-identical rows."""
    args = [sys.executable, "-m",
            "defending_against_backdoors_with_robust_learning_rate_tpu"
            ".service.driver",
            "--data", "synthetic", "--num_agents", "8", "--bs", "16",
            "--local_ep", "1", "--synth_train_size", "256",
            "--synth_val_size", "64", "--eval_bs", "64", "--snap", "3",
            "--num_corrupt", "2", "--poison_frac", "1.0",
            "--robustLR_threshold", "3", "--seed", "5",
            "--no_tensorboard", "--service_rounds", "6",
            "--service_backoff_s", "0.01",
            "--agg_mode", "buffered", "--async_buffer_k", "16",
            "--straggler_rate", "0.4"]
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}

    def drill(tag, extra):
        cmd = args + ["--log_dir", str(tmp_path / f"{tag}_logs"),
                      "--checkpoint_dir", str(tmp_path / f"{tag}_ck")] \
            + extra
        return subprocess.run(cmd, env=env, capture_output=True,
                              text=True, timeout=600)

    assert drill("a", []).returncode == 0
    first = drill("b", ["--chaos", "kill_midbuf@4"])
    assert first.returncode == -signal.SIGKILL
    second = drill("b", ["--chaos", "kill_midbuf@4"])   # must not re-fire
    assert second.returncode == 0, second.stderr[-2000:]

    def lines(tag):
        cfg = SVC.replace(log_dir=str(tmp_path / f"{tag}_logs"),
                          service_rounds=6, agg_mode="buffered",
                          async_buffer_k=16, straggler_rate=0.4, snap=3,
                          churn_available=1.0)
        return _metric_lines(cfg)

    assert lines("b") == lines("a")
