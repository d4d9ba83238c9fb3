"""Multi-tenant tenant packs (ISSUE 13, fl/tenancy.py +
service/tenancy.py): E experiments folded into one resident *_mt
program must be a pure EXECUTION-layout change.

Parity tiers, by what the arithmetic guarantees:

- the tenant programs run the SAME ops with the same keys as the solo
  paths, so per-tenant metrics are ulp-close to solo runs (measured
  bit-identical on XLA:CPU at these shapes — pinned at 1e-6 for
  headroom, sign-rule params BITWISE);
- E=1 is the degenerate pack: bit-identity with the untenanted path;
- everything queue-side (pack grouping via the fingerprint field
  algebra, knob packing/unpacking, serial fallback, fingerprint split
  on tenant count) is host logic pinned exactly.
"""

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from defending_against_backdoors_with_robust_learning_rate_tpu.config import (  # noqa: E402
    Config)
from defending_against_backdoors_with_robust_learning_rate_tpu.data.registry import (  # noqa: E402
    get_federated_data)
from defending_against_backdoors_with_robust_learning_rate_tpu.fl import (  # noqa: E402
    tenancy as ftenancy)
from defending_against_backdoors_with_robust_learning_rate_tpu.fl.common import (  # noqa: E402
    make_normalizer)
from defending_against_backdoors_with_robust_learning_rate_tpu.fl.rounds import (  # noqa: E402
    make_round_fn)
from defending_against_backdoors_with_robust_learning_rate_tpu.models.registry import (  # noqa: E402
    get_model, init_params)
from defending_against_backdoors_with_robust_learning_rate_tpu.service import (  # noqa: E402
    tenancy as stenancy)
from defending_against_backdoors_with_robust_learning_rate_tpu.service.queue import (  # noqa: E402
    run_queue)
from defending_against_backdoors_with_robust_learning_rate_tpu.utils import (  # noqa: E402
    compile_cache)

# rows the parity compares: everything experiment-derived; wall-clock
# (Throughput/, Spans/), memory watermarks and the run-boundary record
# legitimately differ between a pack and a solo run
PARITY_PREFIXES = ("Validation/", "Poison/", "Train/", "Defense/",
                   "Faults/", "Churn/")


def _cfg(**kw):
    base = dict(data="synthetic", num_agents=8, bs=16, local_ep=1,
                synth_train_size=128, synth_val_size=64, eval_bs=64,
                rounds=2, snap=2, chain=1, num_corrupt=2, poison_frac=1.0,
                aggr="avg", seed=3, tensorboard=False, spans=False,
                heartbeat=False, compile_cache=False,
                data_dir="/nonexistent_use_synthetic")
    base.update(kw)
    return Config(**base)


def _rows(run_dir):
    out = {}
    with open(os.path.join(run_dir, "metrics.jsonl")) as f:
        for line in f:
            r = json.loads(line)
            if r["tag"].startswith(PARITY_PREFIXES):
                out[(r["tag"], r["step"])] = r["value"]
    return out


def _run_dir(cfg):
    from defending_against_backdoors_with_robust_learning_rate_tpu.utils.metrics import (
        run_name)
    return os.path.join(cfg.log_dir, run_name(cfg))


# ------------------------------------------------------------------ parity ---

def test_pack_parity_vs_solo(tmp_path):
    """Tenant-pack acceptance parity: a pack of knob-varying cells
    (undefended / defended / boosted-attack tenants) produces per-tenant
    metrics streams matching each cell's SOLO run — every experiment-
    derived row within 1e-6 (measured bit-identical on XLA:CPU), through
    the full fan-out incl. the Defense/* telemetry filter (the thr=0
    tenant must not grow the tel_flip_frac series its solo twin never
    emits)."""
    base = _cfg(telemetry="full", attack="boost", attack_boost=4.0,
                log_dir=str(tmp_path / "pack"))
    cells = [base.replace(robustLR_threshold=0),
             base.replace(robustLR_threshold=4, attack_boost=8.0)]
    summaries, info = stenancy.run_pack(cells, names=["avg", "rlr"])
    assert info["tenants"] == 2 and info["rounds"] == base.rounds
    from defending_against_backdoors_with_robust_learning_rate_tpu.train import (
        run)
    for i, cell in enumerate(cells):
        solo_cfg = cell.replace(log_dir=str(tmp_path / f"solo{i}"))
        solo = run(solo_cfg)
        for key in ("val_acc", "val_loss", "poison_acc", "poison_loss"):
            assert abs(summaries[i][key] - solo[key]) <= 1e-6, \
                f"tenant {i} {key}: pack {summaries[i][key]} " \
                f"!= solo {solo[key]}"
        pack_rows = _rows(_run_dir(cell))
        solo_rows = _rows(_run_dir(solo_cfg))
        assert set(pack_rows) == set(solo_rows), \
            f"tenant {i} row tags/steps diverge: " \
            f"{set(pack_rows) ^ set(solo_rows)}"
        for k in solo_rows:
            assert abs(pack_rows[k] - solo_rows[k]) <= 1e-6, \
                f"tenant {i} row {k}: {pack_rows[k]} != {solo_rows[k]}"
    # the undefended tenant's stream must NOT contain the flip series
    avg_tags = {t for t, _ in _rows(_run_dir(cells[0]))}
    assert "Defense/LR_Flip_Fraction" not in avg_tags
    assert "Defense/LR_Flip_Fraction" in {
        t for t, _ in _rows(_run_dir(cells[1]))}


def test_e1_bit_identity_with_untenanted_path(tmp_path):
    """E=1 is the degenerate pack: the tenant vmap over a single slot
    must reproduce the untenanted engine's metrics BITWISE (every shared
    row exactly equal)."""
    cfg = _cfg(robustLR_threshold=4, log_dir=str(tmp_path / "pack"))
    summaries, _ = stenancy.run_pack([cfg], names=["solo-twin"])
    from defending_against_backdoors_with_robust_learning_rate_tpu.train import (
        run)
    solo_cfg = cfg.replace(log_dir=str(tmp_path / "solo"))
    solo = run(solo_cfg)
    assert summaries[0]["val_acc"] == solo["val_acc"]
    assert summaries[0]["poison_acc"] == solo["poison_acc"]
    pack_rows, solo_rows = _rows(_run_dir(cfg)), _rows(_run_dir(solo_cfg))
    assert set(pack_rows) == set(solo_rows)
    for k in solo_rows:
        assert pack_rows[k] == solo_rows[k], \
            f"row {k}: {pack_rows[k]} != {solo_rows[k]} (must be bitwise)"


def test_sign_rule_bitwise_and_slot_isolation():
    """Program-level pin: the sign+RLR tenant program's slot-0 params
    equal the solo round's params BITWISE (integer sign-vote arithmetic
    reduces exactly in any order), and a
    different server_lr in slot 1 leaves slot 0 untouched (knob
    isolation across the tenant axis)."""
    solo_cfg = _cfg(aggr="sign", server_lr=0.5, robustLR_threshold=3,
                    telemetry="off")
    fed = get_federated_data(solo_cfg)
    model = get_model(solo_cfg.data, solo_cfg.model_arch, solo_cfg.dtype)
    norm = make_normalizer(fed.mean, fed.std, fed.raw_is_normalized)
    arrays = (jnp.asarray(fed.train.images),
              jnp.asarray(fed.train.labels),
              jnp.asarray(fed.train.sizes))
    params = init_params(model, solo_cfg.image_shape, jax.random.PRNGKey(3))
    key = jax.random.fold_in(jax.random.PRNGKey(solo_cfg.seed), 1)
    solo_fn = make_round_fn(solo_cfg, model, norm, *arrays)
    solo_params, solo_info = solo_fn(params, key)

    cells = [solo_cfg, solo_cfg.replace(server_lr=1.0, seed=9)]
    rep = ftenancy.canonical_rep(solo_cfg.replace(tenants=2), cells=cells)
    mt_fn = ftenancy.make_tenant_round_fn(rep, model, norm, *arrays)
    params_E = ftenancy.stack_params([
        params, init_params(model, solo_cfg.image_shape,
                            jax.random.PRNGKey(9))])
    keys_E = jnp.stack([key, jax.random.fold_in(jax.random.PRNGKey(9), 1)])
    knobs = jax.tree_util.tree_map(jnp.asarray,
                                   ftenancy.knob_vectors(cells))
    packed_E, info_E = mt_fn(params_E, keys_E, jnp.int32(1), knobs)
    slot0 = ftenancy.tenant_slice(packed_E, 0)
    for a, b in zip(jax.tree_util.tree_leaves(solo_params),
                    jax.tree_util.tree_leaves(slot0), strict=True):
        assert np.array_equal(np.asarray(a), np.asarray(b)), \
            "sign-rule tenant slot 0 must be BITWISE the solo round"
    assert float(solo_info["train_loss"]) == \
        float(info_E["train_loss"][0])
    # slot 1 trained a different stream entirely
    assert not np.array_equal(
        np.asarray(jax.tree_util.tree_leaves(packed_E)[0][0]),
        np.asarray(jax.tree_util.tree_leaves(packed_E)[0][1]))


# ---------------------------------------------------- packing / grouping ---

def test_plan_packs_grouping_and_serial_fallback(capsys):
    """Queue grouping: knob-varying cells pack (incl. thr=0 with thr>0 —
    the vote degenerates exactly); program/shape-changing overrides split
    packs via the fingerprint field algebra; ineligible cells fall back
    serial with a printed note; a leftover singleton runs serial."""
    base = _cfg()
    cells = [
        {"name": "a0", "overrides": {"seed": 0}},
        {"name": "a1", "overrides": {"seed": 1, "robustLR_threshold": 4}},
        {"name": "a2", "overrides": {"server_lr": 0.5}},
        # aggr is a program field -> its own (singleton -> serial) class
        {"name": "b0", "overrides": {"aggr": "comed"}},
        # telemetry is a program field -> splits
        {"name": "c0", "overrides": {"telemetry": "basic"}},
        # ineligible -> serial with note
        {"name": "d0", "overrides": {"diagnostics": True}},
    ]
    items = stenancy.plan_packs(base, cells, tenants=2,
                                apply_overrides=lambda c, o: c.replace(**o))
    kinds = [(kind, [c["name"] for c in group]) for kind, group in items]
    assert ("pack", ["a0", "a1"]) in kinds
    # a2 is the a-class leftover singleton -> serial
    assert ("serial", ["a2"]) in kinds
    assert ("serial", ["b0"]) in kinds
    assert ("serial", ["c0"]) in kinds
    assert ("serial", ["d0"]) in kinds
    out = capsys.readouterr().out
    assert "diagnostics" in out          # the ineligibility note printed
    assert "no shape-compatible partner" in out


def test_pack_key_knobs_vs_programs():
    """tenant_pack_key: equal across every per-tenant knob
    (fl/tenancy.TENANT_KNOB_FIELDS), split by program/shape/data fields
    AND by the lockstep dispatch schedule (rounds/snap/chain)."""
    base = _cfg()
    k = compile_cache.tenant_pack_key(base)
    for kw in ({"seed": 7}, {"server_lr": 0.25}, {"robustLR_threshold": 9},
               {"attack_boost": 8.0}, {"attack_start": 2},
               {"attack_every": 3}, {"log_dir": "/elsewhere"}):
        assert compile_cache.tenant_pack_key(base.replace(**kw)) == k, kw
    for kw in ({"aggr": "sign"}, {"bs": 32}, {"telemetry": "full"},
               {"attack": "boost"}, {"dropout_rate": 0.3},
               {"num_agents": 12}, {"rounds": 4}, {"snap": 1},
               {"poison_frac": 0.5}):
        assert compile_cache.tenant_pack_key(base.replace(**kw)) != k, kw


def test_fingerprint_splits_on_tenant_count_not_knobs():
    """The AOT fingerprint for the *_mt families must split on the
    tenant count (the [E, ...] avals AND cfg.tenants) but NOT on knob
    values — one banked executable serves every pack of the same
    shape."""
    base = _cfg(tenants=2, robustLR_threshold=4)
    ex = (jnp.zeros((3,)),)
    fp2 = compile_cache.fingerprint(base, "round_mt", ex)
    assert compile_cache.fingerprint(
        base.replace(tenants=4), "round_mt", ex) != fp2
    for kw in ({"seed": 7}, {"server_lr": 0.25},
               {"robustLR_threshold": 9}, {"attack_boost": 8.0}):
        assert compile_cache.fingerprint(
            base.replace(**kw), "round_mt", ex) == fp2, kw
    # ... but the one STRUCTURAL bit a knob carries (is the RLR vote
    # built at all) legitimately splits the program
    assert compile_cache.fingerprint(
        base.replace(robustLR_threshold=0), "round_mt", ex) != fp2
    # family naming: the tenancy suffix composes after the buffered one
    assert compile_cache.family_suffix(base) == "_mt"
    assert compile_cache.family_suffix(
        base.replace(agg_mode="buffered")) == "_async_mt"
    assert compile_cache.family_suffix(base.replace(tenants=0)) == ""


def test_knob_vectors_roundtrip_and_canonical_rep():
    """Knob packing: the aggr=='sign' server-LR rule resolves per
    tenant; stack/slice roundtrip; canonical_rep collapses knob values
    but keeps the pack-level RLR structure bit."""
    cells = [_cfg(aggr="sign", server_lr=0.5, seed=1),
             _cfg(aggr="sign", server_lr=2.0, seed=2,
                  robustLR_threshold=4)]
    kn = ftenancy.knob_vectors(cells)
    assert kn.server_lr.tolist() == [0.5, 2.0]
    assert kn.rlr_threshold.tolist() == [0.0, 4.0]
    avg_cells = [c.replace(aggr="avg") for c in cells]
    assert ftenancy.knob_vectors(avg_cells).server_lr.tolist() == [1.0, 1.0]
    rep = ftenancy.canonical_rep(avg_cells[0].replace(tenants=2),
                                 cells=avg_cells)
    assert rep.robustLR_threshold == 1 and rep.server_lr == 1.0
    assert rep.seed == 0 and rep.attack_boost == 1.0
    rep_off = ftenancy.canonical_rep(
        avg_cells[0].replace(tenants=2, robustLR_threshold=0),
        cells=[avg_cells[0].replace(robustLR_threshold=0)])
    assert rep_off.robustLR_threshold == 0
    # stack/slice roundtrip
    trees = [{"w": jnp.arange(3.0) + i} for i in range(3)]
    stacked = ftenancy.stack_params(trees)
    for i in range(3):
        got = ftenancy.tenant_slice(jax.device_get(stacked), i)
        assert np.array_equal(got["w"], np.arange(3.0) + i)


def test_refusals():
    """Shape-incompatible / unsupported configs refuse loudly (program
    refusals in fl/tenancy, runtime routing in service/tenancy), and a
    pack mixing shape classes is rejected at run_pack."""
    assert ftenancy.ineligible_reason(_cfg()) == ""
    assert "diagnostics" in ftenancy.ineligible_reason(
        _cfg(diagnostics=True))
    # buffered and cohort packs became ELIGIBLE in ISSUE 16 (the stacked
    # (params, state) carry / the shared bank gather)
    assert ftenancy.ineligible_reason(_cfg(agg_mode="buffered")) == ""
    assert ftenancy.ineligible_reason(
        _cfg(cohort_sampled="on", num_agents=8, cohort_size=4)) == ""
    assert "host-sampled" in stenancy.serial_reason(
        _cfg(host_sampled="on"))
    # the PR-13 mesh refusal is retired (ISSUE 16): the engine resolves
    # --mesh like the solo driver and runs the sharded *_mt families
    assert stenancy.serial_reason(_cfg(mesh=0)) == ""
    with pytest.raises(ValueError, match="tenants >= 1"):
        ftenancy.check(_cfg(tenants=0))
    with pytest.raises(ValueError, match="one tenant_pack_key"):
        stenancy.run_pack([_cfg(), _cfg(aggr="comed")])
    # the one-experiment engine refuses the pack knob with a pointer
    from defending_against_backdoors_with_robust_learning_rate_tpu.train import (
        RoundEngine)
    with pytest.raises(ValueError, match="service/queue.py --tenants"):
        RoundEngine(_cfg(tenants=2))


def test_chained_mt_donates_params():
    """Donation-audit pin (contracts.DONATED_FAMILIES): the chained
    tenant block aliases its [E, ...]-stacked params argument in the
    lowered StableHLO — no double-buffered pack params per dispatch."""
    cfg = _cfg(chain=2, tenants=2)
    fed = get_federated_data(cfg)
    model = get_model(cfg.data, cfg.model_arch, cfg.dtype)
    norm = make_normalizer(fed.mean, fed.std, fed.raw_is_normalized)
    specs = compile_cache.plan_programs(cfg, model, norm, fed)
    fams = {s.family: s for s in specs}
    assert {"round_mt", "chained_mt", "eval_val_mt",
            "eval_poison_mt"} <= set(fams)
    text = compile_cache.lower_program(
        fams["chained_mt"].jit_obj,
        fams["chained_mt"].example_args).as_text()
    assert "tf.aliasing_output" in text
    text = compile_cache.lower_program(
        fams["round_mt"].jit_obj, fams["round_mt"].example_args).as_text()
    assert "tf.aliasing_output" not in text


def test_queue_rows_run_name_and_summary(tmp_path):
    """Queue satellites: every cell row carries the resolved run_name
    (rows join to run dirs), packed rows carry their tenancy slot, and
    the final queue_results.jsonl row is the queue-level throughput
    summary (cells/hour + compile-vs-steady split)."""
    base = _cfg(log_dir=str(tmp_path / "logs"))
    cells = [{"name": "t0", "overrides": {"seed": 0}},
             {"name": "t4", "overrides": {"robustLR_threshold": 4}}]
    results = str(tmp_path / "q.jsonl")
    rows = run_queue(base, cells, results_path=results, tenants=2)
    assert [r["ok"] for r in rows] == [True, True]
    for r in rows:
        assert r["run_name"], "every cell row must carry run_name"
        assert r["tenancy"]["tenants"] == 2
    assert [r["tenancy"]["slot"] for r in rows] == [0, 1]
    with open(results) as f:
        recs = [json.loads(line) for line in f]
    assert recs[-1]["queue_summary"] is True
    assert recs[-1]["cells"] == 2 and recs[-1]["ok"] == 2
    assert recs[-1]["packed_cells"] == 2
    assert recs[-1]["cells_per_hour"] > 0
    assert recs[-1]["wall_s"] >= recs[-1]["steady_s"] >= 0
    # rows join: the run dirs named in the rows exist with metrics
    for r, cell in zip(rows, cells, strict=True):
        d = os.path.join(base.log_dir, r["run_name"])
        assert os.path.exists(os.path.join(d, "metrics.jsonl"))
    # packed rows bill compile from run_pack's measured pack-level
    # compile_s (1/E share), never the pack-level steady rate (which
    # would overcount steady seconds E-fold)
    share = sum(min(r["wall_s"],
                    r["tenancy"]["compile_s"] / r["tenancy"]["tenants"])
                for r in rows)
    assert abs(recs[-1]["compile_warmup_s"] - share) <= 1e-6


def test_pack_host_mode_preflight_falls_back_serial(tmp_path, monkeypatch):
    """host_sampled='auto' resolves against the LOADED dataset's byte
    size — information plan_packs never has. run_pack's pre-flight
    raises PackIneligible before any program build, and the queue routes
    the members through their solo runs instead of recording a pack
    failure (the solo driver picks the host-sampled families the pack
    cannot bind device-resident)."""
    monkeypatch.setattr(compile_cache, "DEVICE_RESIDENT_BYTES", 1)
    base = _cfg(log_dir=str(tmp_path / "logs"))
    assert base.host_sampled == "auto"
    with pytest.raises(stenancy.PackIneligible, match="host-sampled"):
        stenancy.run_pack([base.replace(seed=0), base.replace(seed=1)])
    cells = [{"name": f"s{s}", "overrides": {"seed": s}} for s in (0, 1)]
    rows = run_queue(base, cells,
                     results_path=str(tmp_path / "q.jsonl"), tenants=2)
    assert [r["ok"] for r in rows] == [True, True]
    # the members ran SOLO (host-sampled), not as a failed/packed pack
    assert all("tenancy" not in r for r in rows)
