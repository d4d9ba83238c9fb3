"""Defense provenance plane (ISSUE 20, obs/reputation.py).

Three layers, mirroring the module split:

- lane math: the in-jit rep_agree/rep_norm reductions against numpy
  host oracles (sign ties, MASKED sentinel slots), and full
  round-program parity vmap vs sharded on the faked 8-device mesh — the
  agreement lane is integer-count arithmetic so parity is bitwise, the
  norm lane gets the cross-path tolerance.
- tracker: the two-signal suspicion fold against hand-computed
  EMA/streak oracles (a boosted client scores on the norm term with
  PERFECT agreement, a sign-flipper on the agreement term), the
  Mann-Whitney AUC helper, count-min sketch mode (heavy-hitter
  admission, overestimate-only error, bounded on the fixture), and the
  journal round-trip: interrupted-and-resumed folds reproduce the
  uninterrupted tracker's rows and events byte-for-byte (the serve-
  level twin of this claim rides test_service's crash-exact drill,
  whose SVC config compiles the lanes in).
- serve() drills: suspicion AUC >= 0.9 for BOTH the boost and signflip
  attacks with the ranking blind to ground truth (the AUC row is the
  only corrupt-flag consumer), streak-crossing rep/suspect ledger
  events, and the --reputation off twin: same stream minus the
  Reputation/* rows, no suspicion summary, no journal key.
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from defending_against_backdoors_with_robust_learning_rate_tpu.config import (
    Config)
from defending_against_backdoors_with_robust_learning_rate_tpu.data.registry import (
    get_federated_data)
from defending_against_backdoors_with_robust_learning_rate_tpu.fl.common import (
    make_normalizer)
from defending_against_backdoors_with_robust_learning_rate_tpu.fl.rounds import (
    make_round_fn)
from defending_against_backdoors_with_robust_learning_rate_tpu.models.registry import (
    get_model, init_params)
from defending_against_backdoors_with_robust_learning_rate_tpu.obs import (
    events as obs_events, reputation as rep)
from defending_against_backdoors_with_robust_learning_rate_tpu.parallel.mesh import (
    make_mesh)
from defending_against_backdoors_with_robust_learning_rate_tpu.parallel.rounds import (
    make_sharded_round_fn)
from defending_against_backdoors_with_robust_learning_rate_tpu.service.driver import (
    serve)
from defending_against_backdoors_with_robust_learning_rate_tpu.utils import (
    checkpoint as ckpt)
from defending_against_backdoors_with_robust_learning_rate_tpu.utils.metrics import (
    run_name)

# --- config validation + mode resolution ----------------------------------


def test_check_validation_is_loud():
    rep.check(Config(reputation="auto"))
    with pytest.raises(ValueError, match="--reputation"):
        rep.check(Config(reputation="loud"))
    with pytest.raises(ValueError, match="sign vote"):
        rep.check(Config(reputation="on", robustLR_threshold=0))
    rep.check(Config(reputation="on", robustLR_threshold=0, aggr="sign"))
    with pytest.raises(ValueError, match="rep_topk"):
        rep.check(Config(rep_topk=0))
    with pytest.raises(ValueError, match="rep_streak"):
        rep.check(Config(rep_streak=0))


def test_mode_resolution():
    # auto: on exactly when a committed sign vote exists
    assert rep.reputation_on(Config(robustLR_threshold=3))
    assert not rep.reputation_on(Config(robustLR_threshold=0))
    assert rep.reputation_on(Config(robustLR_threshold=0, aggr="sign"))
    assert not rep.reputation_on(
        Config(robustLR_threshold=3, reputation="off"))
    assert rep.rep_keys(Config(robustLR_threshold=3)) == (
        "rep_agree", "rep_norm")
    assert rep.rep_keys(Config(reputation="off")) == ()


# --- lane math vs host oracles --------------------------------------------


def _stacked(m=6, seed=0):
    """Two-leaf stacked updates with planted structure: row 1 is an
    exact sign flip of row 0, row 4 is row 0 boosted 5x (same signs),
    and leaf 'b' column 3 is all-zero (a vote tie — never agreement)."""
    rng = np.random.RandomState(seed)
    a = rng.randn(m, 3, 2).astype(np.float32)
    b = rng.randn(m, 5).astype(np.float32)
    b[:, 3] = 0.0
    a[1], b[1] = -a[0], -b[0]
    a[4], b[4] = 5.0 * a[0], 5.0 * b[0]
    return {"a": jnp.asarray(a), "b": jnp.asarray(b)}


def _oracle(upd, mask=None):
    """Numpy reference for both lanes."""
    leaves = [np.asarray(upd["a"]), np.asarray(upd["b"])]
    m = leaves[0].shape[0]
    total = sum(l.size // m for l in leaves)
    match = np.zeros(m)
    nsq = np.zeros(m)
    for u in leaves:
        flat = u.reshape(m, -1).astype(np.float64)
        vote = np.sign(np.sign(flat).sum(axis=0))   # sum of SIGNS
        match += ((np.sign(flat) * vote[None, :]) > 0).sum(axis=1)
        nsq += (flat.astype(np.float32) ** 2).sum(axis=1)
    agree, norm = match / total, np.sqrt(nsq)
    if mask is not None:
        agree = np.where(mask, agree, rep.MASKED)
        norm = np.where(mask, norm, rep.MASKED)
    return agree, norm


def test_lane_rows_match_host_oracle():
    upd = _stacked()
    sums = rep.sign_sums_from(upd)
    got_a = np.asarray(jax.jit(rep.agree_rows)(upd, sums))
    got_n = np.asarray(jax.jit(rep.norm_rows)(upd))
    want_a, want_n = _oracle(upd)
    np.testing.assert_allclose(got_a, want_a, atol=1e-6)
    np.testing.assert_allclose(got_n, want_n, rtol=1e-5)
    # planted structure: the boosted row has the SAME agreement as its
    # honest original (magnitude blindness — the reason rep_norm exists)
    # but 5x its norm; the flipped row disagrees where the original
    # agrees (ties count for neither)
    assert got_a[4] == got_a[0]
    np.testing.assert_allclose(got_n[4], 5.0 * got_n[0], rtol=1e-5)
    assert got_a[1] < got_a[0]

    # masked slots carry the sentinel in BOTH lanes
    mask = np.array([True, True, False, True, False, True])
    got_am = rep.agree_rows(upd, sums, mask=jnp.asarray(mask))
    got_nm = rep.norm_rows(upd, mask=jnp.asarray(mask))
    want_am, want_nm = _oracle(upd, mask)
    np.testing.assert_allclose(np.asarray(got_am), want_am, atol=1e-6)
    np.testing.assert_allclose(np.asarray(got_nm), want_nm, rtol=1e-5)
    assert float(got_am[2]) == float(got_nm[2]) == rep.MASKED


def test_round_program_lane_parity_vmap_leaf():
    """One full round on the faked 8-device mesh: the vmap and the
    sharded programs emit the SAME [m] rep rows. Agreement counts
    integer-valued f32 partials (bitwise across the two); the norm gets
    the cross-path tolerance."""
    assert len(jax.devices()) == 8, "conftest must fake 8 CPU devices"
    cfg = Config(data="synthetic", num_agents=8, bs=16, local_ep=1,
                 synth_train_size=256, synth_val_size=64,
                 num_corrupt=2, poison_frac=1.0, seed=11,
                 robustLR_threshold=3)
    assert rep.reputation_on(cfg)
    fed = get_federated_data(cfg)
    model = get_model(cfg.data, cfg.model_arch, cfg.dtype)
    params = init_params(model, cfg.image_shape, jax.random.PRNGKey(0))
    norm = make_normalizer(fed.mean, fed.std, fed.raw_is_normalized)
    arrays = (jnp.asarray(fed.train.images),
              jnp.asarray(fed.train.labels),
              jnp.asarray(fed.train.sizes))
    key = jax.random.PRNGKey(42)
    mesh = make_mesh(8)

    _, i0 = make_round_fn(cfg, model, norm, *arrays)(params, key)
    _, i1 = make_sharded_round_fn(cfg, model, norm, mesh, *arrays)(
        params, key)
    for info in (i0, i1):
        assert np.asarray(info["rep_agree"]).shape == (8,)
        assert np.asarray(info["rep_norm"]).shape == (8,)
    np.testing.assert_array_equal(np.asarray(i0["rep_agree"]),
                                  np.asarray(i1["rep_agree"]))
    np.testing.assert_allclose(np.asarray(i0["rep_norm"]),
                               np.asarray(i1["rep_norm"]),
                               atol=1e-5, rtol=1e-5)
    # every agreement is a real fraction, nothing masked in a full draw
    agrees = np.asarray(i0["rep_agree"])
    assert ((agrees >= 0.0) & (agrees <= 1.0)).all()


# --- tracker: two-signal suspicion fold -----------------------------------


def test_tracker_fold_matches_hand_oracle():
    t = rep.ReputationTracker(population=4, cap=100, topk=4, streak_thr=2)
    # round 0: client 3 outvoted (agree .2 -> susp .8), the rest agree
    # .8 at equal norms (no deviation -> susp .2, under the threshold)
    t.fold(0, [0, 1, 2, 3], [0.8, 0.8, 0.8, 0.2], [1.0, 1.0, 1.0, 1.0])
    assert t.clients[3] == [0.2, 1, 1, 0.8]
    assert t.clients[0] == [0.8, 1, 0, pytest.approx(0.2)]
    assert t.suspect_count() == 0 and t.drain_events() == []
    # round 1: client 3 loses again -> streak 2 == threshold, one event;
    # the EMA folds at decay 0.9
    t.fold(1, [0, 1, 2, 3], [0.8, 0.8, 0.8, 0.2], [1.0, 1.0, 1.0, 1.0])
    ent = t.clients[3]
    assert ent[1] == 2 and ent[2] == 2
    assert ent[3] == pytest.approx(0.9 * 0.8 + 0.1 * 0.8)
    assert t.suspect_count() == 1
    (ev,) = t.drain_events()
    assert ev["client"] == 3 and ev["streak"] == 2 and ev["round"] == 1
    # round 2: client 3 wins -> streak resets, and NO second event fires
    # on later crossings of lower counts
    t.fold(2, [0, 1, 2, 3], [0.8, 0.8, 0.8, 0.9], [1.0, 1.0, 1.0, 1.0])
    assert t.clients[3][2] == 0 and t.drain_events() == []
    # MASKED slots neither win nor lose; norms=None degrades to
    # agreement-only
    t.fold(3, [0, 1], [rep.MASKED, 0.5], None)
    assert t.clients[0][1] == 3 and t.clients[1][1] == 4


def test_tracker_two_signals_separate_both_attacks():
    """The fold's max(1-agree, 1-med/norm) scores a 5x-boosted pair with
    PERFECT agreement above honest clients (norm term), and a
    sign-flipped pair above honest clients (agreement term)."""
    boost = rep.ReputationTracker(6, 100, 6, 3)
    flip = rep.ReputationTracker(6, 100, 6, 3)
    for r in range(5):
        # corrupt 0/1 agree perfectly but shout ~5x the honest median
        boost.fold(r, [0, 1, 2, 3, 4, 5],
                   [1.0, 1.0, 0.8, 0.7, 0.75, 0.85],
                   [5.0, 5.0, 1.0, 0.9, 1.1, 1.0])
        # corrupt 0/1 lose the vote at honest norms
        flip.fold(r, [0, 1, 2, 3, 4, 5],
                  [0.1, 0.2, 0.8, 0.7, 0.75, 0.85],
                  [1.0, 1.0, 1.0, 0.9, 1.1, 1.0])
    for t in (boost, flip):
        ranked = t.ranked()
        assert {cid for cid, _ in ranked[:2]} == {0, 1}
        assert ranked[1][1] > ranked[2][1] + 0.2   # real separation
        assert t.suspect_count() == 2
        rows = dict(t.boundary_rows(corrupt_pred=lambda c: c < 2))
        assert rows[rep.TAGS["auc"]] == 1.0
        assert rows[rep.TAGS["suspect_count"]] == 2.0
    # the boosted pair's PERFECT agreement means the agreement EMA alone
    # ranks them LEAST suspect — the norm lane is load-bearing
    agree_rank = sorted(boost.clients, key=lambda c: -boost.clients[c][0])
    assert set(agree_rank[:2]) == {0, 1}


def test_rank_auc():
    assert rep.rank_auc([0.9, 0.8, 0.1, 0.2],
                        [True, True, False, False]) == 1.0
    assert rep.rank_auc([0.1, 0.2, 0.9, 0.8],
                        [True, True, False, False]) == 0.0
    assert rep.rank_auc([0.5, 0.5, 0.5, 0.5],
                        [True, True, False, False]) == 0.5  # all ties
    assert rep.rank_auc([0.9, 0.1], [True, True]) is None
    assert rep.rank_auc([], []) is None


# --- sketch mode ----------------------------------------------------------


def test_sketch_mode_admission_and_bounds():
    """Population past the cap: count-min + top-k ledger. The planted
    heavy hitters are admitted; estimates only OVERESTIMATE the exact
    per-client mean suspicion, within a fixture-bounded error."""
    t = rep.ReputationTracker(population=10_000, cap=100, topk=4,
                              streak_thr=3)
    assert t.sketch_mode
    exact = {}
    rng = np.random.RandomState(7)
    for r in range(6):
        ids = list(range(r * 40, r * 40 + 40)) + [9000, 9001]
        agrees = list(np.clip(rng.uniform(0.6, 0.9, 40), 0, 1)) + [0.0, 0.1]
        norms = [1.0] * 40 + [5.0, 5.0]
        t.fold(r, ids, agrees, norms)
        med = float(np.median(norms))
        for cid, a, n in zip(ids, agrees, norms):
            s = max(1.0 - a, 0.0 if n <= med else 1.0 - med / n)
            exact.setdefault(cid, []).append(s)
    # ledger: bounded at topk, the two planted repeat offenders are in
    assert len(t.clients) == 4
    assert {9000, 9001} <= set(t.clients)
    assert {cid for cid, _ in t.ranked()[:2]} == {9000, 9001}
    # count-min overestimates MASS one-sidedly; the mean RATIO is a
    # two-sided approximation — a collision mixes in the colliding
    # client's mean, and the min-over-rows prefers the diluted row —
    # bounded on this fixture (242 ids vs 4x4096 cells; worst observed
    # deviation 0.14, honest scores all land in [0.1, 0.4])
    for cid, obs in exact.items():
        if cid in t.clients:
            continue   # ledger members answer from exact EMAs
        mean = sum(obs) / len(obs)
        assert abs(t.suspicion(cid) - mean) < 0.2
    # AUC rows are dense-mode only (class doc)
    assert rep.TAGS["auc"] not in dict(
        t.boundary_rows(corrupt_pred=lambda c: c >= 9000))
    # journal round-trips the sketch arrays
    t2 = rep.ReputationTracker(10_000, 100, 4, 3)
    t2.load_state(json.loads(json.dumps(t.state_dict())))
    assert t2.mass == t.mass and t2.clients == t.clients


def test_sketch_columns_are_interpreter_stable():
    """The sketch must hash identically across interpreters/resumes —
    pin the fixed-salt mix on literal values."""
    assert rep._sketch_cols(0) == rep._sketch_cols(0)
    assert rep._sketch_cols(12345) == [1626, 2541, 3128, 2130]


# --- journal: crash-exact fold resume -------------------------------------


def test_tracker_journal_resume_is_byte_identical():
    """Fold 5 rounds / journal / resume / fold 5 more == fold all 10 on
    one tracker: rows, summary and the event stream all match exactly
    (what keeps replayed Reputation/* rows byte-identical through
    train.py's checkpoint journal)."""
    rng = np.random.RandomState(3)
    rounds = [([0, 1, 2, 3, 4],
               list(np.round(rng.uniform(0.0, 1.0, 5), 6)),
               list(np.round(rng.uniform(0.5, 2.0, 5), 6)))
              for _ in range(10)]
    full = rep.ReputationTracker(5, 100, 5, 2)
    for r, (ids, ag, nm) in enumerate(rounds):
        full.fold(r, ids, ag, nm)
    events_full = full.drain_events()

    first = rep.ReputationTracker(5, 100, 5, 2)
    for r in range(5):
        first.fold(r, *rounds[r])
    events_a = first.drain_events()
    state = json.loads(json.dumps(first.state_dict()))   # disk round-trip

    resumed = rep.ReputationTracker(5, 100, 5, 2)
    resumed.load_state(state)
    for r in range(5, 10):
        resumed.fold(r, *rounds[r])
    assert resumed.clients == full.clients
    assert resumed.boundary_rows(lambda c: c < 2) == full.boundary_rows(
        lambda c: c < 2)
    assert resumed.summary(lambda c: c < 2) == full.summary(lambda c: c < 2)
    assert events_a + resumed.drain_events() == events_full


# --- serve() drills -------------------------------------------------------

SVC = Config(data="synthetic", num_agents=8, bs=16, local_ep=1,
             synth_train_size=256, synth_val_size=64, eval_bs=64,
             snap=2, seed=5, tensorboard=False, num_corrupt=2,
             poison_frac=1.0, robustLR_threshold=3,
             service_backoff_s=0.01, service_rounds=8)


@pytest.fixture(scope="module")
def attack_runs(tmp_path_factory):
    """Three serve() runs shared by the drills below: boost with the
    plane on, its --reputation off twin, and signflip."""
    root = tmp_path_factory.mktemp("rep_runs")
    out = {}
    for tag, kw in (("boost", dict(attack="boost", attack_boost=5.0)),
                    ("boost_off", dict(attack="boost", attack_boost=5.0,
                                       reputation="off")),
                    ("signflip", dict(attack="signflip",
                                      attack_boost=2.0))):
        cfg = SVC.replace(log_dir=str(root / f"{tag}_logs"),
                          checkpoint_dir=str(root / f"{tag}_ck"), **kw)
        out[tag] = (cfg, serve(cfg))
    return out


def _lines(cfg):
    path = os.path.join(cfg.log_dir, run_name(cfg), "metrics.jsonl")
    from defending_against_backdoors_with_robust_learning_rate_tpu.obs.constants import (
        NON_TIMING_PREFIXES)
    return [l for l in open(path)
            if not any(json.loads(l)["tag"].startswith(p)
                       for p in NON_TIMING_PREFIXES)]


@pytest.mark.parametrize("attack", ["boost", "signflip"])
def test_serve_suspicion_auc(attack_runs, attack):
    """THE acceptance drill: the ranking — which never reads a corrupt
    flag — separates the corrupt pair for both the magnitude attack
    (boost 5x: perfect sign agreement, norm lane catches it) and the
    sign attack (flip: agreement lane catches it)."""
    _, summary = attack_runs[attack]
    susp = summary["suspicion"]
    assert susp["mode"] == "dense" and susp["rounds"] == 8
    assert susp["auc"] >= 0.9
    assert set(susp["suspects"][:2]) == {0, 1}   # the corrupt pair
    assert susp["suspect_count"] >= 1            # streaks actually fired


def test_serve_reputation_rows_and_events(attack_runs):
    cfg, _ = attack_runs["boost"]
    tags = {json.loads(l)["tag"] for l in _lines(cfg)}
    for key in ("clients", "mean_agree", "suspect_count", "top_score",
                "auc"):
        assert rep.TAGS[key] in tags
    # streak crossings became typed warn-severity ledger events
    evs = [e for e in obs_events.read_events(
        os.path.join(cfg.log_dir, run_name(cfg), "events.jsonl"))
        if e["event"] == rep.SUSPECT_EVENT]
    # the corrupt pair both cross (honest clients CAN transiently
    # streak in noisy early rounds — ranking, not one streak, is the
    # detector; the AUC drill above pins that)
    assert {0, 1} <= {e["client"] for e in evs}
    assert all(e["severity"] == "warn" for e in evs)
    # the journal carries the tracker state for crash-exact resumes
    entries = list(ckpt.journal_read(cfg.checkpoint_dir))
    assert entries and all("reputation" in e for e in entries)


def test_serve_reputation_off_twin(attack_runs):
    """--reputation off: the SAME stream minus the Reputation/* rows
    (bit-identical training), no suspicion summary, no journal key."""
    cfg_on, sum_on = attack_runs["boost"]
    cfg_off, sum_off = attack_runs["boost_off"]
    on_minus_rep = [l for l in _lines(cfg_on)
                    if not json.loads(l)["tag"].startswith("Reputation/")]
    assert _lines(cfg_off) == on_minus_rep
    assert "suspicion" not in sum_off and "suspicion" in sum_on
    assert all("reputation" not in e
               for e in ckpt.journal_read(cfg_off.checkpoint_dir))
