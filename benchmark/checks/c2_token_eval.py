"""The token task's model check: what the engine's own evaluation wrote at
the warm-up boundary against the plain reference on the same trained
parameters and the same sequences.

Compared: validation loss and token accuracy, poison loss and accuracy, and
for every sparse layer the (token, expert) pairs routed to each held expert
and to the experts not held. Losses and counts, not arg-max tokens one by
one. The poisoned sequences are the engine's, after this check has held
them to the backdoor the configuration's file states: equal to the clean
ones but for `triggers_per_sequence` places, each the three trigger ids and
the target, the mask on the position that predicts the target. The pairs of
a layer must add up to tokens x experts per token exactly: no pair dropped.

The reference (`reference/lfm2_moe.py`: float32, products at the highest
precision, every held expert applied to every token) runs one sequence at a
time. On the device beside the engine's parameters, which it reads in place:
one sequence's activations, the largest the attention scores 32 x 2048 x
2048 float32 (0.54 GB) and a held expert's hidden layer for all tokens.
Seconds on the v5e: in PERF.md section 6 (PR 27)."""

from __future__ import annotations

from typing import Any, Dict

import jax
import numpy as np

PHASE = "model"
EVAL_TAGS = ("Validation/Loss", "Validation/Accuracy", "Poison/Poison_Loss",
             "Poison/Poison_Accuracy")
PAIRS = "Moe/Eval_Pairs/"


def _rows(split):
    tokens, mask, weights = (np.asarray(jax.device_get(x)) for x in split)
    keep = weights.reshape(-1) > 0
    return (tokens.reshape((-1,) + tokens.shape[2:])[keep],
            mask.reshape((-1,) + mask.shape[2:])[keep])


def reference_eval(ref, params, dims, tokens, mask):
    """(loss, accuracy, counted positions, pairs [layers, held + 1]) of the
    reference over `tokens` [n, T + 1] where `mask` [n, T] counts, a
    sequence at a time."""
    step = jax.jit(lambda p, row: ref.token_losses(p, row, dims))
    loss = hits = n = 0.0
    pairs = 0
    for row, mk in zip(tokens, mask, strict=True):
        ce, hit, pr = jax.device_get(step(params, row[None]))
        w = mk.astype(np.float64)
        loss += float((np.asarray(ce, np.float64)[0] * w).sum())
        hits += float((np.asarray(hit)[0] * w).sum())
        n += float(w.sum())
        pairs = pairs + np.asarray(pr, np.int64)
    return loss / n, hits / n, n, pairs


def held_to_the_backdoor(clean, poisoned, pmask, backdoor) -> Dict[str, int]:
    """How the engine's poisoned sequences depart from what the file
    states; all zeros where they are its backdoor."""
    trig = np.asarray(backdoor["trigger"])
    k = len(trig)
    wrong_place = wrong_ids = stray = 0
    for c, p, m in zip(clean, poisoned, pmask, strict=True):
        places = np.flatnonzero(m)              # position predicting target
        wrong_place += int(len(places) != backdoor["triggers_per_sequence"])
        touched = np.zeros(len(p), bool)
        for pos in places:
            start = pos - (k - 1)
            wrong_ids += int(start < 0
                             or not np.array_equal(p[start:pos + 1], trig)
                             or p[pos + 1] != backdoor["target"])
            touched[max(start, 0):pos + 2] = True
        stray += int(np.count_nonzero((c != p) & ~touched))
    return {"wrong_place": wrong_place, "wrong_ids": wrong_ids,
            "stray": stray}


def run(ctx) -> Dict[str, Any]:
    config, ref, rows = ctx["config"], ctx["reference"], ctx["rows"]
    tol, dims = config["check"], ctx["reference"].dims_of(ctx["config"])
    params = ctx["params"]
    val, vmask = _rows(ctx["val"])
    pval, pmask = _rows(ctx["eng"].pval)
    stamp = held_to_the_backdoor(val, pval, pmask, config["backdoor"])
    v_loss, v_acc, v_n, pairs = reference_eval(ref, params, dims, val, vmask)
    p_loss, p_acc, p_n, _ = reference_eval(ref, params, dims, pval, pmask)
    want = {"Validation/Loss": v_loss, "Validation/Accuracy": v_acc,
            "Poison/Poison_Loss": p_loss, "Poison/Poison_Accuracy": p_acc}
    counts = {"Validation/Accuracy": v_n, "Poison/Poison_Accuracy": p_n}
    out = {"ok": not any(stamp.values()), "n_val": v_n, "n_poison": p_n,
           "backdoor": stamp,
           "engine": {t: rows.get(t) for t in EVAL_TAGS}, "reference": want,
           "deviation": {}, "compared": {
               f"backdoor_{k}": [v, 0] for k, v in stamp.items()}}
    for tag, r in want.items():
        e = rows.get(tag, float("nan"))
        if tag in counts:
            dev = abs(e - r) * counts[tag]              # in tokens
            limit = float(tol["acc_tokens"])
        else:
            dev = abs(e - r) / max(abs(e), abs(r), 1e-30)    # relative
            limit = float(tol["val_loss_rtol" if tag.startswith("Validation")
                              else "poison_loss_rtol"])
        good = bool(np.isfinite(dev)) and dev <= limit
        out["deviation"][tag] = dev
        out["compared"][tag] = [dev, limit]
        out["ok"] = out["ok"] and good
    # routing: every pair accounted for, and each held expert's load
    n_tokens = int(val.shape[0] * (val.shape[1] - 1))
    worst, dropped, moved = 0.0, 0, 0.0
    for li, row in enumerate(pairs):
        got = np.array(
            [rows.get(f"{PAIRS}L{li}E{e}", np.nan)
             for e in range(len(row) - 1)]
            + [rows.get(f"{PAIRS}L{li}Absent", np.nan)])
        dropped += abs(int(np.nansum(got)) - n_tokens * dims["top_k"])
        dropped += abs(int(row.sum()) - n_tokens * dims["top_k"])
        moved += float(np.nansum(np.abs(got - row)))
        allowed = tol["pairs_rtol"] * row + tol["pairs_atol"]
        with np.errstate(invalid="ignore"):
            share = np.abs(got - row) / allowed
        worst = max(worst, float(np.nanmax(share)) if np.isfinite(
            share).all() else float("inf"))
    out["pairs_reference"] = [[int(c) for c in row] for row in pairs]
    out["deviation"]["pairs_worst_share_of_allowed"] = worst
    out["compared"]["pairs_worst_share_of_allowed"] = [worst, 1.0]
    out["compared"]["pairs_unaccounted"] = [dropped, 0]
    # the share of all pairs that sit with another expert than the
    # reference's: what rounding the router's input moves
    moved_share = moved / (2.0 * len(pairs) * n_tokens * dims["top_k"])
    out["deviation"]["pairs_moved_share"] = moved_share
    out["compared"]["pairs_moved_share"] = [moved_share,
                                            float(tol["pairs_moved_share"])]
    out["ok"] = bool(out["ok"] and worst <= 1.0 and dropped == 0
                     and moved_share <= tol["pairs_moved_share"])
    return out


def contract(cfg, config) -> None:
    """The flags parse to the model, the cut, the backdoor and the tokens a
    round that the file states (`mfu_pct` multiplies by
    `examples_per_round`)."""
    from defending_against_backdoors_with_robust_learning_rate_tpu.models import (
        lfm2_moe)
    assert cfg.data == "tokens" and cfg.model_arch == "lfm2_moe"
    spec = lfm2_moe.spec_from_cfg(cfg)
    assert [src for src, _k, _s in spec.layers] == config["layers_held"]
    assert [k for _s, k, _x in spec.layers] == config["layer_types"]
    assert sum(1 for _s, _k, x in spec.layers if not x) == \
        config["num_dense_layers"]
    for key, have in (
            ("hidden_size", spec.hidden), ("vocab_size", spec.vocab_held),
            ("intermediate_size", spec.dense_ffn),
            ("moe_intermediate_size", spec.moe_ffn),
            ("num_attention_heads", spec.heads),
            ("num_key_value_heads", spec.kv_heads),
            ("head_dim", spec.head_dim), ("conv_L_cache", spec.conv_taps),
            ("num_experts", spec.experts_held),
            ("expert_offset", spec.expert_offset),
            ("num_experts_per_tok", spec.top_k), ("seq_len", cfg.seq_len)):
        assert config[key] == have, key
    assert config["published"]["num_experts"]["source"] == spec.n_experts
    bd = config["backdoor"]
    assert bd["target"] == cfg.target_class
    assert bd["trigger"] == list(range(spec.vocab_held - 3, spec.vocab_held))
    assert cfg.synth_val_size % cfg.eval_bs == 0      # no padded sequence
    assert (cfg.local_ep * cfg.synth_train_size * cfg.seq_len
            == config["examples_per_round"])
    for key in ("val_loss_rtol", "poison_loss_rtol", "acc_tokens",
                "pairs_rtol", "pairs_atol", "pairs_moved_share"):
        assert config["check"][key] > 0, key
