"""The task boundary: what a client's batch is, batch -> loss, batch -> eval
metrics. A second task is a second implementation here, not a fork of
every round builder (`data/tokens.py` holds batch -> poisoned batch for
tokens, `attack/` for images).

- image task (every `--data` but `tokens`): batch `(images [bs, H, W, C],
  labels [bs])`, cross-entropy over the real samples, eval with the
  reference's 10-class confusion matrix (`fl/evaluate.py`).
- token task (`--data=tokens`): batch `[bs, T + 1]` token ids; the loss is
  the next-token cross-entropy per token over the real sequences, plus,
  where the model's training forward gives logits for tokens further on
  (multi-token prediction), `model.ahead_weight` times the mean of their
  cross-entropies; eval runs the main model only, per token over the
  positions its mask counts, and also returns the (token, expert) pairs the
  validation tokens were routed to.

What a combination of task and round does not support is refused in
`utils/compile_cache.unsupported`, with one sentence each."""

from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp
import optax

from defending_against_backdoors_with_robust_learning_rate_tpu.fl.common import (
    masked_ce)
from defending_against_backdoors_with_robust_learning_rate_tpu.ops import loops

# per-client values a token client returns beside its loss, summed over its
# steps: [sparse layers, experts_held + 1] (token, expert) pairs, the last
# column those routed to experts this chip does not hold
MOE_PAIRS = "moe_pairs"
# and how many of its sparse-layer forwards held more pairs than the sorted
# buffer's first pass takes (`model.dispatch_rows`): the second pass ran
MOE_OVERFLOW = "moe_overflow"
# and, of a model that predicts further on, its auxiliary term (before the
# weight) summed over the steps that held a real sequence, and those steps
MTP_LOSS = "mtp_loss"
MTP_STEPS = "mtp_steps"
# the aggregation rules a folded round can run: sums over clients
FOLD_RULES = ("avg", "sign")


def is_tokens(cfg) -> bool:
    return cfg.data == "tokens"


def input_shape(cfg, fed):
    """Shape of one example as the model takes it."""
    shape = tuple(fed.train.images.shape[2:])
    return (shape[0] - 1,) if is_tokens(cfg) else shape


def make_batch_loss(model, cfg, normalize, deterministic: bool = False):
    """loss(params, x, y, w, rng) -> (mean loss over the real part of the
    batch, per-step values to sum). `w` [bs] marks the real rows."""
    if is_tokens(cfg):
        def token_loss(params, x, _y, w, _rng):
            logits, pairs, *ahead = model.apply({"params": params}, x[:, :-1],
                                                train=True)

            def token_ce(lg, k=0):
                """[bs, T]: the logits at position i against the token
                1 + k on; 0 at the last k positions, which have none."""
                if not k:
                    return optax.softmax_cross_entropy_with_integer_labels(
                        lg, x[:, 1:])
                ce = optax.softmax_cross_entropy_with_integer_labels(
                    lg, jnp.pad(x[:, 1 + k:], ((0, 0), (0, k))))
                return jnp.where(jnp.arange(lg.shape[1]) < lg.shape[1] - k,
                                 ce, 0.0)

            ce = token_ce(logits)
            wf = w.astype(jnp.float32)
            n = jnp.maximum(jnp.sum(wf) * ce.shape[1], 1.0)
            over = (jnp.sum(pairs[:, :-1], axis=1)
                    > model.dispatch_rows(ce.size))
            loss = jnp.sum(ce * wf[:, None]) / n
            sums = {MOE_PAIRS: pairs.astype(jnp.float32),
                    MOE_OVERFLOW: jnp.sum(over, dtype=jnp.float32)}
            if ahead and ahead[0]:
                # multi-token prediction: module k's logits against the
                # token k + 1 on, the modules' mean weighted into the loss
                aux = sum(
                    jnp.sum(token_ce(lg, k) * wf[:, None])
                    / jnp.maximum(jnp.sum(wf) * (ce.shape[1] - k), 1.0)
                    for k, lg in enumerate(ahead[0], start=1)) / len(ahead[0])
                real = (jnp.sum(wf) > 0).astype(jnp.float32)
                loss = loss + model.ahead_weight * aux
                sums.update({MTP_LOSS: aux * real, MTP_STEPS: real})
            return loss, sums
        return token_loss

    def image_loss(params, x, y, w, rng):
        if deterministic:
            logits = model.apply({"params": params}, normalize(x),
                                 train=False)
        else:
            logits = model.apply({"params": params}, normalize(x),
                                 train=True, rngs={"dropout": rng})
        return masked_ce(logits, y, w), {}
    return image_loss


def per_client(loss, sums: Dict):
    """What local training returns beside the update: the loss alone, or
    with the task's per-client sums."""
    return {"loss": loss, **sums} if sums else loss


def split_per_client(per):
    """(losses [m], {name: [m, ...]}) of the per-client values."""
    if isinstance(per, dict):
        rest = dict(per)
        return rest.pop("loss"), rest
    return per, {}


def round_counters(sums: Dict) -> Dict:
    """The round's drained counters from the per-client sums: pairs
    computed here, pairs routed to absent experts, the largest and the
    mean load of a held expert (over layers, clients and steps), the
    sparse-layer forwards that took the second pass and, of a model with
    an auxiliary term, its mean over the clients' steps (`mtp_loss`)."""
    if MOE_PAIRS not in sums:
        return {}
    pairs = jnp.sum(sums[MOE_PAIRS], axis=0)          # [layers, held + 1]
    held = pairs[:, :-1]
    ahead = ({"mtp_loss": jnp.sum(sums[MTP_LOSS])
              / jnp.maximum(jnp.sum(sums[MTP_STEPS]), 1.0)}
             if MTP_LOSS in sums else {})
    return {**ahead,
            "moe_pairs_held": jnp.sum(held),
            "moe_pairs_absent": jnp.sum(pairs[:, -1]),
            "moe_load_max": jnp.max(held) if held.size else jnp.float32(0),
            "moe_load_mean": jnp.mean(held) if held.size else jnp.float32(0),
            "moe_overflow_steps": jnp.sum(sums[MOE_OVERFLOW])}


MOE_ROUND_KEYS = ("moe_pairs_held", "moe_pairs_absent", "moe_load_max",
                  "moe_load_mean", "moe_overflow_steps")


def make_eval_fn(model, normalize, cfg):
    """eval_fn(params, x [nb, bs, ...], y, w [nb, bs]) -> (loss, accuracy,
    third): the image task's is `fl/evaluate.make_eval_fn` (third: per-class
    accuracy); the token task's counts the positions `y` [nb, bs, T] marks
    (third: the pairs routed, [sparse layers, experts_held + 1])."""
    if not is_tokens(cfg):
        from defending_against_backdoors_with_robust_learning_rate_tpu.fl.evaluate import (
            make_eval_fn as make_image_eval)
        return make_image_eval(model, normalize, cfg.n_classes)

    @jax.jit
    def eval_fn(params, tokens, mask, weights):
        def body(carry, batch):
            loss_sum, correct, n, pairs = carry
            x, mk, w = batch
            logits, pr = model.apply({"params": params}, x[:, :-1],
                                     train=False)
            tgt = x[:, 1:]
            ce = optax.softmax_cross_entropy_with_integer_labels(logits, tgt)
            wt = mk.astype(jnp.float32) * w[:, None]
            hit = (jnp.argmax(logits, axis=-1) == tgt).astype(jnp.float32)
            return (loss_sum + jnp.sum(ce * wt), correct + jnp.sum(hit * wt),
                    n + jnp.sum(wt), pairs + pr.astype(jnp.float32)), None

        init = (jnp.float32(0.0), jnp.float32(0.0), jnp.float32(0.0),
                jnp.zeros(model.pairs_shape, jnp.float32))
        py_loops = loops.cpu_backend() and tokens.shape[0] <= 32
        (loss_sum, correct, n, pairs), _ = loops.maybe_unrolled_scan(
            body, init, (tokens, mask, weights), py_loops)
        n = jnp.maximum(n, 1.0)
        return loss_sum / n, jnp.clip(correct / n, 0.0, 1.0), pairs

    return eval_fn
