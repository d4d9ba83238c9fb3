"""The task boundary: what a client's batch is, batch -> loss, batch -> eval
metrics. A second task is a second implementation here, not a fork of
every round builder (`data/tokens.py` holds batch -> poisoned batch for
tokens, `attack/` for images).

- image task (every `--data` but `tokens`): batch `(images [bs, H, W, C],
  labels [bs])`, cross-entropy over the real samples, eval with the
  reference's 10-class confusion matrix (`fl/evaluate.py`).
- token task (`--data=tokens`): batch `[bs, T + 1]` token ids; the loss is
  the next-token cross-entropy per token over the real sequences; eval is
  per token over the positions its mask counts, and also returns the
  (token, expert) pairs the validation tokens were routed to.

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
            logits, pairs = model.apply({"params": params}, x[:, :-1],
                                        train=True)
            ce = optax.softmax_cross_entropy_with_integer_labels(
                logits, x[:, 1:])
            wf = w.astype(jnp.float32)
            n = jnp.maximum(jnp.sum(wf) * ce.shape[1], 1.0)
            over = (jnp.sum(pairs[:, :-1], axis=1)
                    > model.dispatch_rows(ce.size))
            return (jnp.sum(ce * wf[:, None]) / n,
                    {MOE_PAIRS: pairs.astype(jnp.float32),
                     MOE_OVERFLOW: jnp.sum(over, dtype=jnp.float32)})
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
    mean load of a held expert (over layers, clients and steps), and the
    sparse-layer forwards that took the second pass."""
    if MOE_PAIRS not in sums:
        return {}
    pairs = jnp.sum(sums[MOE_PAIRS], axis=0)          # [layers, held + 1]
    held = pairs[:, :-1]
    return {"moe_pairs_held": jnp.sum(held),
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
