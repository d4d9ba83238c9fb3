"""Jitted evaluation: loss, accuracy, per-class accuracy.

Reference: `get_loss_n_accuracy` (src/utils.py:128-157) — batch loop with a
Python double-loop confusion matrix (the slowest part of the reference's
eval, SURVEY.md 3.5). Here the confusion matrix is a scatter-add inside a
`lax.scan` over fixed-shape batches; padding samples carry weight 0. The
10-class hardcoding is kept for parity (SURVEY.md 2.3.7)."""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax

from defending_against_backdoors_with_robust_learning_rate_tpu.ops import loops


def pad_eval_set(images: np.ndarray, labels: np.ndarray, bs: int
                 ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Pad to a multiple of bs and reshape to [nb, bs, ...] + weight mask.
    `labels` may carry trailing axes (the token task's per-position
    masks)."""
    n = len(labels)
    nb = max(1, -(-n // bs))
    pad = nb * bs - n
    if pad:
        images = np.concatenate([images, np.zeros((pad,) + images.shape[1:],
                                                  images.dtype)])
        labels = np.concatenate([labels, np.zeros(
            (pad,) + labels.shape[1:], labels.dtype)])
    w = (np.arange(nb * bs) < n).astype(np.float32)
    return (images.reshape((nb, bs) + images.shape[1:]),
            labels.reshape((nb, bs) + labels.shape[1:]).astype(np.int32),
            w.reshape(nb, bs))


def make_eval_fn(model, normalize, n_classes: int = 10):
    """Returns eval_fn(params, images[nb,bs,...], labels[nb,bs], w[nb,bs])
    -> (avg_loss, accuracy, per_class_accuracy[n_classes])."""

    @jax.jit
    def eval_fn(params, images, labels, weights):
        def body(carry, batch):
            loss_sum, correct, conf = carry
            x, y, w = batch
            logits = model.apply({"params": params}, normalize(x), train=False)
            ce = optax.softmax_cross_entropy_with_integer_labels(logits, y)
            pred = jnp.argmax(logits, axis=-1)
            loss_sum = loss_sum + jnp.sum(ce * w)
            correct = correct + jnp.sum((pred == y) * w)
            conf = conf.at[y, pred].add(w)
            return (loss_sum, correct, conf), None

        init = (jnp.float32(0.0), jnp.float32(0.0),
                jnp.zeros((n_classes, n_classes), jnp.float32))
        # XLA:CPU conv-in-while slow path (ops/loops.py): unroll short eval
        # loops; the cap is higher than local training's (32 vs 16) because
        # the fwd-only body is ~3x cheaper to trace/compile per step
        py_loops = loops.cpu_backend() and images.shape[0] <= 32
        (loss_sum, correct, conf), _ = loops.maybe_unrolled_scan(
            body, init, (images, labels, weights), py_loops)
        n = jnp.sum(weights)
        per_class = jnp.diag(conf) / jnp.maximum(jnp.sum(conf, axis=1), 1.0)
        # f32 rounding can push correct/n a hair above 1.0 (round-1
        # results.json recorded poison_acc=1.0000001); clamp the ratios.
        acc = jnp.clip(correct / n, 0.0, 1.0)
        return loss_sum / n, acc, jnp.clip(per_class, 0.0, 1.0)

    return eval_fn
