"""Validation and backdoor numbers of a parameter tree, the plain way.

The reference's `get_loss_n_accuracy` (src/utils.py:128-157) over the clean
validation set and over the poisoned one: every `base_class` image of the
validation set with the trojan pattern stamped on its raw pixels and its
label set to `target_class` (src/federated.py:42-45). Normalisation is
ToTensor + Normalize: (x/255 - mean)/std. Pattern geometry, classes and
constants come from the configuration's file, not from the program."""

from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

BATCH = 1000


def stamp(images: np.ndarray, backdoor: Dict[str, Any]) -> np.ndarray:
    """The trojan pattern on raw pixels [n, H, W, C], every channel: each
    stroke is a block of rows and columns, ends included, set to `value`
    (the plus is one vertical and one horizontal stroke)."""
    out = images.copy()
    for stroke in backdoor["strokes"]:
        (r0, r1), (c0, c1) = stroke["rows"], stroke["cols"]
        out[:, r0:r1 + 1, c0:c1 + 1, :] = backdoor["value"]
    return out


def poisoned_set(images: np.ndarray, labels: np.ndarray,
                 backdoor: Dict[str, Any]) -> Tuple[np.ndarray, np.ndarray]:
    pick = labels == backdoor["base_class"]
    imgs = stamp(images[pick], backdoor)
    return imgs, np.full((len(imgs),), backdoor["target_class"], labels.dtype)


def loss_and_accuracy(forward, params, images: np.ndarray,
                      labels: np.ndarray, normalise: Dict[str, Any]
                      ) -> Tuple[float, float, int]:
    """(mean cross-entropy, accuracy, n) over raw uint8 images, float32 on
    the device at the highest matmul precision, summed in float64 here."""
    mean = jnp.asarray(normalise["mean"], jnp.float32)
    std = jnp.asarray(normalise["std"], jnp.float32)

    @jax.jit
    def batch(p, x, y):
        x = (x.astype(jnp.float32) / 255.0 - mean) / std
        logits = forward(p, x)
        logp = jax.nn.log_softmax(logits, axis=-1)
        ce = -jnp.take_along_axis(logp, y[:, None], axis=-1)[:, 0]
        return ce, jnp.argmax(logits, axis=-1) == y

    n = len(labels)
    pad = -n % BATCH
    x = np.concatenate([images, np.zeros((pad,) + images.shape[1:],
                                         images.dtype)])
    y = np.concatenate([labels, np.zeros((pad,), labels.dtype)]
                       ).astype(np.int32)
    losses, hits = [], []
    with jax.default_matmul_precision("highest"):
        for i in range(0, n + pad, BATCH):
            ce, hit = batch(params, x[i:i + BATCH], y[i:i + BATCH])
            losses.append(ce)
            hits.append(hit)
    ce = np.concatenate(jax.device_get(losses))[:n].astype(np.float64)
    hit = np.concatenate(jax.device_get(hits))[:n]
    return float(ce.mean()), float(hit.mean()), n
