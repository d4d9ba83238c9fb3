"""Shared pieces of the train/eval compute path."""

from __future__ import annotations

import jax.numpy as jnp
import optax


def make_normalizer(mean, std, raw_is_normalized: bool):
    """Raw pixels -> model input. For uint8 datasets this is ToTensor+Normalize
    (x/255 - mean)/std with the reference constants (src/utils.py:101,113-116);
    fedemnist inputs are already normalized floats (identity)."""
    mean = jnp.asarray(mean, jnp.float32)
    std = jnp.asarray(std, jnp.float32)

    def norm(x):
        x = x.astype(jnp.float32)
        if raw_is_normalized:
            return x
        return (x / 255.0 - mean) / std
    return norm


def masked_ce(logits, labels, weights):
    """Cross-entropy mean over the real (unpadded) samples of a batch —
    matches nn.CrossEntropyLoss's batch mean (src/agent.py:47) when the batch
    is partially padding."""
    ce = optax.softmax_cross_entropy_with_integer_labels(logits, labels)
    w = weights.astype(jnp.float32)
    return jnp.sum(ce * w) / jnp.maximum(jnp.sum(w), 1.0)

