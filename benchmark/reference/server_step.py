"""The reference's server step, transcribed to NumPy float64.

`compute_robustLR` + `agg_avg` + the parameter update of the paper's code
(src/aggregation.py:19-54): per coordinate, the learning rate is +server_lr
where |sum_k sign(u_k)| reaches the threshold and -server_lr elsewhere; the
aggregate is the data-size-weighted mean; new = old + lr * aggregate. One
leaf at a time, so the host never holds a whole stack in float64."""

from __future__ import annotations

import numpy as np


def robust_lr(updates: np.ndarray, threshold: float,
              server_lr: float) -> np.ndarray:
    """updates [m, ...] -> learning rate per coordinate, float64."""
    votes = np.abs(np.sign(updates).sum(axis=0, dtype=np.float64))
    return np.where(votes >= threshold, server_lr, -server_lr)


def fedavg(updates: np.ndarray, sizes: np.ndarray) -> np.ndarray:
    """sum_k n_k u_k / sum_k n_k in float64."""
    w = sizes.astype(np.float64)
    return np.tensordot(w, updates.astype(np.float64), axes=1) / w.sum()


def server_step(params: np.ndarray, updates: np.ndarray, sizes: np.ndarray,
                threshold: float, server_lr: float):
    """(lr, new_params) of one leaf, both float64."""
    lr = (robust_lr(updates, threshold, server_lr) if threshold > 0
          else np.full(params.shape, server_lr, np.float64))
    return lr, params.astype(np.float64) + lr * fedavg(updates, sizes)
