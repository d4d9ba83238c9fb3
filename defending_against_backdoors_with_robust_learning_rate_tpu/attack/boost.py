"""Model-replacement boosting: scale corrupt updates to survive averaging.

The classic backdoor amplifier ("How To Backdoor Federated Learning",
arXiv:1807.00459): with m clients averaged, a single attacker's update is
diluted by ~1/m, so the attacker submits ``boost * u`` — at boost ≈ m the
poisoned model *replaces* the average. Weighted FedAvg dilutes by the
sample-size weights instead, so the effective replacement factor is
``boost * w_corrupt / sum(w)``.

What the defenses see:

- plain FedAvg: defeated — the boosted update dominates the weighted sum
  (tests/test_attack.py pins poison accuracy rising on a quick CPU
  config);
- RLR: the vote is on *signs*, which boosting cannot change — backdoor
  coordinates still lack the honest-agreement margin, their learning rate
  flips, and the boosted magnitude is applied in the WRONG direction
  (the paper's mechanism, held by the same test);
- ``--payload_norm_cap``: a boosted update's L2 norm grows by exactly
  ``boost``, so server-side validation masks it out — the attack is
  applied BEFORE payload validation in the round body precisely so this
  interaction is real.

The transform is a per-row multiplicative scale on the stacked updates —
elementwise and collective-free (the corrupt flags and the schedule
gate arrive replicated on every device of a mesh).
"""

from __future__ import annotations

import jax.numpy as jnp


def scale_rows(corrupt_flags, active, boost: float):
    """[m] f32 multiplicative row scale: ``boost`` on corrupt slots while
    the schedule is active, 1 elsewhere. ``active`` is a scalar bool (or
    None = always on)."""
    hit = corrupt_flags if active is None else corrupt_flags & active
    return jnp.where(hit, jnp.float32(boost), jnp.float32(1.0))
