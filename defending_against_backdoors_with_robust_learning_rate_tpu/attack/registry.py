"""Adaptive-adversary attack registry: config-selected attack strategies
that compose with every round-program dispatch surface.

The attack surface used to be exactly one fixed behavior — the paper's
static trojan stamped at dataset construction (attack/poison.py). But
sign-vote defenses like RLR are broken by *adaptive* attackers, not fixed
triggers ("Learning to Backdoor Federated Learning", arXiv:2303.03320),
so the simulator needs a pluggable strategy space (FL_PyTorch,
arXiv:2202.03099, is the precedent for scenario-pluggable FL simulation).
This module is that space's single source: ``--attack <name>`` selects a
strategy, the strategy declares its two hooks, and every round builder
consults the SAME predicates so the dispatch surfaces can never drift.

Two hook kinds, both collective-free by construction:

- **data hook** (``data_mode``): which trigger geometry each corrupt
  client stamps at construction/gather time. ``legacy`` is the
  reference's exact behavior (per-agent stamp, bitwise-pinned — the
  ``static`` strategy IS the historical poison path, untouched);
  ``split`` deals the full pattern across the corrupt cohort
  (attack/dba.py).
- **in-jit update hook** (``in_jit``): a per-row multiplicative scale on
  the stacked client updates, applied INSIDE the round program right
  after local training — before fault injection and server-side payload
  validation, so norm caps and robust aggregators see what a real server
  would. Corrupt flags derive from real client ids on every path (in-jit
  sampling, cohort recomputation, or the host-sampled flag argument), and
  the schedule gate (attack/schedule.py) is a pure function of the traced
  round index — so the transform adds ZERO collectives on the vmap,
  shard_map and cohort paths alike (pinned by the
  ``*_atk_*`` specs in analysis/contracts.py).

Adding a strategy: one module with its scale/stamp function, one
``AttackStrategy`` row here, and the scenario matrix
(scripts/sweep_scenarios.py) picks it up by name.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Optional

import jax
import jax.numpy as jnp

from defending_against_backdoors_with_robust_learning_rate_tpu.attack import (
    boost as boost_mod, schedule, signflip as signflip_mod)
from defending_against_backdoors_with_robust_learning_rate_tpu.ops import tree


@dataclasses.dataclass(frozen=True)
class AttackStrategy:
    """One registered adversary behavior.

    ``data_mode``: 'legacy' = the reference per-agent stamp (static
    parity), 'split' = the DBA round-robin pattern deal (attack/dba.py).
    ``scale_rows``: the strategy's in-jit update hook —
    ``(corrupt_flags, active, boost) -> [m] f32 row scale`` — or None
    for the data-poisoning strategies; a non-None hook needs the
    corrupt-slot flags in-program and composes with the round-index
    schedule."""
    name: str
    data_mode: str      # legacy | split
    summary: str        # one-line banner text
    scale_rows: Optional[Callable] = None

    @property
    def in_jit(self) -> bool:
        return self.scale_rows is not None


REGISTRY = {
    "static": AttackStrategy(
        "static", "legacy",
        "the paper's static trojan (data poisoning only; bitwise the "
        "pre-registry path)"),
    "dba": AttackStrategy(
        "dba", "split",
        "distributed trigger: the full pattern dealt round-robin across "
        "the corrupt cohort (attack/dba.py)"),
    "boost": AttackStrategy(
        "boost", "legacy",
        "model-replacement boosting: corrupt updates scaled by "
        "--attack_boost to survive averaging (attack/boost.py)",
        scale_rows=boost_mod.scale_rows),
    "signflip": AttackStrategy(
        "signflip", "legacy",
        "RLR-aware anti-vote: corrupt updates negated (x -boost) to "
        "shrink honest sign margins (attack/signflip.py)",
        scale_rows=signflip_mod.scale_rows),
}


def get(cfg) -> AttackStrategy:
    strat = REGISTRY.get(cfg.attack)
    if strat is None:
        raise ValueError(f"--attack must be one of {sorted(REGISTRY)}, "
                         f"got {cfg.attack!r}")
    return strat


def check(cfg) -> None:
    """Validate the whole attack config once, loudly, at engine/planner
    construction — not deep inside a trace."""
    strat = get(cfg)
    schedule.check(cfg)
    if cfg.attack_boost <= 0:
        raise ValueError(f"--attack_boost must be > 0, got "
                         f"{cfg.attack_boost} (signflip applies the "
                         f"negation itself)")
    if not strat.in_jit and not schedule.is_trivial(cfg):
        raise ValueError(
            f"--attack {strat.name} poisons data at construction time — "
            f"there is no per-round behavior for a schedule to gate; "
            f"attack_start/attack_stop/attack_every compose with the "
            f"in-jit strategies "
            f"({sorted(s.name for s in REGISTRY.values() if s.in_jit)})")


def in_jit(cfg) -> bool:
    """Does this config transform updates inside the round program?
    (Drives host_takes_flags and the host-mode chaining budget — single
    source for every builder.)"""
    return get(cfg).in_jit


def needs_round(cfg) -> bool:
    """Does the round program need the traced round index for the attack
    (an in-jit strategy under a non-trivial schedule)? Composes into
    fl/rounds.step_takes_round alongside the churn lifecycle."""
    return in_jit(cfg) and not schedule.is_trivial(cfg)


def update_scale(cfg, corrupt_flags, active, boost=None):
    """The strategy's [m] per-row multiplicative scale. ``boost``
    overrides ``cfg.attack_boost`` with a traced scalar — the
    multi-tenant pack's per-tenant knob (fl/tenancy.py); None keeps the
    config constant (the solo paths, program unchanged)."""
    strat = get(cfg)
    if strat.scale_rows is None:
        raise ValueError(f"attack {strat.name!r} has no in-jit update "
                         f"hook")
    return strat.scale_rows(corrupt_flags, active,
                            cfg.attack_boost if boost is None else boost)


def apply_update_attack(cfg, stacked_updates, corrupt_flags,
                        active=None, boost=None):
    """Apply the in-jit strategy to the [m(/d), ...]-stacked updates.

    ``corrupt_flags`` marks which rows hold malicious clients (the
    caller's slot flags — full [m] on single-device paths, this device's
    local block on shard_map paths); ``active`` is the scalar schedule
    gate (None = always on, the trivial-schedule fast path). A None
    flags argument is a wiring bug on the caller's dispatch surface, not
    a soft degrade: an attack silently not applied would corrupt every
    scenario-matrix row downstream, so fail at trace time."""
    if not in_jit(cfg):
        return stacked_updates
    if corrupt_flags is None:
        raise ValueError(
            f"--attack {cfg.attack} transforms updates in-jit and needs "
            f"the corrupt-slot flags; this dispatch surface has no flag "
            f"channel (host-sampled chained blocks) — run device-resident "
            f"or cohort-sampled")
    with jax.named_scope("attack"):
        scale = update_scale(cfg, corrupt_flags, active, boost=boost)

        def leaf(u):
            s = scale.reshape((-1,) + (1,) * (u.ndim - 1))
            return (u.astype(jnp.float32) * s).astype(u.dtype)
        return tree.map(leaf, stacked_updates)


def schedule_active(cfg, rnd):
    """Replicated scalar schedule gate for round ``rnd`` (None when the
    attack needs no gate — always-on or not in-jit)."""
    if not needs_round(cfg):
        return None
    if rnd is None:
        raise ValueError(
            f"--attack {cfg.attack} with a schedule needs the round index "
            f"in-program, but this dispatch surface has no round channel "
            f"(host-sampled mode) — run device-resident or "
            f"cohort-sampled, or drop attack_start/attack_stop/"
            f"attack_every")
    return schedule.active(cfg, rnd)


def stamp_for_agent(cfg, agent_id: int):
    """Corrupt agent ``agent_id``'s trigger stamp under the selected
    strategy — THE stamp source for the dense build, the bank-row gather
    and any future data surface (attack/poison.poison_client_row routes
    here, so every path stamps bitwise-identical pixels)."""
    if get(cfg).data_mode == "split":
        from defending_against_backdoors_with_robust_learning_rate_tpu.attack import (
            dba)
        return dba.stamp_for_agent(cfg, agent_id)
    from defending_against_backdoors_with_robust_learning_rate_tpu.attack.patterns import (
        build_stamp)
    return build_stamp(cfg.data, cfg.pattern_type, agent_idx=agent_id,
                       data_dir=cfg.data_dir)


def banner(cfg) -> Optional[str]:
    """Driver log line for a non-default attack config."""
    strat = get(cfg)
    if strat.name == "static":
        return None
    msg = f"[attack] {strat.name}: {strat.summary}"
    if strat.in_jit:
        msg += f"; boost x{cfg.attack_boost}"
        if not schedule.is_trivial(cfg):
            stop = cfg.attack_stop if cfg.attack_stop else "inf"
            msg += (f"; schedule rounds [{cfg.attack_start}, {stop}) "
                    f"every {cfg.attack_every}")
    return msg
