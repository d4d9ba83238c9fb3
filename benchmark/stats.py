"""Arithmetic of the end-to-end metrics: no clock, no JAX, hand-checkable."""

from __future__ import annotations

import math
from typing import Sequence


def percentile(values: Sequence[float], q: float) -> float:
    """The q-th percentile (0..100) by linear interpolation between closest
    ranks (numpy's default): rank = q/100 * (n-1)."""
    if not values:
        raise ValueError("percentile of no values")
    v = sorted(values)
    rank = q / 100.0 * (len(v) - 1)
    lo = math.floor(rank)
    hi = min(lo + 1, len(v) - 1)
    return v[lo] + (v[hi] - v[lo]) * (rank - lo)


def median(values: Sequence[float]) -> float:
    return percentile(values, 50.0)


def intervals(stamps: Sequence[float]) -> list:
    """Differences of successive stamps."""
    return [b - a for a, b in zip(stamps[:-1], stamps[1:], strict=True)]


def rounds_per_s(first_stamp: float, unit_stamps: Sequence[float],
                 unit_rounds: Sequence[int]) -> float:
    """Rounds of the units stamped in the window over (last stamp - first
    stamp). `first_stamp` opens the window; unit i completed at
    `unit_stamps[i]` and held `unit_rounds[i]` rounds."""
    if not unit_stamps:
        raise ValueError("no unit completed inside the window")
    span = unit_stamps[-1] - first_stamp
    if span <= 0:
        raise ValueError("window has no length")
    return sum(unit_rounds) / span


def operation_counts(unit_rounds: Sequence[int],
                     unit_ok: Sequence[bool]) -> tuple:
    """(attempted, failed) in rounds: a round is attempted when its unit was
    dispatched in the window and failed when that unit did not complete or
    left a non-finite parameter. `unit_ok` may be shorter than
    `unit_rounds`: units past its end never completed."""
    attempted = sum(unit_rounds)
    failed = 0
    for i, n in enumerate(unit_rounds):
        if i >= len(unit_ok) or not unit_ok[i]:
            failed += n
    return attempted, failed
