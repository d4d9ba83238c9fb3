"""Operations the algorithm needs, from shapes, and the table of peaks they
are divided by. Recomputed operations (remat) are not counted:
`mfu_pct` is model utilisation."""

from __future__ import annotations

import json
import os
from typing import Any, Dict

HERE = os.path.dirname(os.path.abspath(__file__))


def peaks(device_kind: str) -> Dict[str, Any]:
    """Published peaks of `device_kind`. A chip that is not in
    `peaks.json` is an error, not a default."""
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)["by_device_kind"]
    if device_kind not in table:
        raise ValueError(
            f"device_kind {device_kind!r} is not in benchmark/peaks.json; "
            f"add its published peaks, with their source, before a "
            f"utilisation is computed against them")
    return table[device_kind]


def round_train_flops(forward_flops: float, examples_per_round: float
                      ) -> float:
    """Forward + backward of one federated round: three forward passes'
    worth per example (backward = 2 x forward), over every real example
    every sampled client trains on in its local epochs."""
    return 3.0 * forward_flops * examples_per_round
