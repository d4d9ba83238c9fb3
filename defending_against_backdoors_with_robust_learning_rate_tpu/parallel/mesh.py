"""Device-mesh construction for the `agents` axis.

The reference's only multi-device story is backgrounding independent
processes pinned to cuda:0/cuda:1 (src/runner.sh:12-18; SURVEY.md 2.2). The
TPU build owns one 1-D mesh with a named axis ``"agents"``: the m sampled
clients of a round are blocked m/d per device, local training runs under
``shard_map``, and aggregation is psum/all_gather collectives over ICI.
"""

from __future__ import annotations

import numpy as np
import jax
from jax.sharding import Mesh

AGENTS_AXIS = "agents"


def agent_mesh_size(requested: int, agents_per_round: int,
                    n_devices: int | None = None) -> int:
    """Largest device count <= min(requested or all, available) that divides
    the per-round participant count (blocking policy, SURVEY.md 7.2.5 — e.g.
    m=10 on a v5e-8 slice uses 5 devices, 2 agents per device)."""
    avail = n_devices if n_devices is not None else len(jax.devices())
    want = requested if requested > 0 else avail
    return next(d for d in range(min(want, avail), 0, -1)
                if agents_per_round % d == 0)


def pick_agent_mesh_size(requested: int, agents_per_round: int,
                         n_devices: int | None = None) -> int:
    """`agent_mesh_size` for the program about to be built. A mesh smaller
    than the one asked for says so: the run is otherwise indistinguishable
    from the requested one except by its speed."""
    avail = n_devices if n_devices is not None else len(jax.devices())
    want = requested if requested > 0 else avail
    picked = agent_mesh_size(requested, agents_per_round, avail)
    if picked < want:
        print(f"[mesh] WARNING: --mesh {requested} asked for {want} "
              f"device(s), using {picked}: {avail} available, and the mesh "
              f"must divide agents_per_round={agents_per_round}")
    return picked


def make_mesh(n_devices: int = 0) -> Mesh:
    devs = jax.devices()
    n = n_devices if n_devices > 0 else len(devs)
    return Mesh(np.array(devs[:n]), (AGENTS_AXIS,))
