"""Multi-host (DCN) support — the scale-out path for v5e-256-class meshes.

The reference has no distributed backend at all (SURVEY.md 2.2: no
torch.distributed/NCCL/MPI; its only "multi-GPU" story is backgrounding
independent processes, src/runner.sh:12-18). Here multi-host is first-class:

- one process per host, rendezvoused with `jax.distributed.initialize`
  (driven by --coordinator/--num_processes/--process_id flags, or the
  standard cloud env auto-detection when the flags are absent);
- ONE global 1-D `agents` mesh over all hosts' devices, ordered by
  `mesh_utils.create_hybrid_device_mesh` so that neighboring mesh positions
  are ICI neighbors and the DCN (inter-host) hops are minimized — the
  psum/all_gather/all_to_all collectives in parallel/rounds.py then ride
  ICI within a slice and DCN only at slice boundaries;
- process-local numpy arrays are promoted to global jax.Arrays (replicated
  for params/datasets — every host loads the identical seeded data — and
  agents-sharded for per-agent stacks);
- the aggregation collective PLAN matters most here: per-leaf psums
  (2L+2 on the flagship) are latency-bound over DCN, and `agg_plan_note`
  prints the plan a mesh is about to run so pod bring-up logs show the
  collective shape next to the topology.

Single-process runs degrade transparently: every helper is a no-op or the
trivial local construction, so the same driver code serves a laptop CPU, a
single TPU chip, a v5e-8 slice, and a multi-host pod.
"""

from __future__ import annotations


import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from defending_against_backdoors_with_robust_learning_rate_tpu.parallel.mesh import (
    AGENTS_AXIS)


def maybe_initialize(coordinator: str = "", num_processes: int = 0,
                     process_id: int = -1) -> None:
    """Rendezvous this process into the multi-host job.

    With explicit flags, passes them through; with no flags on a cloud TPU
    pod, `jax.distributed.initialize()` auto-detects from the environment.
    Safe to skip entirely for single-process runs (the default)."""
    if num_processes > 1 or coordinator:
        jax.distributed.initialize(
            coordinator_address=coordinator or None,
            num_processes=num_processes or None,
            process_id=process_id if process_id >= 0 else None)


def is_lead() -> bool:
    """True on the process that owns logging/metrics/checkpoint writes."""
    return jax.process_index() == 0


def global_agents_mesh(n_devices: int = 0) -> Mesh:
    """A 1-D `agents` mesh over the job's GLOBAL device list.

    Multi-host: hybrid ICI/DCN ordering via mesh_utils, so the agent axis
    walks each host's slice contiguously before crossing DCN. The mesh MUST
    span every process (each host can only run SPMD programs whose mesh
    includes its addressable devices), so a partial n_devices is rejected
    rather than silently excluding hosts. Single-host: parallel/mesh
    construction."""
    if jax.process_count() == 1:
        from defending_against_backdoors_with_robust_learning_rate_tpu.parallel.mesh import (
            make_mesh)
        return make_mesh(n_devices)
    total = jax.device_count()
    if n_devices not in (0, total):
        raise ValueError(
            f"multi-host mesh must span all {total} global devices, got "
            f"n_devices={n_devices}; pick num_agents/agent_frac so the "
            f"per-round participant count is divisible by {total}")
    from jax.experimental import mesh_utils
    # process_is_granule=True: one DCN granule per *process*. The default
    # granule is the slice, and on any slice spanning multiple hosts
    # (v5e-16 .. v5e-256) slice_count != process_count, which would make
    # this construction raise. Per-process granules are valid on every
    # topology and still order ICI neighbors contiguously within a host.
    devices = mesh_utils.create_hybrid_device_mesh(
        mesh_shape=(jax.local_device_count(),),
        dcn_mesh_shape=(jax.process_count(),),
        process_is_granule=True).reshape(-1)
    return Mesh(devices, (AGENTS_AXIS,))


def require_pod_divisible(m: int, what: str) -> int:
    """Global-mesh precondition: the mesh must span every host's devices
    (each host can only run SPMD programs whose mesh includes its
    addressable devices), so the per-round participant count has to divide
    over the full pod. Returns the pod's device count."""
    n = jax.device_count()
    if m % n != 0:
        raise ValueError(
            f"agents_per_round={m} must be divisible by the pod's {n} "
            f"devices for a {what} run; adjust --num_agents/--agent_frac")
    return n


def agg_plan_note(cfg, params) -> str:
    """One bring-up log line for the aggregation collective plan this
    mesh will run each round: it belongs next to the `[mesh]` topology
    line in the driver log."""
    n_leaves = len(jax.tree_util.tree_leaves(params))
    if cfg.aggr in ("avg", "sign"):
        per_leaf = 2 if (cfg.aggr == "avg"
                         and cfg.robustLR_threshold > 0) else 1
        return (f"leaf aggregation: {per_leaf} psum(s) x {n_leaves} "
                f"leaves + scalars per round")
    if cfg.aggr == "rfa":
        return ("leaf aggregation: rfa's replicated Weiszfeld iterate "
                "(two psums per iteration, no transpose)")
    return (f"leaf aggregation: {cfg.aggr} rides the all_to_all "
            f"transpose plan over {n_leaves} leaves")


def take_agents_sharded(mesh: Mesh, base: np.ndarray, ids: np.ndarray):
    """`base[ids]` as a global jax.Array sharded over the `agents` axis,
    WITHOUT materializing the full [m, ...] stack on any host.

    Every process holds the full `base` (replicated seeded data) and the
    identical `ids`; `jax.make_array_from_callback` asks each process only
    for its addressable shards, so each host fancy-index-copies just its
    m/P rows. Correct for any mesh device order (hybrid ICI/DCN
    included)."""
    sharding = NamedSharding(mesh, P(AGENTS_AXIS))
    shape = (len(ids),) + base.shape[1:]
    return jax.make_array_from_callback(
        shape, sharding, lambda idx: base[ids[idx[0]]])


def take_agents_sharded_block(mesh: Mesh, base: np.ndarray,
                              ids_blk: np.ndarray):
    """`base[ids_blk]` for a [chain, m] id block as a global
    [chain, m, ...] jax.Array sharded on the m axis (P(None, agents)) —
    the chained-host payload (fl/rounds.make_chained_host). Same
    no-full-stack property as `take_agents_sharded`: each process
    fancy-index-copies only its addressable [chain, m/P, ...] block."""
    sharding = NamedSharding(mesh, P(None, AGENTS_AXIS))
    shape = ids_blk.shape + base.shape[1:]
    return jax.make_array_from_callback(
        shape, sharding, lambda idx: base[ids_blk[idx[0], idx[1]]])


def put_replicated(mesh: Mesh, x):
    """Promote (a pytree of) process-local arrays, identical on every host
    (seeded data / init), to fully-replicated global jax.Arrays."""
    sharding = NamedSharding(mesh, P())

    def one(a):
        a = np.asarray(a)
        if jax.process_count() == 1:
            return jax.device_put(a, sharding)
        from jax.experimental import multihost_utils
        return multihost_utils.host_local_array_to_global_array(
            a, mesh, P())
    return jax.tree_util.tree_map(one, x)


