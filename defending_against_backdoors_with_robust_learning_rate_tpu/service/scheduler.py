"""Resident fleet scheduler: bin-packed, ledger-driven experiment
packing that never idles the chip (ISSUE 16).

The FIFO queue (service/queue.py --tenants E) packs shape-compatible
cells E at a time, but a pack only retires when its SLOWEST member
finishes and a failed or quarantined tenant leaves its slot computing
masked garbage for the rest of the run. This module closes that gap
with three layers on top of service/tenancy.PackEngine:

- `CapacityModel` — how many tenants fit the device: an ANALYTIC
  bytes-per-tenant estimate (params x dtype x workspace multiplier,
  buffered carry ~2x params — the r13 measurement) against the
  device-resident budget (utils/compile_cache.DEVICE_RESIDENT_BYTES),
  with a conservative cap on the CPU backend where host RAM backs the
  "HBM" and the model is uncalibrated. The r14 HBM-watermark bench
  (BENCH_NOTES.md) is the calibration source; until those numbers land
  the estimate deliberately over-counts (workspace x3) so the packer
  under-packs rather than OOMs.
- `plan_fleet` — deterministic bin-packing: cells group by their
  `tenant_pack_key` (the compile-cache fingerprint's own field algebra,
  exactly like the FIFO planner) into per-shape-class BINS of
  capacity-modelled width; ineligible cells fall to the serial path and
  cohort-sampled bins run as fixed FIFO packs (the shared bank gather
  serves ONE draw — no mid-run backfill, by construction).
- `Scheduler` — the pure slot state machine: width W slots + a pending
  deque, consuming LEDGER-SHAPED events (`scheduler/slot_done`,
  `health/incident`, `service/recover`, `scheduler/evict`) and emitting
  deterministic decisions (backfill slot e with the next queued cell /
  idle slot e). No jax, no clocks — a synthetic event stream drives it
  in tests exactly like the live loop does.
- `run_bin` — the resident loop: one PackEngine per bin, pack clock
  advancing in snap-blocks PAST `cfg.rounds`; a slot whose effective
  round (pack_round + rnd_offset) reaches `rounds` retires and its slot
  is backfilled at offset = -pack_round so the incoming cell's key
  streams and schedule gates replay its solo program exactly
  (fl/tenancy.TenantKnobs.rnd_offset); a per-tenant health enforcement
  failure evicts JUST that slot (record-and-skip — the queue rows the
  failure) and backfills it the same way. Every admit/evict/backfill/
  idle decision is also emitted on the queue's event ledger, so the
  live run and the synthetic-stream tests see the same records.

Throughput accounting: slot OCCUPANCY = busy-slot-dispatches over
total-slot-dispatches (idle slots compute masked garbage — the metric,
not a mask, accounts for the waste), and the fleet-level `cells/hour`
gauge rides the Prometheus textfile exporter plus a `fleet`
comparability group of a trajectory series (obs/trajectory.py)
like every other perf number.
"""

from __future__ import annotations

import collections
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

from defending_against_backdoors_with_robust_learning_rate_tpu.utils import (
    compile_cache)

# bytes-per-tenant multipliers (analytic; r14 calibration pending):
# params + server update + donation/eval scratch
WORKSPACE_FACTOR = 3.0
# buffered packs carry (params, state): sum + sign-vote accumulators
# measured ~2x params bytes at K <= m (BENCH_NOTES r13)
BUFFERED_STATE_FACTOR = 2.0
# share of the device budget reserved for the SHARED side (train stacks,
# eval sets, executables) before tenants bill against it
TENANT_BUDGET_FRACTION = 0.5
# CPU backend: host RAM backs the "HBM" budget and the analytic model is
# uncalibrated there — cap the pack width instead of trusting it
CPU_MAX_WIDTH = 8

_DTYPE_BYTES = {"f32": 4, "bf16": 2, "f16": 2}


class CapacityModel:
    """HBM-vs-E: how many resident tenants one device carries.

    Analytic until the r14 HBM-watermark bench lands (BENCH_NOTES.md —
    the calibration TODO is recorded there): per-tenant bytes =
    param_count x dtype_bytes x (1 + workspace) [+ buffered carry], and
    the tenant side of the device budget is TENANT_BUDGET_FRACTION of
    utils/compile_cache.DEVICE_RESIDENT_BYTES. Deliberately
    conservative — under-packing costs throughput, over-packing OOMs a
    resident fleet mid-run."""

    def __init__(self, budget_bytes: Optional[int] = None,
                 backend: Optional[str] = None):
        self.budget = (compile_cache.DEVICE_RESIDENT_BYTES
                       if budget_bytes is None else int(budget_bytes))
        if backend is None:
            import jax
            backend = jax.default_backend()
        self.backend = backend

    def tenant_bytes(self, cfg) -> int:
        """Analytic per-tenant resident footprint (no device work: the
        param tree is shape-evaluated, never materialized)."""
        from defending_against_backdoors_with_robust_learning_rate_tpu.fl import (
            buffered)
        from defending_against_backdoors_with_robust_learning_rate_tpu.models.registry import (
            abstract_params, get_model, param_count)
        # remat changes what backward keeps, never the parameter tree
        model = get_model(cfg.data, cfg.model_arch, cfg.dtype)
        n_params = param_count(abstract_params(model, cfg.image_shape))
        per = n_params * _DTYPE_BYTES.get(cfg.dtype, 4)
        mult = 1.0 + WORKSPACE_FACTOR
        if buffered.is_buffered(cfg):
            mult += BUFFERED_STATE_FACTOR
        return max(1, int(per * mult))

    def max_width(self, cfg, requested: int) -> int:
        """The pack width for this shape class: the user's E, clamped by
        what the budget fits (and by CPU_MAX_WIDTH on the CPU backend)."""
        tenant_budget = int(self.budget * TENANT_BUDGET_FRACTION)
        fit = max(1, tenant_budget // self.tenant_bytes(cfg))
        width = max(1, min(int(requested), fit))
        if self.backend == "cpu":
            width = min(width, CPU_MAX_WIDTH)
        return width


def plan_fleet(base_cfg, cells: List[Dict[str, Any]], tenants: int,
               apply_overrides: Callable,
               capacity: Optional[CapacityModel] = None
               ) -> List[Tuple[str, List[Dict[str, Any]], int]]:
    """Deterministic bin-packing: [(kind, cells, width)] with kind one of
    ``bin`` (scheduler-resident, backfilled), ``fifo`` (cohort packs —
    fixed membership, the shared gather admits no clock skew) or
    ``serial``. Grouping is by `tenant_pack_key` exactly like the FIFO
    planner (service/tenancy.plan_packs); width is capacity-modelled per
    shape class. Same cells + same capacity model => same plan (the
    determinism pin in tests/test_scheduler.py)."""
    from defending_against_backdoors_with_robust_learning_rate_tpu.service.tenancy import (
        serial_reason)
    if capacity is None:
        capacity = CapacityModel()
    groups: Dict[str, List[Dict[str, Any]]] = {}
    order: List[str] = []
    items: List[Tuple[str, List[Dict[str, Any]], int]] = []
    cfg0: Dict[str, Any] = {}
    for cell in cells:
        try:
            cfg = apply_overrides(base_cfg, cell["overrides"])
            reason = serial_reason(cfg)
            key = None if reason else compile_cache.tenant_pack_key(cfg)
        except Exception as e:
            reason, key = f"{type(e).__name__}: {e}", None
        if key is None:
            print(f"[scheduler] cell {cell['name']!r} -> serial "
                  f"({reason})")
            items.append(("serial", [cell], 1))
            continue
        if key not in groups:
            groups[key] = []
            order.append(key)
            cfg0[key] = cfg
        groups[key].append(cell)
    for key in order:
        group = groups[key]
        if len(group) < 2:
            print(f"[scheduler] cell {group[0]['name']!r} -> serial "
                  f"(no shape-compatible partner in this queue)")
            items.append(("serial", group, 1))
            continue
        width = capacity.max_width(cfg0[key], tenants)
        if compile_cache.is_cohort_mode(cfg0[key]):
            # cohort packs: fixed membership (no backfill — the shared
            # bank gather serves ONE cohort_seed-driven draw), chunked
            # to the capacity-modelled width like the FIFO planner
            for i in range(0, len(group), width):
                chunk = group[i:i + width]
                items.append(("fifo" if len(chunk) >= 2 else "serial",
                              chunk, min(width, len(chunk))))
        else:
            items.append(("bin", group, width))
    pos = {id(c): i for i, c in enumerate(cells)}
    items.sort(key=lambda it: pos[id(it[1][0])])
    return items


class Scheduler:
    """The pure slot state machine (no jax, no clocks): W slots, a
    pending deque, ledger-shaped events in, deterministic decisions out.

    Events consumed (the live loop emits the same names on the queue
    ledger, so a synthetic `read_events` stream replays a run exactly):

    - ``scheduler/slot_done``   — slot's cell completed; vacate+fill
    - ``scheduler/evict``       — slot evicted (health enforcement)
    - ``health/incident``       — a quarantine-triggering incident on
      the slot's tenant; treated as an eviction trigger
    - ``service/recover``       — the slot's tenant entered recovery;
      its slot backfills from the queue instead of idling

    Decisions: ``{"op": "backfill", "slot": e, "item": cell}`` or
    ``{"op": "idle", "slot": e}``. Backfill order IS queue order — the
    deque pops left, nothing reorders."""

    VACATE_EVENTS = ("scheduler/slot_done", "scheduler/evict",
                     "health/incident", "service/recover")

    def __init__(self, width: int, resident: List[Any],
                 pending: List[Any]):
        if len(resident) > width:
            raise ValueError(f"{len(resident)} resident items in "
                             f"{width} slots")
        self.width = width
        self.slots: List[Any] = list(resident) + [None] * (
            width - len(resident))
        self.pending = collections.deque(pending)
        self.decisions: List[Dict[str, Any]] = []

    def occupancy(self) -> float:
        return (sum(1 for s in self.slots if s is not None)
                / max(self.width, 1))

    def on_event(self, event: Dict[str, Any]) -> List[Dict[str, Any]]:
        """Consume one ledger record; return the decisions it forces.
        Unknown events and events without a slot are no-ops (a live
        ledger interleaves queue/cell records the scheduler ignores)."""
        name = event.get("event")
        slot = event.get("slot")
        if name not in self.VACATE_EVENTS or slot is None:
            return []
        if not (0 <= int(slot) < self.width):
            return []
        return self._vacate(int(slot))

    def _vacate(self, slot: int) -> List[Dict[str, Any]]:
        if self.pending:
            item = self.pending.popleft()
            self.slots[slot] = item
            decision = {"op": "backfill", "slot": slot, "item": item}
        else:
            self.slots[slot] = None
            decision = {"op": "idle", "slot": slot}
        self.decisions.append(decision)
        return [decision]


def run_bin(base_cfg, bin_cells: List[Dict[str, Any]], width: int,
            qledger=None) -> Tuple[List[Dict[str, Any]], Dict[str, Any]]:
    """One shape-class bin through the resident loop: up to `width`
    cells live as PackEngine slots; the pack clock advances in
    snap-blocks until every cell has retired, with completed/evicted
    slots backfilled from the bin's queue at offset = -pack_round.

    Returns (one queue row per cell in COMPLETION order, bin stats for
    the fleet summary). Row schema matches the FIFO queue's pack rows
    (summary under SUMMARY_KEYS + a "tenancy" clause) plus a
    "scheduler" clause with the slot's admission/retirement rounds."""
    from defending_against_backdoors_with_robust_learning_rate_tpu.service.queue import (
        SUMMARY_KEYS, _cell_cfg, _new_row)
    from defending_against_backdoors_with_robust_learning_rate_tpu.service.tenancy import (
        PackEngine)
    from defending_against_backdoors_with_robust_learning_rate_tpu.train import (
        dispatch_schedule)

    def emit(name, severity="info", **fields):
        if qledger is not None:
            qledger.emit(name, severity=severity, **fields)

    W = min(width, len(bin_cells))
    resident, pending = bin_cells[:W], bin_cells[W:]
    sched = Scheduler(W, resident, pending)
    t0 = time.perf_counter()
    emit("scheduler/bin_start", width=W, cells=len(bin_cells))
    rows: List[Dict[str, Any]] = []
    engine = PackEngine(
        [_cell_cfg(base_cfg, c) for c in resident],
        names=[c["name"] for c in resident],
        offsets=[0] * W, evict_on_anomaly=True)
    # per-slot bookkeeping the engine doesn't carry: the queue row under
    # construction and the slot's admission round/wall
    meta = [{"cell": c, "row": _new_row(base_cfg, c),
             "admitted_round": 0, "t_admit": t0} for c in resident]
    for e, c in enumerate(resident):
        emit("scheduler/admit", slot=e, cell=c["name"], round=0)
    busy = total = 0
    rounds, snap = engine.rounds, engine.snap

    def finish_row(e: int, ok: bool, pack_rnd: int,
                   summary: Optional[Dict[str, Any]] = None,
                   error: Optional[str] = None) -> None:
        m = meta[e]
        row = m["row"]
        now = time.perf_counter()
        # amortized share, matching the FIFO pack's wall/E billing
        row["wall_s"] = round((now - m["t_admit"]) / max(W, 1), 3)
        row["ok"] = ok
        if summary is not None:
            row["summary"] = {k: summary[k] for k in SUMMARY_KEYS
                              if k in summary}
        if error is not None:
            row["error"] = error
        row["tenancy"] = {"slot": e, "tenants": W, "rounds": rounds,
                          "compile_s": round(engine.compile_s, 3)}
        row["scheduler"] = {"admitted_round": m["admitted_round"],
                            "retired_round": pack_rnd,
                            "offset": engine.slots[e].offset}
        rows.append(row)

    def backfill(e: int, event_name: str, pack_rnd: int,
                 severity: str = "info") -> None:
        """Vacate slot e through the scheduler and load whatever it
        decides; a cell whose load fails is recorded-and-skipped and the
        slot asks again."""
        emit(event_name, severity=severity, slot=e, round=pack_rnd)
        decisions = sched.on_event({"event": event_name, "slot": e})
        while decisions:
            d = decisions[0]
            if d["op"] == "idle":
                engine.idle_slot(e)
                emit("scheduler/idle", slot=e, round=pack_rnd)
                return
            cell = d["item"]
            try:
                engine.load_slot(e, _cell_cfg(base_cfg, cell),
                                 cell["name"], offset=-pack_rnd)
            except Exception as err:  # record-and-skip, slot re-asks
                meta[e] = {"cell": cell, "row": _new_row(base_cfg, cell),
                           "admitted_round": pack_rnd,
                           "t_admit": time.perf_counter()}
                finish_row(e, ok=False, pack_rnd=pack_rnd,
                           error=f"{type(err).__name__}: {err}")
                emit("scheduler/load_failed", severity="warn", slot=e,
                     cell=cell["name"],
                     error=f"{type(err).__name__}: {err}")
                decisions = sched.on_event(
                    {"event": "scheduler/evict", "slot": e})
                continue
            meta[e] = {"cell": cell, "row": _new_row(base_cfg, cell),
                       "admitted_round": pack_rnd,
                       "t_admit": time.perf_counter()}
            emit("scheduler/backfill", slot=e, cell=cell["name"],
                 round=pack_rnd, offset=-pack_rnd)
            return

    pack_rnd = 0
    loop_ok = False
    # hard ceiling: every cell runs `rounds` rounds; with backfill only
    # at snap boundaries the worst case is one snap-block of slack per
    # cell per slot — anything past that is a livelock, not progress
    max_blocks = (len(bin_cells) + W) * ((rounds + snap - 1) // snap + 1)
    try:
        for _ in range(max_blocks):
            if not engine.active_slots():
                break
            units = dispatch_schedule(pack_rnd, pack_rnd + snap, snap,
                                      engine.chain_n, False,
                                      engine.chained_fn is not None)
            info = None
            for unit in units:
                rnd, info = engine.dispatch_unit(unit)
                busy += len(engine.active_slots()) * len(unit)
                total += W * len(unit)
            pack_rnd += snap
            errors = engine.eval_boundary(
                pack_rnd, info, pack_rnd,
                max(time.perf_counter() - t0, 1e-9))
            for e, err in sorted(errors.items()):
                finish_row(e, ok=False, pack_rnd=pack_rnd,
                           error=f"{type(err).__name__}: {err}")
                engine.fail_slot(e, err)
                emit("health/incident", severity="warn", slot=e,
                     cell=meta[e]["cell"]["name"], round=pack_rnd,
                     error=f"{type(err).__name__}: {err}")
                backfill(e, "scheduler/evict", pack_rnd,
                         severity="warn")
            for e in list(engine.active_slots()):
                if pack_rnd + engine.slots[e].offset >= rounds:
                    summary = engine.finalize_slot(e)
                    summary["rounds_per_sec"] = rounds / max(
                        time.perf_counter() - meta[e]["t_admit"], 1e-9)
                    finish_row(e, ok=True, pack_rnd=pack_rnd,
                               summary=summary)
                    backfill(e, "scheduler/slot_done", pack_rnd)
        else:
            raise RuntimeError(
                f"scheduler bin made no progress in {max_blocks} "
                f"snap-blocks ({len(rows)}/{len(bin_cells)} cells "
                f"retired)")
        loop_ok = True
    finally:
        engine.close(loop_ok)
        if not loop_ok:
            # cells still resident when the bin dies get failure rows —
            # the record-and-skip contract, bin-shaped
            for e in engine.active_slots():
                finish_row(e, ok=False, pack_rnd=pack_rnd,
                           error="bin aborted (see queue log)")

    wall = time.perf_counter() - t0
    stats = {"wall_s": round(wall, 3), "width": W,
             "busy_slot_rounds": busy, "total_slot_rounds": total,
             "slot_occupancy": round(busy / max(total, 1), 4),
             "compile_s": round(engine.compile_s, 3),
             "pack_rounds": pack_rnd}
    emit("scheduler/bin_done", cells=len(rows),
         ok=sum(1 for r in rows if r.get("ok")), **stats)
    print(f"[scheduler] bin done: {len(rows)} cells over {W} slots, "
          f"{pack_rnd} pack rounds, occupancy "
          f"{stats['slot_occupancy']:.0%}, {wall:.1f}s")
    return rows, stats
