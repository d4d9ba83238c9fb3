"""Device-time attribution from ``jax.profiler`` traces.

``Spans/*`` rows measure host wall-clock only: a `round/dispatch` span
says how long the host waited, never where the DEVICE spent the round —
compute, collective (all-reduce/all-gather), or idle gap. The op-level
truth has lived in an ad-hoc script (`scripts/trace_top_ops.py`) nobody
runs automatically. This module is the shared parser + capture layer that
turns profiler traces into judged numbers (FedJAX ships per-phase timing
as a core simulator feature, arXiv:2108.02117; Podracer makes device-
utilization accounting the primary scaling signal, arXiv:2104.06272):

- ``attribute(trace_dir)`` parses the gzipped Chrome-trace output of a
  `jax.profiler` capture into a per-program-family and per-named-scope
  split of device **compute vs collective vs gap** time, correlating XLA
  ops back to the ``jax.named_scope`` annotations the round fns plant
  (`sample_gather` / `local_train` / `aggregate_rlr` / `telemetry`).
  A trace with no device track (XLA:CPU runs ops on host threadpool
  lanes) degrades gracefully: ``device_present: false``, host side only.
- ``RoundProfiler`` is the driver's opt-in sampled capture window
  (``--profile_rounds N``): it opens ONE `jax.profiler` trace at the
  first steady dispatch unit (never the compile unit), closes it after N
  rounds, and polls ``device.memory_stats()`` per captured unit for the
  HBM live/peak watermarks.
- ``parse_top_ops`` is the op-level top-sinks report
  `scripts/trace_top_ops.py` now delegates to — one parser, two views.
- ``memory_watermarks()`` wraps ``device.memory_stats()`` (None on
  backends without allocator stats) into the ``hbm_live_bytes`` /
  ``hbm_peak_bytes`` fields the heartbeat and bench JSON carry.

The parse side is stdlib-only (gzip/json/re) so `obs/report.py` can run
on machines without jax; everything touching a backend imports jax
lazily inside the function.
"""

from __future__ import annotations

import collections
import glob
import gzip
import json
import os
import re
from typing import Any, Dict, List, Optional, Set, Tuple

# HLO op groups counted as collective (interconnect) time; everything
# else on a device op lane is compute. Matches the primitive families the
# jaxpr contracts budget (analysis/contracts.COLLECTIVE_PRIMITIVES).
COLLECTIVE_OP_GROUPS = frozenset({
    "all-reduce", "all-gather", "all-to-all", "collective-permute",
    "reduce-scatter", "collective-broadcast",
})

# the jax.named_scope annotations planted in fl/rounds.py and
# parallel/rounds.py (PR 3) — the correlation targets. Order is the
# report's display order; unmatched ops land in "unscoped".
KNOWN_SCOPES = ("sample_gather", "local_train", "aggregate_rlr",
                "telemetry")
UNSCOPED = "unscoped"

CAPTURE_META = "capture_meta.json"

GROUP_RE = re.compile(r"(\.(\d+|remat\d*|clone))+$")


def group_name(name: str) -> str:
    """fusion.123 -> fusion; convolution.4.remat -> convolution (group HLO
    instances of the same op kind, including remat/clone-suffixed copies)."""
    base = GROUP_RE.sub("", name)
    return base or name


def find_trace_file(trace_dir: str) -> Optional[str]:
    """Newest *.trace.json.gz under the dir (one per host per profiler
    run; multiple files mean multiple capture runs — parse the newest,
    merging across runs would mix programs)."""
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "**", "*.trace.json.gz"), recursive=True))
    if not paths:
        return None
    return max(paths, key=os.path.getmtime)


def load_trace_events(path: str) -> List[Dict[str, Any]]:
    with gzip.open(path, "rt") as f:
        return json.load(f).get("traceEvents", [])


def read_capture_meta(trace_dir: str) -> Dict[str, Any]:
    try:
        with open(os.path.join(trace_dir, CAPTURE_META)) as f:
            return json.load(f)
    except (OSError, ValueError):
        return {}


def write_capture_meta(trace_dir: str, meta: Dict[str, Any]) -> None:
    try:
        os.makedirs(trace_dir, exist_ok=True)
        with open(os.path.join(trace_dir, CAPTURE_META), "w") as f:
            json.dump(meta, f, indent=1)
    except OSError:
        pass  # observability must never take down the run


# --------------------------------------------------------------------------
# lane classification (shared by attribute() and parse_top_ops())
# --------------------------------------------------------------------------

def _trace_meta(events) -> Tuple[Dict, Dict]:
    """Chrome-trace metadata: pid -> process name, (pid, tid) -> thread
    name. Device lanes are the /device:TPU:* (or TPU:*) processes, host
    threads are everything else."""
    pnames: Dict[Any, str] = {}
    tnames: Dict[Tuple[Any, Any], str] = {}
    for e in events:
        if e.get("ph") != "M":
            continue
        if e.get("name") == "process_name":
            pnames[e["pid"]] = e.get("args", {}).get("name", "")
        elif e.get("name") == "thread_name":
            tnames[(e["pid"], e.get("tid"))] = \
                e.get("args", {}).get("name", "")
    return pnames, tnames


def _device_pids(pnames) -> Set:
    return {pid for pid, n in pnames.items()
            if "tpu" in n.lower() or "/device" in n.lower()}


def _op_lanes(dev_pids, tnames) -> Set:
    """A device process exports several stacked lanes (an 'XLA Modules'
    envelope spanning the whole executable above per-op 'XLA Ops' rows,
    and often a 'TensorFlow Ops' framework-attribution lane covering the
    SAME device time); summing across all of them double-counts. Prefer
    the exact 'XLA Ops' lane(s); fall back to the substring heuristic
    only when no lane carries that name."""
    xla_tids = {(p, t) for (p, t), n in tnames.items()
                if p in dev_pids and n.strip().lower() == "xla ops"}
    return xla_tids or {(p, t) for (p, t), n in tnames.items()
                        if p in dev_pids and "op" in n.lower()
                        and "module" not in n.lower()}


def _make_op_lane_filter(dev_pids, op_tids, tnames):
    def in_op_lane(e):
        if (e["pid"], e.get("tid")) in op_tids:
            return True
        # no op-level lane metadata: fall back to excluding known
        # envelope lanes by name
        if not op_tids:
            lane = tnames.get((e["pid"], e.get("tid")), "").lower()
            return "module" not in lane and "step" not in lane
        return False
    return in_op_lane


def scope_of(event: Dict[str, Any],
             known: Tuple[str, ...] = KNOWN_SCOPES) -> str:
    """Named-scope of a device op event. The profiler exports the HLO
    op_name metadata — which carries the jax.named_scope path, e.g.
    ``jit_step/local_train/fusion.1`` — in the event args (`long_name`
    on TPU 'XLA Ops' lanes, `tf_op` on framework lanes); scan every
    "/"-separated component against the planted scope names."""
    args = event.get("args", {}) or {}
    for field in ("long_name", "tf_op", "name"):
        path = args.get(field, "")
        if not path:
            continue
        for part in str(path).split("/"):
            # strip any trailing HLO instance suffix before matching
            if group_name(part) in known:
                return group_name(part)
    return UNSCOPED


# --------------------------------------------------------------------------
# attribution
# --------------------------------------------------------------------------

def attribute(trace_dir: str, rounds: Optional[int] = None,
              events: Optional[List[Dict[str, Any]]] = None
              ) -> Optional[Dict[str, Any]]:
    """Parse a profiler trace dir into the device-time attribution dict.

    Returns None when the dir holds no trace file at all. A trace with
    no device track (XLA:CPU) yields ``{"device_present": False, ...}``
    so callers/report can say "no device lanes" instead of crashing.
    `rounds` (or capture_meta.json's record) normalizes the per-round
    figures; without either, per-round fields are omitted. `events`
    skips the gunzip+json load when the caller already holds the newest
    trace file's events (full-shape XLA:CPU traces run to GBs)."""
    path = find_trace_file(trace_dir)
    if path is None:
        return None
    meta = read_capture_meta(trace_dir)
    if rounds is None:
        rounds = meta.get("rounds")
    if events is None:
        events = load_trace_events(path)
    pnames, tnames = _trace_meta(events)
    dev_pids = _device_pids(pnames)
    out: Dict[str, Any] = {
        "trace_file": path,
        "device_present": bool(dev_pids),
        "devices": sorted(pnames[p] for p in dev_pids),
        "rounds": rounds,
    }
    if meta.get("backend"):
        out["backend"] = meta["backend"]
    if not dev_pids:
        out["note"] = ("no device lanes in this trace (XLA:CPU runs ops "
                       "on host threadpool lanes; host spans in "
                       "trace.json are the attribution source there)")
        return out
    op_tids = _op_lanes(dev_pids, tnames)
    in_op_lane = _make_op_lane_filter(dev_pids, op_tids, tnames)

    busy = compute = collective = 0.0
    t_min, t_max = float("inf"), float("-inf")
    by_scope: Dict[str, float] = {}
    by_program: Dict[str, Dict[str, float]] = {}
    per_group: collections.Counter = collections.Counter()
    for e in events:
        if e.get("ph") != "X" or e.get("pid") not in dev_pids \
                or not in_op_lane(e):
            continue
        dur = float(e.get("dur", 0.0))  # microseconds
        ts = float(e.get("ts", 0.0))
        t_min = min(t_min, ts)
        t_max = max(t_max, ts + dur)
        name = e.get("name", "?")
        grp = group_name(name)
        per_group[grp] += dur
        busy += dur
        is_coll = grp in COLLECTIVE_OP_GROUPS
        if is_coll:
            collective += dur
        else:
            compute += dur
        scope = scope_of(e)
        by_scope[scope] = by_scope.get(scope, 0.0) + dur
        module = (e.get("args", {}) or {}).get("hlo_module", "?")
        prog = by_program.setdefault(
            module, {"compute_us": 0.0, "collective_us": 0.0})
        prog["collective_us" if is_coll else "compute_us"] += dur

    if busy == 0.0:
        out["device_present"] = False
        out["note"] = ("device lanes exist but no duration events "
                       "matched the op-level filter; lanes: "
                       f"{sorted(set(tnames.values()))}")
        return out
    window = t_max - t_min
    gap = max(window - busy, 0.0)
    out.update({
        "window_ms": round(window / 1e3, 3),
        "busy_ms": round(busy / 1e3, 3),
        "compute_ms": round(compute / 1e3, 3),
        "collective_ms": round(collective / 1e3, 3),
        "gap_ms": round(gap / 1e3, 3),
        "collective_frac": round(collective / busy, 4),
        "by_scope_ms": {k: round(v / 1e3, 3)
                        for k, v in sorted(by_scope.items())},
        "by_program": {
            mod: {
                "compute_ms": round(v["compute_us"] / 1e3, 3),
                "collective_ms": round(v["collective_us"] / 1e3, 3),
                "collective_frac": round(
                    v["collective_us"]
                    / max(v["compute_us"] + v["collective_us"], 1e-9), 4),
            } for mod, v in sorted(by_program.items())},
        "top_groups": [
            {"op": name, "ms": round(dur / 1e3, 1),
             "pct": round(100 * dur / busy, 1)}
            for name, dur in per_group.most_common(12)],
    })
    if rounds:
        out["per_round"] = {
            "busy_ms": round(busy / 1e3 / rounds, 3),
            "compute_ms": round(compute / 1e3 / rounds, 3),
            "collective_ms": round(collective / 1e3 / rounds, 3),
            "gap_ms": round(gap / 1e3 / rounds, 3),
        }
    return out


def scalar_rows(attr: Dict[str, Any]) -> List[Tuple[str, float]]:
    """Flat (tag, value) rows for metrics.jsonl: Device/*."""
    if not attr or not attr.get("device_present"):
        return []
    rows: List[Tuple[str, float]] = [
        ("Device/Collective_Frac", float(attr["collective_frac"]))]
    per_round = attr.get("per_round")
    if per_round:
        for key in ("busy_ms", "compute_ms", "collective_ms", "gap_ms"):
            tag = "Device/" + key.split("_")[0].capitalize() \
                + "_Ms_Per_Round"
            rows.append((tag, float(per_round[key])))
        rounds = attr.get("rounds") or 1
        for scope, ms in attr.get("by_scope_ms", {}).items():
            rows.append((f"Device/Scope/{scope}_Ms_Per_Round",
                         round(ms / rounds, 3)))
    return rows


# --------------------------------------------------------------------------
# op-level top-sinks view (scripts/trace_top_ops.py delegates here)
# --------------------------------------------------------------------------

def parse_top_ops(trace_dir: str, top: int, rounds: int,
                  events: Optional[List[Dict[str, Any]]] = None):
    """Print + return the op-level top time sinks of a trace dir — the
    historical `scripts/trace_top_ops.py` report, now a view over the
    shared lane classification above. `events` skips the load as in
    ``attribute`` (must be the newest trace file's events)."""
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "**", "*.trace.json.gz"), recursive=True))
    if not paths:
        raise SystemExit(f"no *.trace.json.gz under {trace_dir}")
    meta = read_capture_meta(trace_dir)
    if "rounds" in meta:
        rounds = meta["rounds"]
    else:
        print(f"[trace] no capture_meta.json — assuming --rounds={rounds} "
              f"for the ms/round figure")
    chosen = max(paths, key=os.path.getmtime)
    if len(paths) > 1:
        print(f"[trace] {len(paths)} trace files under {trace_dir}; "
              f"parsing the newest: {chosen}")
    if events is None:
        events = load_trace_events(chosen)
    pnames, tnames = _trace_meta(events)
    dev_pids = _device_pids(pnames)
    if not dev_pids:
        print("[trace] NO device lanes in this trace (profiler saw only "
              "host threads). "
              f"Processes seen: {sorted(set(pnames.values()))}")
        return None
    op_tids = _op_lanes(dev_pids, tnames)
    in_op_lane = _make_op_lane_filter(dev_pids, op_tids, tnames)

    per_op: collections.Counter = collections.Counter()
    per_group: collections.Counter = collections.Counter()
    total = 0.0
    for e in events:
        if e.get("ph") != "X" or e.get("pid") not in dev_pids \
                or not in_op_lane(e):
            continue
        dur = float(e.get("dur", 0.0))  # microseconds
        name = e.get("name", "?")
        per_op[name] += dur
        per_group[group_name(name)] += dur
        total += dur
    if total == 0.0:
        print("[trace] device lanes exist but no duration events matched "
              f"the op-level filter; lanes: "
              f"{sorted(set(tnames.values()))}")
        return None
    lanes = (sorted(tnames[t] for t in op_tids)
             or "(fallback: all non-module lanes)")
    print(f"[trace] device processes: "
          f"{sorted(pnames[p] for p in dev_pids)}; op lanes: {lanes}")
    print(f"[trace] total device-op time in window: {total/1e3:.1f} ms "
          f"({rounds} rounds -> {total/1e3/max(rounds,1):.1f} ms/round)")
    print(f"\ntop {top} op groups (device time, % of captured op time):")
    rows = []
    for name, dur in per_group.most_common(top):
        print(f"  {name:<44s} {dur/1e3:8.1f} ms  {100*dur/total:5.1f}%")
        rows.append({"op": name, "ms": round(dur / 1e3, 1),
                     "pct": round(100 * dur / total, 1)})
    print(f"\ntop {top} individual ops:")
    for name, dur in per_op.most_common(top):
        print(f"  {name:<44s} {dur/1e3:8.1f} ms  {100*dur/total:5.1f}%")
    return {"total_ms": round(total / 1e3, 1), "rounds": rounds,
            "top_groups": rows}


# --------------------------------------------------------------------------
# memory watermarks
# --------------------------------------------------------------------------

# metrics.jsonl tag per heartbeat memory field
MEMORY_TAGS = {
    "hbm_live_bytes": "Memory/HBM_Live_Bytes",
    "hbm_peak_bytes": "Memory/HBM_Peak_Bytes",
    "host_peak_rss_bytes": "Memory/Host_Peak_RSS_Bytes",
}


def host_watermarks() -> Dict[str, int]:
    """Peak host RSS of this process (stdlib getrusage; ru_maxrss is KiB
    on Linux, bytes on macOS) — the population-axis memory judge: the
    constant-memory claim (ISSUE 7) pins this flat across a
    10k -> 100k -> 1M client ladder. Kept separate from
    ``memory_watermarks`` (device allocator stats) so backends without
    memory_stats still report host pressure."""
    try:
        import resource
        import sys
        rss = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
        if sys.platform != "darwin":
            rss *= 1024
        return {"host_peak_rss_bytes": int(rss)}
    except Exception:
        return {}


def memory_rows(mem: Dict[str, int]) -> List[Tuple[str, float]]:
    """Flat (tag, value) rows for metrics.jsonl: Memory/*."""
    return [(MEMORY_TAGS.get(k, f"Memory/{k}"), float(v))
            for k, v in sorted(mem.items())]


def memory_watermarks(device=None) -> Dict[str, int]:
    """HBM live/peak bytes from ``device.memory_stats()``, or {} when the
    backend exposes none (XLA:CPU returns None). Keys match the heartbeat
    fields the session stall detectors read (``hbm_live_bytes`` /
    ``hbm_peak_bytes``)."""
    try:
        if device is None:
            import jax
            device = jax.devices()[0]
        stats = device.memory_stats()
    except Exception:
        return {}
    if not stats:
        return {}
    out: Dict[str, int] = {}
    if "bytes_in_use" in stats:
        out["hbm_live_bytes"] = int(stats["bytes_in_use"])
    if "peak_bytes_in_use" in stats:
        out["hbm_peak_bytes"] = int(stats["peak_bytes_in_use"])
    return out


# --------------------------------------------------------------------------
# sampled capture window (--profile_rounds)
# --------------------------------------------------------------------------

class RoundProfiler:
    """Driver-side sampled profiler window: capture N steady rounds.

    The window opens at the start of the first dispatch unit AFTER the
    compile unit (``maybe_start`` is a no-op until the caller says warmup
    is done) and closes once >= N rounds have been dispatched — blocking
    on the last unit's params first, so the device events of every
    captured round are actually in the trace. Each captured unit also
    polls the HBM watermarks. ``--profile_rounds 0`` (the default) never
    constructs a window: the run is bit-identical to a build without
    this class."""

    def __init__(self, n_rounds: int, trace_dir: str):
        self.n = int(n_rounds)
        self.dir = trace_dir
        self.active = False
        self.done = self.n <= 0
        self.captured = 0
        self.mem: Dict[str, int] = {}

    @property
    def enabled(self) -> bool:
        return self.n > 0

    def maybe_start(self) -> None:
        """Open the capture window (idempotent; call at the start of each
        steady dispatch unit)."""
        if self.done or self.active:
            return
        import jax
        os.makedirs(self.dir, exist_ok=True)
        jax.profiler.start_trace(self.dir)
        self.active = True
        print(f"[profile] capture window open -> {self.dir} "
              f"({self.n} rounds)")

    def after_unit(self, params, rounds_in_unit: int) -> None:
        """Account a dispatched unit; close the window when the budget is
        reached. `params` is the unit's output — blocked on before
        stop_trace so the captured rounds' device work is in the file."""
        if not self.active:
            return
        self.captured += int(rounds_in_unit)
        for key, val in memory_watermarks().items():
            self.mem[key] = max(self.mem.get(key, 0), val)
        if self.captured >= self.n:
            self._stop(params)

    def close(self, params=None) -> None:
        """Teardown for runs that end before the budget is reached.
        Swallows teardown errors: this runs on the driver's exception
        path too, and observability must never mask the real failure."""
        if self.active:
            try:
                self._stop(params)
            except Exception as e:
                print(f"[profile] capture teardown failed: "
                      f"{type(e).__name__}: {e}")
                self.active = False
                self.done = True

    def _stop(self, params) -> None:
        import jax
        if params is not None:
            jax.block_until_ready(params)
        jax.profiler.stop_trace()
        self.active = False
        self.done = True
        write_capture_meta(self.dir, {
            "rounds": self.captured,
            "backend": jax.default_backend(),
            "source": "train --profile_rounds",
        })
        print(f"[profile] captured {self.captured} steady rounds -> "
              f"{self.dir}")

    def result(self) -> Optional[Dict[str, Any]]:
        """Attribution of the captured window (None when nothing was
        captured)."""
        if self.captured == 0:
            return None
        return attribute(self.dir, rounds=self.captured)
