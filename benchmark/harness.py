"""Runs one cell once: `config.args_parser` -> `train.RoundEngine` ->
`dispatch` / `eval_boundary` / `post_unit`, the loop `train.run` documents,
for a window of seconds; no side path into the round programs.

A run is: build the engine (span `engine_build`), C1, the warm-up (the
schedule's first unit, or as many as reach its first eval boundary), C2,
then the window, `drain_flush()`, C3, `close()`.
Everything before the window's first stamp is `setup_s`, less the seconds
`jax.devices()` took to bring the backend up. In the window at
most two units are in flight, and a unit is stamped complete when a small
reduction of the parameters it produced, enqueued right behind it, is ready
on the host. With `--trace 1` a few more units run under the profiler after
the window, and the per-layer readers turn that trace and the run's spans
into the per-layer metrics."""

from __future__ import annotations

import collections
import contextlib
import json
import math
import os
import shutil
import tempfile
import time
from typing import Any, Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import check, flops, registry, stats
from benchmark.trace import reduce as trace_reduce

ROUNDS = 100_000          # more than any window finishes
IN_FLIGHT = 2
MIN_UNITS = 3
EVAL_TAGS = ("Validation/Loss", "Validation/Accuracy", "Poison/Poison_Loss",
             "Poison/Poison_Accuracy")
ROW_TAGS = EVAL_TAGS + ("Train/Loss",)
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class MemoryWriter:
    """The writer the engine is handed: scalar rows kept in memory (the
    engine calls only `scalar`, `flush` and `close` on it)."""

    def __init__(self):
        self.rows: List[tuple] = []

    def scalar(self, tag: str, value, step: int) -> None:
        self.rows.append((tag, float(value), int(step)))

    def flush(self) -> None:
        pass

    def close(self) -> None:
        pass

    def at(self, step: int) -> Dict[str, float]:
        return {t: v for t, v, s in self.rows if s == step}

    def count(self, tag: str) -> int:
        return sum(1 for t, _v, _s in self.rows if t == tag)


class Spans:
    """The benchmark's own host spans around its calls into the engine: on
    the host clock here, and as `bench/<name>` annotations in the profiler's
    trace, where they share the device's clock."""

    def __init__(self):
        self.closed: List[tuple] = []   # (name, start, end, phase, cpu s)
        self.phase = "setup"

    @contextlib.contextmanager
    def span(self, name: str):
        t0, c0 = time.perf_counter(), time.thread_time()
        with jax.profiler.TraceAnnotation(trace_reduce.SPAN_PREFIX + name):
            try:
                yield
            finally:
                self.closed.append((name, t0, time.perf_counter(),
                                    self.phase, time.thread_time() - c0))

    def durations(self, name: str, phase: Optional[str] = None,
                  cpu: bool = False) -> List[float]:
        """Seconds of each closed span of that name (and phase): on the
        wall clock, or with `cpu` the calling thread's own CPU time, which
        leaves out what the span spent blocked."""
        return [(c if cpu else e - s) for n, s, e, p, c in self.closed
                if n == name and (phase is None or p == phase)]


class CompileCounter:
    """Counts programs acquired by the backend (compiled, or fetched from
    the persistent cache) from `jax.monitoring`."""

    def __init__(self):
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, _secs: float, **_kw) -> None:
        if event == COMPILE_EVENT:
            self.count += 1

    def close(self) -> None:
        jax.monitoring.unregister_event_duration_listener(self._on)


def device_record(chips: int) -> Dict[str, Any]:
    """The device as JAX reports it. The peak is on the fullest chip: what
    the allocator handed out plus what the runtime reserved for the
    programs' temporaries (the two are disjoint: PERF.md section 6)."""
    devices = jax.devices()
    peaks = []
    for d in devices[:max(chips, 1)]:
        ms = d.memory_stats() or {}
        peaks.append(int(ms.get("peak_bytes_in_use", 0))
                     + int(ms.get("peak_bytes_reserved", 0)))
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": max(peaks)}


class Driver:
    """The loop over units, with the stamps."""

    def __init__(self, eng, spans: Spans):
        self.eng, self.spans = eng, spans
        self.units = iter(eng.schedule())
        self.boundaries = 0

        @jax.jit
        def probe(params):
            # one element: are all parameters of this unit finite
            flags = [jnp.all(jnp.isfinite(x))
                     for x in jax.tree_util.tree_leaves(params)]
            return jnp.all(jnp.stack(flags))

        self._probe = probe

    def dispatch_next(self):
        """One unit through the engine; returns (rounds, probe handle)."""
        eng, cfg = self.eng, self.eng.cfg
        unit = next(self.units)
        with self.spans.span("dispatch"):
            eng.dispatch(unit)
            handle = self._probe(eng.model_params)
        if eng.rnd % cfg.snap == 0:
            with self.spans.span("eval_boundary"):
                eng.eval_boundary(eng.rnd)
            self.boundaries += 1
        with self.spans.span("post_unit"):
            eng.post_unit()
        return len(unit), handle

    def wait(self, handle) -> tuple:
        """(finite?, host time) once the unit behind `handle` is done."""
        with self.spans.span("wait"):
            ok = bool(handle)
        return ok, time.perf_counter()

    def run(self, seconds: Optional[float] = None,
            units: Optional[int] = None) -> Dict[str, Any]:
        """Dispatch for `seconds` (ending at a unit boundary inside them,
        but never under MIN_UNITS units) or for exactly `units` units, at
        most IN_FLIGHT ahead of the device. The device is idle when this
        starts, and the first stamp is taken then."""
        first = time.perf_counter()
        in_flight = collections.deque()
        rounds, oks, stamps = [], [], []

        def may_dispatch() -> bool:
            n = len(rounds)
            if units is not None:
                return n < units
            if n < MIN_UNITS:
                return True
            # the units in flight and one more have to end inside the window
            unit_s = (stats.median(stats.intervals([first] + stamps))
                      if stamps else 0.0)
            ahead = len(in_flight) + 1
            return time.perf_counter() - first + ahead * unit_s <= seconds

        while True:
            while len(in_flight) < IN_FLIGHT and may_dispatch():
                n, handle = self.dispatch_next()
                rounds.append(n)
                in_flight.append(handle)
            if not in_flight:
                break
            ok, t = self.wait(in_flight.popleft())
            oks.append(ok)
            stamps.append(t)
        return {"first": first, "stamps": stamps, "rounds": rounds,
                "ok": oks}


def replicas_equal(params) -> bool:
    """Every device holds the same bits of every parameter."""
    for leaf in jax.tree_util.tree_leaves(params):
        shards = [np.asarray(s.data) for s in leaf.addressable_shards]
        if any(not np.array_equal(shards[0], s, equal_nan=True)
               for s in shards[1:]):
            return False
    return True


def read_layer_metrics(cell: registry.Cell, ctx: Dict[str, Any],
                       say: Callable) -> Dict[str, Any]:
    """Each per-layer metric of the cell from its own reader; a reader that
    finds nothing to read returns None and the metric is left out."""
    out = {}
    for entry in cell.per_layer:
        reader = registry.load_module(cell.search_dirs, "layer_metrics",
                                      entry["name"])
        value = reader.read(ctx)
        if value is None:
            say(f"[bench] per-layer metric {entry['name']}: nothing to read")
            continue
        out[entry["name"]] = {"value": float(value), "unit": entry["unit"]}
    return out


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             platform: str = "tpu", bench_path: Optional[str] = None,
             t_start: Optional[float] = None, say: Callable = print) -> Dict[str, Any]:
    """One run of one cell; returns the result line as a dict. Raises where
    the platform is absent or holds fewer chips than the cell asks for."""
    t_start = time.perf_counter() if t_start is None else t_start
    bench = registry.load_benchmark(bench_path)
    cell = registry.resolve(bench, workload)

    marks = {"start": t_start, "jax_imported": time.perf_counter()}
    jax.config.update("jax_platforms", platform)
    devices = jax.devices()          # raises where the platform is absent
    marks["backend_up"] = time.perf_counter()
    if devices[0].platform != platform or len(devices) < cell.chips:
        raise RuntimeError(
            f"cell {workload} needs {cell.chips} {platform} chip(s); JAX "
            f"reports {len(devices)} x {devices[0].platform}")

    from defending_against_backdoors_with_robust_learning_rate_tpu import (
        train)
    from defending_against_backdoors_with_robust_learning_rate_tpu.config import (
        args_parser)

    marks["program_imported"] = time.perf_counter()
    ref_model = registry.load_module(cell.search_dirs, "reference",
                                     cell.config["reference"])
    tmp = tempfile.mkdtemp(prefix="bench_run_")
    spans, writer, eng, compiles = Spans(), MemoryWriter(), None, None
    try:
        cfg = args_parser(cell.flags + [
            f"--seed={seed}", f"--platform={platform}",
            f"--log_dir={os.path.join(tmp, 'logs')}",
            f"--data_dir={os.path.join(tmp, 'no_data')}",
            f"--rounds={ROUNDS}", "--no_tensorboard"])
        with spans.span("engine_build"):
            eng = train.RoundEngine(cfg, writer=writer)
        drv = Driver(eng, spans)
        n_params = sum(int(x.size) for x in
                       jax.tree_util.tree_leaves(eng.model_params))

        # ---- C1, then the warm-up unit and C2 on what it trained
        with spans.span("check_c1"):
            c1 = check.c1_server_step(cfg, eng.model_params, seed)
        say("[bench] C1 " + json.dumps(c1))
        with spans.span("warmup"):
            warm_ok = []
            while drv.boundaries == 0:     # up to the first eval boundary
                warm_ok += drv.run(units=1)["ok"]
            eng.drain_flush()
        warm_rnd = eng.rnd
        with spans.span("check_c2"):
            c2 = check.c2_model(
                {t: v for t, v in writer.at(warm_rnd).items()
                 if t in EVAL_TAGS},
                eng.model_params, eng.val, cell.config, ref_model.forward)
        say("[bench] C2 " + json.dumps(c2))

        # ---- the window: nothing may compile from here on
        compiles = CompileCounter()
        spans.phase = "window"
        win = drv.run(seconds=seconds)
        # the seconds libtpu took to bring the backend up are the machine's,
        # swing between 6 and 15 s from run to run and no PR can move them:
        # they are printed with the set-up's parts, not counted in it
        setup_s = (win["first"] - t_start
                   - (marks["backend_up"] - marks["jax_imported"]))
        spans.phase = "after"
        eng.drain_flush()
        compiled_in_window = compiles.count

        # ---- the traced part, after the timed part
        summary, traced = None, None
        if trace:
            own_dir = os.path.join(tmp, "trace")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            spans.phase = "traced"
            jax.profiler.start_trace(own_dir, profiler_options=opts)
            try:
                traced = drv.run(units=int(cell.traffic.get("trace_units", 3)))
            finally:
                jax.profiler.stop_trace()
            spans.phase = "after"
            eng.drain_flush()
            xplane_path = trace_reduce.find_xplane(own_dir)
            summary = (trace_reduce.summarize(xplane_path)
                       if xplane_path else None)
            if summary is None and platform == "tpu":
                raise RuntimeError("the traced part left no device "
                                   "operation in the trace")

        # ---- C3: the integrity of the window
        rows_finite = all(math.isfinite(v) for t, v, _s in writer.rows
                          if t in ROW_TAGS)
        evals_received = writer.count("Validation/Loss")
        params_finite = bool(drv._probe(eng.model_params))
        replicas_ok = (replicas_equal(eng.model_params)
                       if cell.chips > 1 else True)
        attempted, failed = stats.operation_counts(win["rounds"], win["ok"])
        c3 = {"rows_finite": rows_finite, "params_finite": params_finite,
              "compilations_in_window": compiled_in_window,
              "eval_rows": evals_received,
              "eval_boundaries": drv.boundaries,
              "replicas_equal": replicas_ok, "failed_rounds": failed}
        c3["ok"] = (rows_finite and params_finite and compiled_in_window == 0
                    and evals_received == drv.boundaries and replicas_ok
                    and failed == 0 and all(warm_ok))
        say("[bench] C3 " + json.dumps(c3))
        eng.close()
        eng = None

        # ---- the numbers
        unit_ms = [1e3 * d for d in
                   stats.intervals([win["first"]] + win["stamps"])]
        e2e = {
            "rounds_per_s": stats.rounds_per_s(win["first"], win["stamps"],
                                               win["rounds"]),
            "round_p90_ms": stats.percentile(unit_ms, 90.0),
            "setup_s": setup_s,
        }
        device = device_record(cell.chips)
        last = writer.at(max(s for _t, _v, s in writer.rows))
        say("[bench] window " + json.dumps({
            "units": len(win["stamps"]), "rounds": sum(win["rounds"]),
            "span_s": win["stamps"][-1] - win["first"],
            "unit_ms_median": stats.median(unit_ms),
            "unit_ms_p90": e2e["round_p90_ms"], "unit_ms_max": max(unit_ms),
            "setup_parts_s": {
                "import_jax": marks["jax_imported"] - marks["start"],
                "backend_up": marks["backend_up"] - marks["jax_imported"],
                "import_program": (marks["program_imported"]
                                   - marks["backend_up"]),
                **{n: sum(spans.durations(n, "setup"))
                   for n in ("engine_build", "check_c1", "warmup",
                             "check_c2")}},
            "last_eval": {t: v for t, v in last.items() if t in ROW_TAGS},
            "memory": {str(d.id): d.memory_stats()
                       for d in jax.devices()[:cell.chips]
                       if d.memory_stats()}}))
        result = {"correct": bool(c1["ok"] and c2["ok"] and c3["ok"]),
                  "attempted": attempted, "failed": failed}
        if not trace:
            result["metrics"] = {
                m["name"]: {"value": float(e2e[m["name"]]), "unit": m["unit"]}
                for m in bench["end_to_end"] if m["name"] in cell.end_to_end}
        else:
            traced_rounds = sum(traced["rounds"])
            ctx = {
                "cell": cell, "cfg": cfg, "spans": spans, "trace": summary,
                "traced_rounds": traced_rounds,
                "traced_units": len(traced["rounds"]),
                "traced_boundaries": sum(
                    1 for n, _s, _e, p, _c in spans.closed
                    if n == "eval_boundary" and p == "traced"),
                "rounds_per_s": e2e["rounds_per_s"], "device": device,
                "n_params": n_params, "chips": cell.chips,
                "forward_flops": ref_model.forward_flops(
                    tuple(cell.config["image_shape"]),
                    int(cell.config.get("n_classes", 10))),
                "flops": flops,
            }
            result["metrics"] = read_layer_metrics(cell, ctx, say)
            if summary is not None:
                device["busy_s"] = summary["busy_s"]
                device["window_s"] = summary["window_s"]
                result["breakdown"] = {
                    "device_ops": trace_reduce.top(summary["by_group_s"]),
                    "idle_gaps": trace_reduce.top(summary["idle_by_span_s"])}
                say("[bench] trace " + json.dumps({
                    k: summary[k] for k in (
                        "devices", "busy_s_per_device", "window_s_per_device",
                        "by_scope_s", "by_program_s", "collective_s",
                        "collective_exposed_s", "longest_gaps_s",
                        "host_spans")} | {"traced_rounds": traced_rounds}))
        result["device"] = device
        return result
    finally:
        if compiles is not None:
            compiles.close()
        if eng is not None:
            eng.close()
        shutil.rmtree(tmp, ignore_errors=True)
