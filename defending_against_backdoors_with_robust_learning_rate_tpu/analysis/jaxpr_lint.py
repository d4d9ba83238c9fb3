"""Jaxpr/IR contract checker: lower each program family, assert contracts.

The perf contracts this repo's hot paths live by are invisible to tests
that only check VALUES: the shard_map round must issue exactly the
collectives parallel/rounds.py's communication plan documents ("sign
psums CSE with the RLR vote", "the only faults collective is one [m]-bit
validation all_gather"), nothing may promote to f64, no host-callback
primitive may ride a round program (it would stall the dispatch pipeline
and break AOT serialization), and ``--telemetry off`` must add NOTHING to
the traced program. This pass turns each claim into a machine check:

- **collective budgets** (jaxpr level): recursively count collective
  primitives (psum/all_gather/all_to_all/...) in the traced jaxpr of
  every checked family (contracts.check_specs()) — deterministic,
  compile-free, runs in milliseconds;
- **HLO collective ceilings** (``compiled=True``): count ``all-reduce``
  etc. in the post-optimization HLO, where CSE/combining has happened —
  the only level at which "the sign psums CSE with the RLR vote" is
  testable;
- **f64 / forbidden primitives**: no `convert_element_type` to float64
  anywhere, no callback/infeed primitives;
- **telemetry-off inertness**: trace the round families with
  `obs.telemetry.compute*` replaced by a tripwire — `--telemetry off`
  lowering provably contains zero Defense/* computation;
- **baseline**: exact per-family counts land in `analysis_baseline.json`
  so later PRs diff their budgets instead of discovering them.
"""

from __future__ import annotations

import json
import os
import re
from typing import Any, Dict, List, Tuple

from defending_against_backdoors_with_robust_learning_rate_tpu.analysis import (
    contracts)
from defending_against_backdoors_with_robust_learning_rate_tpu.analysis.ast_rules import (
    Finding)
from defending_against_backdoors_with_robust_learning_rate_tpu.utils.jaxprs import (
    iter_eqns)

BASELINE_NAME = "analysis_baseline.json"


class _rolled_scans:
    """Force lax.scan while tracing: ops/loops.maybe_unrolled_scan's
    XLA:CPU Python-loop escape hatch replicates the body per iteration
    (a 2-round chained block would double-count every collective), but
    the contract is about the per-round communication plan of the rolled
    program — the shape that runs on TPU."""

    def __enter__(self):
        self._prev = os.environ.get("RLR_SCAN_MODE")
        os.environ["RLR_SCAN_MODE"] = "scan"

    def __exit__(self, *exc):
        if self._prev is None:
            os.environ.pop("RLR_SCAN_MODE", None)
        else:
            os.environ["RLR_SCAN_MODE"] = self._prev
# the result type is one array type, or — where XLA's combiner merged
# several collectives into one op — a parenthesized tuple of them
_HLO_COLLECTIVE_RE = re.compile(
    r"=\s+(?:\(.*?\)|\S+)\s+(all-reduce|all-gather|all-to-all|"
    r"collective-permute|reduce-scatter)(?:-start)?\(")


# --------------------------------------------------------------------------
# jaxpr walking
# --------------------------------------------------------------------------

def count_primitives(closed) -> Dict[str, int]:
    counts: Dict[str, int] = {}
    for eqn in iter_eqns(closed):
        name = eqn.primitive.name
        counts[name] = counts.get(name, 0) + 1
    return counts


def collective_counts(closed) -> Dict[str, int]:
    counts = count_primitives(closed)
    return {p: counts.get(p, 0) for p in contracts.COLLECTIVE_PRIMITIVES}


def f64_sites(closed) -> List[str]:
    import numpy as np

    def is_f64(dt) -> bool:
        try:
            return np.dtype(dt) == np.float64
        except TypeError:
            return False   # extended dtypes (PRNG keys) are not f64

    sites: List[str] = []
    for eqn in iter_eqns(closed):
        if eqn.primitive.name == "convert_element_type":
            if is_f64(eqn.params.get("new_dtype")):
                sites.append("convert_element_type -> f64")
        for v in eqn.outvars:
            dt = getattr(getattr(v, "aval", None), "dtype", None)
            if dt is not None and is_f64(dt):
                sites.append(f"{eqn.primitive.name} produces f64")
    return sites


def forbidden_sites(closed) -> List[str]:
    counts = count_primitives(closed)
    return sorted(f"{name} x{n}" for name, n in counts.items()
                  if name in contracts.FORBIDDEN_PRIMITIVES)


def eqn_count(closed) -> int:
    return sum(1 for _ in iter_eqns(closed))


def hlo_collective_counts(hlo_text: str) -> Dict[str, int]:
    counts: Dict[str, int] = {}
    for m in _HLO_COLLECTIVE_RE.finditer(hlo_text):
        counts[m.group(1)] = counts.get(m.group(1), 0) + 1
    return counts


# --------------------------------------------------------------------------
# program building
# --------------------------------------------------------------------------

def _build_env(cfg):
    from defending_against_backdoors_with_robust_learning_rate_tpu.data.registry import (
        get_federated_data)
    from defending_against_backdoors_with_robust_learning_rate_tpu.fl.common import (
        make_normalizer)
    from defending_against_backdoors_with_robust_learning_rate_tpu.models.registry import (
        get_model)
    from defending_against_backdoors_with_robust_learning_rate_tpu.utils import (
        compile_cache)
    fed = get_federated_data(cfg)
    model = get_model(
        cfg.data, cfg.model_arch, cfg.dtype, remat=cfg.remat,
        remat_policy=compile_cache.resolved_remat(cfg, fed).policy)
    norm = make_normalizer(fed.mean, fed.std, fed.raw_is_normalized)
    return fed, model, norm


def _make_mesh_for(cfg, mesh_size: int = 0):
    import jax
    from defending_against_backdoors_with_robust_learning_rate_tpu.parallel.mesh import (
        make_mesh, pick_agent_mesh_size)
    if mesh_size:
        # explicit topology (the per-topology contract matrix): a 1-way
        # mesh is legitimate here — the collectives still trace
        if mesh_size > jax.device_count():
            raise RuntimeError(
                f"topology {mesh_size} needs {mesh_size} devices, have "
                f"{jax.device_count()} (XLA_FLAGS="
                f"--xla_force_host_platform_device_count={mesh_size})")
        return make_mesh(mesh_size)
    d = pick_agent_mesh_size(0, cfg.agents_per_round)
    if d <= 1:
        raise RuntimeError(
            f"sharded jaxpr contracts need >1 devices dividing "
            f"agents_per_round={cfg.agents_per_round}; have "
            f"{jax.device_count()} (run under "
            f"XLA_FLAGS=--xla_force_host_platform_device_count=8 on CPU)")
    return make_mesh(d)


def build_family(check: "contracts.CheckSpec", mesh_size: int = 0):
    """(jit_obj, example_args) for one CheckSpec — via the compile-cache
    planners so the analysis surface and the AOT surface cannot drift.
    `mesh_size` pins the sharded topology (contracts.TOPOLOGIES); 0 keeps
    the historical pick (all devices dividing m). The check config's
    population grows to the topology when m would not divide it (the
    budgets are participant-count-free)."""
    from defending_against_backdoors_with_robust_learning_rate_tpu.utils import (
        compile_cache)
    cfg = contracts.base_check_config().replace(**check.cfg_overrides)
    if check.sharded and mesh_size and \
            cfg.agents_per_round % mesh_size != 0:
        # agent_frac=1 -> m = d; the synthetic set must still deal
        # K x 10 class-shards (data/partition.py bound)
        cfg = cfg.replace(num_agents=mesh_size,
                          synth_train_size=max(cfg.synth_train_size,
                                               20 * mesh_size))
    fed, model, norm = _build_env(cfg)
    if check.sharded:
        mesh = _make_mesh_for(cfg, mesh_size)
        specs = compile_cache.plan_sharded_programs(
            cfg, model, norm, fed, mesh, host_mode=check.host_mode)
    else:
        specs = compile_cache.plan_programs(cfg, model, norm, fed)
    for spec in specs:
        if spec.family == check.family:
            return spec.jit_obj, spec.example_args
    raise RuntimeError(
        f"planner emitted no family {check.family!r} for check "
        f"{check.name!r} (got {[s.family for s in specs]})")


# --------------------------------------------------------------------------
# checks
# --------------------------------------------------------------------------

def check_family(check: "contracts.CheckSpec", compiled: bool = False,
                 mesh_size: int = 0
                 ) -> Tuple[List[Finding], Dict[str, Any]]:
    """Run one CheckSpec (optionally at an explicit sharded topology).
    Returns (findings, baseline_record)."""
    from defending_against_backdoors_with_robust_learning_rate_tpu.utils import (
        compile_cache)
    path = f"{contracts.PKG}/analysis/contracts.py"
    jit_obj, example_args = build_family(check, mesh_size=mesh_size)
    with _rolled_scans():
        closed = compile_cache.trace_program(jit_obj, example_args)
    findings: List[Finding] = []
    counts = collective_counts(closed)
    for prim, budget in check.collective_budget.items():
        if counts.get(prim, 0) > budget:
            findings.append(Finding(
                "collective-budget", path, 1,
                f"{check.name}/{check.family}: {counts[prim]} {prim} "
                f"eqns traced, budget {budget} — the communication plan "
                f"changed; justify and update the contract"))
    if check.forbid_f64:
        for site in f64_sites(closed):
            findings.append(Finding(
                "f64-promotion", path, 1,
                f"{check.name}/{check.family}: {site}"))
    if check.forbid_callbacks:
        for site in forbidden_sites(closed):
            findings.append(Finding(
                "forbidden-primitive", path, 1,
                f"{check.name}/{check.family}: {site} in the lowered "
                f"program"))
    record: Dict[str, Any] = {
        "family": check.family,
        "collectives": {k: v for k, v in counts.items() if v},
        "eqns": eqn_count(closed),
    }
    if compiled:
        with _rolled_scans():
            lowered = compile_cache.lower_program(jit_obj, example_args)
        record["stablehlo_bytes"] = len(lowered.as_text())
        hlo = lowered.compile().as_text()
        hcounts = hlo_collective_counts(hlo)
        record["hlo_collectives"] = hcounts
        if check.hlo_all_reduce_max is not None:
            got = hcounts.get("all-reduce", 0)
            if got > check.hlo_all_reduce_max:
                findings.append(Finding(
                    "collective-budget", path, 1,
                    f"{check.name}/{check.family}: {got} all-reduce ops "
                    f"in optimized HLO, ceiling "
                    f"{check.hlo_all_reduce_max} — CSE/combining "
                    f"regressed (e.g. the sign/RLR shared psum split)"))
    return findings, record


def telemetry_off_findings(sharded: bool = False) -> List[Finding]:
    """Trace the round families with EVERY obs.telemetry entry point
    replaced by a tripwire: --telemetry off lowering must not touch the
    telemetry module at all (the bit-identity contract, made
    structural)."""
    from defending_against_backdoors_with_robust_learning_rate_tpu.obs import (
        telemetry)
    from defending_against_backdoors_with_robust_learning_rate_tpu.utils import (
        compile_cache)
    path = f"{contracts.PKG}/obs/telemetry.py"
    specs = contracts.check_specs()
    names = (("sharded_rlr_avg", "sharded_rlr_avg_async") if sharded
             else ("vmap_rlr_avg", "vmap_rlr_avg_async"))

    def tripwire(*_a, **_k):
        raise AssertionError("telemetry computed under --telemetry off")

    hooks = ("compute", "compute_sharded")
    orig = {h: getattr(telemetry, h) for h in hooks}
    for h in hooks:
        setattr(telemetry, h, tripwire)
    findings: List[Finding] = []
    try:
        for name in names:
            check = specs[name]
            assert contracts.base_check_config().replace(
                **check.cfg_overrides).telemetry == "off"
            try:
                jit_obj, example_args = build_family(check)
                with _rolled_scans():
                    compile_cache.trace_program(jit_obj, example_args)
            except AssertionError as e:
                findings.append(Finding(
                    "telemetry-off-leak", path, 1,
                    f"{check.name}: {e} — the off level must add "
                    f"nothing to the traced program"))
    finally:
        for h, fn in orig.items():
            setattr(telemetry, h, fn)
    return findings


# --------------------------------------------------------------------------
# driver + baseline
# --------------------------------------------------------------------------

def run(sharded: bool = False, compiled: bool = False,
        topologies=None) -> Tuple[List[Finding], Dict[str, Any]]:
    """All jaxpr contracts (vmap always; shard_map families when
    `sharded`, each traced at every requested topology — default: every
    contracts.TOPOLOGIES entry the faked device count allows). The
    REFERENCE_TOPOLOGY keeps the historical unsuffixed baseline keys;
    other sizes record as `<name>@<d>w`. Returns (findings, baseline)."""
    import jax
    findings: List[Finding] = []
    families: Dict[str, Any] = {}
    if topologies is None:
        topologies = [d for d in contracts.TOPOLOGIES
                      if d <= jax.device_count()]
    else:
        # an EXPLICIT topology request must not silently shrink: a gate
        # invoked for the pod shape that quietly traces nothing would
        # report green with zero coverage at the requested width
        too_wide = [d for d in topologies if d > jax.device_count()]
        if too_wide:
            raise RuntimeError(
                f"requested topologies {too_wide} exceed the "
                f"{jax.device_count()} faked devices; run under "
                f"XLA_FLAGS=--xla_force_host_platform_device_count="
                f"{max(too_wide)}")
    for name, check in sorted(contracts.check_specs().items()):
        if check.sharded and not sharded:
            continue
        if not check.sharded:
            f, record = check_family(check, compiled=compiled)
            findings.extend(f)
            families[name] = record
            continue
        for d in topologies:
            f, record = check_family(check, compiled=compiled,
                                     mesh_size=d)
            findings.extend(f)
            record["topology"] = d
            key = (name if d == contracts.REFERENCE_TOPOLOGY
                   else f"{name}@{d}w")
            families[key] = record
    findings.extend(telemetry_off_findings(sharded=False))
    if sharded:
        findings.extend(telemetry_off_findings(sharded=True))
    baseline = {"jax": jax.__version__,
                "device_count": jax.device_count(),
                "families": families}
    return findings, baseline


def baseline_path(repo_root: str) -> str:
    return os.path.join(repo_root, BASELINE_NAME)


def write_baseline(repo_root: str, baseline: Dict[str, Any],
                   prune: bool = False) -> str:
    """Merge `baseline` into analysis_baseline.json. With `prune=True`
    (the `--write-baseline` CLI path), records whose spec x topology key
    no longer exists in contracts.check_specs() are dropped, so the
    committed file is exactly the live set — the coverage pass's
    dead-baseline rule then has nothing to flag."""
    path = baseline_path(repo_root)
    existing: Dict[str, Any] = {}
    if os.path.exists(path):
        with open(path, encoding="utf-8") as f:
            existing = json.load(f)
    merged = dict(existing)
    merged.update({k: v for k, v in baseline.items() if k != "families"})
    fams = dict(existing.get("families", {}))
    fams.update(baseline["families"])
    if prune:
        # imported lazily: coverage imports this module at top level
        from defending_against_backdoors_with_robust_learning_rate_tpu.analysis import (
            coverage)
        live = coverage.live_baseline_keys(repo_root)
        dead = sorted(set(fams) - live)
        for key in dead:
            del fams[key]
        if dead:
            import sys
            print(f"[analysis] baseline: pruned {len(dead)} dead "
                  f"record(s): {', '.join(dead)}", file=sys.stderr)
    merged["families"] = fams
    with open(path, "w", encoding="utf-8") as f:
        json.dump(merged, f, indent=1, sort_keys=True)
        f.write("\n")
    return path


def compare_baseline(repo_root: str, baseline: Dict[str, Any]
                     ) -> List[Finding]:
    """Exact-count drift detection against analysis_baseline.json. Only
    collective counts are asserted (eqn/StableHLO sizes drift with jax
    versions and are recorded for diffing, not gated)."""
    path = baseline_path(repo_root)
    if not os.path.exists(path):
        return []
    with open(path, encoding="utf-8") as f:
        pinned = json.load(f)
    if pinned.get("jax") != baseline.get("jax"):
        return []   # cross-version counts may legitimately differ
    findings: List[Finding] = []
    for name, record in baseline["families"].items():
        want = pinned.get("families", {}).get(name)
        if want is None:
            continue
        if record["collectives"] != want.get("collectives"):
            findings.append(Finding(
                "collective-drift", BASELINE_NAME, 1,
                f"{name}: collective counts {record['collectives']} != "
                f"baseline {want.get('collectives')} — review the "
                f"communication change, then refresh with "
                f"--write-baseline"))
    return findings
