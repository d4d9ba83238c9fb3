"""Program-family coverage fixpoint: the lattice the planners can emit
vs. the contracts that pin it.

The family lattice (sync/buffered x dense/cohort/host x tenant, each
with vmap and shard_map twins) long ago outgrew the hand-enumerated
CheckSpec matrix — a new `family_suffix` branch or a new
planner surface can silently ship with no collective-budget pin, and a
deleted spec leaves its baseline records rotting in
`analysis_baseline.json`. This pass closes the loop structurally:

- the suffix tokens are read from `compile_cache.family_suffix`'s OWN
  AST (never a duplicated list); `contracts.SUFFIX_DRIVERS` maps each
  token to the config overrides that activate it, and a token without a
  driver fails the gate (`suffix-unmapped`) — adding an algebra branch
  forces this pass to learn how to reach it;
- the reachable set is enumerated SEMANTICALLY: every token subset,
  crossed with the planner surfaces (dense / cohort-sampled /
  host-sampled, plain and `--diagnostics`), is pushed through the real
  `plan_programs` / `plan_sharded_programs` (memoized — the lattice
  walk never builds the same plan twice, and never traces anything);
- every reachable family must then carry a CheckSpec (with
  `analysis_baseline.json` records at every `contracts.TOPOLOGIES`
  entry for the sharded ones) or a `contracts.WAIVED_FAMILIES` entry
  whose reason says why no pin is needed (`missing-pin`,
  `topology-gap`);
- dead weight is flagged from the other side: specs for unreachable
  families (`dead-spec`), baseline records no live spec produces
  (`dead-baseline`, pruned by `--write-baseline`), stale waivers
  (`stale-waiver`), and `DONATED_FAMILIES` drifting from the reachable
  chained set (`donated-drift`);
- the run_name collision rule (the PR-3/11/13 bug class) becomes
  static: every `program`-tagged `FIELD_PROVENANCE` field must
  influence `utils/metrics.run_name` (computed by a transitive AST walk
  through the helpers run_name calls with the config) or carry a
  `contracts.RUN_NAME_EXEMPT` reason (`run-name-blind`,
  `stale-run-name-exemption`).

Like `fingerprint_audit.audit`, every input of `audit()` is a keyword
override so tests can plant synthetic lattices without editing real
modules.
"""

from __future__ import annotations

import ast
import itertools
import json
import os
from typing import Any, Dict, List, Optional, Sequence, Set, Tuple

from defending_against_backdoors_with_robust_learning_rate_tpu.analysis import (
    contracts)
from defending_against_backdoors_with_robust_learning_rate_tpu.analysis.ast_rules import (
    Finding)

_CONTRACTS_REL = f"{contracts.PKG}/analysis/contracts.py"
_CC_REL = f"{contracts.PKG}/utils/compile_cache.py"
_METRICS_REL = f"{contracts.PKG}/utils/metrics.py"
_BASELINE_REL = "analysis_baseline.json"

# the chained families only exist when the chain budget exceeds 1; the
# enumeration pins the same tiny chain the sharded_chained spec uses
_CHAIN_OVERRIDES = {"chain": 2, "snap": 2}


# --------------------------------------------------------------------------
# suffix algebra (from family_suffix's own AST)
# --------------------------------------------------------------------------

def suffix_tokens(repo_root: str) -> List[str]:
    """The suffix tokens `compile_cache.family_suffix` can emit, in
    emission order, read from its source — the single source of the
    family algebra. Any string constant assigned or `+=`-appended to the
    suffix accumulator counts; a refactor renaming the accumulator
    breaks this loudly (empty token list -> every driver goes stale)."""
    path = os.path.join(repo_root, _CC_REL)
    with open(path, encoding="utf-8") as f:
        tree = ast.parse(f.read(), filename=path)
    func = None
    for node in ast.walk(tree):
        if isinstance(node, ast.FunctionDef) and \
                node.name == "family_suffix":
            func = node
            break
    if func is None:
        raise RuntimeError(
            f"compile_cache.family_suffix not found in {path} — the "
            f"coverage pass derives the family algebra from it")
    tokens: List[Tuple[int, str]] = []

    def strings_of(expr: ast.AST) -> List[str]:
        return [n.value for n in ast.walk(expr)
                if isinstance(n, ast.Constant) and isinstance(n.value, str)
                and n.value]

    target_names: Set[str] = set()
    for node in ast.walk(func):
        if isinstance(node, (ast.Assign, ast.AugAssign)):
            targets = (node.targets if isinstance(node, ast.Assign)
                       else [node.target])
            for t in targets:
                if isinstance(t, ast.Name):
                    target_names.add(t.id)
                    for s in strings_of(node.value):
                        tokens.append((node.lineno, s))
    # the accumulator is whatever name the return statement yields; only
    # its assignments count (guards against unrelated locals)
    ret_names = {n.id for node in ast.walk(func)
                 if isinstance(node, ast.Return) and node.value is not None
                 for n in ast.walk(node.value) if isinstance(n, ast.Name)}
    if not ret_names & target_names:
        raise RuntimeError(
            "family_suffix no longer returns its string accumulator — "
            "update analysis/coverage.py's algebra reader")
    seen: Set[str] = set()
    ordered: List[str] = []
    for _, tok in sorted(tokens):
        if tok not in seen:
            seen.add(tok)
            ordered.append(tok)
    return ordered


# --------------------------------------------------------------------------
# reachable-family enumeration (memoized planner walk — no tracing)
# --------------------------------------------------------------------------

_PLAN_MEMO: Dict[Tuple, Tuple[str, ...]] = {}
_ENV_MEMO: Dict[Tuple, Tuple] = {}
_MESH_CACHE: List[Any] = []

# env construction only reads the data/model axes; every lattice point
# shares them, so the (slow) synthetic build happens once
_ENV_FIELDS = ("data", "num_agents", "agent_frac", "synth_train_size",
               "synth_val_size", "bs", "eval_bs", "model_arch", "dtype",
               "remat", "remat_policy")


def _env_for(cfg):
    key = tuple(getattr(cfg, f) for f in _ENV_FIELDS)
    if key not in _ENV_MEMO:
        from defending_against_backdoors_with_robust_learning_rate_tpu.analysis import (
            jaxpr_lint)
        _ENV_MEMO[key] = jaxpr_lint._build_env(cfg)
    return _ENV_MEMO[key]


def _mesh():
    """A 1-way mesh: family NAMES are mesh-size-independent (the per-
    topology tracing lives in jaxpr_lint), so the cheapest mesh that
    satisfies the planner signature is the right one here."""
    if not _MESH_CACHE:
        from defending_against_backdoors_with_robust_learning_rate_tpu.parallel.mesh import (
            make_mesh)
        _MESH_CACHE.append(make_mesh(1))
    return _MESH_CACHE[0]


def plan_families(overrides: Dict[str, object], sharded: bool,
                  host_mode: Optional[bool] = None) -> Tuple[str, ...]:
    """Family names one planner call emits for `base_check_config +
    overrides` — memoized on (overrides, sharded, host_mode) so the
    lattice walk never re-plans a point (and NEVER traces: planning
    builds jit objects lazily). Raises whatever the planner raises for
    an invalid combination; callers record those as unplannable."""
    key = (tuple(sorted(overrides.items())), sharded, bool(host_mode))
    if key in _PLAN_MEMO:
        return _PLAN_MEMO[key]
    from defending_against_backdoors_with_robust_learning_rate_tpu.utils import (
        compile_cache)
    cfg = contracts.base_check_config().replace(**overrides)
    fed, model, norm = _env_for(cfg)
    if sharded:
        specs = compile_cache.plan_sharded_programs(
            cfg, model, norm, fed, _mesh(), host_mode=bool(host_mode))
    else:
        specs = compile_cache.plan_programs(cfg, model, norm, fed,
                                            host_mode=host_mode)
    _PLAN_MEMO[key] = tuple(s.family for s in specs)
    return _PLAN_MEMO[key]


def reachable_families(repo_root: str,
                       tokens: Optional[Sequence[str]] = None,
                       drivers: Optional[Dict[str, Dict[str, object]]] = None,
                       ) -> Tuple[Dict[str, List[str]], List[str]]:
    """Enumerate the reachable lattice: every driver-mapped token subset
    x {dense, cohort, host} x {plain, diagnostics} x {vmap, sharded},
    through the real planners. Returns (family -> sorted witness combo
    labels, unplannable-combo log). Unmapped tokens are skipped here —
    `audit` reports them as findings."""
    if tokens is None:
        tokens = suffix_tokens(repo_root)
    if drivers is None:
        drivers = contracts.SUFFIX_DRIVERS
    mapped = [t for t in tokens if t in drivers]
    reach: Dict[str, Set[str]] = {}
    skips: List[str] = []
    for r in range(len(mapped) + 1):
        for combo in itertools.combinations(mapped, r):
            ov: Dict[str, object] = dict(_CHAIN_OVERRIDES)
            for tok in combo:
                ov.update(drivers[tok])
            for diag in (False, True):
                dov = {**ov, "diagnostics": diag} if diag else ov
                surfaces = [
                    ("dense", dov, None),
                    ("cohort", {**dov, "cohort_sampled": "on"}, None),
                    ("host", dov, True),
                ]
                for surf, sov, host in surfaces:
                    label = (f"{surf}{''.join(combo)}"
                             + ("+diag" if diag else ""))
                    for sharded in (False, True):
                        try:
                            fams = plan_families(sov, sharded,
                                                 host_mode=host)
                        except Exception as e:   # noqa: BLE001 — an
                            # unplannable lattice point is data, not a
                            # crash; the skip log keeps it visible
                            skips.append(
                                f"{label}{'/sharded' if sharded else ''}:"
                                f" {type(e).__name__}: {e}")
                            continue
                        for fam in fams:
                            reach.setdefault(fam, set()).add(label)
    return ({fam: sorted(wit) for fam, wit in sorted(reach.items())},
            skips)


# --------------------------------------------------------------------------
# run_name influence (transitive AST walk)
# --------------------------------------------------------------------------

def _parse_rel(repo_root: str, relpath: str) -> Optional[ast.Module]:
    path = os.path.join(repo_root, relpath)
    if not os.path.exists(path):
        return None
    with open(path, encoding="utf-8") as f:
        return ast.parse(f.read(), filename=path)


def _dotted_to_rel(dotted: str) -> Optional[str]:
    if not dotted.startswith(contracts.PKG):
        return None
    return dotted.replace(".", "/") + ".py"


def _imports_map(tree: ast.Module) -> Dict[str, str]:
    """local name -> package-dotted module it refers to (ImportFrom of
    modules only — `from pkg.utils import compile_cache` binds
    `compile_cache`; function-level imports included, which is how
    run_name imports its helpers)."""
    out: Dict[str, str] = {}
    for node in ast.walk(tree):
        if isinstance(node, ast.ImportFrom) and node.module \
                and node.module.startswith(contracts.PKG):
            for alias in node.names:
                out[alias.asname or alias.name] = \
                    f"{node.module}.{alias.name}"
        elif isinstance(node, ast.Import):
            for alias in node.names:
                if alias.name.startswith(contracts.PKG):
                    out[alias.asname or alias.name.split(".")[0]] = \
                        alias.name
    return out


def run_name_fields(repo_root: str) -> Set[str]:
    """Config fields that influence `utils/metrics.run_name`, by
    transitive closure: direct `cfg.<attr>` reads in run_name, plus the
    reads of every package function run_name (transitively) passes the
    config to, with `@property` names expanded to the concrete fields
    they read (fingerprint_audit.property_field_map)."""
    from defending_against_backdoors_with_robust_learning_rate_tpu.analysis import (
        fingerprint_audit)
    config_path = os.path.join(repo_root, contracts.PKG, "config.py")
    props = fingerprint_audit.property_field_map(config_path)
    fields = fingerprint_audit.config_fields()

    # (relpath, funcname) worklist; each entry analyzed once
    seen: Set[Tuple[str, str]] = set()
    work: List[Tuple[str, str]] = [(_METRICS_REL, "run_name")]
    attrs: Set[str] = set()

    while work:
        relpath, funcname = work.pop()
        if (relpath, funcname) in seen:
            continue
        seen.add((relpath, funcname))
        tree = _parse_rel(repo_root, relpath)
        if tree is None:
            continue
        func = next((n for n in ast.walk(tree)
                     if isinstance(n, ast.FunctionDef)
                     and n.name == funcname), None)
        if func is None:
            continue
        imports = _imports_map(tree)
        # the cfg-bearing names inside this function: its first
        # positional param (every helper in this chain takes cfg
        # leading) plus the conventional names
        cfg_names = {"cfg", "config"}
        if func.args.args:
            cfg_names.add(func.args.args[0].arg)
        for node in ast.walk(func):
            if isinstance(node, ast.Attribute) \
                    and isinstance(node.value, ast.Name) \
                    and node.value.id in cfg_names:
                attrs.add(node.attr)
            elif isinstance(node, ast.Call) \
                    and isinstance(node.func, ast.Name) \
                    and node.func.id == "getattr" \
                    and len(node.args) >= 2 \
                    and isinstance(node.args[0], ast.Name) \
                    and node.args[0].id in cfg_names \
                    and isinstance(node.args[1], ast.Constant):
                # getattr(cfg, "field", default) — the is_buffered idiom
                attrs.add(node.args[1].value)
            elif isinstance(node, ast.Call):
                passes_cfg = any(
                    isinstance(a, ast.Name) and a.id in cfg_names
                    for a in node.args)
                if not passes_cfg:
                    continue
                # resolve the callee to a package module function
                if isinstance(node.func, ast.Name):
                    dotted = imports.get(node.func.id)
                    if dotted:
                        mod, _, fn = dotted.rpartition(".")
                        rel = _dotted_to_rel(mod)
                        if rel:
                            work.append((rel, fn))
                    else:
                        work.append((relpath, node.func.id))
                elif isinstance(node.func, ast.Attribute) \
                        and isinstance(node.func.value, ast.Name):
                    dotted = imports.get(node.func.value.id)
                    rel = _dotted_to_rel(dotted) if dotted else None
                    if rel:
                        work.append((rel, node.func.attr))

    out: Set[str] = set()
    for attr in attrs:
        for field in (props.get(attr, {attr}) if attr in props
                      else {attr}):
            if field in fields:
                out.add(field)
    return out


# --------------------------------------------------------------------------
# audit
# --------------------------------------------------------------------------

def _expected_baseline_keys(specs: Dict[str, "contracts.CheckSpec"],
                            topologies: Sequence[int]) -> Set[str]:
    """The exact `analysis_baseline.json` family-key set a full
    `--sharded` run at every topology produces — jaxpr_lint.run's
    naming: unsuffixed at REFERENCE_TOPOLOGY, `<name>@<d>w` elsewhere;
    non-sharded specs record once, unsuffixed."""
    keys: Set[str] = set()
    for name, check in specs.items():
        if not check.sharded:
            keys.add(name)
            continue
        for d in topologies:
            keys.add(name if d == contracts.REFERENCE_TOPOLOGY
                     else f"{name}@{d}w")
    return keys


def load_baseline(repo_root: str) -> Dict[str, Any]:
    path = os.path.join(repo_root, _BASELINE_REL)
    if not os.path.exists(path):
        return {}
    with open(path, encoding="utf-8") as f:
        return json.load(f)


def audit(repo_root: str,
          tokens: Optional[Sequence[str]] = None,
          drivers: Optional[Dict[str, Dict[str, object]]] = None,
          reachable: Optional[Dict[str, List[str]]] = None,
          specs: Optional[Dict[str, "contracts.CheckSpec"]] = None,
          baseline: Optional[Dict[str, Any]] = None,
          donated: Optional[Sequence[str]] = None,
          waived: Optional[Dict[str, str]] = None,
          program_fields: Optional[Set[str]] = None,
          run_fields: Optional[Set[str]] = None,
          exempt: Optional[Dict[str, str]] = None,
          topologies: Optional[Sequence[int]] = None,
          ) -> List[Finding]:
    """Run the coverage fixpoint; every input is overridable so tests
    can plant synthetic lattices. Returns findings (empty = the
    contracts exactly cover the reachable set)."""
    findings: List[Finding] = []

    def err(rule: str, path: str, message: str) -> None:
        findings.append(Finding(rule, path, 1, message))

    if tokens is None:
        tokens = suffix_tokens(repo_root)
    if drivers is None:
        drivers = contracts.SUFFIX_DRIVERS

    # 1. the algebra <-> driver table must match exactly: an unmapped
    # token means a family_suffix branch the lattice walk cannot reach
    # (the silent-new-family hole this pass exists to close)
    for tok in tokens:
        if tok not in drivers:
            err("suffix-unmapped", _CC_REL,
                f"family_suffix emits token '{tok}' but "
                f"contracts.SUFFIX_DRIVERS has no overrides to activate "
                f"it — the coverage walk cannot enumerate its families; "
                f"add a driver (and CheckSpecs or waivers for the new "
                f"lattice slice)")
    for tok in drivers:
        if tok not in tokens:
            err("suffix-unmapped", _CONTRACTS_REL,
                f"SUFFIX_DRIVERS maps token '{tok}' which "
                f"family_suffix no longer emits — remove the stale "
                f"driver")

    if reachable is None:
        reachable, _skips = reachable_families(repo_root, tokens=tokens,
                                               drivers=drivers)
    if specs is None:
        specs = contracts.check_specs()
    if baseline is None:
        baseline = load_baseline(repo_root)
    if donated is None:
        donated = contracts.DONATED_FAMILIES
    if waived is None:
        waived = contracts.WAIVED_FAMILIES
    if exempt is None:
        exempt = contracts.RUN_NAME_EXEMPT
    if topologies is None:
        topologies = contracts.TOPOLOGIES
    if program_fields is None:
        from defending_against_backdoors_with_robust_learning_rate_tpu.analysis import (
            fingerprint_audit)
        program_fields = {
            f for f, tag in fingerprint_audit.field_provenance().items()
            if tag == "program"
            and f in fingerprint_audit.config_fields()}
    if run_fields is None:
        run_fields = run_name_fields(repo_root)

    spec_families = {check.family for check in specs.values()}

    # 2. every reachable family is pinned or waived (with a reason)
    for fam, witnesses in sorted(reachable.items()):
        if fam in spec_families:
            continue
        if fam in waived:
            if not str(waived[fam]).strip():
                err("missing-pin", _CONTRACTS_REL,
                    f"WAIVED_FAMILIES['{fam}'] has an empty reason — "
                    f"waivers must say why no collective-budget pin is "
                    f"needed")
            continue
        err("missing-pin", _CONTRACTS_REL,
            f"planner family '{fam}' (reachable via "
            f"{', '.join(witnesses[:3])}"
            f"{', ...' if len(witnesses) > 3 else ''}) has no CheckSpec "
            f"and no WAIVED_FAMILIES reason — a program family is "
            f"shipping with no collective-budget pin")

    # 3. stale waivers: a waiver for an unreachable family, or for one
    # that meanwhile gained a spec, is dead weight that would mask a
    # future regression
    for fam in sorted(waived):
        if fam not in reachable:
            err("stale-waiver", _CONTRACTS_REL,
                f"WAIVED_FAMILIES['{fam}'] names a family no planner "
                f"emits — remove it")
        elif fam in spec_families:
            err("stale-waiver", _CONTRACTS_REL,
                f"WAIVED_FAMILIES['{fam}'] is shadowed by a CheckSpec "
                f"for the same family — remove the waiver")

    # 4. dead specs: a spec whose family no planner emits would trace
    # nothing real (build_family would raise at gate time, but the
    # coverage view names the drift directly)
    for name, check in sorted(specs.items()):
        if check.family not in reachable:
            err("dead-spec", _CONTRACTS_REL,
                f"CheckSpec '{name}' pins family '{check.family}', "
                f"which no planner surface emits — prune it (or fix the "
                f"planner regression that dropped the family)")

    # 5. baseline coverage + dead records: the committed baseline must
    # be exactly the live spec x topology matrix
    expected = _expected_baseline_keys(specs, topologies)
    recorded = set(baseline.get("families", {}))
    if recorded:
        for key in sorted(expected - recorded):
            err("topology-gap", _BASELINE_REL,
                f"no baseline record '{key}' — the spec matrix expects "
                f"one at every contracts.TOPOLOGIES entry; run "
                f"scripts/check_static.py --write-baseline")
        for key in sorted(recorded - expected):
            err("dead-baseline", _BASELINE_REL,
                f"baseline record '{key}' matches no live CheckSpec x "
                f"topology — prune it with --write-baseline")

    # 6. donated-set drift: the donation pin must cover exactly the
    # reachable chained families
    reachable_chained = {f for f in reachable if f.startswith("chained")}
    for fam in sorted(reachable_chained - set(donated)):
        err("donated-drift", _CONTRACTS_REL,
            f"reachable chained family '{fam}' is missing from "
            f"DONATED_FAMILIES — its scan carry would silently hold two "
            f"parameter buffers")
    for fam in sorted(set(donated) - reachable_chained):
        err("donated-drift", _CONTRACTS_REL,
            f"DONATED_FAMILIES lists '{fam}', which no planner emits — "
            f"prune the stale pin")

    # 7. run_name blindness: every program-provenance field must mark
    # the run dir or carry a written exemption
    for field in sorted(program_fields):
        if field in run_fields:
            continue
        if field in exempt:
            if not str(exempt[field]).strip():
                err("run-name-blind", _CONTRACTS_REL,
                    f"RUN_NAME_EXEMPT['{field}'] has an empty reason")
            continue
        err("run-name-blind", _METRICS_REL,
            f"program-provenance field '{field}' influences neither "
            f"run_name nor RUN_NAME_EXEMPT — two runs differing only in "
            f"it would interleave one metrics.jsonl stream (the "
            f"PR-3/11/13 collision class)")
    for field in sorted(exempt):
        if field in run_fields:
            err("stale-run-name-exemption", _CONTRACTS_REL,
                f"RUN_NAME_EXEMPT['{field}'] is stale — run_name now "
                f"reads the field; remove the exemption")
        elif field not in program_fields:
            err("stale-run-name-exemption", _CONTRACTS_REL,
                f"RUN_NAME_EXEMPT['{field}'] names a field that is not "
                f"program provenance — remove it")
    return findings


def scan_repo(repo_root: str) -> List[Finding]:
    return audit(repo_root)


def live_baseline_keys(repo_root: str) -> Set[str]:
    """The spec x topology key set --write-baseline prunes against."""
    return _expected_baseline_keys(contracts.check_specs(),
                                   contracts.TOPOLOGIES)
