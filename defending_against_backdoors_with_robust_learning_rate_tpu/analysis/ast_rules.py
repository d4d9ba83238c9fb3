"""JAX-aware AST lint over the package source.

Generic linters cannot see the hazards this codebase actually trips over
(ISSUE 4): a `float()` on a device value stalls the dispatch pipeline but
is idiomatic Python; a `print` inside a jitted function fires once at
trace time and then silently never again; a reused PRNG key correlates
streams without any runtime signal; reading a donated buffer after the
call returns garbage only under jit. Each is mechanically checkable from
the AST plus a little project knowledge (analysis/contracts.py).

Rules (ids are stable — they appear in commit messages and pragmas):

- ``host-sync``        `float()`, `.item()`, `np.asarray`/`np.array`,
                       `jax.device_get` inside the round/eval hot-path
                       modules (contracts.HOT_PATH_MODULES), outside the
                       MetricsDrain. `float(cfg.*)`/literals are
                       trace-time constants and exempt.
- ``jit-side-effect``  `print`, `time.*`, `datetime.*`, `np.random.*`,
                       `os.environ` reads, and closure/global list
                       mutation inside functions that get traced
                       (jit/vmap/scan/shard_map — detected structurally,
                       see below).
- ``prng-reuse``       the same key name consumed by more than one
                       `jax.random` draw in a function (keys are
                       single-use; derive with split/fold_in).
- ``prng-unused-split``a `jax.random.split` result (or unpacked element)
                       that is never read — dead entropy usually means a
                       key was meant to be rotated and was not.
- ``donate-reuse``     an argument passed in a donated position
                       (`donate_argnums`) and read again before being
                       rebound — donated buffers are invalid after the
                       call.

Traced-function detection is a package-wide fixpoint: seeds are functions
decorated with / passed to jit-family transforms (`jit`, `vmap`, `grad`,
`shard_map`, `lax.scan`, `ops.loops.maybe_unrolled_scan`, ...), nested
defs inside `make_*`/`_build*` builder functions (this codebase's
convention for trace-destined closures), and methods of flax ``Module``
classes; any package function a traced function calls is traced too.

Suppression: a line (or the statement it starts) can carry
``# static: ok(rule)`` — or ``# static: ok(*)`` — and whole functions can
be exempted with a justification in ``contracts.ALLOW``.
"""

from __future__ import annotations

import ast
import dataclasses
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Set, Tuple

from defending_against_backdoors_with_robust_learning_rate_tpu.analysis import (
    contracts)

PRAGMA_RE = re.compile(r"#\s*static:\s*ok\(([^)]*)\)")

# terminal names whose call arguments enter trace context
_TRACER_ENTRY = frozenset({
    "jit", "vmap", "pmap", "grad", "value_and_grad", "shard_map",
    "remat", "checkpoint", "custom_jvp", "custom_vjp", "checkify",
    "maybe_unrolled_scan", "named_call", "eval_shape", "make_jaxpr",
})
# these only count when the attribute chain goes through jax.lax (plain
# `map`/`scan` name collisions with tree.map / builtins are too common)
_LAX_ENTRY = frozenset({"scan", "map", "while_loop", "fori_loop", "cond",
                        "switch", "associative_scan"})

_BUILDER_RE = re.compile(r"_?(make|build)_")

_HOST_SYNC_FLOAT_EXEMPT_ROOTS = frozenset({"cfg", "self", "config", "args"})

# jax.random draws that CONSUME a key (split included: splitting the same
# key twice yields correlated children). fold_in is derivation, not
# consumption — fold_in(key, i) with distinct i is the sanctioned pattern.
_PRNG_CONSUMERS = frozenset({
    "split", "uniform", "normal", "bernoulli", "permutation", "randint",
    "categorical", "truncated_normal", "gamma", "exponential", "choice",
    "gumbel", "laplace", "rademacher", "bits", "beta", "dirichlet",
    "shuffle", "poisson",
})

_LIST_MUTATORS = frozenset({"append", "extend", "insert", "pop", "remove",
                            "clear"})


@dataclasses.dataclass(frozen=True)
class Finding:
    rule: str
    path: str
    line: int
    message: str

    def __str__(self) -> str:
        return f"{self.path}:{self.line}: [{self.rule}] {self.message}"


# --------------------------------------------------------------------------
# helpers
# --------------------------------------------------------------------------

def _attr_chain(node: ast.AST) -> List[str]:
    """`a.b.c` -> ["a", "b", "c"]; non-name roots yield a leading ""."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    parts.append(node.id if isinstance(node, ast.Name) else "")
    return list(reversed(parts))


def _terminal_name(func: ast.AST) -> str:
    if isinstance(func, ast.Name):
        return func.id
    if isinstance(func, ast.Attribute):
        return func.attr
    return ""


def _is_hot(relpath: str) -> bool:
    return any(relpath.startswith(p) if p.endswith("/") else relpath == p
               for p in contracts.HOT_PATH_MODULES)


def _pragmas(source: str) -> Dict[int, Set[str]]:
    out: Dict[int, Set[str]] = {}
    for i, line in enumerate(source.splitlines(), start=1):
        m = PRAGMA_RE.search(line)
        if m:
            out[i] = {r.strip() for r in m.group(1).split(",") if r.strip()}
    return out


# --------------------------------------------------------------------------
# module model
# --------------------------------------------------------------------------

@dataclasses.dataclass
class FuncInfo:
    qualname: str
    node: ast.AST                      # FunctionDef / AsyncFunctionDef
    module: "ModuleInfo"
    parent: Optional["FuncInfo"]
    traced: bool = False
    builder: bool = False
    flax_method: bool = False
    # (terminal_name, base_name_or_None, lineno) of every call in the body
    calls: List[Tuple[str, Optional[str], int]] = dataclasses.field(
        default_factory=list)


@dataclasses.dataclass
class ModuleInfo:
    path: str                          # absolute
    relpath: str                       # repo-relative
    dotted: Optional[str]              # package dotted name, None for scripts
    tree: ast.Module = None
    pragmas: Dict[int, Set[str]] = dataclasses.field(default_factory=dict)
    funcs: List[FuncInfo] = dataclasses.field(default_factory=list)
    by_name: Dict[str, List[FuncInfo]] = dataclasses.field(
        default_factory=dict)
    # imported name -> (dotted module, attr or None when the name IS a module)
    imports: Dict[str, Tuple[str, Optional[str]]] = dataclasses.field(
        default_factory=dict)
    # physical line -> start line of the innermost statement covering it
    # (so a pragma above a multi-line statement reaches every node in it)
    stmt_start: Dict[int, int] = dataclasses.field(default_factory=dict)


def _collect_imports(mod: ModuleInfo) -> None:
    for node in ast.walk(mod.tree):
        if isinstance(node, ast.ImportFrom) and node.module:
            for alias in node.names:
                name = alias.asname or alias.name
                mod.imports[name] = (node.module, alias.name)
        elif isinstance(node, ast.Import):
            for alias in node.names:
                name = alias.asname or alias.name.split(".")[0]
                mod.imports[name] = (alias.name, None)


def _collect_funcs(mod: ModuleInfo) -> None:
    def walk(node: ast.AST, parent: Optional[FuncInfo],
             in_flax_class: bool) -> None:
        for child in ast.iter_child_nodes(node):
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef)):
                qual = (f"{parent.qualname}.{child.name}" if parent
                        else child.name)
                fi = FuncInfo(qualname=qual, node=child, module=mod,
                              parent=parent,
                              builder=bool(_BUILDER_RE.match(child.name)),
                              flax_method=in_flax_class)
                for sub in ast.walk(child):
                    if isinstance(sub, ast.Call):
                        term = _terminal_name(sub.func)
                        base = None
                        if isinstance(sub.func, ast.Attribute):
                            root = sub.func.value
                            if isinstance(root, ast.Name):
                                base = root.id
                        fi.calls.append((term, base, sub.lineno))
                mod.funcs.append(fi)
                mod.by_name.setdefault(child.name, []).append(fi)
                walk(child, fi, False)
            elif isinstance(child, ast.ClassDef):
                bases = {_terminal_name(b) if isinstance(b, ast.Attribute)
                         else getattr(b, "id", "") for b in child.bases}
                flax = any("Module" in b for b in bases)
                walk(child, parent, flax)
            else:
                walk(child, parent, in_flax_class)

    walk(mod.tree, None, False)


def _decorated_traced(node: ast.AST) -> bool:
    for dec in getattr(node, "decorator_list", ()):
        for sub in ast.walk(dec):
            if isinstance(sub, (ast.Name, ast.Attribute)):
                if _terminal_name(sub) in ("jit", "checkify"):
                    return True
    return False


def _call_enters_trace(call: ast.Call) -> bool:
    term = _terminal_name(call.func)
    if term in _TRACER_ENTRY:
        return True
    if term in _LAX_ENTRY:
        chain = (_attr_chain(call.func)
                 if isinstance(call.func, ast.Attribute) else [term])
        return "lax" in chain
    return False


def _seed_traced(mod: ModuleInfo) -> None:
    """Mark trace seeds: decorated jits, fns passed to transforms, nested
    defs of builders, flax methods."""
    names_passed: Set[str] = set()
    for node in ast.walk(mod.tree):
        if isinstance(node, ast.Call) and _call_enters_trace(node):
            for arg in list(node.args) + [kw.value for kw in node.keywords]:
                if isinstance(arg, ast.Name):
                    names_passed.add(arg.id)
    for fi in mod.funcs:
        if _decorated_traced(fi.node):
            fi.traced = True
        elif fi.node.name in names_passed:
            fi.traced = True
        elif fi.flax_method and fi.node.name != "setup":
            fi.traced = True
        elif fi.parent is not None and fi.parent.builder:
            # builder convention: nested defs exist to be traced later
            fi.traced = True


def _propagate_traced(mods: Dict[str, ModuleInfo]) -> None:
    """Fixpoint: anything a traced function calls (resolvable inside the
    package) is traced. Resolution: bare names match same-module functions
    and `from X import name`; `alias.attr` matches module-alias imports."""
    by_dotted = {m.dotted: m for m in mods.values() if m.dotted}

    def resolve(fi: FuncInfo, term: str,
                base: Optional[str]) -> List[FuncInfo]:
        mod = fi.module
        out: List[FuncInfo] = []
        if base is None:
            out.extend(mod.by_name.get(term, ()))
            imp = mod.imports.get(term)
            if imp and imp[1] is not None:
                target = by_dotted.get(f"{imp[0]}.{imp[1]}")
                if target is None:
                    tm = by_dotted.get(imp[0])
                    if tm is not None:
                        out.extend(tm.by_name.get(imp[1], ()))
        else:
            imp = mod.imports.get(base)
            if imp is not None:
                dotted = (imp[0] if imp[1] is None
                          else f"{imp[0]}.{imp[1]}")
                tm = by_dotted.get(dotted)
                if tm is not None:
                    out.extend(tm.by_name.get(term, ()))
        return out

    work = [fi for m in mods.values() for fi in m.funcs if fi.traced]
    seen = set(id(f) for f in work)
    while work:
        fi = work.pop()
        for term, base, _ in fi.calls:
            for target in resolve(fi, term, base):
                if id(target) not in seen:
                    target.traced = True
                    seen.add(id(target))
                    work.append(target)


# --------------------------------------------------------------------------
# per-function rule checks
# --------------------------------------------------------------------------

def _allowed(fi: FuncInfo, rule: str) -> bool:
    cur: Optional[FuncInfo] = fi
    while cur is not None:
        rules = contracts.ALLOW.get((fi.module.relpath, cur.qualname))
        if rules and rule in rules:
            return True
        cur = cur.parent
    return False


def _suppressed(mod: ModuleInfo, node: ast.AST, rule: str) -> bool:
    start = getattr(node, "lineno", 0)
    end = getattr(node, "end_lineno", start) or start
    stmt = mod.stmt_start.get(start, start)
    lines = set(range(max(1, start - 1), end + 1))
    lines.update((stmt, max(1, stmt - 1)))
    for line in lines:
        tags = mod.pragmas.get(line)
        if tags and (rule in tags or "*" in tags):
            return True
    return False


def _emit(findings: List[Finding], mod: ModuleInfo, fi: Optional[FuncInfo],
          node: ast.AST, rule: str, message: str) -> None:
    if fi is not None and _allowed(fi, rule):
        return
    if _suppressed(mod, node, rule):
        return
    findings.append(Finding(rule, mod.relpath, node.lineno, message))


def _own_nodes(fi: FuncInfo) -> Iterable[ast.AST]:
    """Walk fi's body but do not descend into nested function defs (they
    are their own FuncInfo)."""
    stack: List[ast.AST] = [fi.node]
    first = True
    while stack:
        node = stack.pop()
        if not first and isinstance(node, (ast.FunctionDef,
                                           ast.AsyncFunctionDef)):
            continue
        first = False
        yield node
        stack.extend(ast.iter_child_nodes(node))


def _np_alias(mod: ModuleInfo) -> Optional[str]:
    for name, (dotted, attr) in mod.imports.items():
        if dotted == "numpy" and attr is None:
            return name
    return None


def _check_host_sync(mod: ModuleInfo, fi: FuncInfo,
                     findings: List[Finding]) -> None:
    np_name = _np_alias(mod) or "np"
    for node in _own_nodes(fi):
        if not isinstance(node, ast.Call):
            continue
        func = node.func
        if isinstance(func, ast.Name) and func.id == "float" and node.args:
            arg = node.args[0]
            if isinstance(arg, ast.Constant):
                continue
            chain = _attr_chain(arg) if isinstance(arg, ast.Attribute) \
                else None
            if chain and chain[0] in _HOST_SYNC_FLOAT_EXEMPT_ROOTS:
                continue  # float(cfg.x): trace-time constant, not a sync
            _emit(findings, mod, fi, node, "host-sync",
                  "float() on a (possibly device) value in a hot-path "
                  "module forces a blocking transfer; route it through "
                  "the MetricsDrain or fetch in one batched device_get")
        elif isinstance(func, ast.Attribute):
            chain = _attr_chain(func)
            if func.attr == "item" and not node.args:
                _emit(findings, mod, fi, node, "host-sync",
                      ".item() blocks on device->host transfer in a "
                      "hot-path module")
            elif (chain[0] == np_name and func.attr in ("asarray", "array")
                  and chain[-2] == np_name):
                _emit(findings, mod, fi, node, "host-sync",
                      f"{np_name}.{func.attr}() on a device value "
                      "synchronously copies to host; use jnp or defer to "
                      "the metrics drain")
            elif func.attr == "device_get" and chain[0] == "jax":
                _emit(findings, mod, fi, node, "host-sync",
                      "jax.device_get in a hot-path module: the only "
                      "sanctioned home for the round loop's host sync is "
                      "the MetricsDrain's batched fetch")


def _check_jit_side_effects(mod: ModuleInfo, fi: FuncInfo,
                            findings: List[Finding]) -> None:
    assigned: Set[str] = set()
    for node in _own_nodes(fi):
        if isinstance(node, (ast.Assign, ast.AugAssign, ast.AnnAssign)):
            targets = (node.targets if isinstance(node, ast.Assign)
                       else [node.target])
            for t in targets:
                for sub in ast.walk(t):
                    if isinstance(sub, ast.Name):
                        assigned.add(sub.id)
        elif isinstance(node, (ast.For, ast.comprehension)):
            for sub in ast.walk(node.target):
                if isinstance(sub, ast.Name):
                    assigned.add(sub.id)
        elif isinstance(node, ast.With):
            for item in node.items:
                if item.optional_vars is not None:
                    for sub in ast.walk(item.optional_vars):
                        if isinstance(sub, ast.Name):
                            assigned.add(sub.id)
    args = fi.node.args
    for a in (args.posonlyargs + args.args + args.kwonlyargs
              + ([args.vararg] if args.vararg else [])
              + ([args.kwarg] if args.kwarg else [])):
        assigned.add(a.arg)

    for node in _own_nodes(fi):
        if isinstance(node, ast.Call):
            func = node.func
            if isinstance(func, ast.Name) and func.id == "print":
                _emit(findings, mod, fi, node, "jit-side-effect",
                      "print() inside a traced function fires once at "
                      "trace time and never again; use jax.debug.print "
                      "or move it to the host loop")
            elif isinstance(func, ast.Attribute):
                chain = _attr_chain(func)
                if chain[0] == "time":
                    _emit(findings, mod, fi, node, "jit-side-effect",
                          "time.* inside a traced function measures trace "
                          "time, not run time")
                elif chain[0] == "datetime":
                    _emit(findings, mod, fi, node, "jit-side-effect",
                          "datetime.* inside a traced function is a "
                          "trace-time constant")
                elif chain[:2] == ["np", "random"] or \
                        chain[:2] == ["numpy", "random"]:
                    _emit(findings, mod, fi, node, "jit-side-effect",
                          "numpy RNG inside a traced function bakes one "
                          "draw into the program; use jax.random with an "
                          "explicit key")
                elif (func.attr in _LIST_MUTATORS
                      and isinstance(func.value, ast.Name)
                      and func.value.id not in assigned):
                    _emit(findings, mod, fi, node, "jit-side-effect",
                          f"mutating closure/global '{func.value.id}' "
                          "inside a traced function leaks tracers (runs "
                          "at trace time only)")
        elif isinstance(node, ast.Attribute):
            chain = _attr_chain(node)
            if chain == ["os", "environ"]:
                _emit(findings, mod, fi, node, "jit-side-effect",
                      "os.environ read inside a traced function makes the "
                      "compiled program depend on trace-time env state "
                      "(invisible to the AOT fingerprint)")


def _is_jax_random_call(node: ast.Call) -> Optional[str]:
    """Return the draw name when node is jax.random.<draw>/random.<draw>."""
    if not isinstance(node.func, ast.Attribute):
        return None
    chain = _attr_chain(node.func)
    if node.func.attr in _PRNG_CONSUMERS and "random" in chain[:-1]:
        return node.func.attr
    return None


def _check_prng(mod: ModuleInfo, fi: FuncInfo,
                findings: List[Finding]) -> None:
    # loads include nested defs: a split key consumed only inside a
    # closure (fl/client.py's fold_in(drop_key, b) in the batch body) is
    # used, not dead. stores stay own-scope: a nested def rebinding the
    # name is a different variable.
    loads: Dict[str, List[int]] = {}
    for node in ast.walk(fi.node):
        if isinstance(node, ast.Name) and isinstance(node.ctx, ast.Load):
            loads.setdefault(node.id, []).append(node.lineno)
    stores: Dict[str, List[int]] = {}
    for node in _own_nodes(fi):
        if isinstance(node, ast.Name) and not isinstance(node.ctx,
                                                         ast.Load):
            stores.setdefault(node.id, []).append(node.lineno)

    consumed: Dict[str, List[ast.Call]] = {}
    for node in _own_nodes(fi):
        if not isinstance(node, ast.Call):
            continue
        draw = _is_jax_random_call(node)
        if draw is None:
            continue
        if node.args and isinstance(node.args[0], ast.Name):
            consumed.setdefault(node.args[0].id, []).append(node)

    # prng-reuse: one name, >1 consuming draw, never rotated (reassigned)
    for name, calls in consumed.items():
        if len(calls) > 1 and name not in stores:
            for call in calls[1:]:
                _emit(findings, mod, fi, call, "prng-reuse",
                      f"key '{name}' already consumed by a jax.random "
                      f"draw at line {calls[0].lineno}; split or fold_in "
                      "a fresh key instead of reusing it")

    # prng-unused-split: split results that are never read
    for node in _own_nodes(fi):
        if isinstance(node, ast.Expr) and isinstance(node.value, ast.Call) \
                and _is_jax_random_call(node.value) == "split":
            _emit(findings, mod, fi, node, "prng-unused-split",
                  "jax.random.split result discarded — dead entropy")
        elif isinstance(node, ast.Assign) and isinstance(node.value,
                                                         ast.Call) \
                and _is_jax_random_call(node.value) == "split":
            targets: List[ast.Name] = []
            for t in node.targets:
                if isinstance(t, ast.Name):
                    targets.append(t)
                elif isinstance(t, ast.Tuple):
                    targets.extend(e for e in t.elts
                                   if isinstance(e, ast.Name))
            src_key = (node.value.args[0].id
                       if node.value.args
                       and isinstance(node.value.args[0], ast.Name)
                       else None)
            for t in targets:
                if t.id == "_" or t.id.startswith("_unused"):
                    continue
                if t.id == src_key:
                    continue   # rotation idiom: key, sub = split(key)
                used = any(line > node.lineno
                           for line in loads.get(t.id, ()))
                if not used:
                    _emit(findings, mod, fi, t, "prng-unused-split",
                          f"split key '{t.id}' is never used; drop it or "
                          "rotate the parent key")


def _donated_local_jits(mod: ModuleInfo) -> Dict[str, Tuple[int, ...]]:
    """Function names in this module decorated with
    functools.partial(jax.jit, donate_argnums=...)."""
    out: Dict[str, Tuple[int, ...]] = {}
    for fi in mod.funcs:
        for dec in getattr(fi.node, "decorator_list", ()):
            if not isinstance(dec, ast.Call):
                continue
            if _terminal_name(dec.func) != "partial":
                continue
            if not any(_terminal_name(a) == "jit"
                       for a in dec.args if isinstance(a, (ast.Name,
                                                           ast.Attribute))):
                continue
            for kw in dec.keywords:
                if kw.arg == "donate_argnums":
                    val = kw.value
                    nums: Tuple[int, ...] = ()
                    if isinstance(val, ast.Constant) \
                            and isinstance(val.value, int):
                        nums = (val.value,)
                    elif isinstance(val, (ast.Tuple, ast.List)):
                        nums = tuple(e.value for e in val.elts
                                     if isinstance(e, ast.Constant))
                    if nums:
                        out[fi.node.name] = nums
    return out


def _check_donate_reuse(mod: ModuleInfo, fi: FuncInfo,
                        donated: Dict[str, Tuple[int, ...]],
                        findings: List[Finding]) -> None:
    loads: Dict[str, List[int]] = {}
    stores: Dict[str, List[int]] = {}
    for node in _own_nodes(fi):
        if isinstance(node, ast.Name):
            (loads if isinstance(node.ctx, ast.Load)
             else stores).setdefault(node.id, []).append(node.lineno)

    for node in _own_nodes(fi):
        if not isinstance(node, ast.Call):
            continue
        callee = _terminal_name(node.func)
        positions = donated.get(callee)
        if not positions:
            continue
        for pos in positions:
            if pos >= len(node.args):
                continue
            arg = node.args[pos]
            if not isinstance(arg, ast.Name):
                continue
            cline = node.lineno
            # rebound on the call line itself (params, x = f(params, ...))
            # -> the stale buffer is unreachable
            rebound_lines = [line for line in stores.get(arg.id, ())
                             if line >= cline]
            for lline in loads.get(arg.id, ()):
                if lline <= cline:
                    continue
                if any(cline <= s <= lline for s in rebound_lines):
                    break
                _emit(findings, mod, fi, node, "donate-reuse",
                      f"'{arg.id}' is donated to {callee}() (argument "
                      f"{pos}) but read again at line {lline}; donated "
                      "buffers are invalid after the call")
                break


# --------------------------------------------------------------------------
# driver
# --------------------------------------------------------------------------

def _dotted_name(relpath: str) -> Optional[str]:
    if not relpath.startswith(contracts.PKG + "/"):
        return None
    mod = relpath[:-3] if relpath.endswith(".py") else relpath
    dotted = mod.replace("/", ".")
    if dotted.endswith(".__init__"):
        dotted = dotted[: -len(".__init__")]
    return dotted


def load_module(path: str, repo_root: str) -> ModuleInfo:
    relpath = os.path.relpath(path, repo_root)
    with open(path, encoding="utf-8") as f:
        source = f.read()
    mod = ModuleInfo(path=path, relpath=relpath,
                     dotted=_dotted_name(relpath))
    mod.tree = ast.parse(source, filename=relpath)
    mod.pragmas = _pragmas(source)
    for node in ast.walk(mod.tree):
        if isinstance(node, ast.stmt):
            end = node.end_lineno or node.lineno
            for line in range(node.lineno, end + 1):
                # innermost statement wins (largest start line <= line)
                if mod.stmt_start.get(line, 0) < node.lineno:
                    mod.stmt_start[line] = node.lineno
    _collect_imports(mod)
    _collect_funcs(mod)
    return mod


def default_paths(repo_root: str) -> List[str]:
    """The scanned surface: the package, the live scripts, and the bench/
    driver entry points. Tests are excluded (they exercise pathological
    patterns on purpose)."""
    paths: List[str] = []
    pkg_dir = os.path.join(repo_root, contracts.PKG)
    for base, dirs, files in os.walk(pkg_dir):
        dirs[:] = [d for d in dirs if d != "__pycache__"]
        paths.extend(os.path.join(base, f) for f in files
                     if f.endswith(".py"))
    scripts = os.path.join(repo_root, "scripts")
    if os.path.isdir(scripts):
        paths.extend(os.path.join(scripts, f)
                     for f in os.listdir(scripts) if f.endswith(".py"))
    for extra in ("bench.py", "chip_smoke.py", "federated.py"):
        p = os.path.join(repo_root, extra)
        if os.path.exists(p):
            paths.append(p)
    return sorted(paths)


def scan(paths: Sequence[str], repo_root: str) -> List[Finding]:
    """Run every AST rule over `paths`; returns findings sorted by
    location."""
    mods: Dict[str, ModuleInfo] = {}
    for path in paths:
        mod = load_module(path, repo_root)
        mods[mod.relpath] = mod
    for mod in mods.values():
        _seed_traced(mod)
    _propagate_traced(mods)

    findings: List[Finding] = []
    for mod in mods.values():
        hot = _is_hot(mod.relpath)
        donated = dict(contracts.DONATED_CALLS)
        donated.update(_donated_local_jits(mod))
        for fi in mod.funcs:
            if hot:
                _check_host_sync(mod, fi, findings)
            if fi.traced:
                _check_jit_side_effects(mod, fi, findings)
            _check_prng(mod, fi, findings)
            _check_donate_reuse(mod, fi, donated, findings)
    return sorted(findings, key=lambda f: (f.path, f.line, f.rule))


def scan_repo(repo_root: str) -> List[Finding]:
    return scan(default_paths(repo_root), repo_root)
