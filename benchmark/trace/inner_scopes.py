"""Device self time by the innermost of a given set of `jax.named_scope`
names on an operation's path.

`reduce.scope_of` files an operation under the FIRST planted scope on its
`tf_op` path, so everything a client's model does reads as `local_train`.
A model plants scopes of its own inside that one (`short_conv`,
`moe_router`, `moe_experts`, ...); this files an operation's self time
under the LAST part of its path that names one of the scopes asked for.
Transformations wrap a scope's name where they rewrite the operations under
it (`jvp(moe_experts)`, `transpose(jvp(moe_experts))`, and the like for
recomputed blocks), so a part is compared without such wrappers: forward,
backward and recompute of a scope all read under its name. One kind of
operation carries no path at all: XLA's TPU compiler rewrites a
`jax.lax.ragged_dot` into a grouped-product custom call of its own and
names it `ragged-dot-...` in place of the path (the reducer files it as a
program of that name, under no scope); `OP_SCOPES` files such an operation
under the scope the model plants around its only use. Such an operation
does not say which program it ran in either: it is given the program of
the operation with a path that ran last before it on its device (programs
run one at a time there, and no program starts with a grouped product), so
that `skip_programs` can leave the eval programs' share of a scope out of a
round's. Seconds, mean over the device planes, like `reduce.summarize`. A reader opens the profile
the harness keeps until the readers have run, through `reduce.find_xplane`
(as `idle_outside_spans_pct.py` does)."""

from __future__ import annotations

import functools
import os
import re
from typing import Dict, Optional, Sequence, Tuple

from benchmark.trace import reduce, xplane

_WRAPPED = re.compile(r"^(?:[A-Za-z_][A-Za-z0-9_]*\()+|\)+$")
# operations the compiler names itself, by the start of that name, and the
# scope their only use lies in (models/lfm2_moe.sparse_ffn)
OP_SCOPES = (("ragged-dot", "moe_experts"),)


def bare(part: str) -> str:
    """`transpose(jvp(moe_experts))` -> `moe_experts`."""
    return _WRAPPED.sub("", part)


def innermost(tf_op: str, names: Sequence[str]) -> str:
    """The last part of the path that names one of `names`, or ''."""
    found = ""
    path = str(tf_op or "").rstrip(":")
    for start, scope in OP_SCOPES:
        if path.startswith(start) and scope in names:
            return scope
    for part in path.split("/"):
        b = bare(part)
        if b in names:
            found = b
    return found


def _want(plane: str, line: str) -> bool:
    return bool(reduce.DEVICE_PLANE.match(plane)) and line == reduce.OPS_LINE


def program_of(tf_op: str) -> str:
    """The head of a path (`jit(step)`), or '' where there is no path."""
    path = str(tf_op or "")
    return path.split("/", 1)[0] if "/" in path else ""


@functools.lru_cache(maxsize=4)
def _by_inner_scope(path: str, names: Tuple[str, ...],
                    skip_programs: Tuple[str, ...] = ()
                    ) -> Optional[Dict[str, float]]:
    planes = [p for p in xplane.read(path, _want)
              if reduce.DEVICE_PLANE.match(p.name)]
    tables = []
    for plane in planes:
        ops = next((ln.events for ln in plane.lines
                    if ln.name == reduce.OPS_LINE), [])
        if not ops:
            continue
        ops = sorted(ops, key=lambda e: (e[0], -e[1]))
        table: Dict[str, float] = {}
        program = ""
        for (_s, _d, mid), own in zip(ops, reduce.self_times(ops),
                                      strict=True):
            meta = plane.event_meta.get(mid) or xplane.EventMeta()
            tf_op = meta.stats.get("tf_op", "")
            program = program_of(tf_op) or program
            scope = innermost(tf_op, names)
            if scope and program not in skip_programs:
                table[scope] = table.get(scope, 0.0) + own
        tables.append(table)
    if not tables:
        return None
    return {n: sum(t.get(n, 0.0) for t in tables) / len(tables) / 1e9
            for n in names}


def self_seconds(path: str, names: Sequence[str],
                 skip_programs: Sequence[str] = ()
                 ) -> Optional[Dict[str, float]]:
    """{scope: device self seconds} over the trace at `path`, the
    operations of `skip_programs` left out; None where it holds no device
    operation."""
    return _by_inner_scope(path, tuple(names), tuple(skip_programs))


# the scopes models/lfm2_moe.py plants inside `local_train`
MODEL_SCOPES = ("short_conv", "attention", "moe_router", "moe_experts",
                "dense_ffn", "lm_head")
# the eval boundary's two programs run the same model under the same scopes
# (`eval_boundary_ms` reads them by this name): a round's time leaves them out
EVAL_PROGRAMS = ("jit(eval_fn)",)


def model_scope_ms_per_round(ctx, scope: str) -> Optional[float]:
    """Device milliseconds a traced round spends under one of
    `MODEL_SCOPES` in the round program (the eval programs left out, so that
    it is a part of what `local_train_ms` reads), for the per-layer readers;
    None where there is no trace, no traced round, or no operation under
    the scope (a program that plants none)."""
    if ctx["trace"] is None or not ctx["traced_rounds"]:
        return None
    path = reduce.find_xplane(
        os.path.join(os.path.dirname(ctx["cfg"].log_dir), "trace"))
    table = (self_seconds(path, MODEL_SCOPES, EVAL_PROGRAMS) if path
             else None)
    if not table or not table.get(scope):
        return None
    return 1e3 * table[scope] / ctx["traced_rounds"]
