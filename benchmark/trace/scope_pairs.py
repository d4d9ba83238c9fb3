"""Device self time by two scopes of an operation's path at once, and the
path-less layout copies that run beside a scope.

`inner_scopes.self_seconds` files an operation under the innermost of one
set of names. The attention core (`attn_core`, around
`token_ops.causal_attention`) and the rotary step (`attn_rope`) lie inside
each model's layer scope (`window_attention`, `global_attention`,
`mla_attention`, `attention`), so the core of one layer kind is read by a
pair: the innermost of the inner names on an operation's path with the
innermost of the outer ones (`pair_seconds`).

XLA puts copies that change an operand's layout (`is_layout`) before a
custom call (the attention kernel takes q, k and v head-major) and after
it, and they carry no path. `beside` files such an operation under a scope
where its neighbour on the same device lies under that scope: the next
operation with a path, for an operand, or the last one before it, for a
result. Only
operations that hold no other are neighbours (a `while` that holds the
kernel is not), and an operation that `inner_scopes.OP_SCOPES` files by its
name counts as one with a path. Programs, the eval programs' skipping and
the mean over device planes are as in `inner_scopes`."""

from __future__ import annotations

import functools
from typing import Dict, List, Optional, Sequence, Tuple

from benchmark.trace import inner_scopes, reduce, xplane

# what `beside` files: XLA's `hlo_category` of layout changes, and copies
# but the asynchronous ones (`copy-start` / `copy-done`: moves between
# memory spaces that XLA schedules wherever they fit, which on the chip were
# mostly not the core's operands: PERF.md section 6, PR 36)
LAYOUT_CATEGORY = "data formatting"
ASYNC_COPY = ("copy-start", "copy-done")


def is_layout(name: str, category: str) -> bool:
    return category == LAYOUT_CATEGORY or (
        name.startswith("copy") and not name.startswith(ASYNC_COPY))


def _has_path(tf_op: str) -> bool:
    return bool(inner_scopes.program_of(tf_op)) or any(
        tf_op.startswith(start) for start, _s in inner_scopes.OP_SCOPES)


@functools.lru_cache(maxsize=2)
def _device_ops(path: str) -> Tuple[List[tuple], ...]:
    """Per device plane that holds operations, each in order of start:
    (self ns, tf_op, program, name, hlo_category, leaf)."""
    out = []
    for plane in xplane.read(path, inner_scopes._want):
        if not reduce.DEVICE_PLANE.match(plane.name):
            continue
        ops = next((ln.events for ln in plane.lines
                    if ln.name == reduce.OPS_LINE), [])
        if not ops:
            continue
        ops = sorted(ops, key=lambda e: (e[0], -e[1]))
        rows, program = [], ""
        for i, ((s, d, mid), own) in enumerate(
                zip(ops, reduce.self_times(ops), strict=True)):
            meta = plane.event_meta.get(mid) or xplane.EventMeta()
            tf_op = str(meta.stats.get("tf_op") or "").rstrip(":")
            program = inner_scopes.program_of(tf_op) or program
            leaf = i + 1 == len(ops) or ops[i + 1][0] >= s + d
            rows.append((own, tf_op, program, reduce._short(meta),
                         str(meta.stats.get("hlo_category") or ""), leaf))
        out.append(rows)
    return tuple(out)


def pair_seconds(path: str, inner: Sequence[str], outer: Sequence[str],
                 skip_programs: Sequence[str] = ()
                 ) -> Optional[Dict[Tuple[str, str], float]]:
    """{(innermost of `outer` or '', innermost of `inner`): device self
    seconds} over the operations with one of `inner` on their path, those
    of `skip_programs` left out; None where the trace holds no device
    operation."""
    planes = _device_ops(path)
    if not planes:
        return None
    table: Dict[Tuple[str, str], float] = {}
    for rows in planes:
        for own, tf_op, program, _name, _cat, _leaf in rows:
            scope = inner_scopes.innermost(tf_op, inner)
            if scope and program not in skip_programs:
                key = (inner_scopes.innermost(tf_op, outer), scope)
                table[key] = table.get(key, 0.0) + own
    return {k: v / len(planes) / 1e9 for k, v in table.items()}


def beside(path: str, scope: str, skip_programs: Sequence[str] = ()
           ) -> Optional[Dict[str, float]]:
    """{operation name: device self seconds} of the path-less layout
    operations (`is_layout`) whose neighbour lies under `scope`, those of
    `skip_programs` left out; None where no operation lies under `scope`
    (a program that plants none) or there is no device operation."""
    planes = _device_ops(path)
    table: Dict[str, float] = {}
    seen = False
    for rows in planes:
        after, pending = False, []     # the last neighbour; copies since
        for own, tf_op, program, name, cat, leaf in rows:
            if not leaf:
                continue
            if _has_path(tf_op):
                under = inner_scopes.innermost(tf_op, (scope,)) == scope
                seen = seen or under
                for copy, secs, after_it in pending:
                    if after_it or under:
                        table[copy] = table.get(copy, 0.0) + secs
                after, pending = under, []
            elif is_layout(name, cat) and program not in skip_programs:
                pending.append((name, own, after))
        for copy, secs, after_it in pending:
            if after_it:
                table[copy] = table.get(copy, 0.0) + secs
    if not seen:
        return None
    return {k: v / len(planes) / 1e9 for k, v in table.items()}
