"""scan-or-unroll: lax.scan with a Python-loop escape hatch for XLA:CPU.

XLA:CPU executes convolutions inside while-loops (every lax.scan) via a slow
reference path — measured ~60x slower than the identical step traced outside
a loop (28x28 CNN, batch 640: 213s vs 3.7s for 2 steps). scan cannot opt
out: even a LENGTH-1 scan with unroll=True still lowers to a while loop and
stays slow (128s for one step). TPU is unaffected (rolled scans are the
right choice there: one compiled body, minimal compile time).

`maybe_unrolled_scan` is therefore lax.scan everywhere, except when the
caller's `python_mode` policy says this backend+shape combination should be
traced as a plain Python loop instead. The Python path replays the exact
same ops with the same key derivations; XLA fuses the unrolled program
differently, so results agree to ~1 ulp rather than bitwise
(tests/test_client.py::test_python_loop_path_matches_scan pins this).

Call-site policy lives at the call site (each knows its per-step cost and
picks its own trip-count cap); the `RLR_SCAN_MODE` env var overrides both
ways (`scan` | `python`) so tests can compare the two paths on one backend.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp


def _parse_scan_unroll() -> int:
    """RLR_SCAN_UNROLL=n replicates the scan body n times per while-loop
    iteration (XLA unroll) — an A/B knob for TPU loop overhead; results are
    identical, only fusion scope changes. It applies to EVERY
    maybe_unrolled_scan call site (local-epoch loop, chained-round scan,
    agent-chunk loop), not just the round scan. Parsed once at import so a
    malformed value fails loudly here, not deep inside a jit trace."""
    raw = os.environ.get("RLR_SCAN_UNROLL", "1")
    try:
        n = int(raw)
    except ValueError:
        raise ValueError(
            f"RLR_SCAN_UNROLL must be an integer, got {raw!r}") from None
    if n < 1:
        raise ValueError(f"RLR_SCAN_UNROLL must be >= 1, got {n}")
    return n


_SCAN_UNROLL = _parse_scan_unroll()


def cpu_backend() -> bool:
    return jax.default_backend() == "cpu"


# A rolled loop keeps its carry in buffers of its own, and the compiler
# holds a second copy of each beside them: on the v5e a two-epoch client
# loop over 2 GB of parameters and 2 GB of momentum took 4 GB more rolled
# than traced flat (XLA's memory analysis, PERF.md section 6, PR 27). So a
# short loop whose carried parameters are a sixteenth of the device or more
# is traced flat; ResNet-9's 26 MB a client stay rolled.
LARGE_CARRY_SHARE = 16
LARGE_CARRY_MAX_STEPS = 8


def carry_is_large(params, steps: int) -> bool:
    """Whether a loop of `steps` steps that carries `params` (and as much
    optimizer state again) should be traced flat on this device."""
    if steps > LARGE_CARRY_MAX_STEPS:
        return False
    stats = jax.local_devices()[0].memory_stats()
    limit = stats.get("bytes_limit") if stats else None
    if not limit:
        return False
    nbytes = sum(x.size * x.dtype.itemsize
                 for x in jax.tree_util.tree_leaves(params))
    return LARGE_CARRY_SHARE * nbytes >= limit


def maybe_unrolled_scan(body, init, xs, python_mode: bool):
    """Drop-in for `jax.lax.scan(body, init, xs)` (no length/reverse args).

    python_mode=True traces a Python loop over the leading axis of `xs`
    (bit-identical results, no while loop in the lowered program);
    python_mode=False is exactly lax.scan. RLR_SCAN_MODE=scan|python
    overrides the caller's choice."""
    mode = os.environ.get("RLR_SCAN_MODE", "")
    if mode == "scan":
        python_mode = False
    elif mode == "python":
        python_mode = True
    if not python_mode:
        return jax.lax.scan(body, init, xs, unroll=_SCAN_UNROLL)

    length = jax.tree_util.tree_leaves(xs)[0].shape[0]
    carry = init
    ys = []
    for i in range(length):
        x_i = jax.tree_util.tree_map(lambda a, i=i: a[i], xs)
        carry, y = body(carry, x_i)
        ys.append(y)
    if not ys or all(
            not jax.tree_util.tree_leaves(y) for y in ys):
        return carry, None
    stacked = jax.tree_util.tree_map(
        lambda *leaves: jnp.stack(leaves), *ys)
    return carry, stacked
