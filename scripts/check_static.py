#!/usr/bin/env python
"""CI entry point for the static-analysis gate (analysis/).

Runs, in order, with a non-zero exit on any finding:

1. AST rules + fingerprint audit (pure AST + config import — fast, no
   programs built);
2. host-concurrency race detector (thread_rules — also pure AST: the
   execution-context graph over Thread/Timer/ThreadPoolExecutor/Pool
   call sites, cross-context state writes, racy file writes,
   check-then-act on shared paths);
3. jaxpr contracts for the single-device (vmap) families;
4. jaxpr contracts for the shard_map families at EVERY topology in
   contracts.TOPOLOGIES (1/8/16-way `agents` meshes, faked CPU devices —
   the tests/conftest.py trick at pod width), including the compiled-HLO
   collective ceilings when --compiled (the CI default) is given — so
   the gate judges the aggregation plan at pod shapes, not just the
   8-way CI mesh;
5. program-family coverage fixpoint (coverage — the reachable family
   lattice derived from compile_cache.family_suffix's own field algebra
   crossed with every planner surface, checked against CheckSpecs,
   waivers, the committed baseline, DONATED_FAMILIES, and the run_name
   provenance walk). Planning is memoized: the lattice walk never
   retraces a program the jaxpr pass already built.

Exit codes are staged so the workflow log says WHICH gate tripped
(they come from analysis/__main__.py):

    0 clean | 1 ast/audit/jaxpr findings | 2 internal error
    3 thread (race) findings | 4 coverage (lattice) findings

A per-pass finding census is printed and, under GitHub Actions,
appended to the job summary ($GITHUB_STEP_SUMMARY).

Equivalent to:

    XLA_FLAGS=--xla_force_host_platform_device_count=16 JAX_PLATFORMS=cpu \
    python -m defending_against_backdoors_with_robust_learning_rate_tpu.analysis \
        --sharded --compiled --topologies 1,8,16

but sets the env itself (before jax initializes) so it works as a bare
`python scripts/check_static.py` anywhere.
"""

import argparse
import json
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

EXIT_NAMES = {0: "clean", 1: "ast/audit/jaxpr", 2: "internal error",
              3: "thread (races)", 4: "coverage (lattice)"}


def _report_census(path: str, elapsed_s: float) -> None:
    """Print the per-pass finding census; mirror it into the GitHub
    Actions job summary when running under CI."""
    if not os.path.exists(path):
        return
    with open(path, encoding="utf-8") as f:
        doc = json.load(f)
    census = doc.get("census", {})
    code = doc.get("exit_code", 0)
    verdict = EXIT_NAMES.get(code, str(code))
    line = " ".join(f"{p}={n}" for p, n in census.items())
    print(f"[check_static] census: {line} | exit {code} ({verdict}) "
          f"| {elapsed_s:.1f}s")
    summary = os.environ.get("GITHUB_STEP_SUMMARY")
    if not summary:
        return
    rows = "\n".join(f"| {p} | {n} |" for p, n in census.items())
    with open(summary, "a", encoding="utf-8") as f:
        f.write("### Static analysis census\n\n"
                "| pass | findings |\n|---|---|\n"
                f"{rows}\n\n"
                f"Exit {code} ({verdict}), {elapsed_s:.1f}s wall.\n")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--fast", action="store_true",
                    help="AST + audit + thread only (no jax program "
                         "builds; the race pass is pure AST)")
    ap.add_argument("--no-compiled", action="store_true",
                    help="skip the compiled-HLO collective ceilings "
                         "(trace-level contracts only)")
    ap.add_argument("--write-baseline", action="store_true",
                    help="refresh analysis_baseline.json (merge + prune "
                         "to the live spec x topology set) instead of "
                         "diffing against it")
    args = ap.parse_args()

    # fake enough CPU devices for the widest topology in the contract
    # matrix (must happen before jax initializes)
    from defending_against_backdoors_with_robust_learning_rate_tpu.analysis.contracts import (
        TOPOLOGIES)
    import re
    widest = max(TOPOLOGIES)
    flags = os.environ.get("XLA_FLAGS", "")
    m = re.search(r"--xla_force_host_platform_device_count=(\d+)", flags)
    if m is None:
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={widest}"
        ).strip()
    elif int(m.group(1)) < widest:
        # a pre-existing smaller count (e.g. the 8 this script used to
        # document) cannot trace the pod-shape topologies — widen it
        # rather than dying in jaxpr_lint's explicit-topology check
        print(f"[check_static] raising faked device count "
              f"{m.group(1)} -> {widest} (pod-shape topologies)")
        os.environ["XLA_FLAGS"] = flags.replace(
            m.group(0), f"--xla_force_host_platform_device_count={widest}")
    os.environ.setdefault("JAX_PLATFORMS", "cpu")

    from defending_against_backdoors_with_robust_learning_rate_tpu.analysis.__main__ import (
        main as analysis_main)

    census_path = os.path.join(tempfile.gettempdir(),
                               f"static_census_{os.getpid()}.json")
    if args.fast:
        argv = ["--rules", "ast,audit,thread"]
    else:
        argv = ["--rules", "ast,audit,jaxpr,thread,coverage", "--sharded",
                "--topologies", ",".join(str(d) for d in TOPOLOGIES)]
        if not args.no_compiled:
            argv.append("--compiled")
        if args.write_baseline:
            argv.append("--write-baseline")
    argv += ["--census-json", census_path]
    t0 = time.monotonic()
    try:
        code = analysis_main(argv)
    finally:
        _report_census(census_path, time.monotonic() - t0)
        if os.path.exists(census_path):
            os.unlink(census_path)
    return code


if __name__ == "__main__":
    sys.exit(main())
