#!/usr/bin/env python
"""Precompile & bank every program family for a config list, OFFLINE.

Every program family the flagship bench/driver will dispatch is compiled
ahead-of-time and banked as a serialized executable
(utils/compile_cache.py). Subsequent `bench.py` / `train.py` runs that
share the cache root load the executables and never enter XLA — on the
chip that means the same tool call, unless the machine sets
$JAX_COMPILATION_CACHE_DIR to a directory that outlives it.

    python scripts/precompile.py                       # fmnist + resnet9
    python scripts/precompile.py --configs fmnist
    python scripts/precompile.py --print_manifest      # list families, no compile

`--print_manifest` lists, per config, every program family with its
fingerprint and whether it is already banked. Idempotent: re-running skips
(and verifies) already-banked families.
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--configs", default="fmnist,resnet9",
                    help="comma list of named configs (fmnist|resnet9 — "
                         "the bench.py shapes)")
    ap.add_argument("--platform", default="",
                    help="force a jax platform (cpu|tpu); empty = default")
    ap.add_argument("--chain", type=int, default=10,
                    help="chained-block length to bank (bench.py default); "
                         "the per-round + eval families are banked "
                         "regardless")
    ap.add_argument("--rng_impl", choices=("auto", "threefry", "rbg"),
                    default="auto",
                    help="PRNG bit generator — must match the later run "
                         "(auto = hardware rbg on TPU)")
    ap.add_argument("--cache_dir", default="",
                    help="compile-cache root (default: .compile_cache/ "
                         "in the checkout; $JAX_COMPILATION_CACHE_DIR, "
                         "where set, takes precedence)")
    ap.add_argument("--synth_train_size", type=int, default=0,
                    help="override synthetic dataset size (CI/small-shape "
                         "verification; 0 = config default)")
    ap.add_argument("--print_manifest", action="store_true",
                    help="list every program family + fingerprint + banked "
                         "state per config, WITHOUT compiling anything")
    args = ap.parse_args()

    import jax
    if args.platform:
        jax.config.update("jax_platforms", args.platform)

    from bench import bench_config
    from defending_against_backdoors_with_robust_learning_rate_tpu.data.registry import (
        get_federated_data)
    from defending_against_backdoors_with_robust_learning_rate_tpu.fl.common import (
        make_normalizer)
    from defending_against_backdoors_with_robust_learning_rate_tpu.models.registry import (
        get_model)
    from defending_against_backdoors_with_robust_learning_rate_tpu.train import (
        apply_rng_impl)
    from defending_against_backdoors_with_robust_learning_rate_tpu.utils import (
        compile_cache)

    apply_rng_impl(args.rng_impl)
    cache_cfg = bench_config("fmnist", compile_cache_dir=args.cache_dir)
    root = compile_cache.cache_root(cache_cfg)
    if not args.print_manifest:
        compile_cache.enable_persistent_cache(cache_cfg)
    bank = compile_cache.AotBank(root)
    print(f"[precompile] cache root: {root}", file=sys.stderr)

    summary = []
    for name in (c for c in args.configs.split(",") if c):
        cfg = bench_config(name, compile_cache_dir=args.cache_dir)
        # chain/snap only select WHICH families the planner emits (both are
        # excluded from fingerprints; the round_ids length pins the shape)
        cfg = cfg.replace(chain=args.chain, snap=max(1, args.chain))
        if args.synth_train_size:
            cfg = cfg.replace(
                synth_train_size=args.synth_train_size,
                synth_val_size=max(512, args.synth_train_size // 10),
                data_dir="/nonexistent_use_synthetic_reduced")
        # cohort-mode configs must NOT be materialized densely (the point
        # of the population axis) — and their shard avals come from the
        # bank's padded row length, not the dense stack's, so the banked
        # executables match what train.py dispatches
        if compile_cache.is_cohort_mode(cfg):
            from defending_against_backdoors_with_robust_learning_rate_tpu.data.registry import (
                get_cohort_data)
            fed = get_cohort_data(cfg)
        else:
            fed = get_federated_data(cfg)
        # resolved as the engine resolves it, and written back: the keys
        # banked here are the keys train.py will ask for
        cfg = cfg.replace(
            remat_policy=compile_cache.resolved_remat(cfg, fed).policy)
        model = get_model(cfg.data, cfg.model_arch, cfg.dtype,
                          remat=cfg.remat, remat_policy=cfg.remat_policy)
        norm = make_normalizer(fed.mean, fed.std, fed.raw_is_normalized)
        if args.print_manifest:
            for spec in compile_cache.plan_programs(cfg, model, norm, fed):
                fp = compile_cache.fingerprint(cfg, spec.family,
                                               spec.example_args)
                banked = bank.lookup(spec.family, fp) is not None
                print(json.dumps({"config": name, "family": spec.family,
                                  "fingerprint": fp, "banked": banked}))
            continue
        rows = compile_cache.precompile(
            cfg, model, norm, fed, bank,
            log=lambda m, name=name: print(f"[{name}] {m}",
                                           file=sys.stderr))
        summary.extend({"config": name, "family": r["family"],
                        "cache_hit": r["cache_hit"],
                        "seconds": r["seconds"]} for r in rows)
    if not args.print_manifest:
        print(json.dumps({"precompiled": summary, "cache_root": root}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
