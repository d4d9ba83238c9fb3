#!/usr/bin/env python
"""Op-level top time sinks of steady flagship rounds, from a jax.profiler
trace (VERDICT r3 next #3).

The ablation ladder (BENCH_NOTES.md r3, `profile_round.py --ablate`)
decomposes the round by re-compiling it with one component removed at a
time; its deltas overlap (removals change XLA's schedule), which caps
attribution precision. This script is the other half: capture ONE op-level
trace of steady-state rounds and print where XLA's own schedule says the
time goes, so the two decompositions can be reconciled in BENCH_NOTES.md.

Since the obs/ attribution layer landed, this is a thin CLI: the parsing
lives in ``obs.attribution`` (`parse_top_ops` for this op-kind view,
`attribute` for the compute/collective/gap + named-scope split the run
report uses) — one parser, re-used by `python -m ..obs.report`, bench.py
and the driver's `--profile_rounds` window.

Usage:
  python scripts/trace_top_ops.py              # capture + parse (TPU)
  python scripts/trace_top_ops.py --parse DIR  # re-parse an existing trace
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from defending_against_backdoors_with_robust_learning_rate_tpu.obs.attribution import (  # noqa: E402
    attribute, find_trace_file, group_name, load_trace_events, parse_top_ops)

# historical names kept importable (tests/test_trace_tool.py and any
# notebook that did `from trace_top_ops import parse`)
parse = parse_top_ops
__all__ = ["attribute", "capture", "group_name", "parse", "parse_top_ops"]


def capture(trace_dir: str, rounds: int, platform: str = "",
            smoke: bool = False) -> None:
    import jax
    if platform:
        jax.config.update("jax_platforms", platform)
    import jax.numpy as jnp

    from defending_against_backdoors_with_robust_learning_rate_tpu.config import Config
    from defending_against_backdoors_with_robust_learning_rate_tpu.data.registry import (
        get_federated_data)
    from defending_against_backdoors_with_robust_learning_rate_tpu.fl.common import (
        make_normalizer)
    from defending_against_backdoors_with_robust_learning_rate_tpu.fl.rounds import (
        make_round_fn)
    from defending_against_backdoors_with_robust_learning_rate_tpu.models.registry import (
        get_model, init_params)
    from defending_against_backdoors_with_robust_learning_rate_tpu.obs.attribution import (
        write_capture_meta)
    from defending_against_backdoors_with_robust_learning_rate_tpu.train import (
        apply_rng_impl)

    apply_rng_impl("auto")
    # the bench.py flagship config, unchained: per-round dispatch gives the
    # trace clean per-round boundaries (chained timing itself is within 1%
    # of unchained at chain>=10, BENCH_NOTES.md r2 ladder)
    cfg = Config(data="fmnist", num_agents=10, local_ep=2, bs=256,
                 num_corrupt=1, poison_frac=0.5, robustLR_threshold=4,
                 synth_train_size=60000, synth_val_size=10000, seed=0)
    if smoke:
        # tiny shapes: validates capture->parse end-to-end on any backend
        # (timings meaningless; XLA:CPU runs scan convs on a slow path)
        cfg = cfg.replace(bs=32, synth_train_size=640, synth_val_size=128,
                          data_dir="/nonexistent_use_synthetic")
    fed = get_federated_data(cfg)
    model = get_model(cfg.data, cfg.model_arch, cfg.dtype)
    params = init_params(model, fed.train.images.shape[2:],
                         jax.random.PRNGKey(0))
    norm = make_normalizer(fed.mean, fed.std, fed.raw_is_normalized)
    round_fn = make_round_fn(cfg, model, norm,
                             jnp.asarray(fed.train.images),
                             jnp.asarray(fed.train.labels),
                             jnp.asarray(fed.train.sizes))
    base_key = jax.random.PRNGKey(1)
    print(f"[trace] device={jax.devices()[0]}", flush=True)
    # warm up: compile + 2 steady rounds outside the capture window; round
    # r's key is fold_in(base_key, r) — the driver loop's derivation
    for r in range(3):
        params, _ = round_fn(params, jax.random.fold_in(base_key, r))
    jax.block_until_ready(params)
    jax.profiler.start_trace(trace_dir)
    for r in range(3, 3 + rounds):
        params, _ = round_fn(params, jax.random.fold_in(base_key, r))
    jax.block_until_ready(params)
    jax.profiler.stop_trace()
    write_capture_meta(trace_dir, {"rounds": rounds,
                                   "backend": jax.default_backend(),
                                   "source": "trace_top_ops"})
    print(f"[trace] captured {rounds} steady rounds -> {trace_dir}",
          flush=True)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--parse", default="", dest="parse_dir",
                    help="parse an existing trace dir instead of capturing")
    ap.add_argument("--trace_dir", default="/tmp/rlr_trace")
    ap.add_argument("--rounds", type=int, default=3,
                    help="steady rounds inside the capture window")
    ap.add_argument("--top", type=int, default=12)
    ap.add_argument("--platform", default="",
                    help="force a jax platform (e.g. cpu)")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny shapes — validates the capture->parse "
                         "pipeline without the full config")
    args = ap.parse_args()
    tdir = args.parse_dir or args.trace_dir
    if not args.parse_dir:
        capture(tdir, args.rounds, args.platform, args.smoke)
    # load the trace once — both views parse the same newest file, and a
    # full-shape XLA:CPU capture runs to GBs (minutes per gunzip+load)
    path = find_trace_file(tdir)
    events = load_trace_events(path) if path else None
    parse_top_ops(tdir, args.top, args.rounds, events=events)
    # the attribution view of the same trace: compute vs collective vs gap
    # and the named-scope split the run report renders
    attr = attribute(tdir, events=events)
    if attr and attr.get("device_present"):
        print(f"\n[trace] attribution: compute {attr['compute_ms']:.1f} ms"
              f" | collective {attr['collective_ms']:.1f} ms"
              f" ({100 * attr['collective_frac']:.1f}%)"
              f" | gap {attr['gap_ms']:.1f} ms")
        print(f"[trace] by scope: "
              f"{json.dumps(attr.get('by_scope_ms', {}))}")


if __name__ == "__main__":
    main()
