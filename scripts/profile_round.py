#!/usr/bin/env python
"""Component-level timing of one FL round on the bench config.

Answers "where does round time go" (VERDICT r1 #2) with direct measurement
instead of a trace viewer: times the full round fn, the vmapped local-train
sweep alone, the server step (aggregate+RLR+apply) alone, the eval fn, and a
forward-only variant of the client loss to split fwd vs bwd cost.

Usage: python scripts/profile_round.py [--platform cpu]
"""

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


REPS = 5


def timed(fn, *args, warmup=1):
    import jax
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(REPS):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / REPS


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--platform", default="")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny shapes + 1 rep: validates the script runs "
                         "end-to-end (timings meaningless)")
    ap.add_argument("--ablate", action="store_true",
                    help="in-program ablation ladder: re-times the FULL "
                         "round with shuffle / dropout / gather removed "
                         "one at a time (RLR_ABLATE) — standalone "
                         "micro-probes measure their own dispatch floor, "
                         "not a sink's share of the round")
    args = ap.parse_args()
    if args.smoke:
        global REPS
        REPS = 1

    import jax
    if args.platform:
        jax.config.update("jax_platforms", args.platform)
    import jax.numpy as jnp

    from defending_against_backdoors_with_robust_learning_rate_tpu.config import Config
    from defending_against_backdoors_with_robust_learning_rate_tpu.data.registry import (
        get_federated_data)
    from defending_against_backdoors_with_robust_learning_rate_tpu.fl.client import (
        make_local_train)
    from defending_against_backdoors_with_robust_learning_rate_tpu.fl.common import (
        make_normalizer, masked_ce)
    from defending_against_backdoors_with_robust_learning_rate_tpu.fl.evaluate import (
        make_eval_fn, pad_eval_set)
    from defending_against_backdoors_with_robust_learning_rate_tpu.fl.rounds import (
        make_round_fn)
    from defending_against_backdoors_with_robust_learning_rate_tpu.models.registry import (
        get_model, init_params)
    from defending_against_backdoors_with_robust_learning_rate_tpu.ops.aggregate import (
        aggregate_updates, apply_aggregate, robust_lr)

    # on CPU, shrink the dataset so local_ep*nb stays under the py-loop cap
    # (ops/loops.py): the full 60k config would run the 46-step scan on
    # XLA:CPU's slow conv-in-while path and never finish on a laptop-class
    # host; the TPU numbers are the ones that matter
    on_cpu = (args.platform == "cpu" or jax.default_backend() == "cpu")
    cfg = Config(data="fmnist", num_agents=10, local_ep=2, bs=256,
                 num_corrupt=1, poison_frac=0.5, robustLR_threshold=4,
                 synth_train_size=(6000 if on_cpu else 60000),
                 synth_val_size=(1000 if on_cpu else 10000), seed=0)
    if args.smoke:
        # force the synthetic fallback: the on-disk fmnist files have the
        # full 60k geometry regardless of synth_* settings
        cfg = cfg.replace(bs=32, synth_train_size=640, synth_val_size=128,
                          data_dir="/nonexistent_use_synthetic")
    if on_cpu:
        print("[profile] CPU backend: reduced shapes (6k train) — timings "
              "are not comparable to TPU rows", flush=True)
    fed = get_federated_data(cfg)
    model = get_model(cfg.data, cfg.model_arch, cfg.dtype)
    params = init_params(model, fed.train.images.shape[2:],
                         jax.random.PRNGKey(0))
    norm = make_normalizer(fed.mean, fed.std, fed.raw_is_normalized)
    imgs = jnp.asarray(fed.train.images)
    lbls = jnp.asarray(fed.train.labels)
    szs = jnp.asarray(fed.train.sizes)
    key = jax.random.PRNGKey(1)

    print(f"[profile] device={jax.devices()[0].device_kind} "
          f"({jax.default_backend()})", flush=True)

    # 0. dispatch floor: a trivial jitted op measures the fixed per-call
    # cost (host dispatch + device round trip); every standalone probe
    # below is bounded from below by this — only differences of FULL-round
    # timings (--ablate) see through it
    t_null = timed(jax.jit(lambda x: x + 1.0), jnp.zeros((8, 8)))
    print(f"dispatch floor (jitted x+1): {t_null*1e3:6.1f} ms", flush=True)

    # 1. full round
    round_fn = make_round_fn(cfg, model, norm, imgs, lbls, szs)
    t_round = timed(round_fn, params, key)
    print(f"full round:            {t_round*1e3:8.1f} ms", flush=True)

    if args.ablate:
        # in-program ablation ladder: each variant recompiles the whole
        # round with one component removed (fl/client.py RLR_ABLATE);
        # the timing DELTA vs the full round is that component's true
        # in-program cost (overlap caveat: removals can also change XLA's
        # fusion/overlap, so deltas are attributions, not exact splits)
        base = t_round
        print(f"\n[ablate] full round {base*1e3:.1f} ms; component costs "
              f"by removal:", flush=True)
        for tag in ("noshuffle", "nodropout", "nogather",
                    "noshuffle,nodropout,nogather"):
            os.environ["RLR_ABLATE"] = tag
            fn = make_round_fn(cfg, model, norm, imgs, lbls, szs)
            t = timed(fn, params, key)
            print(f"  -{tag:<30s} {t*1e3:8.1f} ms  "
                  f"(delta {1e3*(base-t):+7.1f} ms, "
                  f"{100*(base-t)/base:+5.1f}% of round)", flush=True)
        os.environ.pop("RLR_ABLATE", None)

    # 2. local training sweep alone (all agents, vmapped — no aggregation)
    local = make_local_train(model, cfg, norm)
    m = cfg.agents_per_round
    keys = jax.random.split(key, m)

    @jax.jit
    def sweep(params, keys):
        return jax.vmap(local, in_axes=(None, 0, 0, 0, 0))(
            params, imgs[:m], lbls[:m], szs[:m], keys)

    t_sweep = timed(sweep, params, keys)
    print(f"local-train sweep:     {t_sweep*1e3:8.1f} ms "
          f"({100*t_sweep/t_round:.0f}% of round)", flush=True)

    # 3. server step alone (RLR vote + weighted avg + apply) on real updates
    updates, _ = sweep(params, keys)
    updates = jax.block_until_ready(updates)

    @jax.jit
    def server(params, updates, szs, key):
        lr = robust_lr(updates, cfg.robustLR_threshold,
                       cfg.effective_server_lr)
        agg = aggregate_updates(updates, szs[:m], cfg, key)
        return apply_aggregate(params, lr, agg)

    t_server = timed(server, params, updates, szs, key)
    print(f"server step:           {t_server*1e3:8.1f} ms "
          f"({100*t_server/t_round:.0f}% of round)", flush=True)

    # 4. eval pass (val set, batched scan)
    eval_fn = make_eval_fn(model, norm, cfg.n_classes)
    val = tuple(map(jnp.asarray, pad_eval_set(
        fed.val_images, fed.val_labels, cfg.eval_bs)))
    t_eval = timed(eval_fn, params, *val)
    print(f"eval (10k val):        {t_eval*1e3:8.1f} ms "
          f"(runs every snap={cfg.snap} rounds)", flush=True)

    # 5. fwd vs fwd+bwd on one batch shape [m*bs, ...] (the effective
    # per-scan-step tensor after vmap)
    x = jnp.zeros((m * cfg.bs,) + fed.train.images.shape[2:], jnp.float32)
    y = jnp.zeros((m * cfg.bs,), jnp.int32)
    w = jnp.ones((m * cfg.bs,), bool)

    def loss_fn(p):
        logits = model.apply({"params": p}, norm(x), train=True,
                             rngs={"dropout": jax.random.PRNGKey(0)})
        return masked_ce(logits, y, w)

    def loss_fn_nodrop(p):
        logits = model.apply({"params": p}, norm(x), train=False)
        return masked_ce(logits, y, w)

    fwd = jax.jit(loss_fn)
    fwdbwd = jax.jit(jax.value_and_grad(loss_fn))
    fwdbwd_nd = jax.jit(jax.value_and_grad(loss_fn_nodrop))
    t_fwd = timed(fwd, params)
    t_fb = timed(fwdbwd, params)
    t_fb_nd = timed(fwdbwd_nd, params)
    n_steps = cfg.local_ep * (imgs.shape[1] // cfg.bs)
    print(f"one eff-batch[{m*cfg.bs}] fwd:     {t_fwd*1e3:8.1f} ms",
          flush=True)
    print(f"one eff-batch[{m*cfg.bs}] fwd+bwd: {t_fb*1e3:8.1f} ms "
          f"(x {n_steps} steps/round = {t_fb*n_steps*1e3:.0f} ms)",
          flush=True)
    print(f"  ... without dropout:  {t_fb_nd*1e3:8.1f} ms "
          f"(dropout RNG+mask cost {100*(t_fb-t_fb_nd)/max(t_fb,1e-12):.0f}% "
          f"of step)", flush=True)

    # 6. per-epoch shuffle cost (fl/client.py: uniform + argsort per agent
    #    per epoch) — VERDICT r2 candidate sink
    n_total = imgs.shape[1]

    @jax.jit
    def shuffles(key):
        ks = jax.random.split(key, m * cfg.local_ep)
        return jax.vmap(
            lambda k: jnp.argsort(jax.random.uniform(k, (n_total,))))(ks)

    t_shuf = timed(shuffles, key)
    print(f"shuffles ({m}x{cfg.local_ep} argsort[{n_total}]): "
          f"{t_shuf*1e3:8.1f} ms/round", flush=True)

    # 7. per-step batch gather (dynamic_slice of perm + row gather from the
    #    agent's padded shard)
    perm_all = shuffles(key)[:m]

    @jax.jit
    def gathers(perm_all):
        idx = jax.lax.dynamic_slice_in_dim(perm_all, 0, cfg.bs, axis=1)
        return jax.vmap(lambda im, ix: jnp.take(im, ix, axis=0))(
            imgs[:m], idx)

    t_gather = timed(gathers, perm_all)
    print(f"batch gather [{m}x{cfg.bs}]:  {t_gather*1e3:8.1f} ms "
          f"(x {n_steps} steps/round = {t_gather*n_steps*1e3:.0f} ms)",
          flush=True)

    # --- top-sinks summary, dispatch-floor-corrected: every standalone
    # probe pays t_null of fixed per-call overhead that does NOT exist
    # inside the fused round program, so subtract it before extrapolating.
    # Floor-dominated probes (t - t_null ~ 0) are reported as upper bounds;
    # the --ablate ladder is the authoritative in-program decomposition.
    def net(t):
        return max(t - t_null, 0.0)

    accounted = (net(t_fb) + net(t_gather)) * n_steps + net(t_shuf)
    print(f"\n[summary] round anatomy (floor-corrected, -{t_null*1e3:.1f} ms "
          f"per probe; see --ablate for the in-program ladder):", flush=True)
    rows = [
        ("fwd+bwd compute", net(t_fb) * n_steps),
        ("batch gathers", net(t_gather) * n_steps),
        ("epoch shuffles", net(t_shuf)),
        ("server step", net(t_server)),
        ("residual (scan/loop overhead, optimizer, clip)",
         max(net(t_round) - accounted - net(t_server), 0.0)),
    ]
    for name, t in sorted(rows, key=lambda r: -r[1]):
        print(f"  {name:<46s} {t*1e3:8.1f} ms  "
              f"({100*t/t_round:5.1f}% of round)", flush=True)

    # --- FLOPs / MFU from XLA's cost analysis (same math as bench.py)
    try:
        from bench import peak_tflops, train_step_flops
        step_flops = train_step_flops(model, params, norm, cfg,
                                      fed.train.images.shape[2:])
        flops_round = cfg.agents_per_round * cfg.local_ep * \
            (imgs.shape[1] // cfg.bs) * step_flops
        peak = peak_tflops(jax.devices()[0].device_kind)
        tfs = flops_round / t_round / 1e12
        print(f"\n[mfu] {flops_round/1e12:.2f} TFLOP/round -> "
              f"{tfs:.1f} TFLOP/s"
              + (f" = {100*tfs/peak:.1f}% MFU of {peak:.0f} TFLOP/s bf16 "
                 f"peak" if peak else ""), flush=True)
    except Exception as e:
        print(f"[mfu] cost analysis unavailable: {e}", flush=True)


if __name__ == "__main__":
    main()
