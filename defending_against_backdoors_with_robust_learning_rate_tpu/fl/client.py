"""Client local training — the reference's `Agent.local_train`
(src/agent.py:33-64) as a pure jittable function.

Reference semantics preserved:
- fresh SGD(momentum) state every round (src/agent.py:37; momentum buffer
  starts at zero — SURVEY.md 7.3.4);
- `local_ep` epochs, reshuffled each epoch (DataLoader shuffle=True,
  src/agent.py:28), last batch partial;
- per-minibatch global-grad-norm clip to 10 (src/agent.py:50);
- optional per-minibatch PGD projection of the cumulative update onto the
  L2 ball `clip` (src/agent.py:54-60, inside the batch loop — SURVEY.md 2.3.3);
- dropout active during local training;
- returns the flat update (final - initial); f32 here instead of the
  reference's f64 (SURVEY.md 2.3.2).

TPU-native shape discipline: the agent's shard is padded to `n_batches * bs`;
every agent runs an identical trace (`lax.scan` over epochs x batches). A
random shuffle sorts real samples in front of padding, so batch b's samples
are real iff their shuffled position < size; fully-padded batches are exact
no-ops (masked optimizer step). This function is `vmap`ped over the sampled
agents on one chip and `shard_map`ped over the `agents` mesh axis at scale.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp

from defending_against_backdoors_with_robust_learning_rate_tpu.fl import task
from defending_against_backdoors_with_robust_learning_rate_tpu.ops import (
    loops, tree)
from defending_against_backdoors_with_robust_learning_rate_tpu.ops.sgd import (
    clip_by_global_norm, pgd_project, sgd_momentum_step)


def make_local_train(model, cfg, normalize):
    """Returns local_train(params0, images, labels, size, key) ->
    (update pytree, per-client values: the mean epoch loss, or for a task
    with per-step sums a dict of it and them, fl/task.per_client).

    images: [n_total, H, W, C] raw pixels, n_total a multiple of cfg.bs;
    labels: [n_total] int32; size: scalar int32 true shard size; key: PRNGKey.
    The token task's `images` are [n_total, T + 1] ids and its `labels`
    unused; what the batch means is the task's (fl/task.make_batch_loss).

    RLR_ABLATE (measurement-only, comma-separated): in-program ablations for
    the round-anatomy ladder (scripts/profile_round.py --ablate) — a
    standalone micro-probe measures its own dispatch floor, not the sink's
    share of a round, so sinks are isolated by differencing FULL-round
    timings:
      noshuffle  — identity permutation (skips per-epoch uniform+argsort)
      nodropout  — deterministic forward (skips dropout RNG + masks)
      nogather   — ordered contiguous batches (skips the per-step row gather)
    Every ablation CHANGES TRAINING SEMANTICS; never set outside profiling.
    """
    bs = cfg.bs
    ablate = set(filter(None, os.environ.get("RLR_ABLATE", "").split(",")))
    if ablate:
        # loud on purpose: a leftover env var silently corrupts training
        print(f"[ABLATE] local training is running with {sorted(ablate)} "
              f"REMOVED — measurement mode, results are not real training",
              flush=True)
    batch_loss = task.make_batch_loss(model, cfg, normalize,
                                      deterministic="nodropout" in ablate)

    def _local_train(params0, images, labels, size, key, ep_budget):
        n_total = images.shape[0]
        nb = n_total // bs
        # policy for ops/loops.maybe_unrolled_scan (XLA:CPU conv-in-while
        # slow path): trace short local loops as Python loops on CPU,
        # capped at 16 fwd+bwd steps to keep trace/compile time sane
        py_loops = ((loops.cpu_backend() and cfg.local_ep * nb <= 16)
                    or loops.carry_is_large(params0, cfg.local_ep * nb))
        params0 = tree.astype(params0, jnp.float32)

        def epoch_body(carry, xs):
            ep_key, ep_idx = xs
            params, mom = carry
            # straggler truncation (faults/): epochs past the agent's budget
            # zero every batch weight, so the already-masked optimizer step
            # (and the loss accumulation) become exact no-ops. When the
            # budget is the static local_ep (no stragglers configured), XLA
            # constant-folds ep_active=True away — the dense path's program
            # is unchanged.
            ep_active = ep_idx < ep_budget
            shuffle_key, drop_key = jax.random.split(ep_key)
            if "noshuffle" in ablate:
                perm = jnp.arange(n_total)  # real samples already in front
            else:
                r = jax.random.uniform(shuffle_key, (n_total,))
                r = jnp.where(jnp.arange(n_total) < size, r, 2.0)
                perm = jnp.argsort(r)      # real samples first, shuffled

            def batch_body(carry, b):
                params, mom = carry
                idx = jax.lax.dynamic_slice(perm, (b * bs,), (bs,))
                if "nogather" in ablate:
                    # remove only the IMAGE row gather; labels still gather
                    # through perm so the shuffle stays live — otherwise XLA
                    # DCEs uniform+argsort along with the gather and the
                    # delta misattributes the shuffle's cost (code review r3)
                    x = jax.lax.dynamic_slice_in_dim(images, b * bs, bs, 0)
                else:
                    x = jnp.take(images, idx, axis=0)
                y = jnp.take(labels, idx, axis=0)
                w = ((b * bs + jnp.arange(bs)) < size) & ep_active

                def loss_fn(p):
                    return batch_loss(p, x, y, w,
                                      jax.random.fold_in(drop_key, b))

                (loss, sums), grads = jax.value_and_grad(
                    loss_fn, has_aux=True)(params)
                grads = clip_by_global_norm(grads, 10.0)
                w_n = jnp.sum(w)
                params, mom = sgd_momentum_step(
                    params, mom, grads, cfg.client_lr, cfg.client_moment,
                    w_n > 0)
                if cfg.clip > 0:
                    params = pgd_project(params, params0, cfg.clip)
                return (params, mom), (loss * w_n, w_n, sums)

            (params, mom), (loss_sums, w_sums, sums) = \
                loops.maybe_unrolled_scan(
                    batch_body, (params, mom), jnp.arange(nb), py_loops)
            # sample-weighted epoch loss: padding batches contribute nothing
            ep_loss = jnp.sum(loss_sums) / jnp.maximum(jnp.sum(w_sums), 1.0)
            return (params, mom), (ep_loss, tree.map(
                lambda a: jnp.sum(a, axis=0), sums))

        ep_keys = jax.random.split(key, cfg.local_ep)
        (params, _), (ep_losses, sums) = loops.maybe_unrolled_scan(
            epoch_body, (params0, tree.zeros_like(params0)),
            (ep_keys, jnp.arange(cfg.local_ep)), py_loops)
        update = tree.sub(params, params0)
        return update, task.per_client(
            jnp.mean(ep_losses),
            tree.map(lambda a: jnp.sum(a, axis=0), sums))

    # fl/rounds.vmap_agents maps a block's clients instead of batching them
    sequential = task.is_tokens(cfg)
    if cfg.straggler_rate > 0:
        # faults path: callers pass a per-agent epoch budget (6th arg)
        _local_train.sequential = sequential
        return _local_train

    def local_train(params0, images, labels, size, key):
        # dense path: the static full budget constant-folds to a no-op
        return _local_train(params0, images, labels, size, key,
                            jnp.int32(cfg.local_ep))

    local_train.sequential = sequential
    return local_train

