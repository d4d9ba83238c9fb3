"""The token task's training check: the round the warm-up ran, against the
plain reference's round from the same initial parameters on the same shards.

`c2_token_eval` runs the reference forward on parameters the engine trained;
this one trains. The reference (`reference/lfm2_moe.py`: float32, products at
the highest precision, every held expert applied to every token, no
recompute) runs every client of the round: from the initial parameters, per
epoch the gradient of the batch's mean next-token loss, a sequence at a time,
then `sgd_step` (clip at 10, momentum from zero). Each client's update is
sampled on `check.round_sample` seeded coordinates of every leaf (all of a
smaller one) and the samples go to the host, where `reference/server_step.py`
(NumPy float64: `compute_robustLR` + FedAvg) folds them. Compared with what
the engine's timed `round` program produced, its `Train/Loss` row and the
change of its parameters on the same coordinates:

- `train_loss`: relative deviation of the mean, over clients and epochs, of
  the batch loss;
- `update_rel_err`: the norm of (|engine's change| - |reference's change|)
  over the norm of the reference's change, each leaf's samples weighted by
  the coordinates they stand for. A state left unchanged reads 1. The vote
  only sets a coordinate's sign (the server's rate is +-1 times the mean), so
  magnitudes compare the clients' gradients, optimiser steps and the fold's
  mean without the vote's discontinuity;
- `vote_flipped_share`: the share of coordinates whose change has another
  sign than the reference's vote gives it. A client's update near zero at a
  coordinate takes either sign under rounding, and one flipped sign moves a
  coordinate across the threshold, so this is small and not zero.

The harness keeps neither the initial parameters nor the shards: both are
made again from the run's seed by the engine's own initialiser and
generator, which is what the engine did. On the device beside the engine's parameters: the reference's
parameters, momentum and the batch's gradient (three trees, 6.1 GB at
`lfm2-8b-a1b-ep4`) and one sequence's forward and backward (3.3 GB of
temporaries in XLA's analysis for a v5e). Seconds on the
v5e: in PERF.md section 6 (PR 27)."""

from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference import server_step as ref_server

PHASE = "model"
TRAIN_LOSS = "Train/Loss"


def sample_index(shapes, n: int, seed: int):
    """Per leaf: flat coordinates sampled (all of a leaf of at most `n`),
    and how many coordinates of the leaf each sample stands for."""
    rng = np.random.default_rng([int(seed), 0xC2])
    idx, weight = [], []
    for shape in shapes:
        size = int(np.prod(shape))
        take = (np.arange(size) if size <= n
                else np.sort(rng.integers(0, size, n)))
        idx.append(take.astype(np.int32))
        weight.append(np.full(len(take), size / len(take)))
    return idx, np.concatenate(weight)


def make_take(idx):
    idx = [jnp.asarray(i) for i in idx]

    @jax.jit
    def take(tree):
        return jnp.concatenate([
            x.reshape(-1)[i] for x, i in
            zip(jax.tree_util.tree_leaves(tree), idx, strict=True)])
    return take


def make_steps(ref, dims, cfg):
    """The two programs of the reference's client (two, because compiling
    one is most of this check's seconds on a cold cache): a sequence's
    share of the batch gradient added to the shares before it, and the
    optimiser's step; accumulator, parameters and momentum in place. A
    batch starts from a zero accumulator and a client from a zero buffer."""
    def add_grad(p, acc, row, share):
        loss, g = ref.loss_and_grads(p, row[None], dims)
        return loss, jax.tree_util.tree_map(lambda a, x: a + share * x,
                                            acc, g)

    def step(p, buf, g):
        return ref.sgd_step(p, buf, g, cfg.client_lr, cfg.client_moment)
    return (jax.jit(add_grad, donate_argnums=1),
            jax.jit(step, donate_argnums=(0, 1)))


def reference_round(ref, dims, initial, shards, cfg, take):
    """(sampled updates [m, n] float32, losses [m, epochs], the initial
    parameters' samples) of the reference's clients, one after another.
    `initial()` gives a fresh copy of the round's parameters; `shards` [m,
    sequences, T + 1] are whole batches (`contract`)."""
    add_grad, step = make_steps(ref, dims, cfg)
    zeros = jax.jit(lambda t: jax.tree_util.tree_map(jnp.zeros_like, t))
    base = np.asarray(jax.device_get(take(initial())))
    updates, losses = [], []
    for shard in shards:
        p, ep_losses = initial(), []
        buf = zeros(p)
        share = 1.0 / len(shard)
        for _ep in range(cfg.local_ep):
            seq, g = [], zeros(p)
            for row in shard:
                loss, g = add_grad(p, g, row, share)
                seq.append(loss)
            p, buf = step(p, buf, g)
            del g
            ep_losses.append(float(np.mean(jax.device_get(seq))))
        updates.append(np.asarray(jax.device_get(take(p))) - base)
        losses.append(ep_losses)
        del p, buf
    return np.stack(updates), np.asarray(losses), base


def run(ctx) -> Dict[str, Any]:
    from defending_against_backdoors_with_robust_learning_rate_tpu.data.registry import (
        get_federated_data)
    from defending_against_backdoors_with_robust_learning_rate_tpu.models.registry import (
        get_model, init_params)
    config, ref, eng = ctx["config"], ctx["reference"], ctx["eng"]
    cfg, tol, dims = eng.cfg, config["check"], ref.dims_of(config)
    train = get_federated_data(cfg).train       # the seed's shards, again
    shards, sizes = np.asarray(train.images), np.asarray(train.sizes)
    model = get_model(cfg.data, cfg.model_arch, cfg.dtype, remat=cfg.remat,
                      remat_policy=cfg.remat_policy, cfg=cfg)

    # one program, the engine's own with the key as its argument
    draw = jax.jit(lambda key: init_params(model, (cfg.seq_len,), key))

    def initial():
        return draw(jax.random.PRNGKey(cfg.seed))

    leaves = jax.tree_util.tree_leaves(ctx["params"])
    idx, weight = sample_index([x.shape for x in leaves],
                               int(tol["round_sample"]), cfg.seed)
    take = make_take(idx)
    updates, losses, base = reference_round(ref, dims, initial, shards, cfg,
                                            take)
    threshold = float(cfg.robustLR_threshold)
    _lr, new_ref = ref_server.server_step(
        base, updates, sizes, threshold, float(cfg.effective_server_lr))
    want = new_ref - base.astype(np.float64)
    got = (np.asarray(jax.device_get(take(ctx["params"])), np.float64)
           - base.astype(np.float64))
    scale = float(np.sqrt(np.sum(weight * want * want)))
    rel_err = float(np.sqrt(np.sum(
        weight * (np.abs(got) - np.abs(want)) ** 2))) / max(scale, 1e-30)
    signed = float(np.sqrt(np.sum(weight * (got - want) ** 2))) / max(
        scale, 1e-30)
    voted = want != 0
    flipped = float(np.sum(weight * (voted & (np.sign(got) != np.sign(want))))
                    / max(np.sum(weight * voted), 1.0))
    loss_ref = float(np.mean(losses))
    loss_eng = ctx["rows"].get(TRAIN_LOSS, float("nan"))
    loss_dev = abs(loss_eng - loss_ref) / max(abs(loss_eng), abs(loss_ref),
                                              1e-30)
    compared = {
        "train_loss": [loss_dev, float(tol["train_loss_rtol"])],
        "update_rel_err": [rel_err, float(tol["update_rel_err"])],
        "vote_flipped_share": [flipped, float(tol["vote_flipped_share"])]}
    ok = all(bool(np.isfinite(v)) and v <= lim
             for v, lim in compared.values())
    return {"ok": ok, "clients": len(shards), "epochs": int(cfg.local_ep),
            "coordinates_compared": int(len(weight)),
            "coordinates_stood_for": float(np.sum(weight)),
            "engine": {TRAIN_LOSS: loss_eng}, "reference": {
                TRAIN_LOSS: loss_ref,
                "client_losses": [[float(x) for x in row] for row in losses],
                "update_norm": scale},
            "deviation": {"train_loss": loss_dev, "update_rel_err": rel_err,
                          "update_rel_err_signed": signed,
                          "vote_flipped_share": flipped},
            "compared": compared}


def contract(cfg, config) -> None:
    """The round the reference transcribes: every client, each epoch one
    whole batch (so no shuffle enters), the plain optimiser, the sign vote
    and FedAvg, one round before the boundary."""
    from defending_against_backdoors_with_robust_learning_rate_tpu.attack import (
        registry as attack_registry)
    assert cfg.data == "tokens" and cfg.snap == 1
    assert cfg.agents_per_round == cfg.num_agents == config["agents"]
    assert cfg.synth_train_size == cfg.num_agents * cfg.bs
    assert cfg.aggr == "avg" and cfg.noise == 0 and cfg.clip == 0
    assert not attack_registry.in_jit(cfg)
    assert not (cfg.faults_enabled or cfg.churn_enabled
                or cfg.traffic_enabled)
    assert (cfg.local_ep * cfg.synth_train_size * cfg.seq_len
            == config["examples_per_round"])
    for key in ("round_sample", "train_loss_rtol", "update_rel_err",
                "vote_flipped_share"):
        assert config["check"][key] > 0, key
    assert config["check"]["update_rel_err"] < 1    # an unchanged state
