"""The token task's data, all from the seed: packed documents with
per-client vocabulary skew, and the trigger n-gram -> target token backdoor.

A client's shard is `[n_seq, seq_len + 1]` token ids (inputs are the first
`seq_len`, targets the last `seq_len`). Documents have log-normal lengths
(median `DOC_MEDIAN`, capped at `seq_len`), are packed back to back with a
separator id between them, and attention is causal across the pack. Ids
come from the held vocabulary slice by a Zipf law over ranks; each client
ranks the ids by a permutation of its own, so clients disagree on which
ids are frequent (non-IID). Validation sequence j is drawn under client
`j % K`'s ranking.

The backdoor (`attack/` stamps pixels; this is its token twin): a corrupt
client overwrites `TRIGGERS_PER_SEQ` seeded, non-overlapping places of
`poison_frac` of its sequences with a fixed three-token trigger followed
by the target token `cfg.target_class`. The poisoned validation set carries
the trigger in every sequence, and its mask marks the positions whose next
token is the target: poison accuracy is the share of those whose arg-max
is the target.

They ride `FederatedData`'s fields: `images` are the id rows, train
`labels` are unused zeros, eval `labels` are `[n, seq_len]` masks of the
positions an eval counts."""

from __future__ import annotations

import numpy as np

SEPARATOR = 0
DOC_MEDIAN, DOC_SIGMA = 256.0, 1.0
ZIPF_EXPONENT = 1.1
TRIGGERS_PER_SEQ = 16
TRIGGER_LEN = 3


def trigger_ids(vocab: int) -> np.ndarray:
    """The fixed trigger: the last three ids of the held slice."""
    return np.arange(vocab - TRIGGER_LEN, vocab, dtype=np.int32)


def _zipf_probs(vocab: int) -> np.ndarray:
    p = 1.0 / np.arange(1, vocab, dtype=np.float64) ** ZIPF_EXPONENT
    return p / p.sum()


def _pack(rng, n_seq: int, seq_len: int, ranking: np.ndarray,
          probs: np.ndarray) -> np.ndarray:
    """[n_seq, seq_len + 1] ids: documents back to back, a separator after
    each, drawn by rank from `probs` and named through `ranking`."""
    total = n_seq * (seq_len + 1)
    ids = ranking[rng.choice(len(probs), size=total, p=probs)]
    lens = np.minimum(
        np.maximum(1, rng.lognormal(np.log(DOC_MEDIAN), DOC_SIGMA,
                                    size=max(8, total // 16)).astype(int)),
        seq_len)
    ends = np.cumsum(lens + 1) - 1
    ids[ends[ends < total]] = SEPARATOR
    return ids.reshape(n_seq, seq_len + 1).astype(np.int32)


def stamp(rng, rows: np.ndarray, vocab: int, target: int):
    """Write the trigger and the target into each row at seeded places;
    returns (rows, mask [n, seq_len] of the positions that predict the
    target)."""
    rows = rows.copy()
    n, width = rows.shape
    span = TRIGGER_LEN + 1
    slots = width // span
    k = min(TRIGGERS_PER_SEQ, slots)
    mask = np.zeros((n, width - 1), np.uint8)
    trig = trigger_ids(vocab)
    for r in range(n):
        for s in rng.choice(slots, size=k, replace=False):
            p = int(s) * span
            rows[r, p:p + TRIGGER_LEN] = trig
            rows[r, p + TRIGGER_LEN] = target
            mask[r, p + TRIGGER_LEN - 1] = 1
    return rows, mask


def get_federated_tokens(cfg):
    """The token task's `FederatedData`; ids are drawn from the rows the
    configured model holds (models/registry.token_vocab)."""
    from defending_against_backdoors_with_robust_learning_rate_tpu.data.arrays import (
        AgentShards)
    from defending_against_backdoors_with_robust_learning_rate_tpu.data.registry import (
        FederatedData)
    from defending_against_backdoors_with_robust_learning_rate_tpu.obs import (
        spans)

    from defending_against_backdoors_with_robust_learning_rate_tpu.models.registry import (
        token_vocab)
    vocab = token_vocab(cfg)
    K, T = cfg.num_agents, cfg.seq_len
    if cfg.synth_train_size % K or (cfg.synth_train_size // K) % cfg.bs:
        raise ValueError(
            f"--synth_train_size {cfg.synth_train_size} sequences do not "
            f"deal into {K} clients in whole batches of --bs {cfg.bs}")
    if vocab <= TRIGGER_LEN + 2 or not 0 < cfg.target_class < vocab - 3:
        raise ValueError(
            f"a vocabulary of {vocab} rows cannot hold the separator, the "
            f"trigger and the target token {cfg.target_class}")
    per = cfg.synth_train_size // K
    with spans.span("setup/task"):
        rng = np.random.default_rng([int(cfg.seed), 0x70C5])
        probs = _zipf_probs(vocab)
        rankings = [1 + rng.permutation(vocab - 1) for _ in range(K)]
        train = np.stack([_pack(rng, per, T, rankings[a], probs)
                          for a in range(K)])
        pmask = np.zeros((K, per), bool)
        for a in range(min(cfg.num_corrupt, K)):
            n_poison = int(np.floor(cfg.poison_frac * per))
            rows = rng.choice(per, size=n_poison, replace=False)
            train[a, rows], _ = stamp(rng, train[a, rows], vocab,
                                      cfg.target_class)
            pmask[a, rows] = True
        val = np.concatenate([
            _pack(rng, 1, T, rankings[j % K], probs)
            for j in range(cfg.synth_val_size)])
        pval, pmask_val = stamp(rng, val, vocab, cfg.target_class)
    shards = AgentShards(train, np.zeros((K, per), np.int32),
                         np.full((K,), per, np.int32), poison_mask=pmask)
    zero = np.zeros((1,), np.float32)
    return FederatedData(
        train=shards, val_images=val,
        val_labels=np.ones((len(val), T), np.uint8),
        pval_images=pval, pval_labels=pmask_val,
        mean=zero, std=zero + 1.0, raw_is_normalized=True, synthetic=True)
