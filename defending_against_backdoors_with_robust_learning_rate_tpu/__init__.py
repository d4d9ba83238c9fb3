"""TPU-native federated-learning simulator with robust-learning-rate backdoor defense.

A brand-new JAX/XLA/Flax framework with the capabilities of the reference
`TinfoilHat0/Defending-Against-Backdoors-with-Robust-Learning-Rate` (AAAI 2021),
re-designed TPU-first:

- agents are a real parallel axis (``jax.vmap`` on one chip, ``shard_map`` over a
  ``jax.sharding.Mesh`` axis named ``"agents"`` on a slice/pod) instead of the
  reference's sequential Python loop (reference: src/federated.py:68-72);
- aggregation rules (FedAvg / coordinate-median / sign-majority / krum) and the
  robust-learning-rate defense are XLA collectives (``psum`` / ``all_gather``)
  over ICI (reference: src/aggregation.py:48-75 operates on an in-process dict);
- trojan-pattern backdoor injection, including the Distributed Backdoor Attack
  partitioning, is a jit-compiled device-side data transform driven by
  precomputed stamp masks (reference: src/utils.py:160-284 mutates stored
  dataset pixels with Python loops);
- models are Flax modules (reference: src/models.py);
- everything is deterministic under explicit ``jax.random`` keys (the reference
  is unseeded, SURVEY.md section 2.3.12).

Package layout::

    config.py   flag-parity CLI -> frozen dataclass config
    data/       dataset registry, label-sorted partitioner, padded agent stacks
    attack/     trojan pattern mask library + poisoning
    models/     Flax CNN_MNIST / CNN_CIFAR / ResNet-9
    ops/        numeric building blocks (sgd, clipping, aggregation rules)
    fl/         client local training, server aggregation, round step, eval
    faults/     fault injection: dropout/straggler/corrupt-payload sampling
                + the participation-mask aggregation protocol
    parallel/   mesh construction + shard_map round step
    utils/      metrics writers, checkpointing, misc
"""

__version__ = "0.1.0"

from defending_against_backdoors_with_robust_learning_rate_tpu.config import (  # noqa: F401
    Config,
    args_parser,
)
