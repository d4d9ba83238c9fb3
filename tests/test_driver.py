"""Driver-level tests of train.run: the round loop, eval, and the
host-sampled + mesh path added in round 2 (VERDICT r1 #5)."""

import jax
import numpy as np
import pytest

from defending_against_backdoors_with_robust_learning_rate_tpu import train
from defending_against_backdoors_with_robust_learning_rate_tpu.config import Config
from defending_against_backdoors_with_robust_learning_rate_tpu.utils.metrics import (
    NullWriter)

BASE = Config(data="synthetic", num_agents=8, bs=16, local_ep=1,
              synth_train_size=256, synth_val_size=64, eval_bs=64,
              rounds=4, snap=2, seed=5, tensorboard=False)


def _run(cfg):
    return train.run(cfg, writer=NullWriter())


def test_driver_device_resident():
    summary = _run(BASE)
    assert summary["round"] == 4
    assert np.isfinite(summary["val_acc"])
    assert 0.0 <= summary["val_acc"] <= 1.0
    assert 0.0 <= summary["poison_acc"] <= 1.0


def test_driver_host_mode_single_device(monkeypatch):
    monkeypatch.setattr(train, "DEVICE_RESIDENT_BYTES", 0)
    summary = _run(BASE)
    assert summary["round"] == 4 and np.isfinite(summary["val_acc"])


def test_driver_host_mode_sharded_matches_single(monkeypatch, capsys):
    """--data=fedemnist-scale + --mesh>1: host-gathered shards partitioned
    over the agents mesh must reproduce the single-device host path."""
    monkeypatch.setattr(train, "DEVICE_RESIDENT_BYTES", 0)
    s1 = _run(BASE)
    s2 = _run(BASE.replace(mesh=0))   # 0 = all (8 faked CPU) devices
    # guard against vacuous parity: the second run must actually shard
    assert "host-sampled shards" in capsys.readouterr().out
    assert s2["round"] == s1["round"]
    np.testing.assert_allclose(s2["val_acc"], s1["val_acc"], atol=1e-4)
    np.testing.assert_allclose(s2["val_loss"], s1["val_loss"],
                               atol=1e-4, rtol=1e-4)


def test_driver_host_mode_prefetch_parity(monkeypatch, capsys):
    """The host->device prefetch pipeline (data/prefetch.py) only moves the
    gather off the critical path — results must equal the synchronous host
    gather exactly (same sampling sequence, same device arrays)."""
    monkeypatch.setattr(train, "DEVICE_RESIDENT_BYTES", 0)
    sync = _run(BASE.replace(host_prefetch=0))
    pre = _run(BASE)  # default: depth-2 prefetch
    assert "[prefetch] host->device pipeline" in capsys.readouterr().out
    assert pre["round"] == sync["round"]
    assert pre["val_acc"] == sync["val_acc"]
    assert pre["val_loss"] == sync["val_loss"]
    assert pre["poison_acc"] == sync["poison_acc"]


def test_round_prefetcher_order_and_errors():
    from defending_against_backdoors_with_robust_learning_rate_tpu.data.prefetch import (
        RoundPrefetcher)

    seen = []

    def produce(r):
        seen.append(r)
        return r * 10

    pf = RoundPrefetcher(produce, range(3, 8), depth=2)
    assert [pf.get(r) for r in range(3, 8)] == [30, 40, 50, 60, 70]
    # exhausted: asking past the constructed range raises, not hangs
    with pytest.raises(RuntimeError, match="exhausted"):
        pf.get(8)
    pf.close()
    assert seen == list(range(3, 8))

    def boom(r):
        if r == 2:
            raise ValueError("producer died")
        return r

    pf = RoundPrefetcher(boom, range(1, 5), depth=2)
    assert pf.get(1) == 1
    with pytest.raises(RuntimeError, match="prefetch worker failed"):
        pf.get(2)
    pf.close()


def test_round_prefetcher_error_while_queue_full():
    """Producer death with a full queue must still surface the error: the
    sentinel retries until a slot frees instead of being dropped (a dropped
    sentinel would turn the consumer's next get() into a permanent hang)."""
    import time

    from defending_against_backdoors_with_robust_learning_rate_tpu.data.prefetch import (
        RoundPrefetcher)

    def boom(r):
        if r == 2:
            raise ValueError("producer died")
        return r

    pf = RoundPrefetcher(boom, range(1, 5), depth=1)
    time.sleep(1.0)  # worker fills the 1-slot queue, then hits the error
    assert pf.get(1) == 1
    with pytest.raises(RuntimeError, match="prefetch worker failed"):
        pf.get(2)
    pf.close()


def test_driver_agent_chunk_parity():
    """--agent_chunk trades round latency for peak activation HBM; agents
    train independently, so chunked results must match the full vmap."""
    full = _run(BASE)
    chunked = _run(BASE.replace(agent_chunk=2))
    assert chunked["round"] == full["round"]
    np.testing.assert_allclose(chunked["val_acc"], full["val_acc"],
                               atol=1e-4)
    np.testing.assert_allclose(chunked["val_loss"], full["val_loss"],
                               atol=1e-4, rtol=1e-4)


def test_driver_agent_chunk_parity_sharded():
    """Chunking applies per-device on the mesh path (2 agents/device on the
    8-device mesh, chunk=1 -> 2 sequential chunks per device)."""
    cfg = BASE.replace(num_agents=16, synth_train_size=512)
    full = _run(cfg.replace(mesh=0))
    chunked = _run(cfg.replace(mesh=0, agent_chunk=1))
    np.testing.assert_allclose(chunked["val_acc"], full["val_acc"],
                               atol=1e-4)
    np.testing.assert_allclose(chunked["val_loss"], full["val_loss"],
                               atol=1e-4, rtol=1e-4)


def test_driver_256_agent_krum_on_mesh():
    """BASELINE configs[4] shape scaled to CI: 256 agents (32/device on the
    faked 8-device mesh), 10% corrupt, krum aggregation via the
    param-sharded all_to_all path."""
    cfg = BASE.replace(num_agents=256, bs=8, synth_train_size=8192,
                       synth_val_size=128, rounds=2, snap=2, mesh=0,
                       aggr="krum", num_corrupt=26, poison_frac=1.0)
    summary = _run(cfg)
    assert summary["round"] == 2
    assert np.isfinite(summary["val_acc"])


def test_partitioner_too_small_dataset_raises():
    from defending_against_backdoors_with_robust_learning_rate_tpu.data.partition import (
        distribute_data)
    labels = np.arange(10).repeat(10)   # 100 samples
    with pytest.raises(ValueError, match="dataset too small"):
        distribute_data(labels, num_agents=256)


def test_driver_mesh_device_resident_with_rlr():
    summary = _run(BASE.replace(mesh=0, num_corrupt=2, poison_frac=1.0,
                                robustLR_threshold=4))
    assert summary["round"] == 4 and np.isfinite(summary["val_acc"])


def test_driver_reports_steady_throughput():
    """steady_rounds_per_sec: window opens at the first snap boundary and
    closes at the last one, so a final partial segment's fresh round_fn
    compile is excluded (VERDICT r1 #9). Since the AOT bank
    (utils/compile_cache.py) moved program compiles out of the timed loop
    entirely — pre-loop on cold runs, skipped on warm — steady and
    wall-clock rates now only differ by boundary effects, so the old
    steady >= wall-clock invariant no longer holds; both must simply be
    present, positive and finite."""
    # rounds=5, snap=2: boundaries at 2 and 4; round 5 is a partial tail
    # (summary["round"] records the last EVALUATED round, i.e. 4)
    cfg = BASE.replace(rounds=5, snap=2, chain=2)
    summary = _run(cfg)
    assert summary["round"] == 4
    assert "steady_rounds_per_sec" in summary
    assert np.isfinite(summary["steady_rounds_per_sec"])
    assert summary["steady_rounds_per_sec"] > 0
    assert summary["rounds_per_sec"] > 0


def test_driver_rng_impl_rbg():
    """--rng_impl=rbg (the TPU hardware-RNG lever; forced here on CPU via
    XLA's RngBitGenerator) trains end-to-end; the impl is restored to the
    default afterwards so the rest of the suite keeps threefry streams."""
    try:
        summary = _run(BASE.replace(rng_impl="rbg", num_corrupt=1,
                                    poison_frac=1.0, robustLR_threshold=3))
        assert summary["round"] == 4 and np.isfinite(summary["val_acc"])
    finally:
        jax.config.update("jax_default_prng_impl", "threefry2x32")


def test_driver_host_chain_with_diagnostics(monkeypatch, capsys):
    """diagnostics + host-sampled + --chain: the dispatch schedule must keep
    every snap round unchained (it needs prev_params + the diag-compiled
    variant) while chaining the off-snap budget, all through the unit
    prefetcher. snap=3 with chain=2 so chaining actually engages (snap=2
    would clamp chain_n to snap-1 = 1 under diagnostics and test nothing —
    code review r3); the [chain] banner is asserted to keep it that way."""
    monkeypatch.setattr(train, "DEVICE_RESIDENT_BYTES", 0)
    cfg = BASE.replace(rounds=6, snap=3, chain=2, diagnostics=True,
                       num_corrupt=1, poison_frac=1.0, robustLR_threshold=3)
    summary = _run(cfg)
    out = capsys.readouterr().out
    assert "[chain] 2 rounds per compiled dispatch" in out, out
    assert summary["round"] == 6 and np.isfinite(summary["val_acc"])
