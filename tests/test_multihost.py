"""REAL multi-process SPMD: two OS processes, each with 4 faked CPU
devices, rendezvoused via jax.distributed into one 8-device global
`agents` mesh (parallel/multihost.py).

Round 1 shipped the multi-host code paths (hybrid mesh, put_replicated,
lead gating) exercised only single-process; the ADVICE r1 medium finding
(process_is_granule) was fixed without ever running >1 process. This test
actually runs the rendezvous + global-mesh training end-to-end the way a
v5e pod job would, just with CPU devices and DCN = localhost TCP.
"""

import json
import os
import socket
import subprocess
import sys

import numpy as np
import pytest

DRIVER = r"""
import json, sys
import jax
jax.config.update("jax_platforms", "cpu")
coordinator, n_proc, pid, ckpt_dir = (
    sys.argv[1], int(sys.argv[2]), int(sys.argv[3]), sys.argv[4])
from defending_against_backdoors_with_robust_learning_rate_tpu.parallel import (
    multihost)
multihost.maybe_initialize(coordinator, n_proc, pid)
assert jax.process_count() == n_proc, jax.process_count()
assert jax.device_count() == 4 * n_proc
from defending_against_backdoors_with_robust_learning_rate_tpu import train
from defending_against_backdoors_with_robust_learning_rate_tpu.config import Config
from defending_against_backdoors_with_robust_learning_rate_tpu.utils.metrics import (
    NullWriter)
cfg = Config(data="synthetic", num_agents=8, bs=16, local_ep=1,
             synth_train_size=256, synth_val_size=64, eval_bs=64,
             rounds=2, snap=2, seed=5, mesh=0, chain=2,
             num_corrupt=1, poison_frac=1.0, robustLR_threshold=3,
             checkpoint_dir=ckpt_dir, tensorboard=False)
summary = train.run(cfg, writer=NullWriter())
print("SUMMARY" + str(pid) + "=" + json.dumps(
    {k: v for k, v in summary.items() if isinstance(v, (int, float))}),
    flush=True)
# resume from the round-2 checkpoint and train 2 more rounds — the
# multi-process restore + put_replicated + save barrier path
summary2 = train.run(cfg.replace(rounds=4, resume=True),
                     writer=NullWriter())
print("RESUMED" + str(pid) + "=" + json.dumps(
    {k: v for k, v in summary2.items() if isinstance(v, (int, float))}),
    flush=True)
"""


HOST_DRIVER = r"""
import json, sys
import jax
jax.config.update("jax_platforms", "cpu")
coordinator, n_proc, pid = sys.argv[1], int(sys.argv[2]), int(sys.argv[3])
from defending_against_backdoors_with_robust_learning_rate_tpu.parallel import (
    multihost)
multihost.maybe_initialize(coordinator, n_proc, pid)
from defending_against_backdoors_with_robust_learning_rate_tpu import train
from defending_against_backdoors_with_robust_learning_rate_tpu.config import Config
from defending_against_backdoors_with_robust_learning_rate_tpu.utils.metrics import (
    NullWriter)
# host-sampled + global mesh: every process gathers the identical seeded
# stacks and contributes only its addressable shards; chain=2 makes the
# dispatch a chained [2, m, ...] block through
# multihost.take_agents_sharded_block (r3); prefetch pipeline on
cfg = Config(data="synthetic", num_agents=8, bs=16, local_ep=1,
             synth_train_size=256, synth_val_size=64, eval_bs=64,
             rounds=2, snap=2, seed=5, mesh=0, chain=2,
             num_corrupt=1, poison_frac=1.0, robustLR_threshold=3,
             host_sampled="on", tensorboard=False)
summary = train.run(cfg, writer=NullWriter())
print("SUMMARY" + str(pid) + "=" + json.dumps(
    {k: v for k, v in summary.items() if isinstance(v, (int, float))}),
    flush=True)
"""


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


@pytest.mark.slow  # the pinned jax's XLA:CPU cannot run cross-process
# collectives ("Multiprocess computations aren't implemented on the CPU
# backend") — needs a real multi-host TPU/GPU backend
def test_two_process_host_sampled_trains():
    """Multi-process host-sampled mode: the fedemnist-scale gather path
    distributed over a 2-process global mesh (train.py host_mode branch,
    take_agents_sharded), with the prefetch pipeline on."""
    port = _free_port()
    coord = f"127.0.0.1:{port}"
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env.pop("JAX_PLATFORMS", None)
    procs = [subprocess.Popen(
        [sys.executable, "-c", HOST_DRIVER, coord, "2", str(pid)],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True, env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
        for pid in (0, 1)]
    outs = []
    try:
        for p in procs:
            out, err = p.communicate(timeout=420)
            outs.append((p.returncode, out, err))
    except subprocess.TimeoutExpired:
        for p in procs:
            p.kill()
        pytest.fail("multi-process host-sampled run timed out")

    for rc, out, err in outs:
        assert rc == 0, f"rc={rc}\nstdout:\n{out}\nstderr:\n{err[-3000:]}"
        assert "host-sampled shards, 2 processes" in out, out
        assert "[prefetch] host->device pipeline" in out, out
        # chained host-sampled blocks over the 2-process global mesh (r3)
        assert ("[chain] 2 rounds per compiled dispatch (lax.scan, "
                "host-sampled blocks)") in out, out
        # the redundant-work warning must NOT fire: this IS a distributed job
        assert "training REDUNDANTLY" not in out, out

    summaries = {}
    for pid, (_rc, out, _err) in enumerate(outs):
        for line in out.splitlines():
            if line.startswith(f"SUMMARY{pid}="):
                summaries[pid] = json.loads(line.split("=", 1)[1])
    assert set(summaries) == {0, 1}, summaries
    assert summaries[0]["round"] == summaries[1]["round"] == 2
    np.testing.assert_allclose(summaries[0]["val_acc"],
                               summaries[1]["val_acc"], atol=1e-6)
    np.testing.assert_allclose(summaries[0]["val_loss"],
                               summaries[1]["val_loss"], atol=1e-5)
    assert 0.0 <= summaries[0]["val_acc"] <= 1.0


@pytest.mark.slow  # same CPU-backend gate as above
def test_two_process_global_mesh_trains(tmp_path):
    port = _free_port()
    coord = f"127.0.0.1:{port}"
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env.pop("JAX_PLATFORMS", None)
    procs = [subprocess.Popen(
        [sys.executable, "-c", DRIVER, coord, "2", str(pid),
         str(tmp_path / "ckpt")],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True, env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
        for pid in (0, 1)]
    outs = []
    try:
        for p in procs:
            out, err = p.communicate(timeout=420)
            outs.append((p.returncode, out, err))
    except subprocess.TimeoutExpired:
        for p in procs:
            p.kill()
        pytest.fail("multi-process run timed out: " + repr(
            [(p.returncode) for p in procs]))

    for rc, out, err in outs:
        assert rc == 0, f"rc={rc}\nstdout:\n{out}\nstderr:\n{err[-3000:]}"

    summaries, resumed = {}, {}
    for pid, (_rc, out, _err) in enumerate(outs):
        for line in out.splitlines():
            if line.startswith(f"SUMMARY{pid}="):
                summaries[pid] = json.loads(line.split("=", 1)[1])
            if line.startswith(f"RESUMED{pid}="):
                resumed[pid] = json.loads(line.split("=", 1)[1])
    assert set(summaries) == {0, 1}, summaries
    # SPMD: both processes computed the identical replicated program
    assert summaries[0]["round"] == summaries[1]["round"] == 2
    np.testing.assert_allclose(summaries[0]["val_acc"],
                               summaries[1]["val_acc"], atol=1e-6)
    np.testing.assert_allclose(summaries[0]["val_loss"],
                               summaries[1]["val_loss"], atol=1e-5)
    assert 0.0 <= summaries[0]["val_acc"] <= 1.0
    # checkpoint written at round 2 was restored by BOTH processes (orbax
    # barriers under jax.distributed must not deadlock) and training
    # continued to round 4. The resumed-marker assertion keeps this
    # non-vacuous: without it a silent fall-back to training from scratch
    # would also report round=4 with identical losses.
    for _rc, out, _err in outs:
        assert "[ckpt] resumed from round 2" in out, out
    assert set(resumed) == {0, 1}, resumed
    assert resumed[0]["round"] == resumed[1]["round"] == 4
    np.testing.assert_allclose(resumed[0]["val_loss"],
                               resumed[1]["val_loss"], atol=1e-5)
