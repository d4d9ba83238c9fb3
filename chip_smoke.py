#!/usr/bin/env python
"""The quickest proof that the system still starts on the chip.

Drives the flagship federated round through the entry point a user calls
(`train.main(argv)`, what `federated.py` runs) at the full BENCH shape of
the fmnist CNN — 60k x 28x28 seeded synthetic images, 10 agents, 2 local
epochs at batch 256, 1 corrupt agent, RLR threshold 4, every default-on
lane left on — and checks what comes out by the repo's own means:

  first   4 rounds, eval every 2, chained 2 per dispatch.
          Finite Validation/Poison rows at rounds 2 and 4,
          Health/Params_Finite == 1, heartbeat phase `done`, every program
          family acquired through the executable bank (compiled+banked on
          an empty cache, loaded when an earlier process filled it).
  warm    the same run again, same process, new log dir: every family
          `loaded from cache`, and every metrics row outside
          obs/constants.NON_TIMING_PREFIXES byte-identical to the first
          phase's — the deserialized executable computes the same thing.

One process, no child (a chip belongs to one process), no network. Any
failed check raises: the exit code is non-zero and no result line is
printed. Without a TPU, naming the platform makes JAX itself raise. On success
the last line of stdout is `{"ok": true, "device": {"platform", "kind",
"count"}}` with the device as JAX reports it, and nothing else; the
`[chip_smoke] {...}` line above it carries the jax version, how the first
phase acquired its programs and set-up seconds first vs warm (set-up time,
not a rate).

Run it through the chip tool from the checkout root:
    chiprun -- python3 chip_smoke.py
"""

import contextlib
import io
import json
import math
import os
import re
import sys
import time

import jax

import bench
from defending_against_backdoors_with_robust_learning_rate_tpu import train
from defending_against_backdoors_with_robust_learning_rate_tpu.obs.constants import (
    NON_TIMING_PREFIXES)

PLATFORM = "tpu"
FLAGSHIP = [
    "--data=fmnist", "--synth_train_size=60000",
    "--synth_val_size=10000", "--num_agents=10", "--local_ep=2", "--bs=256",
    "--num_corrupt=1", "--poison_frac=0.5", "--robustLR_threshold=4",
    "--seed=0", "--no_tensorboard"]

AOT_LINE = re.compile(
    r"^\[aot\] (\S+): (loaded from cache|compiled\+banked) in ([\d.]+)s$")


class _Tee(io.TextIOBase):
    def __init__(self, *sinks):
        self.sinks = sinks

    def write(self, text):
        for sink in self.sinks:
            sink.write(text)
        return len(text)

    def flush(self):
        for sink in self.sinks:
            sink.flush()


def run_phase(name, root, extra):
    """One `train.main` run. Returns its log text, its log dir and its
    wall seconds."""
    log_dir = os.path.join(root, name)
    argv = [f"--platform={PLATFORM}", *FLAGSHIP, f"--log_dir={log_dir}",
            f"--data_dir={os.path.join(root, 'no_data')}", *extra]
    print(f"[chip_smoke] {name}: {' '.join(argv)}", flush=True)
    captured = io.StringIO()
    t0 = time.perf_counter()
    with contextlib.redirect_stdout(_Tee(sys.stdout, captured)):
        rc = train.main(argv)
    wall_s = time.perf_counter() - t0
    assert rc == 0, f"{name}: train.main returned {rc}"
    return captured.getvalue(), log_dir, wall_s


def aot_families(name, log):
    """{family: (how, seconds)} from the run's `[aot]` lines; any line that
    is not a clean bank acquisition fails the phase."""
    for bad in ("falling back to jit", "unloadable", "NOT banked"):
        assert bad not in log, f"{name}: `{bad}` in the run log"
    lines = [l for l in log.splitlines() if l.startswith("[aot]")]
    fams = {}
    for line in lines:
        m = AOT_LINE.match(line)
        assert m, f"{name}: unexpected line {line!r}"
        fams[m.group(1)] = (m.group(2), float(m.group(3)))
    assert fams, f"{name}: no [aot] line — the executable bank never ran"
    return fams


def metric_lines(log_dir):
    """(all rows parsed, the raw non-timing lines) of the run's
    metrics.jsonl."""
    (run,) = [d for d in os.listdir(log_dir)
              if os.path.isdir(os.path.join(log_dir, d))]
    with open(os.path.join(log_dir, run, "metrics.jsonl")) as f:
        lines = f.read().splitlines()
    rows = [json.loads(l) for l in lines]
    stable = [l for l, r in zip(lines, rows)
              if not r["tag"].startswith(NON_TIMING_PREFIXES)]
    return rows, stable


def check_run(name, log_dir, rounds):
    rows, stable = metric_lines(log_dir)
    at = {(r["tag"], r["step"]): r["value"] for r in rows}
    for rnd in rounds:
        for tag in ("Validation/Loss", "Validation/Accuracy",
                    "Poison/Poison_Loss", "Poison/Poison_Accuracy",
                    "Train/Loss"):
            assert math.isfinite(at[(tag, rnd)]), (name, tag, rnd, at)
        assert at[("Health/Params_Finite", rnd)] == 1.0, (name, rnd)
    with open(os.path.join(log_dir, "status.json")) as f:
        status = json.load(f)
    assert status["phase"] == "done", (name, status)
    start = [r for r in rows if r["tag"] == "_run/start"][-1]
    assert start["device"]["platform"] == PLATFORM, (name, start)
    return stable


def main():
    jax.config.update("jax_platforms", PLATFORM)
    device = train.device_record()
    assert device["platform"] == PLATFORM, device
    bench.peak_tflops(device["kind"])   # raises on a chip not in the table

    root = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "chiprun_out", "chip_smoke",
                        time.strftime("%Y%m%d-%H%M%S"))
    os.makedirs(root)

    schedule = ["--rounds=4", "--snap=2", "--chain=2"]
    log, first_dir, first_wall = run_phase("first", root, schedule)
    first = aot_families("first", log)
    assert set(first) == {"round", "chained", "eval_val", "eval_poison"}, \
        first
    first_how = {how for how, _ in first.values()}
    assert len(first_how) == 1, f"first: mixed bank outcomes {first}"
    first_rows = check_run("first", first_dir, (2, 4))

    log, warm_dir, warm_wall = run_phase("warm", root, schedule)
    warm = aot_families("warm", log)
    assert set(warm) == set(first) and all(
        how == "loaded from cache" for how, _ in warm.values()), warm
    warm_rows = check_run("warm", warm_dir, (2, 4))
    assert warm_rows == first_rows, (
        "warm run's non-timing metrics rows differ from the first run's:\n"
        + "\n".join(f"{a}\n{b}" for a, b in zip(first_rows, warm_rows)
                    if a != b))

    print("[chip_smoke] " + json.dumps({
        "jax": jax.__version__, "first_phase": first_how.pop(),
        "setup_s": {"first": round(sum(s for _, s in first.values()), 2),
                    "warm": round(sum(s for _, s in warm.values()), 2)},
        "wall_s": {"first": round(first_wall, 1),
                   "warm": round(warm_wall, 1)}}))
    # the result line the driver reads: exactly these keys, nothing after it
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
