"""The chip-side profiling scripts run end to end at `--smoke` shapes: a
process of their own each, as on the chip, so that a name the script
forgot to import fails here and not in a chip call."""

import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.parametrize("script,args,says", [
    # main(): the probe ladder over the flagship CNN round
    ("profile_round.py", [], "[summary] round anatomy"),
    # capture() then parse of the trace it wrote
    ("trace_top_ops.py", ["--rounds", "1"], "[trace] captured 1 steady"),
])
def test_profiling_script_smoke(tmp_path, script, args, says):
    if script == "trace_top_ops.py":
        args = args + ["--trace_dir", str(tmp_path / "trace")]
    run = subprocess.run(
        [sys.executable, os.path.join(ROOT, "scripts", script), "--smoke",
         "--platform", "cpu", *args],
        cwd=str(tmp_path), capture_output=True, text=True, timeout=600,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert run.returncode == 0, run.stdout[-2000:] + run.stderr[-2000:]
    assert says in run.stdout
