"""run_baselines.py rendering tests (no backend, --regen path only).

The sweep script's RESULTS.md renderer grew real logic in r4: seed-matrix
rows (name@sN) must aggregate into the seed-robustness table and stay OUT
of the main table. A fixture results.json drives `--regen` in a tmp cwd.
"""

import json
import os
import subprocess
import sys

SCRIPT = os.path.join(os.path.dirname(__file__), "..", "scripts",
                      "run_baselines.py")


def _row(name, val, poi, steady=1.5):
    return {
        "name": name,
        "summary": {"round": 200, "val_acc": val, "poison_acc": poi,
                    "rounds_per_sec": 1.2, "steady_rounds_per_sec": steady},
        "milestones": {"20": {"val_acc": val - 0.1, "poison_acc": poi}},
        "curves": {},
        "wall_s": 100.0,
        "hardness": 0.5,
        "device": "fake",
    }


def test_regen_renders_seed_table_and_filters_seed_rows(tmp_path):
    rows = [
        _row("fmnist-attack-rlr", 0.96, 0.005),
        _row("fmnist-attack-rlr@s1", 0.95, 0.008),
        _row("fmnist-attack-rlr@s2", 0.97, 0.002),
        _row("cifar10-dba-rlr", 1.0, 0.013),
    ]
    with open(tmp_path / "results.json", "w") as f:
        json.dump(rows, f)
    out = tmp_path / "R.md"
    r = subprocess.run(
        [sys.executable, os.path.abspath(SCRIPT), "--regen",
         "--out", str(out)],
        cwd=tmp_path, capture_output=True, text=True)
    assert r.returncode == 0, r.stderr
    text = out.read_text()
    main_table = text.split("## Seed robustness")[0]
    assert "fmnist-attack-rlr@s1" not in main_table
    assert "| fmnist-attack-rlr |" in main_table
    # stream-marginality flag stays attached to the cifar CNN defended row
    assert "| cifar10-dba-rlr† |" in main_table
    assert "## Seed robustness" in text
    # mean of 0.96/0.95/0.97 = 0.960, range 0.950-0.970
    assert "0.960 (0.950–0.970)" in text
    # poison mean 0.005 (0.002-0.008)
    assert "0.005 (0.002–0.008)" in text
    assert "[0, 1, 2]" in text


def test_print_configs_pins_row_staging(tmp_path):
    """The close-out sweep's staged rows carry load-bearing calibrations
    that nothing else checks until TPU time is burned: the clipnoise row
    must dispatch per-round (chain=1 — the chain=10 clip+noise program is
    the slowest compile of the sweep, for a measured null), the bf16
    ResNet-9 row must exist, the cifar DBA pair must join the seed matrix,
    and the sign rows must pick up the per-rule hardness overrides."""
    r = subprocess.run(
        [sys.executable, os.path.abspath(SCRIPT), "--print_configs",
         "--seeds", "1,2", "--sign_data_dir", "./data_h025",
         "--sign_hardness", "0.25"],
        cwd=tmp_path, capture_output=True, text=True)
    assert r.returncode == 0, r.stderr
    rows = {row["name"]: row for row in json.loads(r.stdout)}

    assert rows["fmnist-attack-rlr-clipnoise"]["chain"] == 1
    assert rows["fmnist-attack-rlr"]["chain"] == 10      # others unchanged
    assert rows["cifar10-resnet9-dba-rlr-bf16"]["dtype"] == "bf16"
    assert rows["cifar10-resnet9-dba-rlr-bf16"]["remat"]
    for s in (1, 2):
        assert f"cifar10-dba-rlr@s{s}" in rows
        assert rows[f"cifar10-dba-rlr@s{s}"]["seed"] == s
    sign = rows["fmnist-attack-sign"]
    assert sign["data_dir"] == "./data_h025"
    assert sign["synth_hardness"] == 0.25
    assert sign["aggr"] == "sign"


def test_regen_without_seed_rows_has_no_seed_section(tmp_path):
    with open(tmp_path / "results.json", "w") as f:
        json.dump([_row("fmnist-clean", 0.9, None)], f)
    out = tmp_path / "R.md"
    r = subprocess.run(
        [sys.executable, os.path.abspath(SCRIPT), "--regen",
         "--out", str(out)],
        cwd=tmp_path, capture_output=True, text=True)
    assert r.returncode == 0, r.stderr
    assert "## Seed robustness" not in out.read_text()
