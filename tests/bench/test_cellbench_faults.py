"""Each cell, rehearsed with a fault planted under its timed path, and with
the control (the program's own lossy exchange), reads as not correct.  The
four-chip cell runs on four CPU devices, which ``run.main`` forces for a
rehearsal, and has the fault only a mesh can have: no exchange between
chips."""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]

FAULTS = {
    "bb8_1chip.ior_d": ["control", "unchanged", "half", "altered"],
    "bb8_1chip.ior_a": ["control", "unchanged", "half", "altered"],
    "bb8_1chip.mdtest_a": ["control", "unchanged", "half", "altered"],
    "bb4_4chip.ior_d": ["control", "unchanged", "half", "altered",
                        "exchange"],
}


@pytest.mark.parametrize("cell", sorted(FAULTS))
def test_planted_faults_read_not_correct(cell):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, "tests/bench/cellbench_faults.py", cell,
         *FAULTS[cell]], cwd=ROOT, env=env, capture_output=True, text=True,
        timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    got = {}
    for line in out.stdout.splitlines():
        if line.startswith("FAULT "):
            _, fault, result = line.split(" ", 2)
            got[fault] = json.loads(result)
    assert sorted(got) == sorted(FAULTS[cell])
    for fault, result in got.items():
        assert result["correct"] is False, fault
        assert result["failed"] > 0, fault
        assert any(v["value"] > v["limit"]
                   for v in result["checks"].values()), fault
