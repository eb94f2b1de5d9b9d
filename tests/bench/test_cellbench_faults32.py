"""``bb32_4chip.ior_d``, rehearsed on four CPU devices with a fault planted
under its timed path, and with the control (the program's own lossy
exchange), reads as not correct — as ``test_cellbench_faults.py`` checks
for the other cells.  With 8 node rows per chip, the ``exchange`` fault
leaves out only what crosses between chips: the reads of the chip's last
node and the metadata bound for other chips."""
import json
import os
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
FAULTS = ["control", "unchanged", "half", "altered", "exchange"]


def test_planted_faults_read_not_correct_at_32_nodes():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, "tests/bench/cellbench_faults.py",
         "bb32_4chip.ior_d", *FAULTS], cwd=ROOT, env=env,
        capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    got = {}
    for line in out.stdout.splitlines():
        if line.startswith("FAULT "):
            _, fault, result = line.split(" ", 2)
            got[fault] = json.loads(result)
    assert sorted(got) == sorted(FAULTS)
    for fault, result in got.items():
        assert result["correct"] is False, fault
        assert result["failed"] > 0, fault
        assert any(v["value"] > v["limit"]
                   for v in result["checks"].values()), fault
