"""Every cell runs end to end at a tiny size on the CPU (``--rehearse``)
and prints a last line of the contract's shape with no device metric; a
run that finds no chip, or no program, fails and prints no result."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [c["name"] for c in BENCH["workloads"]]


def _run(args, cwd=ROOT):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    env.pop("XLA_FLAGS", None)
    return subprocess.run([sys.executable, "bench/run.py", *args], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=600)


def _expected(cell, trace):
    e2e = [m for m in BENCH["end_to_end"]
           if "workloads" not in m or cell in m["workloads"]]
    if not trace:
        return [m["name"] for m in e2e]
    moves = {m["name"] for m in e2e}
    return [m["name"] for m in BENCH["per_layer"]
            if (cell in m["workloads"] if "workloads" in m
                else m["moves"] in moves)]


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("cell", CELLS)
def test_rehearsal_prints_the_contract_line(cell, trace):
    out = _run(["--workload", cell, "--seed", str(2**31 + 12345),
                "--seconds", "0.3", "--trace", str(trace), "--rehearse"])
    assert out.returncode == 0, out.stderr[-3000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert list(line)[-1] == "checks"
    assert {"correct", "attempted", "failed", "metrics",
            "device"} <= set(line)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert line["metrics"] == {}                  # no device metric off-chip
    assert line["device"]["platform"] == "cpu"
    assert line["would_report"] == _expected(cell, trace)
    assert "setup_s" in _expected(cell, 0)
    assert all(v["value"] == 0 and v["limit"] == 0
               for v in line["checks"].values())
    last = out.stderr.strip().splitlines()[-len(line["checks"]):]
    assert all(s.startswith("check ") for s in last)


def test_no_chip_no_result():
    out = _run(["--workload", CELLS[0], "--seed", "1", "--seconds", "1"])
    assert out.returncode != 0
    assert "no TPU" in out.stderr
    assert not out.stdout.strip().startswith("{")
    assert not any(s.startswith("{") for s in out.stdout.splitlines())


def test_benchmark_files_alone_are_not_enough(tmp_path):
    """A directory holding only BENCHMARK.json and the benchmark's paths
    has no program: the run fails and prints no result."""
    for p in BENCH["paths"]:
        shutil.copytree(ROOT / p, tmp_path / "co" / p)
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "co")
    out = _run(["--workload", CELLS[0], "--seed", "1", "--seconds", "1",
                "--rehearse"], cwd=tmp_path / "co")
    assert out.returncode != 0
    assert not any(s.startswith("{") for s in out.stdout.splitlines())


def test_memory_report_rehearsal():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, "bench/memory_report.py", "--workload", CELLS[0],
         "--seed", "3", "--rehearse"], cwd=ROOT, env=env,
        capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    kinds = {line.split(":")[0] for line in out.stdout.splitlines()
             if "temporaries" in line}
    assert {"write", "meta"} <= kinds
