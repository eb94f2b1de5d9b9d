"""A cell's mesh programs do not depend on its seed.

The seed draws the path tags, so another seed hashes the metadata of the
same files to other nodes.  The client's mesh plans are keyed only by the
widths their executors run, quantized so that every draw lands on the
same key: after one warm-up round of one seed, a warm-up round of another
seed through the same client builds no new program and grows no planning
floor.  Run on four forced CPU devices at the cell's rehearsal sizes."""
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]

SCRIPT = """
import sys
from pathlib import Path
sys.path[:0] = ['bench', 'src']
from cell import Cell
from repro.core import obs

CELL = 'bb32_4chip.ior_d'


def programs(client):
    return (set(client._mesh_ops), set(client._mesh_probe),
            set(client._mesh_migrate))


def respecializations(rec):
    return sum(v for k, v in rec.metrics.snapshot()['counters'].items()
               if k.startswith('ragged_respecializations_total'))


rec = obs.TraceRecorder()
a = Cell(Path('.'), CELL, int(sys.argv[1]), True, {'trace': rec})
a.setup()
a.run_round()
built, grown = programs(a.client), respecializations(rec)
assert built[0] and built[1], built
b = Cell(Path('.'), CELL, int(sys.argv[2]), True)
b.setup()
b.client = a.client            # seed B's round through seed A's client
b.run_round()
assert programs(a.client) == built, 'a new mesh program for seed B'
assert respecializations(rec) == grown, 'a planning floor grew'
assert not any(b.check().values()), b.check()
print('SEEDS_OK', len(built[0]), len(built[1]))
"""


@pytest.mark.mesh
def test_second_seed_builds_no_new_mesh_program():
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=4"}
    out = subprocess.run(
        [sys.executable, "-c", SCRIPT, str(2**31 + 101), str(3 * 10**9 + 7)],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert "SEEDS_OK" in out.stdout, out.stdout + out.stderr[-3000:]
