"""Where a cell's arrays live: a ``"mesh"`` configuration shards the node
tables, the payload pool and the read answers over the node axis, one
shard of ``nodes / chips`` node rows on each of its chips; a ``"stacked"``
one keeps them whole on one device.  Each cell of ``BENCHMARK.json`` is
set up at its rehearsal size on as many CPU devices as it asks chips for,
and runs one round."""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = {c["name"]: c for c in BENCH["workloads"]}

SCRIPT = r"""
import json, sys
from pathlib import Path
root = Path(sys.argv[1])
sys.path[:0] = [str(root / "bench"), str(root / "src")]
import jax
from cell import Cell

cell = Cell(root, sys.argv[2], 2**31 + 11, rehearse=True)
cell.setup()
cell.run_round()


def shards(x):
    return sorted([s.device.id, s.index[0].start or 0,
                   s.data.shape[0]] for s in x.addressable_shards)


st = cell.client.state
heads = [r.out[0] for r in cell.records if r.op == "read"]
print(json.dumps({
    "nodes": cell.nodes,
    "state": {k: shards(getattr(st, k))
              for k in ("data", "data_keys", "data_count", "dropped")},
    "pool": [shards(p) for p in cell.pool],
    "heads": [shards(h) for h in heads[:2]],
}))
"""


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_node_axis_sharding(cell):
    chips = CELLS[cell]["chips"]
    config = json.loads((ROOT / "bench" / "configs" /
                         f"{CELLS[cell]['config']}.json").read_text())
    traffic = json.loads((ROOT / "bench" / "traffic" /
                          f"{CELLS[cell]['traffic']}.json").read_text())
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": f"--xla_force_host_platform_device_count={chips}"}
    out = subprocess.run([sys.executable, "-c", SCRIPT, str(ROOT), cell],
                         cwd=ROOT, env=env, capture_output=True, text=True,
                         timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    got = json.loads(out.stdout.strip().splitlines()[-1])
    nodes = got["nodes"]
    if config["backend"] == "mesh":
        per = nodes // chips
        want = [[d, d * per, per] for d in range(chips)]
    else:
        want = [[0, 0, nodes]]
    arrays = (list(got["state"].values()) + got["pool"] + got["heads"])
    assert len(got["pool"]) == traffic["payload_pool"]
    for shards in arrays:
        assert shards == want
