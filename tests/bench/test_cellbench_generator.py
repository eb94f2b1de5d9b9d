"""Each traffic mix's round, for each cell's deployment: sizes and targets
as the mix says, and a round of writes fills every node table exactly,
without a drop, through the real client at a tiny size."""
import json
import sys
from collections import Counter
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "bench"), str(ROOT / "src")]

from generator import Mix  # noqa: E402

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [c["name"] for c in BENCH["workloads"]]


def _load(cell, rehearse=False):
    spec = next(c for c in BENCH["workloads"] if c["name"] == cell)
    config = json.loads((ROOT / "bench" / "configs" /
                         f"{spec['config']}.json").read_text())
    traffic = json.loads((ROOT / "bench" / "traffic" /
                          f"{spec['traffic']}.json").read_text())
    if rehearse:
        config = {**config, **config["rehearse"]}
        traffic = {**traffic, **traffic["rehearse"]}
    mix = Mix(traffic, nodes=config["nodes"],
              ranks_per_node=config["ranks_per_node"], cap=config["cap"],
              seed=2**33 + 5)
    return config, traffic, mix


@pytest.mark.parametrize("cell", CELLS)
def test_round_shapes(cell):
    config, traffic, mix = _load(cell)
    n, rpn = config["nodes"], config["ranks_per_node"]
    for call in mix.calls:
        assert len(call.paths) == n
        assert all(len(row) == mix.q for row in call.paths)
    writes = [c for c in mix.calls if c.op == "write"]
    if traffic["chunks_per_call"]:
        # a round of writes fills every node's data slots exactly, and
        # writes each (file, chunk) once
        assert len(writes) * mix.q == config["cap"]
        keys = Counter((p, int(c)) for w in writes
                       for prow, crow in zip(w.paths, w.cids)
                       for p, c in zip(prow, crow))
        assert set(keys.values()) == {1}
        for r in (c for c in mix.calls if c.op == "read"):
            for node, row in enumerate(r.paths):
                # IOR -C: every rank reads a file written on another node
                own = set(writes[0].paths[node])
                assert not own & set(row)
    else:
        files = {p for c in mix.calls for row in c.paths for p in row}
        assert len(files) == n * rpn * traffic["files_per_rank"]
        assert len(files) <= n * config["mcap"]


def test_seed_changes_names_not_sizes():
    a = _load("bb8_1chip.mdtest_a")[2]
    b = Mix(a.spec, nodes=a.nodes, ranks_per_node=a.rpn, cap=512, seed=1)
    assert a.tag != b.tag
    assert [c.op for c in a.calls] == [c.op for c in b.calls]
    assert a.calls[0].paths != b.calls[0].paths


@pytest.mark.parametrize("cell", [c for c in CELLS
                                  if c.startswith("bb8_1chip.ior")])
def test_round_of_writes_fills_the_table_without_a_drop(cell):
    from repro.core.client import BBClient
    from repro.core.intent.selector import select_layout
    from repro.core.workloads import workload_by_name
    config, traffic, mix = _load(cell, rehearse=True)
    n = config["nodes"]
    policy = select_layout(workload_by_name(
        traffic["job"], n_nodes=n)).layout_policy(n_nodes=n)
    client = BBClient(policy, cap=config["cap"], words=config["words"],
                      mcap=config["mcap"])
    rng = np.random.default_rng(0)
    for call in (c for c in mix.calls if c.op == "write"):
        payload = rng.integers(-2**31, 2**31 - 1, (n, mix.q, config["words"]),
                               dtype=np.int32)
        client.write(client.encode(call.paths, chunk_id=call.cids,
                                   payload=payload))
    st = client.state
    assert np.asarray(st.data_count).tolist() == [config["cap"]] * n
    assert int(np.asarray(st.dropped).sum()) == 0
