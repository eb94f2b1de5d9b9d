"""The host reference: its semantics, that it agrees with the engine at a
tiny size, and that the checker counts every planted difference."""
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "bench"), str(ROOT / "src")]

import reference  # noqa: E402
from generator import STAMP_WORDS  # noqa: E402

NODES, Q, WORDS = 4, 4, 256


def test_host_store_newest_version_wins_and_records():
    s = reference.HostStore(records_loc=True)
    s.write([["/a", "/a"]], [[0, 1]], [["v0", "v1"]])
    s.write([["/b"], ["/a"]], [[0], [1]], [["b0"], ["v1'"]])
    assert s.read([["/a", "/a", "/b", "/c"]], [[0, 1, 0, 0]]) == \
        [["v0", "v1'", "b0", None]]
    found, size, loc = s.stat([["/a", "/b", "/c"]])
    assert found.tolist() == [[True, True, False]]
    assert size.tolist() == [[2, 1, -1]] and loc.tolist() == [[1, 0, -1]]
    assert s.remove([["/a", "/c"]]).tolist() == [[True, False]]
    s.stage_out()
    assert s.read([["/b"]], [[0]]) == [[None]]


def _pool(seed=0):
    rng = np.random.default_rng(seed)
    p = rng.integers(-2**31, 2**31 - 1, (NODES, Q, WORDS), dtype=np.int32)
    p[..., :STAMP_WORDS] = 0
    return p


def _stamps(uid, cids, rnd):
    return np.stack([uid, cids, np.full_like(uid, rnd),
                     np.full_like(uid, 77)], axis=-1).astype(np.int32)


def test_device_digest_matches_reference():
    import jax
    from cell import _digest_weights
    x = _pool(1)
    w = _digest_weights(WORDS)
    dev = jax.jit(lambda d: (jax.lax.bitcast_convert_type(d, np.uint32) *
                             w).sum(axis=(1, 2), dtype=np.uint32))(x)
    want = int(reference.row_digests(x).sum()) % 2**32
    assert int(np.asarray(dev).astype(np.uint64).sum()) % 2**32 == want


def test_reference_agrees_with_engine_tiny():
    """A HYBRID client (the IOR-D decision) at 4 nodes: two versions of a
    chunk, reads from another node, stats, a remove and a stage-out."""
    from repro.core.client import BBClient
    from repro.core.intent.selector import select_layout
    from repro.core.workloads import workload_by_name
    policy = select_layout(workload_by_name("IOR-D", n_nodes=NODES)) \
        .layout_policy(n_nodes=NODES)
    client = BBClient(policy, cap=32, words=WORDS, mcap=64)
    pool = [_pool(0), _pool(1)]
    chk = reference.Checker(records_loc=True, pool=pool)
    paths = [[f"/f{n}"] * Q for n in range(NODES)]
    uid = np.repeat(np.arange(NODES)[:, None], Q, 1).astype(np.int32)
    for t, slot in ((0, 0), (1, 1), (0, 1)):      # chunk 0..3 rewritten
        cids = np.full((NODES, Q), 0, np.int32) + np.arange(Q) + 4 * (t % 2)
        st = _stamps(uid, cids, t)
        payload = pool[slot].copy()
        payload[..., :STAMP_WORDS] = st
        client.write(client.encode(paths, chunk_id=cids, payload=payload))
        chk.write(paths, cids, st, slot)
    rp = paths[1:] + paths[:1]
    for base in (0, 4):
        cids = np.zeros((NODES, Q), np.int32) + np.arange(Q) + base
        got, found = client.read(client.encode(rp, chunk_id=cids))
        got = np.asarray(got)
        chk.read(rp, cids, got[..., :STAMP_WORDS], np.asarray(found), got)
    files = [[f"/f{(n + 1) % NODES}", "/nope"] for n in range(NODES)]
    chk.stat(files, *(np.asarray(a) for a in
                      client.stat(client.encode(files))))
    gone = [[f"/f{n}"] for n in range(NODES)]
    chk.remove(gone, np.asarray(client.remove(client.encode(gone))))
    data = np.asarray(client.state.data)
    chk.stage_out(int(np.asarray(client.state.data_count).sum()),
                  int(reference.row_digests(data).sum()) % 2**32)
    assert int(np.asarray(client.state.dropped).sum()) == 0
    assert chk.counts == {"read_rows": 0, "stat_rows": 0, "remove_rows": 0,
                          "stage_out": 0}


@pytest.mark.parametrize("fault", ["stamp", "byte", "found", "loc", "count"])
def test_checker_counts_planted_differences(fault):
    pool = [_pool(0)]
    chk = reference.Checker(records_loc=True, pool=pool)
    paths = [[f"/f{n}"] * Q for n in range(NODES)]
    uid = np.repeat(np.arange(NODES)[:, None], Q, 1).astype(np.int32)
    cids = np.zeros((NODES, Q), np.int32) + np.arange(Q)
    st = _stamps(uid, cids, 3)
    chk.write(paths, cids, st, 0)
    full = pool[0].copy()
    full[..., :STAMP_WORDS] = st
    digest = int(reference.row_digests(full).sum()) % 2**32
    stamps, found = st.copy(), np.ones((NODES, Q), bool)
    if fault == "stamp":
        stamps[2, 1, 2] += 1
    elif fault == "byte":
        full[3, 0, -1] ^= 1
    elif fault == "found":
        found[0, 0] = False
    chk.read(paths, cids, stamps, found, full)
    fnd, size, loc = chk.store.stat([[f"/f{n}"] for n in range(NODES)])
    if fault == "loc":
        loc = loc.copy()
        loc[1, 0] = 0
    chk.stat([[f"/f{n}"] for n in range(NODES)], fnd, size, loc)
    chk.stage_out(NODES * Q - (fault == "count"), digest)
    bad = {k: v for k, v in chk.counts.items() if v}
    want = {"stamp": "read_rows", "byte": "read_rows", "found": "read_rows",
            "loc": "stat_rows", "count": "stage_out"}[fault]
    assert bad == {want: 1}
