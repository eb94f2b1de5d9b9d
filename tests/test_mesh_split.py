"""The two-level mesh exchange: several node rows per chip.

Same-chip rows never reach a collective and only rows for another chip
cross the fabric, compacted per chip pair (``exchange_plan.
MeshSplitExecutor`` / ``PermuteExecutor``).  The stacked cases run the
split on one device with the node rows grouped into ``n_chips`` chips
(the hooks swap and roll whole groups); the subprocess cases run Proteus's
Table-I scale, 32 nodes on 4 host devices (8 rows per device), through
``BBClient``'s normal mesh path against the stacked client and the
benchmark's host reference.
"""
import pathlib
import subprocess
import sys
import textwrap

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import burst_buffer as bb
from repro.core.exchange_plan import _mesh_width, plan_mesh_ragged_spec
from repro.core.layouts import LayoutMode, route_data, route_meta
from repro.core.policy import LayoutPolicy

ROOT = pathlib.Path(__file__).resolve().parents[1]
N, Q, W = 8, 16, 8


def _state_equal(a, b):
    for x, y in zip(a.tree_flatten()[0], b.tree_flatten()[0]):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


@pytest.mark.parametrize("executor", ["padded", "ppermute"])
@pytest.mark.parametrize("chips", [1, 2, 4])
def test_split_lifecycle_matches_dense_stacked(chips, executor):
    """Write, hybrid read and stat through the split with N / chips node
    rows per chip are the dense oracle's, table for table."""
    pol = LayoutPolicy.from_scopes(
        {"/bb/hot": LayoutMode.HYBRID, "/bb/meta2": LayoutMode.CENTRAL_META},
        n_nodes=N, default=LayoutMode.DIST_HASH)
    rng = np.random.RandomState(chips)
    ph = jnp.asarray(rng.randint(1, 1 << 20, (N, Q)), jnp.int32)
    cid = jnp.asarray(rng.randint(0, 4, (N, Q)), jnp.int32)
    pay = jnp.asarray(rng.randint(0, 9999, (N, Q, W)), jnp.int32)
    valid = jnp.asarray(rng.rand(N, Q) > 0.15)
    mode = jnp.asarray(rng.choice([2, 3, 4], (N, Q)), jnp.int32)
    ranks = jnp.arange(N, dtype=jnp.int32)[:, None]
    owner = route_meta(mode, N, pol.n_md_servers, ph, ranks, xp=jnp)
    dest = route_data(mode, N, ph, cid, ranks, xp=jnp)

    def spec(d):
        return plan_mesh_ragged_spec(d, valid, N, n_chips=chips,
                                     executor=executor)

    cfg = bb.ExchangeConfig("compacted", data_spec=spec(dest),
                            meta_spec=spec(owner))
    dense = bb.forward_write(bb.init_state(N, 256, W, 256), pol, ph, cid,
                             pay, valid, mode=mode, config=bb.DENSE)
    s = bb.forward_write(bb.init_state(N, 256, W, 256), pol, ph, cid, pay,
                         valid, mode=mode, config=cfg)
    _state_equal(s, dense)
    _, fm, _, loc = bb.meta_op(
        s, pol, jnp.full_like(ph, bb.OP_STAT), ph, jnp.zeros_like(ph),
        jnp.full_like(ph, -1), valid & (mode == 4), mode=mode, config=cfg)
    data_loc = jnp.where(fm & (loc >= 0), loc, jnp.broadcast_to(ranks,
                                                                ph.shape))
    cfg_r = bb.ExchangeConfig("compacted", data_spec=spec(route_data(
        mode, N, ph, cid, ranks, data_loc=data_loc, xp=jnp)))
    got = bb.forward_read(s, pol, ph, cid, valid, mode=mode, config=cfg_r,
                          data_loc=data_loc)
    want = bb.forward_read(dense, pol, ph, cid, valid, mode=mode,
                           config=bb.DENSE)
    for a, b in zip(got, want):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    stat = [bb.meta_op(st, pol, jnp.full_like(ph, bb.OP_STAT), ph,
                       jnp.zeros_like(ph), jnp.full_like(ph, -1), valid,
                       mode=mode, config=c)[1:]
            for st, c in ((s, cfg), (dense, bb.DENSE))]
    for a, b in zip(*stat):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_planner_picks_the_ring_for_one_chip_offset():
    """Reads of the next node's files at 32 nodes on 4 chips: 7 of a
    chip's 8 nodes read on the chip, and the one fabric segment goes one
    chip along the ring — the ring ships it alone (16 rows), where the
    padded chip all_to_all would ship a segment to every chip."""
    n, q, chips = 32, 16, 4
    dest = np.repeat((np.arange(n)[:, None] + 1) % n, q, axis=1)
    spec = plan_mesh_ragged_spec(dest, np.ones((n, q), bool), n,
                                 n_chips=chips, row_bytes=4 * 131075)
    assert spec.executor == "ppermute"
    assert spec.widths == (q, 0, 0) and spec.view == q
    assert spec.exchanged_cols == q and spec.operand_cols == q
    assert (spec.local_rows, spec.fabric_rows) == (28 * q, 4 * q)
    padded = spec.with_executor("padded")
    assert padded.widths == (q,) and padded.operand_cols == chips * q
    # hashed metadata spreads over every chip: one padded all_to_all
    rng = np.random.RandomState(0)
    owner = np.repeat(rng.randint(0, n, (n, q // 2)), 2, axis=1)
    meta = plan_mesh_ragged_spec(owner, np.ones((n, q), bool), n,
                                 n_chips=chips, row_bytes=32)
    assert meta.executor == "padded" and meta.n_chips == chips
    assert meta.local_rows + meta.fabric_rows == n * q


def test_mesh_widths_cover_the_hash_tail():
    """Equal bins keep their exact width; unequal (hashed) bins also
    cover three times their mean, so every hash draw of one workload
    lands on one width — one jit key whatever the seed."""
    assert _mesh_width(np.array([16, 16, 0, 16]), 512, 8) == 16
    assert _mesh_width(np.array([0, 0]), 512, 8) == 0
    assert _mesh_width(np.array([3]), 512, 8) == 8
    widths = set()
    for seed in range(200):
        files = np.random.RandomState(seed).randint(0, 32, 256)
        rows = 2 * np.bincount(files, minlength=32)
        widths.add(_mesh_width(rows, 512, 8))
    assert widths == {64}
    assert _mesh_width(np.array([100, 1]), 64, 8) == 64      # the cap


MESH32_SCRIPT = textwrap.dedent("""
    import os
    os.environ['XLA_FLAGS'] = '--xla_force_host_platform_device_count=4'
    import sys; sys.path[:0] = ['src', 'bench']
    import jax, jax.numpy as jnp, numpy as np
    from reference import HostStore
    from repro.core import obs
    from repro.core.client import BBClient
    from repro.core.layouts import LayoutMode
    from repro.core.mesh_engine import make_node_mesh
    from repro.core.policy import LayoutPolicy

    N, q, w = 32, 6, 16
    mesh = make_node_mesh(4)
    rng = np.random.RandomState(0)

    def same(a, b, what):
        for x, y in zip(a, b):
            assert np.array_equal(np.asarray(x), np.asarray(y)), what

    for mode in LayoutMode:
        policy = LayoutPolicy.uniform(mode, N)
        mc = BBClient(policy, mesh, cap=64, words=w, mcap=512,
                      exchange="compacted")
        sc = BBClient(policy, cap=64, words=w, mcap=512,
                      exchange="compacted")
        host = HostStore(mode == LayoutMode.HYBRID)
        # three files of two chunks per node: no (file, chunk) twice
        paths = [[f"/bb/{mode.name}/n{n}/f{j % 3}" for j in range(q)]
                 for n in range(N)]
        cid = np.tile(np.arange(q, dtype=np.int32) // 3, (N, 1))
        pay = rng.randint(0, 9999, (N, q, w)).astype(np.int32)
        reqs = [c.encode(paths, chunk_id=cid, payload=pay)
                for c in (mc, sc)]
        for c, r in zip((mc, sc), reqs):
            c.write(r)
        host.write(paths, cid, [[tuple(v) for v in row] for row in pay])
        same(mc.state.tree_flatten()[0], sc.state.tree_flatten()[0],
             ("write", mode))
        # node n reads node n + 1's files: 1 of 8 reads leaves the device
        rpaths = paths[1:] + paths[:1]
        rcid = np.roll(cid, -1, axis=0)
        got = [c.read(c.encode(rpaths, chunk_id=rcid)) for c in (mc, sc)]
        same(got[0], got[1], ("read", mode))
        want = host.read(rpaths, rcid)
        out, found = (np.asarray(a) for a in got[0])
        assert found.all(), mode
        assert all(tuple(out[n, j]) == want[n][j]
                   for n in range(N) for j in range(q)), mode
        st = [c.stat(c.encode(paths)) for c in (mc, sc)]
        same(st[0], st[1], ("stat", mode))
        for a, b in zip(st[0], host.stat(paths)):
            assert np.array_equal(np.asarray(a), b), ("stat ref", mode)
        cpaths = [[f"/bb/{mode.name}/new{n}/{j}" for j in range(q)]
                  for n in range(N)]
        cr = [c.create(c.encode(cpaths)) for c in (mc, sc)]
        same(cr[0:1], cr[1:2], ("create", mode))
        host.create(cpaths)
        assert np.asarray(cr[0]).all(), mode
        rm = [c.remove(c.encode(cpaths)) for c in (mc, sc)]
        same(rm[0:1], rm[1:2], ("remove", mode))
        assert np.array_equal(np.asarray(rm[0]), host.remove(cpaths)), mode
        same(mc.state.tree_flatten()[0], sc.state.tree_flatten()[0],
             ("final", mode))
        assert int(np.asarray(mc.state.dropped).sum()) == 0, mode
        # the mesh ran split plans, the ring among them for HYBRID reads
        specs = [s for cfg in list(mc._mesh_ops) + list(mc._mesh_probe)
                 for s in (cfg.data_spec, cfg.meta_spec) if s is not None]
        assert specs and all(s.n_chips == 4 for s in specs), mode
        if mode == LayoutMode.HYBRID:
            assert any(s.executor == 'ppermute' for s in specs)
    print('MESH32_OK')
""")


@pytest.mark.slow
@pytest.mark.mesh
def test_32_nodes_on_4_devices_match_stacked_and_reference():
    """Every layout mode at 32 nodes on 4 devices: write, next-node read,
    stat, create and remove through the mesh client equal the stacked
    client bit for bit and the host reference answer for answer."""
    r = subprocess.run([sys.executable, "-c", MESH32_SCRIPT],
                       capture_output=True, text=True, timeout=900,
                       cwd=str(ROOT))
    assert "MESH32_OK" in r.stdout, r.stdout + r.stderr


COUNTER_SCRIPT = textwrap.dedent("""
    import os
    os.environ['XLA_FLAGS'] = '--xla_force_host_platform_device_count=4'
    import sys; sys.path.insert(0, 'src')
    import jax, jax.numpy as jnp, numpy as np
    from repro.core import obs
    from repro.core.client import BBClient, BBRequest
    from repro.core.layouts import LayoutMode, route_data, route_meta
    from repro.core.mesh_engine import make_node_mesh
    from repro.core.policy import LayoutPolicy

    N, q, w, L = 32, 8, 16, 8
    policy = LayoutPolicy.uniform(LayoutMode.DIST_HASH, N)
    rec = obs.TraceRecorder()
    c = BBClient(policy, make_node_mesh(4), cap=64, words=w, mcap=64,
                 exchange="compacted", trace=rec)
    rng = np.random.RandomState(1)
    ph = rng.randint(1, 1 << 20, (N, q)).astype(np.int32)
    cid = rng.randint(0, 4, (N, q)).astype(np.int32)
    valid = rng.rand(N, q) > 0.2
    pay = rng.randint(0, 99, (N, q, w)).astype(np.int32)
    c.write(BBRequest(path_hash=jnp.asarray(ph), chunk_id=jnp.asarray(cid),
                      payload=jnp.asarray(pay), valid=jnp.asarray(valid)))
    mode = np.full((N, q), int(LayoutMode.DIST_HASH), np.int32)
    ranks = np.arange(N)[:, None]
    dest = route_data(mode, N, ph, cid, ranks, xp=np)
    owner = route_meta(mode, N, policy.n_md_servers, ph, ranks, xp=np)
    chip = np.arange(N)[:, None] // L
    want_fab = sum(int((valid & (d // L != chip)).sum())
                   for d in (dest, owner))
    m = rec.metrics
    local = m.get("exchange_rows_total", op="write", path="local")
    fabric = m.get("exchange_rows_total", op="write", path="fabric")
    assert local + fabric == 2 * int(valid.sum()), (local, fabric)
    assert fabric == want_fab and 0 < fabric < local + fabric
    assert m.get("exchange_fabric_bytes_total", op="write") > 0
    # the trace-time split span fired for the write's two planes
    names = [s.name for s in rec.spans]
    assert names.count("exchange.split") == 2, names
    print('COUNTERS_OK')
""")


@pytest.mark.mesh
def test_split_counters_count_local_and_fabric_rows():
    """``exchange_rows_total{path}`` adds up to every row a write
    exchanged (data and metadata planes), ``path="fabric"`` is exactly
    the rows bound for another device, and each plane's split ran in an
    ``exchange.split`` span."""
    r = subprocess.run([sys.executable, "-c", COUNTER_SCRIPT],
                       capture_output=True, text=True, timeout=600,
                       cwd=str(ROOT))
    assert "COUNTERS_OK" in r.stdout, r.stdout + r.stderr


CARRY_MIGRATE_SCRIPT = textwrap.dedent("""
    import os
    os.environ['XLA_FLAGS'] = '--xla_force_host_platform_device_count=4'
    import sys; sys.path.insert(0, 'src')
    import jax, jax.numpy as jnp, numpy as np
    from repro.core.adapt import LiveMigrator
    from repro.core.client import BBClient, BBRequest
    from repro.core.layouts import LayoutMode
    from repro.core.mesh_engine import make_node_mesh
    from repro.core.policy import LayoutPolicy

    N, q, w = 32, 8, 8
    mesh = make_node_mesh(4)
    # 1. an incast at budget 2 overflows into the lossless carry round,
    #    whose cond every device takes alike
    pol = LayoutPolicy.uniform(LayoutMode.DIST_HASH, N)
    mc = BBClient(pol, mesh, cap=N * q, words=w, mcap=N * q,
                  exchange="compacted", budget=2, meta_budget=q)
    dn = BBClient(pol, cap=N * q, words=w, mcap=N * q, exchange="dense")
    rng = np.random.RandomState(2)
    req = BBRequest(
        path_hash=jnp.full((N, q), 7919, jnp.int32),
        chunk_id=jnp.asarray(rng.randint(0, 3, (N, q)), jnp.int32),
        payload=jnp.asarray(np.full((N, q, w), 5), jnp.int32))
    mc.write(req); dn.write(req)
    assert int(np.asarray(mc.state.dropped).sum()) == 0
    for get in (lambda c: (c.state.data_count, c.state.meta_count),
                lambda c: c.read(req), lambda c: c.stat(req)):
        for a, b in zip(get(mc), get(dn)):
            assert np.array_equal(np.asarray(a), np.asarray(b)), 'carry'
    # 2. a live relayout (build_mesh_migrate) with 8 rows per device
    policy = LayoutPolicy.from_scopes({"/bb/hot": LayoutMode.HYBRID},
                                      n_nodes=N,
                                      default=LayoutMode.DIST_HASH)
    clients = {"mesh": BBClient(policy, mesh, cap=64, words=w, mcap=64,
                                telemetry=True, exchange="compacted"),
               "stacked": BBClient(policy, cap=64, words=w, mcap=64,
                                   telemetry=True, exchange="compacted")}
    paths = [[f"/bb/hot/r{i}/f{j % 2}" for j in range(q)] for i in range(N)]
    cid = np.tile(np.arange(q, dtype=np.int32) // 2, (N, 1))
    pay = rng.randint(0, 9999, (N, q, w)).astype(np.int32)
    perm = np.roll(np.arange(N), -1)
    seen = {}
    for name, c in clients.items():
        r = c.encode(paths, chunk_id=cid, payload=pay)
        c.write(r)
        rreq = BBRequest(path_hash=r.path_hash[perm],
                         chunk_id=r.chunk_id[perm],
                         scope_hash=r.scope_hash[perm])
        mig = LiveMigrator(c, "/bb/hot", LayoutMode.DIST_HASH,
                           step_chunks=64)
        outs = []
        while not mig.done:
            mig.step()
            out, found = c.read(rreq)
            assert bool(np.asarray(found).all()), (name, mig.watermark)
            outs += [out, found, *c.stat(rreq)]
        mig.finish()
        out, found = c.read(rreq)
        assert np.array_equal(np.asarray(out), pay[perm]), name
        seen[name] = outs + [out, found, *c.state.tree_flatten()[0]]
    for a, b in zip(seen["mesh"], seen["stacked"]):
        assert np.array_equal(np.asarray(a), np.asarray(b)), 'relayout'
    print('CARRY_MIGRATE_OK')
""")


@pytest.mark.slow
@pytest.mark.mesh
def test_32_node_carry_round_and_relayout_match():
    """With 8 node rows per device, a budget-2 incast is carried, not
    dropped, and a live relayout (``build_mesh_migrate``) answers every
    read and stat at every watermark as the stacked client does."""
    r = subprocess.run([sys.executable, "-c", CARRY_MIGRATE_SCRIPT],
                       capture_output=True, text=True, timeout=900,
                       cwd=str(ROOT))
    assert "CARRY_MIGRATE_OK" in r.stdout, r.stdout + r.stderr
