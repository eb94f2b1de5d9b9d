"""Unified exchange planner: executor parity (padded + ppermute mesh-ragged
vs ragged-stacked vs dense), losslessness sweeps over skewed histograms,
the two-phase hybrid read, telemetry-seeded ragged presizing, the measured
fabric model behind the executor pick and the migration-cost gate, and the
subprocess mesh digest test on the PR-4 pinned op stream."""
import json
import pathlib
import subprocess
import sys
import textwrap

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import burst_buffer as bb
from repro.core import exchange_select as xs
from repro.core.client import BBClient, BBRequest
from repro.core.exchange_plan import (MeshRaggedSpec, PermuteExecutor,
                                      build_executor, plan_mesh_ragged_spec)
from repro.core.layouts import LayoutMode, route_data, route_meta
from repro.core.policy import LayoutPolicy

ROOT = pathlib.Path(__file__).resolve().parents[1]

N, Q, W = 8, 16, 8


def _mixed_policy(n=N):
    return LayoutPolicy.from_scopes(
        {"/bb/hot": LayoutMode.HYBRID, "/bb/meta2": LayoutMode.CENTRAL_META},
        n_nodes=n, default=LayoutMode.DIST_HASH)


def _state_arrays(state):
    return state.tree_flatten()[0]


def _assert_state_equal(a, b):
    for x, y in zip(_state_arrays(a), _state_arrays(b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def _batch(seed=0, n=N, q=Q, w=W, modes=(2, 3)):
    rng = np.random.RandomState(seed)
    ph = jnp.asarray(rng.randint(1, 1 << 20, (n, q)), jnp.int32)
    cid = jnp.asarray(rng.randint(0, 4, (n, q)), jnp.int32)
    pay = jnp.asarray(rng.randint(0, 9999, (n, q, w)), jnp.int32)
    valid = jnp.asarray(rng.rand(n, q) > 0.15)
    mode = jnp.asarray(rng.choice(list(modes), (n, q)), jnp.int32)
    return ph, cid, pay, valid, mode


# ---------------------------------------------------------------------------
# the planner
# ---------------------------------------------------------------------------
def test_build_executor_is_the_single_routing_decision():
    pol = _mixed_policy()
    q = 16
    assert type(build_executor("data", pol, q, bb.DENSE)).__name__ == \
        "DenseExecutor"
    ex = build_executor("data", pol, q, bb.COMPACTED)
    # hybrid present → structural concentration → B = q, no carry
    assert type(ex).__name__ == "UniformExecutor"
    assert ex.budget == q and ex.carry_budget == 0
    cfg = bb.ExchangeConfig("compacted", budget=4)
    ex = build_executor("data", pol, q, cfg)
    assert ex.budget == 4 and ex.carry_budget == q - 4 and not ex.drop
    cfg = bb.ExchangeConfig("compacted", budget=4, lossless=False)
    ex = build_executor("data", pol, q, cfg)
    assert ex.carry_budget == 0 and ex.drop
    spec = bb.RaggedSpec((8,) * N)
    cfg = bb.ExchangeConfig("compacted", data_spec=spec)
    assert type(build_executor("data", pol, q, cfg)).__name__ == \
        "RaggedExecutor"
    # meta role reads the meta spec slot, not the data one
    assert type(build_executor("meta", pol, q, cfg)).__name__ == \
        "UniformExecutor"
    mspec = MeshRaggedSpec("padded", 4, 16, (8,))
    ex = build_executor("data", pol, q,
                        bb.ExchangeConfig("compacted", data_spec=mspec))
    assert type(ex).__name__ == "MeshSplitExecutor" and ex.spec == mspec
    assert ex.carry_budget == 0
    pspec = MeshRaggedSpec("ppermute", 4, 16, (8, 0, 0))
    ex = build_executor("data", pol, q,
                        bb.ExchangeConfig("compacted", data_spec=pspec))
    assert type(ex).__name__ == "PermuteExecutor"


def test_mesh_ragged_spec_validation():
    with pytest.raises(ValueError, match="executor"):
        MeshRaggedSpec("bogus", 4, 8, (8,))
    with pytest.raises(ValueError, match="fabric widths"):
        MeshRaggedSpec("ppermute", 4, 8, (8,))
    with pytest.raises(ValueError, match="fabric widths"):
        MeshRaggedSpec("padded", 4, 8, (8, 8, 8))
    with pytest.raises(ValueError, match="per node"):
        MeshRaggedSpec("padded", 4, 8, (8,), budgets=(1, 1),
                       round_widths=(1,))
    spec = MeshRaggedSpec("ppermute", 4, 16, (8, 0, 4))
    assert spec.segments == (8, 4) and spec.offsets == (1, 3)
    assert spec.operand_cols == spec.exchanged_cols == 12
    padded = MeshRaggedSpec("padded", 4, 16, (8,))
    assert padded.segments == (8,) * 4 and padded.operand_cols == 32
    assert padded.exchanged_cols == 24           # the self segment stays
    # hashable → usable as a jit cache key inside ExchangeConfig; only
    # what the executor runs is in the key, the per-node summaries not
    twin = MeshRaggedSpec("ppermute", 4, 16, (8, 0, 4), budgets=(3,) * 4,
                          round_widths=(1,) * 4, local_rows=5,
                          fabric_rows=7, sizes=(16, 8, (8, 0, 4)))
    assert twin == spec and hash(twin) == hash(spec)
    assert MeshRaggedSpec("ppermute", 4, 32, (8, 0, 4)) != spec


def test_plan_mesh_ragged_spec_measures_diagonals():
    """Round width k must be the max over sources i of hist[i, (i+k)%N]."""
    n, q = 4, 8
    # node i sends everything to node (i + 1) % 4 → only round 1 is wide
    dest = jnp.asarray([[(i + 1) % n] * q for i in range(n)], jnp.int32)
    valid = jnp.ones((n, q), bool)
    spec = plan_mesh_ragged_spec(dest, valid, n, align=1)
    assert spec.round_widths == (0, q, 0, 0)
    assert spec.budgets == (q, q, q, q)      # every dest is SOME row's max
    # one chip offset carries everything: the ring ships one segment
    assert spec.executor == "ppermute" and spec.widths == (q, 0, 0)
    assert spec.exchanged_cols == q          # vs N·bmax = 4q padded
    # self traffic lands in round 0 — free
    dest0 = jnp.asarray([[i] * q for i in range(n)], jnp.int32)
    spec0 = plan_mesh_ragged_spec(dest0, valid, n, align=1)
    assert spec0.round_widths == (q, 0, 0, 0)
    assert spec0.exchanged_cols == 0


def test_permute_plan_covers_measured_traffic():
    """PermuteExecutor plans over a measured spec must have zero overflow
    and serve every valid request (the ppermute losslessness invariant):
    every row bound for another chip has its own send column, and rows
    for the same chip ship nothing."""
    for seed in range(5):
        ph, cid, pay, valid, mode = _batch(seed)
        client = jnp.arange(N, dtype=jnp.int32)[:, None]
        dest = route_data(mode, N, ph, cid, client, xp=jnp)
        for chips in (2, 4, 8):
            spec = plan_mesh_ragged_spec(dest, valid, N, n_chips=chips,
                                         align=1, executor="ppermute")
            ex = PermuteExecutor(N, spec)
            plan = ex.plan(dest, valid, client=client)
            assert int(np.asarray(plan.overflow).sum()) == 0
            assert bool(np.asarray(ex.served(plan))[np.asarray(valid)].all())
            L = N // chips
            ri = np.asarray(plan.reply_idx).reshape(chips, L, Q)
            v = np.asarray(valid).reshape(chips, L, Q)
            d = np.asarray(dest).reshape(chips, L, Q)
            for c in range(chips):
                fab = v[c] & (d[c] // L != c)
                assert (ri[c][fab] >= 0).all() and (ri[c][~fab] < 0).all()
                slots = ri[c][fab]
                assert len(set(slots.tolist())) == len(slots)
                assert (slots < spec.operand_cols).all()
            assert spec.fabric_rows == int((v & (d // L != np.arange(
                chips)[:, None, None])).sum())


# ---------------------------------------------------------------------------
# executor parity: padded + ppermute vs ragged-stacked vs dense
# ---------------------------------------------------------------------------
def _spec_pair(dest, owner, valid, executor, chips=N):
    d = plan_mesh_ragged_spec(dest, valid, N, n_chips=chips,
                              executor=executor)
    m = plan_mesh_ragged_spec(owner, valid, N, n_chips=chips,
                              executor=executor)
    return d, m


@pytest.mark.parametrize("executor", ["padded", "ppermute"])
def test_mesh_ragged_full_lifecycle_parity_stacked(executor):
    """Both mesh-ragged transports must be bit-for-bit the dense oracle —
    state tables after write, read replies, stat triples — on a mixed
    hybrid/hashed batch (the stacked backend runs the identical executor
    code the mesh runs; the subprocess test below covers the real
    collectives)."""
    pol = _mixed_policy()
    ph, cid, pay, valid, mode = _batch(1, modes=(2, 3, 4))
    client = jnp.arange(N, dtype=jnp.int32)[:, None]
    owner = route_meta(mode, N, pol.n_md_servers, ph, client, xp=jnp)

    s_dense = bb.init_state(N, 256, W, 256)
    s_dense = bb.forward_write(s_dense, pol, ph, cid, pay, valid, mode=mode,
                               config=bb.DENSE)

    # write destinations are computable up front; read dest for hybrid
    # rows resolves via the meta phase, so give the read its own spec
    dest_w = route_data(mode, N, ph, cid, client, xp=jnp)
    dspec, mspec = _spec_pair(dest_w, owner, valid, executor)
    cfg = bb.ExchangeConfig("compacted", data_spec=dspec, meta_spec=mspec)
    s = bb.init_state(N, 256, W, 256)
    s = bb.forward_write(s, pol, ph, cid, pay, valid, mode=mode, config=cfg)
    _assert_state_equal(s, s_dense)

    # hybrid read: resolve loc like the engine, then plan the data round
    _, fm, _, loc = bb.meta_op(
        s, pol, jnp.full_like(ph, bb.OP_STAT), ph, jnp.zeros_like(ph),
        jnp.full_like(ph, -1), valid & (mode == 4), mode=mode, config=cfg)
    data_loc = jnp.where(fm & (loc >= 0), loc,
                         jnp.broadcast_to(client, ph.shape))
    dest_r = route_data(mode, N, ph, cid, client, data_loc=data_loc, xp=jnp)
    dspec_r, _ = _spec_pair(dest_r, owner, valid, executor)
    cfg_r = bb.ExchangeConfig("compacted", data_spec=dspec_r,
                              meta_spec=mspec)
    p, f = bb.forward_read(s, pol, ph, cid, valid, mode=mode, config=cfg_r,
                           data_loc=data_loc)
    pd, fd = bb.forward_read(s_dense, pol, ph, cid, valid, mode=mode,
                             config=bb.DENSE)
    np.testing.assert_array_equal(np.asarray(p), np.asarray(pd))
    np.testing.assert_array_equal(np.asarray(f), np.asarray(fd))
    st, fn, sz, lc = bb.meta_op(
        s, pol, jnp.full_like(ph, bb.OP_STAT), ph, jnp.zeros_like(ph),
        jnp.full_like(ph, -1), valid, mode=mode, config=cfg)
    std, fnd, szd, lcd = bb.meta_op(
        s_dense, pol, jnp.full_like(ph, bb.OP_STAT), ph,
        jnp.zeros_like(ph), jnp.full_like(ph, -1), valid, mode=mode,
        config=bb.DENSE)
    for a, b in ((fn, fnd), (sz, szd), (lc, lcd)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# losslessness: skewed histograms × budgets {1, 2, q/4, q}
# ---------------------------------------------------------------------------
def _skewed_batch(shape_kind, seed, n=N, q=Q, w=W):
    """Batches whose destination histograms are deliberately skewed."""
    rng = np.random.RandomState(seed)
    if shape_kind == "one_file":       # every chunk of one file per node
        ph = np.repeat(rng.randint(1, 1 << 20, (n, 1)), q, axis=1)
        cid = np.tile(np.arange(q, dtype=np.int32), (n, 1))
    elif shape_kind == "incast":       # all nodes hammer one destination
        ph = np.full((n, q), 7919, np.int32)
        cid = rng.randint(0, 3, (n, q))
    else:                              # lopsided: half hot, half spread
        hot = np.repeat(rng.randint(1, 1 << 20, (n, 1)), q // 2, axis=1)
        spread = rng.randint(1, 1 << 20, (n, q - q // 2))
        ph = np.concatenate([hot, spread], axis=1)
        cid = rng.randint(0, 3, (n, q))
    # payload is a pure function of the key: cross-source duplicate keys
    # (incast) then store identical bytes whichever version "wins", so
    # the parity contract stays order-insensitive
    pay = np.broadcast_to(((ph * 7 + cid) % 9973)[..., None],
                          (n, q, w)).astype(np.int32)
    return (jnp.asarray(ph, jnp.int32), jnp.asarray(cid, jnp.int32),
            jnp.asarray(pay))


@pytest.mark.parametrize("budget", [1, 2, Q // 4, Q])
@pytest.mark.parametrize("shape_kind", ["one_file", "incast", "lopsided"])
def test_lossless_property_skewed_histograms(shape_kind, budget):
    """The lossless plane must equal the dense oracle on every observable
    at ANY uniform budget, for destination histograms built to overflow
    it (single-file concentration, incast, lopsided mixes)."""
    policy = LayoutPolicy.uniform(LayoutMode.DIST_HASH, N)
    ph, cid, pay = _skewed_batch(shape_kind, seed=budget)
    req = BBRequest(path_hash=ph, chunk_id=cid, payload=pay)
    dense = BBClient(policy, cap=4 * Q, words=W, mcap=4 * Q,
                     exchange="dense")
    tight = BBClient(policy, cap=4 * Q, words=W, mcap=4 * Q,
                     exchange="compacted", budget=budget, meta_budget=Q)
    dense.write(req)
    tight.write(req)
    assert int(np.asarray(tight.state.dropped).sum()) == 0
    # carried requests append AFTER round-1 ones, so raw table layout may
    # differ from dense — the lossless contract is on counts + observables
    np.testing.assert_array_equal(np.asarray(dense.state.data_count),
                                  np.asarray(tight.state.data_count))
    np.testing.assert_array_equal(np.asarray(dense.state.meta_count),
                                  np.asarray(tight.state.meta_count))
    out_d, f_d = dense.read(req)
    out_t, f_t = tight.read(req)
    np.testing.assert_array_equal(np.asarray(out_d), np.asarray(out_t))
    np.testing.assert_array_equal(np.asarray(f_d), np.asarray(f_t))
    for a, b in zip(dense.stat(req), tight.stat(req)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("shape_kind", ["one_file", "incast", "lopsided"])
def test_mesh_ragged_lossless_on_skewed_histograms(shape_kind):
    """Measured mesh-ragged plans (both transports) cover skewed
    histograms with zero overflow and dense-identical state."""
    policy = LayoutPolicy.uniform(LayoutMode.DIST_HASH, N)
    ph, cid, pay = _skewed_batch(shape_kind, seed=11)
    valid = jnp.ones(ph.shape, bool)
    client = jnp.arange(N, dtype=jnp.int32)[:, None]
    mode = jnp.full(ph.shape, int(LayoutMode.DIST_HASH), jnp.int32)
    dest = route_data(mode, N, ph, cid, client, xp=jnp)
    owner = route_meta(mode, N, policy.n_md_servers, ph, client, xp=jnp)
    s_dense = bb.forward_write(bb.init_state(N, 4 * Q, W, 4 * Q), policy,
                               ph, cid, pay, valid, mode=mode,
                               config=bb.DENSE)
    for executor in ("padded", "ppermute"):
        dspec, mspec = _spec_pair(dest, owner, valid, executor)
        cfg = bb.ExchangeConfig("compacted", data_spec=dspec,
                                meta_spec=mspec)
        s = bb.forward_write(bb.init_state(N, 4 * Q, W, 4 * Q), policy,
                             ph, cid, pay, valid, mode=mode, config=cfg)
        _assert_state_equal(s, s_dense)


# ---------------------------------------------------------------------------
# two-phase hybrid read
# ---------------------------------------------------------------------------
def test_two_phase_hybrid_read_parity():
    """The two-phase client (probe → ragged data round) must answer every
    read/stat identically to the one-phase uniform plan AND the dense
    oracle, across writers scattered by a mixed hybrid/hashed policy."""
    pol = _mixed_policy()
    rng = np.random.RandomState(5)
    paths = [[(f"/bb/hot/r{i}/f{j % 3}" if j % 2 else f"/shared/g{j}")
              for j in range(Q)] for i in range(N)]
    cid = rng.randint(0, 4, (N, Q)).astype(np.int32)
    pay = rng.randint(0, 9999, (N, Q, W)).astype(np.int32)
    clients = {
        "dense": BBClient(pol, cap=256, words=W, mcap=256,
                          exchange="dense"),
        "one_phase": BBClient(pol, cap=256, words=W, mcap=256,
                              exchange="compacted", two_phase=False),
        "two_phase": BBClient(pol, cap=256, words=W, mcap=256,
                              exchange="compacted", two_phase=True),
    }
    reqs = {k: c.encode(paths, chunk_id=cid, payload=pay)
            for k, c in clients.items()}
    for k, c in clients.items():
        c.write(reqs[k])
    _assert_state_equal(clients["dense"].state, clients["two_phase"].state)
    # cross-rank read: hybrid rows must chase the recorded data location
    perm = np.roll(np.arange(N), 3)
    outs = {}
    for k, c in clients.items():
        r = reqs[k]
        outs[k] = c.read(BBRequest(path_hash=r.path_hash[perm],
                                   chunk_id=r.chunk_id[perm],
                                   scope_hash=r.scope_hash[perm]))
    for k in ("one_phase", "two_phase"):
        np.testing.assert_array_equal(np.asarray(outs["dense"][0]),
                                      np.asarray(outs[k][0]))
        np.testing.assert_array_equal(np.asarray(outs["dense"][1]),
                                      np.asarray(outs[k][1]))
    # the two-phase client actually planned a measured data spec for the
    # read (the one-phase client cannot — destinations are table state)
    assert ("read", "data", Q) in clients["two_phase"]._spec_floor
    for a, b in zip(clients["dense"].stat(reqs["dense"]),
                    clients["two_phase"].stat(reqs["two_phase"])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# telemetry-driven ragged presizing
# ---------------------------------------------------------------------------
def test_presizing_converges_to_one_spec():
    """A steady workload must converge to ONE ragged spec (one jit
    specialization): the running floor absorbs per-batch histogram
    jitter after warmup."""
    policy = LayoutPolicy.uniform(LayoutMode.DIST_HASH, N)
    client = BBClient(policy, cap=256, words=W, mcap=256,
                      exchange="compacted", telemetry=True)
    specs = []
    for seed in range(12):
        ph, cid, pay, valid, _ = _batch(seed, modes=(3,))
        mode = jnp.full(ph.shape, 3, jnp.int32)
        cfg = client._call_config("write", mode, ph, cid, valid)
        specs.append((cfg.data_spec, cfg.meta_spec))
    warm = specs[4:]
    assert len({d for d, _ in warm}) == 1, "data specs did not converge"
    assert len({m for _, m in warm}) == 1, "meta specs did not converge"
    # floors only ever widen → later plans always cover earlier maxima
    floors = client._spec_floor[("write", "data", Q)]
    assert (np.asarray(specs[-1][0].budgets) >= 0).all()
    assert (floors >= np.asarray(specs[0][0].budgets)).all()


def test_suggest_align_tracks_extent():
    from repro.core.adapt.telemetry import ScopeTelemetry
    policy = LayoutPolicy.uniform(LayoutMode.DIST_HASH, N)
    t = ScopeTelemetry(policy)
    assert t.suggest_align(64) == 8          # no signal yet → default
    ph = jnp.asarray(np.arange(1, N * Q + 1).reshape(N, Q), jnp.int32)
    big_cid = jnp.full((N, Q), 40, jnp.int32)       # extent bin ≥ 16
    dest = jnp.zeros((N, Q), jnp.int32)
    for _ in range(4):
        t.record("write", None, ph, big_cid, dest,
                 jnp.ones((N, Q), bool), words=W, n_nodes=N)
    a = t.suggest_align(64)
    assert a > 8 and a <= 32
    assert t.suggest_align(8) == 8           # clamped to q // 2 floor-of-8


def test_per_node_telemetry_matches_flat():
    """Per-node counters (the mesh-shardable layout) must reduce to the
    exact flat counters for the same call stream."""
    from repro.core.adapt.telemetry import ScopeTelemetry
    policy = _mixed_policy()
    flat = ScopeTelemetry(policy)
    pern = ScopeTelemetry(policy, per_node=N)
    rng = np.random.RandomState(2)
    for seed in range(3):
        ph, cid, pay, valid, mode = _batch(seed)
        sh = jnp.asarray(rng.randint(0, 3, (N, Q)), jnp.int32)
        dest = jnp.asarray(rng.randint(0, N, (N, Q)), jnp.int32)
        for kind in ("write", "read", "meta"):
            hint = jnp.asarray(rng.rand(N, Q) > 0.5)
            for t in (flat, pern):
                t.record(kind, sh, ph, cid, dest, valid,
                         words=0 if kind == "meta" else W,
                         self_hint=hint if kind == "read" else None,
                         n_nodes=N)
    assert pern.counts.shape == (N,) + flat.counts.shape
    from repro.core.adapt.telemetry import F_EXTENT_MAX

    def but_extent_max(c):
        return np.delete(c, F_EXTENT_MAX, axis=-1)

    # the node-sum view matches exactly — except F_EXTENT_MAX, where the
    # reduction sums per-node maxima (a documented upper bound; the
    # signature's extent dimension reads the histogram bins instead)
    np.testing.assert_allclose(but_extent_max(pern.snapshot()),
                               but_extent_max(flat.snapshot()),
                               rtol=1e-6, atol=1e-4)
    assert (pern.snapshot()[:, F_EXTENT_MAX] >=
            flat.snapshot()[:, F_EXTENT_MAX] - 1e-6).all()
    # rebind keeps surviving scopes' history in both layouts
    pern.rebind(policy)
    np.testing.assert_allclose(but_extent_max(pern.snapshot()),
                               but_extent_max(flat.snapshot()),
                               rtol=1e-6, atol=1e-4)


# ---------------------------------------------------------------------------
# the measured fabric model
# ---------------------------------------------------------------------------
def test_fabric_model_fit_and_fallback(tmp_path):
    # no artifact → analytic fallback, flagged unmeasured
    xs.refresh()
    a, bw, measured = xs.fabric_model(str(tmp_path))
    assert (a, bw) == xs.FALLBACK_FABRIC and not measured
    # a measured artifact: us = 10 + bytes / 100
    rows = [{"us_per_call": 10 + b / 100, "exchanged_bytes": b}
            for b in (1000, 10000, 100000)]
    (tmp_path / "BENCH_pr5.json").write_text(
        json.dumps({"fabric": {"rows": rows}}))
    xs.refresh()
    a, bw, measured = xs.fabric_model(str(tmp_path))
    assert measured and abs(a - 10) < 1e-6 and abs(bw - 100) < 1e-3
    # malformed rows degrade to the fallback, never raise
    (tmp_path / "BENCH_pr5.json").write_text(
        json.dumps({"fabric": {"rows": [None, {"us_per_call": "x"}]}}))
    xs.refresh()
    assert xs.fabric_model(str(tmp_path))[2] is False
    xs.refresh()


def test_pick_mesh_executor_crossover():
    model = (50.0, 100.0)          # 50 µs overhead, 100 B/µs
    # even histogram: padded ships the same bytes in ONE collective
    assert xs.pick_mesh_executor(8, 8000, [1000] * 7, model) == "padded"
    # skew: one hot diagonal, everything else empty → one cheap round
    assert xs.pick_mesh_executor(8, 80000, [1000], model) == "ppermute"
    # latency-free fabric → the byte-optimal plan always wins
    assert xs.pick_mesh_executor(8, 8000, [999] * 8,
                                 (0.0, 100.0)) == "ppermute"


def test_migration_cost_uses_measured_fabric():
    from repro.core.adapt import redecide
    analytic = redecide.migration_cost_s(1024, W, N, fabric=None) \
        if xs.fabric_model()[2] else None
    fast = redecide.migration_cost_s(1024, W, N, fabric=(10.0, 1e6))
    slow = redecide.migration_cost_s(1024, W, N, fabric=(10.0, 1e2))
    assert slow > fast > 0
    if analytic is not None:
        assert analytic > 0
    d = redecide.PolicyDelta("/bb/hot", LayoutMode.NODE_LOCAL,
                             LayoutMode.DIST_HASH, 2.0, 1.0)
    ok, audit = redecide.gate_delta(d, 256, W, N, horizon_rounds=1e4)
    assert ok and "fabric_measured" in audit


# ---------------------------------------------------------------------------
# the real mesh: PR-4 pinned stream digest + telemetry psum (subprocess)
# ---------------------------------------------------------------------------
MESH_PLAN_SCRIPT = textwrap.dedent("""
    import os
    os.environ['XLA_FLAGS'] = '--xla_force_host_platform_device_count=8'
    import sys; sys.path.insert(0, 'src'); sys.path.insert(0, 'tests')
    import jax, jax.numpy as jnp, numpy as np
    from repro.core import burst_buffer as bb
    from repro.core.client import BBClient, BBRequest
    from repro.core.layouts import LayoutMode
    from repro.core.mesh_engine import (build_telemetry_reduce,
                                        make_node_mesh)
    from repro.core.policy import LayoutPolicy

    # 1. the PR-4 digest-pinned op stream, driven through the MESH backend
    #    with ragged planning on: every observable must still hit the
    #    frozen digest (ragged-mesh ≡ ragged-stacked ≡ dense).
    from test_adapt import STREAM_DIGEST, _digest, _interleaved_stream
    mesh = make_node_mesh(8)
    client, outs = _interleaved_stream(relayout=False, backend=mesh)
    assert _digest(*outs) == STREAM_DIGEST, "mesh stream digest drifted"

    # 2. forced-ppermute lifecycle on the real collective ring, vs dense
    pol = LayoutPolicy.from_scopes({"/bb/hot": LayoutMode.HYBRID},
                                   n_nodes=8, default=LayoutMode.DIST_HASH)
    rng = np.random.RandomState(0)
    q, w = 16, 8
    ph = jnp.asarray(rng.randint(1, 1 << 20, (8, q)), jnp.int32)
    cid = jnp.asarray(rng.randint(0, 4, (8, q)), jnp.int32)
    pay = jnp.asarray(rng.randint(0, 999, (8, q, w)), jnp.int32)
    valid = jnp.ones((8, q), bool)
    mode = jnp.asarray(rng.choice([3, 4], (8, q)), jnp.int32)
    from repro.core.layouts import route_data, route_meta
    ranks = jnp.arange(8, dtype=jnp.int32)[:, None]
    dest = route_data(mode, 8, ph, cid, ranks, xp=jnp)
    owner = route_meta(mode, 8, pol.n_md_servers, ph, ranks, xp=jnp)
    cfg = bb.ExchangeConfig(
        "compacted",
        data_spec=bb.plan_mesh_ragged_spec(dest, valid, 8,
                                           executor="ppermute"),
        meta_spec=bb.plan_mesh_ragged_spec(owner, valid, 8,
                                           executor="ppermute"))
    from repro.core.mesh_engine import build_mesh_ops
    write, read, meta, read_loc = build_mesh_ops(mesh, pol, cfg)
    dense_write = build_mesh_ops(mesh, pol, bb.DENSE)[0]
    sm = bb.init_state(8, 256, w, 256)
    sd = bb.init_state(8, 256, w, 256)
    sm = write(sm, mode, ph, cid, pay, valid)
    sd = dense_write(sd, mode, ph, cid, pay, valid)
    for a, b in zip(sm.tree_flatten()[0], sd.tree_flatten()[0]):
        assert np.array_equal(np.asarray(a), np.asarray(b)), "ppermute!"

    # a forced-compacted mesh client must plan mesh-ragged specs per call
    cc = BBClient(pol, mesh, cap=256, words=w, mcap=256,
                  exchange="compacted")
    cc.write(BBRequest(path_hash=ph, chunk_id=cid, payload=pay,
                       mode=mode))
    specs = [s for c in cc._mesh_ops
             for s in (c.data_spec, c.meta_spec) if s is not None]
    assert specs and all(isinstance(s, bb.MeshRaggedSpec) for s in specs)

    # 3. mesh-wide telemetry reduction: the psum'd per-node counters must
    #    equal the host-side sum, replicated on every device
    tel = client.telemetry
    assert tel.per_node == 8
    reduce = build_telemetry_reduce(mesh)
    reduced = np.asarray(reduce(tel.counts))
    np.testing.assert_allclose(reduced, tel.snapshot(), rtol=1e-5,
                               atol=1e-3)
    print('MESH_PLAN_OK')
""")


@pytest.mark.slow
@pytest.mark.mesh
def test_mesh_ragged_pinned_stream_and_telemetry_reduce():
    """Real 8-device shard_map run: the PR-4 pinned op stream digest must
    hold on the ragged mesh data plane, a forced-ppermute write must be
    bit-for-bit dense, and ``build_telemetry_reduce`` must psum the
    per-node counters to the host-side truth."""
    r = subprocess.run([sys.executable, "-c", MESH_PLAN_SCRIPT],
                       capture_output=True, text=True, timeout=900,
                       cwd=str(ROOT))
    assert "MESH_PLAN_OK" in r.stdout, r.stdout + r.stderr


SHARDED_PLAN_SCRIPT = textwrap.dedent("""
    import os
    os.environ['XLA_FLAGS'] = '--xla_force_host_platform_device_count=4'
    import sys; sys.path.insert(0, 'src')
    import functools
    import jax, numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as PS
    from repro.core import exchange_plan
    from repro.core.mesh_engine import NODE_AXIS, make_node_mesh
    from repro.kernels.chunk_router.chunk_router import (
        dest_histogram2d_kernel)

    rng = np.random.RandomState(0)
    dest = rng.randint(0, 4, (4, 64)).astype(np.int32)
    valid = rng.rand(4, 64) < 0.8
    want = exchange_plan.plan_mesh_ragged_spec(dest, valid, 4)
    # the chip's path: planning from node-sharded outputs of a mesh op (a
    # two-phase read's data_loc), with any device histogram interpreted
    exchange_plan.histogram_rows2d = functools.partial(
        dest_histogram2d_kernel, interpret=True)
    shard = NamedSharding(make_node_mesh(4), PS(NODE_AXIS))
    got = exchange_plan.plan_mesh_ragged_spec(
        jax.device_put(dest, shard), jax.device_put(valid, shard), 4)
    assert got == want, (got, want)
    print('SHARDED_PLAN_OK')
""")


@pytest.mark.mesh
def test_mesh_ragged_spec_from_node_sharded_inputs():
    """The mesh planner plans from node-sharded inputs (regression: a
    device histogram kernel was handed the sharded array and refused
    it; the planner now copies both arrays to the host in one sync)."""
    r = subprocess.run([sys.executable, "-c", SHARDED_PLAN_SCRIPT],
                       capture_output=True, text=True, timeout=600,
                       cwd=str(ROOT))
    assert "SHARDED_PLAN_OK" in r.stdout, r.stdout + r.stderr
