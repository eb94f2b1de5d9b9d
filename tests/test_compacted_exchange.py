"""Compacted exchange data plane: dense-vs-compacted parity (all modes,
mixed-mode batches), seed-digest pinning of the dense oracle, losslessness
of the ragged and multi-round-carry plans at any budget ≥ 1, the legacy
drop plane's overflow accounting, reply-permutation round-trips, per-call
backend auto-selection and the client-side caches."""
import subprocess
import sys
import textwrap

import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import burst_buffer as bb
from repro.core.client import BBClient, BBRequest, _build_stacked_ops
from repro.core.layouts import (LayoutMode, LayoutParams, f_data, f_meta_f,
                                route_data, route_meta, str_hash)
from repro.core.policy import LayoutPolicy

from test_policy import SEED_DIGESTS, _digest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:                    # pragma: no cover - env dependent
    from _minihyp import given, settings, strategies as st

N, Q, W = 8, 5, 8


def _state_arrays(state):
    return state.tree_flatten()[0]


def _assert_state_equal(a, b):
    for x, y in zip(_state_arrays(a), _state_arrays(b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# ---------------------------------------------------------------------------
# seed-digest pinning: the dense client path IS the PR-1 engine, and at
# these sizes the compacted auto-budgets degenerate to B = q, so the
# compacted path must hit the very same bits.
# ---------------------------------------------------------------------------
def _client_trace(mode, exchange):
    policy = LayoutPolicy.uniform(mode, N)
    client = BBClient(policy, cap=64, words=W, mcap=64, exchange=exchange)
    rng = np.random.RandomState(42)
    ph = jnp.asarray(rng.randint(1, 1 << 20, (N, Q)), jnp.int32)
    cid = jnp.asarray(rng.randint(0, 4, (N, Q)), jnp.int32)
    payload = jnp.asarray(rng.randint(0, 9999, (N, Q, W)), jnp.int32)
    client.write(BBRequest(path_hash=ph, chunk_id=cid, payload=payload))
    # the digest is taken before the stat, which donates this state
    state = client.state
    state_digest = _digest(state.data, state.data_keys, state.data_count,
                           state.meta_key, state.meta_size, state.meta_loc,
                           state.meta_count, state.dropped)
    perm = rng.permutation(N)
    rpay, rfound = client.read(BBRequest(path_hash=ph[perm],
                                         chunk_id=cid[perm]))
    fnd, size, loc = client.stat(BBRequest(path_hash=ph))
    return {"state": state_digest,
            "read": _digest(rpay, rfound),
            "meta": _digest(fnd, size, loc)}


@pytest.mark.parametrize("exchange", ["dense", "compacted"])
@pytest.mark.parametrize("mode", list(LayoutMode))
def test_client_trace_pins_seed_digests(mode, exchange):
    assert _client_trace(mode, exchange) == SEED_DIGESTS[int(mode)]


# ---------------------------------------------------------------------------
# mixed-mode parity: one interleaved batch over three modes, full state and
# every reply compared element-for-element after each op
# ---------------------------------------------------------------------------
def _hetero_policy(n=N):
    return LayoutPolicy.from_scopes(
        {"/bb/ckpt": LayoutMode.HYBRID, "/bb/shared": LayoutMode.DIST_HASH},
        n_nodes=n, default=LayoutMode.CENTRAL_META)


def test_mixed_mode_full_lifecycle_parity():
    q = 6
    rng = np.random.RandomState(3)
    paths = [[(f"/bb/ckpt/rank{r}/f{j}" if j % 3 == 0 else
               f"/bb/shared/obj{r * q + j}" if j % 3 == 1 else
               f"/bb/other/g{r * q + j}") for j in range(q)]
             for r in range(N)]
    valid = jnp.asarray(rng.rand(N, q) > 0.2)
    clients = {}
    for kind in ("dense", "compacted"):
        clients[kind] = BBClient(_hetero_policy(), cap=128, words=W,
                                 mcap=256, exchange=kind)
    req = clients["dense"].encode(
        paths, chunk_id=rng.randint(0, 3, (N, q)),
        payload=rng.randint(0, 9999, (N, q, W)), valid=valid)
    for c in clients.values():
        c.write(req)
    _assert_state_equal(clients["dense"].state, clients["compacted"].state)
    outs = {k: c.read(req) for k, c in clients.items()}
    np.testing.assert_array_equal(*[np.asarray(outs[k][0]) for k in outs])
    np.testing.assert_array_equal(*[np.asarray(outs[k][1]) for k in outs])
    stats = {k: c.stat(req) for k, c in clients.items()}
    for i in range(3):
        np.testing.assert_array_equal(np.asarray(stats["dense"][i]),
                                      np.asarray(stats["compacted"][i]))
    for c in clients.values():
        c.remove(req)
    _assert_state_equal(clients["dense"].state, clients["compacted"].state)
    fnd_d, _, _ = clients["dense"].stat(req)
    fnd_c, _, _ = clients["compacted"].stat(req)
    np.testing.assert_array_equal(np.asarray(fnd_d), np.asarray(fnd_c))
    assert not np.asarray(fnd_c).any()


# ---------------------------------------------------------------------------
# overflow / budget accounting
# ---------------------------------------------------------------------------
def test_overflow_is_accounted_exactly():
    """Legacy drop plane (``lossless=False``): budget=1 → only the first
    request per (source, destination) survives; everything else must land
    in ``dropped`` — data and metadata drops."""
    n, q, w = 4, 16, 4
    policy = LayoutPolicy.uniform(LayoutMode.DIST_HASH, n)
    params = LayoutParams(mode=LayoutMode.DIST_HASH, n_nodes=n)
    writer = BBClient(policy, cap=256, words=w, mcap=256,
                      exchange="compacted", budget=1, lossless=False)
    ph = np.arange(1, n * q + 1, dtype=np.int32).reshape(n, q)
    cid = np.zeros((n, q), np.int32)
    payload = np.broadcast_to(ph[..., None], (n, q, w)).astype(np.int32)
    writer.write(BBRequest(path_hash=jnp.asarray(ph),
                           chunk_id=jnp.asarray(cid),
                           payload=jnp.asarray(payload)))

    client_rank = np.arange(n, dtype=np.int32)[:, None]
    dest = np.asarray(f_data(params, ph, cid, client_rank))
    owner = np.asarray(f_meta_f(params, ph, client_rank))

    def survivors(d, eligible):
        surv = np.zeros((n, q), bool)
        for r in range(n):
            seen = set()
            for j in range(q):
                if eligible[r, j] and d[r, j] not in seen:
                    seen.add(d[r, j])
                    surv[r, j] = True
        return surv

    surv_data = survivors(dest, np.ones((n, q), bool))
    # metadata is only attempted for writes whose payload survived (no
    # phantom entries), then faces its own per-owner budget
    surv_meta = survivors(owner, surv_data)
    drops = (n * q - surv_data.sum()) + (surv_data.sum() - surv_meta.sum())
    assert int(np.asarray(writer.state.dropped).sum()) == drops
    assert int(np.asarray(writer.state.data_count).sum()) == surv_data.sum()
    assert int(np.asarray(writer.state.meta_count).sum()) == surv_meta.sum()

    # a lossless-budget reader over the same state finds exactly the
    # chunks that survived the writer's budget
    reader = BBClient(policy, cap=256, words=w, mcap=256,
                      exchange="compacted", budget=q, state=writer.state)
    req = BBRequest(path_hash=jnp.asarray(ph), chunk_id=jnp.asarray(cid))
    _, found = reader.read(req)
    np.testing.assert_array_equal(np.asarray(found), surv_data)
    # no phantom metadata: every stat()-visible file has its chunk stored
    found_meta, _, _ = reader.stat(req)
    np.testing.assert_array_equal(np.asarray(found_meta), surv_meta)
    assert not (np.asarray(found_meta) & ~surv_data).any()


def test_read_overflow_returns_not_found_not_garbage():
    """Legacy drop plane: read-side budget overflow must yield
    found=False/zero payload for the requests that did not fit — never
    another request's reply."""
    n, q, w = 4, 8, 4
    policy = LayoutPolicy.uniform(LayoutMode.DIST_HASH, n)
    full = BBClient(policy, cap=128, words=w, mcap=128, exchange="dense")
    ph = np.arange(1, n * q + 1, dtype=np.int32).reshape(n, q)
    cid = np.zeros((n, q), np.int32)
    payload = np.broadcast_to(ph[..., None], (n, q, w)).astype(np.int32)
    req = BBRequest(path_hash=jnp.asarray(ph), chunk_id=jnp.asarray(cid),
                    payload=jnp.asarray(payload))
    full.write(req)
    tight = BBClient(policy, cap=128, words=w, mcap=128,
                     exchange="compacted", budget=1, lossless=False,
                     state=full.state)
    out, found = tight.read(req)
    out, found = np.asarray(out), np.asarray(found)
    assert found.sum() < n * q                     # some overflowed
    assert (out[found] == ph[found][:, None]).all()  # hits are the right rows
    assert (out[~found] == 0).all()                # misses are zero, not junk


def test_budget_auto_sizing_rules():
    cfg = bb.COMPACTED
    hash_pol = LayoutPolicy.uniform(LayoutMode.DIST_HASH, 32)
    assert bb.data_budget(hash_pol, 256, cfg) == 16      # 2·256/32
    local_pol = LayoutPolicy.uniform(LayoutMode.NODE_LOCAL, 32)
    assert bb.data_budget(local_pol, 256, cfg) == 256    # concentration
    hybrid_pol = LayoutPolicy.uniform(LayoutMode.HYBRID, 32)
    assert bb.data_budget(hybrid_pol, 256, cfg) == 256   # data_loc reads
    central = LayoutPolicy.uniform(LayoutMode.CENTRAL_META, 32)
    # metadata auto is ALWAYS lossless: route_meta keys on path_hash
    # alone, so a per-file chunk batch concentrates on one owner no
    # matter the mode — hash-spread sizing needs an explicit meta_budget
    for pol in (hash_pol, local_pol, hybrid_pol, central):
        assert bb.meta_budget(pol, 256, cfg) == 256
    # explicit budgets are clamped to [1, q] and never auto-rounded
    tight = bb.ExchangeConfig("compacted", budget=3)
    assert bb.data_budget(hash_pol, 256, tight) == 3
    assert bb.meta_budget(hash_pol, 256, tight) == 3
    assert bb.data_budget(hash_pol, 2, tight) == 2
    split = bb.ExchangeConfig("compacted", budget=4, meta_budget=6)
    assert bb.meta_budget(hash_pol, 256, split) == 6


def test_per_file_chunk_batch_keeps_full_metadata():
    """Each node writes q chunks of ONE file (the checkpoint pattern): all
    its metadata ops hit a single hash owner.  The default compacted
    client must keep every one of them — stat() sizes equal to the chunk
    count, nothing dropped, bit-for-bit with dense."""
    n, q, w = 8, 16, 4
    rng = np.random.RandomState(9)
    ph = np.repeat(rng.randint(1, 1 << 20, (n, 1)).astype(np.int32), q,
                   axis=1)
    cid = np.tile(np.arange(q, dtype=np.int32), (n, 1))
    payload = rng.randint(0, 9999, (n, q, w)).astype(np.int32)
    req = BBRequest(path_hash=jnp.asarray(ph), chunk_id=jnp.asarray(cid),
                    payload=jnp.asarray(payload))
    policy = LayoutPolicy.uniform(LayoutMode.DIST_HASH, n)
    clients = {}
    for kind in ("dense", "compacted"):
        c = BBClient(policy, cap=256, words=w, mcap=64, exchange=kind)
        c.write(req)
        fnd, size, _ = c.stat(req)
        assert bool(np.asarray(fnd).all()), kind
        np.testing.assert_array_equal(np.asarray(size),
                                      np.full((n, q), q, np.int32))
        assert int(np.asarray(c.state.dropped).sum()) == 0, kind
        clients[kind] = c
    _assert_state_equal(clients["dense"].state, clients["compacted"].state)


# ---------------------------------------------------------------------------
# reply permutation round-trip
# ---------------------------------------------------------------------------
def test_reply_permutation_round_trip_with_holes():
    """Shuffled read requests with invalid holes: every valid slot gets its
    own chunk back through the inverse permutation; holes stay zero."""
    n, q, w = 8, 12, 4
    rng = np.random.RandomState(11)
    policy = LayoutPolicy.uniform(LayoutMode.DIST_HASH, n)
    client = BBClient(policy, cap=256, words=w, mcap=256,
                      exchange="compacted")
    ph = np.arange(1, n * q + 1, dtype=np.int32).reshape(n, q)
    cid = np.zeros((n, q), np.int32)
    payload = np.broadcast_to(ph[..., None], (n, q, w)).astype(np.int32)
    client.write(BBRequest(path_hash=jnp.asarray(ph),
                           chunk_id=jnp.asarray(cid),
                           payload=jnp.asarray(payload)))
    perm = np.stack([rng.permutation(q) for _ in range(n)])
    ph_s = np.take_along_axis(ph, perm, axis=1)
    valid = rng.rand(n, q) > 0.3
    out, found = client.read(BBRequest(path_hash=jnp.asarray(ph_s),
                                       chunk_id=jnp.asarray(cid),
                                       valid=jnp.asarray(valid)))
    out, found = np.asarray(out), np.asarray(found)
    np.testing.assert_array_equal(found, valid)
    np.testing.assert_array_equal(out[valid], ph_s[valid][:, None] *
                                  np.ones((1, w), np.int32))
    assert (out[~valid] == 0).all()


# ---------------------------------------------------------------------------
# property sweep: random batches, modes, and validity — dense vs compacted
# ---------------------------------------------------------------------------
@settings(max_examples=8, deadline=None)
@given(st.integers(min_value=0, max_value=10 ** 6))
def test_property_dense_compacted_parity(seed):
    n, q, w = 4, 7, 4
    rng = np.random.RandomState(seed % (2 ** 31))
    policy = LayoutPolicy.from_scopes(
        {"/bb/ckpt": LayoutMode.HYBRID}, n_nodes=n,
        default=LayoutMode.DIST_HASH)
    mode = jnp.asarray(rng.choice([int(LayoutMode.HYBRID),
                                   int(LayoutMode.DIST_HASH)], (n, q)),
                       jnp.int32)
    ph = jnp.asarray(rng.randint(1, 1 << 20, (n, q)), jnp.int32)
    cid = jnp.asarray(rng.randint(0, 3, (n, q)), jnp.int32)
    payload = jnp.asarray(rng.randint(0, 9999, (n, q, w)), jnp.int32)
    valid = jnp.asarray(rng.rand(n, q) > 0.25)
    cfg = bb.ExchangeConfig("compacted")
    s_d = bb.init_state(n, 64, w, 64)
    s_c = bb.init_state(n, 64, w, 64)
    s_d = bb.forward_write(s_d, policy, ph, cid, payload, valid, mode=mode)
    s_c = bb.forward_write(s_c, policy, ph, cid, payload, valid, mode=mode,
                           config=cfg)
    for a, b in zip(_state_arrays(s_d), _state_arrays(s_c)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    r_d = bb.forward_read(s_d, policy, ph, cid, valid, mode=mode)
    r_c = bb.forward_read(s_c, policy, ph, cid, valid, mode=mode, config=cfg)
    np.testing.assert_array_equal(np.asarray(r_d[0]), np.asarray(r_c[0]))
    np.testing.assert_array_equal(np.asarray(r_d[1]), np.asarray(r_c[1]))
    stat = jnp.full((n, q), bb.OP_STAT, jnp.int32)
    zeros = jnp.zeros((n, q), jnp.int32)
    neg = jnp.full((n, q), -1, jnp.int32)
    m_d = bb.meta_op(s_d, policy, stat, ph, zeros, neg, valid, mode=mode)
    m_c = bb.meta_op(s_c, policy, stat, ph, zeros, neg, valid, mode=mode,
                     config=cfg)
    for a, b in zip(m_d[1:], m_c[1:]):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# client-side plumbing: defaults, validation, cached ops, memoized encode
# ---------------------------------------------------------------------------
def test_client_exchange_defaults_and_validation():
    policy = LayoutPolicy.uniform(LayoutMode.DIST_HASH, 4)
    client = BBClient(policy)
    assert client.exchange_mode == "auto"          # per-call backend pick
    assert client.exchange_config.kind == "compacted"
    assert client.exchange_config.lossless         # drops retired by default
    with pytest.raises(ValueError, match="exchange"):
        BBClient(policy, exchange="bogus")
    cfg = BBClient(policy, exchange="dense").exchange_config
    assert cfg == bb.DENSE
    # auto resolves each call to a real backend from the measured table
    from repro.core import exchange_select
    for q in (1, 8, 64, 512):
        assert client._select_kind(q) in ("dense", "compacted")
        assert client._select_kind(q) == exchange_select.pick_backend(
            4, q, client.words)


def test_stacked_ops_cached_per_engine_key():
    """Policies that differ only in scope strings share one engine
    specialization — constructing many clients must not retrace."""
    p1 = LayoutPolicy.from_scopes({"/a": LayoutMode.CENTRAL_META},
                                  n_nodes=8, default=LayoutMode.DIST_HASH)
    p2 = LayoutPolicy.from_scopes({"/completely/else":
                                   LayoutMode.CENTRAL_META},
                                  n_nodes=8, default=LayoutMode.DIST_HASH)
    assert p1.engine_key() == p2.engine_key()
    assert LayoutPolicy.for_engine_key(p1.engine_key()).engine_key() == \
        p1.engine_key()
    c1, c2 = BBClient(p1), BBClient(p2)
    cfg = bb.COMPACTED
    assert c1._ops(cfg) is c2._ops(cfg)          # one jitted specialization
    # different exchange config → different specialization
    assert _build_stacked_ops(p1, bb.DENSE) is not c1._ops(cfg)
    assert _build_stacked_ops(p1, bb.DENSE) is _build_stacked_ops(p2,
                                                                  bb.DENSE)


def test_encode_memoizes_path_hashing():
    policy = _hetero_policy(4)
    client = BBClient(policy, cap=16, words=4, mcap=16)
    paths = [[f"/bb/ckpt/f{j}" for j in range(3)] for _ in range(4)]
    req1 = client.encode(paths)
    before = client._path_codes.cache_info()
    req2 = client.encode(paths)
    after = client._path_codes.cache_info()
    assert after.hits >= before.hits + 12        # steady state: all hits
    np.testing.assert_array_equal(np.asarray(req1.path_hash),
                                  np.asarray(req2.path_hash))
    # memoized values match the uncached resolution
    assert req1.path_hash[0, 1] == str_hash("/bb/ckpt/f1")
    assert req1.scope_hash[0, 1] == policy.scope_hash_of("/bb/ckpt/f1")


def test_float_payload_keys_survive_fused_exchange():
    """A float32 payload must not promote the fused buffer and round the
    31-bit routing keys (regression: keys rode the concatenated buffer in
    the payload dtype).  Both planes truncate the payload to the int32
    tables identically."""
    n, q, w = 4, 8, 4
    rng = np.random.RandomState(5)
    policy = LayoutPolicy.uniform(LayoutMode.DIST_HASH, n)
    ph = jnp.asarray(rng.randint(1 << 25, 1 << 30, (n, q)), jnp.int32)
    cid = jnp.zeros((n, q), jnp.int32)
    payload = jnp.asarray(rng.rand(n, q, w) * 1000, jnp.float32)
    req = BBRequest(path_hash=ph, chunk_id=cid, payload=payload)
    outs = {}
    for kind in ("dense", "compacted"):
        c = BBClient(policy, cap=64, words=w, mcap=64, exchange=kind)
        c.write(req)
        outs[kind] = c.read(req)
    assert bool(np.asarray(outs["compacted"][1]).all())
    np.testing.assert_array_equal(np.asarray(outs["dense"][0]),
                                  np.asarray(outs["compacted"][0]))
    np.testing.assert_array_equal(np.asarray(outs["dense"][1]),
                                  np.asarray(outs["compacted"][1]))


def test_engine_key_distinguishes_default_mode():
    """Policies with the same mode set but different defaults must not
    share cached engine ops: the engine falls back to default_mode when a
    caller passes mode=None."""
    a = LayoutPolicy.from_scopes({"/x": LayoutMode.NODE_LOCAL},
                                 n_nodes=8, default=LayoutMode.DIST_HASH)
    b = LayoutPolicy.from_scopes({"/x": LayoutMode.DIST_HASH},
                                 n_nodes=8, default=LayoutMode.NODE_LOCAL)
    assert a.engine_key() != b.engine_key()
    for p in (a, b):
        canon = LayoutPolicy.for_engine_key(p.engine_key())
        assert canon.default_mode == p.default_mode
        assert canon.modes_present() == p.modes_present()
        assert canon.engine_key() == p.engine_key()


def test_encode_empty_rows():
    """q=0 batches must still encode to well-formed (n, 0) requests
    (regression: the memoized encode dropped the pair axis on empty rows)."""
    client = BBClient(LayoutPolicy.uniform(LayoutMode.DIST_HASH, 2),
                      cap=16, words=4, mcap=16)
    req = client.encode([[], []])
    assert req.path_hash.shape == (2, 0)
    assert req.scope_hash.shape == (2, 0)


# ---------------------------------------------------------------------------
# losslessness: ragged budgets and the multi-round carry vs the dense oracle
# ---------------------------------------------------------------------------
def _sorted_tables(state):
    """Node tables canonicalized by key (append order is NOT part of the
    lossless contract: the carry round appends residuals after round 1)."""
    dk = np.asarray(state.data_keys)
    dd = np.asarray(state.data)
    mk = np.asarray(state.meta_key)
    ms = np.asarray(state.meta_size)
    ml = np.asarray(state.meta_loc)
    outs = []
    for n in range(dk.shape[0]):
        o = np.lexsort((dk[n, :, 1], dk[n, :, 0]))
        m = np.argsort(mk[n])
        outs.append((dk[n][o], dd[n][o], mk[n][m], ms[n][m], ml[n][m]))
    return outs


def _assert_state_canonical_equal(a, b):
    for ta, tb in zip(_sorted_tables(a), _sorted_tables(b)):
        for x, y in zip(ta, tb):
            np.testing.assert_array_equal(x, y)
    np.testing.assert_array_equal(np.asarray(a.data_count),
                                  np.asarray(b.data_count))
    np.testing.assert_array_equal(np.asarray(a.meta_count),
                                  np.asarray(b.meta_count))


@pytest.mark.parametrize("budget", [1, 2, 4, 16])
def test_multi_round_carry_is_lossless_at_any_budget(budget):
    """Unique-key batch at pathological budgets (incl. B=1): the carry
    round must deliver every chunk and every metadata op — canonical state,
    all replies and all counts equal to dense, dropped == 0, and the
    read/stat reply digests pin the dense plane's bits exactly."""
    n, q, w = 4, 16, 4
    policy = LayoutPolicy.uniform(LayoutMode.DIST_HASH, n)
    ph = np.arange(1, n * q + 1, dtype=np.int32).reshape(n, q)
    cid = np.zeros((n, q), np.int32)
    payload = np.broadcast_to(ph[..., None], (n, q, w)).astype(np.int32)
    req = BBRequest(path_hash=jnp.asarray(ph), chunk_id=jnp.asarray(cid),
                    payload=jnp.asarray(payload))
    dense = BBClient(policy, cap=256, words=w, mcap=256, exchange="dense")
    tight = BBClient(policy, cap=256, words=w, mcap=256,
                     exchange="compacted", budget=budget)
    assert tight.exchange_config.lossless
    dense.write(req)
    tight.write(req)
    assert int(np.asarray(tight.state.dropped).sum()) == 0
    _assert_state_canonical_equal(dense.state, tight.state)
    out_d = dense.read(req)
    out_t = tight.read(req)
    assert _digest(*out_t) == _digest(*out_d)
    stat_d = dense.stat(req)
    stat_t = tight.stat(req)
    assert _digest(*stat_t) == _digest(*stat_d)
    assert bool(np.asarray(out_t[1]).all())          # nothing went missing
    rm_d, rm_t = dense.remove(req), tight.remove(req)
    np.testing.assert_array_equal(np.asarray(rm_d), np.asarray(rm_t))
    _assert_state_canonical_equal(dense.state, tight.state)


def test_stat_after_overflowed_write_regression():
    """The drop plane skipped the metadata phase for overflowed writes (no
    phantom entries); the lossless plane must do the opposite — carry the
    write AND its metadata, so stat() reports every chunk.  Regression for
    the seam between the two rounds: sizes must reflect the carried
    chunks, not just round 1's."""
    n, q, w = 4, 12, 4
    rng = np.random.RandomState(7)
    policy = LayoutPolicy.uniform(LayoutMode.DIST_HASH, n)
    # every node writes q chunks of its own single file → all q metadata
    # ops of a node hit ONE owner, guaranteeing deep overflow at B=1
    ph = np.repeat(rng.randint(1, 1 << 20, (n, 1)).astype(np.int32), q,
                   axis=1)
    cid = np.tile(np.arange(q, dtype=np.int32), (n, 1))
    payload = rng.randint(0, 9999, (n, q, w)).astype(np.int32)
    req = BBRequest(path_hash=jnp.asarray(ph), chunk_id=jnp.asarray(cid),
                    payload=jnp.asarray(payload))
    tight = BBClient(policy, cap=256, words=w, mcap=64,
                     exchange="compacted", budget=1, meta_budget=1)
    tight.write(req)
    assert int(np.asarray(tight.state.dropped).sum()) == 0
    fnd, size, _ = tight.stat(req)
    assert bool(np.asarray(fnd).all())
    np.testing.assert_array_equal(np.asarray(size),
                                  np.full((n, q), q, np.int32))
    out, found = tight.read(req)
    assert bool(np.asarray(found).all())
    np.testing.assert_array_equal(np.asarray(out), payload)


@settings(max_examples=6, deadline=None)
@given(st.integers(min_value=0, max_value=10 ** 6))
def test_property_lossless_carry_parity_mixed_modes(seed):
    """Random mixed-mode batches at budgets {1, 2, q//4, q}: the lossless
    compacted plane must match dense on every observable reply and every
    count, with dropped == 0 — at every budget."""
    n, q, w = 4, 8, 4
    rng = np.random.RandomState(seed % (2 ** 31))
    policy = LayoutPolicy.from_scopes(
        {"/bb/meta2": LayoutMode.CENTRAL_META}, n_nodes=n,
        default=LayoutMode.DIST_HASH)
    mode = jnp.asarray(rng.choice([int(LayoutMode.CENTRAL_META),
                                   int(LayoutMode.DIST_HASH)], (n, q)),
                       jnp.int32)
    ph = jnp.asarray(rng.randint(1, 1 << 20, (n, q)), jnp.int32)
    cid = jnp.asarray(rng.randint(0, 3, (n, q)), jnp.int32)
    payload = jnp.asarray(rng.randint(0, 9999, (n, q, w)), jnp.int32)
    valid = jnp.asarray(rng.rand(n, q) > 0.25)
    s_d = bb.init_state(n, 64, w, 64)
    s_d = bb.forward_write(s_d, policy, ph, cid, payload, valid, mode=mode)
    r_d = bb.forward_read(s_d, policy, ph, cid, valid, mode=mode)
    stat = jnp.full((n, q), bb.OP_STAT, jnp.int32)
    zeros = jnp.zeros((n, q), jnp.int32)
    neg = jnp.full((n, q), -1, jnp.int32)
    m_d = bb.meta_op(s_d, policy, stat, ph, zeros, neg, valid, mode=mode)
    for budget in (1, 2, q // 4, q):
        cfg = bb.ExchangeConfig("compacted", budget=budget)
        s_c = bb.init_state(n, 64, w, 64)
        s_c = bb.forward_write(s_c, policy, ph, cid, payload, valid,
                               mode=mode, config=cfg)
        assert int(np.asarray(s_c.dropped).sum()) == 0, budget
        np.testing.assert_array_equal(np.asarray(s_c.data_count),
                                      np.asarray(s_d.data_count))
        np.testing.assert_array_equal(np.asarray(s_c.meta_count),
                                      np.asarray(s_d.meta_count))
        r_c = bb.forward_read(s_c, policy, ph, cid, valid, mode=mode,
                              config=cfg)
        np.testing.assert_array_equal(np.asarray(r_d[0]), np.asarray(r_c[0]))
        np.testing.assert_array_equal(np.asarray(r_d[1]), np.asarray(r_c[1]))
        m_c = bb.meta_op(s_c, policy, stat, ph, zeros, neg, valid, mode=mode,
                         config=cfg)
        for a, b in zip(m_d[1:], m_c[1:]):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# ragged budgets: histogram-sized per-destination segments
# ---------------------------------------------------------------------------
def test_ragged_spec_plan_covers_measured_traffic():
    rng = np.random.RandomState(3)
    n, q = 8, 32
    dest = jnp.asarray(rng.randint(0, n, (n, q)), jnp.int32)
    valid = jnp.asarray(rng.rand(n, q) > 0.3)
    spec = bb.plan_ragged_spec(dest, valid, n, align=1)
    d = np.where(np.asarray(valid), np.asarray(dest), -1)
    counts = np.stack([np.bincount(row[row >= 0], minlength=n)
                       for row in d])
    np.testing.assert_array_equal(np.asarray(spec.budgets),
                                  counts.max(axis=0))
    assert spec.total == sum(spec.budgets)
    np.testing.assert_array_equal(
        spec.offsets, np.concatenate([[0], np.cumsum(spec.budgets)[:-1]]))
    # the plan built from its own measurement can never overflow
    _, reply_idx, overflow = bb._compact_plan_ragged(dest, valid, n, spec)
    assert int(np.asarray(overflow).sum()) == 0
    assert bool((np.asarray(reply_idx)[np.asarray(valid)] >= 0).all())
    # the default alignment rounds up (never down) and clamps to q, with
    # zero-traffic destinations kept at 0 columns
    q8 = bb.plan_ragged_spec(dest, valid, n)
    assert all(b8 >= b and b8 % 8 == 0 and b8 <= q
               for b8, b in zip(q8.budgets, spec.budgets) if b8)
    assert all(b8 == 0 for b8, b in zip(q8.budgets, spec.budgets)
               if b == 0)


def test_ragged_spec_quantization_collapses_jit_shape_space():
    """Fresh hashed batches must NOT mint a fresh RaggedSpec (→ a fresh
    XLA compile of the engine ops) on nearly every call: with the default
    alignment, many random batches of one workload shape land on a
    handful of specs (regression: exact maxima produced ~1 spec per
    call)."""
    n, q = 8, 64
    rng = np.random.RandomState(0)
    specs = set()
    for _ in range(30):
        dest = jnp.asarray(rng.randint(0, n, (n, q)), jnp.int32)
        valid = jnp.ones((n, q), bool)
        specs.add(bb.plan_ragged_spec(dest, valid, n))
    assert len(specs) <= 6, len(specs)


def test_ragged_client_is_bit_for_bit_dense():
    """The default stacked client (auto→compacted with ragged budgets) must
    produce the dense plane's exact table bits — ragged segments preserve
    the source-major receive order, so this is full state equality, not
    just canonical equality."""
    n, q, w = 8, 16, 4
    rng = np.random.RandomState(13)
    policy = _hetero_policy(n)
    paths = [[(f"/bb/ckpt/r{r}/f{j}" if j % 3 == 0 else
               f"/bb/shared/o{r * q + j}" if j % 3 == 1 else
               f"/bb/other/g{r * q + j}") for j in range(q)]
             for r in range(n)]
    ragged = BBClient(policy, cap=128, words=w, mcap=256,
                      exchange="compacted", ragged=True)
    dense = BBClient(policy, cap=128, words=w, mcap=256, exchange="dense")
    req = ragged.encode(paths, chunk_id=rng.randint(0, 3, (n, q)),
                        payload=rng.randint(0, 9999, (n, q, w)),
                        valid=jnp.asarray(rng.rand(n, q) > 0.2))
    ragged.write(req)
    dense.write(req)
    _assert_state_equal(dense.state, ragged.state)
    assert int(np.asarray(ragged.state.dropped).sum()) == 0
    # ragged read path: policy has HYBRID, so reads stay uniform — exercise
    # a hash-only policy for the ragged read plan as well
    hash_pol = LayoutPolicy.uniform(LayoutMode.DIST_HASH, n)
    rc = BBClient(hash_pol, cap=128, words=w, mcap=256, exchange="compacted")
    dc = BBClient(hash_pol, cap=128, words=w, mcap=256, exchange="dense")
    req2 = rc.encode(paths, chunk_id=np.zeros((n, q), np.int32),
                     payload=rng.randint(0, 9999, (n, q, w)))
    rc.write(req2)
    dc.write(req2)
    _assert_state_equal(dc.state, rc.state)
    for a, b in zip(rc.read(req2), dc.read(req2)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for a, b in zip(rc.stat(req2), dc.stat(req2)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_ragged_specs_specialize_engine_ops_per_traffic_shape():
    """Two calls with the same traffic shape must share one jitted
    specialization (the RaggedSpec is part of the cache key), and the
    footprint model must count the packed Σbᵢ columns, not N·B."""
    n, q, w = 4, 64, 4
    policy = LayoutPolicy.uniform(LayoutMode.DIST_HASH, n)
    client = BBClient(policy, cap=64, words=w, mcap=64,
                      exchange="compacted")
    ph = np.arange(1, n * q + 1, dtype=np.int32).reshape(n, q)
    mode = client.policy.mode_array((n, q), xp=jnp)
    cid = jnp.zeros((n, q), jnp.int32)
    valid = jnp.ones((n, q), bool)
    cfg1 = client._call_config("write", mode, jnp.asarray(ph), cid, valid)
    cfg2 = client._call_config("write", mode, jnp.asarray(ph), cid, valid)
    assert cfg1 == cfg2 and cfg1.data_spec is not None
    assert client._ops(cfg1) is client._ops(cfg2)
    foot = bb.exchange_footprint(policy, q, w, cfg1)
    assert foot["write_elems"] < bb.exchange_footprint(
        policy, q, w, bb.COMPACTED)["write_elems"]
    assert foot["write_carry_elems"] == 0            # ragged never carries


# ---------------------------------------------------------------------------
# per-call backend auto-selection
# ---------------------------------------------------------------------------
def test_auto_exchange_picks_per_call_and_stays_exact():
    from repro.core import exchange_select
    n, w = 4, 4
    policy = LayoutPolicy.uniform(LayoutMode.DIST_HASH, n)
    auto = BBClient(policy, cap=256, words=w, mcap=256, exchange="auto")
    dense = BBClient(policy, cap=256, words=w, mcap=256, exchange="dense")
    for q in (2, 64):
        ph = np.arange(1, n * q + 1, dtype=np.int32).reshape(n, q)
        cid = np.zeros((n, q), np.int32)
        payload = np.broadcast_to(ph[..., None], (n, q, w)).astype(np.int32)
        req = BBRequest(path_hash=jnp.asarray(ph), chunk_id=jnp.asarray(cid),
                        payload=jnp.asarray(payload))
        auto.write(req)
        dense.write(req)
        _assert_state_equal(dense.state, auto.state)
        for a, b in zip(auto.read(req), dense.read(req)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # the pick is the measured-crossover answer, memoized per shape
    for q in (2, 64):
        assert auto._select_kind(q) == exchange_select.pick_backend(n, q, w)
        assert q in auto._pick_cache


def test_exchange_select_crossover_and_fallback():
    from repro.core import exchange_select as xs
    rows = [
        {"backend": "dense", "n_nodes": 4, "batch": 8, "words": 4,
         "write_us": 1.0, "read_us": 1.0, "stat_us": 1.0},
        {"backend": "compacted", "n_nodes": 4, "batch": 8, "words": 4,
         "write_us": 2.0, "read_us": 2.0, "stat_us": 2.0},
        {"backend": "dense", "n_nodes": 32, "batch": 64, "words": 16,
         "write_us": 9.0, "read_us": 9.0, "stat_us": 9.0},
        {"backend": "compacted", "n_nodes": 32, "batch": 64, "words": 16,
         "write_us": 3.0, "read_us": 3.0, "stat_us": 3.0},
        {"backend": "dense", "n_nodes": 99, "batch": 1, "words": 1,
         "write_us": 1.0, "read_us": 1.0, "stat_us": 1.0},  # unpaired
    ]
    table = xs.crossover_table(rows)
    assert table == ((4, 8, 4, "dense"), (32, 64, 16, "compacted"))
    assert xs.pick_backend(4, 8, 4, table) == "dense"
    assert xs.pick_backend(4, 4, 4, table) == "dense"       # nearest cell
    assert xs.pick_backend(64, 128, 16, table) == "compacted"
    # fallback table drives the pick when no bench JSON exists
    assert xs.pick_backend(4, 8, 8, xs.FALLBACK_TABLE) == "dense"
    assert xs.pick_backend(64, 256, 16, xs.FALLBACK_TABLE) == "compacted"


def test_exchange_select_tolerates_missing_or_malformed_bench(tmp_path):
    """Fresh-clone robustness: no artifact, junk JSON, or rows missing
    fields must all degrade to the baked-in table — never raise."""
    from repro.core import exchange_select as xs
    import json as _json
    # 1. no benchmark files at all
    assert xs.load_crossover(str(tmp_path)) == xs.FALLBACK_TABLE
    # 2. unparseable / wrong-shaped artifacts
    (tmp_path / "BENCH_pr3.json").write_text("{not json")
    xs.refresh()
    assert xs.load_crossover(str(tmp_path)) == xs.FALLBACK_TABLE
    (tmp_path / "BENCH_pr3.json").write_text(_json.dumps([1, 2, 3]))
    xs.refresh()
    assert xs.load_crossover(str(tmp_path)) == xs.FALLBACK_TABLE
    # 3. rows present but malformed (missing fields, wrong types, junk
    # entries) — well-formed pairs still win, junk is skipped
    good = [{"backend": b, "n_nodes": 4, "batch": 8, "words": 4,
             "write_us": t, "read_us": t, "stat_us": t}
            for b, t in (("dense", 1.0), ("compacted", 2.0))]
    bad = [None, 42, {"backend": "dense"}, {"n_nodes": 8},
           {"backend": "dense", "n_nodes": 8, "batch": 8, "words": 4,
            "write_us": "oops", "read_us": 1, "stat_us": 1},
           {"backend": "???", "n_nodes": 8, "batch": 8, "words": 4,
            "write_us": 1, "read_us": 1, "stat_us": 1}]
    (tmp_path / "BENCH_pr3.json").write_text(
        _json.dumps({"rows": good + bad}))
    xs.refresh()
    assert xs.load_crossover(str(tmp_path)) == ((4, 8, 4, "dense"),)
    # 4. all-malformed rows → fallback again
    (tmp_path / "BENCH_pr3.json").write_text(_json.dumps({"rows": bad}))
    xs.refresh()
    assert xs.load_crossover(str(tmp_path)) == xs.FALLBACK_TABLE
    # 5. the degradation is never silent: with a recorder active, each
    # fallback load emits a structured audit event carrying the reason
    from repro.core import obs
    rec = obs.TraceRecorder()
    with obs.activate(rec):
        xs.refresh()
        assert xs.load_crossover(str(tmp_path)) == xs.FALLBACK_TABLE
        assert xs.fabric_model(str(tmp_path))[2] is False
    falls = rec.audit.records("crossover_fallback")
    assert len(falls) == 1
    assert falls[0].choice == "fallback_table"
    assert falls[0].inputs["reason"] == "malformed"   # artifact exists
    assert falls[0].evidence["grade"] == "fallback"
    fabs = rec.audit.records("fabric_fallback")
    assert len(fabs) == 1 and fabs[0].choice == "analytic"
    assert fabs[0].evidence["grade"] == "fallback"
    # a missing artifact is distinguished from a malformed one
    (tmp_path / "BENCH_pr3.json").unlink()
    with obs.activate(rec):
        xs.refresh()
        assert xs.load_crossover(str(tmp_path)) == xs.FALLBACK_TABLE
    assert rec.audit.records("crossover_fallback")[-1] \
        .inputs["reason"] == "missing"
    xs.refresh()                  # drop the tmp tables for other tests


MESH_COMPACT_SCRIPT = textwrap.dedent("""
    import os
    os.environ['XLA_FLAGS'] = '--xla_force_host_platform_device_count=4'
    import sys; sys.path.insert(0, 'src')
    import jax, jax.numpy as jnp, numpy as np
    from repro.core.client import BBClient, BBRequest
    from repro.core.layouts import LayoutMode
    from repro.core.mesh_engine import make_node_mesh
    from repro.core.policy import LayoutPolicy

    N, q, w = 4, 16, 8
    policy = LayoutPolicy.uniform(LayoutMode.DIST_HASH, N)
    kw = dict(cap=128, words=w, mcap=128, exchange="compacted", budget=2,
              lossless=False)
    mc = BBClient(policy, make_node_mesh(4), **kw)
    sc = BBClient(policy, **kw)
    rng = np.random.RandomState(0)
    req = BBRequest(
        path_hash=jnp.asarray(rng.randint(1, 1 << 20, (N, q)), jnp.int32),
        chunk_id=jnp.asarray(rng.randint(0, 4, (N, q)), jnp.int32),
        payload=jnp.asarray(rng.randint(0, 999, (N, q, w)), jnp.int32))
    mc.write(req); sc.write(req)
    for a, b in zip(mc.state.tree_flatten()[0], sc.state.tree_flatten()[0]):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    assert int(np.asarray(mc.state.dropped).sum()) > 0   # B=2 < q overflows
    out_m, f_m = mc.read(req)
    out_s, f_s = sc.read(req)
    assert np.array_equal(np.asarray(out_m), np.asarray(out_s))
    assert np.array_equal(np.asarray(f_m), np.asarray(f_s))
    for a, b in zip(mc.stat(req), sc.stat(req)):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    print('MESH_COMPACT_OK')
""")


@pytest.mark.slow
@pytest.mark.mesh
def test_mesh_compacted_overflow_parity():
    """The compacted plane on a real 4-device shard_map mesh with a budget
    SMALLER than q: the (L, N, B) all_to_all wiring, fused reply
    collectives and overflow accounting must match the stacked backend
    element-for-element (lossless small-size parity is covered by the PR-1
    mesh tests; this one forces real overflow)."""
    r = subprocess.run([sys.executable, "-c", MESH_COMPACT_SCRIPT],
                       capture_output=True, text=True, timeout=600, cwd=".")
    assert "MESH_COMPACT_OK" in r.stdout, r.stdout + r.stderr


MESH_LOSSLESS_SCRIPT = textwrap.dedent("""
    import os
    os.environ['XLA_FLAGS'] = '--xla_force_host_platform_device_count=4'
    import sys; sys.path.insert(0, 'src')
    import jax, jax.numpy as jnp, numpy as np
    from repro.core import burst_buffer as bb
    from repro.core.client import BBClient, BBRequest
    from repro.core.layouts import LayoutMode
    from repro.core.mesh_engine import make_node_mesh
    from repro.core.policy import LayoutPolicy

    N, q, w = 4, 16, 8
    policy = LayoutPolicy.uniform(LayoutMode.DIST_HASH, N)
    kw = dict(cap=128, words=w, mcap=128, exchange="compacted", budget=2)
    mc = BBClient(policy, make_node_mesh(4), **kw)      # lossless default
    dn = BBClient(policy, **dict(kw, exchange="dense"))
    rng = np.random.RandomState(0)
    req = BBRequest(
        path_hash=jnp.asarray(rng.randint(1, 1 << 20, (N, q)), jnp.int32),
        chunk_id=jnp.asarray(rng.randint(0, 4, (N, q)), jnp.int32),
        payload=jnp.asarray(rng.randint(0, 999, (N, q, w)), jnp.int32))
    mc.write(req); dn.write(req)
    assert int(np.asarray(mc.state.dropped).sum()) == 0   # carry, not drop
    assert np.array_equal(np.asarray(mc.state.data_count),
                          np.asarray(dn.state.data_count))
    assert np.array_equal(np.asarray(mc.state.meta_count),
                          np.asarray(dn.state.meta_count))
    out_m, f_m = mc.read(req)
    out_d, f_d = dn.read(req)
    assert np.array_equal(np.asarray(out_m), np.asarray(out_d))
    assert np.array_equal(np.asarray(f_m), np.asarray(f_d))
    assert bool(np.asarray(f_m).all())
    for a, b in zip(mc.stat(req), dn.stat(req)):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    print('MESH_LOSSLESS_OK')
""")


@pytest.mark.slow
@pytest.mark.mesh
def test_mesh_lossless_carry_parity():
    """The cond-gated carry round on a real 4-device shard_map mesh: the
    psum-composed predicate must take the same branch on every device, the
    all_to_all inside the cond must line up, and a budget-2 write of a
    16-slot batch must come out lossless — every reply equal to the dense
    oracle and ``dropped`` == 0."""
    r = subprocess.run([sys.executable, "-c", MESH_LOSSLESS_SCRIPT],
                       capture_output=True, text=True, timeout=600, cwd=".")
    assert "MESH_LOSSLESS_OK" in r.stdout, r.stdout + r.stderr


def test_mesh_rejects_packed_ragged_specs():
    """build_mesh_ops must refuse PACKED ragged configs (all_to_all needs
    uniform splits) and mesh plans made for another chip count, while
    accepting the mesh-ragged plans — the ppermute one too when a device
    holds several node rows; the client keeps ragged planning on,
    producing MeshRaggedSpec configs instead."""
    from repro.core.mesh_engine import build_mesh_ops, make_node_mesh
    policy = LayoutPolicy.uniform(LayoutMode.DIST_HASH, 1)
    spec = bb.RaggedSpec((1,))
    with pytest.raises(ValueError, match="ragged"):
        build_mesh_ops(make_node_mesh(1), policy,
                       bb.ExchangeConfig("compacted", data_spec=spec))
    # a MeshRaggedSpec is carried fine (the split with no fabric block)
    mspec = bb.MeshRaggedSpec("padded", 1, 8, ())
    build_mesh_ops(make_node_mesh(1), policy,
                   bb.ExchangeConfig("compacted", data_spec=mspec))
    with pytest.raises(ValueError, match="4 chips"):
        build_mesh_ops(make_node_mesh(1), policy, bb.ExchangeConfig(
            "compacted", data_spec=bb.MeshRaggedSpec("padded", 4, 8, (8,))))
    # the ppermute plan runs with two node rows on one device: the split
    # keeps both rows' traffic on the chip, and the write is the dense one
    pol2 = LayoutPolicy.uniform(LayoutMode.DIST_HASH, 2)
    rng = np.random.RandomState(3)
    ph = jnp.asarray(rng.randint(1, 999, (2, 8)), jnp.int32)
    cid = jnp.zeros((2, 8), jnp.int32)
    pay = jnp.asarray(rng.randint(0, 99, (2, 8, 4)), jnp.int32)
    valid = jnp.ones((2, 8), bool)
    mode = jnp.full((2, 8), int(LayoutMode.DIST_HASH), jnp.int32)
    ranks = jnp.arange(2, dtype=jnp.int32)[:, None]
    dest = route_data(mode, 2, ph, cid, ranks, xp=jnp)
    owner = route_meta(mode, 2, pol2.n_md_servers, ph, ranks, xp=jnp)
    pcfg = bb.ExchangeConfig(
        "compacted",
        data_spec=bb.plan_mesh_ragged_spec(dest, valid, 2, n_chips=1,
                                           executor="ppermute"),
        meta_spec=bb.plan_mesh_ragged_spec(owner, valid, 2, n_chips=1,
                                           executor="ppermute"))
    write = build_mesh_ops(make_node_mesh(1), pol2, pcfg)[0]
    dense = build_mesh_ops(make_node_mesh(1), pol2, bb.DENSE)[0]
    got = write(bb.init_state(2, 16, 4, 16), mode, ph, cid, pay, valid)
    want = dense(bb.init_state(2, 16, 4, 16), mode, ph, cid, pay, valid)
    for a, b in zip(got.tree_flatten()[0], want.tree_flatten()[0]):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    client = BBClient(policy, make_node_mesh(1), cap=16, words=4, mcap=16,
                      exchange="compacted", ragged=True)
    assert client.ragged is True                 # mesh plans ragged now
    assert client._n_chips == 1                  # 1 node on 1 device


def test_exchange_footprint_scaling():
    """Modeled exchange volume: dense grows O(N²·q); compacted O(N·q)
    (with hash-spread metadata budgets, as distinct-path workloads use —
    the auto meta budget stays lossless and would scale as dense)."""
    q, w = 256, 16
    dense, comp = {}, {}
    for n in (8, 32):
        pol = LayoutPolicy.uniform(LayoutMode.DIST_HASH, n)
        cfg = bb.ExchangeConfig(
            "compacted", meta_budget=bb._auto_budget(q, n, 2.0))
        dense[n] = bb.exchange_footprint(pol, q, w, bb.DENSE)
        comp[n] = bb.exchange_footprint(pol, q, w, cfg)
    assert dense[32]["write_elems"] == 16 * dense[8]["write_elems"]  # N²
    ratio = comp[32]["write_elems"] / comp[8]["write_elems"]
    assert ratio == pytest.approx(4.0, rel=0.35)                     # ~N
    assert comp[32]["write_elems"] * 2 < dense[32]["write_elems"]
