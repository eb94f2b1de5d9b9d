"""Pipelined-vs-synchronous parity: the ``pipeline`` flag may only move
work, never change a bit of it.

Every test drives the SAME op stream through ``pipeline=False`` (serial
rounds, cond-planned carry) and ``pipeline=True`` (fused write
round-trips, hoisted carry plans, double-buffered shift rounds) and
demands bit-identical observables — anchored to the frozen PR-4 stream
digest so neither side can drift, plus a property sweep over random op
streams and budgets.  The fused write's "exactly one collective
round-trip" claim is asserted structurally via flight-recorder span
counts, not wall-clock.
"""
import dataclasses
import subprocess
import sys
import textwrap

import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:          # pragma: no cover - env dependent
    from _minihyp import given, settings, strategies as st

from repro.core import burst_buffer as bb
from repro.core import obs
from repro.core.client import BBClient, BBRequest
from repro.core.layouts import LayoutMode
from repro.core.policy import LayoutPolicy

from test_adapt import (STREAM_DIGEST, _digest, _interleaved_stream)

N, Q, W = 4, 16, 8


def _hash_policy(n=N):
    return LayoutPolicy.from_scopes({}, n_nodes=n,
                                    default=LayoutMode.DIST_HASH)


def _states_equal(a, b):
    return all(np.array_equal(np.asarray(x), np.asarray(y))
               for x, y in zip(a.tree_flatten()[0], b.tree_flatten()[0]))


# ---------------------------------------------------------------------------
# the PR-4 pinned stream, pipelining off and on
# ---------------------------------------------------------------------------
def test_stream_digest_pipeline_off():
    """The synchronous plan still reproduces the frozen PR-4 digest."""
    _, outs = _interleaved_stream(relayout=False, pipeline=False)
    assert _digest(*outs) == STREAM_DIGEST


def test_stream_digest_pipeline_on():
    """And the pipelined plan reproduces the SAME digest bit-for-bit."""
    _, outs = _interleaved_stream(relayout=False, pipeline=True)
    assert _digest(*outs) == STREAM_DIGEST


def test_stream_digest_compacted_pipeline_both():
    """The compacted exchange under both pipeline settings also lands on
    the pinned digest: fused write round-trips and hoisted carry plans
    are invisible next to the dense-era observables."""
    _, off = _interleaved_stream(relayout=False, exchange="compacted",
                                 pipeline=False)
    _, on = _interleaved_stream(relayout=False, exchange="compacted",
                                pipeline=True)
    assert _digest(*off) == STREAM_DIGEST
    assert _digest(*on) == STREAM_DIGEST


# ---------------------------------------------------------------------------
# random op streams (property): budgets from the lossless regression set
# ---------------------------------------------------------------------------
def _drive(client, ops, seed):
    """Run a deterministic op stream; return every observable."""
    rng = np.random.RandomState(seed)
    outs, reqs = [], []
    for kind in ops:
        if kind == 0 or not reqs:        # write (also forced first op)
            req = BBRequest(
                path_hash=jnp.asarray(
                    rng.randint(1, 1 << 12, (client.n_nodes, Q)),
                    jnp.int32),
                chunk_id=jnp.asarray(
                    rng.randint(0, 4, (client.n_nodes, Q)), jnp.int32),
                payload=jnp.asarray(
                    rng.randint(0, 9999, (client.n_nodes, Q, W)),
                    jnp.int32),
                valid=jnp.asarray(rng.rand(client.n_nodes, Q) < 0.85))
            client.write(req)
            reqs.append(req)
        elif kind == 1:                  # read-back of a prior batch
            out, found = client.read(reqs[rng.randint(len(reqs))])
            outs += [out, found]
        else:                            # stat of a prior batch
            fnd, size, loc = client.stat(reqs[rng.randint(len(reqs))])
            outs += [fnd, size, loc]
    outs += list(client.state.tree_flatten()[0])
    return outs


@settings(max_examples=6, deadline=None)
@given(st.lists(st.integers(0, 2), min_size=3, max_size=8),
       st.integers(0, 3), st.integers(0, 1 << 20))
def test_random_streams_pipeline_parity(ops, b_idx, seed):
    """Random write/read/stat streams at lossless budgets {1, 2, q/4, q}:
    pipelined and synchronous clients agree on every reply and on the
    final tables."""
    budget = (1, 2, Q // 4, Q)[b_idx]
    outs = {}
    for pipe in (False, True):
        client = BBClient(_hash_policy(), cap=8 * Q, words=W, mcap=8 * Q,
                          exchange="compacted", budget=budget,
                          pipeline=pipe)
        outs[pipe] = _drive(client, ops, seed)
    assert _digest(*outs[False]) == _digest(*outs[True])


# ---------------------------------------------------------------------------
# fused write: exactly ONE collective round-trip (span-counted)
# ---------------------------------------------------------------------------
def _write_collective_spans(pipe, budget=Q):
    """Eager forward_write under a flight recorder; count collectives."""
    policy = _hash_policy()
    cfg = dataclasses.replace(bb.COMPACTED, budget=budget,
                              meta_budget=budget, pipeline=pipe)
    rng = np.random.RandomState(0)
    state = bb.init_state(N, 8 * Q, W, 8 * Q)
    rec = obs.TraceRecorder()
    with obs.activate(rec):
        bb.forward_write(
            state, policy,
            jnp.asarray(rng.randint(1, 1 << 20, (N, Q)), jnp.int32),
            jnp.asarray(rng.randint(0, 4, (N, Q)), jnp.int32),
            jnp.asarray(rng.randint(0, 99, (N, Q, W)), jnp.int32),
            jnp.ones((N, Q), bool), config=cfg)
    return [s for s in rec.spans if s.name == "exchange.all_to_all"]


def test_fused_write_is_one_collective_round_trip():
    """At lossless B = q the serial write launches three collectives
    (data round, metadata request, metadata reply); the fused plan
    launches exactly ONE."""
    assert len(_write_collective_spans(pipe=False)) == 3
    assert len(_write_collective_spans(pipe=True)) == 1


def test_under_budget_write_keeps_serial_rounds():
    """B < q can overflow into the carry round, so fusion is elided —
    the pipelined write keeps the serial launch structure (carry rounds
    are cond-gated extras on top of the three)."""
    assert len(_write_collective_spans(pipe=True, budget=2)) >= 3


# ---------------------------------------------------------------------------
# donation: the public calls hand their state over, never changing results
# ---------------------------------------------------------------------------
def _replay(client, ops, seed):
    """``_drive``'s stream through the state-explicit entries, which keep
    every state they are given; returns the observables and the states."""
    rng = np.random.RandomState(seed)
    outs, reqs, states = [], [], [client.state]
    for kind in ops:
        if kind == 0 or not reqs:
            req = BBRequest(
                path_hash=jnp.asarray(
                    rng.randint(1, 1 << 12, (client.n_nodes, Q)),
                    jnp.int32),
                chunk_id=jnp.asarray(
                    rng.randint(0, 4, (client.n_nodes, Q)), jnp.int32),
                payload=jnp.asarray(
                    rng.randint(0, 9999, (client.n_nodes, Q, W)),
                    jnp.int32),
                valid=jnp.asarray(rng.rand(client.n_nodes, Q) < 0.85))
            states.append(client._write(
                states[-1], client._modes(req), req.path_hash,
                req.chunk_id, req.payload, req.valid))
            reqs.append(req)
            continue
        req = reqs[rng.randint(len(reqs))]
        mode, valid = client._modes(req), client._valid(req)
        if kind == 1:
            outs += list(client._read(states[-1], mode, req.path_hash,
                                      req.chunk_id, valid))
        else:
            shape = req.path_hash.shape
            state, *reply = client._meta(
                states[-1], mode, jnp.full(shape, bb.OP_STAT, jnp.int32),
                req.path_hash, jnp.zeros(shape, jnp.int32),
                jnp.full(shape, -1, jnp.int32), valid)
            states.append(state)
            outs += reply
    outs += list(states[-1].tree_flatten()[0])
    return outs, states


@pytest.mark.parametrize("exchange", ["dense", "compacted"])
def test_donation_parity_stacked(exchange):
    """The public calls donate the state they rebind; a replay of the
    same stream through the state-explicit entries donates nothing.
    Both land on one digest, and every state of the replay survives."""
    ops = [0, 1, 2, 0, 1, 2]
    kw = dict(cap=8 * Q, words=W, mcap=8 * Q, exchange=exchange,
              pipeline=True)
    if exchange == "compacted":
        kw["budget"] = Q
    public = BBClient(_hash_policy(), **kw)
    first = public.state
    owned = _drive(public, ops, seed=5)
    assert first.data.is_deleted()
    replayed, states = _replay(BBClient(_hash_policy(), **kw), ops, seed=5)
    assert not any(s.data.is_deleted() for s in states)
    assert _digest(*owned) == _digest(*replayed)


# ---------------------------------------------------------------------------
# measured carry hint: losslessness and floor behaviour
# ---------------------------------------------------------------------------
def test_carry_hint_lossless_at_regression_budgets():
    """Explicit hint regression: at every budget in {1, 2, q/4, q} the
    pipelined (hinted, capped carry) client matches the dense oracle on
    replies and drops nothing."""
    rng = np.random.RandomState(11)
    req = BBRequest(
        path_hash=jnp.asarray(rng.randint(1, 1 << 8, (N, Q)), jnp.int32),
        chunk_id=jnp.asarray(rng.randint(0, 4, (N, Q)), jnp.int32),
        payload=jnp.asarray(rng.randint(0, 999, (N, Q, W)), jnp.int32))
    oracle = BBClient(_hash_policy(), cap=8 * Q, words=W, mcap=8 * Q,
                      exchange="dense")
    oracle.write(req)
    o_out, o_fnd = oracle.read(req)
    for budget in (1, 2, Q // 4, Q):
        client = BBClient(_hash_policy(), cap=8 * Q, words=W, mcap=8 * Q,
                          exchange="compacted", budget=budget,
                          pipeline=True)
        client.write(req)
        assert int(np.asarray(client.state.dropped).sum()) == 0
        out, fnd = client.read(req)
        assert np.array_equal(np.asarray(out), np.asarray(o_out))
        assert np.array_equal(np.asarray(fnd), np.asarray(o_fnd))


def test_carry_hint_measures_and_floors():
    """The hint is None when no plane can overflow, quantized-up-to-8
    and residual-covering when one can, and monotone per q so steady
    traffic keeps ONE jit specialization."""
    q = 16
    client = BBClient(_hash_policy(), cap=8 * q, words=W, mcap=8 * q,
                      exchange="compacted", budget=4, pipeline=True)
    cfg_full = dataclasses.replace(bb.COMPACTED, budget=q, meta_budget=q)
    cfg_b4 = dataclasses.replace(bb.COMPACTED, budget=4, meta_budget=q)
    mode = jnp.full((N, q), int(LayoutMode.DIST_HASH), jnp.int32)
    incast = jnp.full((N, q), 12345, jnp.int32)   # one owner: residual q−B
    cid = jnp.zeros((N, q), jnp.int32)
    valid = jnp.ones((N, q), bool)
    # B = q on both planes: no overflow, no hint, no routing work
    assert client._carry_hint("write", mode, incast, cid, valid, None,
                              q, cfg_full) is None
    # incast at B=4: worst residual q−4 = 12, already a multiple of 8? no:
    # 12 → quantized up to 16
    hint = client._carry_hint("write", mode, incast, cid, valid, None,
                              q, cfg_b4)
    assert hint == 16 and hint >= q - 4
    # calmer traffic later cannot lower the floor (one specialization)
    spread = jnp.asarray(
        np.arange(N * q).reshape(N, q) % N, jnp.int32)
    assert client._carry_hint("write", mode, spread, cid, valid, None,
                              q, cfg_b4) == hint


# ---------------------------------------------------------------------------
# mesh backend (subprocess): pipeline on/off parity on real devices
# ---------------------------------------------------------------------------
MESH_PIPELINE_SCRIPT = textwrap.dedent("""
    import os
    os.environ['XLA_FLAGS'] = '--xla_force_host_platform_device_count=4'
    import sys; sys.path.insert(0, 'src')
    import jax.numpy as jnp, numpy as np
    from repro.core.client import BBClient, BBRequest
    from repro.core.layouts import LayoutMode
    from repro.core.mesh_engine import make_node_mesh
    from repro.core.policy import LayoutPolicy

    N, q, w = 4, 16, 8
    policy = LayoutPolicy.uniform(LayoutMode.DIST_HASH, N)
    rng = np.random.RandomState(0)
    req = BBRequest(
        path_hash=jnp.asarray(rng.randint(1, 1 << 10, (N, q)), jnp.int32),
        chunk_id=jnp.asarray(rng.randint(0, 4, (N, q)), jnp.int32),
        payload=jnp.asarray(rng.randint(0, 999, (N, q, w)), jnp.int32))
    for budget in (q, 2):         # fused round-trip, then carry territory
        outs = []
        for pipe in (False, True):
            c = BBClient(policy, make_node_mesh(N), cap=128, words=w,
                         mcap=128, exchange="compacted", budget=budget,
                         pipeline=pipe)
            before = c.state
            c.write(req)               # public calls donate sharded state
            assert before.data.is_deleted(), budget
            out, fnd = c.read(req)
            before = c.state
            st = c.stat(req)
            assert before.data.is_deleted(), budget
            outs.append((c.state, out, fnd, st))
        (sa, oa, fa, ta), (sb, ob_, fb, tb) = outs
        for a, b in zip(sa.tree_flatten()[0], sb.tree_flatten()[0]):
            assert np.array_equal(np.asarray(a), np.asarray(b)), budget
        assert np.array_equal(np.asarray(oa), np.asarray(ob_))
        assert np.array_equal(np.asarray(fa), np.asarray(fb))
        for a, b in zip(ta, tb):
            assert np.array_equal(np.asarray(a), np.asarray(b))
    print('MESH_PIPELINE_OK')
""")


@pytest.mark.slow
@pytest.mark.mesh
def test_mesh_pipeline_parity():
    """Fused write round-trips and hoisted carry plans on a real
    4-device shard_map mesh: ``pipeline`` on/off leaves every table and
    every reply bit-identical, at B = q (fused) and B = 2 (carry).  The
    public write and stat donate the sharded state they rebind."""
    r = subprocess.run([sys.executable, "-c", MESH_PIPELINE_SCRIPT],
                       capture_output=True, text=True, timeout=600,
                       cwd=".")
    assert "MESH_PIPELINE_OK" in r.stdout, r.stdout + r.stderr
