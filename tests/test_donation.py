"""Ownership of the node tables: who may donate the state.

``BBClient``'s mutating calls own ``self.state``: they donate it to the
engine program and rebind it to the result, so XLA updates the tables in
place (the compiled program aliases the state parameter to the state
output and holds no copy of the data table) and the old arrays are
deleted.  The state-explicit entries (``_write``, ``_read``, ``_meta``)
take a state their caller may reuse, and leave it alive.
"""
import re

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import burst_buffer as bb
from repro.core.adapt import LiveMigrator
from repro.core.client import BBClient, _stacked_migrate_for
from repro.core.layouts import LayoutMode
from repro.core.policy import LayoutPolicy

from test_adapt import (STREAM_DIGEST, _digest, _interleaved_stream)

N, Q, W, CAP = 4, 8, 16, 64
SCOPE = "/bb/h"


def _client(exchange="auto", **kw):
    policy = LayoutPolicy.from_scopes({SCOPE: LayoutMode.HYBRID},
                                      n_nodes=N,
                                      default=LayoutMode.DIST_HASH)
    return BBClient(policy, cap=CAP, words=W, mcap=CAP, exchange=exchange,
                    **kw)


def _request(client, seed=0):
    rng = np.random.RandomState(seed)
    paths = [[f"{SCOPE}/r{i}/f{j}" if j % 2 else f"/bb/g/r{i}/f{j}"
              for j in range(Q)] for i in range(N)]
    return client.encode(paths, chunk_id=rng.randint(0, 4, (N, Q)),
                         payload=rng.randint(0, 9999, (N, Q, W)))


def _resolved(client, req):
    return client._modes(req), client._chunk_id(req), client._valid(req)


def _meta_args(client, req, opcode=bb.OP_STAT):
    mode, _, valid = _resolved(client, req)
    shape = req.path_hash.shape
    return (mode, jnp.full(shape, opcode, jnp.int32), req.path_hash,
            jnp.zeros(shape, jnp.int32), jnp.full(shape, -1, jnp.int32),
            valid)


def _program(client, req, kind, owned):
    """The jitted program a call of ``kind`` runs, and its arguments."""
    mode, cid, valid = _resolved(client, req)
    ph = req.path_hash
    if kind == "write":
        cfg = client._call_config("write", mode, ph, cid, valid)
        return (client._ops_for(cfg, owned)[0],
                (client.state, mode, ph, cid, req.payload, valid))
    if kind == "meta":
        cfg = client._call_config("meta", mode, ph, None, valid)
        return (client._ops_for(cfg, owned)[2],
                (client.state,) + _meta_args(client, req))
    old = jnp.full(ph.shape, int(LayoutMode.HYBRID), jnp.int32)
    new = jnp.full(ph.shape, int(LayoutMode.DIST_HASH), jnp.int32)
    op = _stacked_migrate_for(client.policy.engine_key(),
                              client._migrate_config())
    return op, (client.state, ph, cid, valid, old, new)


def _table_copies(text):
    """Operands of the copies of data-table shape in a compiled program,
    and which of them are the program's own parameters."""
    shape = rf"s32\[{N},{CAP},{W}\]\S*"
    copies = re.findall(rf"{shape} copy\((%[\w.-]+)\)", text)
    params = set(re.findall(rf"(%[\w.-]+) = {shape} parameter\(", text))
    return copies, [c for c in copies if c in params]


@pytest.mark.parametrize("exchange", ["dense", "compacted"])
@pytest.mark.parametrize("kind", ["write", "meta", "migrate"])
def test_public_programs_update_the_tables_in_place(kind, exchange):
    """The compiled program of each public mutating call aliases the
    state parameter to its output and never copies the table it was
    given.  Write and metadata programs copy no table at all; a
    migration's re-compaction builds its new table by a gather."""
    client = _client(exchange)
    req = _request(client)
    client.write(req)
    op, args = _program(client, req, kind, owned=True)
    compiled = op.lower(*args).compile()
    text = compiled.as_text()
    assert re.search(r"input_output_alias=\{ \{0\}: \(0, \{\}", text)
    assert compiled.memory_analysis().alias_size_in_bytes >= \
        client.state.data.nbytes
    copies, of_params = _table_copies(text)
    assert not of_params
    if kind != "migrate":
        assert not copies


@pytest.mark.parametrize("kind", ["write", "meta"])
def test_state_explicit_programs_keep_the_state(kind):
    """The programs of ``_ops`` donate nothing: their output is a copy."""
    client = _client("compacted")
    req = _request(client)
    client.write(req)
    op, args = _program(client, req, kind, owned=False)
    compiled = op.lower(*args).compile()
    assert compiled.memory_analysis().alias_size_in_bytes == 0
    assert _table_copies(compiled.as_text())[1]


@pytest.mark.parametrize("call", ["write", "create", "stat", "remove"])
def test_public_mutating_calls_delete_the_old_state(call):
    client = _client()
    req = _request(client)
    client.write(req)
    before = client.state
    getattr(client, call)(req)
    assert before.data.is_deleted() and before.meta_key.is_deleted()
    assert not client.state.data.is_deleted()


def test_read_leaves_the_state_alive():
    client = _client()
    req = _request(client)
    client.write(req)
    before = client.state
    out, found = client.read(req)
    assert not before.data.is_deleted()
    assert bool(np.asarray(found).all())
    assert np.array_equal(np.asarray(out), np.asarray(req.payload))


def test_state_explicit_entries_leave_the_state_alive():
    """``_write`` / ``_meta`` / ``_read`` may run again on the state they
    were given, with the same answers."""
    client = _client()
    req = _request(client)
    mode, cid, valid = _resolved(client, req)
    state = client.state
    first = client._write(state, mode, req.path_hash, cid, req.payload,
                          valid)
    again = client._write(state, mode, req.path_hash, cid, req.payload,
                          valid)
    assert not state.data.is_deleted()
    assert _digest(*first.tree_flatten()[0]) == \
        _digest(*again.tree_flatten()[0])
    args = _meta_args(client, req)
    m1 = client._meta(first, *args)
    m2 = client._meta(first, *args)
    assert not first.data.is_deleted()
    assert _digest(*m1[1:]) == _digest(*m2[1:])
    assert bool(np.asarray(m1[1]).all())
    out, found = client._read(first, mode, req.path_hash, cid, valid)
    assert not first.data.is_deleted()
    assert np.array_equal(np.asarray(out), np.asarray(req.payload))


def test_adopted_state_is_handed_over():
    """``BBClient(state=...)`` takes the state over: the first mutating
    call deletes the caller's arrays, and the client reads its own."""
    writer = _client()
    req = _request(writer)
    writer.write(req)
    shared = writer.state
    reader = _client(state=shared)
    out, found = reader.read(req)
    assert not shared.data.is_deleted()
    found_meta, _, _ = reader.stat(req)
    assert shared.data.is_deleted()
    assert bool(np.asarray(found_meta).all())
    out2, found2 = reader.read(req)
    assert np.array_equal(np.asarray(out2), np.asarray(out))


def test_live_migration_donates_and_keeps_the_stream_digest(monkeypatch):
    """Every ``LiveMigrator`` installment runs through the public
    ``migrate_rows``, which donates the state, and the relayout stream
    still reproduces its pinned digest."""
    seen = []
    migrate = BBClient.migrate_rows

    def spy(self, *args, **kw):
        before = self.state
        out = migrate(self, *args, **kw)
        seen.append(before.data.is_deleted())
        return out

    monkeypatch.setattr(BBClient, "migrate_rows", spy)
    client, outs = _interleaved_stream(relayout=True)
    assert seen and all(seen)
    assert _digest(*outs) == STREAM_DIGEST
    assert client.fallback is None


def test_migrator_step_rebinds_the_client_state():
    client = _client(telemetry=True)
    client.write(_request(client))
    mig = LiveMigrator(client, SCOPE, LayoutMode.DIST_HASH, step_chunks=4)
    before = client.state
    mig.step()
    assert before.data.is_deleted()
    mig.run()
    found, _, _ = client.stat(_request(client))
    assert not client.state.data.is_deleted()
    assert bool(np.asarray(found).all())
