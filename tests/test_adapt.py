"""Online adaptation: telemetry counters, drift hysteresis, re-decision
gating, and the losslessness of the live relayout (stacked + mesh).

The heart of the file is the interleaved-stream digest: the SAME op
sequence is driven through a client with and without a mid-stream
relayout, and every observable (read payloads/found, stat triples) must
be bit-for-bit identical — pinned against a frozen digest so neither run
can drift.
"""
import hashlib
import json
import pathlib
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
from repro.core.adapt import (AdaptConfig, AdaptationController,
                              DriftConfig, DriftDetector, LiveMigrator,
                              ScopeTelemetry, signature_from_phases,
                              signature_from_stats)
from repro.core.adapt import redecide, telemetry as tm
from repro.core.adapt.migrate import final_policy, transition_policy
from repro.core.client import BBClient, BBRequest
from repro.core.intent.probe import RuntimeStats
from repro.core.layouts import LayoutMode, str_hash
from repro.core.policy import LayoutPolicy

ROOT = pathlib.Path(__file__).resolve().parents[1]

N, Q, W = 8, 6, 8
SCOPE = "/bb/hot"


def _policy(default=LayoutMode.DIST_HASH, scope_mode=LayoutMode.NODE_LOCAL):
    return LayoutPolicy.from_scopes({SCOPE: scope_mode}, n_nodes=N,
                                    default=default)


def _digest(*arrays):
    h = hashlib.sha256()
    for a in arrays:
        h.update(np.ascontiguousarray(np.asarray(a)).tobytes())
    return h.hexdigest()[:32]


# ---------------------------------------------------------------------------
# telemetry
# ---------------------------------------------------------------------------
def test_telemetry_counts_op_mix_and_locality():
    client = BBClient(_policy(), cap=128, words=W, mcap=128, telemetry=True)
    rng = np.random.RandomState(0)
    paths = [[f"{SCOPE}/r{i}/f{j % 2}" for j in range(Q)] for i in range(N)]
    cid = np.tile(np.arange(Q, dtype=np.int32), (N, 1))
    payload = rng.randint(0, 99, (N, Q, W)).astype(np.int32)
    req = client.encode(paths, chunk_id=cid, payload=payload)
    client.write(req)
    client.read(req)                 # self-written → locality 1
    client.stat(req)
    counts = np.asarray(client.telemetry.counts)
    row = counts[client.telemetry.row_of(SCOPE)]
    assert row[tm.F_WRITES] == N * Q
    assert row[tm.F_READS] == N * Q
    assert row[tm.F_META] == N * Q
    assert row[tm.F_WORDS_W] == N * Q * W
    assert row[tm.F_SELF] == N * Q          # every read self-affine
    assert counts[0, tm.F_WRITES] == 0      # nothing in the default row
    sig = tm.signature_of_row(row)
    assert sig.shape == (len(tm.SIG_NAMES),)
    assert np.all((sig >= 0) & (sig <= 1))
    assert sig[2] == 1.0                    # locality
    # cross-rank replay flips the locality signal
    perm = np.roll(np.arange(N), 1)
    rreq = BBRequest(path_hash=req.path_hash[perm],
                     chunk_id=req.chunk_id[perm],
                     scope_hash=req.scope_hash[perm])
    before = client.telemetry.snapshot()
    client.read(rreq)
    sigs = client.telemetry.signatures(since=before)
    sig2, weight = sigs[SCOPE]
    assert weight == N * Q
    assert sig2[0] == 1.0                   # pure-read tick
    assert sig2[2] == 0.0                   # nothing self-written


def test_telemetry_sequential_stride_signature():
    client = BBClient(_policy(), cap=64, words=W, mcap=64, telemetry=True)
    paths = [[f"{SCOPE}/s{i}" for _ in range(Q)] for i in range(N)]
    cid = np.tile(np.arange(Q, dtype=np.int32), (N, 1))      # strictly seq
    payload = np.zeros((N, Q, W), np.int32)
    client.write(client.encode(paths, chunk_id=cid, payload=payload))
    row = np.asarray(client.telemetry.counts)[1]
    assert row[tm.F_PAIRS] == N * (Q - 1)
    assert row[tm.F_SEQ] == N * (Q - 1)
    assert tm.signature_of_row(row)[3] == 1.0                # seq


def test_telemetry_rebind_preserves_surviving_scopes():
    client = BBClient(_policy(), cap=64, words=W, mcap=64, telemetry=True)
    paths = [[f"{SCOPE}/x" for _ in range(Q)] for _ in range(N)]
    client.write(client.encode(paths, chunk_id=np.zeros((N, Q), np.int32),
                               payload=np.zeros((N, Q, W), np.int32)))
    before = np.asarray(client.telemetry.counts)[1].copy()
    client.install_policy(_policy(scope_mode=LayoutMode.DIST_HASH))
    after = np.asarray(client.telemetry.counts)
    assert np.array_equal(after[client.telemetry.row_of(SCOPE)], before)


def test_baseline_signatures_share_the_live_space():
    rs = RuntimeStats(posix_bytes_written=1e6, posix_bytes_read=9e6,
                      posix_writes=10, posix_reads=90, posix_meta_ops=5,
                      posix_seq_ratio=0.8, cross_rank_ops=45)
    sig = signature_from_stats(rs)
    assert sig.shape == (len(tm.SIG_NAMES),)
    assert sig[0] == pytest.approx(0.9)
    assert sig[2] == pytest.approx(0.5)
    phases = redecide.phases_from_signature(SCOPE, sig)
    sig2 = signature_from_phases(phases)
    # synthesized phases round-trip the load-bearing dimensions
    assert abs(sig2[0] - sig[0]) < 0.1
    assert (sig2[2] >= 0.5) == (sig[2] >= 0.5)


# ---------------------------------------------------------------------------
# drift detection + hysteresis
# ---------------------------------------------------------------------------
BASE = np.array([0.1, 0.05, 1.0, 0.9, 0.0, 0.5])
DRIFTED = np.array([0.95, 0.05, 0.0, 0.2, 0.0, 0.5])


def test_drift_fires_only_after_patience():
    det = DriftDetector(baseline={"s": BASE.copy()},
                        cfg=DriftConfig(patience=2, cooldown=3))
    assert not det.observe("s", BASE, 100).fired       # stable
    r1 = det.observe("s", DRIFTED, 100)
    assert r1.armed == 1 and not r1.fired              # transient burst
    r2 = det.observe("s", DRIFTED, 100)
    assert r2.fired                                    # sustained


def test_transient_burst_does_not_thrash():
    det = DriftDetector(baseline={"s": BASE.copy()},
                        cfg=DriftConfig(patience=2, alpha=1.0))
    assert det.observe("s", DRIFTED, 100).armed == 1
    assert det.observe("s", BASE, 100).armed == 0      # burst over: re-arm
    assert not det.observe("s", DRIFTED, 100).fired


def test_cooldown_blocks_refire_inside_hysteresis_window():
    cfg = DriftConfig(patience=1, cooldown=3, alpha=1.0)
    det = DriftDetector(baseline={"s": BASE.copy()}, cfg=cfg)
    assert det.observe("s", DRIFTED, 100).fired
    det.rebase("s")                                    # decision taken
    other = np.array([0.1, 0.9, 1.0, 0.9, 0.0, 0.5])
    for _ in range(cfg.cooldown):
        assert not det.observe("s", other, 100).fired  # silenced
    det.observe("s", other, 100)                       # cooldown spent: arms
    assert det.observe("s", other, 100).fired


def test_low_volume_ticks_carry_no_signal():
    det = DriftDetector(baseline={"s": BASE.copy()},
                        cfg=DriftConfig(patience=1, min_weight=8))
    assert not det.observe("s", DRIFTED, 2).fired
    assert det.observe("s", DRIFTED, 100).fired


def test_drift_fires_exactly_at_patience_boundary_per_metrics():
    """Hysteresis edge, observed through the public metrics counters
    only: tick N-1 of an over-threshold run is `armed`, tick N (N =
    patience) is `fired` — never earlier."""
    from repro.core import obs
    rec = obs.TraceRecorder()
    det = DriftDetector(baseline={"s": BASE.copy()},
                        cfg=DriftConfig(patience=3, cooldown=3, alpha=1.0))
    with obs.activate(rec):
        for _ in range(2):                     # patience-1 armed ticks
            det.observe("s", DRIFTED, 100)
        m = rec.metrics
        assert m.get("drift_ticks_total", scope="s", outcome="armed") == 2
        assert m.get("drift_fired_total", scope="s") == 0
        assert m.gauge("drift_armed", scope="s") == 2.0
        det.observe("s", DRIFTED, 100)         # tick `patience`: fires
        assert m.get("drift_fired_total", scope="s") == 1
        assert m.get("drift_ticks_total", scope="s", outcome="fired") == 1


def test_drift_rearms_and_refires_after_cooldown_per_metrics():
    from repro.core import obs
    rec = obs.TraceRecorder()
    cfg = DriftConfig(patience=1, cooldown=3, alpha=1.0)
    det = DriftDetector(baseline={"s": BASE.copy()}, cfg=cfg)
    other = np.array([0.1, 0.9, 1.0, 0.9, 0.0, 0.5])
    with obs.activate(rec):
        det.observe("s", DRIFTED, 100)         # fire #1
        det.rebase("s")                        # decision taken → cooldown
        m = rec.metrics
        assert m.get("drift_rebase_total", scope="s") == 1
        assert m.gauge("drift_cooling", scope="s") == float(cfg.cooldown)
        for _ in range(cfg.cooldown):
            det.observe("s", other, 100)       # silenced
        assert m.get("drift_ticks_total", scope="s",
                     outcome="cooling") == cfg.cooldown
        assert m.get("drift_fired_total", scope="s") == 1
        det.observe("s", other, 100)           # cooldown spent: fire #2
        assert m.get("drift_fired_total", scope="s") == 2
        assert m.gauge("drift_cooling", scope="s") == 0.0


def test_drift_transient_burst_never_fires_per_metrics():
    from repro.core import obs
    rec = obs.TraceRecorder()
    det = DriftDetector(baseline={"s": BASE.copy()},
                        cfg=DriftConfig(patience=2, alpha=1.0))
    with obs.activate(rec):
        for _ in range(4):                     # alternating burst/stable
            det.observe("s", DRIFTED, 100)
            det.observe("s", BASE, 100)
    m = rec.metrics
    assert m.get("drift_fired_total", scope="s") == 0
    assert m.get("drift_ticks_total", scope="s", outcome="armed") == 4
    assert m.get("drift_ticks_total", scope="s", outcome="quiet") == 4
    assert m.gauge("drift_armed", scope="s") == 0.0


# ---------------------------------------------------------------------------
# re-decision + cost/benefit gate
# ---------------------------------------------------------------------------
def test_redecision_moves_cross_rank_reads_off_node_local():
    policy = _policy(scope_mode=LayoutMode.NODE_LOCAL)
    deltas = redecide.propose_deltas(policy, {SCOPE: (DRIFTED, 1000.0)})
    assert len(deltas) == 1
    d = deltas[0]
    assert d.old_mode == LayoutMode.NODE_LOCAL
    assert d.new_mode != LayoutMode.NODE_LOCAL   # stranded reads priced out
    assert d.gain_s > 0


def test_redecision_keeps_a_matched_layout():
    policy = _policy(scope_mode=LayoutMode.NODE_LOCAL)
    local_burst = np.array([0.0, 0.02, 1.0, 1.0, 0.0, 0.5])
    assert redecide.propose_deltas(policy,
                                   {SCOPE: (local_burst, 1000.0)}) == []


def test_gate_weighs_horizon_win_against_migration_cost():
    policy = _policy()
    (d,) = redecide.propose_deltas(policy, {SCOPE: (DRIFTED, 1000.0)})
    ok_long, audit = redecide.gate_delta(d, n_chunks=256, words=16,
                                         n_nodes=N, horizon_rounds=1e4)
    assert ok_long and audit["horizon_win_s"] > audit["migration_cost_s"]
    ok_short, _ = redecide.gate_delta(d, n_chunks=1 << 22, words=16,
                                      n_nodes=N, horizon_rounds=1e-6)
    assert not ok_short


def test_signature_workload_runs_the_full_selector():
    from repro.core.intent.selector import select_layout
    wl = redecide.signature_workload(SCOPE, DRIFTED, n_nodes=N)
    decision = select_layout(wl, use_runtime=True)
    assert decision.mode in set(LayoutMode)


# ---------------------------------------------------------------------------
# live relayout: transition policies + migration invariants
# ---------------------------------------------------------------------------
def test_transition_policy_keeps_both_epoch_modes_present():
    p = _policy(scope_mode=LayoutMode.NODE_LOCAL)
    trans, old = transition_policy(p, SCOPE, LayoutMode.DIST_HASH, epoch=1)
    assert old == LayoutMode.NODE_LOCAL
    assert trans.mode_for_path(f"{SCOPE}/f") == LayoutMode.DIST_HASH
    assert {LayoutMode.NODE_LOCAL,
            LayoutMode.DIST_HASH} <= trans.modes_present()
    fin = final_policy(trans, SCOPE, LayoutMode.DIST_HASH)
    assert fin.modes_present() == frozenset({LayoutMode.DIST_HASH})
    assert not any(s.startswith("/__epoch") for s, _ in fin.scopes)


def _interleaved_stream(relayout: bool, backend="stacked",
                        new_mode=LayoutMode.DIST_HASH, **client_kw):
    """Drive one fixed interleaved op stream; return every observable.

    With ``relayout=True`` a LiveMigrator for SCOPE runs one installment
    between every op (partial-watermark reads/stats exercised at every
    prefix), completing mid-stream.  Reads are cross-rank (well-defined
    under a NODE_LOCAL source via the stranded-data broadcast); stats are
    writer-aligned — Mode-1 cross-rank stat is the paper's structural
    metadata collapse, i.e. its answer depends on the accidental
    requester/writer alignment, which no lossless relayout can (or
    should) reproduce.
    """
    client = BBClient(_policy(), backend, cap=256, words=W, mcap=256,
                      telemetry=True, **client_kw)
    rng = np.random.RandomState(7)
    outs = []
    reqs = []
    for r in range(3):                     # phase A: local write bursts
        paths = [[f"{SCOPE}/r{i}/f{j % 2}" for j in range(Q)]
                 for i in range(N)]
        shared = [[f"/shared/g{j}" for j in range(Q)] for _ in range(N)]
        cid = rng.randint(0, 4, (N, Q)).astype(np.int32)
        pay = rng.randint(0, 9999, (N, Q, W)).astype(np.int32)
        wreq = client.encode(paths, chunk_id=cid, payload=pay)
        client.write(wreq)
        client.write(client.encode(shared, chunk_id=cid, payload=pay))
        reqs.append(wreq)

    mig = None
    if relayout:
        mig = LiveMigrator(client, SCOPE, new_mode, step_chunks=8)
        assert mig.total_chunks > 0

    perm = np.roll(np.arange(N), 3)
    for step in range(12):                 # phase B: cross-rank analysis
        base = reqs[step % len(reqs)]
        rreq = BBRequest(path_hash=base.path_hash[perm],
                         chunk_id=base.chunk_id[perm],
                         scope_hash=base.scope_hash[perm])
        out, found = client.read(rreq)
        fnd, size, _ = client.stat(base)       # writer-aligned stat
        outs += [out, found, fnd, size]
        if mig is not None and not mig.done:
            mig.step()                     # advance the watermark mid-stream
            if mig.done:
                mig.finish()
    if mig is not None and mig.done and client.fallback is not None:
        mig.finish()
    return client, outs


# frozen observables of the stream above WITHOUT any relayout — both runs
# must reproduce it bit-for-bit (captured at PR 4)
STREAM_DIGEST = "cfd76da6b40767fb96d3095ded4fbb01"


def test_relayout_is_invisible_to_reads_and_stats():
    _, plain = _interleaved_stream(relayout=False)
    client, migrated = _interleaved_stream(relayout=True)
    assert _digest(*plain) == _digest(*migrated)
    assert _digest(*plain) == STREAM_DIGEST
    assert client.epoch == 2               # transition + final
    assert client.fallback is None
    assert client.policy.mode_for_path(f"{SCOPE}/x") == LayoutMode.DIST_HASH


def test_relayout_into_hybrid_is_also_lossless():
    _, plain = _interleaved_stream(relayout=False)
    _, migrated = _interleaved_stream(relayout=True,
                                      new_mode=LayoutMode.HYBRID)
    assert _digest(*plain) == _digest(*migrated)


def test_stat_mid_relayout_reports_the_larger_epoch_size():
    """A file written again during its scope's relayout, before its
    installment, has an entry in each epoch; stat reports the larger
    size, as it would have without the relayout."""
    q = 2
    client = BBClient(_policy(scope_mode=LayoutMode.DIST_HASH), cap=64,
                      words=W, mcap=64, telemetry=True)
    paths = [[f"{SCOPE}/r{i}/f"] * q for i in range(N)]
    pay = np.ones((N, q, W), np.int32)
    client.write(client.encode(paths, chunk_id=np.tile([0, 2], (N, 1)),
                               payload=pay))
    LiveMigrator(client, SCOPE, LayoutMode.CENTRAL_META, step_chunks=4)
    req = client.encode(paths, chunk_id=np.ones((N, q), np.int32),
                        payload=pay)
    client.write(req)                      # chunk 1, new epoch only
    found, size, _ = client.stat(req)
    assert bool(np.asarray(found).all())
    assert (np.asarray(size) == 3).all()


def test_migration_moves_the_bytes_not_just_the_policy():
    client = BBClient(_policy(), cap=256, words=W, mcap=256, telemetry=True)
    paths = [[f"{SCOPE}/n{i}" for _ in range(Q)] for i in range(N)]
    cid = np.tile(np.arange(Q, dtype=np.int32), (N, 1))
    pay = np.random.RandomState(3).randint(0, 999, (N, Q, W)).astype(
        np.int32)
    req = client.encode(paths, chunk_id=cid, payload=pay)
    client.write(req)
    # NODE_LOCAL: every chunk sits on its writer
    assert np.array_equal(np.asarray(client.state.data_count),
                          np.full(N, Q))
    LiveMigrator(client, SCOPE, LayoutMode.DIST_HASH, step_chunks=16).run()
    counts = np.asarray(client.state.data_count)
    assert int(counts.sum()) == N * Q      # tombstones reclaimed the rest
    assert not np.array_equal(counts, np.full(N, Q))   # hash-spread now
    # reads under the PURE new policy (fallback disarmed) still find all
    out, found = client.read(req)
    assert bool(np.asarray(found).all())
    assert np.array_equal(np.asarray(out), pay)


def test_migrate_rows_skips_phantom_worklist_entries():
    client = BBClient(_policy(), cap=64, words=W, mcap=64, telemetry=True)
    trans, old = transition_policy(client.policy, SCOPE,
                                   LayoutMode.DIST_HASH, epoch=1)
    client.install_policy(trans, migrating=SCOPE, old_mode=int(old))
    ghost = np.full((N, 1), str_hash(f"{SCOPE}/never-written"), np.int32)
    moved, found_old = client.migrate_rows(
        jnp.asarray(ghost), jnp.zeros((N, 1), jnp.int32),
        jnp.ones((N, 1), bool),
        old_mode=int(old), new_mode=int(LayoutMode.DIST_HASH))
    assert not bool(np.asarray(moved).any())
    assert not bool(np.asarray(found_old).any())
    # and crucially: no phantom metadata entry was minted
    req = BBRequest(path_hash=jnp.asarray(ghost),
                    scope_hash=jnp.full((N, 1), str_hash(SCOPE), jnp.int32))
    fnd, _, _ = client.stat(req)
    assert not bool(np.asarray(fnd).any())


def test_remove_during_migration_cannot_resurrect():
    client = BBClient(_policy(), cap=128, words=W, mcap=128, telemetry=True)
    paths = [[f"{SCOPE}/d{i}" for _ in range(Q)] for i in range(N)]
    cid = np.tile(np.arange(Q, dtype=np.int32), (N, 1))
    pay = np.zeros((N, Q, W), np.int32)
    req = client.encode(paths, chunk_id=cid, payload=pay)
    client.write(req)
    mig = LiveMigrator(client, SCOPE, LayoutMode.DIST_HASH, step_chunks=4)
    mig.step()                            # partial watermark
    assert client.remove(req) is not None
    fnd, _, _ = client.stat(req)
    assert not bool(np.asarray(fnd).any())   # gone in BOTH epochs
    while not mig.done:
        mig.step()
    mig.finish()
    fnd, _, _ = client.stat(req)
    assert not bool(np.asarray(fnd).any())


@settings(max_examples=8, deadline=None)
@given(st.integers(min_value=0, max_value=10_000))
def test_property_random_streams_migration_parity(seed):
    """Random op sequences: relayout at a random point is unobservable.

    Old modes are drawn from {DIST_HASH, HYBRID} (hashed metadata, so
    cross-rank stats are well-defined either epoch — Mode-1's stat
    collapse is covered by the writer-aligned digest stream instead).
    Writes are per-row-unique N-N files (duplicate same-key writes in
    ONE batch pick their winner by mode-specific tiebreaks, so a
    post-relayout write batch legitimately behaves like the new mode);
    reads/stats are cross-rank.  Observables exclude the ``loc`` routing
    hint, which legitimately changes when data physically moves.
    """
    rng = np.random.RandomState(seed)
    n, q, w = 4, 4, 4
    policy = LayoutPolicy.from_scopes(
        {SCOPE: LayoutMode(rng.choice([3, 4]))}, n_nodes=n,
        default=LayoutMode.DIST_HASH)
    new_mode = LayoutMode(rng.choice([2, 3]))
    if new_mode == policy.mode_for_path(SCOPE):
        new_mode = LayoutMode.HYBRID
    mig_at = rng.randint(0, 8)
    ops = rng.randint(0, 3, 10)

    def drive(relayout):
        client = BBClient(policy, cap=128, words=w, mcap=128,
                          telemetry=True)
        r2 = np.random.RandomState(seed + 1)
        outs, mig = [], None
        for t, op in enumerate(ops):
            if op == 0:              # N-N write burst: row-unique files
                paths = [[f"{SCOPE}/r{i}/p{r2.randint(3)}"
                          for _ in range(q)] for i in range(n)]
            else:                    # cross-rank analysis access
                owner = r2.randint(0, n, (n, q))
                paths = [[f"{SCOPE}/r{owner[i, j]}/p{r2.randint(3)}"
                          for j in range(q)] for i in range(n)]
            cid = r2.randint(0, 3, (n, q)).astype(np.int32)
            pay = r2.randint(0, 99, (n, q, w)).astype(np.int32)
            req = client.encode(paths, chunk_id=cid, payload=pay)
            if op == 0:
                client.write(req)
            elif op == 1:
                out, found = client.read(req)
                outs += [out, found]
            else:
                fnd, size, _ = client.stat(req)
                outs += [fnd, size]
            if relayout:
                if t == mig_at and mig is None:
                    mig = LiveMigrator(client, SCOPE, new_mode,
                                       step_chunks=4)
                if mig is not None and not mig.done:
                    mig.step()
                    if mig.done:
                        mig.finish()
        return outs

    plain, moved = drive(False), drive(True)
    assert len(plain) == len(moved)
    for a, b in zip(plain, moved):
        assert np.array_equal(np.asarray(a), np.asarray(b)), seed


# ---------------------------------------------------------------------------
# controller end-to-end + thrash guard
# ---------------------------------------------------------------------------
def _drifting_controller(n=4, q=8, w=4):
    policy = LayoutPolicy.from_scopes({SCOPE: LayoutMode.NODE_LOCAL},
                                      n_nodes=n,
                                      default=LayoutMode.DIST_HASH)
    client = BBClient(policy, cap=256, words=w, mcap=256, telemetry=True)
    ctl = AdaptationController(
        client, cfg=AdaptConfig(
            drift=DriftConfig(patience=2, cooldown=3, min_weight=4.0),
            horizon_rounds=1e4, step_chunks=16))
    rng = np.random.RandomState(0)
    paths = [[f"{SCOPE}/c{i}" for _ in range(q)] for i in range(n)]
    cid = np.tile(np.arange(q, dtype=np.int32), (n, 1))
    pay = rng.randint(0, 999, (n, q, w)).astype(np.int32)
    req = client.encode(paths, chunk_id=cid, payload=pay)
    return ctl, client, req, pay


def test_controller_adapts_a_drifting_stream_losslessly():
    ctl, client, req, pay = _drifting_controller()
    n = client.n_nodes
    client.write(req)
    ctl.tick()                                      # baseline: local writes
    perm = np.roll(np.arange(n), 1)
    rreq = BBRequest(path_hash=req.path_hash[perm],
                     chunk_id=req.chunk_id[perm],
                     scope_hash=req.scope_hash[perm])
    phases = []
    for _ in range(12):                             # cross-rank read phase
        out, found = client.read(rreq)
        assert bool(np.asarray(found).all())
        assert np.array_equal(np.asarray(out), pay[perm])
        phases.append(ctl.tick().phase)
    assert "adopted" in phases
    assert "completed" in phases
    assert client.policy.mode_for_path(f"{SCOPE}/c0") != \
        LayoutMode.NODE_LOCAL
    assert client.fallback is None
    summary = ctl.summary()
    assert summary["adoptions"] and summary["completions"]
    assert summary["epoch"] == client.epoch


def test_controller_thrash_guard_one_adoption_per_drift():
    ctl, client, req, pay = _drifting_controller()
    client.write(req)
    ctl.tick()
    perm = np.roll(np.arange(client.n_nodes), 1)
    rreq = BBRequest(path_hash=req.path_hash[perm],
                     chunk_id=req.chunk_id[perm],
                     scope_hash=req.scope_hash[perm])
    for _ in range(16):
        client.read(rreq)
        ctl.tick()
    adoptions = [r for r in ctl.history if r.phase == "adopted"]
    assert len(adoptions) == 1          # sustained drift ≠ repeated churn
    # and no adoption happened while another migration was in flight
    for prev, cur in zip(ctl.history, ctl.history[1:]):
        if prev.phase == "migrating":
            assert cur.phase in ("migrating", "completed")


def test_controller_never_adapts_the_default_bucket():
    """Unscoped traffic drifts in telemetry row 0, but "<default>" is not
    a path scope — the controller must never mint it as one."""
    policy = LayoutPolicy.from_scopes({SCOPE: LayoutMode.NODE_LOCAL},
                                      n_nodes=4,
                                      default=LayoutMode.NODE_LOCAL)
    client = BBClient(policy, cap=256, words=4, mcap=256, telemetry=True)
    ctl = AdaptationController(
        client, cfg=AdaptConfig(drift=DriftConfig(patience=1, cooldown=0,
                                                  min_weight=1.0),
                                horizon_rounds=1e9))
    rng = np.random.RandomState(0)
    # raw requests with no scope_hash → telemetry default row
    req = BBRequest(
        path_hash=jnp.asarray(rng.randint(1, 1 << 20, (4, 8)), jnp.int32),
        chunk_id=jnp.zeros((4, 8), jnp.int32),
        payload=jnp.asarray(rng.randint(0, 9, (4, 8, 4)), jnp.int32))
    client.write(req)
    ctl.tick()                                   # baseline: write burst
    for _ in range(6):                           # drift: pure reads
        client.read(req)
        rep = ctl.tick()
        assert rep.phase in ("idle", "drifted"), rep.phase
    assert not any(r.phase == "adopted" for r in ctl.history)
    assert all(s != tm.DEFAULT_SCOPE for s, _ in client.policy.scopes)


def test_migrator_normalizes_trailing_slash_scopes():
    client = BBClient(_policy(), cap=128, words=W, mcap=128, telemetry=True)
    paths = [[f"{SCOPE}/t{i}" for _ in range(Q)] for i in range(N)]
    cid = np.tile(np.arange(Q, dtype=np.int32), (N, 1))
    pay = np.random.RandomState(5).randint(0, 99, (N, Q, W)).astype(
        np.int32)
    req = client.encode(paths, chunk_id=cid, payload=pay)
    client.write(req)
    mig = LiveMigrator(client, SCOPE + "/", LayoutMode.DIST_HASH,
                       step_chunks=16)
    assert mig.total_chunks == N * Q             # worklist found the files
    assert client.fallback.scope_hash == str_hash(SCOPE)
    mig.step()                                   # mid-watermark dual-epoch
    out, found = client.read(req)
    assert bool(np.asarray(found).all())
    while not mig.done:
        mig.step()
    mig.finish()
    # exactly ONE scope entry survives, in the new mode
    assert [m for s, m in client.policy.scopes if s == SCOPE] == \
        [LayoutMode.DIST_HASH]
    out, found = client.read(req)
    assert bool(np.asarray(found).all())
    assert np.array_equal(np.asarray(out), pay)


def test_train_loop_runs_the_adaptation_tick():
    """The loop ticks the controller on its cadence and re-points the
    checkpoint manager at the adapted plan when a tick adopts."""
    import tempfile

    from repro.configs import all_configs
    from repro.core.adapt.controller import TickReport
    from repro.models import build_model
    from repro.train.loop import LoopConfig, run_training

    adopted_policy = LayoutPolicy.from_scopes(
        {"ckpt": LayoutMode.DIST_HASH}, n_nodes=8,
        default=LayoutMode.DIST_HASH)

    class StubController:
        """Duck-typed controller: adopts a new plan on its 2nd tick."""

        def __init__(self):
            self.ticks = 0
            self.client = type("C", (), {"policy": adopted_policy})()

        def tick(self):
            self.ticks += 1
            phase = "adopted" if self.ticks == 2 else "idle"
            return TickReport(self.ticks, phase)

    ctl = StubController()
    cfg = all_configs()["gemma3-1b"].reduced()
    model = build_model(cfg)
    with tempfile.TemporaryDirectory() as d:
        loop_cfg = LoopConfig(steps=6, ckpt_every=3, ckpt_dir=d,
                              adapt_controller=ctl, adapt_every=2)
        res = run_training(model, cfg, batch_size=2, seq_len=16,
                           loop_cfg=loop_cfg)
        # ckpt at step 3 predates the adoption (tick 2 = step 4); the
        # step-6 one must already be routed by the adapted plan
        metas = {p.name: json.loads(p.read_text())
                 for p in pathlib.Path(d).glob("ckpt_*.json")}
        assert metas["ckpt_3.json"]["layout_mode"] == \
            int(LayoutMode.NODE_LOCAL)
        assert metas["ckpt_6.json"]["layout_mode"] == \
            int(LayoutMode.DIST_HASH)
    assert res.final_step == 6
    assert ctl.ticks == 3                  # steps 2, 4, 6


# ---------------------------------------------------------------------------
# committed BENCH_pr4 artifact (make bench-adapt regenerates)
# ---------------------------------------------------------------------------
def test_bench_pr4_adapted_beats_static_mismatch():
    p = ROOT / "BENCH_pr4.json"
    if not p.is_file():
        pytest.skip("BENCH_pr4.json not present (run `make bench-adapt`)")
    data = json.loads(p.read_text())
    s = data["summary"]
    assert s["steady_state_speedup"] >= 1.5
    # migration pays for itself inside the measured run
    assert s["amortized_after_rounds"] <= data["meta"]["rounds_b"]
    assert s["detection_round"] is not None
    assert data["adaptation"]["adoptions"]
    assert data["adaptation"]["completions"]


# ---------------------------------------------------------------------------
# mesh backend: the same relayout, shard_map + all_to_all data plane
# ---------------------------------------------------------------------------
MESH_MIGRATE_SCRIPT = textwrap.dedent("""
    import os
    os.environ['XLA_FLAGS'] = '--xla_force_host_platform_device_count=4'
    import sys; sys.path.insert(0, 'src')
    import numpy as np
    import jax.numpy as jnp
    from repro.core.adapt import LiveMigrator
    from repro.core.client import BBClient, BBRequest
    from repro.core.layouts import LayoutMode
    from repro.core.mesh_engine import make_node_mesh
    from repro.core.policy import LayoutPolicy

    N, q, w = 4, 6, 8
    policy = LayoutPolicy.from_scopes({"/bb/hot": LayoutMode.NODE_LOCAL},
                                      n_nodes=N,
                                      default=LayoutMode.DIST_HASH)
    clients = {"mesh": BBClient(policy, make_node_mesh(4), cap=128,
                                words=w, mcap=128, telemetry=True),
               "stacked": BBClient(policy, cap=128, words=w, mcap=128,
                                   telemetry=True)}
    rng = np.random.RandomState(0)
    paths = [[f"/bb/hot/r{i}/f{j % 2}" for j in range(q)]
             for i in range(N)]
    # unique (file, chunk) per row so payload expectations are exact
    cid = np.tile(np.arange(q, dtype=np.int32) // 2, (N, 1))
    pay = rng.randint(0, 9999, (N, q, w)).astype(np.int32)
    perm = np.roll(np.arange(N), 1)
    obs = {}
    for name, c in clients.items():
        req = c.encode(paths, chunk_id=cid, payload=pay)
        c.write(req)
        rreq = BBRequest(path_hash=req.path_hash[perm],
                         chunk_id=req.chunk_id[perm],
                         scope_hash=req.scope_hash[perm])
        outs = []
        mig = LiveMigrator(c, "/bb/hot", LayoutMode.DIST_HASH,
                           step_chunks=4)
        while not mig.done:
            before = c.state
            mig.step()                       # partial watermark each loop
            assert before.data.is_deleted(), name    # donated, rebound
            out, found = c.read(rreq)
            assert bool(np.asarray(found).all()), (name, mig.watermark)
            outs += [out, found, *c.stat(rreq)]
        mig.finish()
        out, found = c.read(rreq)
        assert np.array_equal(np.asarray(out), pay[perm]), name
        outs += [out, found]
        obs[name] = outs
    for a, b in zip(obs["mesh"], obs["stacked"]):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    print('MESH_MIGRATE_OK')
""")


@pytest.mark.slow
@pytest.mark.mesh
def test_mesh_relayout_matches_stacked():
    r = subprocess.run([sys.executable, "-c", MESH_MIGRATE_SCRIPT],
                       capture_output=True, text=True, timeout=900,
                       cwd=str(ROOT))
    assert "MESH_MIGRATE_OK" in r.stdout, r.stdout + r.stderr
