"""The client's spans on the profiler's clock.

A stacked HYBRID client with ``exchange="compacted"`` and no recorder runs
one write, one two-phase read and one create under ``jax.profiler``; the
capture is read back with ``ProfileData``.  Every ``obs`` span is then a
``/host:CPU`` event of the same name: the op spans hold the resolve, plan,
route and dispatch spans, and each blocking device-to-host read is one
``client.sync.<site>`` span — two per write (data and metadata spec
budgets), three per two-phase read (probe mask, the probe's metadata spec,
the data spec) and one per metadata call.
"""
import glob
import os

import numpy as np
import pytest

N, Q, W = 4, 8, 8
OPS = ("client.write", "client.read", "client.meta")
SYNCS = {"client.write": 2, "client.read": 3, "client.meta": 1}


def _client(trace=None):
    from repro.core.client import BBClient
    from repro.core.layouts import LayoutMode
    from repro.core.policy import LayoutPolicy
    policy = LayoutPolicy.uniform(LayoutMode.HYBRID, N)
    return BBClient(policy, cap=4 * Q, words=W, mcap=4 * Q,
                    exchange="compacted", trace=trace)


def _requests(client):
    rng = np.random.RandomState(0)
    paths = [[f"/ckpt/r{i}/f{j}" for j in range(Q)] for i in range(N)]
    cids = rng.randint(0, 4, (N, Q)).astype(np.int32)
    payload = rng.randint(0, 9999, (N, Q, W)).astype(np.int32)
    wreq = client.encode(paths, chunk_id=cids, payload=payload)
    rreq = client.encode(paths, chunk_id=cids)
    mreq = client.encode([[f"/ckpt/r{i}/m{j}" for j in range(Q)]
                          for i in range(N)])
    return wreq, rreq, mreq


def _calls(client, reqs):
    import jax
    wreq, rreq, mreq = reqs
    client.write(wreq)
    jax.block_until_ready(client.state)
    out, found = client.read(rreq)
    jax.block_until_ready((out, found))
    assert bool(np.asarray(found).all())
    jax.block_until_ready(client.create(mreq))


def _host_spans(logdir):
    """(start, end, name) of every ``client.``/``engine.``/``exchange.``
    event on the capture's host plane."""
    from jax.profiler import ProfileData
    found = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                      recursive=True)
    assert found, f"no capture under {logdir}"
    pd = ProfileData.from_file(found[0])
    return sorted(
        (ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
        for plane in pd.planes if plane.name == "/host:CPU"
        for line in plane.lines for ev in line.events
        if ev.name.startswith(("client.", "engine.", "exchange.")))


def _capture(logdir, fn):
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(logdir), profiler_options=opts)
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    return _host_spans(str(logdir))


def _inside(span, outer):
    return outer[0] <= span[0] and span[1] <= outer[1]


@pytest.fixture(scope="module")
def captured(tmp_path_factory):
    """A cold capture (compiles), then a warm one of the same calls."""
    client = _client()
    reqs = _requests(client)
    cold = _capture(tmp_path_factory.mktemp("cold"),
                    lambda: _calls(client, reqs))
    warm = _capture(tmp_path_factory.mktemp("warm"),
                    lambda: _calls(client, reqs))
    return cold, warm


def test_sync_spans_per_call(captured):
    _, warm = captured
    for op, want in SYNCS.items():
        calls = [s for s in warm if s[2] == op]
        assert len(calls) == 1, op
        syncs = [s for s in warm if s[2].startswith("client.sync.")
                 and _inside(s, calls[0])]
        assert len(syncs) == want, (op, [s[2] for s in syncs])
    # every sync of the capture lies in an op span: 2 + 3 + 1
    assert sum(s[2].startswith("client.sync.") for s in warm) == 6
    read = next(s for s in warm if s[2] == "client.read")
    sites = sorted(s[2] for s in warm if s[2].startswith("client.sync.")
                   and _inside(s, read))
    assert sites == ["client.sync.probe_mask", "client.sync.spec",
                     "client.sync.spec"]


def test_program_spans_nest_in_their_op_span(captured):
    _, warm = captured
    ops = [s for s in warm if s[2] in OPS]
    assert sorted(s[2] for s in ops) == sorted(OPS)
    for s in warm:
        if s[2] in OPS:
            continue
        assert any(_inside(s, o) for o in ops), s
    for op in ops:
        names = {s[2] for s in warm if _inside(s, op) and s is not op}
        assert {"client.resolve", "client.plan", "client.route",
                "client.dispatch"} <= names, (op[2], names)
    # routing is part of planning
    plans = [s for s in warm if s[2] == "client.plan"]
    for r in (s for s in warm if s[2] == "client.route"):
        assert any(_inside(r, p) for p in plans)
    # the two-phase read's phases sit in the read span
    read = next(s for s in ops if s[2] == "client.read")
    for phase in ("client.read.probe", "client.read.data"):
        assert any(s[2] == phase and _inside(s, read) for s in warm)


def test_trace_time_spans_mark_compiles(captured):
    """Engine and exchange spans run while jax traces: the cold capture
    holds them inside the op span that compiled, the warm one none."""
    cold, warm = captured
    engine = [s for s in cold if s[2].startswith(("engine.", "exchange."))]
    assert {"engine.forward_write", "engine.forward_read",
            "engine.meta_op", "exchange.plan"} <= {s[2] for s in engine}
    ops = [s for s in cold if s[2] in OPS]
    assert all(any(_inside(s, o) for o in ops) for s in engine)
    assert not [s for s in warm if s[2].startswith(("engine.",
                                                    "exchange."))]


def test_recorder_ring_holds_the_same_spans(captured, tmp_path,
                                            monkeypatch):
    from repro.core import obs
    _, warm = captured
    rec = obs.TraceRecorder()
    client = _client(trace=rec)
    reqs = _requests(client)
    _calls(client, reqs)          # compiled ops are shared: no retrace
    rec.spans.clear()
    profiled = _capture(tmp_path, lambda: _calls(client, reqs))
    ring = sorted(s.name for s in rec.spans)
    assert ring == sorted(n for *_, n in warm)
    assert ring == sorted(n for *_, n in profiled)
    # no recorder: no ring is written, nothing fences

    def no_ring(*args, **kwargs):
        raise AssertionError("a span reached a recorder")
    monkeypatch.setattr(obs.TraceRecorder, "span", no_ring)
    plain = _client()
    _calls(plain, _requests(plain))
    assert obs.current_recorder() is None
