"""The split of the calls' device-idle time by the program's own spans
(``bench/host_split.py``).

Synthetic spans check the exact innermost-span sweep against brute force,
nesting deeper than eight and with more than eight siblings.  A window
recorded on a v5e (``bb8_1chip.ior_d`` with ``--trace 1 --trace-out``, cut
to 0.3 s of writes and reads) checks the shared clock of the benchmark's
and the program's spans, the sync counts per call, and each number
against a plain recomputation from the events.  The older fixture, from a
program without spans, reads null for the program-span numbers.
"""
import bisect
import random
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "bench"), str(ROOT / "src")]

from host_split import (  # noqa: E402
    OUTSIDE, SpanTrace, attribute, complement, group_of, innermost,
    intersect, per_call, split)
from tracing import merge  # noqa: E402

DATA = Path(__file__).resolve().parent / "data"
SPANS = DATA / "ior_d_v5e_spans.xplane.pb"
OLD = DATA / "ior_d_v5e.xplane.pb"


def _brute(spans, t):
    """Innermost span at ``t``: of those holding it, the latest to start
    (the shorter on a tie, the later listed on equal intervals)."""
    holding = [sp for sp in spans if sp[0] <= t < sp[1]]
    if not holding:
        return OUTSIDE
    return max(reversed(holding), key=lambda sp: (sp[0], -sp[1]))[2]


def _nested(rng, lo, hi, depth, prefix):
    """Random properly nested spans in [lo, hi)."""
    out = []
    t = lo
    while t < hi - 2 and depth > 0:
        s = rng.randint(t, hi - 2)
        e = rng.randint(s + 1, hi)
        name = f"{prefix}.{len(out)}"
        out.append((s, e, name))
        if e - s > 2 and rng.random() < 0.7:
            out += _nested(rng, s, e, depth - 1, name)
        t = e
    return out


def _check_partition(spans, lo, hi):
    pieces = innermost(spans, lo, hi)
    assert pieces[0][0] == lo and pieces[-1][1] == hi
    for (_, e, _), (s, _, _) in zip(pieces, pieces[1:]):
        assert e == s
    for s, e, name in pieces:
        for t in range(s, e):
            assert name == _brute(spans, t), (t, name)
    return pieces


def test_innermost_deep_nesting_and_many_siblings():
    # a call holding a chain of 12 nested spans, the innermost holding 20
    # siblings; idle after the last sibling belongs to the chain's end
    spans = [(0, 1000, "bench.call.write")]
    spans += [(10 + i, 900 - i, f"depth{i}") for i in range(12)]
    spans += [(100 + 10 * i, 105 + 10 * i, f"sibling{i}") for i in range(20)]
    pieces = _check_partition(spans, 0, 1000)
    assert dict(((s, e), n) for s, e, n in pieces)[(295, 889)] == "depth11"
    got = attribute(pieces, [(500, 600), (950, 1000)])
    assert got == {"depth11": 100, "bench.call.write": 50}


def test_innermost_random_nesting():
    rng = random.Random(7)
    for _ in range(30):
        spans = _nested(rng, 0, 400, 6, "s")
        _check_partition(spans, 0, 400)
        # clipped to a stretch that cuts spans at both ends
        _check_partition(spans, 50, 350)


def test_attribute_sums_and_interval_helpers():
    rng = random.Random(3)
    spans = _nested(rng, 0, 500, 5, "s")
    pieces = innermost(spans, 0, 500)
    busy = [(20, 40), (100, 180), (300, 301)]
    idle = complement(busy, 0, 500)
    assert idle == [(0, 20), (40, 100), (180, 300), (301, 500)]
    assert sum(attribute(pieces, idle).values()) == 500 - 101
    calls = [(10, 50), (90, 400)]
    assert intersect(idle, calls) == [(10, 20), (40, 50), (90, 100),
                                      (180, 300), (301, 400)]
    assert group_of("client.sync.spec") == "sync"
    assert group_of("client.read.probe") == "read_phase"
    assert group_of("client.read") == "rest"
    assert group_of("bench.encode") == "encode"


def test_old_fixture_has_no_program_spans():
    trace = SpanTrace(str(OLD))
    assert trace.program_spans == []
    got = per_call(trace, ("write", "read", "drain"))
    assert got["plan_ms"] is None and got["syncs"] is None
    assert sum(got["idle_ms"].values()) == pytest.approx(got["host_gap_ms"])
    gaps = trace.idle_gaps(k=100)
    assert sum(t for _, t in gaps) == pytest.approx(
        trace.window_s - trace.busy_s, rel=1e-6)


# ---------------------------------------------------------------------------
# the v5e window recorded with the program's spans
# ---------------------------------------------------------------------------
CKPT = {"bench.call.write": 2, "bench.call.read": 3, "bench.call.drain": 1}


@pytest.fixture(scope="module")
def trace():
    return SpanTrace(str(SPANS))


@pytest.fixture(scope="module")
def raw():
    """Host events and device ops of the window, read plainly."""
    from jax.profiler import ProfileData
    host, ops = [], []
    for plane in ProfileData.from_file(str(SPANS)).planes:
        for line in plane.lines:
            evs = [(ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
                   for ev in line.events]
            if plane.name == "/host:CPU":
                host += evs
            elif plane.name.startswith("/device:TPU:") and \
                    line.name == "XLA Ops":
                ops += evs
    return host, ops


def _within(sp, outer):
    return outer[0] <= sp[0] and sp[1] <= outer[1]


def test_program_spans_share_the_benchmark_clock(trace):
    calls = [sp for sp in trace.spans if sp[2] in CKPT]
    client = [sp for sp in trace.program_spans
              if sp[2].startswith("client.")]
    assert calls and client
    for sp in client:
        assert any(_within(sp, c) for c in calls), sp
    # one op span and its sync count per benchmark call
    ops = {"bench.call.write": "client.write",
           "bench.call.read": "client.read",
           "bench.call.drain": "client.meta"}
    for c in calls:
        inside = [sp for sp in client if _within(sp, c)]
        assert [sp[2] for sp in inside].count(ops[c[2]]) == 1
        syncs = [sp for sp in inside if sp[2].startswith("client.sync.")]
        assert len(syncs) == CKPT[c[2]], (c[2], syncs)
    # a warmed window traces nothing: no engine or exchange span
    assert not [sp for sp in trace.program_spans
                if not sp[2].startswith("client.")]


def test_per_call_matches_a_plain_recomputation(trace, raw):
    host, ops = raw
    calls = sorted((s, e) for s, e, n in host if n in CKPT)
    n = len(calls)

    def in_calls(s):
        return any(a <= s < b for a, b in calls)
    got = per_call(trace, ("write", "read", "drain"))
    assert got["calls"] == n
    assert got["plan_ms"] == pytest.approx(sum(
        e - s for s, e, name in host
        if name == "client.plan" and in_calls(s)) / 1e6 / n)
    sync = [(s, e) for s, e, name in host
            if name.startswith("client.sync.") and in_calls(s)]
    assert got["sync_ms"] == pytest.approx(
        sum(e - s for s, e in sync) / 1e6 / n)
    assert got["syncs"] == len(sync) / n
    # idle inside the calls, split by the innermost span, stretch by
    # stretch between consecutive event boundaries
    spans = [sp for sp in host if sp[2] != "bench.window" and
             sp[2].startswith(("bench.", "client.", "engine.",
                               "exchange."))]
    cuts = sorted({x for a, b in calls for x in (a, b)} |
                  {x for s, e, _ in spans + ops for x in (s, e)
                   if in_calls(x)})
    busy = merge((s, e) for s, e, _ in ops)
    starts = [s for s, _ in busy]

    def is_busy(t):
        i = bisect.bisect_right(starts, t) - 1
        return i >= 0 and t < busy[i][1]
    want = {}
    for a, b in zip(cuts, cuts[1:]):
        mid = (a + b) / 2
        if not in_calls(mid) or is_busy(mid):
            continue
        name = _brute(spans, mid)
        want[name] = want.get(name, 0.0) + (b - a)
    split_ms = {}
    for name, t in want.items():
        g = group_of(name)
        split_ms[g] = split_ms.get(g, 0.0) + t / 1e6 / n
    assert got["idle_ms"] == pytest.approx(split_ms, rel=1e-9)
    assert sum(split_ms.values()) == pytest.approx(got["host_gap_ms"],
                                                   rel=1e-9)
    assert got["named_share"] == pytest.approx(
        1 - split_ms.get("rest", 0.0) / sum(split_ms.values()))


def test_idle_gaps_sum_to_the_window_idle(trace):
    gaps = trace.idle_gaps(k=1000)
    assert sum(t for _, t in gaps) == pytest.approx(
        trace.window_s - trace.busy_s, rel=1e-6)
    names = {name for name, _ in gaps}
    assert "client.plan" in names or "client.sync.spec" in names
    res = split(str(SPANS))
    assert set(res) >= {"ckpt", "idle_gaps", "window_s", "idle_s"}
    assert "md" not in res
