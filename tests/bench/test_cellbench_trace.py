"""The trace reduction, on a small trace recorded on a v5e: a
``bb8_1chip.ior_d`` window (``--trace 1 --trace-out``) cut to 0.3 s of
writes and reads, with the device's ``XLA Ops`` and ``XLA Modules`` lines
and the benchmark's own spans.  The readers' numbers are checked against
plain recomputations from the same events."""
import importlib.util
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "bench"), str(ROOT / "src")]

from peaks import peaks_for  # noqa: E402
from tracing import Trace, merge  # noqa: E402

TRACE = Path(__file__).resolve().parent / "data" / "ior_d_v5e.xplane.pb"


class View:
    def __init__(self, trace):
        self.trace = trace
        self.peaks = peaks_for("TPU v5 lite")
        self.host = {"decide_s": 0.05}


def _reader(name):
    spec = importlib.util.spec_from_file_location(
        f"layer_{name}", ROOT / "bench" / "layers" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


@pytest.fixture(scope="module")
def trace():
    return Trace(str(TRACE))


def _union_in(intervals, lo, hi):
    """Brute force: covered length of [lo, hi) by intervals."""
    pts = sorted({lo, hi} | {x for s, e in intervals for x in (s, e)
                             if lo < x < hi})
    return sum(b - a for a, b in zip(pts, pts[1:])
               if any(s <= a and b <= e for s, e in intervals))


def test_merge():
    assert merge([(5, 6), (0, 2), (1, 3), (3, 4)]) == [(0, 4), (5, 6)]


def test_window_busy_and_idle(trace):
    assert trace.chips == 1
    lo, hi = trace.window
    evs = [(s, e) for s, e, _ in next(iter(trace.ops.values()))]
    assert trace.busy_s == pytest.approx(_union_in(evs, lo, hi) / 1e9,
                                         rel=1e-9)
    assert 0 < trace.busy_s < trace.window_s
    idle = _reader("idle_share.ckpt")(View(trace))
    assert idle == pytest.approx(100 * (1 - trace.busy_s / trace.window_s))
    gaps = trace.idle_gaps(k=100)
    assert sum(t for _, t in gaps) == pytest.approx(
        trace.window_s - trace.busy_s, rel=1e-6)


def test_call_spans_split_into_device_and_host(trace):
    view = View(trace)
    for ops, dev in ((("write",), "write_dev_ms"), (("read",),
                                                    "read_dev_ms")):
        spans = trace.calls(ops)
        assert spans
        evs = [(s, e) for s, e, _ in next(iter(trace.ops.values()))]
        want = sum(_union_in(evs, s, e) for s, e in spans) / 1e6 / len(spans)
        assert _reader(dev)(view) == pytest.approx(want, rel=1e-9)
    gap = _reader("host_gap_ms.ckpt")(view)
    spans = trace.calls(("write", "read", "drain"))
    span_ms = sum(e - s for s, e in spans) / 1e6 / len(spans)
    busy_ms = 1e3 * trace.busy_in(spans) / len(spans)
    assert gap == pytest.approx(span_ms - busy_ms)
    assert gap > 0


def test_kernel_share_and_absent_metrics(trace):
    view = View(trace)
    # the chunk_pack kernel carries no payload in these cells: a sliver of
    # the device's busy time, which no reader reports
    pack = trace.op_seconds(lambda n: n.startswith("%pack_chunks_kernel"))
    assert 0 < pack < 0.01 * trace.busy_s
    # no metadata call in an IOR trace
    assert _reader("meta_dev_ms")(view) is None
    assert _reader("decide_s")(view) == 0.05


def test_breakdown_lists(trace):
    ops = trace.top_ops()
    assert 0 < len(ops) <= 10
    assert all(t > 0 for _, t in ops)
    assert [t for _, t in ops] == sorted((t for _, t in ops), reverse=True)
    names = {n for n, _ in trace.idle_gaps()}
    assert names <= {"bench.call.write", "bench.call.read",
                     "bench.call.drain", "bench.encode", "between calls"}
