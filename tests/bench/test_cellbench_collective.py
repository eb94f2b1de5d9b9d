"""The four-chip trace reduction, on a trace recorded on a four-chip v5e
host: a ``bb4_4chip.ior_d`` window (``--trace 1 --trace-out``) cut to
0.15 s that holds two writes and a read, with each chip's ``XLA Ops``
line (the op metadata's statistics dropped to keep the file small) and the
benchmark's own spans.  ``collective_ms`` and the per-call readers are
checked against plain recomputations, chip by chip, from the same
events."""
import importlib.util
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "bench"), str(ROOT / "src")]

from tracing import Trace  # noqa: E402

DATA = Path(__file__).resolve().parent / "data"


class View:
    def __init__(self, trace):
        self.trace = trace


def _layer(name):
    spec = importlib.util.spec_from_file_location(
        f"layer_{name}", ROOT / "bench" / "layers" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def trace():
    return Trace(str(DATA / "ior_d_v5e_4chip.xplane.pb"))


def _union_in(intervals, lo, hi):
    """Brute force: covered length of [lo, hi) by intervals."""
    pts = sorted({lo, hi} | {x for s, e in intervals for x in (s, e)
                             if lo < x < hi})
    return sum(b - a for a, b in zip(pts, pts[1:])
               if any(s <= a and b <= e for s, e in intervals))


def _per_call_ms(trace, spans, keep):
    """Mean over chips of the union of the kept events inside ``spans``,
    per span, in ms."""
    per_chip = [sum(_union_in([(s, e) for s, e, n in evs if keep(n)], a, b)
                    for a, b in spans) for evs in trace.ops.values()]
    return sum(per_chip) / len(per_chip) / 1e6 / len(spans)


@pytest.mark.parametrize("name, want", [
    ("%all_to_all.14 = s32[1,4,16,131073]{3,2,0,1:T(8,128)S(1)} all-to-all("
     "s32[1,4,16,131073]{3,2,0,1:T(8,128)S(1)} %bitcast.1), channel_id=1",
     True),
    ("%all-to-all.3 = s32[4,16]{1,0} all-to-all(s32[4,16]{1,0} %p)", True),
    ("%collective-permute-start.2 = (s32[1,16]{1,0}, s32[1,16]{1,0}) "
     "collective-permute-start(s32[1,16]{1,0} %x), source_target_pairs="
     "{{0,1}}", True),
    ("%ppermute.1 = s32[1,16]{1,0} collective-permute(s32[1,16]{1,0} %x)",
     True),
    ("%copy.52 = s32[1,4,8,5]{2,1,0,3:T(4,128)S(1)} copy(s32[1,4,8,5]"
     "{2,0,3,1:T(1,128)S(1)} %all_to_all.2)", False),
    ("%reduce.59 = pred[4,16]{1,0} reduce(pred[1,4,16]{2,0,1} "
     "%all_to_all.15, pred[]{:T(512)} %constant.179), dimensions={0}, "
     "to_apply=%all_to_all.15.reduce_sub_computation", False),
    ("%fusion.21 = s32[1,64,131072]{2,1,0} fusion(s32[1,64,32768]{2,1,0} "
     "%collective-permute-done.1), kind=kLoop", False),
])
def test_is_collective(name, want):
    assert _layer("collective_ms").is_collective(name) is want


def test_collective_ms_over_four_chips(trace):
    coll = _layer("collective_ms")
    assert trace.chips == 4
    calls = trace.calls(("write", "read", "drain"))
    assert len(calls) == 3
    got = coll.read(View(trace))
    assert got == pytest.approx(
        _per_call_ms(trace, calls, coll.is_collective), rel=1e-9)
    assert got > 0
    # a collective is device work: it lies inside the busy time
    busy_ms = 1e3 * trace.busy_in(calls) / len(calls)
    assert got < busy_ms
    # every chip takes part in every all_to_all
    counts = {chip: sum(coll.is_collective(n) for _, _, n in evs)
              for chip, evs in trace.ops.items()}
    assert len(set(counts.values())) == 1 and min(counts.values()) > 0


def test_dev_and_idle_readers_are_means_over_chips(trace):
    for op, name in (("write", "write_dev_ms"), ("read", "read_dev_ms")):
        spans = trace.calls((op,))
        assert _layer(name).read(View(trace)) == pytest.approx(
            _per_call_ms(trace, spans, lambda n: True), rel=1e-9)
    lo, hi = trace.window
    busy = [_union_in([(s, e) for s, e, _ in evs], lo, hi)
            for evs in trace.ops.values()]
    assert trace.busy_s == pytest.approx(sum(busy) / 4 / 1e9, rel=1e-9)
    idle = _layer("idle_share.ckpt").read(View(trace))
    assert idle == pytest.approx(100 * (1 - trace.busy_s / trace.window_s))
    # chip 0 also runs the client's eager routing ops, so it is the busiest
    assert max(busy) == busy[sorted(trace.ops).index("/device:TPU:0")]


def test_one_chip_trace_has_no_collective():
    one = Trace(str(DATA / "ior_d_v5e.xplane.pb"))
    assert _layer("collective_ms").read(View(one)) is None
