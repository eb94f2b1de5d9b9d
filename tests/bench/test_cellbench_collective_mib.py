"""``collective_MiB``: the bytes the mesh's collectives carry per call and
chip, read from the operand types of the trace's collective events.

Checked on the committed four-chip trace (``ior_d_v5e_4chip.xplane.pb``,
two writes and a read of ``bb4_4chip.ior_d`` before the two-level
exchange), whose read ships its data over the padded node ``all_to_all``
of ``s32[1,4,16,131073]``: 32 MiB per chip in one call."""
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


@pytest.mark.parametrize("name, want", [
    ("%all_to_all.14 = s32[1,4,16,131073]{3,2,0,1:T(8,128)S(1)} all-to-all("
     "s32[1,4,16,131073]{3,2,0,1:T(8,128)S(1)} %bitcast.158), channel_id=1",
     4 * 16 * 131073 * 4),
    ("%all_to_all.15 = pred[1,4,16]{2,0,1:T(4,128)(4,1)S(1)} all-to-all("
     "pred[1,4,16]{2,0,1:T(4,128)(4,1)S(1)} %broadcast_in_dim.169)", 64),
    ("%collective-permute-start.2 = (s32[1,16,131073]{2,1,0}, "
     "s32[1,16,131073]{2,1,0}) collective-permute-start(s32[1,16,131073]"
     "{2,1,0} %x), source_target_pairs={{0,1},{1,2},{2,3},{3,0}}",
     16 * 131073 * 4),
    # the -done half of an async pair carries nothing more
    ("%collective-permute-done.2 = s32[1,16,131073]{2,1,0} "
     "collective-permute-done((s32[1,16,131073]{2,1,0}, s32[1,16,131073]"
     "{2,1,0}) %collective-permute-start.2)", 0),
    ("%ppermute.1 = bf16[8,128]{1,0} collective-permute(bf16[8,128]{1,0} "
     "%x)", 8 * 128 * 2),
])
def test_operand_bytes(name, want):
    assert _layer("collective_MiB").operand_bytes(name) == want


def test_collective_mib_over_four_chips():
    trace = Trace(str(DATA / "ior_d_v5e_4chip.xplane.pb"))
    mib = _layer("collective_MiB")
    calls = trace.calls(("write", "read", "drain"))
    per_chip = []
    for evs in trace.ops.values():
        got = [mib.operand_bytes(n) for s, _, n in evs
               if mib.is_collective(n) and any(a <= s < b for a, b in calls)]
        per_chip.append(sum(got))
        # the read's data all_to_all: 32 MiB of operand on every chip
        assert max(got) == 4 * 16 * 131073 * 4
    got = mib.read(View(trace))
    assert got == pytest.approx(sum(per_chip) / 4 / 2**20 / len(calls))
    # the read's two payload all_to_alls (its data round and the stranded
    # search) carry 64 MiB; the metadata rounds add the rest
    assert 64 / 3 < got < 64 / 3 + 1


def test_one_chip_trace_has_no_collective_bytes():
    one = Trace(str(DATA / "ior_d_v5e.xplane.pb"))
    assert _layer("collective_MiB").read(View(one)) is None
