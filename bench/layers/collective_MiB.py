"""engine programs (exchange): MiB carried by the mesh's collectives per
call and chip, inside the benchmark's call spans (moves ``ckpt_GiBps``).

Every device event that ``collective_ms.is_collective`` takes for an
``all-to-all`` or a ``collective-permute`` counts the bytes of its
operand, from the shape and element type the instruction names.  An async
pair counts once: its ``-start`` carries the operand and its ``-done`` is
skipped.  On each chip the events that start inside a call span are
summed; the number is the mean over chips, per call.  An ``all_to_all``'s
operand holds the segment a chip keeps for itself, so this is what the
collectives carry, not only what crosses the interconnect; no share of an
interconnect peak is taken from it.  A trace with no collective (a
one-chip cell) reads nothing."""
import bisect
import importlib.util
import re
from pathlib import Path

from tracing import CALL_PREFIX

_spec = importlib.util.spec_from_file_location(
    "layer_collective_ms", Path(__file__).with_name("collective_ms.py"))
_coll = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_coll)
is_collective = _coll.is_collective

#: bytes per element of the HLO element types a collective may carry
ELEMENT_BYTES = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2,
                 "f16": 2, "bf16": 2, "s32": 4, "u32": 4, "f32": 4,
                 "s64": 8, "u64": 8, "f64": 8}
_TYPE = r"([a-z]+[0-9]*)\[([0-9,]*)\]"
#: the operand of the collective's opcode call, e.g. ``all-to-all(s32[..``
_OPERAND = re.compile(r"(?:all-to-all|collective-permute)(-start|-done)?"
                      r"\(\(?" + _TYPE)
#: the instruction's result type, for a name without its opcode call
_RESULT = re.compile(r" = \(?" + _TYPE)


def operand_bytes(event_name: str) -> int:
    """Bytes of a collective event's operand; 0 for the ``-done`` half of
    an async pair and for a name that states no type."""
    m = _OPERAND.search(event_name)
    if m:
        if m.group(1) == "-done":
            return 0
        dtype, dims = m.group(2), m.group(3)
    else:
        m = _RESULT.search(event_name)
        if not m or "-done" in event_name.partition(" = ")[0]:
            return 0
        dtype, dims = m.group(1), m.group(2)
    n = ELEMENT_BYTES.get(dtype, 0)
    for d in filter(None, dims.split(",")):
        n *= int(d)
    return n


def read(run):
    t = run.trace
    calls = sorted((s, e) for s, e, n in t.spans
                   if n.startswith(CALL_PREFIX))
    if not calls or not t.ops:
        return None
    starts = [s for s, _ in calls]
    total, seen = 0, False
    for evs in t.ops.values():
        for s, _, n in evs:
            if not is_collective(n):
                continue
            i = bisect.bisect_right(starts, s) - 1
            if i >= 0 and s < calls[i][1]:
                seen = True
                total += operand_bytes(n)
    if not seen:
        return None
    return total / len(t.ops) / 2**20 / len(calls)
