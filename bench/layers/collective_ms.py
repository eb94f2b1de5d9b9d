"""engine programs (exchange): device milliseconds per call spent in the
mesh's collectives, inside the benchmark's call spans (moves
``ckpt_GiBps``).

A collective is a device event whose HLO instruction is an ``all-to-all``
or a ``collective-permute`` (``lax.all_to_all``, ``lax.ppermute``), by its
opcode or by its instruction's name, so that async ``-start``/``-done``
halves and ``%all_to_all``-named instructions count too.  On each chip the
union of those events inside the call spans is summed; the number is the
mean over chips, per call: the time one chip spends in the exchange per
call, its wait for its peers included.  A trace with no collective (a
one-chip cell) reads nothing."""
import re

from tracing import CALL_PREFIX, merge, overlap

_KINDS = r"(?:all[-_]to[-_]all|collective[-_]permute|ppermute)"
#: the instruction's own name, or its opcode (the word before ``(`` that no
#: ``%`` or ``.`` prefixes, which an operand's name would have)
_NAME = re.compile(r"^%?" + _KINDS + r"\b")
_OPCODE = re.compile(r"(?<![%\w.-])(?:all-to-all|collective-permute)"
                     r"(?:-start|-done)?\(")


def is_collective(event_name: str) -> bool:
    return bool(_NAME.match(event_name) or
                _OPCODE.search(event_name.partition(" = ")[2]))


def read(run):
    t = run.trace
    calls = [(s, e) for s, e, n in t.spans if n.startswith(CALL_PREFIX)]
    if not calls or not t.ops:
        return None
    total, seen = 0.0, False
    for evs in t.ops.values():
        coll = merge((s, e) for s, e, n in evs if is_collective(n))
        seen = seen or bool(coll)
        starts = [s for s, _ in coll]
        total += sum(overlap(coll, starts, s, e) for s, e in calls)
    if not seen:
        return None
    return total / len(t.ops) / 1e6 / len(calls)
