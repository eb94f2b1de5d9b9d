"""Split the device-idle time of the benchmark's calls by the program's spans.

    python bench/host_split.py <window.xplane.pb> [<window.xplane.pb> ...]

Reads windows kept by ``bench/run.py --trace 1 --trace-out <dir>``.  Every
``repro.core.obs`` span of the program is a host event of the same capture
(named ``client.*``, ``engine.*`` or ``exchange.*``), on the clock of the
device's events; ``tracing.Trace`` keeps only the benchmark's ``bench.*``
spans, and ``SpanTrace`` adds the program's as ``program_spans``.

For the IOR calls (write, read, drain) and the mdtest calls (create, stat,
remove) it prints one JSON line per file with, per call:

- ``host_gap_ms``: device-idle ms inside the call spans (as the reader of
  ``host_gap_ms.*`` computes it);
- ``plan_ms``, ``sync_ms``: host ms inside ``client.plan`` spans and inside
  ``client.sync.*`` spans (a plan span holds its routing and spec syncs);
  ``syncs``: the number of ``client.sync.*`` spans, one per blocking
  device-to-host read;
- ``idle_ms``: the device-idle ms split by the innermost span holding each
  idle stretch, grouped as resolve, route, plan (outside its routing and
  syncs), sync, dispatch, the two-phase read's phases, encode, and rest
  (the bare call or op span, trace-time spans, between calls);
- ``named_share``: the share of that idle time held by a named child.

With no program spans in the window (a program without them) the
program-span numbers are null.
"""
from __future__ import annotations

import bisect
import json
import sys
from collections import defaultdict
from pathlib import Path
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

sys.path[:0] = [str(Path(__file__).resolve().parent)]

from layer_common import META_OPS, host_gap_ms  # noqa: E402
from tracing import WINDOW_SPAN, Interval, Trace  # noqa: E402

Span = Tuple[float, float, str]

HOST_PLANE = "/host:CPU"
PROGRAM_PREFIXES = ("client.", "engine.", "exchange.")
OUTSIDE = "between calls"
CKPT_OPS = ("write", "read", "drain")
#: idle-time groups: a name matches a pattern equal to it, or a pattern
#: ending in "." that it starts with; what matches none is "rest"
GROUPS = (("resolve", ("client.resolve",)), ("route", ("client.route",)),
          ("plan", ("client.plan",)), ("sync", ("client.sync.",)),
          ("dispatch", ("client.dispatch",)),
          ("read_phase", ("client.read.probe", "client.read.data")),
          ("encode", ("bench.encode",)))


def _matches(name: str, patterns: Sequence[str]) -> bool:
    return any(name == p or (p.endswith(".") and name.startswith(p))
               for p in patterns)


def group_of(name: str) -> str:
    for group, patterns in GROUPS:
        if _matches(name, patterns):
            return group
    return "rest"


def innermost(spans: Iterable[Span], lo: float, hi: float) -> List[Span]:
    """Partition [lo, hi) into (start, end, name) pieces, each named by
    the innermost span that covers it (``OUTSIDE`` where none does).

    A sweep with a stack: spans enter in order of start (the longer first
    on a tie) and leave once they end, so nesting of any depth and any
    number of siblings resolves exactly; where spans overlap without
    nesting, the later-started open span counts as the inner one."""
    spans = list(spans)
    order = sorted(spans, key=lambda sp: (sp[0], -sp[1]))
    cuts = sorted({lo, hi} | {x for s, e, _ in spans for x in (s, e)
                              if lo < x < hi})
    out: List[Span] = []
    stack: List[Span] = []
    i = 0
    for a, b in zip(cuts, cuts[1:]):
        while i < len(order) and order[i][0] <= a:
            stack.append(order[i])
            i += 1
        while stack and stack[-1][1] <= a:
            stack.pop()
        name = stack[-1][2] if stack else OUTSIDE
        if out and out[-1][2] == name:
            out[-1] = (out[-1][0], b, name)
        else:
            out.append((a, b, name))
    return out


def attribute(pieces: Sequence[Span], intervals: Iterable[Interval]
              ) -> Dict[str, float]:
    """Length of ``intervals`` by the name of the ``pieces`` (a partition
    from ``innermost``) they fall in."""
    starts = [s for s, _, _ in pieces]
    agg: Dict[str, float] = defaultdict(float)
    for a, b in intervals:
        i = max(bisect.bisect_right(starts, a) - 1, 0)
        while i < len(pieces) and pieces[i][0] < b:
            s, e, name = pieces[i]
            cut = min(e, b) - max(s, a)
            if cut > 0:
                agg[name] += cut
            i += 1
    return dict(agg)


def complement(merged: Sequence[Interval], lo: float, hi: float
               ) -> List[Interval]:
    """[lo, hi) less the sorted, disjoint ``merged``."""
    edges = [lo] + [x for iv in merged for x in iv] + [hi]
    return [(a, b) for a, b in zip(edges[0::2], edges[1::2]) if b > a]


def intersect(xs: Sequence[Interval], ys: Sequence[Interval]
              ) -> List[Interval]:
    """Intersection of two sorted, disjoint interval lists."""
    out, i, j = [], 0, 0
    while i < len(xs) and j < len(ys):
        a, b = max(xs[i][0], ys[j][0]), min(xs[i][1], ys[j][1])
        if b > a:
            out.append((a, b))
        if xs[i][1] < ys[j][1]:
            i += 1
        else:
            j += 1
    return out


def starting_in(spans: Sequence[Span], within: Sequence[Interval]
                ) -> List[Span]:
    """The ``spans`` that start inside one of the sorted ``within``."""
    starts = [s for s, _ in within]
    out = []
    for sp in spans:
        i = bisect.bisect_right(starts, sp[0]) - 1
        if i >= 0 and sp[0] < within[i][1]:
            out.append(sp)
    return out


class SpanTrace(Trace):
    """A ``Trace`` that also keeps the program's spans, and splits idle
    time at span boundaries by the innermost span over both lists."""

    def __init__(self, path: str):
        super().__init__(path)
        from jax.profiler import ProfileData
        pd = ProfileData.from_file(path)
        self.program_spans: List[Span] = sorted(
            (ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
            for plane in pd.planes if plane.name == HOST_PLANE
            for line in plane.lines for ev in line.events
            if ev.name.startswith(PROGRAM_PREFIXES))
        lo, hi = self.window
        self.pieces = innermost(
            [sp for sp in self.spans if sp[2] != WINDOW_SPAN] +
            self.program_spans, lo, hi)

    def idle_by_span(self, within: Optional[Sequence[Interval]] = None
                     ) -> Dict[str, float]:
        """Idle seconds of the device by innermost span name, mean over
        chips; only inside ``within`` (sorted, disjoint) when given."""
        agg: Dict[str, float] = defaultdict(float)
        for m in self.busy.values():
            idle = complement(m, *self.window)
            if within is not None:
                idle = intersect(idle, within)
            for name, t in attribute(self.pieces, idle).items():
                agg[name] += t / 1e9
        n_chips = max(len(self.busy), 1)
        return {name: t / n_chips for name, t in agg.items()}

    def idle_gaps(self, k: int = 10) -> List[List]:
        """``Trace.idle_gaps`` over both span lists, split exactly."""
        return [[n, t] for n, t in sorted(self.idle_by_span().items(),
                                          key=lambda kv: -kv[1])[:k]]


def per_call(trace: SpanTrace, ops: Sequence[str]) -> Optional[dict]:
    """The split of the ``ops`` call spans, per call (see the module's
    docstring); None when the window has no such call."""
    calls = trace.calls(ops)
    if not calls or not trace.busy:
        return None
    n = len(calls)
    inside = starting_in(trace.program_spans, calls)
    idle = trace.idle_by_span(calls)
    groups: Dict[str, float] = defaultdict(float)
    for name, t in idle.items():
        groups[group_of(name)] += 1e3 * t / n
    total = sum(groups.values())
    out = {"calls": n, "host_gap_ms": host_gap_ms(trace, ops),
           "plan_ms": None, "sync_ms": None, "syncs": None,
           "idle_ms": dict(groups),
           "named_share": (1.0 - groups.get("rest", 0.0) / total
                           if total > 0 else None)}
    if any(name.startswith("client.") for _, _, name in inside):
        sync = [sp for sp in inside if sp[2].startswith("client.sync.")]
        out["plan_ms"] = sum(e - s for s, e, name in inside
                             if name == "client.plan") / 1e6 / n
        out["sync_ms"] = sum(e - s for s, e, _ in sync) / 1e6 / n
        out["syncs"] = len(sync) / n
    return out


def split(path: str) -> dict:
    trace = SpanTrace(path)
    res = {"file": path, "window_s": trace.window_s,
           "idle_s": trace.window_s - trace.busy_s}
    for family, ops in (("ckpt", CKPT_OPS), ("md", META_OPS)):
        got = per_call(trace, ops)
        if got is not None:
            res[family] = got
    res["idle_gaps"] = trace.idle_gaps()
    return res


def main(argv: Optional[Sequence[str]] = None) -> int:
    paths = list(sys.argv[1:] if argv is None else argv)
    if not paths:
        sys.exit(__doc__.split("\n\n")[1])
    for path in paths:
        print(json.dumps(split(path)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
