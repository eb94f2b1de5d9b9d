"""Profiler capture and the reduction from a device trace to numbers.

``Capture`` runs the measured window under ``jax.profiler`` with the Python
tracer off, so the only host spans are the benchmark's own
``TraceAnnotation``s (named ``bench.*``).  ``Trace`` reads the
``.xplane.pb`` that it writes, through ``jax.profiler.ProfileData``:

- device events: the ``XLA Ops`` line of every ``/device:TPU:<n>`` plane;
- host spans: ``bench.*`` events of the ``/host:CPU`` plane.

Both are on the host's clock in the trace.  Busy time is the union of the
device events' intervals; a chip's idle share is one minus its busy time
over the traced window, and a number over several chips is their mean.
The per-layer readers in ``bench/layers/`` take their numbers from here.
"""
from __future__ import annotations

import bisect
import glob
import os
import shutil
import tempfile
from collections import defaultdict
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]

WINDOW_SPAN = "bench.window"
CALL_PREFIX = "bench.call."
DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
#: a device op's name is its whole HLO instruction; the breakdown keeps
#: the instruction's name, result type and opcode
NAME_CHARS = 120


class Capture:
    """Trace the enclosed block with the Python tracer off.

    The trace goes to a temporary directory under ``TMPDIR``; ``load``
    reads it and deletes the directory, copying the ``.xplane.pb`` first
    when ``keep`` names a directory for it."""

    def __enter__(self):
        import jax
        self.dir = tempfile.mkdtemp(prefix="bench_trace_")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        return self

    def __exit__(self, *exc):
        import jax
        jax.profiler.stop_trace()
        return False

    def load(self, keep: Optional[str] = None) -> "Trace":
        try:
            found = glob.glob(os.path.join(self.dir, "**", "*.xplane.pb"),
                              recursive=True)
            if not found:
                raise FileNotFoundError(f"no .xplane.pb under {self.dir}")
            if keep:
                os.makedirs(keep, exist_ok=True)
                shutil.copy(found[0], keep)
            return Trace(found[0])
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


def merge(intervals: Iterable[Interval]) -> List[Interval]:
    """Sorted, disjoint union of intervals."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def overlap(merged: Sequence[Interval], starts: Sequence[float],
            s: float, e: float) -> float:
    """Length of [s, e) covered by ``merged`` (``starts`` its left ends)."""
    total = 0.0
    i = max(bisect.bisect_right(starts, s) - 1, 0)
    while i < len(merged) and merged[i][0] < e:
        a, b = merged[i]
        total += max(0.0, min(b, e) - max(a, s))
        i += 1
    return total


class Trace:
    """Device events and benchmark spans of one traced window (ns)."""

    def __init__(self, path: str):
        from jax.profiler import ProfileData
        pd = ProfileData.from_file(path)
        self.ops: Dict[str, List[Tuple[float, float, str]]] = {}
        self.spans: List[Tuple[float, float, str]] = []
        for plane in pd.planes:
            if plane.name.startswith(DEVICE_PLANE):
                evs = []
                for line in plane.lines:
                    if line.name == OPS_LINE:
                        evs.extend((ev.start_ns, ev.start_ns + ev.duration_ns,
                                    ev.name) for ev in line.events)
                self.ops[plane.name] = evs
            elif plane.name == "/host:CPU":
                for line in plane.lines:
                    self.spans.extend(
                        (ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
                        for ev in line.events if ev.name.startswith("bench."))
        self.spans.sort()
        win = [(s, e) for s, e, n in self.spans if n == WINDOW_SPAN]
        if not win:
            raise ValueError(f"{path}: no {WINDOW_SPAN} span in the trace")
        self.window = (min(s for s, _ in win), max(e for _, e in win))
        self.busy: Dict[str, List[Interval]] = {}
        for chip, evs in self.ops.items():
            self.busy[chip] = merge(
                (max(s, self.window[0]), min(e, self.window[1]))
                for s, e, _ in evs if e > self.window[0] and
                s < self.window[1])
        self._starts = {c: [s for s, _ in m] for c, m in self.busy.items()}

    # ---- whole window -----------------------------------------------------
    @property
    def chips(self) -> int:
        return len(self.busy)

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    @property
    def busy_s(self) -> float:
        """Seconds in which an op ran on the device, mean over chips."""
        if not self.busy:
            return 0.0
        return sum(sum(e - s for s, e in m) for m in self.busy.values()) / \
            len(self.busy) / 1e9

    # ---- host spans -------------------------------------------------------
    def calls(self, ops: Sequence[str]) -> List[Interval]:
        names = {CALL_PREFIX + op for op in ops}
        return [(s, e) for s, e, n in self.spans if n in names]

    def spans_named(self, name: str, within: Sequence[Interval]
                    ) -> List[Interval]:
        """``name`` spans that start inside one of ``within``."""
        starts = [s for s, _ in within]
        out = []
        for s, e, n in self.spans:
            if n != name:
                continue
            i = bisect.bisect_right(starts, s) - 1
            if i >= 0 and s < within[i][1]:
                out.append((s, e))
        return out

    def busy_in(self, spans: Sequence[Interval]) -> float:
        """Device-busy seconds inside ``spans``, mean over chips."""
        if not self.busy:
            return 0.0
        tot = 0.0
        for chip, m in self.busy.items():
            st = self._starts[chip]
            tot += sum(overlap(m, st, s, e) for s, e in spans)
        return tot / len(self.busy) / 1e9

    # ---- device events ----------------------------------------------------
    def op_seconds(self, match) -> float:
        """Summed duration of device events whose name ``match``es, inside
        the window, mean over chips."""
        if not self.ops:
            return 0.0
        lo, hi = self.window
        tot = sum(min(e, hi) - max(s, lo)
                  for evs in self.ops.values() for s, e, n in evs
                  if e > lo and s < hi and match(n))
        return tot / len(self.ops) / 1e9

    def top_ops(self, k: int = 10) -> List[List]:
        """Device seconds by op name, mean over chips, the largest ``k``.
        A loop's event holds its body's events, so the times overlap."""
        lo, hi = self.window
        agg: Dict[str, float] = defaultdict(float)
        for evs in self.ops.values():
            for s, e, n in evs:
                if e > lo and s < hi:
                    agg[n[:NAME_CHARS]] += (min(e, hi) - max(s, lo)) / 1e9
        n_chips = max(len(self.ops), 1)
        return [[n, t / n_chips] for n, t in
                sorted(agg.items(), key=lambda kv: -kv[1])[:k]]

    def idle_gaps(self, k: int = 10) -> List[List]:
        """Idle seconds of the device, by the innermost benchmark span the
        gap's midpoint fell in, mean over chips; the largest ``k``."""
        spans = [(s, e, n) for s, e, n in self.spans if n != WINDOW_SPAN]
        starts = [s for s, _, _ in spans]
        agg: Dict[str, float] = defaultdict(float)
        for m in self.busy.values():
            edges = [self.window[0]] + [x for iv in m for x in iv] + \
                [self.window[1]]
            for a, b in zip(edges[0::2], edges[1::2]):
                if b > a:
                    agg[self._inner(spans, starts, (a + b) / 2)] += \
                        (b - a) / 1e9
        n_chips = max(len(self.busy), 1)
        return [[n, t / n_chips] for n, t in
                sorted(agg.items(), key=lambda kv: -kv[1])[:k]]

    @staticmethod
    def _inner(spans, starts, t: float) -> str:
        """Name of the innermost span holding ``t``: spans nest at most a
        few deep, so it is among the last few that start before ``t``."""
        i = bisect.bisect_right(starts, t) - 1
        for j in range(i, max(i - 8, -1), -1):
            if spans[j][1] > t:
                return spans[j][2]
        return "between calls"
