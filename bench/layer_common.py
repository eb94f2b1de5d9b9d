"""Reductions shared by the per-layer readers in ``bench/layers/``."""
from __future__ import annotations

from typing import Optional, Sequence

META_OPS = ("create", "stat", "remove")


def dev_ms(trace, ops: Sequence[str]) -> Optional[float]:
    """Device-busy milliseconds inside the ``ops`` call spans, per call."""
    spans = trace.calls(ops)
    if not spans or not trace.busy:
        return None
    return 1e3 * trace.busy_in(spans) / len(spans)


def host_gap_ms(trace, ops: Sequence[str]) -> Optional[float]:
    """Device-idle milliseconds inside the ``ops`` call spans, per call."""
    spans = trace.calls(ops)
    if not spans or not trace.busy:
        return None
    span_s = sum(e - s for s, e in spans) / 1e9
    return 1e3 * (span_s - trace.busy_in(spans)) / len(spans)


def idle_share(trace) -> Optional[float]:
    if not trace.busy or trace.window_s <= 0:
        return None
    return 100.0 * (1.0 - trace.busy_s / trace.window_s)
