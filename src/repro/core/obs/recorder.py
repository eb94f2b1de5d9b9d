"""Flight recorder: bounded ring of structured spans with jit-safe timing.

A :class:`TraceRecorder` owns three planes of one recording: the span
ring (this module), a :class:`~.metrics.MetricsRegistry` and a
:class:`~.audit.DecisionAudit`.  Passing one to ``BBClient(trace=...)``
turns the whole exchange/adapt pipeline into an instrumented run.

Every :func:`span` also enters a ``jax.profiler.TraceAnnotation`` of the
same name while a profiler capture runs, recorder or not, so the
program's spans land on the capture's ``/host:CPU`` plane on the same
clock as the device's events.  With no capture the annotation is skipped
after one check; the profiler path never fences.

Two span categories exist because jax splits every computation into a
trace/compile phase and an execute phase:

* ``cat="trace"`` spans wrap code that runs while jax is *tracing*
  (``run_exchange``, the burst-buffer entry points).  They fire once
  per specialization and measure plan/lowering cost — and, crucially,
  they give the recording its nested plan → pack → all_to_all/ppermute
  → apply → carry structure.  In a profiler capture of warmed calls
  one of them marks a retrace.
* host-side spans (``cat="client"``, ``"adapt"``, ...) wrap dispatch
  sites.  Wall-clocking a jax dispatch without synchronizing measures
  only the async enqueue, so a span may register a **fence** value:
  at span exit the recorder calls ``jax.block_until_ready`` on its
  leaves *before* taking the end timestamp.  That is the one correct
  way to time jit work with the recorder's own clock, and
  ``tools/repo_lint.py`` rejects the unfenced pattern everywhere else.
  The fence applies only with an active recorder: a profiler capture
  times the device itself.

Activation is dynamically scoped: ``with activate(rec): ...`` pushes
``rec`` on a stack consulted by the module-level :func:`span` /
:func:`current_recorder` helpers, so deep library code (executors,
selectors) records into whatever client invoked it without threading a
recorder argument through every signature.
"""
from __future__ import annotations

import contextlib
import functools
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Dict, Iterator, List, Optional

from jax.profiler import TraceAnnotation

from repro.core.obs.audit import DecisionAudit
from repro.core.obs.metrics import MetricsRegistry

#: dynamically scoped stack of active recorders (top = current)
_ACTIVE: List["TraceRecorder"] = []


@dataclass
class Span:
    """One completed span: name, category, start/duration (µs), depth, args.

    ``ts_us`` is relative to the owning recorder's epoch so a recording
    always starts near 0; ``depth`` is the nesting level at entry (the
    Perfetto exporter keeps all spans on one track — nesting is implied
    by timestamp containment, which a stack discipline guarantees).
    """

    name: str
    cat: str
    ts_us: float
    dur_us: float
    depth: int
    args: Dict[str, object] = field(default_factory=dict)


class SpanHandle:
    """Mutable handle yielded by :meth:`TraceRecorder.span`.

    Lets the instrumented code attach attributes discovered mid-span
    (:meth:`set`) and register the jax value whose completion defines
    the span's end (:meth:`fence`).
    """

    def __init__(self, args: Dict[str, object]) -> None:
        self.args = args
        self._fence = None

    def set(self, **attrs: object) -> None:
        """Merge ``attrs`` into the span's args."""
        self.args.update(attrs)

    def fence(self, value):
        """Register ``value`` to be blocked on at span exit; returns it.

        The recorder calls ``jax.block_until_ready`` on the pytree's
        leaves before taking the end timestamp, so the span duration
        covers device execution, not just async dispatch.
        """
        self._fence = value
        return value


def block_on(value):
    """Fence helper: block until every jax leaf of ``value`` is ready.

    Accepts arbitrary pytrees (states, tuples, None) and returns the
    value, so it can wrap a return expression in timed code.
    """
    if value is None:
        return None
    import jax

    jax.block_until_ready(jax.tree_util.tree_leaves(value))
    return value


class TraceRecorder:
    """Bounded flight recorder for one client/run.

    ``capacity`` bounds the span ring (oldest spans evicted first, with
    ``dropped_spans`` counting evictions); ``metrics`` and ``audit``
    default to fresh instances and are shared with every
    instrumentation site that runs while this recorder is active.
    """

    def __init__(self, capacity: int = 8192, *,
                 metrics: Optional[MetricsRegistry] = None,
                 audit: Optional[DecisionAudit] = None) -> None:
        self.spans: deque = deque(maxlen=int(capacity))
        self.metrics = metrics if metrics is not None else MetricsRegistry()
        self.audit = audit if audit is not None else DecisionAudit()
        self.dropped_spans = 0
        self._depth = 0
        self._epoch = time.perf_counter()
        #: span name → premade (count_key, us_key) rollup counter keys —
        #: the rollup runs on every span exit in the client hot path, so
        #: the ``metric_key`` string build is paid once per name
        self._rollup: Dict[str, tuple] = {}

    def _now_us(self) -> float:
        return (time.perf_counter() - self._epoch) * 1e6

    @contextlib.contextmanager
    def span(self, name: str, cat: str = "bb",
             **attrs: object) -> Iterator[SpanHandle]:
        """Record one span around the ``with`` body.

        The yielded :class:`SpanHandle` can attach attributes and a
        fence value; the end timestamp is taken only after the fence
        (if any) has been blocked on.
        """
        handle = SpanHandle(dict(attrs))
        t0 = self._now_us()
        depth = self._depth
        self._depth += 1
        try:
            yield handle
        finally:
            self._depth -= 1
            if handle._fence is not None:
                block_on(handle._fence)
            t1 = self._now_us()
            if len(self.spans) == self.spans.maxlen:
                self.dropped_spans += 1
            self.spans.append(Span(
                name=name, cat=cat, ts_us=t0, dur_us=t1 - t0,
                depth=depth, args=handle.args))
            keys = self._rollup.get(name)
            if keys is None:
                keys = (f"span_count_total{{span={name}}}",
                        f"span_us_total{{span={name}}}")
                self._rollup[name] = keys
            counters = self.metrics.counters
            counters[keys[0]] = counters.get(keys[0], 0.0) + 1.0
            counters[keys[1]] = counters.get(keys[1], 0.0) + (t1 - t0)


# ---------------------------------------------------------------------------
# dynamic activation
# ---------------------------------------------------------------------------
@contextlib.contextmanager
def activate(recorder: Optional[TraceRecorder]) -> Iterator[None]:
    """Make ``recorder`` the current recorder for the ``with`` body.

    ``activate(None)`` is a no-op context manager, so call sites can
    always write ``with activate(client.obs): ...`` without branching.
    """
    if recorder is None:
        yield
        return
    _ACTIVE.append(recorder)
    try:
        yield
    finally:
        _ACTIVE.pop()


def current_recorder() -> Optional[TraceRecorder]:
    """The innermost active recorder, or ``None`` outside any activation."""
    return _ACTIVE[-1] if _ACTIVE else None


def current_metrics() -> Optional[MetricsRegistry]:
    """The active recorder's metrics registry, or ``None``."""
    rec = current_recorder()
    return rec.metrics if rec is not None else None


class _NullHandle(SpanHandle):
    """Inert handle for the no-recorder path: records and retains nothing."""

    def __init__(self) -> None:
        super().__init__({})

    def set(self, **attrs: object) -> None:
        """Drop the attributes (nothing is recording)."""

    def fence(self, value):
        """Pass the value through without retaining it or blocking."""
        return value


_NULL_HANDLE = _NullHandle()


class _Span:
    """The context manager :func:`span` returns (a class, not a generator:
    it runs on every client call, tracing or not)."""

    __slots__ = ("name", "cat", "attrs", "_ann", "_rec")

    def __init__(self, name: str, cat: str, attrs: Dict[str, object]):
        self.name, self.cat, self.attrs = name, cat, attrs
        self._ann = self._rec = None

    def __enter__(self) -> SpanHandle:
        if TraceAnnotation.is_enabled():
            self._ann = TraceAnnotation(self.name)
            self._ann.__enter__()
        if not _ACTIVE:
            return _NULL_HANDLE
        self._rec = _ACTIVE[-1].span(self.name, cat=self.cat, **self.attrs)
        return self._rec.__enter__()

    def __exit__(self, *exc) -> bool:
        try:
            if self._rec is not None:
                self._rec.__exit__(*exc)
        finally:
            if self._ann is not None:
                self._ann.__exit__(*exc)
        return False


def span(name: str, cat: str = "bb", **attrs: object) -> _Span:
    """Span on the *current* recorder and the profiler, when either runs.

    With no active recorder the span yields a shared inert handle (its
    ``set``/``fence`` still work, they just record nothing), so
    instrumented code never branches on whether tracing is on; with no
    profiler capture either, entering and leaving it costs two checks.
    """
    return _Span(name, cat, attrs)


def trace_span(name: str, cat: str = "trace"):
    """Decorator: wrap a function in a :func:`span`.

    Used on the burst-buffer entry points, which execute during jit
    *tracing* — the span fires once per specialization and nests under
    the dispatching client span.
    """
    def deco(fn):
        @functools.wraps(fn)
        def wrapped(*args, **kwargs):
            with span(name, cat=cat):
                return fn(*args, **kwargs)
        return wrapped
    return deco
