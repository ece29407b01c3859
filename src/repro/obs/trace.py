"""Nestable span tracer with device-sync-aware timing, bridged into
the JAX profiler's trace.

Overhead contract (DESIGN.md §9):

* **Tracing off and profiler idle** (the default): :func:`span`
  returns one shared module-level no-op object -- one global read and
  one ``TraceAnnotation.is_enabled()`` call, no event record, no
  attribute dict walk, and crucially *no host sync*, so the serving
  hot path is untouched and the ``hot-path-sync`` lint rule stays
  green by construction.
* **Profiler recording, tracing off**: a span opens a
  ``jax.profiler.TraceAnnotation`` of the same name (its attributes
  become the annotation's metadata) and nothing else -- no event
  record and no sync even when ``sync=`` was given: the profiler sees
  device time directly, and a traced run must keep the untraced run's
  schedule.  The span lands on the host thread's line of the
  ``.xplane.pb``, on the same clock as the device ops.
* **Tracing on**: a span syncs *only at its close*, and only when the
  caller registered device values to block on (``Span.sync(...)`` or
  the ``sync=`` kwarg) -- one intended block point per stage, which is
  exactly the discipline the serving plane already follows.  Those
  close-time syncs are the only host syncs the tracer ever performs
  and each carries a justified ``grit-lint`` pragma.  When the
  profiler records too, the span also opens its annotation, closed
  after the sync so that it covers the device wait.

Spans nest lexically (context managers); the tracer keeps a per-thread
stack so the exporter can emit parent-ordered Chrome trace events and
the viewer can compute self-times.  Timestamps are
``time.perf_counter`` microseconds relative to the tracer's start --
monotonic, which is what Perfetto wants.
"""

from __future__ import annotations

import threading
import time
from typing import Any, Dict, List, Optional

try:
    from jax.profiler import TraceAnnotation
    # whether the JAX profiler is recording: jaxlib's static TraceMe
    # check (no allocation, no lock)
    _profiling = TraceAnnotation.is_enabled
except ImportError:          # jax not importable: host spans still work
    TraceAnnotation = None

    def _profiling() -> bool:
        return False

__all__ = ["Tracer", "Span", "NOOP_SPAN", "span", "enabled", "enable",
           "disable", "get_tracer"]


class _NoopSpan:
    """The disabled-tracer span: one shared instance, every method a
    no-op returning fast.  Reentrant (``__enter__`` just returns self),
    so one module-level object serves arbitrarily nested ``with``
    blocks with zero allocations."""

    __slots__ = ()

    def __enter__(self) -> "_NoopSpan":
        return self

    def __exit__(self, *exc) -> None:
        return None

    def set(self, **attrs: Any) -> "_NoopSpan":
        return self

    def sync(self, *values: Any) -> "_NoopSpan":
        return self


NOOP_SPAN = _NoopSpan()


class _ProfilerSpan:
    """The span while only the profiler records: a
    ``TraceAnnotation`` of the span's name and nothing else -- no event
    record and never a sync (``sync`` registers nothing)."""

    __slots__ = ("_ann",)

    def __init__(self, name: str, attrs: Dict[str, Any]):
        self._ann = TraceAnnotation(name, **attrs)

    def __enter__(self) -> "_ProfilerSpan":
        self._ann.__enter__()
        return self

    def __exit__(self, *exc) -> None:
        self._ann.__exit__(*exc)

    def set(self, **attrs: Any) -> "_ProfilerSpan":
        self._ann.set_metadata(**attrs)
        return self

    def sync(self, *values: Any) -> "_ProfilerSpan":
        return self


class Span:
    """One live span.  Use as a context manager; at ``__exit__`` it
    optionally blocks on the registered device values (so the recorded
    duration covers the device work the stage dispatched, not just the
    Python that enqueued it) and records one complete event."""

    __slots__ = ("_tracer", "name", "attrs", "_sync", "_t0", "_ann")

    def __init__(self, tracer: "Tracer", name: str,
                 attrs: Optional[Dict[str, Any]],
                 sync: Optional[Any] = None):
        self._tracer = tracer
        self.name = name
        self.attrs = attrs
        self._sync = [sync] if sync is not None else []
        self._t0 = 0.0
        self._ann = None

    def set(self, **attrs: Any) -> "Span":
        """Attach attributes mid-span (rendered as Chrome trace args)."""
        if self.attrs is None:
            self.attrs = {}
        self.attrs.update(attrs)
        if self._ann is not None:
            self._ann.set_metadata(**attrs)
        return self

    def sync(self, *values: Any) -> "Span":
        """Register device values to block on at span close."""
        self._sync.extend(values)
        return self

    def __enter__(self) -> "Span":
        if _profiling():
            self._ann = TraceAnnotation(self.name, **(self.attrs or {}))
            self._ann.__enter__()
        self._tracer._push(self)
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        if self._sync and exc_type is None:
            import jax
            # the tracer's single intended block point: enabled-mode
            # spans time device work by blocking at stage close --
            # that sync is the feature, and it never runs when
            # tracing is off (span() returns NOOP_SPAN then)
            jax.block_until_ready(self._sync)  # grit-lint: disable=hot-path-sync -- enabled-mode span close is the stage's intended block point; tracing-off serving never reaches this line
        t1 = time.perf_counter()
        if self._ann is not None:
            self._ann.__exit__(exc_type, exc, tb)
        self._tracer._pop(self, self._t0, t1, error=exc_type is not None)


class Tracer:
    """Records complete-span events (thread-safe append)."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._local = threading.local()
        self.t0 = time.perf_counter()
        self.events: List[Dict[str, Any]] = []

    # -- span plumbing -----------------------------------------------------

    def _stack(self) -> List["Span"]:
        st = getattr(self._local, "stack", None)
        if st is None:
            st = self._local.stack = []
        return st

    def _push(self, span: Span) -> None:
        self._stack().append(span)

    def _pop(self, span: Span, t0: float, t1: float,
             error: bool = False) -> None:
        stack = self._stack()
        depth = len(stack) - 1
        if stack and stack[-1] is span:
            stack.pop()
        ev: Dict[str, Any] = {
            "name": span.name,
            "ph": "X",
            "ts": (t0 - self.t0) * 1e6,          # us, perf_counter base
            "dur": (t1 - t0) * 1e6,
            "pid": 0,
            "tid": threading.get_ident() % 100_000,
            "depth": depth,
        }
        if span.attrs:
            ev["args"] = span.attrs
        if error:
            ev.setdefault("args", {})["error"] = True
        with self._lock:
            self.events.append(ev)

    # -- public ------------------------------------------------------------

    def span(self, name: str, sync: Optional[Any] = None,
             **attrs: Any) -> Span:
        return Span(self, name, attrs or None, sync=sync)

    def clear(self) -> None:
        with self._lock:
            self.events.clear()
        self.t0 = time.perf_counter()

    def snapshot_events(self) -> List[Dict[str, Any]]:
        with self._lock:
            return [dict(e) for e in self.events]


# --------------------------------------------------------------------------
# module-level switch
# --------------------------------------------------------------------------

_TRACER: Optional[Tracer] = None


def enabled() -> bool:
    return _TRACER is not None


def get_tracer() -> Optional[Tracer]:
    return _TRACER


def enable(clear: bool = False) -> Tracer:
    """Turn tracing on (idempotent); returns the live tracer."""
    global _TRACER
    if _TRACER is None:
        _TRACER = Tracer()
    elif clear:
        _TRACER.clear()
    return _TRACER


def disable() -> Optional[Tracer]:
    """Turn tracing off; returns the (frozen) tracer for export."""
    global _TRACER
    t, _TRACER = _TRACER, None
    return t


def span(name: str, sync: Optional[Any] = None, **attrs: Any):
    """A span under the process tracer, bridged into the profiler's
    trace while it records -- or the shared no-op when neither is on
    (the hot-path fast exit: one global read and one ``is_enabled()``)."""
    t = _TRACER
    if t is None:
        if not _profiling():
            return NOOP_SPAN
        return _ProfilerSpan(name, attrs)
    return Span(t, name, attrs or None, sync=sync)
