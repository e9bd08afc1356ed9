"""Span tracer: nested host-timed regions, recorded into the JAX
profiler's trace and exportable as Chrome trace JSON.

    from repro.observability import trace

    with trace.span("serving.stage", bucket="64x64", batch=8):
        buf = stage(chunk)

    trace.export_chrome_trace("trace.json")   # load in chrome://tracing

Design points:

  * **On while tracing is enabled or a profiler session captures.**  A
    span records when :func:`repro.observability.enable` turned tracing
    on, or while ``jax.profiler`` is capturing (between ``start_trace``
    and ``stop_trace``), so a captured profile always carries the
    program's spans.  Otherwise :func:`span` returns a shared
    ``_NullSpan`` singleton: two flag tests, no clock reads, no
    allocation.  The overhead-budget test in tests/test_observability.py
    holds that path to < 1% of the tiled 256² solve.
  * **Two sinks, one span.**  An open span is also a
    ``jax.profiler.TraceAnnotation`` under the same name, with its
    labels as the event's stats, so it lands on the profiler's host
    plane on the same clock as the device's ``XLA Ops``.  The completed
    span is kept in memory for :func:`spans`, :func:`tree` and
    :func:`chrome_trace`.
  * **Never blocks.**  A span measures host time only: it waits for no
    device value, so a traced program runs as an untraced one does.
    The device side of the same interval is in the profiler's trace.
  * **Correct nesting.**  A thread-local stack gives every span a
    parent; depths and parent ids survive into the export, and
    :func:`tree` renders the hierarchy as text.

Spans belong in host code: inside a jitted function a span times the
trace, once per compile, not the execution.

Export is the Chrome trace-event format: ``{"traceEvents": [...]}``
with ``ph: "X"`` complete events, microsecond ``ts``/``dur``, ``pid`` /
``tid``, and span labels in ``args``.
"""

from __future__ import annotations

import json
import os
import threading
import time
from typing import Any, Dict, List, Optional

from jax.profiler import TraceAnnotation

from . import instrument

__all__ = [
    "Span",
    "chrome_trace",
    "clear",
    "export_chrome_trace",
    "span",
    "spans",
    "tree",
]

_EVENTS: List["Span"] = []
_EVENTS_LOCK = threading.Lock()
_TLS = threading.local()
_IDS = iter(range(1, 1 << 62))


def _stack() -> List["Span"]:
    s = getattr(_TLS, "stack", None)
    if s is None:
        s = _TLS.stack = []
    return s


class Span:
    """One timed region.  Create via :func:`span`, not directly."""

    __slots__ = ("name", "labels", "sid", "parent_sid", "depth", "tid",
                 "t_start", "t_end", "_annotation")

    def __init__(self, name: str, labels: Dict[str, Any]) -> None:
        self.name = name
        self.labels = labels
        self.sid = next(_IDS)
        self.parent_sid: Optional[int] = None
        self.depth = 0
        self.tid = threading.get_ident()
        self.t_start = 0.0
        self.t_end = 0.0
        self._annotation: Optional[TraceAnnotation] = None

    @property
    def duration_us(self) -> float:
        return (self.t_end - self.t_start) * 1e6

    def set(self, **labels: Any) -> "Span":
        self.labels.update(labels)
        if self._annotation is not None:
            self._annotation.set_metadata(**labels)
        return self

    def __enter__(self) -> "Span":
        stack = _stack()
        if stack:
            parent = stack[-1]
            self.parent_sid = parent.sid
            self.depth = parent.depth + 1
        stack.append(self)
        self._annotation = TraceAnnotation(self.name, **self.labels)
        self._annotation.__enter__()
        self.t_start = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        self.t_end = time.perf_counter()
        self._annotation.__exit__(exc_type, exc, tb)
        self._annotation = None
        stack = _stack()
        if stack and stack[-1] is self:
            stack.pop()
        elif self in stack:  # tolerate out-of-order exits
            stack.remove(self)
        with _EVENTS_LOCK:
            _EVENTS.append(self)


class _NullSpan:
    """Shared do-nothing span: the disabled-mode fast path."""

    __slots__ = ()

    def __enter__(self) -> "_NullSpan":
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        pass

    def set(self, **labels: Any) -> "_NullSpan":
        return self


_NULL_SPAN = _NullSpan()


def span(name: str, **labels: Any):
    """Context manager timing a host region: a recorded :class:`Span`
    while tracing is enabled or a profiler session captures, else the
    shared no-op singleton."""
    if not (instrument.tracing_enabled() or TraceAnnotation.is_enabled()):
        return _NULL_SPAN
    return Span(name, labels)


def spans() -> List[Span]:
    """Completed spans, in completion order."""
    with _EVENTS_LOCK:
        return list(_EVENTS)


def clear() -> None:
    with _EVENTS_LOCK:
        _EVENTS.clear()


def chrome_trace() -> Dict[str, Any]:
    """Chrome trace-event JSON object for all completed spans."""
    pid = os.getpid()
    events = []
    for sp in spans():
        events.append({
            "name": sp.name,
            "ph": "X",
            "ts": sp.t_start * 1e6,
            "dur": sp.duration_us,
            "pid": pid,
            "tid": sp.tid,
            "args": {str(k): _jsonable(v) for k, v in sp.labels.items()},
        })
    events.sort(key=lambda e: e["ts"])
    return {"traceEvents": events, "displayTimeUnit": "ms"}


def export_chrome_trace(path: str) -> str:
    """Write :func:`chrome_trace` JSON to ``path``; returns the path."""
    with open(path, "w") as f:
        json.dump(chrome_trace(), f, indent=1)
    return path


def _jsonable(v: Any) -> Any:
    if isinstance(v, (str, int, float, bool)) or v is None:
        return v
    return str(v)


def tree(max_spans: int = 200) -> str:
    """Text rendering of the span hierarchy (start-time ordered)."""
    all_spans = sorted(spans(), key=lambda s: s.t_start)[:max_spans]
    if not all_spans:
        return "(no spans recorded — is observability enabled?)"
    lines = []
    for sp in all_spans:
        label = " ".join(f"{k}={v}" for k, v in sp.labels.items())
        lines.append(f"{'  ' * sp.depth}{sp.name:<40s} "
                     f"{sp.duration_us:12.1f} us"
                     + (f"  [{label}]" if label else ""))
    return "\n".join(lines)
