"""Span tracer: nested, monotonic-clock phase timing with explicit device
fencing.

A :class:`Tracer` produces :class:`Span` records — name, wall-clock duration
on the monotonic ``time.perf_counter`` clock, nesting path and thread — and
hands each finished span to an ``emit`` callback (the telemetry session's
sink fan-out).  Spans nest per *thread* (the stack lives in
``threading.local``), so the :class:`~repro.data.pipeline.RoundFeeder`'s
producer thread traces its assembly work without interleaving into the main
thread's round spans.

Device attribution is explicit rather than implicit: JAX dispatch is
asynchronous, so the wall-clock interval around ``runner.accept(...)`` only
measures *enqueue* time unless the span waits for the device.  Call
:meth:`Span.fence` with the arrays the phase produced and the span exit runs
``jax.block_until_ready`` on them *before* reading the clock — the device
work is attributed to the phase that launched it, and the following phase
(e.g. the host fetch) measures only its own cost.  Fencing waits for
completion; it performs no device→host data transfer, so enabling telemetry
adds no extra fetches to the batched path.

Every live span is also a ``jax.profiler.TraceAnnotation`` of the same name
carrying its scalar attrs, opened at entry and closed after the fence, so a
``jax.profiler`` trace holds the program's phases on its host plane, on the
same clock as the device's operations: an idle gap in the device trace falls
inside a named phase (``round.feeder_wait``, ``assemble.put``, ...).  The
annotation costs about a microsecond when no profiler runs; the disabled
tracer (:class:`NullSpan`) opens none.

:class:`Stopwatch` is the module's plain timer helper (the launch scripts'
replacement for non-monotonic ``time.time()`` deltas).
"""
from __future__ import annotations

import threading
import time
from typing import Any, Callable, Dict, List, Optional

import jax


class Stopwatch:
    """Monotonic context-manager timer: ``with Stopwatch() as sw: ...`` then
    read ``sw.elapsed`` (seconds on the ``perf_counter`` clock).  The wall
    clock (``time.time``) can step backwards under NTP adjustment; every
    telemetry duration goes through this helper or :class:`Tracer`."""

    def __enter__(self) -> "Stopwatch":
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        self.elapsed = time.perf_counter() - self.t0


class Span:
    """One live span.  Created by :meth:`Tracer.span`; used as a context
    manager.  ``fence(arrays)`` registers pytrees whose device computation
    belongs to this span — span exit blocks on them before stopping the
    clock."""

    __slots__ = ("name", "attrs", "_tracer", "_t0", "_fences", "_annot")

    def __init__(self, tracer: "Tracer", name: str, attrs: Dict[str, Any]):
        self._tracer = tracer
        self.name = name
        self.attrs = attrs
        self._fences: List[Any] = []

    def fence(self, *arrays: Any) -> None:
        """Attribute the device work producing ``arrays`` (any pytrees) to
        this span: exit calls ``jax.block_until_ready`` on them before the
        duration is read."""
        self._fences.extend(arrays)

    def __enter__(self) -> "Span":
        self._tracer._push(self.name)
        self._annot = jax.profiler.TraceAnnotation(self.name, **{
            k: v for k, v in self.attrs.items()
            if isinstance(v, (bool, int, float, str))})
        self._annot.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        try:
            if self._fences:
                jax.block_until_ready(self._fences)
            dur = time.perf_counter() - self._t0
        finally:
            self._annot.__exit__(exc_type, exc, tb)
        path, depth = self._tracer._pop()
        event = {"event": "span", "name": self.name, "path": path,
                 "depth": depth, "start_s": self._t0, "dur_s": dur,
                 "thread": threading.current_thread().name}
        if exc_type is not None:
            event["error"] = exc_type.__name__
        event.update(self.attrs)
        self._tracer._emit(event)


class Tracer:
    """Factory for nested spans.  ``emit`` receives one dict per finished
    span (children before parents, since parents exit last).  Thread-safe:
    each thread nests independently."""

    def __init__(self, emit: Callable[[Dict[str, Any]], None]):
        self._emit = emit
        self._local = threading.local()

    def _stack(self) -> List[str]:
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._local.stack = []
        return stack

    def _push(self, name: str) -> None:
        self._stack().append(name)

    def _pop(self) -> tuple:
        stack = self._stack()
        path = "/".join(stack)
        stack.pop()
        return path, len(stack)

    def span(self, name: str, **attrs: Any) -> Span:
        return Span(self, name, attrs)


class NullSpan:
    """The disabled tracer's span: every operation is a no-op, so the hot
    loop pays one attribute lookup and one method call per phase."""

    __slots__ = ()

    def fence(self, *arrays: Any) -> None:
        pass

    def __enter__(self) -> "NullSpan":
        return self

    def __exit__(self, *exc) -> None:
        pass


NULL_SPAN = NullSpan()


class NullTracer:
    __slots__ = ()

    def span(self, name: str, **attrs: Any) -> NullSpan:
        return NULL_SPAN


NULL_TRACER = NullTracer()
