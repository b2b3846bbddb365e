"""Telemetry subsystem: round-span tracing, on-device-fenced timing,
per-round metrics and provenance-stamped event logs.

The protocol stack's observability layer (see README "Observability"):

* :mod:`trace`      — nested monotonic-clock spans with explicit
                      ``block_until_ready`` fencing at span exit, each also a
                      ``jax.profiler.TraceAnnotation``, plus the
                      :class:`Stopwatch` timer helper the launch scripts use.
* :mod:`metrics`    — per-round gauges and run counters, populated from the
                      batched path's existing single stacked host fetch (no
                      extra device→host syncs).
* :mod:`sinks`      — JSONL event log (crash-tolerant append), in-memory
                      sink for tests, console sink (the ``verbose=True``
                      replacement).
* :mod:`profile`    — opt-in windowed ``jax.profiler`` trace hooks.
* :mod:`provenance` — the environment stamp (jax/jaxlib, backend, device
                      kind, cpu count, git sha, timestamp) shared by traces
                      and benchmark JSONs.
* :mod:`session`    — the :class:`Telemetry` config object threaded through
                      ``ProtocolConfig``/driver kwargs and the per-run
                      :class:`TelemetrySession` runtime.

Telemetry is a strict no-op on the math: it consumes no RNG streams and
dispatches no device ops, so a telemetry-enabled run produces a
bit-identical ``History`` and CommMeter to a disabled one
(``tests/test_telemetry.py`` pins this across engines × placements ×
prefetch).
"""
from .metrics import (MetricsRegistry, jit_cache_stats, pool_gauges,
                      round_gauges)
from .profile import ProfileHook
from .provenance import provenance
from .session import (DISABLED, NULL_SESSION, NullSession, Telemetry,
                      TelemetrySession, resolve_telemetry)
from .sinks import ConsoleSink, JSONLSink, MemorySink, Sink, read_jsonl
from .trace import NULL_SPAN, NULL_TRACER, Span, Stopwatch, Tracer

__all__ = [
    "Telemetry", "TelemetrySession", "NullSession", "NULL_SESSION",
    "DISABLED", "resolve_telemetry",
    "Tracer", "Span", "Stopwatch", "NULL_TRACER", "NULL_SPAN",
    "MetricsRegistry", "round_gauges", "pool_gauges", "jit_cache_stats",
    "Sink", "JSONLSink", "MemorySink", "ConsoleSink", "read_jsonl",
    "ProfileHook", "provenance",
]
