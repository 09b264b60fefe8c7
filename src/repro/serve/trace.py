"""Spans and counters of the serving path, on the profiler's clock.

A :class:`Tracer` records what the engine's host does in each tick:
``span(name, **ids)`` is a context manager that keeps (name, start, end,
parent, ids), ``count(name, n)`` adds to a counter, and ``summary()`` gives
per span name the sample count, p50, p95 and total, plus the counters and
gauges. It is the one thing an operator reads.

Timestamps come from ``time.time_ns()``, the epoch clock the JAX profiler
stamps its trace with: an event of the trace starts at the trace's
``profile_start_time`` (a stat of its ``Task Environment`` plane) plus its
own ``start_ns``, so a span and a device op can be laid side by side.
The profiler itself cannot carry these spans: at any host tracer level it
also records the TPU runtime's host-side layout transposes, about a
million events a second of serving, which slows a tick about fivefold.

A disabled tracer (the default, :data:`NULL`) records nothing: ``span``
returns one shared no-op context and ``count`` returns at once.

An enabled tracer also counts the process's backend compiles and
persistent-cache hits and misses (``compiles``, ``cache_hits``,
``cache_misses``) from one ``jax.monitoring`` listener registered per
process, and keeps a gauge of the device's peak memory in use
(``hbm_peak_bytes``) read from ``memory_stats()`` once per tick, in a
``memory_stats`` span.
"""
from __future__ import annotations

import time
import weakref
from dataclasses import dataclass, field

import numpy as np

_COMPILE = "/jax/core/compile/backend_compile_duration"
_CACHE_EVENTS = {
    "/jax/compilation_cache/cache_hits": "cache_hits",
    "/jax/compilation_cache/cache_misses": "cache_misses",
}
# enabled tracers that count this process's compiles
_LISTENING: "weakref.WeakSet[Tracer]" = weakref.WeakSet()
_registered = False


def _on_duration(event, duration, **kw):
    if event == _COMPILE:
        for t in list(_LISTENING):
            t.count("compiles")


def _on_event(event, **kw):
    name = _CACHE_EVENTS.get(event)
    if name is not None:
        for t in list(_LISTENING):
            t.count(name)


def _listen(tracer: "Tracer") -> None:
    global _registered
    if not _registered:
        import jax

        jax.monitoring.register_event_duration_secs_listener(_on_duration)
        jax.monitoring.register_event_listener(_on_event)
        _registered = True
    _LISTENING.add(tracer)


@dataclass
class Span:
    """One recorded span: ``parent`` is the index of the enclosing span in
    :attr:`Tracer.spans` (-1 for none); ``ids`` holds the tick id or the
    request id it belongs to."""

    name: str
    start_ns: int
    end_ns: int
    parent: int
    ids: dict = field(default_factory=dict)


class _NullSpan:
    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NULL_SPAN = _NullSpan()


class _Open:
    __slots__ = ("tracer", "span")

    def __init__(self, tracer: "Tracer", span: Span):
        self.tracer, self.span = tracer, span

    def __enter__(self):
        t = self.tracer
        self.span.parent = t._stack[-1] if t._stack else -1
        t._stack.append(len(t.spans))
        t.spans.append(self.span)
        self.span.start_ns = time.time_ns()
        return self.span

    def __exit__(self, *exc):
        self.span.end_ns = time.time_ns()
        self.tracer._stack.pop()
        return False


class Tracer:
    """In-memory spans, counters and gauges of the serving path."""

    def __init__(self, enabled: bool = False):
        self.enabled = enabled
        self.spans: list[Span] = []
        self.counters: dict[str, int] = {}
        self.gauges: dict[str, int] = {}
        self._stack: list[int] = []
        if enabled:
            _listen(self)

    def span(self, name: str, **ids):
        """Context manager timing ``name`` as a child of the open span."""
        if not self.enabled:
            return _NULL_SPAN
        return _Open(self, Span(name, 0, 0, -1, ids))

    def add(self, name: str, start_ns: int, end_ns: int, **ids) -> None:
        """Record a span measured elsewhere (such as a request's time in
        the queue); it has no parent."""
        if self.enabled:
            self.spans.append(Span(name, start_ns, end_ns, -1, ids))

    def count(self, name: str, n: int = 1) -> None:
        if self.enabled:
            self.counters[name] = self.counters.get(name, 0) + n

    def sample_memory(self, device) -> None:
        """Raise the ``hbm_peak_bytes`` gauge to the device's peak bytes in
        use, where the backend reports it. The read is a span of its own
        (``memory_stats``), so what it costs the tick has a name."""
        if not self.enabled:
            return
        with self.span("memory_stats"):
            stats = device.memory_stats() or {}
        peak = stats.get("peak_bytes_in_use")
        if peak is not None:
            self.gauges["hbm_peak_bytes"] = max(
                int(peak), self.gauges.get("hbm_peak_bytes", 0))

    def summary(self) -> dict:
        """Per span name: ``n``, ``p50_ms``, ``p95_ms`` and ``total_ms``;
        then the counters and the gauges."""
        by_name: dict[str, list[int]] = {}
        for s in self.spans:
            by_name.setdefault(s.name, []).append(s.end_ns - s.start_ns)
        spans = {}
        for name, durs in by_name.items():
            ms = np.asarray(durs, np.float64) / 1e6
            spans[name] = {"n": int(ms.size),
                           "p50_ms": float(np.percentile(ms, 50)),
                           "p95_ms": float(np.percentile(ms, 95)),
                           "total_ms": float(ms.sum())}
        return {"spans": spans, "counters": dict(self.counters),
                "gauges": dict(self.gauges)}


NULL = Tracer()  # the shared disabled tracer
