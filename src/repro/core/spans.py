"""Spans and counters at the program's set-up boundaries.

``with span("radon.compile", kind="forward"):`` opens a
:class:`jax.profiler.TraceAnnotation` of the same name, so a profiled
process shows the span on the same clock as the device's events, and
records the span in memory:

* per-name aggregates: ``count``, ``total_s`` and ``self_s`` (the
  duration less what the span's child spans cover);
* a ring of the last :data:`RING` records: name, start and end
  (``time.perf_counter_ns``), the enclosing span's name as ``parent``,
  the attributes, and the counts credited to the span.

``count(name, n)`` adds ``n`` to a process counter and credits it to the
innermost open span of the calling thread; a span's credits roll up
into its parent when it closes, as its duration does into the parent's
``total_s``.  JAX's persistent compile-cache hits and misses and its
backend compiles arrive through :mod:`jax.monitoring` and are counted
the same way (``compile_cache_hits``, ``compile_cache_misses``,
``backend_compiles``), so a miss is put down to the executable whose
build caused it.

Spans sit at compile granularity -- plan builds, executable builds,
executable restores -- and never inside a jitted function or a kernel
body, where they would time tracing, nor on a per-call path.  There is
no switch: a span costs a few microseconds, a few dozen times a process.
:func:`snapshot` returns everything; :mod:`repro.radon.healthz` reports
the aggregates, the counters and the executables built or restored
(from the ring), and the service and router healthz carry that report.
"""
from __future__ import annotations

import collections
import contextlib
import threading
import time
from typing import Optional

import jax

__all__ = ["RING", "Recorder", "span", "count", "snapshot"]

#: records kept per recorder (the newest)
RING = 256

#: jax.monitoring events counted, by the counter name they feed
_EVENTS = {"/jax/compilation_cache/cache_hits": "compile_cache_hits",
           "/jax/compilation_cache/cache_misses": "compile_cache_misses"}
_DURATIONS = {"/jax/core/compile/backend_compile_duration":
              "backend_compiles"}


class _Frame:
    __slots__ = ("name", "parent", "attrs", "start", "child_ns", "credits")

    def __init__(self, name: str, parent: Optional[str], attrs: dict):
        self.name = name
        self.parent = parent
        self.attrs = attrs
        self.start = 0
        self.child_ns = 0
        self.credits: dict = {}


class Recorder:
    """Span aggregates, counters and the record ring of one process (the
    module-level functions use one shared recorder)."""

    def __init__(self):
        self._lock = threading.Lock()
        self._local = threading.local()
        self._spans: dict = {}
        self._counters: dict = {}
        self._records = collections.deque(maxlen=RING)

    def _stack(self) -> list:
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._local.stack = []
        return stack

    @contextlib.contextmanager
    def span(self, name: str, **attrs):
        stack = self._stack()
        frame = _Frame(name, stack[-1].name if stack else None, attrs)
        stack.append(frame)
        frame.start = time.perf_counter_ns()
        try:
            with jax.profiler.TraceAnnotation(name, **attrs):
                yield
        finally:
            end = time.perf_counter_ns()
            stack.pop()
            self._close(frame, end, stack[-1] if stack else None)

    def _close(self, frame: _Frame, end: int,
               parent: Optional[_Frame]) -> None:
        dur = end - frame.start
        with self._lock:
            agg = self._spans.setdefault(
                frame.name, {"count": 0, "total_s": 0.0, "self_s": 0.0})
            agg["count"] += 1
            agg["total_s"] += dur / 1e9
            agg["self_s"] += (dur - frame.child_ns) / 1e9
            for key, n in frame.credits.items():
                agg[key] = agg.get(key, 0) + n
            self._records.append({
                "name": frame.name, "start_ns": frame.start, "end_ns": end,
                "parent": frame.parent, "attrs": frame.attrs,
                "credits": dict(frame.credits)})
        if parent is not None:      # same thread: no lock needed
            parent.child_ns += dur
            for key, n in frame.credits.items():
                parent.credits[key] = parent.credits.get(key, 0) + n

    def count(self, name: str, n=1) -> None:
        with self._lock:
            self._counters[name] = self._counters.get(name, 0) + n
        stack = self._stack()
        if stack:
            credits = stack[-1].credits
            credits[name] = credits.get(name, 0) + n

    def snapshot(self) -> dict:
        """``spans`` (aggregates by name), ``counters`` and ``records``
        (oldest first), as copies."""
        with self._lock:
            return {"spans": {k: dict(v) for k, v in self._spans.items()},
                    "counters": dict(self._counters),
                    "records": list(self._records)}


_RECORDER = Recorder()
span = _RECORDER.span
count = _RECORDER.count
snapshot = _RECORDER.snapshot


def _on_event(event: str, **_) -> None:
    name = _EVENTS.get(event)
    if name is not None:
        count(name)


def _on_duration(event: str, duration: float, **_) -> None:
    name = _DURATIONS.get(event)
    if name is not None:
        count(name)


jax.monitoring.register_event_listener(_on_event)
jax.monitoring.register_event_duration_secs_listener(_on_duration)
