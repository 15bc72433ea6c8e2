"""Spans inside the serving program, on the device trace's clock.

A :class:`SpanLog` belongs to one engine (``engine.spans``) and is disabled
by default.  :meth:`SpanLog.enable` turns it on: each :meth:`SpanLog.span`
then opens a ``jax.profiler.TraceAnnotation`` named ``serve.<name>``
(carrying its ``id``, its ``parent`` and its attributes), so a profiler
trace places the span on the same clock as the device's operations, and
appends a :class:`Span` to a bounded in-memory deque with ``t0``/``t1``
from ``time.perf_counter()``.  Spans are read after the run with
:meth:`SpanLog.records`; nothing is exported while serving.

Disabled, :meth:`SpanLog.span` returns one shared no-op context: no
annotation, no clock read, no allocation.  :meth:`SpanLog.timed` is for
the model calls whose wall time feeds :class:`EngineMetrics` timers: it
always takes one pair of clock reads (``.seconds`` after the block) and
records a span only when the log is on, so the timer and the span share
their clock reads.

Parents come from a stack kept by the log: one engine is driven by one
thread, so no locking is needed.
"""
from __future__ import annotations

import collections
import itertools
import time
from typing import Deque, List, NamedTuple, Optional

import jax


class Span(NamedTuple):
    """One closed span: ``parent_id`` is 0 for a span opened at the top."""
    name: str
    id: int
    parent_id: int
    t0: float
    t1: float
    attrs: dict


class _NoSpan:
    """The disabled log's span: does nothing."""
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **attrs) -> None:
        pass


_NO_SPAN = _NoSpan()


class _Clock:
    """A timed span of the disabled log: the clock reads and nothing else."""
    __slots__ = ("t0", "seconds")

    def __enter__(self):
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self.seconds = time.perf_counter() - self.t0
        return False

    def set(self, **attrs) -> None:
        pass


class _LiveSpan:
    __slots__ = ("log", "name", "attrs", "id", "parent", "t0", "seconds",
                 "ann")

    def __init__(self, log: "SpanLog", name: str, attrs: dict):
        self.log, self.name, self.attrs = log, name, attrs

    def __enter__(self):
        log = self.log
        self.id = next(log._ids)
        self.parent = log._stack[-1] if log._stack else 0
        log._stack.append(self.id)
        self.ann = jax.profiler.TraceAnnotation(
            "serve." + self.name, id=self.id, parent=self.parent, **self.attrs)
        self.ann.__enter__()
        self.t0 = time.perf_counter()
        return self

    def set(self, **attrs) -> None:
        """Attributes known only inside the span (e.g. how many requests
        an admission placed)."""
        self.attrs.update(attrs)
        self.ann.set_metadata(**attrs)

    def __exit__(self, *exc):
        t1 = time.perf_counter()
        self.seconds = t1 - self.t0
        self.ann.__exit__(None, None, None)
        log = self.log
        log._stack.pop()
        log._records.append(Span(self.name, self.id, self.parent, self.t0,
                                 t1, self.attrs))
        return False


class SpanLog:
    def __init__(self):
        self.enabled = False
        self._records: Deque[Span] = collections.deque()
        self._stack: List[int] = []
        self._ids = itertools.count(1)

    def enable(self, capacity: int = 100_000) -> None:
        """Record from now on, keeping the newest ``capacity`` spans."""
        self._records = collections.deque(maxlen=capacity)
        self._stack = []
        self.enabled = True

    def span(self, name: str, **attrs):
        if not self.enabled:
            return _NO_SPAN
        return _LiveSpan(self, name, attrs)

    def timed(self, name: str, **attrs):
        """A span whose wall time is read whether or not the log is on."""
        if not self.enabled:
            return _Clock()
        return _LiveSpan(self, name, attrs)

    def records(self, name: Optional[str] = None) -> List[Span]:
        """The kept spans in the order they closed (children before their
        parent), optionally only those called ``name``."""
        return [s for s in self._records if name is None or s.name == name]
