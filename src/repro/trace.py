"""Spans and counters of the program's host work.

:class:`span` marks one interval of host Python by name.  It always
enters a :class:`jax.profiler.TraceAnnotation`, so inside a profiled
window the span lands on the trace's host plane, on the same clock as
the device operations.  While a :func:`recording` is active it also
appends a :class:`Span` (name, id, parent id, start and end on
``time.perf_counter_ns``, attributes) to that record; the parent is the
innermost span of the same record open on the same thread.
:func:`count` adds to the active record's counters, and so do JAX's
persistent compilation cache events (``jax.cache_hits``,
``jax.cache_misses``).

Recording is off by default: a span then costs its TraceAnnotation and
two clock readings.  Spans go in host Python only, never inside a
traced or jitted function's computation, so no compiled executable
changes with them.  ``docs/ARCHITECTURE.md`` ("Tracing") lists the
spans and counters the program emits.
"""
from __future__ import annotations

import itertools
import threading
import time
from collections import Counter
from contextlib import contextmanager
from dataclasses import dataclass, field
from typing import Optional

import jax
from jax.profiler import TraceAnnotation

#: JAX's persistent compilation cache events and the counters they feed.
JAX_EVENTS = {"/jax/compilation_cache/cache_hits": "jax.cache_hits",
              "/jax/compilation_cache/cache_misses": "jax.cache_misses"}


@dataclass(frozen=True)
class Span:
    """One recorded interval; times on ``time.perf_counter_ns``."""

    name: str
    id: int
    parent: Optional[int]
    start_ns: int
    end_ns: int
    attrs: dict = field(default_factory=dict)

    @property
    def seconds(self) -> float:
        return (self.end_ns - self.start_ns) / 1e9


class Record:
    """The spans and counters recorded while one :func:`recording` was
    active."""

    def __init__(self):
        self.spans: list = []
        self.counters: Counter = Counter()
        self._lock = threading.Lock()

    def add(self, name: str, start_ns: int, end_ns: int, **attrs) -> Span:
        """Record an interval measured outside a :class:`span`, such as
        one that began before the record did; it has no parent."""
        s = Span(name, next(_ids), None, start_ns, end_ns, attrs)
        self.spans.append(s)
        return s

    def count(self, name: str, n: int = 1) -> None:
        with self._lock:
            self.counters[name] += n

    def named(self, name: str) -> list:
        return [s for s in self.spans if s.name == name]

    def total_s(self, name: str) -> float:
        """Seconds in spans called ``name``, a span nested in another of
        the same name counted once."""
        spans = self.named(name)
        ids = {s.id for s in spans}
        return sum(s.seconds for s in spans if s.parent not in ids)

    def roots(self) -> list:
        """The spans with no parent in this record, in start order."""
        return sorted((s for s in self.spans if s.parent is None),
                      key=lambda s: s.start_ns)

    def children(self, parent: Span) -> list:
        return sorted((s for s in self.spans if s.parent == parent.id),
                      key=lambda s: s.start_ns)

    def self_s(self, parent: Span) -> float:
        """Seconds of ``parent`` that none of its children covers."""
        return parent.seconds - sum(c.seconds for c in self.children(parent))


_ids = itertools.count(1)
_active: Optional[Record] = None
_local = threading.local()
_listening = False


def _stack() -> list:
    """This thread's open recorded spans, as ``(record, id)``."""
    try:
        return _local.stack
    except AttributeError:
        _local.stack = []
        return _local.stack


class span:
    """``with span(name, **attrs) as s:`` marks the block as ``name``.
    ``s.start_ns`` and ``s.end_ns`` hold its clock readings whether or
    not a record is active."""

    __slots__ = ("name", "attrs", "start_ns", "end_ns", "_annotation",
                 "_record", "_id", "_parent")

    def __init__(self, name: str, **attrs):
        self.name = name
        self.attrs = attrs

    def __enter__(self) -> "span":
        rec = self._record = _active
        if rec is not None:
            stack = _stack()
            top = stack[-1] if stack else None
            self._parent = top[1] if top is not None and top[0] is rec else None
            self._id = next(_ids)
            stack.append((rec, self._id))
        self._annotation = TraceAnnotation(self.name)
        self._annotation.__enter__()
        self.start_ns = time.perf_counter_ns()
        return self

    def __exit__(self, *exc) -> None:
        self._annotation.__exit__(*exc)
        self.end_ns = time.perf_counter_ns()
        rec = self._record
        if rec is not None:
            _stack().pop()
            rec.spans.append(Span(self.name, self._id, self._parent,
                                  self.start_ns, self.end_ns, self.attrs))


def count(name: str, n: int = 1) -> None:
    """Add ``n`` to counter ``name`` of the active record, if any."""
    rec = _active
    if rec is not None:
        rec.count(name, n)


def _on_jax_event(event: str, **_kw) -> None:
    name = JAX_EVENTS.get(event)
    if name is not None:
        count(name)


@contextmanager
def recording():
    """Record every span and counter of every thread until the block
    ends; yields the :class:`Record`.  A recording inside another
    records into its own record and restores the outer one after."""
    global _active, _listening
    if not _listening:
        jax.monitoring.register_event_listener(_on_jax_event)
        _listening = True
    outer, _active = _active, Record()
    try:
        yield _active
    finally:
        _active = outer
