"""Spans: where the program's host time goes, recorded only while a JAX
profiler session runs in the process.

``with span("layer/what", **attrs) as sp:`` marks one block of host
work; ``sp.set(**attrs)`` adds attributes known only at its end.  An
operator turns spans on the way a JAX process is already profiled
(``jax.profiler.trace`` / ``start_trace``): :func:`enabled` is
``jax.profiler.TraceAnnotation.is_enabled()``.  There is no other
switch.  With no profiler session, :func:`span` returns one shared
no-op object: no clock read, no allocation.

While on, every span goes to two places:

* the profiler's own trace, as a ``TraceAnnotation`` named ``name``
  whose metadata carries the Python thread (the trace names every Python
  thread alike) and the decision, on the device trace's clock;
* an in-memory buffer of the newest ``CAPACITY`` :class:`Record` s
  (:func:`records`; :func:`dropped` counts what the bound discarded).

A record's ``parent`` is the innermost span open on the same thread, or
the span handed in as ``parent=`` (from :func:`current` on the thread
that started the work).  ``decision`` is given at the root span and
inherited by every span under it, across threads too.  Times are
``time.perf_counter_ns()`` and ``cpu_ns`` is the thread's CPU time
(``time.thread_time_ns()``) inside the span.
"""

from __future__ import annotations

import collections
import itertools
import sys
import threading
import time
from dataclasses import dataclass

__all__ = ["CAPACITY", "Record", "span", "current", "enabled", "records",
           "dropped", "clear"]

CAPACITY = 1 << 20


def enabled() -> bool:
    """True exactly while a JAX profiler session runs in this process
    (no session can run before ``jax`` is imported)."""
    jax = sys.modules.get("jax")
    return jax is not None and jax.profiler.TraceAnnotation.is_enabled()


@dataclass(frozen=True, slots=True)
class Record:
    id: int
    name: str
    thread: str
    t0_ns: int
    t1_ns: int
    cpu_ns: int
    parent: int | None
    decision: int | None
    attrs: dict


class _Buffer:
    def __init__(self, capacity: int) -> None:
        self.lock = threading.Lock()
        self.items: collections.deque[Record] = collections.deque(maxlen=capacity)
        self.dropped = 0

    def append(self, rec: Record) -> None:
        with self.lock:
            if len(self.items) == self.items.maxlen:
                self.dropped += 1
            self.items.append(rec)


_buffer = _Buffer(CAPACITY)
_ids = itertools.count()
_local = threading.local()


def _stack() -> list:
    stack = getattr(_local, "stack", None)
    if stack is None:
        stack = _local.stack = []
    return stack


class _Off:
    """The span handle while no profiler session runs."""

    __slots__ = ()

    def __enter__(self) -> "_Off":
        return self

    def __exit__(self, *exc) -> None:
        return None

    def set(self, **attrs) -> None:
        return None


_OFF = _Off()


class _Span:
    __slots__ = ("id", "name", "parent", "decision", "attrs", "_thread",
                 "_ann", "_t0", "_c0")

    def __init__(self, name: str, parent: "_Span | None", decision: int | None,
                 attrs: dict) -> None:
        self.name = name
        self.parent = parent
        self.decision = decision
        self.attrs = attrs

    def set(self, **attrs) -> None:
        self.attrs.update(attrs)

    def __enter__(self) -> "_Span":
        import jax

        stack = _stack()
        if self.parent is None and stack:
            self.parent = stack[-1]
        if self.decision is None and self.parent is not None:
            self.decision = self.parent.decision
        self.id = next(_ids)
        self._thread = threading.current_thread().name
        meta = {"thread": self._thread}
        if self.decision is not None:
            meta["decision"] = self.decision
        self._ann = jax.profiler.TraceAnnotation(self.name, **meta)
        self._ann.__enter__()
        stack.append(self)
        # the CPU clock is read inside the wall interval: cpu_ns <= wall
        self._t0 = time.perf_counter_ns()
        self._c0 = time.thread_time_ns()
        return self

    def __exit__(self, *exc) -> None:
        cpu = time.thread_time_ns() - self._c0
        t1 = time.perf_counter_ns()
        _stack().pop()
        self._ann.__exit__(*exc)
        _buffer.append(Record(
            self.id, self.name, self._thread, self._t0, t1, cpu,
            None if self.parent is None else self.parent.id, self.decision,
            self.attrs))


def span(name: str, *, parent: _Span | None = None, decision: int | None = None,
         **attrs):
    """A context manager over one block of host work, yielding a handle
    with ``set(**attrs)``.  ``parent``: the span that caused this one
    when it runs on another thread; ``decision``: the id this span and
    every span under it carry (inherited from the parent when omitted)."""
    if not enabled():
        return _OFF
    return _Span(name, parent, decision, attrs)


def current() -> _Span | None:
    """The innermost span open on this thread, to hand to work started on
    another thread as its ``parent``."""
    stack = getattr(_local, "stack", None)
    return stack[-1] if stack else None


def records() -> list[Record]:
    """A copy of the buffered records, oldest first."""
    with _buffer.lock:
        return list(_buffer.items)


def dropped() -> int:
    """Records the buffer's bound discarded, oldest first, since the last
    :func:`clear`."""
    return _buffer.dropped


def clear() -> None:
    with _buffer.lock:
        _buffer.items.clear()
        _buffer.dropped = 0
