"""In-process spans and counters of the simulation engine, always on.

``span(name)`` records one interval of host work: its name, an id, the id
of the span it opened inside, the id of the federation it belongs to, and
its start and end in nanoseconds of ``time.time_ns()`` (CLOCK_REALTIME, the
clock ``jax.profiler`` stamps host events with). Each span is also opened as
a ``jax.profiler.TraceAnnotation`` of the same name, so a profile viewed in
Perfetto shows the engine's spans beside the device's operations.

A span opened with ``federation=True`` starts a federation: it and every
span opened inside it carry its id as their ``federation``.

``count(name, n)`` adds to a named counter. Each span also keeps the
counts added while it was open (``Span.counts``), so a counter can be read
per federation.

Spans are kept in a bounded deque (``MAX_SPANS``, oldest dropped first), so
a sweep that runs for hours holds a fixed amount of memory. ``spans()`` and
``counters()`` return snapshots; ``reset()`` clears both.
"""
from __future__ import annotations

import collections
import itertools
import threading
import time

import jax

MAX_SPANS = 65_536

_spans: collections.deque = collections.deque(maxlen=MAX_SPANS)
_counters: dict[str, int] = {}
_lock = threading.Lock()
_ids = itertools.count(1)


class _Open(threading.local):
    """The spans this thread has open, innermost last."""

    def __init__(self):
        self.stack: list[Span] = []


_open = _Open()


class Span:
    """One span: a context manager while open, the record once closed
    (``spans()`` returns the closed ones). ``federation`` is the id of the
    federation it belongs to, ``counts`` the counts added while it was
    open."""

    __slots__ = ("name", "id", "parent", "federation", "start_ns", "end_ns",
                 "counts", "_starts_federation", "_annotation")

    def __init__(self, name: str, starts_federation: bool = False):
        self.name = name
        self._starts_federation = starts_federation
        self.end_ns = None

    @property
    def seconds(self) -> float:
        return (self.end_ns - self.start_ns) / 1e9

    def __enter__(self) -> "Span":
        stack = _open.stack
        outer = stack[-1] if stack else None
        self.id = next(_ids)
        self.parent = outer.id if outer else None
        self.federation = (self.id if self._starts_federation
                           else outer.federation if outer else None)
        self.counts = {}
        self._annotation = jax.profiler.TraceAnnotation(self.name)
        self._annotation.__enter__()
        stack.append(self)
        self.start_ns = time.time_ns()
        return self

    def __exit__(self, *exc) -> None:
        self.end_ns = time.time_ns()
        _open.stack.pop()
        self._annotation.__exit__(*exc)
        self._annotation = None
        _spans.append(self)


def span(name: str, *, federation: bool = False) -> Span:
    """A span named ``name``; ``federation=True`` starts a federation."""
    return Span(name, federation)


def count(name: str, n: int = 1) -> None:
    """Add ``n`` to counter ``name``, and to the counts of every open span
    of this thread."""
    with _lock:
        _counters[name] = _counters.get(name, 0) + n
    for s in _open.stack:
        s.counts[name] = s.counts.get(name, 0) + n


def spans() -> list[Span]:
    """The finished spans still held, oldest first."""
    return list(_spans)


def counters() -> dict[str, int]:
    """Each counter's total since the process started (or ``reset``)."""
    with _lock:
        return dict(_counters)


def reset() -> None:
    """Drop every finished span and counter."""
    _spans.clear()
    with _lock:
        _counters.clear()
