"""Engine spans: one recorder for the profiler and an in-memory log.

``span(name, **attrs)`` times a block of host code twice over:

* it enters a :class:`jax.profiler.TraceAnnotation` of the bare ``name``
  (the attributes ride along as event stats), so whenever a trace is
  recording the span lands on the profiler's host plane, on the same
  clock as the device's XLA operations;
* it appends a :class:`Span` to the process's in-memory log, timed by
  ``time.perf_counter()``, whether or not a trace is recording.

Spans nest by a stack: a span's parent is the one open when it starts.
The log is the process's, as the profiler's host plane is: it outlives
an engine, so a window can be read after the engine is freed.  It is a
``deque`` of ``LOG_SIZE`` records, so the newest spans are kept and
memory stays bounded.  Attributes may be added to a span while
it is open (``with span(...) as s: s.attrs["k"] = v``); the log keeps
them, the profiler event keeps those given at entry.
"""

from __future__ import annotations

import collections
import itertools
import time
from typing import Any, NamedTuple

from jax.profiler import TraceAnnotation

LOG_SIZE = 1 << 15


class Span(NamedTuple):
    id: int
    parent: int  # 0 for a root span
    name: str
    t0: float  # time.perf_counter() seconds
    t1: float
    attrs: dict[str, Any]


_log: collections.deque[Span] = collections.deque(maxlen=LOG_SIZE)
_stack: list[int] = []
_ids = itertools.count(1)


class span:
    """Context manager: one span of host code (see the module doc)."""

    __slots__ = ("name", "attrs", "_id", "_parent", "_t0", "_ann")

    def __init__(self, name: str, **attrs: Any):
        self.name = name
        self.attrs = attrs

    def __enter__(self) -> span:
        self._id = next(_ids)
        self._parent = _stack[-1] if _stack else 0
        _stack.append(self._id)
        self._ann = TraceAnnotation(self.name, **self.attrs)
        self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        t1 = time.perf_counter()
        self._ann.__exit__(*exc)
        _stack.pop()
        _log.append(Span(self._id, self._parent, self.name, self._t0, t1,
                         self.attrs))


def spans(t0: float | None = None, t1: float | None = None) -> list[Span]:
    """The logged spans that start at or after ``t0`` and end at or
    before ``t1`` (either bound may be left open), in start order."""
    lo = float("-inf") if t0 is None else t0
    hi = float("inf") if t1 is None else t1
    return sorted((s for s in _log if s.t0 >= lo and s.t1 <= hi),
                  key=lambda s: s.id)


def totals(log: list[Span]) -> dict[str, tuple[int, float, float]]:
    """Per span name: (count, total seconds, longest seconds)."""
    out: dict[str, tuple[int, float, float]] = {}
    for s in log:
        n, tot, top = out.get(s.name, (0, 0.0, 0.0))
        d = s.t1 - s.t0
        out[s.name] = (n + 1, tot + d, max(top, d))
    return out
