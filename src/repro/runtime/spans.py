"""Program spans, on the host, always on.

A span names a stretch of host work: the ST executor's steps inside one
``synchronize``, the serving engine's step, the decode router's
dispatch. Each span does two things:

  * it enters ``jax.profiler.TraceAnnotation(name)``, so that a profiler
    capture holds it on the same clock as the device's operations, and
    an idle gap of the device can be put down to the span open at the
    time;
  * it appends ``(start_ns, end_ns, name, parent, key)`` to a bounded
    in-memory ring (``time.monotonic_ns()``, the clock of
    ``time.monotonic()``), so that a process can read its own spans with
    no profiler running.

Spans nest through a per-thread stack, so each records the span that
was open around it. ``key`` carries what the span is about: a request
id, a step number. :meth:`Recorder.record` adds a finished span from
two timestamps taken elsewhere (a request's wait in a queue, which
opens in one call and closes in another); it nests in nothing and
reaches the ring only.

Spans leave the process only through the profiler and
:meth:`Recorder.snapshot`. Nothing here runs inside jitted code.

The module-level functions (:func:`span`, :func:`record`,
:func:`snapshot`) use one recorder of :data:`CAPACITY` spans shared by
the process.
"""
from __future__ import annotations

import itertools
import threading
import time
from collections import deque
from typing import Any, List, NamedTuple, Optional, Tuple

from jax.profiler import TraceAnnotation

CAPACITY = 1 << 17

# a row of the ring: [start_ns, end_ns, name, parent serial, key, serial];
# end_ns is None while the span is open
_START, _END, _NAME, _PARENT, _KEY, _SERIAL = range(6)

Span = Tuple[int, Optional[int], str, Optional[int], Any]


class Snapshot(NamedTuple):
    """The ring as it stood: ``spans`` in the order they were recorded
    (a :meth:`Recorder.span` when it opened, a :meth:`Recorder.record`
    when it was added), each ``(start_ns, end_ns, name, parent_index,
    key)`` with ``end_ns`` None for a span still open and
    ``parent_index`` the index in ``spans`` of the span it nests in
    (None for a top-level span, or where the ring has dropped the
    parent); and the number of spans the ring dropped, oldest first, to
    stay within its capacity."""
    spans: List[Span]
    dropped: int

    def self_ns(self, i: int) -> int:
        """Span ``i``'s own time: its duration less its children's."""
        s = self.spans[i]
        return (s[1] - s[0]) - sum(c[1] - c[0] for c in self.spans[i + 1:]
                                   if c[3] == i)


class _Open:
    """The context manager :meth:`Recorder.span` returns."""
    __slots__ = ("_rec", "_name", "_key", "_ann", "_row", "_stack")

    def __init__(self, rec, name, key):
        self._rec, self._name, self._key = rec, name, key

    def __enter__(self):
        rec = self._rec
        self._ann = ann = TraceAnnotation(self._name)
        ann.__enter__()
        self._stack = stack = rec._stack()
        row = [time.monotonic_ns(), None, self._name,
               stack[-1][_SERIAL] if stack else None, self._key,
               next(rec._serial)]
        rec._ring.append(row)
        stack.append(row)
        self._row = row
        return self

    def __exit__(self, *exc):
        self._row[_END] = time.monotonic_ns()
        self._stack.pop()
        self._ann.__exit__(*exc)
        return False


class Recorder:
    """A ring of at most ``capacity`` spans."""

    def __init__(self, capacity: int = CAPACITY):
        self._ring: deque = deque(maxlen=capacity)
        self._serial = itertools.count()
        self._local = threading.local()

    def _stack(self) -> list:
        try:
            return self._local.stack
        except AttributeError:
            stack = self._local.stack = []
            return stack

    def span(self, name: str, key: Any = None) -> _Open:
        """A context manager that records ``name`` around its body,
        nested in the span open around it on this thread."""
        return _Open(self, name, key)

    def record(self, name: str, start_ns: int, end_ns: int,
               key: Any = None):
        """Add a finished span that ran from ``start_ns`` to ``end_ns``
        (``time.monotonic_ns()``). It has no parent and holds no other
        span."""
        self._ring.append([start_ns, end_ns, name, None, key,
                           next(self._serial)])

    def snapshot(self) -> Snapshot:
        rows = list(self._ring)
        index = {r[_SERIAL]: i for i, r in enumerate(rows)}
        spans = [(r[_START], r[_END], r[_NAME], index.get(r[_PARENT]),
                  r[_KEY]) for r in rows]
        dropped = rows[-1][_SERIAL] + 1 - len(rows) if rows else 0
        return Snapshot(spans, dropped)


_recorder = Recorder()
span = _recorder.span
record = _recorder.record
snapshot = _recorder.snapshot
