"""Spans and instants at the serving engine's layer boundaries.

Recording is on exactly while a ``jax.profiler`` session records
(``jax.profiler.trace``, ``start_trace``, or a capture against
``start_server``); there is no other switch.  Off, :func:`span` returns
one shared no-op context: a call costs one ``is_enabled()`` check.  On,
a span does two things:

* it enters ``jax.profiler.TraceAnnotation(name, **args)``, so the span
  sits on the profiler's host plane, on the device trace's clock, under
  its bare name with ``args`` as the event's stats;
* it appends a :class:`Span` to an in-memory recorder, timed with
  ``time.perf_counter()``, its parent taken from a stack (the engine is
  single-threaded).  :func:`spans` reads the recorder back.

Nothing is written to a file: the profiler's ``.xplane.pb`` is the
operator's record, the recorder is what a benchmark reads once its run
has ended.  The profiler session is process-wide, and so is the
recorder.  A span that finds a session recording after one that found
none empties the recorder first, so a server that steps between
captures holds the spans of the last capture only.  Call sites build
costly args only after checking :func:`on`.
"""
from __future__ import annotations

import collections
import itertools
import math
import time
from typing import Deque, List, NamedTuple, Optional

from jax.profiler import TraceAnnotation

#: the most spans the recorder keeps; a longer capture drops the oldest
MAX_SPANS = 1 << 20

#: True while a profiler session records
on = TraceAnnotation.is_enabled


class Span(NamedTuple):
    id: int
    parent: Optional[int]
    name: str
    t0: float
    t1: float
    args: dict


_recorded: Deque[Span] = collections.deque(maxlen=MAX_SPANS)
_open: List[int] = []
_ids = itertools.count(1)
#: whether the last span found a session recording
_live = False


class _Off:
    """The context :func:`span` returns while no session records."""

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **args) -> None:
        pass


_OFF = _Off()


class _On:
    __slots__ = ("name", "args", "id", "parent", "t0", "_ann")

    def __init__(self, name: str, args: dict):
        self.name, self.args = name, args

    def __enter__(self):
        self.id = next(_ids)
        self.parent = _open[-1] if _open else None
        _open.append(self.id)
        self._ann = TraceAnnotation(self.name, **self.args)
        self._ann.__enter__()
        self.t0 = time.perf_counter()
        return self

    def set(self, **args) -> None:
        """Add args known only once the span's work is under way."""
        self.args.update(args)
        self._ann.set_metadata(**args)

    def __exit__(self, *exc):
        t1 = time.perf_counter()
        self._ann.__exit__(*exc)
        _open.pop()
        _recorded.append(Span(self.id, self.parent, self.name, self.t0, t1,
                              self.args))
        return False


def span(name: str, **args):
    """Context manager timing the block it wraps as ``name``."""
    global _live
    if not on():
        _live = False
        return _OFF
    if not _live:                       # a new session: forget the last
        _live = True
        _recorded.clear()
    return _On(name, args)


def event(name: str, **args) -> None:
    """An instant: a span that starts and ends now."""
    with span(name, **args):
        pass


def spans(lo: float = -math.inf, hi: float = math.inf) -> List[Span]:
    """Recorded spans that began at ``lo`` or later and ended by ``hi``
    (``perf_counter`` seconds), in the order they ended."""
    return [s for s in _recorded if s.t0 >= lo and s.t1 <= hi]


def clear() -> None:
    _recorded.clear()
