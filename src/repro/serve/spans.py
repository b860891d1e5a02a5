"""Engine spans: named, nested host intervals, always recorded.

A :class:`SpanRecorder` times what the serving engine does on the host —
a whole ``step``, its admission and each prefill dispatch, the decode tick,
every wait for the device (``engine.sync``), retirement, ROM checks, and at
construction the AOT warm-up of each program — on the engine's own clock.

``with rec.span(name, **attrs) as s:`` records one finished :class:`Span`
``(name, t0, t1, id, parent, attrs)`` when the block exits; ``parent`` is
the span open around it. The same block is a
``jax.profiler.TraceAnnotation`` of the same name and attrs, so while a
profile is being taken the span also lands on the host plane of the
profiler's trace, on the device trace's clock. With no profile running a
span costs a few microseconds.

Finished spans go to a ring of fixed capacity (the oldest are dropped), so
memory does not grow with the run's length; :meth:`SpanRecorder.last`
keeps the newest span of each name besides, so a construction span stays
readable after the ring has turned over. Spans are opened and closed on
one thread (the engine's); counters stay in ``ServeEngine.stats``.

:func:`default` is the process's newest engine recorder (each
``ServeEngine`` installs its own at construction), for readers that hold
no reference to the engine.
"""
from __future__ import annotations

import collections
import itertools
import time
from typing import Callable, NamedTuple

import jax

CAPACITY = 4096  # finished spans kept; an engine step records about ten


class Span(NamedTuple):
    name: str
    t0: float
    t1: float
    id: int
    parent: int | None  # id of the enclosing span, None at the top
    attrs: dict

    @property
    def dur(self) -> float:
        return self.t1 - self.t0


def _annotation_value(v):
    # the profiler encodes attrs as "name#k=v,k=v#": no ',' or '#' in a value
    if isinstance(v, (tuple, list)):
        return " ".join(str(x) for x in v)
    return v


class _Open:
    """One span while its block runs; ``.span`` is the finished Span."""

    __slots__ = ("_rec", "_name", "_attrs", "_id", "_parent", "_t0", "_ann",
                 "span")

    def __init__(self, rec: "SpanRecorder", name: str, attrs: dict):
        self._rec, self._name, self._attrs = rec, name, attrs
        self.span: Span | None = None

    def __enter__(self) -> "_Open":
        rec = self._rec
        self._parent = rec._open[-1] if rec._open else None
        self._id = next(rec._ids)
        rec._open.append(self._id)
        self._ann = jax.profiler.TraceAnnotation(
            self._name, **{k: _annotation_value(v)
                           for k, v in self._attrs.items()})
        self._ann.__enter__()
        self._t0 = rec.clock()
        return self

    def __exit__(self, *exc) -> bool:
        rec = self._rec
        t1 = rec.clock()
        self._ann.__exit__(*exc)
        rec._open.pop()
        self.span = Span(self._name, self._t0, t1, self._id, self._parent,
                         self._attrs)
        rec._ring.append(self.span)
        rec._last[self._name] = self.span
        return False


class SpanRecorder:
    """Always-on span recorder: a bounded ring of finished spans."""

    def __init__(self, clock: Callable[[], float] = time.perf_counter):
        self.clock = clock
        self._ring: collections.deque[Span] = collections.deque(
            maxlen=CAPACITY)
        self._last: dict[str, Span] = {}
        self._open: list[int] = []  # ids of the open spans, innermost last
        self._ids = itertools.count(1)

    def span(self, name: str, **attrs) -> _Open:
        return _Open(self, name, attrs)

    def spans(self) -> list[Span]:
        """The finished spans still in the ring, oldest first."""
        return list(self._ring)

    def last(self, name: str) -> Span | None:
        """The newest finished span called ``name``, ring or not."""
        return self._last.get(name)


_default = SpanRecorder()


def default() -> SpanRecorder:
    """The recorder of the process's newest ``ServeEngine``."""
    return _default


def set_default(rec: SpanRecorder) -> None:
    global _default
    _default = rec
