"""The program's own spans: named host intervals at layer boundaries.

    with span("fwi.dispatch", session=3, steps=8) as s:
        ...
    s.t1 - s.t0        # seconds on the host clock

Each span opens a ``jax.profiler.TraceAnnotation`` under its name, so
that a profiler trace shows it on the device trace's clock, and on exit
appends itself to ``RECORD``: its id, the id of the span that was open
around it on the same thread (``parent``), its name, its start and end
on ``time.perf_counter`` and ``attrs``, the counts measured at the same
boundary (byte counts come from shapes, never from data).  Recording is
always on and costs two clock reads, the annotation and an append: it
reads nothing back from the device and waits for nothing.  Readers take
the spans of an interval with ``recorded``.
"""
from __future__ import annotations

import collections
import dataclasses
import itertools
import threading
import time

from jax.profiler import TraceAnnotation

#: the most spans kept, oldest dropped first.  The smallest grid the FWI
#: engine runs (the paper's 600², a scan block every ~1.7 ms on one TPU
#: v5e) records three spans a block, about 1,800 a second: 2**16 hold
#: 35 s of that, more than a 25 s measured window.
MAX_SPANS = 2 ** 16

RECORD: collections.deque[Span] = collections.deque(maxlen=MAX_SPANS)

_ids = itertools.count(1)
_open = threading.local()


def _stack() -> list[Span]:
    try:
        return _open.stack
    except AttributeError:
        _open.stack = []
        return _open.stack


@dataclasses.dataclass(slots=True)
class Span:
    """One span; a context manager that records it on exit, whether or
    not an exception passes through."""
    name: str
    attrs: dict
    id: int = 0
    parent: int | None = None
    t0: float = 0.0
    t1: float = 0.0
    _annotation: TraceAnnotation | None = dataclasses.field(
        default=None, repr=False, compare=False)

    def __enter__(self) -> Span:
        stack = _stack()
        self.parent = stack[-1].id if stack else None
        self.id = next(_ids)
        stack.append(self)
        self._annotation = TraceAnnotation(self.name)
        self._annotation.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        self.t1 = time.perf_counter()
        self._annotation.__exit__(*exc)
        self._annotation = None
        _stack().pop()
        RECORD.append(self)


def span(name: str, **attrs) -> Span:
    """A span called ``name`` carrying ``attrs``; use it in ``with``."""
    return Span(name, attrs)


def recorded(t0: float, t1: float) -> list[Span]:
    """The recorded spans that lie within ``[t0, t1]`` on the host
    clock, in the order they ended."""
    return [s for s in list(RECORD) if t0 <= s.t0 and s.t1 <= t1]
