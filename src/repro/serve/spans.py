"""The operator's record of the serving loop: named spans on the host clock.

A ``Recorder`` keeps the newest spans of one engine in a bounded ring,
each as ``(name, t0, t1, parent, ident)``: seconds on
``time.perf_counter`` (the clock a benchmark driving the engine reads
around its calls), the name of the enclosing span (``None`` at the top),
and an identifier (the step index for step spans, the request id for
request spans).  Every span opened with ``Recorder.span`` is also a
``jax.profiler.TraceAnnotation``, so a profiler trace shows it on the
same clock as the device's operations, nested as it was opened.

The record is always on; a span costs a few microseconds.  This module
is the engine's only reader of the clock.
"""

from __future__ import annotations

import collections
import time

from jax.profiler import TraceAnnotation

__all__ = ["ENGINE_SPANS", "RING", "Recorder"]

#: Spans of one ``DecodeEngine.run`` call.  ``engine.run`` holds the
#: others; ``engine.splice`` and ``engine.plan`` lie inside
#: ``engine.refill``.
ENGINE_SPANS = ("engine.run", "engine.refill", "engine.splice", "engine.plan",
                "engine.upload", "engine.dispatch", "engine.sample",
                "engine.sync", "engine.lanes")
#: Ring size: 120 s at 30 steps/s holds at most 32,400 step spans (nine a
#: step), and a run's requests add one ``request.queued`` each.
RING = 1 << 16


class _Span:
    """One open span; ``t0`` and ``t1`` are set on entry and exit."""

    __slots__ = ("_rec", "_ann", "name", "ident", "t0", "t1")

    def __init__(self, rec: "Recorder", name: str, ident: int):
        self._rec = rec
        self._ann = TraceAnnotation(name)
        self.name = name
        self.ident = ident

    def __enter__(self) -> "_Span":
        self._ann.__enter__()
        self._rec._open.append(self.name)
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        self.t1 = time.perf_counter()
        rec = self._rec
        rec._open.pop()
        rec.ring.append((self.name, self.t0, self.t1,
                         rec._open[-1] if rec._open else None, self.ident))
        self._ann.__exit__(*exc)


class Recorder:
    """Spans and counters of one engine; the ring keeps the newest
    ``maxlen`` spans, in the order they ended."""

    def __init__(self, maxlen: int = RING):
        self.ring: collections.deque = collections.deque(maxlen=maxlen)
        self.counts: collections.Counter = collections.Counter()
        self._open: list[str] = []     # names of the open spans, innermost last

    def span(self, name: str, ident: int) -> _Span:
        """A context manager recording the time it encloses as ``name``."""
        return _Span(self, name, ident)

    def record(self, name: str, t0: float, t1: float, ident: int) -> None:
        """An interval that began before the current call (``t0`` from
        ``now()``); it has no parent."""
        self.ring.append((name, t0, t1, None, ident))

    def now(self) -> float:
        return time.perf_counter()
