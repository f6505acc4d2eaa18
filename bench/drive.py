"""The measured loop: traffic in, one engine step at a time, records out.

The window drives the engine through its public entry only:
``submit(req, prompt=...)`` when a request is due, and ``run(max_steps=1)``
once per step.  ``run`` syncs on the step's argmax, so the host clock
after it is a completed step.  A request's tokens are read from
``output(rid)`` growing, its admission from ``lane_requests``.

The host's phases are marked with ``jax.profiler.TraceAnnotation``
(``wait_arrival``, ``submit``, ``engine_step``, ``bookkeeping``), so a
traced run can say what the host was doing in each device idle gap.
"""

from __future__ import annotations

import dataclasses
import itertools
import math
import time
from typing import Callable, Iterable, Optional

import numpy as np
from jax.profiler import TraceAnnotation

from .traffic import Arrival


@dataclasses.dataclass
class ReqRec:
    rid: int
    due: float                # host clock
    prompt: list[int]
    out_len: int
    submit: float = math.nan
    admit: float = math.nan   # host clock after the call that put it on a lane
    admit_step: int = -1      # index of that step call; -1: never on a lane
    token_t: list[float] = dataclasses.field(default_factory=list)
    token_step: list[int] = dataclasses.field(default_factory=list)

    @property
    def finished(self) -> bool:
        return len(self.token_t) >= self.out_len


@dataclasses.dataclass
class Run:
    requests: list[ReqRec]        # in submission order
    step_t0: np.ndarray           # host clock around each step call
    step_t1: np.ndarray
    window: tuple[float, float]
    attempted: list[ReqRec]       # the requests the run answers for
    drain_end: float
    trace_span: Optional[tuple[float, float]] = None

    @property
    def seconds(self) -> float:
        return self.window[1] - self.window[0]


class Tracer:
    """Starts and stops the profiler around part of the window."""

    def __init__(self, start_at: float, stop_at: float,
                 start: Callable[[], None], stop: Callable[[], None]):
        self.start_at, self.stop_at = start_at, stop_at
        self._start, self._stop = start, stop
        self.span: Optional[tuple[float, float]] = None
        self._t0 = math.nan
        self.on = False

    def poll(self, now: float, clock) -> None:
        if self.span is None and not self.on and now >= self.start_at:
            self._start()
            self.on, self._t0 = True, clock()
        elif self.on and now >= self.stop_at:
            t1 = clock()
            self._stop()
            self.on, self.span = False, (self._t0, t1)


def drive(engine, traffic: Iterable[Arrival], *, open_loop: bool,
          seconds: float, pre_s: float, backlog: int, drain_cap_s: float,
          tracer: Optional[Tracer] = None,
          clock: Callable[[], float] = time.perf_counter,
          sleep: Callable[[float], None] = time.sleep) -> Run:
    """Serve ``traffic`` for ``pre_s`` seconds, then the window of
    ``seconds``, then drain.

    Open loop: each arrival is submitted once due; the attempted set is
    every request due inside the window, served to completion up to
    ``drain_cap_s`` after the close.  Closed backlog: arrivals are
    submitted whenever fewer than ``backlog`` requests wait, until the
    close; the attempted set is every request that finished inside the
    window.  After the close it serves on, up to ``drain_cap_s``, until
    every request that was on a lane before the close has its first
    token, so that the part of each prefill inside the window is known.
    """
    start = clock()
    ws = start + pre_s
    we = ws + seconds
    if tracer is not None:
        tracer.start_at += ws
        tracer.stop_at += ws
    source = iter(traffic)
    nxt: Optional[Arrival] = next(source, None)
    recs: list[ReqRec] = []
    live: dict[int, ReqRec] = {}        # submitted and not finished
    waiting = 0                         # submitted, not yet on a lane
    t0s: list[float] = []
    t1s: list[float] = []
    rid = itertools.count()

    def submit(a: Arrival, due: float, now: float) -> None:
        nonlocal waiting
        r = ReqRec(next(rid), due, a.prompt, a.out_len, submit=now)
        engine.submit(_request(r), prompt=a.prompt)
        recs.append(r)
        live[r.rid] = r
        waiting += 1

    def owed() -> bool:
        """Whether a request the window answers for is still unserved."""
        if open_loop:
            return any(ws <= r.due < we for r in live.values())
        return any(not r.token_t and 0 <= r.admit_step and t0s[r.admit_step] < we
                   for r in live.values())

    while True:
        now = clock()
        if tracer is not None:
            tracer.poll(now, clock)
        if now >= we and (now >= we + drain_cap_s or not owed()):
            break
        with TraceAnnotation("submit"):
            if open_loop:
                while (nxt is not None and ws + nxt.due <= now
                       and ws + nxt.due < we):
                    submit(nxt, ws + nxt.due, now)
                    nxt = next(source, None)
            elif now < we:
                while nxt is not None and waiting < backlog:
                    submit(nxt, now, now)
                    nxt = next(source, None)
        if not live:
            due = ws + nxt.due if open_loop and nxt is not None else we
            with TraceAnnotation("wait_arrival"):
                sleep(max(0.0, min(due, we) - clock()))
            continue
        with TraceAnnotation("engine_step"):
            t0 = clock()
            engine.run(max_steps=1)
            t1 = clock()
        with TraceAnnotation("bookkeeping"):
            k = len(t0s)
            t0s.append(t0)
            t1s.append(t1)
            for lane_rid in engine.lane_requests:
                r = live.get(lane_rid)
                if r is not None and r.admit_step < 0:
                    r.admit, r.admit_step = t1, k
                    waiting -= 1
            for r in list(live.values()):
                n = len(engine.output(r.rid)) - len(r.token_t)
                if n:
                    r.token_t += [t1] * n
                    r.token_step += [k] * n
                    if r.finished:
                        del live[r.rid]
                        if r.admit_step < 0:     # on and off within one call
                            r.admit, r.admit_step = t1, k
                            waiting -= 1
    if tracer is not None and tracer.on:
        tracer.poll(math.inf, clock)
    if open_loop:
        attempted = [r for r in recs if ws <= r.due < we]
    else:
        attempted = [r for r in recs if r.finished and r.token_t[-1] <= we]
    return Run(requests=recs, step_t0=np.array(t0s), step_t1=np.array(t1s),
               window=(ws, we), attempted=attempted, drain_end=clock(),
               trace_span=None if tracer is None else tracer.span)


def _request(r: ReqRec):
    from repro.serve.scheduler import Request

    return Request(rid=r.rid, arrival=r.due, prompt_len=len(r.prompt),
                   max_new_tokens=r.out_len)
