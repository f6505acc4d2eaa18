"""End-to-end metrics from a run's records, on the host clock."""

from __future__ import annotations

import dataclasses
import math

import numpy as np

from .drive import Run


def percentile(values, q: float) -> float:
    """``q``-th percentile, interpolated linearly between order
    statistics (numpy's default).  ``inf`` stands for a value censored at
    the drain cap: it sorts last, and a percentile that reaches into the
    censored values is ``inf``."""
    v = np.sort(np.asarray(values, float))
    if v.size == 0:
        return math.nan
    x = q / 100.0 * (v.size - 1)
    lo, hi = math.floor(x), math.ceil(x)
    if math.isinf(v[hi]):
        return math.inf
    return float(v[lo] + (v[hi] - v[lo]) * (x - lo))


def ttft(run: Run) -> list[float]:
    """Due time to first token of every attempted request (inf if none
    came before the drain cap)."""
    return [r.token_t[0] - r.due if r.token_t else math.inf
            for r in run.attempted]


def itl(run: Run) -> np.ndarray:
    """Gaps between consecutive tokens, over all attempted requests."""
    parts = [np.diff(r.token_t) for r in run.attempted if len(r.token_t) > 1]
    return np.concatenate(parts) if parts else np.zeros(0)


def queue_wait(run: Run) -> list[float]:
    """Due time to first appearance on a lane (inf if never)."""
    return [r.admit - r.due if r.admit_step >= 0 else math.inf
            for r in run.attempted]


def lateness(run: Run) -> np.ndarray:
    """How late the generator submitted each request after it was due."""
    return np.array([r.submit - r.due for r in run.requests])


@dataclasses.dataclass
class Work:
    """What the model did in each interval ``(edges[i], edges[i + 1]]``,
    read from the host's record of admissions and tokens alone, so the
    same whatever way the engine prefills.

    A request's prompt is credited in proportion to the part of its
    prefill that lies in the interval: from the start of the step call
    that put it on a lane to its first token.  An output token counts
    when it is emitted, and each but the last is fed back as one more
    position.  A request with no first token by the end of the run has
    an unknown prefill: its prompt is not credited and it is counted in
    ``unfinished``.
    """

    tokens: np.ndarray      # prompt tokens credited and output tokens emitted
    positions: np.ndarray   # positions processed: prompt, and outputs fed back
    attended: np.ndarray    # over those positions, the positions each attends
    cached: np.ndarray      # positions in the cache at the interval's end,
    #                         over the requests on a lane in the interval
    lanes: np.ndarray       # requests on a lane in the interval
    unfinished: int


def work(run: Run, edges) -> Work:
    e = np.asarray(edges, float)
    n = e.size - 1
    tokens, positions, attended, cached, lanes = (np.zeros(n) for _ in range(5))
    unfinished = 0
    for r in run.requests:
        tt = np.asarray(r.token_t, float)
        plen = len(r.prompt)
        if tt.size:
            i = np.searchsorted(e, tt, side="left") - 1   # e[i] < t <= e[i+1]
            ok = (i >= 0) & (i < n)
            np.add.at(tokens, i[ok], 1.0)
            fed = ok & (np.arange(tt.size) > 0)   # output j >= 2 at position plen + j - 1
            np.add.at(positions, i[fed], 1.0)
            np.add.at(attended, i[fed], plen + np.arange(tt.size)[fed])
        if r.admit_step < 0:
            continue
        if not tt.size:
            unfinished += 1
            continue
        a, f = run.step_t0[r.admit_step], tt[0]
        done = (np.clip((e - a) / (f - a), 0.0, 1.0) if f > a
                else (e >= f).astype(float))
        p = plen * done                     # prompt positions processed by each edge
        tokens += np.diff(p)
        positions += np.diff(p)
        attended += np.diff(p * (p + 1)) / 2     # 1 + 2 + ... + p
        leave = tt[-1] if r.finished else math.inf
        on = (e[1:] > a) & (e[:-1] < leave)
        fed_back = np.maximum(np.searchsorted(tt, e[1:], side="right") - 1, 0)
        cached += np.where(on, p[1:] + fed_back, 0.0)
        lanes += on
    return Work(tokens, positions, attended, cached, lanes, unfinished)


def tok_per_s(run: Run) -> float:
    """Prompt and output tokens served inside the window, per second."""
    return float(work(run, run.window).tokens[0] / run.seconds)


def failed(run: Run) -> int:
    """Attempted requests that did not get all their tokens."""
    return sum(not r.finished for r in run.attempted)
