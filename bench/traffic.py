"""The one traffic generator.  A mix is a JSON file of parameters.

Every seed gets the same work in another order.  A block of ``n``
requests takes its prompt and output lengths at the ``n`` quantiles
``(i + 1/2) / n`` of the mix's clipped lognormals, and, for an open
loop, its gaps at the same quantiles of an exponential (Poisson
arrivals), scaled so the block spans exactly its duration.  The seed
only permutes them and draws the prompt token ids.  So the work due in
a window is fixed, and runs on different seeds differ by order alone.

Open loop (``"loop": "open"``): a block of ``rate_rps * pre_s`` requests
arrives before the window (so it opens in steady state) and one of
``rate_rps * seconds`` inside it; times are relative to the window's
start.  Closed backlog (``"loop": "closed"``): an endless run of blocks
of ``block`` requests, fed whenever fewer than ``backlog_per_slot *
slots`` wait.
"""

from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist
from typing import Iterator

import numpy as np


@dataclasses.dataclass
class Arrival:
    due: float            # seconds from the window's start (open loop)
    prompt: list[int]
    out_len: int


def lengths(dist: dict, n: int) -> np.ndarray:
    """``n`` lengths at the quantiles (i + 1/2)/n of a lognormal with the
    given median and sigma, clipped to [min, max]; in ascending order."""
    nd = NormalDist()
    z = np.array([nd.inv_cdf((i + 0.5) / n) for i in range(n)])
    x = np.round(dist["median"] * np.exp(dist["sigma"] * z))
    return np.clip(x, dist["min"], dist["max"]).astype(int)


def gaps(n: int, duration: float) -> np.ndarray:
    """``n`` exponential gaps at the quantiles (i + 1/2)/n, scaled to sum
    to ``duration``; in ascending order."""
    g = -np.log1p(-(np.arange(n) + 0.5) / n)
    return g * (duration / g.sum())


def _rngs(seed: int) -> tuple[np.random.Generator, np.random.Generator]:
    # order and token ids from separate streams, so one never shifts the other
    return (np.random.default_rng([seed, 0]), np.random.default_rng([seed, 1]))


def _block(mix: dict, n: int, order, ids, vocab: int, max_len: int):
    p, o = lengths(mix["prompt"], n), lengths(mix["output"], n)
    if int(p.max() + o.max()) > max_len:   # whatever the seed pairs
        raise ValueError(f"{mix['name']}: prompt + output can exceed "
                         f"max_len {max_len}")
    p, o = order.permutation(p), order.permutation(o)
    return [(ids.integers(0, vocab, size=int(pl)).tolist(), int(ol))
            for pl, ol in zip(p, o)]


def open_loop(mix: dict, seed: int, seconds: float, *, vocab: int,
              max_len: int) -> list[Arrival]:
    order, ids = _rngs(seed)
    out = []
    for start, span in ((-mix["pre_s"], mix["pre_s"]), (0.0, seconds)):
        n = int(round(mix["rate_rps"] * span))
        if n == 0:
            continue
        g = order.permutation(gaps(n, span))
        due = start + np.concatenate([[0.0], np.cumsum(g)[:-1]])
        out += [Arrival(float(t), p, o) for t, (p, o) in
                zip(due, _block(mix, n, order, ids, vocab, max_len))]
    return out


def closed_backlog(mix: dict, seed: int, *, vocab: int,
                   max_len: int) -> Iterator[Arrival]:
    order, ids = _rngs(seed)
    while True:
        for p, o in _block(mix, mix["block"], order, ids, vocab, max_len):
            yield Arrival(math.nan, p, o)
