"""The generator: the same work for every seed, in another order."""

from __future__ import annotations

import itertools
import json
from collections import Counter
from pathlib import Path

import numpy as np
import pytest

from bench import traffic

ROOT = Path(__file__).resolve().parents[2]


def mix(name):
    return json.loads((ROOT / "bench" / "traffic" / f"{name}.json").read_text())


def test_lengths_are_stratified_quantiles_clipped():
    d = {"median": 48, "sigma": 0.8, "min": 8, "max": 512}
    x = traffic.lengths(d, 101)
    assert x[50] == 48 and (np.diff(x) >= 0).all()
    assert x.min() >= 8 and x.max() <= 512
    g = traffic.gaps(100, 50.0)
    assert g.sum() == pytest.approx(50.0) and (g > 0).all()


@pytest.mark.parametrize("seeds", [(1, 2), (2**31 + 11, 3 * 2**32)])
def test_open_loop_same_work_every_seed(seeds):
    m = mix("chat")
    runs = [traffic.open_loop(m, s, 30.0, vocab=151936, max_len=2048)
            for s in seeds]
    n_win = round(m["rate_rps"] * 30.0)
    n_pre = round(m["rate_rps"] * m["pre_s"])
    for arr in runs:
        due = np.array([a.due for a in arr])
        assert len(arr) == n_pre + n_win
        assert (np.diff(due) > 0).all()
        assert ((due >= 0) & (due < 30.0)).sum() == n_win
        assert due.min() == -m["pre_s"]
        assert all(0 <= t < 151936 for a in arr for t in a.prompt)
    lens = [Counter(len(a.prompt) for a in arr) for arr in runs]
    outs = [Counter(a.out_len for a in arr) for arr in runs]
    assert lens[0] == lens[1] and outs[0] == outs[1]
    assert [len(a.prompt) for a in runs[0]] != [len(a.prompt) for a in runs[1]]


def test_open_loop_is_a_function_of_the_seed():
    m = mix("chat")
    a, b = (traffic.open_loop(m, 5, 10.0, vocab=1000, max_len=2048)
            for _ in range(2))
    assert [(x.due, x.prompt, x.out_len) for x in a] == \
        [(x.due, x.prompt, x.out_len) for x in b]


def test_closed_backlog_blocks_hold_the_same_lengths():
    m = mix("offline")
    a = list(itertools.islice(traffic.closed_backlog(
        m, 3, vocab=151936, max_len=2048), 2 * m["block"]))
    b = list(itertools.islice(traffic.closed_backlog(
        m, 4, vocab=151936, max_len=2048), 2 * m["block"]))
    for blk in range(2):
        sl = slice(blk * m["block"], (blk + 1) * m["block"])
        assert Counter(len(x.prompt) for x in a[sl]) == \
            Counter(len(x.prompt) for x in b[sl])
        assert Counter(x.out_len for x in a[sl]) == \
            Counter(x.out_len for x in b[sl])
    assert all(len(x.prompt) + x.out_len <= 2048 for x in a)
    assert [len(x.prompt) for x in a] != [len(x.prompt) for x in b]


def test_requests_longer_than_the_cache_are_refused():
    m = dict(mix("offline"), block=4)
    with pytest.raises(ValueError, match="max_len"):
        next(traffic.closed_backlog(m, 0, vocab=10, max_len=100))
