"""End-to-end metric arithmetic on hand-made runs."""

from __future__ import annotations

import math

import numpy as np
import pytest

from bench import stats
from bench.drive import ReqRec, Run, drive
from bench.traffic import Arrival


def rec(rid, due, prompt_len, out_len, admit_step, first_step=None,
        t=lambda k: 0.1 * (k + 1)):
    """A request whose first token came at step ``first_step`` and one
    token per step after, up to its length or the last step (9)."""
    r = ReqRec(rid, due, [1] * prompt_len, out_len, submit=due)
    r.admit_step, r.admit = admit_step, t(admit_step)
    if first_step is not None:
        steps = list(range(first_step, min(first_step + out_len, 10)))
        r.token_step = steps
        r.token_t = [t(k) for k in steps]
    return r


def run_of(reqs, attempted=None, window=(0.0, 1.0)):
    t1 = 0.1 * (np.arange(10) + 1)
    return Run(requests=reqs, step_t0=t1 - 0.05, step_t1=t1, window=window,
               attempted=reqs if attempted is None else attempted,
               drain_end=1.0)


def test_percentile_interpolates_and_censors():
    v = list(range(1, 11))
    assert stats.percentile(v, 50) == pytest.approx(np.percentile(v, 50))
    assert stats.percentile(v, 90) == pytest.approx(9.1)
    assert stats.percentile(v, 100) == 10
    assert stats.percentile([1, 2, 3, math.inf], 50) == 2.5
    assert stats.percentile([1, 2, 3, math.inf], 90) == math.inf
    assert math.isnan(stats.percentile([], 50))


def test_ttft_itl_queue_wait_over_all_attempted():
    a = rec(0, due=0.0, prompt_len=2, out_len=3, admit_step=0, first_step=1)
    b = rec(1, due=0.05, prompt_len=3, out_len=2, admit_step=3, first_step=6)
    c = rec(2, due=0.5, prompt_len=4, out_len=2, admit_step=8)  # no token
    run = run_of([a, b, c])
    assert stats.ttft(run) == pytest.approx([0.2, 0.65, math.inf])
    assert stats.percentile(stats.ttft(run), 50) == pytest.approx(0.65)
    assert stats.percentile(stats.ttft(run), 90) == math.inf
    np.testing.assert_allclose(stats.itl(run), [0.1, 0.1, 0.1])
    assert stats.queue_wait(run) == pytest.approx([0.1, 0.35, 0.4])
    assert stats.failed(run) == 1          # c never finished
    np.testing.assert_allclose(stats.lateness(run), 0.0)


def test_tok_per_s_credits_prompt_steps_and_emitted_tokens():
    # a: on a lane from step 0 (t0 0.05), first token at 0.2, then 0.3, 0.4
    a = rec(0, due=0.0, prompt_len=2, out_len=3, admit_step=0, first_step=1)
    # b: on a lane from step 3 (t0 0.35), first token at 0.6, then 0.7
    b = rec(1, due=0.0, prompt_len=3, out_len=2, admit_step=3, first_step=5)
    # c: on a lane from step 7, no token yet: its prefill is unknown
    c = rec(2, due=0.0, prompt_len=4, out_len=2, admit_step=7)
    # window (0.25, 0.65]: a's outputs at 0.3, 0.4; b's whole prompt and
    # its output at 0.6
    run = run_of([a, b, c], window=(0.25, 0.65))
    assert stats.tok_per_s(run) == pytest.approx((2 + 3 + 1) / 0.4)
    # window (0.25, 0.5]: 0.15 of b's 0.25 s prefill lies inside
    run = run_of([a, b, c], window=(0.25, 0.5))
    assert stats.tok_per_s(run) == pytest.approx((2 + 3 * 0.6) / 0.25)
    assert stats.work(run, run.window).unfinished == 1


def test_a_request_that_never_reached_a_lane_credits_nothing():
    waiting = rec(3, due=0.0, prompt_len=4, out_len=2, admit_step=-1)
    w = stats.work(run_of([waiting]), [0.0, 0.5, 1.0])
    for part in (w.tokens, w.positions, w.attended, w.cached, w.lanes):
        np.testing.assert_array_equal(part, 0)
    assert w.unfinished == 0


def test_work_prorates_the_prompt_and_feeds_outputs_back():
    # prompt 2 on a lane from 0.05, outputs at 0.2, 0.3, 0.4
    a = rec(0, due=0.0, prompt_len=2, out_len=3, admit_step=0, first_step=1)
    run = run_of([a])
    edges = np.r_[0.0, run.step_t1[:4]]
    w = stats.work(run, edges)
    # prompt done at the edges: 0, 1/3, 1, 1, 1 (of 2 positions)
    np.testing.assert_allclose(w.tokens, [2 / 3, 4 / 3 + 1, 1, 1])
    np.testing.assert_allclose(w.positions, [2 / 3, 4 / 3, 1, 1])
    # positions 1 + 2 over the prefill; outputs 1 and 2 fed back at 3, 4
    np.testing.assert_allclose(w.attended, [5 / 9, 22 / 9, 3, 4])
    np.testing.assert_allclose(w.cached, [2 / 3, 2, 3, 4])
    assert stats.work(run, [0.4, 0.5]).cached[0] == 0    # off its lane


def test_work_counts_the_requests_on_a_lane_beside_their_cache():
    # a: prompt 2 on a lane from 0.05, outputs at 0.2, 0.3, 0.4, then off
    # b: prompt 3 on a lane from 0.35, outputs at 0.6, 0.7
    a = rec(0, due=0.0, prompt_len=2, out_len=3, admit_step=0, first_step=1)
    b = rec(1, due=0.0, prompt_len=3, out_len=2, admit_step=3, first_step=5)
    w = stats.work(run_of([a, b]), [0.0, 0.2, 0.4, 0.6, 0.8])
    np.testing.assert_array_equal(w.lanes, [1, 2, 1, 1])
    # a: 2, then 2 + 2 fed back; b: 0.6 of its prompt, 3, then 3 + 1
    np.testing.assert_allclose(w.cached, [2, 4.6, 3, 4])


class OneStepPrefill:
    """An engine that prefills a whole prompt in the step that admits it,
    and emits one token per lane and step; each call takes 1/64 s."""

    def __init__(self, slots, clock):
        self.lanes, self.queue, self.out, self.left = [None] * slots, [], {}, {}
        self.clock = clock

    def submit(self, req, prompt):
        self.queue.append((req.rid, req.max_new_tokens))

    @property
    def lane_requests(self):
        return list(self.lanes)

    def output(self, rid):
        return self.out.get(rid, [])

    def run(self, max_steps):
        for s, rid in enumerate(self.lanes):
            if rid is None and self.queue:
                rid, n = self.queue.pop(0)
                self.lanes[s], self.out[rid], self.left[rid] = rid, [], n
        for s, rid in enumerate(self.lanes):
            if rid is not None:
                self.out[rid].append(7)
                self.left[rid] -= 1
                if not self.left[rid]:
                    self.lanes[s] = None
        self.clock.now += 1 / 64


class Clock:
    now = 0.0

    def __call__(self):
        return self.now

    def sleep(self, s):
        self.now += s


def test_tok_per_s_and_work_hold_when_prefill_takes_one_step():
    clock = Clock()
    engine = OneStepPrefill(1, clock)
    traffic = (Arrival(math.nan, [1] * 5, 3) for _ in iter(int, 1))
    run = drive(engine, traffic, open_loop=False, seconds=1.0, pre_s=0.0,
                backlog=2, drain_cap_s=1.0, clock=clock, sleep=clock.sleep)
    # 64 calls in the window, one output each; a request every third call
    # (0, 3, ..., 63), its 5 prompt tokens inside the call that admits it
    assert len(run.step_t1) == 64
    assert stats.tok_per_s(run) == pytest.approx(64 + 22 * 5)
    w = stats.work(run, np.r_[0.0, run.step_t1])
    np.testing.assert_allclose(w.positions, ([5, 1, 1] * 22)[:64])
    np.testing.assert_allclose(w.cached, ([5, 6, 7] * 22)[:64])
    np.testing.assert_allclose(w.attended, ([15, 6, 7] * 22)[:64])
    assert w.unfinished == 0
    assert len(run.attempted) == 21 and stats.failed(run) == 0
