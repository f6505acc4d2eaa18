"""DecodeEngine: real continuous batching over the model with DLS
admission — including the lane-isolation property that motivated
per-lane cache positions."""

import dataclasses

import jax
import numpy as np
import pytest

from repro.configs import ARCHS, smoke_config
from repro.models import init_decoder
from repro.serve.engine import DecodeEngine
from repro.serve.scheduler import Request, RequestScheduler


@pytest.fixture(scope="module")
def model():
    cfg = dataclasses.replace(smoke_config(ARCHS["qwen3-4b"]),
                              prefix_len=0, compute_dtype="float32")
    params, _ = init_decoder(jax.random.key(0), cfg)
    return cfg, params


def _req(rid, prompt_len=6, new=8):
    return Request(rid=rid, arrival=0.0, prompt_len=prompt_len,
                   max_new_tokens=new)


@pytest.mark.parametrize("technique",
                         ["fac2", "ss", "static", "tss", "fac", "awf_b", "af"])
def test_engine_completes_all_requests(model, technique):
    cfg, params = model
    eng = DecodeEngine(cfg, params, slots=4, max_len=64, technique=technique)
    for i in range(10):
        eng.submit(_req(i))
    stats = eng.run()
    assert stats.completed == 10
    for i in range(10):
        out = eng.output(i)
        assert len(out) == 8
        assert all(0 <= t < cfg.padded_vocab for t in out)


def test_engine_lane_isolation(model):
    """A request decoded after another request freed its lane must produce
    the same tokens as the same request decoded alone — per-lane positions
    keep stale cache entries invisible."""
    cfg, params = model
    prompt = list(np.random.default_rng(7).integers(2, 200, 6))

    # alone: single-slot engine, only request B
    eng_alone = DecodeEngine(cfg, params, slots=1, max_len=64)
    eng_alone.submit(_req(100), prompt=prompt)
    eng_alone.run()
    alone = eng_alone.output(100)

    # after A: same slot runs a different request first
    eng_seq = DecodeEngine(cfg, params, slots=1, max_len=64)
    eng_seq.submit(_req(99), prompt=list(
        np.random.default_rng(3).integers(2, 200, 10)))
    eng_seq.submit(_req(100), prompt=prompt)
    eng_seq.run()
    assert eng_seq.output(100) == alone


def test_engine_dls_admission_pulls_chunks(model):
    cfg, params = model
    eng = DecodeEngine(cfg, params, slots=2, max_len=64, technique="gss")
    for i in range(6):
        eng.submit(_req(i, new=4))
    stats = eng.run()
    assert stats.completed == 6
    assert stats.tokens == 24


def test_engine_reports_chunk_service_times(model):
    """Regression for the adaptivity gap: the engine must report each
    admission chunk's measured decode-steps back through
    RequestScheduler.complete, so adaptive techniques see real per-slot
    service times instead of zero measurements."""
    cfg, params = model
    eng = DecodeEngine(cfg, params, slots=2, max_len=64, technique="awf_c")
    completed = []
    orig = eng.sched.complete

    def spy(worker, elapsed):
        completed.append((worker, elapsed))
        orig(worker, elapsed=elapsed)

    eng.sched.complete = spy
    for i in range(6):
        eng.submit(_req(i, new=4))
    stats = eng.run()
    assert stats.completed == 6
    assert completed, "no chunk measurements reached the scheduler"
    assert all(e > 0 for _, e in completed)
    assert {w for w, _ in completed} <= {0, 1}


def test_engine_refills_only_when_a_lane_retires(model):
    """The serving hot path must not scan for admissions per decode step:
    within one ``run()`` call there is one ``engine.refill`` at its start
    and one after each step in which a lane retired, and no other."""
    cfg, params = model
    eng = DecodeEngine(cfg, params, slots=2, max_len=64)
    for i in range(8):
        eng.submit(_req(i, prompt_len=4, new=4))
    retired_steps = 0
    done = 0
    orig = eng._advance

    def spy(stats):
        nonlocal retired_steps, done
        orig(stats)
        retired_steps += stats.completed > done
        done = stats.completed

    eng._advance = spy
    stats = eng.run()
    assert stats.completed == 8
    refills = [s for s in eng.spans.ring if s[0] == "engine.refill"]
    assert len(refills) == 1 + retired_steps
    assert len(refills) < stats.steps     # not every decode step


def test_engine_slot_disable_mid_stream(model):
    """Failing a lane mid-stream requeues its in-flight work: every
    request still completes exactly once, on the surviving lanes."""
    cfg, params = model
    eng = DecodeEngine(cfg, params, slots=3, max_len=64)
    for i in range(9):
        eng.submit(_req(i, new=4))
    first = eng.run(max_steps=4)   # mid-prefill on all three lanes
    eng.set_slot_enabled(1, False)
    rest = eng.run()
    assert first.completed + rest.completed == 9
    for i in range(9):
        out = eng.output(i)
        assert len(out) == 4, f"request {i} lost across the lane fault"
    assert eng._active[1] is None  # the dead lane stayed out of service


def test_engine_all_slots_disabled_terminates(model):
    """run() must not spin when every lane is out of service — the
    backlog waits for a re-enable instead of burning decode steps."""
    cfg, params = model
    eng = DecodeEngine(cfg, params, slots=2, max_len=64)
    for i in range(4):
        eng.submit(_req(i, new=4))
    eng.set_slot_enabled(0, False)
    eng.set_slot_enabled(1, False)
    stats = eng.run()
    assert stats.completed == 0
    assert eng.sched.backlog == 4
    eng.set_slot_enabled(0, True)
    stats2 = eng.run()
    assert stats2.completed == 4
    for i in range(4):
        assert len(eng.output(i)) == 4


def test_engine_disabled_slot_drops_partial_measurement(model):
    """The interrupted chunk's step count must not reach the scheduler:
    a partial measurement attributed to a dead lane would corrupt the
    adaptive weights."""
    cfg, params = model
    eng = DecodeEngine(cfg, params, slots=2, max_len=64, technique="awf_c")
    reported = []
    orig = eng.sched.complete

    def spy(worker, elapsed):
        reported.append(worker)
        orig(worker, elapsed=elapsed)

    eng.sched.complete = spy
    for i in range(6):
        eng.submit(_req(i, new=4))
    eng.run(max_steps=3)
    before = list(reported)
    eng.set_slot_enabled(0, False)
    assert reported == before  # disable itself reported nothing
    eng.run()
    assert 1 in reported       # the survivor still reports


def _tech_state(sched):
    return {k: np.array(v).tolist() for k, v in sched._tech._state().items()}


@pytest.mark.parametrize("granted", [True, False],
                         ids=["after_pull", "no_grant"])
def test_scheduler_release_drops_the_open_grant(granted):
    """``release`` drops a worker's open grant unmeasured, so a later
    ``complete()`` leaves the adaptive state as it was; on a worker with
    no grant it changes nothing, and other workers' grants stay open."""
    sched = RequestScheduler(num_workers=2, technique="af")
    for i in range(64):
        sched.submit(_req(i))
    if granted:
        assert sched.pull(0)
    assert sched.pull(1)
    backlog, before = sched.backlog, _tech_state(sched)
    sched.release(0)
    sched.complete(0, elapsed=3.0)
    assert _tech_state(sched) == before
    assert sched.backlog == backlog
    sched.complete(1, elapsed=5.0)     # worker 1's grant is still measured
    assert _tech_state(sched) != before


def test_scheduler_release_then_pull_opens_a_fresh_grant():
    """A pull after ``release`` does not fold into the dropped grant: the
    next measurement is attributed to the new take alone."""
    sched = RequestScheduler(num_workers=2, technique="af")
    for i in range(64):
        sched.submit(_req(i))
    first = sched.pull(0)
    sched.release(0)
    second = sched.pull(0)
    assert first and second
    sched.complete(0, elapsed=4.0)
    assert _tech_state(sched)["_cnt"][0] == len(second)


def test_engine_donates_decode_state(model):
    """The jitted step consumes the previous state in place: the cache is
    never held twice, which is what lets a full-width cache fit a chip."""
    cfg, params = model
    eng = DecodeEngine(cfg, params, slots=2, max_len=64)
    before = eng.state
    eng.submit(_req(0))
    eng.run()
    assert all(leaf.is_deleted() for leaf in jax.tree.leaves(before))
    assert eng.last_logits.shape == (2, 1, cfg.padded_vocab)
