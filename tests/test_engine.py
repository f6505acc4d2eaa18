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
from repro.serve.scheduler import Request


@pytest.fixture(scope="module")
def model():
    cfg = dataclasses.replace(smoke_config(ARCHS["qwen3-4b"]),
                              prefix_len=0, compute_dtype="float32")
    params, _ = init_decoder(jax.random.key(0), cfg)
    return cfg, params


def _req(rid, prompt_len=6, new=8):
    return Request(rid=rid, arrival=0.0, prompt_len=prompt_len,
                   max_new_tokens=new)


def test_engine_completes_all_requests(model):
    cfg, params = model
    eng = DecodeEngine(cfg, params, slots=4, max_len=64)
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


def test_engine_plans_only_on_admission_change(model):
    """The serving hot path must not re-plan per decode step: planning
    happens once per admission (one ``engine.plan`` span per kernel
    record), repeated lane-length signatures come out of the memo cache,
    and steady-state decode steps skip the admission scan entirely."""
    from repro.core.jax_sched import kernel_plan_cache_clear

    kernel_plan_cache_clear()
    cfg, params = model
    eng = DecodeEngine(cfg, params, slots=2, max_len=64)
    # identical requests -> identical lane-length signatures across
    # admissions -> the cache serves the repeats
    for i in range(8):
        eng.submit(_req(i, prompt_len=4, new=4))
    stats = eng.run()
    assert stats.completed == 8
    plans = [s for s in eng.spans.ring if s[0] == "engine.plan"]
    assert len(plans) == len(eng.kernel_records)
    assert len(plans) < stats.steps     # not every decode step
    assert eng.spans.counts["plan_cache_hits"] > 0  # signatures reused
    # telemetry still records one plan per admission, in order
    assert [r.instance for r in eng.kernel_records] == \
        list(range(len(eng.kernel_records)))


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


def test_engine_sheds_backlog_tail_over_slo(model):
    """With a shed_slo step budget, the backlog tail the lanes cannot
    decode in time is dropped at admission — bounded queue, recorded
    rids — and everything admitted still completes."""
    cfg, params = model
    eng = DecodeEngine(cfg, params, slots=2, max_len=64, shed_slo=30.0)
    for i in range(10):
        eng.submit(_req(i, prompt_len=6, new=8))
    stats = eng.run()
    assert stats.shed > 0
    assert stats.completed + stats.shed == 10
    assert sorted(eng.shed_rids) == sorted(set(eng.shed_rids))
    assert len(eng.shed_rids) == stats.shed
    # arrival order: the *tail* is shed, the head is served
    assert 0 not in eng.shed_rids
    for i in range(10):
        if i not in eng.shed_rids:
            assert len(eng.output(i)) == 8


def test_engine_shedding_disabled_by_default(model):
    cfg, params = model
    eng = DecodeEngine(cfg, params, slots=2, max_len=64)
    for i in range(10):
        eng.submit(_req(i, new=4))
    stats = eng.run()
    assert stats.shed == 0 and eng.shed_rids == []
    assert stats.completed == 10


def test_engine_disabled_lane_shrinks_shed_budget(model):
    """A gray-failed (disabled) lane halves the step budget: the same
    backlog sheds more."""
    cfg, params = model
    shed_counts = []
    for disable in (False, True):
        eng = DecodeEngine(cfg, params, slots=2, max_len=64, shed_slo=40.0)
        if disable:
            eng.set_slot_enabled(1, False)
        for i in range(10):
            eng.submit(_req(i, prompt_len=6, new=8))
        stats = eng.run()
        shed_counts.append(stats.shed)
    assert shed_counts[1] > shed_counts[0]


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
