"""The serving loop's record (serve/spans.py): the recorder itself, and the
spans DecodeEngine writes into it on the smoke model."""

import dataclasses
import time

import jax
import pytest

from repro.configs import ARCHS, smoke_config
from repro.models import init_decoder
from repro.serve.engine import DecodeEngine
from repro.serve.scheduler import Request
from repro.serve.spans import ENGINE_SPANS, Recorder


@pytest.fixture(scope="module")
def model():
    cfg = dataclasses.replace(smoke_config(ARCHS["qwen3-4b"]),
                              prefix_len=0, compute_dtype="float32")
    params, _ = init_decoder(jax.random.key(0), cfg)
    return cfg, params


def _req(rid, prompt_len=3, new=3):
    return Request(rid=rid, arrival=0.0, prompt_len=prompt_len,
                   max_new_tokens=new)


def _named(rec, name):
    return [s for s in rec.ring if s[0] == name]


def _inside(inner, outer):
    return outer[1] <= inner[1] <= inner[2] <= outer[2]


def test_recorder_nests_records_and_keeps_failed_spans():
    rec = Recorder()
    with rec.span("a", 7) as a:
        with rec.span("b", 7):
            pass
        rec.record("q", 1.0, 2.0, 42)
    with pytest.raises(KeyError):
        with rec.span("c", 8):
            raise KeyError
    (b, q, a_rec, c) = rec.ring
    assert (b[0], b[3], b[4]) == ("b", "a", 7)
    assert q == ("q", 1.0, 2.0, None, 42)
    assert a_rec == ("a", a.t0, a.t1, None, 7)
    assert (c[0], c[3]) == ("c", None)
    assert _inside(b, a_rec) and a.t0 < a.t1


def test_recorder_ring_is_bounded():
    rec = Recorder(maxlen=5)
    for i in range(12):
        with rec.span("s", i):
            pass
    assert [s[4] for s in rec.ring] == [7, 8, 9, 10, 11]


def test_every_run_call_holds_its_step_spans(model):
    cfg, params = model
    eng = DecodeEngine(cfg, params, slots=2, max_len=32)
    for i in range(5):
        eng.submit(_req(i))
    steps = 0
    while eng.sched.backlog or any(r is not None for r in eng.lane_requests):
        steps += eng.run(max_steps=1).steps
    ring = list(eng.spans.ring)
    assert {s[0] for s in ring} <= set(ENGINE_SPANS) | {"request.queued"}
    calls = _named(eng.spans, "engine.run")
    assert len(calls) == steps
    held_by = []
    for k, call in enumerate(calls):
        assert call[3] is None and call[4] == k
        held = [s for s in ring if s is not call and s[0] in ENGINE_SPANS
                and _inside(s, call)]
        for name in ("engine.dispatch", "engine.sync", "engine.lanes"):
            assert [s[4] for s in held if s[0] == name] == [k]
        for s in held:
            assert s[3] in ("engine.run", "engine.refill")
            if s[3] == "engine.refill":
                assert any(_inside(s, p) for p in held
                           if p[0] == "engine.refill")
        held_by += held
    # every step span lies inside a call
    assert len(held_by) + len(calls) == sum(s[0] in ENGINE_SPANS for s in ring)
    splices = _named(eng.spans, "engine.splice")
    assert splices                          # a lane was reused
    assert {s[3] for s in splices} == {"engine.refill"}
    # a refill holds nothing but the splices of the lanes it reuses
    assert {s[0] for s in ring if s[3] == "engine.refill"} == {"engine.splice"}


def test_engine_spans_names_only_spans_a_run_opens(model):
    """A run that admits, reuses a lane and decodes opens every name in
    ``ENGINE_SPANS``: the tuple lists no span the engine no longer opens."""
    cfg, params = model
    eng = DecodeEngine(cfg, params, slots=1, max_len=32)
    for i in range(2):
        eng.submit(_req(i))
    stats = eng.run()
    assert stats.completed == 2        # lane 0 was reused for request 1
    names = {s[0] for s in eng.spans.ring}
    assert names == set(ENGINE_SPANS) | {"request.queued"}


def test_request_queued_runs_from_submit_to_placement(model):
    cfg, params = model
    eng = DecodeEngine(cfg, params, slots=1, max_len=32)
    before = time.perf_counter()
    eng.submit(_req(10))
    eng.submit(_req(11))
    after = time.perf_counter()
    first = eng.run(max_steps=1)
    assert eng.lane_requests == [10]
    (q10,) = _named(eng.spans, "request.queued")
    assert q10[4] == 10 and before <= q10[1] <= after
    (refill,) = _named(eng.spans, "engine.refill")
    assert refill[1] <= q10[2] <= refill[2]
    while eng.lane_requests != [11]:
        eng.run(max_steps=1)
    q11 = _named(eng.spans, "request.queued")[-1]
    assert q11[4] == 11 and before <= q11[1] <= after
    placed = [r for r in _named(eng.spans, "engine.refill")
              if r[1] <= q11[2] <= r[2]]
    assert len(placed) == 1 and q11[2] > q10[2]
    assert first.steps == 1


def test_wall_s_is_the_run_span(model):
    cfg, params = model
    eng = DecodeEngine(cfg, params, slots=2, max_len=32)
    for i in range(3):
        eng.submit(_req(i))
    stats = eng.run()
    (call,) = _named(eng.spans, "engine.run")
    assert stats.wall_s == call[2] - call[1] > 0
    assert len(_named(eng.spans, "engine.sync")) == stats.steps
