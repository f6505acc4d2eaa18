"""The engine's span record read beside a run (``bench/engine_record.py``)
and the idle gaps split by the innermost engine span
(``bench/trace/nested.py``): hand counts, the recorded chip trace, and a
run at smoke width on the CPU."""

from __future__ import annotations

import math
from pathlib import Path

import jax
import numpy as np
import pytest

from bench import cells, engine_record, measure
from bench.drive import ReqRec, Run
from bench.trace import nested, reduce
from repro.serve.spans import ENGINE_SPANS

RECORDED = Path(__file__).parent / "data" / "qwen3-4b-decode-3steps.xplane.pb"

# three step calls in a window of (10, 20) and one before it; the last
# call is a 1 s stall, held by its dispatch
RING = [
    ("engine.sync", 9.02, 9.08, "engine.run", 0),
    ("engine.run", 9.0, 9.1, None, 0),
    ("request.queued", 9.5, 10.002, None, 0),
    ("engine.splice", 10.002, 10.003, "engine.refill", 1),
    ("engine.refill", 10.001, 10.004, "engine.run", 1),
    ("engine.upload", 10.005, 10.006, "engine.run", 1),
    ("engine.dispatch", 10.006, 10.010, "engine.run", 1),
    ("engine.sample", 10.010, 10.011, "engine.run", 1),
    ("engine.sync", 10.011, 10.041, "engine.run", 1),
    ("engine.lanes", 10.041, 10.049, "engine.run", 1),
    ("engine.run", 10.0, 10.05, None, 1),
    ("request.queued", 10.5, 11.0, None, 1),
    ("engine.sync", 11.005, 11.035, "engine.run", 2),
    ("engine.run", 11.0, 11.04, None, 2),
    ("engine.dispatch", 12.0, 12.9, "engine.run", 3),
    ("engine.sync", 12.9, 12.95, "engine.run", 3),
    ("engine.run", 12.0, 13.0, None, 3),
]


def synthetic_run(rids) -> Run:
    reqs = [ReqRec(rid=r, due=0.0, prompt=[1], out_len=1) for r in rids]
    return Run(requests=reqs, step_t0=np.array([8.99, 9.99, 10.99, 11.99]),
               step_t1=np.array([9.11, 10.06, 11.05, 13.01]),
               window=(10.0, 20.0), attempted=reqs, drain_end=20.0)


def test_host_ms_and_span_ms_by_hand():
    # engine.run less engine.sync over the window's calls: 20, 10, 950 ms
    assert engine_record.host_ms(RING, (10.0, 20.0)) == pytest.approx(20.0)
    assert engine_record.host_ms(RING, (30.0, 40.0)) is None
    per = engine_record.span_ms(RING, (10.0, 20.0), ENGINE_SPANS)
    assert list(per) == list(ENGINE_SPANS)
    assert per["engine.sync"] == pytest.approx(30.0)     # 30, 30, 50
    assert per["engine.run"] == pytest.approx(50.0)      # 50, 40, 1000
    assert per["engine.splice"] == per["engine.upload"] == 0.0
    assert engine_record.span_ms(RING, (30.0, 40.0), ENGINE_SPANS) == {}


def test_admit_wait_by_hand():
    # 0.502 and 0.5 s; a request never placed reads as infinitely late
    assert engine_record.admit_wait_p90(RING, [0, 1]) == pytest.approx(
        0.5 + 0.9 * 0.002)
    assert engine_record.admit_wait_p90(RING, [0, 1, 7]) == math.inf
    assert engine_record.admit_wait_p90(RING, []) is None


def test_stalls_name_the_span_with_most_self_time():
    run = synthetic_run([0, 1])
    (stall,) = engine_record.stalls(RING, run, ENGINE_SPANS)
    assert stall["at"] == pytest.approx(1.99)
    assert stall["s"] == pytest.approx(1.02)
    assert stall["held_by"] == "engine.dispatch"
    assert dict(stall["spans"]) == pytest.approx(
        {"engine.dispatch": 0.9, "engine.sync": 0.05, "engine.run": 1.0})
    times = {n: own for n, _, own in engine_record.self_times(
        [s for s in RING if s[4] == 3])}
    assert times["engine.run"] == pytest.approx(0.05)
    call = engine_record.step_call(RING, run, 1, ENGINE_SPANS)
    assert call["held_by"] == "engine.sync"
    assert dict(call["spans"])["engine.refill"] == pytest.approx(0.003)
    own = {n: o for n, _, o in engine_record.self_times(
        [s for s in RING if s[4] == 1 and s[0] in ENGINE_SPANS])}
    assert own["engine.refill"] == pytest.approx(0.002)   # less its splice


@pytest.fixture(scope="module")
def recorded():
    return reduce.load(RECORDED)


def test_recorded_trace_summary_is_unchanged(recorded):
    old = reduce.summarize(recorded)
    new = nested.summarize(recorded, nested.load_inner(RECORDED))
    assert nested.load_inner(RECORDED) == []    # traced before the engine's spans
    assert (new.window_s, new.busy_s) == (old.window_s, old.busy_s)
    assert new.device_ops == old.device_ops
    assert new.programs == old.programs
    assert new.idle_gaps == old.idle_gaps
    overlap = dict(nested.idle_overlap(recorded, []))
    assert set(overlap) <= {*reduce.HOST_SPANS, "outside"}
    assert sum(overlap.values()) == pytest.approx(
        sum(v for _, v in old.idle_gaps), rel=1e-9)


def test_innermost_span_split_adds_up_to_engine_step():
    ev = reduce.Events(
        ops={"/device:TPU:0": [("%a.1 = f32[2] fusion()", 10, 30),
                               ("%a.1 = f32[2] fusion()", 60, 70)]},
        modules={"/device:TPU:0": [("jit_decode_step(1)", 10, 30),
                                   ("jit_decode_step(1)", 60, 70)]},
        spans=[("engine_step", 0, 50), ("bookkeeping", 50, 55),
               ("engine_step", 55, 100)])
    # gaps [0,10] (mid 5), [30,60] (mid 45), [70,100] (mid 85)
    inner = [("engine.plan", 3, 4), ("engine.refill", 2, 8),
             ("engine.run", 2, 49), ("engine.sync", 31, 48),
             ("engine.run", 56, 80)]
    old = dict(reduce.summarize(ev).idle_gaps)
    new = nested.summarize(ev, inner)
    split = dict(new.idle_gaps)
    assert split == pytest.approx({"engine.refill": 10e-9, "engine.sync": 30e-9,
                                   "engine_step": 30e-9})
    assert sum(split.values()) == pytest.approx(old["engine_step"], rel=1e-12)
    assert new.window_s == reduce.summarize(ev).window_s
    # cut at the spans' edges, each part of a gap goes to its own phase
    overlap = dict(nested.idle_overlap(ev, inner))
    assert overlap == pytest.approx(
        {"engine_step": 24e-9, "engine.refill": 5e-9, "engine.plan": 1e-9,
         "engine.run": 18e-9, "engine.sync": 17e-9, "bookkeeping": 5e-9})
    assert sum(overlap.values()) == pytest.approx(
        sum(old.values()), rel=1e-12)
    # spans outside the harness's window set no edge
    early = nested.summarize(ev, inner + [("engine.run", -50, -10)])
    assert early.window_s == new.window_s
    assert dict(early.idle_gaps) == pytest.approx(split)


def test_run_at_smoke_width_keeps_the_engine_record(smoke_root, peaks,
                                                    monkeypatch):
    monkeypatch.setattr(measure, "_trace_file", lambda tmp: str(RECORDED))
    cell = cells.load_cell("qwen3-4b.chat", smoke_root)
    real = (measure.build_engine, measure.drive, measure._trace_file)
    res, kept = engine_record.measure_with_record(
        cell, 2**31 + 5, 1.0, True, jax.devices(), peaks)
    assert res["correct"] is True
    # the harness is left as it was
    assert (measure.build_engine, measure.drive, measure._trace_file) == real
    got = engine_record.readings(kept)
    assert got["engine_host_ms"] > 0
    assert 0 < got["admit_wait_p90_s"] < math.inf
    assert set(got["span_ms"]) == set(ENGINE_SPANS)
    assert got["span_ms"]["engine.sync"] > 0
    assert got["spans_per_step"] >= 6          # run, refill, upload ... lanes
    assert got["longest_step"]["held_by"] in ENGINE_SPANS
    assert {k for k, _ in got["idle_split"]} <= set(reduce.HOST_SPANS)
    assert sum(v for _, v in got["idle_overlap"]) == pytest.approx(
        sum(v for _, v in got["idle_split"]), rel=1e-9)
    cost = engine_record.recorder_us(n=200)
    assert set(cost) == {"span_off", "span_on", "record_off"}
    assert all(v > 0 for v in cost.values())
