"""The trace-to-numbers reduction, on a recorded trace and on hand-made
events."""

from __future__ import annotations

from pathlib import Path

import pytest

from bench.trace import reduce

# three decode steps of qwen3-4b at 16 lanes on one TPU v5e, traced with
# the harness's host annotations and trimmed to the device's "XLA Ops" and
# "XLA Modules" lines and the host's python line
RECORDED = Path(__file__).parent / "data" / "qwen3-4b-decode-3steps.xplane.pb"


@pytest.fixture(scope="module")
def recorded():
    return reduce.load(RECORDED)


def test_recorded_trace_loads_device_and_host_lines(recorded):
    assert list(recorded.ops) == ["/device:TPU:0"]
    assert len(recorded.ops["/device:TPU:0"]) == 6564
    assert [s[0] for s in recorded.spans] == ["engine_step", "bookkeeping"] * 3


def test_recorded_trace_busy_idle_and_gaps(recorded):
    s = reduce.summarize(recorded)
    assert s.window_s == pytest.approx(0.11432341)
    assert 0 < s.busy_s < s.window_s
    # every idle nanosecond is charged to some host phase
    assert sum(v for _, v in s.idle_gaps) == pytest.approx(
        s.window_s - s.busy_s, rel=1e-9)
    assert s.idle_share == pytest.approx(0.0951, abs=1e-3)


def test_recorded_trace_main_program_is_the_decode_step(recorded):
    s = reduce.summarize(recorded)
    name, calls, secs = s.main_program()
    assert name.startswith("jit__lambda") and calls == 3
    assert secs / calls == pytest.approx(0.0344, abs=5e-4)


def test_recorded_trace_top_ops_leave_out_containers(recorded):
    s = reduce.summarize(recorded)
    labels = [k for k, _ in s.device_ops]
    assert len(labels) == 10
    assert not any(k.startswith("while") for k in labels)
    assert labels[0] == "bitcast_add_fusion.3 bf16[16,1,2560]"
    secs = [v for _, v in s.device_ops]
    assert secs == sorted(secs, reverse=True)


def test_op_label():
    assert reduce.op_label(
        "%fusion.12 = bf16[16,9728]{1,0:T(8,128)} fusion(bf16[16,1]{1,0} %a)"
    ) == "fusion.12 bf16[16,9728]"
    assert reduce.op_label("%while.2 = (s32[], bf16[2]) while(...)") == \
        "while.2 tuple"


def test_union_and_gap_attribution_by_hand():
    ev = reduce.Events(
        ops={"/device:TPU:0": [("%a.1 = f32[2] fusion()", 10, 30),
                               ("%b.1 = f32[2] fusion()", 20, 40),
                               ("%a.1 = f32[2] fusion()", 60, 70)]},
        modules={"/device:TPU:0": [("jit_step(1)", 10, 40),
                                   ("jit_step(1)", 60, 70)]},
        spans=[("engine_step", 0, 45), ("bookkeeping", 45, 55),
               ("engine_step", 55, 100)])
    s = reduce.summarize(ev)
    assert s.window_s == pytest.approx(100e-9)
    assert s.busy_s == pytest.approx(40e-9)            # [10,40] and [60,70]
    assert dict(s.idle_gaps) == pytest.approx(
        {"engine_step": 40e-9, "bookkeeping": 20e-9})  # [0,10],[70,100]; [40,60]
    assert dict(s.device_ops) == pytest.approx(
        {"a.1 f32[2]": 30e-9, "b.1 f32[2]": 20e-9})
    assert s.main_program() == ("jit_step(1)", 2, pytest.approx(40e-9))


def test_no_spans_or_no_device_is_an_error():
    with pytest.raises(ValueError):
        reduce.summarize(reduce.Events(ops={}, modules={}, spans=[]))
    with pytest.raises(ValueError):
        reduce.summarize(reduce.Events(ops={}, modules={},
                                       spans=[("submit", 0, 1)]))
