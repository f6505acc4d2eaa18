"""A run end to end at smoke width on the CPU, past the harness's look
for a chip: accounting, the drain cap, lateness, and cells, mixes and
metrics found by name."""

from __future__ import annotations

import json
import math
import time
from pathlib import Path

import jax
import pytest

from bench import cells, measure

RECORDED = Path(__file__).parent / "data" / "qwen3-4b-decode-3steps.xplane.pb"


def run_cell(root, name, peaks, seconds=1.0, trace=False, seed=2**31 + 5):
    cell = cells.load_cell(name, root)
    return measure.measure(cell, seed, seconds, trace, jax.devices(), peaks,
                           time.perf_counter())


def edit_json(path: Path, **changes):
    data = json.loads(path.read_text())
    data.update(changes)
    path.write_text(json.dumps(data))


def test_open_loop_accounting(smoke_root, peaks, capsys):
    res = run_cell(smoke_root, "qwen3-4b.chat", peaks)
    out = capsys.readouterr()
    assert res["correct"] is True
    assert res["attempted"] == 40          # 40 req/s due in a 1 s window
    assert res["failed"] == 0
    assert set(res["metrics"]) == {"ttft_p50_s", "ttft_p90_s", "itl_p95_ms",
                                   "setup_s"}
    assert all(math.isfinite(m["value"]) and m["value"] > 0
               for m in res["metrics"].values())
    assert res["device"]["platform"] == "cpu"
    assert list(res)[-1] == "checks"
    assert res["checks"]["logit_gap"]["value"] <= \
        res["checks"]["logit_gap"]["max"]
    assert "generator lateness s: median" in out.out
    assert "programs built inside the window: 0" in out.out
    assert "check logit_gap:" in out.err.strip().splitlines()[-2]


def test_drain_cap_counts_unfinished_requests_as_failed(smoke_root, peaks):
    edit_json(smoke_root / "bench/traffic/chat-smoke.json", drain_cap_s=0.0)
    res = run_cell(smoke_root, "qwen3-4b.chat", peaks)
    assert res["attempted"] == 40
    assert 0 < res["failed"] < 40
    assert res["correct"] is True          # the finished ones are right


def test_closed_backlog_accounting(smoke_root, peaks):
    res = run_cell(smoke_root, "qwen3-moe-30b-a3b-8l.offline", peaks)
    assert res["correct"] is True
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["metrics"]) == {"tok_per_s", "setup_s"}
    assert res["metrics"]["tok_per_s"]["value"] > 0


def test_traced_run_reports_every_per_layer_metric(smoke_root, peaks,
                                                   monkeypatch):
    # the CPU has no device plane: read the recorded chip trace instead
    monkeypatch.setattr(measure, "_trace_file", lambda tmp: str(RECORDED))
    res = run_cell(smoke_root, "qwen3-4b.chat", peaks, trace=True)
    bench = json.loads((smoke_root / "BENCHMARK.json").read_text())
    want = {m["name"] for m in bench["per_layer"]
            if "qwen3-4b.chat" in m["workloads"]}
    assert set(res["metrics"]) == want
    assert 0 < res["device"]["busy_s"] < res["device"]["window_s"]
    assert 0 < len(res["breakdown"]["device_ops"]) <= 10
    assert res["breakdown"]["idle_gaps"][0][0] in measure.reduce.HOST_SPANS


def test_new_cell_mix_and_metric_are_found_by_name(smoke_root, peaks,
                                                   monkeypatch):
    monkeypatch.setattr(measure, "_trace_file", lambda tmp: str(RECORDED))
    (smoke_root / "bench/traffic/chat-slow.json").write_text(json.dumps(
        dict(json.loads((smoke_root / "bench/traffic/chat-smoke.json")
                        .read_text()), name="chat-slow", rate_rps=10.0)))
    (smoke_root / "bench/metrics/busy_steps_share.py").write_text(
        "from bench import stats\n\n\n"
        "def read(view):\n"
        "    edges = [view.run.step_t0[0], *view.run.step_t1]\n"
        "    return float((stats.work(view.run, edges).cached > 0).mean())\n")
    bench = json.loads((smoke_root / "BENCHMARK.json").read_text())
    bench["workloads"].append({"name": "qwen3-4b.chat-slow",
                               "config": "qwen3-4b-smoke",
                               "traffic": "chat-slow", "chips": 1,
                               "why": "smoke"})
    bench["per_layer"].append({"name": "busy_steps_share", "unit": "share",
                               "better": "higher", "source": "host_clock",
                               "layer": "engine host loop",
                               "moves": "setup_s",
                               "workloads": ["qwen3-4b.chat-slow"]})
    (smoke_root / "BENCHMARK.json").write_text(json.dumps(bench))
    res = run_cell(smoke_root, "qwen3-4b.chat-slow", peaks, trace=True)
    assert list(res["metrics"]) == ["busy_steps_share"]
    assert 0 < res["metrics"]["busy_steps_share"]["value"] <= 1
    assert res["attempted"] == 10
    res = run_cell(smoke_root, "qwen3-4b.chat-slow", peaks)
    assert list(res["metrics"]) == ["setup_s"]


@pytest.mark.parametrize("seconds", [0.5])
def test_same_seed_same_requests(smoke_root, peaks, seconds, monkeypatch):
    seen = []
    real = measure.drive

    def spy(engine, traffic, **kw):
        run = real(engine, traffic, **kw)
        seen.append([(len(r.prompt), r.out_len) for r in run.attempted])
        return run

    monkeypatch.setattr(measure, "drive", spy)
    for _ in range(2):
        run_cell(smoke_root, "qwen3-4b.chat", peaks, seconds=seconds, seed=9)
    assert seen[0] == seen[1] and len(seen[0]) == 20
