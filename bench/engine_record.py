"""The engine's own span record, read beside a benchmark run; not part of one.

    python bench/engine_record.py --workload <cell> --seconds <s> --seeds <n> [<n> ...] [--trace 1]

On the accelerator, in one process, runs the cell once per seed as
``bench/run.py`` does, keeps the engine's span record
(``repro.serve.spans``) and prints one JSON line per seed with the run's
metrics and, read from the record:

- ``engine_host_ms``: median over the window's step calls of
  ``engine.run`` less its ``engine.sync``, the host work of a step that
  the device waits for;
- ``admit_wait_p90_s``: 90th percentile of ``request.queued`` (submit to
  a lane) over the attempted requests, ``inf`` for one never placed;
- ``span_ms``: median time per step call in each engine span, over the
  window;
- ``stalls``: every step call of the run over ``STALL_S``, with the
  engine spans inside it and the one with the most self time;
- ``spans_per_step``: engine spans opened per step call in the window,
  and ``recorder_us``: what one span costs, with the profiler off and on,
  and one ``request.queued`` record;
- with ``--trace 1``, ``idle_split``: the traced window's idle gaps, each
  charged to the innermost engine span over its midpoint, and
  ``idle_overlap``: the same with each gap cut at the spans' edges
  (``trace/nested.py``).
"""

from __future__ import annotations

import argparse
import json
import math
import sys
import tempfile
import time

import numpy as np

STALL_S = 0.5


def _calls(ring, window) -> list[tuple]:
    """The ``engine.run`` spans that start inside ``window``."""
    ws, we = window
    return [s for s in ring if s[0] == "engine.run" and ws <= s[1] < we]


def _index(ring, name: str) -> tuple[np.ndarray, np.ndarray]:
    """Starts and ends of the spans named ``name``, by start."""
    iv = np.array(sorted((s[1], s[2]) for s in ring if s[0] == name),
                  float).reshape(-1, 2)
    return iv[:, 0], iv[:, 1]


def _time_in(index, a: float, b: float) -> float:
    """Seconds of the indexed spans that lie inside ``[a, b]``."""
    t0, t1 = index
    i, j = np.searchsorted(t0, a, "left"), np.searchsorted(t0, b, "right")
    inside = t1[i:j] <= b
    return float((t1[i:j] - t0[i:j])[inside].sum())


def host_ms(ring, window) -> float | None:
    """Median over the step calls starting in ``window`` of ``engine.run``
    less the ``engine.sync`` inside it, in ms."""
    calls = _calls(ring, window)
    if not calls:
        return None
    syncs = _index(ring, "engine.sync")
    return 1e3 * float(np.median([(b - a) - _time_in(syncs, a, b)
                                  for _, a, b, _, _ in calls]))


def admit_wait_p90(ring, rids) -> float | None:
    """90th percentile of submit to a lane over ``rids`` (inf: never
    placed)."""
    from bench import stats

    if not rids:
        return None
    waited = {s[4]: s[2] - s[1] for s in ring if s[0] == "request.queued"}
    return stats.percentile([waited.get(r, math.inf) for r in rids], 90)


def span_ms(ring, window, names) -> dict[str, float]:
    """Median time per step call spent in each of ``names`` (0 in a call
    without it), over the step calls starting in ``window``, in ms."""
    calls = _calls(ring, window)
    if not calls:
        return {}
    out = {}
    for name in names:
        index = _index(ring, name)
        out[name] = 1e3 * float(np.median([_time_in(index, a, b)
                                           for _, a, b, _, _ in calls]))
    return out


def self_times(held) -> list[tuple[str, float, float]]:
    """``(name, seconds, self seconds)`` of each span in ``held`` (one
    step call's spans): self time leaves out the spans it directly holds."""
    out = []
    for s in held:
        kids = [c for c in held if c is not s and c[3] == s[0]
                and s[1] <= c[1] and c[2] <= s[2]]
        out.append((s[0], s[2] - s[1],
                    (s[2] - s[1]) - sum(c[2] - c[1] for c in kids)))
    return out


def step_call(ring, run, k: int, names) -> dict:
    """Step call ``k`` of ``run``: when (seconds from the window's start),
    how long, the engine spans inside it with their seconds, and the one
    with the most self time."""
    a, b = run.step_t0[k], run.step_t1[k]
    times = self_times([s for s in ring
                        if s[0] in names and a <= s[1] and s[2] <= b])
    return {"at": a - run.window[0], "s": b - a,
            "spans": [[n, d] for n, d, _ in times],
            "held_by": max(times, key=lambda x: x[2])[0] if times else None}


def stalls(ring, run, names, over: float = STALL_S) -> list[dict]:
    """Every step call of ``run`` longer than ``over`` (``step_call``)."""
    long = np.flatnonzero(run.step_t1 - run.step_t0 > over)
    return [step_call(ring, run, int(k), names) for k in long]


def recorder_us(n: int = 20_000) -> dict[str, float]:
    """Microseconds per span (opened and closed) and per request record,
    with the profiler off, and per span with it on."""
    from bench import measure
    from repro.serve.spans import Recorder

    def per_span():
        rec = Recorder()
        t0 = time.perf_counter()
        for i in range(n):
            with rec.span("engine.cost", i):
                pass
        return 1e6 * (time.perf_counter() - t0) / n

    rec = Recorder()
    t0 = time.perf_counter()
    for i in range(n):
        rec.record("request.queued", rec.now(), rec.now(), i)
    record = 1e6 * (time.perf_counter() - t0) / n
    off = per_span()
    with tempfile.TemporaryDirectory(prefix="bench-cost-") as tmp:
        start, stop = measure._profiler(tmp)     # as a traced run starts it
        start()
        try:
            on = per_span()
        finally:
            stop()
    return {"span_off": off, "span_on": on, "record_off": record}


def measure_with_record(cell, seed: int, seconds: float, trace: bool,
                        devices, peaks) -> tuple[dict, dict]:
    """One benchmark run (``measure.measure``) that also keeps the
    engine's record, the run, and with a trace its idle split."""
    from bench import measure
    from bench.trace import nested, reduce

    kept: dict = {}
    real = (measure.build_engine, measure.drive, measure._trace_file)

    def build_engine(*a, **kw):
        engine = real[0](*a, **kw)
        kept["record"] = engine.spans
        return engine

    def drive(*a, **kw):
        kept["run"] = real[1](*a, **kw)
        return kept["run"]

    def trace_file(tmp):
        path = real[2](tmp)
        ev, inner = reduce.load(path), nested.load_inner(path)
        kept["idle_split"] = nested.idle_split(ev, inner)
        kept["idle_overlap"] = nested.idle_overlap(ev, inner)
        return path

    measure.build_engine, measure.drive, measure._trace_file = (
        build_engine, drive, trace_file)
    try:
        res = measure.measure(cell, seed, seconds, trace, devices, peaks,
                              time.perf_counter())
    finally:
        measure.build_engine, measure.drive, measure._trace_file = real
    return res, kept


def readings(kept: dict) -> dict:
    """What the module's docstring lists, from ``measure_with_record``'s
    ``kept``; ``longest_step`` is the window's longest step call."""
    from repro.serve.spans import ENGINE_SPANS

    ring, run = list(kept["record"].ring), kept["run"]
    steps = _calls(ring, run.window)
    opened = sum(1 for s in ring if s[0] in ENGINE_SPANS
                 and run.window[0] <= s[1] < run.window[1])
    out = {"engine_host_ms": host_ms(ring, run.window),
           "admit_wait_p90_s": admit_wait_p90(ring, [r.rid for r in run.attempted]),
           "span_ms": span_ms(ring, run.window, ENGINE_SPANS),
           "spans_per_step": opened / max(len(steps), 1),
           "plan_cache_hits": kept["record"].counts["plan_cache_hits"],
           "stalls": stalls(ring, run, ENGINE_SPANS)}
    ws, we = run.window
    inside = (run.step_t0 >= ws) & (run.step_t0 < we)
    if inside.any():
        k = int(np.where(inside, run.step_t1 - run.step_t0, -1).argmax())
        out["longest_step"] = step_call(ring, run, k, ENGINE_SPANS)
    for key in ("idle_split", "idle_overlap"):
        if key in kept:
            out[key] = [list(x) for x in kept[key]]
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="python bench/engine_record.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from run import use_compile_cache  # sets sys.path

    from bench import cells, device

    cell = cells.load_cell(args.workload)
    use_compile_cache()
    devices, peaks = device.accelerator(cell.chips)
    for seed in args.seeds:
        res, kept = measure_with_record(cell, seed, args.seconds,
                                        bool(args.trace), devices, peaks)
        print(json.dumps({"workload": cell.name, "seed": seed,
                          "correct": res["correct"],
                          "metrics": {k: v["value"]
                                      for k, v in res["metrics"].items()},
                          "device": res["device"],
                          "breakdown": res.get("breakdown"),
                          **readings(kept),
                          "recorder_us": recorder_us()}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
