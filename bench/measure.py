"""One run of one cell: set-up, the measured window, the check, the line.

Set-up is everything from process start until the engine is ready to
serve: the weights made on the device from the seed, the engine built,
every shape the window uses warmed up (the step at ``(slots, 1)``, the
lane splice, the argmax).  Compilation is counted as set-up; a
compilation inside the window is counted and printed.  In an open loop
``pre_s`` seconds of arrivals follow, so the window opens in steady
state; they are traffic, not set-up.
"""

from __future__ import annotations

import dataclasses
import gc
import math
import shutil
import sys
import tempfile
import time
from types import ModuleType
from typing import Any, Optional

import jax
import numpy as np

from . import cells, check, stats
from .drive import Run, Tracer, drive
from .trace import reduce
from .traffic import closed_backlog, open_loop
from .weights import make_weights

COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration",
                  "/jax/compilation_cache/cache_retrieval_time_sec")
BUILD_EVENTS = ("/jax/core/compile/backend_compile_duration",
                "/jax/compilation_cache/cache_retrieval_time_sec")
TRACE_S = 3.0      # the traced part of a --trace 1 window, at its end


class CompileLog:
    """Time spent turning programs into executables, with when."""

    def __init__(self, listen: bool = True):
        self.events: list[tuple[float, str, float]] = []
        if listen:
            jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, secs: float, **_: Any) -> None:
        if event in COMPILE_EVENTS:
            self.events.append((time.perf_counter(), event, secs))

    def seconds(self, until: float) -> float:
        return sum(s for t, _, s in self.events if t < until)

    def built(self, lo: float, hi: float) -> int:
        """Programs compiled or loaded from the cache in [lo, hi)."""
        return sum(1 for t, e, _ in self.events
                   if lo <= t < hi and e in BUILD_EVENTS)


class GcLog:
    """Pauses of Python's garbage collector, with when."""

    def __init__(self, listen: bool = True):
        self.pauses: list[tuple[float, float, int]] = []
        self._t = math.nan
        self.listen = listen
        if listen:
            gc.callbacks.append(self._on)

    def _on(self, phase: str, info: dict) -> None:
        now = time.perf_counter()
        if phase == "start":
            self._t = now
        else:
            self.pauses.append((self._t, now - self._t, info["generation"]))

    def close(self) -> None:
        if self.listen:
            gc.callbacks.remove(self._on)


def host_pauses(run: Run, gcs: GcLog) -> str:
    """The longest step call, the longest gap between step calls and the
    longest garbage collection inside the window, each with when (seconds
    from the window's start)."""
    ws, we = run.window
    parts = []
    if len(run.step_t1):
        inside = (run.step_t0 >= ws) & (run.step_t0 < we)
        dt = np.where(inside, run.step_t1 - run.step_t0, 0)
        k = int(dt.argmax())
        parts.append(f"longest step {dt[k]:.6f} s at {run.step_t0[k] - ws:.3f}")
        gap = np.where(inside[1:], run.step_t0[1:] - run.step_t1[:-1], 0)
        if gap.size:
            k = int(gap.argmax())
            parts.append(f"longest gap between steps {gap[k]:.6f} s at "
                         f"{run.step_t1[k] - ws:.3f}")
    pauses = [p for p in gcs.pauses if ws <= p[0] < we]
    if pauses:
        t, d, g = max(pauses, key=lambda p: p[1])
        parts.append(f"{len(pauses)} garbage collections, longest {d:.6f} s "
                     f"(generation {g}) at {t - ws:.3f}")
    return "host: " + "; ".join(parts)


@dataclasses.dataclass
class RunView:
    """What a metric reader gets."""

    run: Run
    conf: dict            # the configuration file
    peaks: dict
    setup_s: float        # process start to the engine ready to serve
    setup_compile_s: float
    trace: Optional[reduce.Summary]
    family: ModuleType    # the configuration's family module


def build_engine(cfg, weights, slots: int, max_len: int, device):
    from repro.serve.engine import DecodeEngine

    return DecodeEngine(cfg, weights, slots=slots, max_len=max_len,
                        device=device)


def warm_up(engine, slots: int) -> None:
    """One request more than there are lanes, so a lane is reused."""
    from repro.serve.scheduler import Request

    for i in range(slots + 1):
        engine.submit(Request(rid=-1 - i, arrival=0.0, prompt_len=2,
                              max_new_tokens=2), prompt=[1, 2])
    engine.run()


def _profiler(tmp: str):
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    return (lambda: jax.profiler.start_trace(tmp, profiler_options=opts),
            jax.profiler.stop_trace)


def _trace_file(tmp: str) -> str:
    import glob

    found = glob.glob(f"{tmp}/**/*.xplane.pb", recursive=True)
    if len(found) != 1:
        raise RuntimeError(f"expected one trace file, found {found}")
    return found[0]


def say(*parts) -> None:
    print(*parts, flush=True)


def measure(cell: cells.Cell, seed: int, seconds: float, trace: bool,
            devices, peaks: dict, start: float, *, control: bool = False,
            listen: bool = True) -> dict:
    """One run.  ``control`` also reads the family's control on the same
    sample and judges it by the same limits (``result["control"]``);
    ``listen=False`` leaves out the compile and garbage-collection
    listeners.  Neither is used by a benchmark run."""
    log = CompileLog(listen)
    gcs = GcLog(listen)
    conf = cell.config
    cfg = cells.model_config(conf, cell.root)
    dev = devices[0]
    slots, max_len = conf["slots"], conf["max_len"]
    marks = [time.perf_counter()]
    weights = make_weights(cfg, cell.family, seed, dev)
    jax.block_until_ready(weights)
    marks.append(time.perf_counter())
    engine = build_engine(cfg, weights, slots, max_len, dev)
    marks.append(time.perf_counter())
    warm_up(engine, slots)
    marks.append(time.perf_counter())
    say("set-up s: start to weights {:.3f}, weights {:.3f}, engine {:.3f}, "
        "warm-up {:.3f}".format(marks[0] - start, *np.diff(marks)))

    mix = cell.traffic
    is_open = mix["loop"] == "open"
    if is_open:
        traffic = open_loop(mix, seed, seconds, vocab=cfg.vocab_size,
                            max_len=max_len)
    else:
        traffic = closed_backlog(mix, seed, vocab=cfg.vocab_size,
                                 max_len=max_len)
    tmp = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    tracer = (Tracer(max(0.0, seconds - TRACE_S), seconds, *_profiler(tmp))
              if trace else None)
    ready = time.perf_counter()
    run = drive(engine, traffic, open_loop=is_open, seconds=seconds,
                pre_s=mix.get("pre_s", 0.0),
                backlog=mix.get("backlog_per_slot", 0) * slots,
                drain_cap_s=mix.get("drain_cap_s", 0.0), tracer=tracer)
    ws, we = run.window
    late = stats.lateness(run)
    say(f"window: {we - ws:.3f} s, {len(run.step_t1)} steps, "
        f"{len(run.requests)} requests submitted, {len(run.attempted)} "
        f"attempted, drain ended {run.drain_end - we:.3f} s after the close")
    say(f"generator lateness s: median {np.median(late):.6f} "
        f"p99 {np.percentile(late, 99):.6f} max {late.max():.6f}")
    say(f"programs built inside the window: {log.built(ws, we)}")
    say(host_pauses(run, gcs))
    gcs.close()
    memory_peak = (dev.memory_stats() or {}).get("peak_bytes_in_use", 0)

    picked = check.sample(run.attempted, seed, conf["check"]["served_tokens"])
    outputs = {r.rid: list(engine.output(r.rid)) for r in picked}
    del engine
    gc.collect()

    summary = None
    if trace:
        summary = reduce.summarize(reduce.load(_trace_file(tmp)))
        shutil.rmtree(tmp, ignore_errors=True)

    t_ref = time.perf_counter()
    numbers = check.compare(cell.family, conf, weights, outputs, picked,
                            control=control)
    ok, checks = check.verdict(conf, numbers)
    say(f"reference: {numbers['requests_compared']} requests, "
        f"{numbers['tokens_compared']} served tokens, "
        f"{time.perf_counter() - t_ref:.3f} s")

    view = RunView(run=run, conf=conf, peaks=peaks, setup_s=ready - start,
                   setup_compile_s=log.seconds(ready), trace=summary,
                   family=cell.family)
    metrics = {}
    for m in cell.per_layer if trace else cell.end_to_end:
        value = cells.metric_reader(m["name"], cell.root)(view)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    device_line = {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(jax.devices()),
                   "memory_peak_bytes": memory_peak}
    result: dict = {"correct": ok, "attempted": len(run.attempted),
                    "failed": stats.failed(run), "metrics": metrics,
                    "device": device_line}
    if summary is not None:
        device_line["busy_s"] = summary.busy_s
        device_line["window_s"] = summary.window_s
        result["breakdown"] = {"device_ops": [list(x) for x in summary.device_ops],
                               "idle_gaps": [list(x) for x in summary.idle_gaps]}
        name, calls, secs = summary.main_program()
        say(f"main program {name}: {calls} calls, {secs:.6f} s on the device")
    if control:
        ctl = check.control_numbers(numbers)
        ctl_ok, _ = check.verdict(conf, ctl)
        result["control"] = {"correct": ctl_ok, **ctl}
        result["numbers"] = numbers
    result["checks"] = checks
    for name, c in checks.items():
        print(f"check {name}: {c}", file=sys.stderr, flush=True)
    return result

