"""Arithmetic shared by the per-layer metric readers in ``metrics/``.

A reader returns ``None`` where it finds nothing to read (no trace in a
``--trace 0`` run), never 0 for a share.
"""

from __future__ import annotations

import numpy as np

from . import reckon, stats


def idle_share(view):
    """Percent of the traced window in which the device ran nothing."""
    return None if view.trace is None else 100.0 * view.trace.idle_share


def step_device_ms(view):
    """Device time per call of the program that takes the most of it
    (the engine's decode step)."""
    if view.trace is None:
        return None
    _, calls, secs = view.trace.main_program()
    return 1e3 * secs / calls


def step_mfu(view):
    """Model FLOPs of the work done inside the window, over the window at
    the chip's bf16 peak, in percent."""
    run = view.run
    done = stats.work(run, run.window)
    if not done.positions[0]:
        return None
    f = float(reckon.flops(view.family.counts(view.conf), done)[0])
    return 100.0 * f / (run.seconds * view.peaks["bf16_flops_per_s"])


def step_roofline(view):
    """Least time a traced step could take (the larger of its FLOPs at
    peak and its least bytes at peak bandwidth), over the step program's
    device time per call, in percent.  A step's work is that of the
    interval from the previous step's end to its own."""
    if view.trace is None or view.run.trace_span is None:
        return None
    run = view.run
    a, b = run.trace_span
    k = np.flatnonzero((run.step_t0 >= a) & (run.step_t1 <= b))
    if k.size == 0 or k[0] == 0:
        return None
    done = stats.work(run, run.step_t1[np.r_[k[0] - 1, k]])
    c = view.family.counts(view.conf)
    bound = np.maximum(reckon.flops(c, done) / view.peaks["bf16_flops_per_s"],
                       reckon.least_bytes(c, done) / view.peaks["hbm_bytes_per_s"])
    _, calls, secs = view.trace.main_program()
    return 100.0 * float(bound.mean()) / (secs / calls)
