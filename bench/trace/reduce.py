"""From a profiler trace (``.xplane.pb``) to device busy time, the top
device operations, the programs run, and the device's idle gaps by what
the host was doing.

The traced window is the span of the harness's own host annotations
(``HOST_SPANS``) in the trace: device events are counted inside it only.
Busy time is the union of the intervals of every event on each device's
"XLA Ops" and "XLA Modules" lines, averaged over the devices.  A gap is
a stretch of the window in which no event of a device runs; it is
charged to the host span that covers its midpoint ("outside" where none
does).
"""

from __future__ import annotations

import collections
import dataclasses
import re

import numpy as np

HOST_SPANS = ("wait_arrival", "submit", "engine_step", "bookkeeping")
# control-flow ops whose interval holds other ops: not operations of their own
CONTAINERS = ("while", "conditional", "call")


@dataclasses.dataclass
class Events:
    """Intervals in nanoseconds on the trace's clock."""

    ops: dict[str, list[tuple[str, float, float]]]       # per device
    modules: dict[str, list[tuple[str, float, float]]]   # per device
    spans: list[tuple[str, float, float]]                # harness, host


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float                      # averaged over devices
    device_ops: list[tuple[str, float]]
    idle_gaps: list[tuple[str, float]]
    programs: dict[str, tuple[int, float]]   # name -> (calls, device s)

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def main_program(self) -> tuple[str, int, float]:
        """The program with the most device time: the engine's step."""
        name, (calls, secs) = max(self.programs.items(),
                                  key=lambda kv: kv[1][1])
        return name, calls, secs


def op_label(hlo: str) -> str:
    """``%fusion.12 = bf16[16,9728]{...} fusion(...)`` -> ``fusion.12
    bf16[16,9728]``: the instruction's trace name and result type
    (``tuple`` for a tuple)."""
    m = re.match(r"%?([\w.\-]+) = (\(|[^{( ]+)", hlo)
    if not m:
        return hlo[:80]
    return f"{m.group(1)} {'tuple' if m.group(2) == '(' else m.group(2)}"


def load(path: str) -> Events:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(path))
    ops: dict = {}
    modules: dict = {}
    spans = []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name in ("XLA Ops", "XLA Modules"):
                    evs = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                           for e in line.events]
                    (ops if line.name == "XLA Ops" else modules)[plane.name] = evs
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                spans += [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                          for e in line.events if e.name in HOST_SPANS]
    spans.sort(key=lambda s: s[1])
    return Events(ops=ops, modules=modules, spans=spans)


def busy_intervals(evs, lo: float, hi: float) -> np.ndarray:
    """Union of the event intervals clipped to [lo, hi], as (k, 2)."""
    iv = np.array([(max(s, lo), min(e, hi)) for _, s, e in evs
                   if e > lo and s < hi], float).reshape(-1, 2)
    if iv.size == 0:
        return iv
    iv = iv[np.argsort(iv[:, 0])]
    merged = [list(iv[0])]
    for s, e in iv[1:]:
        if s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return np.array(merged)


def _host_span_at(spans, starts: np.ndarray, t: float) -> str:
    i = int(np.searchsorted(starts, t, side="right")) - 1
    if i >= 0 and spans[i][2] >= t:
        return spans[i][0]
    return "outside"


def summarize(ev: Events, top: int = 10) -> Summary:
    if not ev.spans:
        raise ValueError("no harness spans in the trace")
    lo = ev.spans[0][1]
    starts = np.array([s for _, s, _ in ev.spans])
    hi = max(e for _, _, e in ev.spans)
    devices = sorted(set(ev.ops) | set(ev.modules))
    if not devices:
        raise ValueError("no device events in the trace")
    busy = []
    gaps: collections.Counter = collections.Counter()
    op_time: collections.Counter = collections.Counter()
    programs: dict[str, list] = {}
    for dev in devices:
        evs = ev.ops.get(dev, []) + ev.modules.get(dev, [])
        iv = busy_intervals(evs, lo, hi)
        busy.append(float((iv[:, 1] - iv[:, 0]).sum()) if iv.size else 0.0)
        edges = np.concatenate([[lo], iv.ravel(), [hi]]).reshape(-1, 2)
        for s, e in edges:
            if e > s:
                at = _host_span_at(ev.spans, starts, (s + e) / 2)
                gaps[at] += float(e - s) * 1e-9
        for name, s, e in ev.ops.get(dev, []):
            if lo <= s < hi:
                label = op_label(name)
                if not label.startswith(CONTAINERS):
                    op_time[label] += (e - s) * 1e-9
        for name, s, e in ev.modules.get(dev, []):
            if lo <= s < hi:
                p = programs.setdefault(name, [0, 0.0])
                p[0] += 1
                p[1] += (e - s) * 1e-9
    n = len(devices)
    return Summary(
        window_s=(hi - lo) * 1e-9,
        busy_s=sum(busy) / n * 1e-9,
        device_ops=[(k, v / n) for k, v in op_time.most_common(top)],
        idle_gaps=[(k, v / n) for k, v in gaps.most_common(top)],
        programs={k: (c, s / n) for k, (c, s) in programs.items()})
