"""Device idle gaps charged to the innermost host span that covers them.

``reduce.summarize`` charges each idle gap to the harness span
(``reduce.HOST_SPANS``) over its midpoint.  The engine's own spans
(``repro.serve.spans.ENGINE_SPANS``, each a ``TraceAnnotation`` nested
inside the harness's ``engine_step``) split that charge further: a gap
under ``engine_step`` goes to the innermost engine span over its
midpoint, and one under no engine span keeps ``engine_step``.  The
harness spans alone set the window, so the window, the busy time, the
device operations and the programs are ``reduce.summarize``'s own.

A gap that spans several host phases goes whole to the one over its
midpoint; ``idle_overlap`` cuts it at every span's edge instead, so each
phase is charged the idle time it actually covered.
"""

from __future__ import annotations

import collections
import dataclasses

import numpy as np

from . import reduce

try:
    from repro.serve.spans import ENGINE_SPANS
except ModuleNotFoundError:     # a program without the engine's span record
    ENGINE_SPANS = ()


def load_inner(path: str) -> list[tuple[str, float, float]]:
    """The engine's spans in a trace, in nanoseconds on its clock."""
    from jax.profiler import ProfileData

    inner = []
    for plane in ProfileData.from_file(str(path)).planes:
        if plane.name == "/host:CPU":
            for line in plane.lines:
                inner += [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                          for e in line.events if e.name in ENGINE_SPANS]
    return inner


def _labeler(ev: reduce.Events, inner):
    """A function from a time in the window to the innermost span over it:
    the harness span (``outside`` where none is), and under
    ``engine_step`` the innermost engine span, if any."""
    starts = np.array([s for _, s, _ in ev.spans])
    inner = sorted(inner, key=lambda s: (s[1], -s[2]))   # outer first at a tie
    inner_starts = np.array([s for _, s, _ in inner])

    def label(t: float) -> str:
        i = int(np.searchsorted(starts, t, side="right")) - 1
        if i < 0 or ev.spans[i][2] < t:
            return "outside"
        name, floor = ev.spans[i][0], ev.spans[i][1]
        if name == "engine_step":
            k = int(np.searchsorted(inner_starts, t, side="right")) - 1
            while k >= 0 and inner[k][1] >= floor:
                if inner[k][2] >= t:
                    return inner[k][0]
                k -= 1
        return name

    return label


def _gaps(ev: reduce.Events):
    """Every idle gap ``(start, end)`` of every device in the window, and
    the number of devices."""
    if not ev.spans:
        raise ValueError("no harness spans in the trace")
    lo = ev.spans[0][1]
    hi = max(e for _, _, e in ev.spans)
    devices = sorted(set(ev.ops) | set(ev.modules))
    gaps = []
    for dev in devices:
        iv = reduce.busy_intervals(ev.ops.get(dev, []) + ev.modules.get(dev, []),
                                   lo, hi)
        edges = np.concatenate([[lo], iv.ravel(), [hi]]).reshape(-1, 2)
        gaps += [(s, e) for s, e in edges if e > s]
    return gaps, len(devices)


def idle_split(ev: reduce.Events, inner) -> list[tuple[str, float]]:
    """Idle seconds by the innermost span over each gap's midpoint, most
    first, averaged over the devices."""
    label = _labeler(ev, inner)
    gaps, n = _gaps(ev)
    by: collections.Counter = collections.Counter()
    for s, e in gaps:
        by[label((s + e) / 2)] += float(e - s) * 1e-9
    return [(k, v / n) for k, v in by.most_common()]


def idle_overlap(ev: reduce.Events, inner) -> list[tuple[str, float]]:
    """Idle seconds by the innermost span over each part of a gap: a gap
    is cut at every span's start and end, and each piece charged by its
    midpoint.  Most first, averaged over the devices."""
    label = _labeler(ev, inner)
    cuts = np.unique([t for _, a, b in [*ev.spans, *inner] for t in (a, b)])
    gaps, n = _gaps(ev)
    by: collections.Counter = collections.Counter()
    for s, e in gaps:
        i, j = np.searchsorted(cuts, s, "right"), np.searchsorted(cuts, e, "left")
        pts = [s, *cuts[i:j], e]
        for a, b in zip(pts[:-1], pts[1:]):
            by[label((a + b) / 2)] += float(b - a) * 1e-9
    return [(k, v / n) for k, v in by.most_common()]


def summarize(ev: reduce.Events, inner, top: int = 10) -> reduce.Summary:
    """``reduce.summarize`` with the idle gaps split by ``inner``."""
    return dataclasses.replace(reduce.summarize(ev, top),
                               idle_gaps=idle_split(ev, inner))
