"""FLOPs and bytes of the work a window did, from a model family's counts.

Each family module (``bench/reference/<module>.py``, named by the
configuration file's ``"reference"``) reckons from the configuration
file's own numbers, never from the program, what one unit of work costs
(``counts(conf)``, a ``Counts``).  What is generic is the arithmetic over
the recorded work (``stats.Work``): FLOPs over the positions processed
and the positions they attend, and the least bytes a step must move.
"""

from __future__ import annotations

import dataclasses

import numpy as np

from .stats import Work


@dataclasses.dataclass(frozen=True)
class Counts:
    per_position: int   # matmul FLOPs per position processed
    per_attended: int   # attention FLOPs per attended position
    weights: int        # bytes of weights, each read once per step
    cache: int          # bytes per position held in the cache
    state: int          # bytes of recurrent state per lane


def flops(c: Counts, w: Work) -> np.ndarray:
    """Model FLOPs of the work in each interval."""
    return c.per_position * w.positions + c.per_attended * w.attended


def least_bytes(c: Counts, w: Work) -> np.ndarray:
    """Least bytes each interval, taken as one step, must move: every
    weight once where the model did any work, the cache of every position
    held on a lane, and each lane's recurrent state read once and written
    once."""
    return (np.where(w.positions > 0, c.weights, 0) + c.cache * w.cached
            + 2 * c.state * w.lanes)
