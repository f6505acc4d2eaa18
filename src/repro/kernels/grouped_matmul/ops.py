"""Jit'd wrapper: expert-capacity layout (E, C, d) -> DLS-planned tiles ->
grouped matmul -> (E, C, f).

`moe_expert_ffn` is the kernel-backed equivalent of the einsum in
models.moe._expert_ffn's ragged path: the (E, C) capacity buffer is cut
into row tiles of `block_rows`, the tile list is ordered by the DLS
planner (see repro.balance.moe.plan_tiles), and each tile hits the MXU
against its expert's weights.

Passing ``schedule=`` (any registry technique / ScheduleSpec) plans the
tile order *inside* this wrapper from the measured per-expert loads
(``expert_rows``, host telemetry) via
`repro.core.jax_sched.plan_tiles_for_kernel` — the schedule-aware path
the MoE balancer and the kernel benchmark drive.
"""

from __future__ import annotations

import functools
from typing import Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np

from .grouped_matmul import grouped_matmul_tiles


@functools.partial(jax.jit,
                   static_argnames=("block_rows", "interpret"))
def _grouped_matmul_core(xe, weights, tile_order, *, block_rows: int,
                         interpret: bool):
    e, c, d = xe.shape
    f = weights.shape[2]
    assert c % block_rows == 0, (c, block_rows)
    tiles_per_e = c // block_rows
    t = e * tiles_per_e
    x_tiles = xe.reshape(t, block_rows, d)
    tile_expert = (jnp.arange(t, dtype=jnp.int32) // tiles_per_e)
    if tile_order is not None:
        x_tiles = x_tiles[tile_order]
        tile_expert = tile_expert[tile_order]
    out = grouped_matmul_tiles(x_tiles, weights, tile_expert,
                               interpret=interpret)
    if tile_order is not None:
        inv = jnp.zeros_like(tile_order).at[tile_order].set(
            jnp.arange(t, dtype=tile_order.dtype))
        out = out[inv]
    return out.reshape(e, c, f)


def grouped_matmul(xe, weights, tile_order=None, *, block_rows: int = 128,
                   interpret: bool = False,
                   schedule: Union[str, object, None] = None,
                   expert_rows: Optional[Sequence[int]] = None,
                   sched_p: int = 8, recorder=None):
    """xe: (E, C, d) capacity layout; weights (E, d, f) -> (E, C, f).

    tile_order: optional (T,) permutation of tile ids from the DLS
    planner (T = E * C / block_rows); identity if omitted.

    schedule: plan the tile order here instead — DLS chunking of the
    live tiles given ``expert_rows`` (host array of live rows per expert;
    defaults to full capacity, i.e. uniform cost).  ``sched_p`` is the
    planner's notional core count and ``recorder`` (LoopRecorder)
    receives the plan's kernel telemetry.  Mutually exclusive with an
    explicit ``tile_order``.
    """
    if schedule is not None:
        if tile_order is not None:
            raise ValueError("pass either tile_order or schedule, not both")
        from repro.balance.moe import plan_tiles  # deferred: avoids a
        # kernels -> balance import at module load

        e, c, _ = xe.shape
        rows = (np.full(e, c, np.int64) if expert_rows is None
                else np.asarray(expert_rows, np.int64))
        order, plan = plan_tiles(rows, block_rows, p=sched_p,
                                 technique=schedule, capacity_rows=c,
                                 return_plan=True)
        if recorder is not None:
            recorder.add(plan.to_record(
                "grouped_matmul",
                instance=recorder.next_instance("grouped_matmul")))
        tile_order = jnp.asarray(order)
    return _grouped_matmul_core(xe, weights, tile_order,
                                block_rows=block_rows, interpret=interpret)
