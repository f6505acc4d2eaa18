"""Jit'd public wrapper for the flash-attention Pallas kernels.

`flash_attention` accepts model-layout tensors (b, s, h, hd) with separate
kv-head counts (GQA/MQA) and handles head broadcast, flattening, padding,
and passes `interpret` through (True only for CPU validation).

Passing ``schedule=`` routes through the schedule-aware kernel
(`flash_attention_sched_bhsd`): the KV-tile grid order is produced by the
DLS planner instead of the implicit identity order, and ragged per-batch
KV lengths (``kv_lens``) are supported — see
`repro.core.jax_sched.plan_tiles_for_kernel`.
"""

from __future__ import annotations

import functools
from typing import Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np

from .flash_attention import flash_attention_bhsd, flash_attention_sched_bhsd


def _broadcast_flatten(q, k, v):
    """(b, s, h|kvh, hd) -> three (b*h, s, hd) lane-major tensors."""
    b, s, h, hd = q.shape
    kvh = k.shape[2]
    if kvh != h:
        g = h // kvh
        k = jnp.broadcast_to(k[:, :, :, None, :],
                             (b, s, kvh, g, hd)).reshape(b, s, h, hd)
        v = jnp.broadcast_to(v[:, :, :, None, :],
                             (b, s, kvh, g, hd)).reshape(b, s, h, hd)

    def flat(x):
        return x.transpose(0, 2, 1, 3).reshape(b * h, s, hd)

    return flat(q), flat(k), flat(v)


@functools.partial(jax.jit, static_argnames=("causal", "window", "block_q",
                                             "block_k", "interpret"))
def _flash_attention_dense(q, k, v, *, causal, window, block_q, block_k,
                           interpret):
    b, s, h, hd = q.shape
    qf, kf, vf = _broadcast_flatten(q, k, v)
    out = flash_attention_bhsd(qf, kf, vf, causal=causal, window=window,
                               block_q=block_q, block_k=block_k,
                               interpret=interpret)
    return out.reshape(b, h, s, hd).transpose(0, 2, 1, 3)


def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    block_q: int = 512, block_k: int = 512,
                    interpret: bool = False,
                    schedule: Union[str, object, None] = None,
                    kv_lens: Optional[Sequence[int]] = None,
                    sched_p: int = 8, recorder=None):
    """q: (b, s, h, hd); k, v: (b, s, kvh, hd) -> (b, s, h, hd).

    ``schedule`` (a ScheduleSpec / registry name) selects the DLS-planned
    kernel; ``kv_lens`` is a host array of per-batch valid KV lengths
    (ragged decode lanes) — columns past a lane's length are masked.
    ``recorder`` (LoopRecorder) collects the plan's kernel telemetry.
    """
    if schedule is None:
        if kv_lens is not None:
            raise ValueError("kv_lens requires schedule= (the DLS-planned "
                             "kernel); the dense grid has no ragged path")
        return _flash_attention_dense(q, k, v, causal=causal, window=window,
                                      block_q=block_q, block_k=block_k,
                                      interpret=interpret)
    b, s, h, hd = q.shape
    qf, kf, vf = _broadcast_flatten(q, k, v)
    lane_lens = None
    if kv_lens is not None:
        lane_lens = np.repeat(np.asarray(kv_lens, np.int64), h)  # per lane
    out = flash_attention_sched_bhsd(
        qf, kf, vf, schedule=schedule, kv_lens=lane_lens, causal=causal,
        window=window, block_q=block_q, block_k=block_k, sched_p=sched_p,
        interpret=interpret, recorder=recorder)
    return out.reshape(b, h, s, hd).transpose(0, 2, 1, 3)
