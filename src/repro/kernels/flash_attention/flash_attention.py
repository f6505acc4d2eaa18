"""Pallas TPU flash-attention forward kernels.

Two variants share the online-softmax math:

  * `flash_attention_bhsd` — dense (bh, q_blocks, kv_blocks) grid with the
    kv dimension innermost — TPU executes the grid sequentially
    minor-to-major, so the running state (m, l, acc) lives in VMEM scratch
    and is carried across kv steps of one q block.  Causal (and
    sliding-window) masking skips fully-masked kv blocks via pl.when,
    which on real hardware elides both the DMA wait and the MXU work for
    the upper triangle — the half of the quadratic the pure-JAX reference
    (models/attention._attend_flash) cannot avoid under XLA.

  * `flash_attention_sched_bhsd` — the schedule-aware form: a 1-D grid
    over only the *live* (lane, q block, kv block) triples, driven by
    scalar-prefetch descriptor arrays the BlockSpec index maps consume
    (megablox-style).  The q-block group order is produced by the DLS
    planner (`repro.core.jax_sched.plan_tiles_for_kernel`) from per-group
    live-KV costs — causal triangles and ragged per-lane KV lengths give
    q blocks wildly different work, and LB4OMP-style chunked assignment
    makes a contiguous multi-core split of the grid near-balanced, where
    the implicit identity order leaves tail cores idle.  Each group's kv
    steps stay contiguous and ascending (the online-softmax state carries
    in scratch), so outputs are bit-identical for every technique — only
    the group order over the grid changes.

Block shapes are MXU-aligned (multiples of 128 on the contracted dims;
block_q x block_k tiles in VMEM).  VMEM budget per grid step:
    q (bq, hd) + k (bk, hd) + v (bk, hd) + acc (bq, hd) + m/l (bq)
with bq = bk = 512, hd <= 256 in fp32 scratch ~= 1.6 MiB — well inside the
~16 MiB/core VMEM of v5e.

Validated in interpret mode against ref.py (tests/test_kernels.py,
tests/test_kernel_sched.py).
"""

from __future__ import annotations

import functools
import math
from typing import Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                  block_q: int, block_k: int, seq_len: int, causal: bool,
                  window: int, scale: float):
    qi = pl.program_id(1)
    kj = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(kj == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_start = qi * block_q
    k_start = kj * block_k

    # a kv block is live unless it is entirely above the causal diagonal
    # (or entirely outside the sliding window)
    live = True
    if causal:
        live = k_start <= q_start + block_q - 1
    if window > 0:
        live = jnp.logical_and(live,
                               q_start - (k_start + block_k - 1) < window)

    @pl.when(live)
    def _compute():
        q = q_ref[0].astype(jnp.float32)            # (bq, hd)
        k = k_ref[0].astype(jnp.float32)            # (bk, hd)
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # (bq, bk)
        rows = q_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        cols = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = cols < seq_len
        if causal:
            mask &= cols <= rows
        if window > 0:
            mask &= (rows - cols) < window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[...]
        l_prev = l_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = l_prev * corr + jnp.sum(p, axis=1)
        m_scr[...] = m_new
        pv = jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        acc_scr[...] = acc_scr[...] * corr[:, None] + pv

    @pl.when(kj == nk - 1)
    def _finalize():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0] = (acc_scr[...] / l[:, None]).astype(o_ref.dtype)


def flash_attention_bhsd(q, k, v, *, causal: bool = True, window: int = 0,
                         block_q: int = 512, block_k: int = 512,
                         interpret: bool = False):
    """q, k, v: (bh, s, hd) with KV already broadcast to the q-head count.

    Returns (bh, s, hd).  s is padded to the block size internally.
    """
    bh, s, hd = q.shape
    scale = 1.0 / math.sqrt(hd)
    block_q = min(block_q, max(s, 8))
    block_k = min(block_k, max(s, 8))
    nq = -(-s // block_q)
    nk = -(-s // block_k)
    pad_q = nq * block_q - s
    pad_k = nk * block_k - s
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0)))
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0)))

    kernel = functools.partial(
        _flash_kernel, block_q=block_q, block_k=block_k, seq_len=s,
        causal=causal, window=window, scale=scale)
    out = pl.pallas_call(
        kernel,
        grid=(bh, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, hd), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, hd), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, hd), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, hd), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, nq * block_q, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q, hd), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)
    return out[:, :s, :]


# ---------------------------------------------------------------------------
# Schedule-aware variant: DLS-planned descriptor grid over live KV tiles
# ---------------------------------------------------------------------------


def _flash_sched_kernel(bi_ref, qi_ref, kj_ref, fst_ref, lst_ref, lim_ref,
                        q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                        block_q: int, block_k: int, causal: bool,
                        window: int, scale: float):
    g = pl.program_id(0)

    @pl.when(fst_ref[g] == 1)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_start = qi_ref[g] * block_q
    k_start = kj_ref[g] * block_k
    lim = lim_ref[g]                       # this lane's valid KV length

    # every grid step is live by construction (the host planner emitted
    # only (lane, q, kv) triples with work) — no pl.when guard needed
    q = q_ref[0].astype(jnp.float32)       # (bq, hd)
    k = k_ref[0].astype(jnp.float32)       # (bk, hd)
    v = v_ref[0].astype(jnp.float32)
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale       # (bq, bk)
    rows = q_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    cols = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    mask = cols < lim
    if causal:
        mask &= cols <= rows
    if window > 0:
        mask &= (rows - cols) < window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_scr[...]
    l_prev = l_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
    p = jnp.exp(s - m_new[:, None])
    corr = jnp.exp(m_prev - m_new)
    l_scr[...] = l_prev * corr + jnp.sum(p, axis=1)
    m_scr[...] = m_new
    pv = jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    acc_scr[...] = acc_scr[...] * corr[:, None] + pv

    @pl.when(lst_ref[g] == 1)
    def _finalize():
        # rows with every column masked (ragged padding) keep m == NEG_INF;
        # zero them instead of emitting the uniform-softmax garbage.  The
        # mask is compared after the (bq,) -> (bq, 1) reshape: Mosaic
        # cannot reshape a 1-D boolean vector
        alive = m_scr[...][:, None] > NEG_INF * 0.5
        l = jnp.maximum(l_scr[...], 1e-30)
        out = acc_scr[...] / l[:, None]
        o_ref[0] = jnp.where(alive, out, 0.0).astype(o_ref.dtype)


def flash_kv_group_costs(bh: int, s: int, block_q: int, block_k: int, *,
                         causal: bool = True, window: int = 0,
                         kv_lens: Optional[np.ndarray] = None):
    """Enumerate the live KV blocks per (lane, q block) group and their
    live-column costs — the cost model of the schedule-aware kernel.

    Returns (group_kjs, costs, lens): per-group ascending kv-block lists,
    the per-group cost array the DLS planner consumes, and the clipped
    per-lane lengths.  Shared by the kernel's descriptor planner and
    `benchmarks/kernel_sched_bench.py` so the published cost model cannot
    drift from what the kernel actually plans.
    """
    nq = -(-s // block_q)
    nk = -(-s // block_k)
    lens = (np.full(bh, s, np.int64) if kv_lens is None
            else np.clip(np.asarray(kv_lens, np.int64), 0, s))
    if lens.shape != (bh,):
        raise ValueError(f"kv_lens must have shape ({bh},), got {lens.shape}")

    group_kjs: list[list[int]] = []
    costs: list[int] = []
    for bi in range(bh):
        lim = int(lens[bi])
        for qi in range(nq):
            q_end = min((qi + 1) * block_q, s) - 1
            kjs = []
            for kj in range(nk):
                k_start = kj * block_k
                if k_start >= lim:
                    break                     # beyond this lane's ragged KV
                if causal and k_start > q_end:
                    break                     # above the causal diagonal
                if window > 0 and (qi * block_q - (k_start + block_k - 1)
                                   >= window):
                    continue                  # below the sliding window
                kjs.append(kj)
            if not kjs:
                # a fully-masked group (padding rows) still needs one step
                # so its output block is initialized and written
                kjs = [0]
            group_kjs.append(kjs)
            # integer block extents: order-exact  # lint: disable=DET004
            costs.append(sum(min(lim, (kj + 1) * block_k) - kj * block_k
                             or block_k for kj in kjs))
    return group_kjs, np.asarray(costs, np.float64), lens


def _plan_kv_descriptors(bh: int, s: int, block_q: int, block_k: int, *,
                         causal: bool, window: int,
                         kv_lens: Optional[np.ndarray], schedule, p: int):
    """Host-side tile planning: enumerate live (lane, q block, kv block)
    triples, DLS-plan the q-block group order, emit descriptor arrays.

    Returns (descriptors, plan): six int32 arrays (bi, qi, kj, first,
    last, lim) of length G = total live triples, plus the KernelTilePlan
    over the (lane, q block) groups.
    """
    from repro.core.jax_sched import plan_tiles_for_kernel

    nq = -(-s // block_q)
    group_kjs, costs, lens = flash_kv_group_costs(
        bh, s, block_q, block_k, causal=causal, window=window,
        kv_lens=kv_lens)
    plan = plan_tiles_for_kernel(costs, p=p, technique=schedule)
    bi_s, qi_s, kj_s, fst_s, lst_s, lim_s = [], [], [], [], [], []
    for gid in plan.order.tolist():
        bi, qi = divmod(gid, nq)
        kjs = group_kjs[gid]
        for j, kj in enumerate(kjs):
            bi_s.append(bi)
            qi_s.append(qi)
            kj_s.append(kj)
            fst_s.append(1 if j == 0 else 0)
            lst_s.append(1 if j == len(kjs) - 1 else 0)
            lim_s.append(int(lens[bi]))
    desc = tuple(np.asarray(a, np.int32)
                 for a in (bi_s, qi_s, kj_s, fst_s, lst_s, lim_s))
    return desc, plan


def flash_attention_sched_bhsd(q, k, v, *,
                               schedule: Union[str, object] = "fac2",
                               kv_lens: Optional[Sequence[int]] = None,
                               causal: bool = True, window: int = 0,
                               block_q: int = 512, block_k: int = 512,
                               sched_p: int = 8, interpret: bool = False,
                               recorder=None, loop_name: str = "flash_kv"):
    """Schedule-aware flash attention: q, k, v (bh, s, hd) -> (bh, s, hd).

    The grid is 1-D over live (lane, q block, kv block) triples only; the
    (lane, q block) group order is DLS-planned from per-group live-KV
    costs via ``plan_tiles_for_kernel`` with ``schedule`` (any registry
    technique / ScheduleSpec).  ``kv_lens`` gives each lane's valid KV
    prefix (ragged sequence lengths, e.g. continuous-batching decode
    lanes); columns at or beyond a lane's length are masked and the dead
    KV blocks never enter the grid.  ``sched_p`` is the number of cores
    the grid is notionally split across (the planner's P).  ``recorder``
    (a ``LoopRecorder``) receives the plan's kernel-level telemetry.

    Output is bit-identical for every ``schedule`` — the technique only
    permutes whole q-block groups; each group's kv steps stay ascending.
    """
    bh, s, hd = q.shape
    scale = 1.0 / math.sqrt(hd)
    block_q = min(block_q, max(s, 8))
    block_k = min(block_k, max(s, 8))
    nq = -(-s // block_q)
    nk = -(-s // block_k)
    pad_q = nq * block_q - s
    pad_k = nk * block_k - s
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0)))
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0)))

    desc, plan = _plan_kv_descriptors(
        bh, s, block_q, block_k, causal=causal, window=window,
        kv_lens=None if kv_lens is None else np.asarray(kv_lens),
        schedule=schedule, p=sched_p)
    if recorder is not None:
        recorder.add(plan.to_record(
            loop_name, instance=recorder.next_instance(loop_name)))
    g = desc[0].shape[0]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=6,
        grid=(g,),
        in_specs=[
            pl.BlockSpec((1, block_q, hd),
                         lambda i, bi, qi, kj, fst, lst, lim: (bi[i], qi[i], 0)),
            pl.BlockSpec((1, block_k, hd),
                         lambda i, bi, qi, kj, fst, lst, lim: (bi[i], kj[i], 0)),
            pl.BlockSpec((1, block_k, hd),
                         lambda i, bi, qi, kj, fst, lst, lim: (bi[i], kj[i], 0)),
        ],
        out_specs=pl.BlockSpec(
            (1, block_q, hd),
            lambda i, bi, qi, kj, fst, lst, lim: (bi[i], qi[i], 0)),
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q, hd), jnp.float32),
        ],
    )
    kernel = functools.partial(
        _flash_sched_kernel, block_q=block_q, block_k=block_k,
        causal=causal, window=window, scale=scale)
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((bh, nq * block_q, hd), q.dtype),
        interpret=interpret,
    )(*desc, q, k, v)
    return out[:, :s, :]
