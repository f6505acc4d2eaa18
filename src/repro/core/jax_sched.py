"""In-graph (jit-compatible) DLS chunk calculus — the TPU-native form.

On SPMD hardware there is no shared queue to poll; instead every worker can
derive its chunk from a monotone request counter — exactly the paper's mFAC
argument ("more computation, cheaper synchronization") taken to its limit:
the *whole schedule* is a pure function of (technique, N, P, params), so it
can be computed inside a jitted program with `jax.lax.while_loop`, sharded,
or planned on host and fed in as data.

Provided here:

  * plan_chunks(...)        -> padded (sizes, starts, count) schedule arrays
    for the deterministic techniques (static/ss/gss/tss/fac2/fac/mfac/
    wf2/tap/fsc/bold-static estimates).
  * awf_update(...)         -> AWF weight update from measured per-worker
    times (the adaptive family's between-step path; cadence = the caller's).
  * af_update(...) / af_chunk(...) -> AF/mAF online mu/sigma estimator and
    chunk rule as jnp functions.
  * balanced_assignment(...) -> DLS-planned partition of ragged work among
    workers (used by the MoE balancer and the grouped-matmul work lists).
  * plan_tiles_for_kernel(...) -> KernelTilePlan: the kernel-facing entry
    point — tile-to-grid-step assignment for the Pallas kernels
    (grouped matmul expert tiles, flash-attention q-block groups) produced
    by the same chunk calculus, with a cost model and per-core telemetry
    (LoopInstanceRecord) so kernel launches feed cov / percent_imbalance
    and the AutoSelector exactly like simulated loops do.

Agreement with the reference implementations in `core/techniques.py` is
property-tested in tests/test_jax_sched.py.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Callable, NamedTuple, Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np

from .metrics import LoopInstanceRecord
from .schedule import REGISTRY, ScheduleSpec, bind_graph_form, resolve

__all__ = [
    "PlanContext",
    "plan_chunks",
    "max_chunks_bound",
    "awf_update",
    "AFState",
    "af_init",
    "af_update",
    "af_chunk",
    "balanced_assignment",
    "KernelTilePlan",
    "plan_tiles_for_kernel",
]


def max_chunks_bound(technique: str | ScheduleSpec, n: int, p: int,
                     chunk_param: Optional[int] = None) -> int:
    """Static upper bound on the number of chunks (for padding).

    An explicit ``chunk_param`` overrides the spec's; with a bare name
    and no chunk_param, 1 (the portfolio default) is assumed.
    """
    if isinstance(technique, ScheduleSpec):
        t = technique.technique
        cp = chunk_param if chunk_param is not None else technique.chunk_param
    else:
        t = technique.lower().replace("-", "_")
        cp = 1 if chunk_param is None else chunk_param
    cp = max(1, cp)
    if t == "static":
        return p if cp <= 1 else math.ceil(n / cp)
    if t in ("ss", "fsc"):
        # fsc degenerates to fixed chunks >= cp; worst case cp itself
        return math.ceil(n / cp)
    if t in REGISTRY:
        gf = REGISTRY[t].graph
        if gf is not None and gf.max_chunks is not None:
            return int(gf.max_chunks(n, p, cp))
    # decreasing-chunk techniques: chunk >= max(cp, 1) each round; the
    # geometric families need ~P*log2(N/(P*cp)) + P rounds; be generous.
    geo = (p + 1) * (int(math.log2(max(n, 2))) + 2)
    return int(min(math.ceil(n / cp), max(geo, 4 * p)))


def _ceil_div(a: jnp.ndarray, b: int) -> jnp.ndarray:
    """Exact integer ceil-division — XLA lowers float division by a
    constant to multiply-by-reciprocal, which is off by 1 ULP around exact
    multiples and breaks agreement with the float64 reference."""
    a = a.astype(jnp.int32)
    return (a + (b - 1)) // b


def _gss_next(remaining: jnp.ndarray, p: int, cp: int) -> jnp.ndarray:
    return jnp.maximum(_ceil_div(remaining, p), cp)


def _fac2_next(remaining, p, cp, k):
    # batch chunk recomputed every P requests; within batch it is frozen.
    # Closed form: batch j chunk = ceil(R_j / 2P), R_{j+1} = R_j - P*c_j.
    del k
    return jnp.maximum(_ceil_div(remaining, 2 * p), cp)


def _tap_next(remaining, p, cp, v):
    t = remaining / p
    c = t + v * v / 2.0 - v * jnp.sqrt(2.0 * t + v * v / 4.0)
    return jnp.maximum(jnp.ceil(c).astype(jnp.int32), cp)


def _fac_batch_chunk(remaining, p, cp, cov):
    b = (p / (2.0 * jnp.sqrt(remaining))) * cov
    x = 1.0 + b * b + b * jnp.sqrt(b * b + 2.0)
    c = jnp.ceil(remaining / (x * p)).astype(jnp.int32)
    return jnp.maximum(c, cp)


class _PlanCarry(NamedTuple):
    i: jnp.ndarray          # chunk index
    scheduled: jnp.ndarray  # iterations handed out
    batch_rem: jnp.ndarray  # remaining at current batch head
    in_batch: jnp.ndarray   # requests inside current batch
    sizes: jnp.ndarray
    starts: jnp.ndarray


@dataclasses.dataclass(frozen=True)
class PlanContext:
    """Everything a registered graph form may need to compute chunks.

    Passed to ``GraphForm.builder(ctx)`` and
    ``GraphForm.next_size(ctx, rem_total, rem_batch, i)`` — plugin
    techniques binding a graph form via
    :func:`repro.core.schedule.bind_graph_form` receive the same context.
    """

    n: int
    p: int
    cp: int                 # chunk_param
    mc: int                 # max chunks (padding bound)
    mu: float = 1.0
    sigma: float = 0.0
    h: float = 1e-6
    alpha: float = 1.3
    cov: float = 0.0        # sigma / mu
    v: float = 0.0          # alpha * cov (TAP)
    w: Any = None           # (P,) normalized worker weights (wf2)
    max_chunks: Optional[int] = None  # caller's explicit padding request


def _prefix_plan(sizes: jnp.ndarray, n: int):
    """(sizes,) -> clipped (sizes, starts, count) triplet.

    Enforces the ``plan_chunks`` contract both ways: sizes are clipped so
    they never overrun ``n``, and any deficit left by an under-sized
    ``max_chunks`` is folded into the last slot so the plan always
    partitions ``[0, n)`` exactly (sum(sizes) == n).
    """
    sizes = _clip_to_n(sizes, n)
    deficit = n - jnp.sum(sizes)
    sizes = sizes.at[-1].add(deficit.astype(jnp.int32))
    starts = jnp.concatenate([jnp.zeros(1, jnp.int32),
                              jnp.cumsum(sizes)[:-1].astype(jnp.int32)])
    count = jnp.sum((sizes > 0).astype(jnp.int32))
    return sizes, starts, count


# -- direct array builders ---------------------------------------------------


def _plan_static(ctx: PlanContext):
    if ctx.cp > 1:
        sizes_np = np.full(ctx.mc, ctx.cp, np.int32)
    else:
        base, rem = divmod(ctx.n, ctx.p)
        nat = [base + (1 if i < rem else 0) for i in range(ctx.p)][:ctx.mc]
        sizes_np = np.array(nat + [0] * (ctx.mc - len(nat)), np.int32)
    return _prefix_plan(jnp.asarray(sizes_np), ctx.n)


def _plan_ss(ctx: PlanContext):
    # fixed chunks of cp; _prefix_plan clips the natural tail and folds any
    # under-sized-max_chunks remainder into the last slot
    return _prefix_plan(jnp.full((ctx.mc,), ctx.cp, jnp.int32), ctx.n)


def _plan_fsc(ctx: PlanContext):
    logp = math.log(max(ctx.p, 2))
    if ctx.sigma <= 0:
        c = max(1, math.ceil(ctx.n / ctx.p))
    else:
        c = max(1, math.ceil(((math.sqrt(2.0) * ctx.n * ctx.h)
                              / (ctx.sigma * ctx.p * math.sqrt(logp)))
                             ** (2.0 / 3.0)))
    c = max(c, ctx.cp)
    return plan_chunks("ss", ctx.n, ctx.p, chunk_param=c,
                       max_chunks=ctx.max_chunks or math.ceil(ctx.n / c))


def _plan_tss(ctx: PlanContext):
    first = max(1, math.ceil(ctx.n / (2 * ctx.p)))
    last = min(max(1, ctx.cp), first)
    steps = max(1, math.ceil(2 * ctx.n / (first + last)))
    delta = (first - last) / (steps - 1) if steps > 1 else 0.0
    idx = jnp.arange(ctx.mc, dtype=jnp.float32)
    raw = jnp.maximum(jnp.ceil(first - idx * delta).astype(jnp.int32), last)
    return _prefix_plan(raw, ctx.n)


# -- per-request next-size forms (consumed by the generic while_loop) --------


def _next_gss(ctx, rem_total, rem_batch, i):
    del rem_batch, i
    return _gss_next(jnp.maximum(rem_total, 1.0), ctx.p, ctx.cp)


def _next_tap(ctx, rem_total, rem_batch, i):
    del rem_batch, i
    return _tap_next(jnp.maximum(rem_total, 1.0), ctx.p, ctx.cp, ctx.v)


def _next_fac(ctx, rem_total, rem_batch, i):
    del rem_total, i
    return _fac_batch_chunk(jnp.maximum(rem_batch, 1.0), ctx.p, ctx.cp, ctx.cov)


def _next_fac2(ctx, rem_total, rem_batch, i):
    del rem_total, i
    return _fac2_next(jnp.maximum(rem_batch, 1.0), ctx.p, ctx.cp, None)


def _next_wf2(ctx, rem_total, rem_batch, i):
    base = _fac2_next(jnp.maximum(rem_batch, 1.0), ctx.p, ctx.cp, None)
    wkr = i % ctx.p
    return jnp.maximum(jnp.ceil(ctx.w[wkr] * base).astype(jnp.int32), ctx.cp)


# jax_sched's dispatch table IS the registry: each in-graph closed form is
# bound to its technique entry, next to the host reference class.
bind_graph_form("static", builder=_plan_static)
bind_graph_form("ss", builder=_plan_ss)
bind_graph_form("fsc", builder=_plan_fsc)
bind_graph_form("tss", builder=_plan_tss)
bind_graph_form("gss", next_size=_next_gss)
bind_graph_form("tap", next_size=_next_tap)
bind_graph_form("fac", next_size=_next_fac, batched=True)
bind_graph_form("mfac", next_size=_next_fac, batched=True)
bind_graph_form("fac2", next_size=_next_fac2, batched=True)
bind_graph_form("wf2", next_size=_next_wf2, batched=True)


def plan_chunks(
    technique: str | ScheduleSpec,
    n: int,
    p: int,
    chunk_param: Optional[int] = None,
    *,
    mu: float = 1.0,
    sigma: float = 0.0,
    h: float = 1e-6,
    alpha: float = 1.3,
    weights: Optional[jnp.ndarray] = None,
    max_chunks: Optional[int] = None,
):
    """Compute the full chunk schedule inside jit.

    Returns (sizes[int32, max_chunks], starts[int32, max_chunks],
    count[int32]).  Entries past ``count`` are zero.  For weighted
    techniques (wf2) the i-th chunk belongs to worker i % p.

    ``max_chunks`` contract: it is a *padding bound*, not a truncation —
    the returned sizes always partition ``[0, n)`` exactly
    (``sum(sizes) == n`` and ``count <= max_chunks``).  When a
    caller-supplied ``max_chunks`` is smaller than the technique's natural
    chunk count, the remainder is folded into the final slot (the last
    chunk absorbs the tail), keeping the result a valid — if coarser —
    schedule; this is jit-safe, unlike raising on a traced value.  An
    explicit ``max_chunks < 1`` raises ``ValueError``.

    Dispatch is registry-driven: any technique whose entry carries a
    :class:`~repro.core.schedule.GraphForm` (including user-registered
    plugins) is plannable here; techniques without one raise ``KeyError``.
    """
    spec = resolve(technique, chunk_param=chunk_param)
    t, cp = spec.technique, spec.chunk_param
    graph = REGISTRY[t].graph
    if graph is None:
        raise KeyError(
            f"plan_chunks: unsupported technique {t!r}; in-graph forms exist "
            f"for {sorted(REGISTRY.graph_names(plannable=True))} (bind one "
            f"with repro.core.schedule.bind_graph_form)")
    if graph.builder is None and graph.next_size is None:
        # campaign (step-only) form: the chunk sequence depends on
        # measured telemetry, so there is no up-front schedule to plan
        raise KeyError(
            f"plan_chunks: technique {t!r} has a campaign (step-only) graph "
            f"form — its chunk sequence depends on runtime measurements; "
            f"run it with repro.core.graph_sim.simulate_batch_graph; "
            f"plannable techniques: "
            f"{sorted(REGISTRY.graph_names(plannable=True))}")

    if max_chunks is not None and max_chunks < 1:
        raise ValueError(f"max_chunks must be >= 1, got {max_chunks}")
    mc = int(max_chunks or max_chunks_bound(t, n, p, cp))
    cov = 0.0 if mu <= 0 else sigma / mu
    if weights is None:
        w = jnp.ones((p,), jnp.float32)
    else:
        w = jnp.asarray(weights, jnp.float32)
        w = w * (p / jnp.sum(w))
    ctx = PlanContext(n=n, p=p, cp=cp, mc=mc, mu=mu, sigma=sigma, h=h,
                      alpha=alpha, cov=cov, v=alpha * cov, w=w,
                      max_chunks=max_chunks)

    if graph.builder is not None:
        return graph.builder(ctx)

    def next_size(carry: _PlanCarry) -> jnp.ndarray:
        rem_total = jnp.maximum(n - carry.scheduled, 0).astype(jnp.float32)
        rem_batch = carry.batch_rem.astype(jnp.float32)
        c = graph.next_size(ctx, rem_total, rem_batch, carry.i)
        return jnp.minimum(jnp.maximum(c, 1), jnp.maximum(n - carry.scheduled, 0))

    def cond(carry: _PlanCarry):
        return jnp.logical_and(carry.scheduled < n, carry.i < mc)

    def body(carry: _PlanCarry):
        c = next_size(carry)
        # final slot: fold whatever remains so the plan always sums to n
        # even when the caller's max_chunks under-estimates the round count
        c = jnp.where(carry.i == mc - 1,
                      jnp.maximum(n - carry.scheduled, 1).astype(jnp.int32), c)
        sizes = carry.sizes.at[carry.i].set(c)
        starts = carry.starts.at[carry.i].set(carry.scheduled)
        scheduled = carry.scheduled + c
        in_batch = carry.in_batch + 1
        new_batch = in_batch >= p
        batch_rem = jnp.where(
            new_batch if graph.batched else False,
            jnp.maximum(n - scheduled, 0),
            carry.batch_rem,
        )
        in_batch = jnp.where(new_batch, 0, in_batch)
        return _PlanCarry(carry.i + 1, scheduled, batch_rem, in_batch, sizes, starts)

    init = _PlanCarry(
        i=jnp.asarray(0, jnp.int32),
        scheduled=jnp.asarray(0, jnp.int32),
        batch_rem=jnp.asarray(n, jnp.int32),
        in_batch=jnp.asarray(0, jnp.int32),
        sizes=jnp.zeros((mc,), jnp.int32),
        starts=jnp.zeros((mc,), jnp.int32),
    )
    out = jax.lax.while_loop(cond, body, init)
    return out.sizes, out.starts, out.i


def _clip_to_n(sizes: jnp.ndarray, n: int) -> jnp.ndarray:
    """Clip a tentative size sequence so cumulative sum == n."""
    cum = jnp.cumsum(sizes)
    prev = jnp.concatenate([jnp.zeros(1, sizes.dtype), cum[:-1]])
    avail = jnp.maximum(n - prev, 0)
    return jnp.minimum(sizes, avail).astype(jnp.int32)


# ---------------------------------------------------------------------------
# Adaptive family — between-step updates (jnp, differentiable-free)
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("recency",))
def awf_update(wap_num: jnp.ndarray, wap_den: jnp.ndarray, k: jnp.ndarray,
               times: jnp.ndarray, sizes: jnp.ndarray, recency: bool = True):
    """One AWF adaptation point: fold measured (time, size) per worker.

    Returns (weights, wap_num, wap_den, k+1).  weights sum to P.
    Matches techniques._AWFBase._adapt (recency-weighted pi averaging).
    """
    p = times.shape[0]
    k1 = k + 1
    pi = times / jnp.maximum(sizes, 1e-30)
    mask = sizes > 0
    kw = jnp.where(recency, k1.astype(jnp.float32), 1.0)
    wap_num = wap_num + jnp.where(mask, kw * pi, 0.0)
    wap_den = wap_den + jnp.where(mask, kw, 0.0)
    wap = wap_num / jnp.maximum(wap_den, 1e-30)
    inv = jnp.where(wap_den > 0, 1.0 / jnp.maximum(wap, 1e-30), 1.0)
    weights = p * inv / jnp.sum(inv)
    return weights, wap_num, wap_den, k1


class AFState(NamedTuple):
    cnt: jnp.ndarray   # (P,)
    mean: jnp.ndarray  # (P,) per-iteration mean time
    m2: jnp.ndarray    # (P,) Welford M2


def af_init(p: int) -> AFState:
    z = jnp.zeros((p,), jnp.float32)
    return AFState(cnt=z, mean=z, m2=z)


@jax.jit
def af_update(s: AFState, worker_times: jnp.ndarray,
              worker_sizes: jnp.ndarray) -> AFState:
    """Size-weighted Welford update of per-worker per-iteration time stats
    (vectorized over workers; a chunk of k iterations contributes k
    observations of its mean — matches techniques.AF.complete_chunk;
    size==0 -> no-op)."""
    valid = worker_sizes > 0
    k = worker_sizes.astype(jnp.float32)
    per_iter = worker_times / jnp.maximum(worker_sizes, 1e-30)
    cnt = s.cnt + jnp.where(valid, k, 0.0)
    d = per_iter - s.mean
    mean = jnp.where(valid, s.mean + d * k / jnp.maximum(cnt, 1.0), s.mean)
    m2 = jnp.where(valid, s.m2 + k * d * (per_iter - mean), s.m2)
    return AFState(cnt=cnt, mean=mean, m2=m2)


@jax.jit
def af_chunk(s: AFState, remaining: jnp.ndarray) -> jnp.ndarray:
    """AF chunk size per worker given current stats: the Banicescu-Liu rule
    c_p = (D + 2TR - sqrt(D^2 + 4DTR)) / (2 mu_p)."""
    mu = jnp.maximum(s.mean, 1e-30)
    var = jnp.where(s.cnt > 1, s.m2 / jnp.maximum(s.cnt - 1.0, 1.0), 0.0)
    d = jnp.sum(var / mu)
    t = 1.0 / jnp.sum(1.0 / mu)
    r = remaining.astype(jnp.float32)
    c = (d + 2.0 * t * r - jnp.sqrt(d * d + 4.0 * d * t * r)) / (2.0 * mu)
    # GSS envelope guard, matching techniques.AF._chunk_size
    c = jnp.minimum(c, jnp.ceil(r / mu.shape[0]))
    return jnp.maximum(jnp.ceil(c).astype(jnp.int32), 1)


# ---------------------------------------------------------------------------
# DLS-planned balanced assignment of ragged work (framework entry point)
# ---------------------------------------------------------------------------


def balanced_assignment(costs: jnp.ndarray, p: int,
                        weights: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Assign N ragged work items to P workers, greedy-LPT weighted by DLS
    (AWF/WF) worker weights.  Returns int32 worker id per item.

    jit-compatible; O(N * P).  Items should be pre-sorted by decreasing
    cost for the classic LPT bound; we sort internally.
    """
    n = costs.shape[0]
    w = jnp.ones((p,), jnp.float32) if weights is None else jnp.asarray(weights, jnp.float32)
    w = w * (p / jnp.sum(w))
    order = jnp.argsort(-costs)

    def body(carry, idx):
        loads = carry
        item = costs[idx]
        # effective finishing time if assigned: (load + cost) / weight
        eff = (loads + item) / jnp.maximum(w, 1e-6)
        tgt = jnp.argmin(eff)
        loads = loads.at[tgt].add(item)
        return loads, tgt

    _, assign_sorted = jax.lax.scan(body, jnp.zeros((p,), costs.dtype), order)
    out = jnp.zeros((n,), jnp.int32)
    return out.at[order].set(assign_sorted.astype(jnp.int32))


# ---------------------------------------------------------------------------
# Kernel tile scheduling — DLS chunk calculus applied to Pallas grid steps
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class KernelTilePlan:
    """A DLS-planned tile-to-grid-step assignment for a Pallas kernel.

    The TPU grid executes sequentially per core; when a launch is split
    across ``p`` cores (megacore / multi-chip shards) each core runs a
    contiguous span of grid steps.  ``order`` is laid out so that the
    per-core spans are exactly the per-worker tile lists the chunk
    calculus produced — core ``w`` owns the steps where
    ``step_worker == w`` (a contiguous run, workers in ascending order).

    ``worker_cost`` is the cost model's estimate of each core's span
    (compute cost of its tiles + per-chunk scheduling overhead), the
    kernel-level analogue of per-thread finish times — ``to_record()``
    turns it into a :class:`~repro.core.metrics.LoopInstanceRecord` so
    kernel launches feed the same cov / percent_imbalance metrics and
    AutoSelector telemetry as simulated loops.
    """

    spec: ScheduleSpec
    p: int
    n: int                    # live tiles planned
    order: np.ndarray         # (n,) int32: tile id per grid step
    step_worker: np.ndarray   # (n,) int32: core owning each grid step
    step_cost: np.ndarray     # (n,) float64: estimated cost per grid step
    worker_cost: np.ndarray   # (p,) float64: estimated cost per core span
    n_chunks: int             # scheduling rounds (o_sr)
    sched_time: float         # total per-chunk overhead across cores

    @property
    def t_par(self) -> float:
        """Cost-model parallel time: the slowest core's span."""
        return float(self.worker_cost.max(initial=0.0))

    @property
    def cov(self) -> float:
        from .metrics import cov
        return cov(self.worker_cost)

    @property
    def percent_imbalance(self) -> float:
        from .metrics import percent_imbalance
        return percent_imbalance(self.worker_cost, self.t_par)

    def shares(self) -> list[np.ndarray]:
        """Per-core contiguous spans of ``order`` (what each core runs)."""
        return [self.order[self.step_worker == w] for w in range(self.p)]

    def to_record(self, loop: str, instance: int = 0) -> LoopInstanceRecord:
        """Kernel-level telemetry in the KMP_TIME_LOOPS unit of record."""
        return LoopInstanceRecord(
            loop=loop, technique=self.spec.technique, instance=instance,
            p=self.p, n=self.n, chunk_param=self.spec.chunk_param,
            t_par=self.t_par,
            thread_times=self.worker_cost.copy(),
            thread_finish=self.worker_cost.copy(),
            n_chunks=self.n_chunks, sched_time=self.sched_time)


def plan_tiles_for_kernel(
    costs: Sequence[float],
    p: int = 8,
    technique: Union[ScheduleSpec, str, None] = "fac2",
    *,
    weights: Optional[Sequence[float]] = None,
    assign: str = "greedy",
    overhead_per_chunk: float = 0.0,
    cost_fn: Optional[Callable[[np.ndarray], np.ndarray]] = None,
) -> KernelTilePlan:
    """Plan the tile order of a Pallas kernel launch with DLS chunking.

    ``costs`` gives the estimated execution cost of each kernel tile
    (live MXU rows for grouped matmul, live KV columns for a
    flash-attention q block).  Tiles are sorted by decreasing cost (the
    LPT preconditioning the factoring family assumes), the sorted list is
    chunked by the technique's calculus (``plan_schedule`` over ``n =
    len(costs)`` iterations), and each chunk is assigned to one of ``p``
    notional cores:

      * ``assign="greedy"`` (default) — cost-weighted least-finish-time,
        optionally scaled by per-core ``weights`` (feed AWF weights from
        :class:`~repro.balance.moe.MoEBalancer` here to bias slow cores
        down, the adaptive hook);
      * ``assign="round_robin"`` — chunk i to core i % p, the canonical
        SPMD order (matches ``plan_schedule``'s request order exactly).

    ``overhead_per_chunk`` is the cost model's per-scheduling-round
    overhead in cost units, scaled by the technique's relative
    chunk-calculation cost ``o_cs`` — it charges fine-grained techniques
    (SS) for their many rounds, reproducing the paper's
    granularity-vs-overhead tradeoff at kernel scale.  ``cost_fn`` maps
    raw costs to effective costs (e.g. a roofline model turning rows into
    cycles) before planning.

    Returns a :class:`KernelTilePlan`; ``order`` is a permutation of
    ``range(len(costs))`` — callers append dead/padding tiles themselves
    (see ``repro.balance.moe.plan_tiles``).
    """
    from .planner import plan_schedule  # deferred: jax_sched has no other
    # dependency on the host reference classes

    if assign not in ("greedy", "round_robin"):
        raise ValueError(
            f"assign must be 'greedy' or 'round_robin', got {assign!r}")
    spec = resolve(technique, default="fac2")
    costs = np.asarray(costs, dtype=np.float64)
    if cost_fn is not None:
        costs = np.asarray(cost_fn(costs), dtype=np.float64)
    if costs.ndim != 1:
        raise ValueError(f"costs must be 1-D, got shape {costs.shape}")
    n = costs.shape[0]
    if n == 0:
        z = np.zeros(0, np.int32)
        return KernelTilePlan(spec=spec, p=p, n=0, order=z, step_worker=z,
                              step_cost=np.zeros(0), n_chunks=0,
                              worker_cost=np.zeros(p), sched_time=0.0)
    if weights is None:
        w = np.ones(p, dtype=np.float64)
    else:
        w = np.asarray(weights, dtype=np.float64)
        if w.shape != (p,):
            raise ValueError(f"weights must have shape ({p},), got {w.shape}")
        if not np.isfinite(w).all() or w.sum() <= 0:
            raise ValueError(
                f"weights must be finite with a positive sum, got {w} — "
                f"an all-zero AWF warm-up should pass weights=None instead")
        w = np.maximum(w * (p / w.sum()), 1e-6)

    by_cost = np.argsort(-costs, kind="stable")       # tile ids, LPT order
    plan = plan_schedule(spec, n=n, p=p)
    o_cs = spec.meta.o_cs * overhead_per_chunk

    # chunk -> core assignment
    loads = np.zeros(p, dtype=np.float64)
    wtiles: list[list[np.ndarray]] = [[] for _ in range(p)]
    csum = np.concatenate([[0.0], np.cumsum(costs[by_cost])])
    for c in plan.chunks:
        chunk_cost = csum[c.start + c.size] - csum[c.start] + o_cs
        if assign == "round_robin":
            tgt = c.worker
        else:
            tgt = int(np.argmin((loads + chunk_cost) / w))
        loads[tgt] += chunk_cost
        wtiles[tgt].append(by_cost[c.start:c.start + c.size])

    order = np.concatenate(
        [np.concatenate(t) if t else np.zeros(0, np.int64) for t in wtiles]
    ).astype(np.int32)
    step_worker = np.concatenate(
        [np.full(sum(map(len, t)), wkr, np.int32)
         for wkr, t in enumerate(wtiles)])
    return KernelTilePlan(
        spec=spec, p=p, n=n, order=order, step_worker=step_worker,
        step_cost=costs[order], worker_cost=loads,
        n_chunks=plan.n_chunks, sched_time=o_cs * plan.n_chunks)

