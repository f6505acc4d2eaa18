"""DecodeEngine: real continuous-batching decode on top of the model.

Binds the DLS RequestScheduler to `models.decode_step`: a fixed pool of
`slots` decodes in lockstep (one jit'd batched step); when a slot's
request finishes, the engine pulls a DLS-sized chunk of queued requests
(FAC2 by default) and refills free slots.  Recurrent/KV state for a
freed slot is reset by re-prefilling the new request's prompt through
the same step function (token-by-token prefill keeps the engine simple;
a production engine fuses a batched prefill — the serving benchmark's
latency model accounts for it).

This is the laptop-scale version of the pod-level engine: slots map to
batch lanes here, to replicas in the scheduler simulation.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..core.jax_sched import plan_tiles_cached
from ..core.metrics import LoopRecorder
from ..core.schedule import resolve
from .. import models
from .scheduler import Request, RequestScheduler
from .spans import Recorder

__all__ = ["DecodeEngine", "EngineStats", "decode_program"]


def decode_program(cfg):
    """The engine's jitted decode step ``(params, state, tokens) ->
    (logits, state)``.  The state is donated, so the step updates the
    caches in place instead of holding an input and an output copy.  A
    profiler trace names the program ``jit_decode_step`` whatever the
    configuration."""
    def decode_step(params, state, tokens):
        return models.decode_step(params, cfg, state, tokens)

    return jax.jit(decode_step, donate_argnums=1)


# jitted so the stacked caches are written once, not built per layer and
# then copied into the stack (twice the cache, briefly, at full width)
_init_state = jax.jit(models.init_decode_state,
                      static_argnames=("cfg", "batch", "max_len"))


@functools.partial(jax.jit, donate_argnums=0)
def _splice_lane(state, fresh, s):
    """``state`` with lane ``s`` replaced by the single-lane ``fresh``:
    per-lane pos -> 0 (which masks the stale KV entries) and recurrent
    states zeroed.  The state is donated, so the caches are updated in
    place instead of copied."""
    grp = jax.tree.map(lambda a, f: a.at[:, s].set(f[:, 0]),
                       state.group_caches, fresh.group_caches)
    rem = jax.tree.map(lambda a, f: a.at[s].set(f[0]),
                       state.rem_caches, fresh.rem_caches)
    return state._replace(group_caches=grp, rem_caches=rem,
                          pos=state.pos.at[s].set(0))


@dataclasses.dataclass
class EngineStats:
    completed: int = 0
    steps: int = 0
    tokens: int = 0
    wall_s: float = 0.0          # the run() call's ``engine.run`` span
    # requests shed at admission by the deadline-aware policy
    # (DecodeEngine(shed_slo=...)); 0 when shedding is disabled
    shed: int = 0

    @property
    def tok_per_s(self) -> float:
        return self.tokens / max(self.wall_s, 1e-9)


class DecodeEngine:
    def __init__(self, cfg, params, slots: int = 4, max_len: int = 128,
                 technique="fac2", greedy: bool = True,
                 temperature: float = 1.0, seed: int = 0,
                 kernel_schedule="fac2", kernel_p: int = 8,
                 kv_block: int = 16, shed_slo: Optional[float] = None,
                 device: Optional[jax.Device] = None):
        self.cfg = cfg
        # params, decode state and per-step tokens all live on one device
        # (default: the first), so replicas can each hold their own chip
        self.device = jax.devices()[0] if device is None else device
        self.params = jax.device_put(params, self.device)
        self.slots = slots
        self.max_len = max_len
        # deadline-aware shedding (serve/resilience.py's admission
        # policy at the engine level): with a step budget of
        # shed_slo * healthy_lanes, backlog beyond what healthy capacity
        # can decode inside the budget is shed at refill instead of
        # queueing unbounded; None disables (byte-identical behavior)
        self.shed_slo = shed_slo
        self.shed_rids: list[int] = []
        self.sched = RequestScheduler(num_workers=slots, technique=technique)
        # decode-attention KV tile planning: the same
        # plan_tiles_for_kernel path the Pallas kernels use, driven by the
        # ragged per-lane cache lengths; records land in kernel_recorder
        # (LoopInstanceRecord telemetry an AutoSelector can consume)
        self.kernel_spec = resolve(kernel_schedule, default="fac2")
        self.kernel_p = kernel_p
        self.kv_block = kv_block
        self.kernel_recorder = LoopRecorder()
        self._step = decode_program(cfg)
        with jax.default_device(self.device):
            self.state = _init_state(cfg=cfg, batch=slots, max_len=max_len)
            self._fresh = _init_state(cfg=cfg, batch=1, max_len=max_len)
        # logits of the latest decode step (slots, 1, vocab), on device
        self.last_logits: Optional[jax.Array] = None
        self.greedy = greedy
        self.temperature = temperature
        self._rng = jax.random.key(seed)
        # per-slot run state
        self._queue: list[list[Request]] = [[] for _ in range(slots)]
        self._active: list[Optional[Request]] = [None] * slots
        self._prompt_left: list[list[int]] = [[] for _ in range(slots)]
        self._emitted: list[int] = [0] * slots
        self._outputs: dict[int, list[int]] = {}
        self._tokens = np.zeros((slots, 1), np.int32)
        self._used = [False] * slots
        # decode steps spent on the slot's current admission chunk — the
        # throughput measurement fed back to the DLS scheduler so adaptive
        # techniques (AF/AWF*) see real per-slot service times
        self._chunk_steps = [0] * slots
        self._chunk_open = [False] * slots
        # serving plan cache bookkeeping: plans are (re)computed only on
        # admission change, through the memoized KernelTilePlan cache;
        # the live-lane mask is maintained incrementally so the hot loop
        # never rebuilds Python lists per decode step
        self._active_mask = np.zeros(slots, bool)
        self._disabled = [False] * slots  # lanes out of service (faults)
        self._need_refill = True
        # the operator's record of the serving loop (serve/spans.py): a
        # span per phase of each step, one per request from submit to its
        # lane, and the plan-cache hits under counts["plan_cache_hits"]
        self.spans = Recorder()
        self._submitted: dict[int, float] = {}   # rid -> submit time
        self._steps_run = 0      # decode steps so far: the step spans' ident

    # -- public ----------------------------------------------------------------
    def submit(self, req: Request, prompt: Optional[list[int]] = None):
        if prompt is None:
            rng = np.random.default_rng(req.rid)
            prompt = rng.integers(
                2, self.cfg.vocab_size, size=max(1, min(req.prompt_len,
                                                        self.max_len // 2))
            ).tolist()
        req.prompt_tokens = prompt  # type: ignore[attr-defined]
        self._submitted[req.rid] = self.spans.now()
        self.sched.submit(req)

    def set_slot_enabled(self, s: int, enabled: bool) -> None:
        """Fault-injection hook: take decode lane ``s`` out of (or back
        into) service.

        Disabling a lane mid-request requeues its active request and the
        unstarted rest of its admission chunk back to the scheduler —
        they are re-admitted (and re-prefilled from scratch) on another
        lane, served exactly once overall.  The interrupted chunk's step
        measurement is dropped instead of being reported: attributing a
        partial chunk to a dead lane would corrupt the adaptive weights.
        Re-enabling makes the lane eligible again at the next refill;
        its recurrent state is reset on reuse as usual.
        """
        if enabled:
            if self._disabled[s]:
                self._disabled[s] = False
                self._need_refill = True
            return
        if self._disabled[s]:
            return
        self._disabled[s] = True
        req = self._active[s]
        if req is not None:
            self._outputs.pop(req.rid, None)  # restarts clean elsewhere
            self.sched.submit(req)
            self._active[s] = None
            self._active_mask[s] = False
        for q in self._queue[s]:
            self.sched.submit(q)
        self._queue[s] = []
        self._chunk_open[s] = False
        self._chunk_steps[s] = 0
        self.sched._outstanding.pop(s, None)  # drop the open grant too
        self._need_refill = True

    def run(self, max_steps: int = 10_000) -> EngineStats:
        stats = EngineStats()
        with self.spans.span("engine.run", self._steps_run) as call:
            self._shed(stats)
            self._refill()
            while self._active_mask.any() or self.sched.backlog:
                if stats.steps >= max_steps:
                    break
                if not self._active_mask.any() and all(self._disabled):
                    break  # every lane out of service: the backlog must wait
                self._advance(stats)
                if self._need_refill:
                    # only when a slot retired: steady-state decode steps
                    # skip the admission scan (and any re-planning) entirely
                    self._shed(stats)
                    self._refill()
        stats.wall_s = call.t1 - call.t0
        return stats

    def output(self, rid: int) -> list[int]:
        return self._outputs.get(rid, [])

    @property
    def lane_requests(self) -> list[Optional[int]]:
        """Request id decoding on each lane (None for an idle lane);
        row ``i`` of ``last_logits`` belongs to lane ``i``."""
        return [None if r is None else r.rid for r in self._active]

    @property
    def kernel_records(self):
        """Kernel-level telemetry: one LoopInstanceRecord per admission
        (decode-attention KV tile plan over the ragged lane lengths)."""
        return self.kernel_recorder.records

    # -- internals ---------------------------------------------------------------
    def _record_kernel_plan(self) -> None:
        """Plan the decode-attention KV scan as kernel tiles.

        Each active lane's valid KV prefix is ragged (lanes restart
        independently under continuous batching); the per-lane cost is
        its live KV block count, and the DLS plan models splitting the
        attention grid across ``kernel_p`` cores — the same path
        ``flash_attention(schedule=..., kv_lens=...)`` executes.

        Runs only on admission change (``_refill`` with a pull) and goes
        through the memoized plan cache: continuous batching revisits the
        same lane-length signatures constantly, so the steady state pays
        a dict lookup instead of the Python chunk planner.
        """
        live = np.asarray(self.state.pos)[self._active_mask].astype(
            np.float64)
        if live.size == 0:
            return
        costs = np.maximum(np.ceil(live / self.kv_block), 1.0)
        from ..core.jax_sched import kernel_plan_cache_stats
        hits0 = kernel_plan_cache_stats()["hits"]
        plan = plan_tiles_cached(costs, p=self.kernel_p,
                                 technique=self.kernel_spec)
        self.spans.counts["plan_cache_hits"] += \
            kernel_plan_cache_stats()["hits"] - hits0
        self.kernel_recorder.add(plan.to_record(
            "decode_kv",
            instance=self.kernel_recorder.next_instance("decode_kv")))

    def _shed(self, stats: Optional[EngineStats] = None) -> int:
        """Deadline-aware shedding: drop the backlog tail the healthy
        lanes cannot decode within the ``shed_slo`` step budget.

        The per-request step estimate is prefill (its prompt tokens) +
        decode (its clamped ``max_new_tokens``); requests are admitted
        in arrival order until the summed estimate exceeds
        ``shed_slo x healthy_lanes``, and the rest are shed — a bounded
        queue under gray failure (disabled lanes shrink the budget), in
        place of unbounded queueing toward a blown SLO.
        """
        if self.shed_slo is None:
            return 0
        lanes = 0
        for s in range(self.slots):
            if not self._disabled[s]:
                lanes += 1
        budget = float(self.shed_slo) * lanes
        acc = 0.0
        over: dict[int, bool] = {}
        for req in self.sched._pending[self.sched._head:]:
            prompt = getattr(req, "prompt_tokens", None)
            pre = (len(prompt) if prompt is not None
                   else min(req.prompt_len, self.max_len // 2))
            est = pre + min(req.max_new_tokens, self.max_len // 2)
            acc += float(est)
            if acc > budget:
                over[req.rid] = True
        if not over:
            return 0
        dropped = self.sched.drop(lambda r: r.rid in over)
        for req in dropped:
            self.shed_rids.append(req.rid)
            self._submitted.pop(req.rid, None)
        if stats is not None:
            stats.shed += len(dropped)
        return len(dropped)

    def _refill(self):
        spans, k = self.spans, self._steps_run
        with spans.span("engine.refill", k):
            admitted = False
            for s in range(self.slots):
                if self._disabled[s]:
                    continue
                if self._active[s] is None:
                    if not self._queue[s]:
                        if self._chunk_open[s]:
                            self.sched.complete(s, elapsed=float(
                                max(self._chunk_steps[s], 1)))
                            self._chunk_open[s] = False
                        chunk = self.sched.pull(s)
                        if chunk:
                            self._queue[s] = chunk
                            self._chunk_open[s] = True
                            self._chunk_steps[s] = 0
                            admitted = True
                    if self._queue[s]:
                        req = self._queue[s].pop(0)
                        if self._used[s]:
                            with spans.span("engine.splice", k):
                                self.state = _splice_lane(self.state,
                                                          self._fresh, s)
                        self._used[s] = True
                        self._active[s] = req
                        self._active_mask[s] = True
                        self._prompt_left[s] = list(req.prompt_tokens)
                        self._emitted[s] = 0
                        self._outputs[req.rid] = []
                        self._tokens[s, 0] = self._prompt_left[s].pop(0)
                        # a request requeued by a lane fault waited once
                        t0 = self._submitted.pop(req.rid, None)
                        if t0 is not None:
                            spans.record("request.queued", t0, spans.now(),
                                         req.rid)
            self._need_refill = False
            if admitted:
                # after activation, so the plan sees the admitted lanes too
                # (a single-slot engine would otherwise never record)
                with spans.span("engine.plan", k):
                    self._record_kernel_plan()

    def _advance(self, stats: EngineStats):
        spans, k = self.spans, self._steps_run
        with spans.span("engine.upload", k):
            tokens = jax.device_put(self._tokens, self.device)
        with spans.span("engine.dispatch", k):
            logits, self.state = self._step(self.params, self.state, tokens)
        self.last_logits = logits
        with spans.span("engine.sample", k):
            self._rng, sub = jax.random.split(self._rng)
            if self.greedy:
                nxt = jnp.argmax(logits[:, -1, :], axis=-1)
            else:
                nxt = jax.random.categorical(
                    sub, logits[:, -1, :] / self.temperature, axis=-1)
        with spans.span("engine.sync", k):
            nxt = np.asarray(nxt)
        self._steps_run += 1
        stats.steps += 1
        with spans.span("engine.lanes", k):
            for s in range(self.slots):
                req = self._active[s]
                if req is None:
                    self._tokens[s, 0] = 0
                    continue
                self._chunk_steps[s] += 1
                if self._prompt_left[s]:
                    # still prefilling: feed the next prompt token
                    self._tokens[s, 0] = self._prompt_left[s].pop(0)
                    continue
                tok = int(nxt[s])
                self._outputs[req.rid].append(tok)
                self._emitted[s] += 1
                stats.tokens += 1
                if self._emitted[s] >= min(req.max_new_tokens,
                                           self.max_len // 2):
                    stats.completed += 1
                    self._active[s] = None
                    self._active_mask[s] = False
                    self._need_refill = True
                    self._tokens[s, 0] = 0
                else:
                    self._tokens[s, 0] = tok
