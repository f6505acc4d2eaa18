"""In-graph (JAX) campaign engine for the adaptive scheduling band.

The third execution form derived from each technique's single
:class:`~repro.core.schedule.TechniqueDef` (see ``core/techniques.py`` for
the scalar and lockstep forms): the same chunk-calculus callables run
under ``jax.numpy`` ops inside a jitted per-round engine, with dense
``(L, p)`` lane state and a ``lax.while_loop`` over chunk rounds — the
campaign scale (technique x workload x p x chunk x seed grids in one
compiled program) that the paper's host-side measurement loop could not
reach.

:func:`simulate_batch_graph` mirrors :func:`repro.core.simulate_batch`
exactly: same config grid, same dedup of provably-identical grid points,
same per-(config, timestep) ``SimResult`` stream.  Configs the graph band
cannot take — prebuilt host instances, stateful 3-arg perturbs, plugins
without a campaign form, mutex-sync techniques, ``record_chunks`` (chunk
logs are host-side) — fall back to the host batch engine; the ``strict``
knob reports those fallbacks the same way ``simulate_batch``'s does.

Numerical contract (asserted by ``tests/test_graph_sim.py``): every
engine operation reproduces the lockstep band's float64 arithmetic —
same operand order, same host-precomputed cost prefix sums — under
``jax.enable_x64``.  Worker-axis reductions are unrolled
at trace time in NumPy's exact ``pairwise_sum`` association order (see
:func:`_numpy_order_sum` — XLA's row reduce may SIMD-reassociate even a
4-element sum), and multiply-add sites are guarded against XLA's FMA
contraction (:func:`_round_mul_add`, ``ops.muladd``/``ops.freeze``), so
results are bit-exact against the scalar oracle at every worker count; the one documented tolerance is BOLD, whose slack
term takes a log (``jnp.log`` vs ``math.log`` are each correctly
rounded but may differ by 1 ulp, and a flipped chunk ``ceil`` then
shifts a grant).
"""

from __future__ import annotations

import dataclasses
import warnings
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from .batch_sim import (
    BatchConfig,
    _copy_result,
    _dedup_key,
    _lane_speeds,
    _stateful_perturb,
    simulate_batch,
)
from .metrics import LoopInstanceRecord, LoopRecorder
from .schedule import REGISTRY, ScheduleSpec, TechniqueDef, resolve
from .simulator import (
    EXACT_PROFILE,
    OverheadModel,
    ProfileModel,
    SimResult,
    _technique_kwargs,
)
from .techniques import Technique

__all__ = ["CampaignStep", "bind_campaign_form", "simulate_batch_graph"]


def _numpy_order_sum(cols: list):
    """Sum traced columns in the exact association order of NumPy's
    ``pairwise_sum`` (numpy/_core/src/umath/loops.c.src): sequential
    below 8 terms, eight interleaved accumulators combined as
    ``((r0+r1)+(r2+r3))+((r4+r5)+(r6+r7))`` up to 128, recursive
    halving (rounded down to a multiple of 8) above.  XLA does not
    reassociate explicit float adds, so the worker-axis reductions of
    the graph form match the host engines' ``np.sum`` bit-for-bit at
    every p."""
    n = len(cols)
    if n < 8:
        acc = cols[0]
        for c in cols[1:]:
            acc = acc + c
        return acc
    if n <= 128:
        r = list(cols[:8])
        i = 8
        while i + 8 <= n:
            for j in range(8):
                r[j] = r[j] + cols[i + j]
            i += 8
        acc = ((r[0] + r[1]) + (r[2] + r[3])) + \
              ((r[4] + r[5]) + (r[6] + r[7]))
        for c in cols[i:]:
            acc = acc + c
        return acc
    n2 = (n // 2) - ((n // 2) % 8)
    return _numpy_order_sum(cols[:n2]) + _numpy_order_sum(cols[n2:])


def _round_mul_add(a, b, c):
    """``round(a*b) + c`` with the product's intermediate rounding
    guaranteed.  XLA CPU's backend contracts ``fmul`` feeding ``fadd``
    into an FMA (measured: ~12% of random operand triples differ from
    NumPy's two-rounding result in the last ulp), but only when the
    product has a single use — so give it a second one, ``m - m``,
    which is exactly ``+0.0`` for finite ``m`` and which neither XLA's
    algebraic simplifier nor LLVM may fold away without fast-math
    (``m`` could be inf/NaN).  The subtraction of ``+0.0`` is
    bit-neutral on the sum."""
    m = a * b
    return (m + c) - (m - m)


class _GraphOps:
    """Ops façade for the in-graph form: per-worker state is ``(L, p)``
    jax arrays, per-lane quantities are ``(L,)`` columns, ``worker`` is
    the ``(L,)`` requesting-worker vector.  Scatters are functional
    (``.at[]``) — the TechniqueDef contract (never read an entry after
    scattering into it) makes that equivalent to the NumPy in-place
    scatters of the batch form."""

    log = staticmethod(jnp.log)
    sqrt = staticmethod(jnp.sqrt)
    ceil = staticmethod(jnp.ceil)
    where = staticmethod(jnp.where)
    maximum = staticmethod(jnp.maximum)
    minimum = staticmethod(jnp.minimum)

    @staticmethod
    def f64(x):
        return jnp.asarray(x, jnp.float64)

    @staticmethod
    def expand(x):
        return jnp.asarray(x)[..., None]

    @staticmethod
    def muladd(a, b, c):
        return _round_mul_add(a, b, c)

    @staticmethod
    def freeze(x):
        # opaque copy of a (finite) product: the result reaches any
        # downstream add as an fsub, which the FMA contraction pattern
        # cannot absorb; ``x - (x - x)`` is bitwise ``x`` for finite
        # values and is not foldable without fast-math
        return x - (x - x)

    @staticmethod
    def rsum(x):
        # XLA's row reduce may SIMD-reassociate even a 4-element sum
        # (measured: ~17% of random rows differ from np.sum in the last
        # ulp), so unroll the reduction at trace time replicating
        # NumPy's pairwise_sum exactly: the worker axis is static.
        return _numpy_order_sum([x[..., i] for i in range(x.shape[-1])])

    @staticmethod
    def rany(x):
        return jnp.any(x, axis=-1)

    @staticmethod
    def rall(x):
        return jnp.all(x, axis=-1)

    @staticmethod
    def gather(x, worker):
        return x[jnp.arange(x.shape[0]), worker]

    @staticmethod
    def scatter_add(x, worker, v):
        return x.at[jnp.arange(x.shape[0]), worker].add(v)

    @staticmethod
    def scatter_set(x, worker, v):
        return x.at[jnp.arange(x.shape[0]), worker].set(v)


@dataclasses.dataclass(frozen=True)
class CampaignStep:
    """The object bound as ``GraphForm.step``: ties a registered name to
    the :class:`TechniqueDef` the campaign engine traces.  Presence of a
    ``CampaignStep`` is what makes a technique graph-band eligible (and
    what the docs generator reports as the "lax.scan campaign" band)."""

    tdef: TechniqueDef


def bind_campaign_form(name: str) -> None:
    """Derive + bind the in-graph campaign form for a registered
    technique that carries a :class:`TechniqueDef` — the graph-side
    counterpart of ``techniques._def_technique``.  Also installs the
    definition's sound ``max_chunks`` bound so ``jax_sched``'s padding
    (``max_chunks_bound``) covers the adaptive band."""
    tdef = REGISTRY[name].techdef
    if tdef is None:
        raise KeyError(
            f"bind_campaign_form: technique {name!r} has no TechniqueDef "
            f"(bind one with repro.core.schedule.bind_techdef first)")
    REGISTRY.bind_graph_step(name, CampaignStep(tdef),
                             max_chunks=tdef.max_chunks)


# ---------------------------------------------------------------------------
# The jitted per-(technique, p) engine
# ---------------------------------------------------------------------------


def _fold_gated(state: dict, upd: dict, gate) -> dict:
    """Merge a callable's returned entries into the state, lane-gated:
    where ``gate`` is False the old value survives — the traced
    equivalent of the batch form's active-row fancy indexing."""
    out = dict(state)
    for k, v in upd.items():
        v = jnp.asarray(v)
        old = jnp.asarray(state[k])
        g = gate.reshape(gate.shape + (1,) * (v.ndim - 1))
        out[k] = jnp.where(g, v, old)
    return out


_ENGINE_CACHE: dict = {}


def _campaign_engine(tdef: TechniqueDef, p: int, use_numa: bool):
    key = (tdef, p, use_numa)
    eng = _ENGINE_CACHE.get(key)
    if eng is None:
        eng = jax.jit(_build_engine(tdef, p, use_numa))
        _ENGINE_CACHE[key] = eng
    return eng


def _build_engine(tdef: TechniqueDef, p: int, use_numa: bool):
    """Build the traced campaign engine for one (technique, p) group.

    Mirrors ``batch_sim._run_lockstep_band`` operation for operation:
    per round, pop each lane's (ready, tiebreak)-least worker, compute
    the thresholded chunk size from the TechniqueDef state, clamp,
    update the factoring bookkeeping, charge the atomic-path costs with
    the oracle's float64 operand order, and fold the measurement back —
    every update gated by ``scheduled < n`` so finished lanes coast.
    The timestep loop is unrolled at trace time; chunk rounds run in a
    ``lax.while_loop`` whose carry holds the adaptive state pytree.
    """
    ops = _GraphOps

    def run(n, cp, offs, csum, cold, sconst, pen, bounds, speeds, tsteps,
            state):
        T = speeds.shape[0]
        L = n.shape[0]
        arL = jnp.arange(L)
        f64 = jnp.float64
        n_f = n.astype(f64)  # the band's tb_base: tiebreak epoch stride
        state = {k: jnp.asarray(v) for k, v in state.items()}

        busy_out, sched_out, fin_out, req_out = [], [], [], []
        for ts in range(T):
            live_ts = tsteps > ts
            # begin_instance: timestep-cadence adapt, then factoring reset
            if tdef.cadence == "timestep" and tdef.adapt is not None:
                state = _fold_gated(state, tdef.adapt(ops, dict(state), p),
                                    live_ts)
            if tdef.factoring:
                in_batch0 = jnp.zeros(L, jnp.int64)
                batch_chunk0 = jnp.maximum(
                    1, jnp.ceil(n_f / (2.0 * p))).astype(jnp.int64)
            else:
                in_batch0 = batch_chunk0 = jnp.zeros(L, jnp.int64)
            carry = dict(
                state=state,
                in_batch=in_batch0,
                batch_chunk=batch_chunk0,
                # dead lanes (tsteps <= ts) start "finished": live below
                # is the traced galive filter of the host band
                scheduled=jnp.where(live_ts, jnp.zeros(L, jnp.int64), n),
                reqidx=jnp.zeros(L, jnp.int64),
                ready=jnp.where(live_ts[:, None], jnp.zeros((L, p)),
                                jnp.inf),
                tb=jnp.tile(jnp.arange(p, dtype=f64), (L, 1)),
                busy=jnp.zeros((L, p)),
                sched=jnp.zeros((L, p)),
            )
            spd = speeds[ts]

            def cond(c):
                return jnp.any(c["scheduled"] < n)

            def body(c):
                st = c["state"]
                scheduled = c["scheduled"]
                ready = c["ready"]
                tb = c["tb"]
                batch_chunk = c["batch_chunk"]
                in_batch = c["in_batch"]
                live = scheduled < n
                # heap order: least ready time, least insertion tiebreak
                t = ready.min(axis=1)
                cand = jnp.where(ready == t[:, None], tb, jnp.inf)
                w = jnp.argmin(cand, axis=1)
                start = scheduled
                rem = n - scheduled
                raw = tdef.chunk_size(
                    ops, dict(st), w, rem.astype(f64), p,
                    batch_chunk if tdef.factoring else None)
                size = jnp.maximum(
                    jnp.maximum(1, jnp.ceil(raw).astype(jnp.int64)), cp)
                if tdef.warming is not None:
                    # warm-up grants bypass the chunk_param threshold
                    warm = tdef.warming(ops, dict(st), w)
                    size = jnp.where(
                        warm,
                        jnp.minimum(tdef.warmup_chunk,
                                    jnp.maximum(1, rem)),
                        size)
                size = jnp.maximum(1, jnp.minimum(size, rem))
                rem_after = rem - size
                # granted: factoring roll + batch-cadence adapt (before
                # complete, exactly like the host forms)
                if tdef.factoring:
                    ib = in_batch + 1
                    roll = ib >= p
                    upd = roll & (rem_after > 0)
                    bc_new = jnp.where(
                        upd,
                        jnp.maximum(1, jnp.ceil(
                            rem_after.astype(f64)
                            / (2.0 * p))).astype(jnp.int64),
                        batch_chunk)
                    in_batch = jnp.where(live, jnp.where(roll, 0, ib),
                                         in_batch)
                    batch_chunk = jnp.where(live, bc_new, batch_chunk)
                    if tdef.cadence == "batch" and tdef.adapt is not None:
                        st = _fold_gated(
                            st, tdef.adapt(ops, dict(st), p), roll & live)
                scheduled = jnp.where(live, start + size, scheduled)
                reqidx = jnp.where(live, c["reqidx"] + 1, c["reqidx"])
                # execution cost off the host-precomputed prefix sums
                # (finished lanes read clamped garbage; every use is
                # gated by `live`)
                idx = offs + start
                base = csum[idx + size] - csum[idx]
                if use_numa:
                    hi = start + size
                    local = jnp.maximum(
                        jnp.minimum(hi, bounds[arL, w + 1])
                        - jnp.maximum(start, bounds[arL, w]), 0)
                    base = base * _round_mul_add(
                        pen, 1.0 - local / size, 1.0)
                e = _round_mul_add(base, spd[arL, w], cold)
                s = sconst
                # complete: fold the measurement, chunk-cadence adapt
                if tdef.on_complete is not None:
                    tm = e + s if tdef.include_overhead else e + 0.0
                    st = _fold_gated(
                        st, tdef.on_complete(ops, dict(st), w, size, tm, p),
                        live)
                    if tdef.cadence == "chunk" and tdef.adapt is not None:
                        st = _fold_gated(st, tdef.adapt(ops, dict(st), p),
                                         live)
                done = t + s + e
                livex = live[:, None]
                return dict(
                    state=st,
                    in_batch=in_batch,
                    batch_chunk=batch_chunk,
                    scheduled=scheduled,
                    reqidx=reqidx,
                    # ready doubles as the finish log (a worker's clock
                    # only ever moves to its chunk completion time)
                    ready=jnp.where(livex, ready.at[arL, w].set(done),
                                    ready),
                    tb=jnp.where(livex,
                                 tb.at[arL, w].set(n_f + reqidx), tb),
                    busy=jnp.where(livex, c["busy"].at[arL, w].add(e),
                                   c["busy"]),
                    sched=jnp.where(livex, c["sched"].at[arL, w].add(s),
                                    c["sched"]),
                )

            out = jax.lax.while_loop(cond, body, carry)
            state = out["state"]
            busy_out.append(out["busy"])
            sched_out.append(out["sched"])
            fin_out.append(out["ready"])
            req_out.append(out["reqidx"])
        return (jnp.stack(busy_out), jnp.stack(sched_out),
                jnp.stack(fin_out), jnp.stack(req_out))

    return run


# ---------------------------------------------------------------------------
# Campaign entry point
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class _GLane:
    """One graph-band config: like the host band's ``_ALane``, a lane
    spans all its timesteps (adaptive state carries across instances)."""

    config_idx: int
    cfg: BatchConfig
    spec: ScheduleSpec
    kw: dict
    overhead: OverheadModel
    tdef: TechniqueDef


def _stack_states(tdef: TechniqueDef, p: int, kws: list) -> dict:
    """Stack per-lane ``init_state`` dicts into dense (L,)/(L, p) arrays
    — the same layout rule as the batch form's ``_init_batch``."""
    states = [tdef.init_state(p, kw) for kw in kws]  # validates kws
    out: dict[str, np.ndarray] = {}
    for k in (tuple(states[0]) if states else ()):
        vals = [s[k] for s in states]
        if isinstance(vals[0], np.ndarray):
            out[k] = np.stack(vals).astype(np.float64)
        elif isinstance(vals[0], (int, np.integer)):
            out[k] = np.asarray(vals, np.int64)
        else:
            out[k] = np.asarray(vals, np.float64)
    return out


def _note_fallback(strict, reason: str) -> None:
    msg = ("simulate_batch_graph: config falls back to the host batch "
           "engine instead of the jitted graph band: " + reason)
    if strict is True:
        raise RuntimeError(msg)
    if strict == "warn":
        warnings.warn(msg, RuntimeWarning, stacklevel=3)


def _run_group(group: list, p: int, results: list) -> None:
    tdef = group[0].tdef
    L = len(group)
    n = np.asarray([gl.cfg.workload.n for gl in group], np.int64)
    cp = np.asarray([gl.spec.chunk_param for gl in group], np.int64)
    tsteps = np.asarray([gl.cfg.timesteps for gl in group], np.int64)
    T = int(tsteps.max())
    if T <= 0:
        for gl in group:
            results[gl.config_idx] = []
        return

    # flat concatenated cost prefix sums (shared per unique workload)
    offs = np.zeros(L, np.int64)
    parts: list[np.ndarray] = []
    seen: dict[int, int] = {}
    total = 0
    for li, gl in enumerate(group):
        wkl = gl.cfg.workload
        coff = seen.get(id(wkl))
        if coff is None:
            csum = np.concatenate([[0.0], np.cumsum(wkl.costs)])
            seen[id(wkl)] = coff = total
            parts.append(csum)
            total += len(csum)
        offs[li] = coff
    csum_flat = np.concatenate(parts)

    cold = np.asarray([gl.cfg.chunk_cold_cost for gl in group])
    sconst = np.asarray([
        (gl.overhead.o_dispatch + gl.overhead.sync_cost(gl.spec.meta.sync))
        + gl.overhead.calc_cost(gl.spec.meta.o_cs) for gl in group])
    pen = np.asarray([gl.cfg.numa_penalty for gl in group])
    use_numa = bool((pen > 0.0).any())
    bounds = np.zeros((L, p + 1), np.int64)
    if use_numa:
        for li, gl in enumerate(group):
            bounds[li] = np.linspace(0, gl.cfg.workload.n,
                                     p + 1).astype(np.int64)
    speeds = np.ones((T, L, p))
    for li, gl in enumerate(group):
        for ts in range(gl.cfg.timesteps):
            speeds[ts, li] = _lane_speeds(gl.cfg, ts)
    state = _stack_states(tdef, p, [gl.kw for gl in group])

    eng = _campaign_engine(tdef, p, use_numa)
    busy, sched, fin, req = eng(n, cp, offs, csum_flat, cold, sconst, pen,
                                bounds, speeds, tsteps, state)
    busy, sched = np.asarray(busy), np.asarray(sched)
    fin, req = np.asarray(fin), np.asarray(req)

    for li, gl in enumerate(group):
        cfg, spec = gl.cfg, gl.spec
        out = []
        for ts in range(cfg.timesteps):
            f = fin[ts, li].copy()
            rec = LoopInstanceRecord(
                loop=cfg.workload.name,
                technique=spec.technique,
                instance=ts,
                p=p,
                n=cfg.workload.n,
                chunk_param=spec.chunk_param,
                t_par=float(f.max()),
                thread_times=busy[ts, li] + sched[ts, li],
                thread_finish=f,
                n_chunks=int(req[ts, li]),
                sched_time=float(sched[ts, li].sum()),
                chunks=None,
            )
            out.append(SimResult(record=rec, engine_used="graph"))
        results[gl.config_idx] = out


def simulate_batch_graph(
    configs: Sequence[BatchConfig],
    *,
    overhead: OverheadModel = OverheadModel(),
    profile: ProfileModel = EXACT_PROFILE,
    recorder: Optional[LoopRecorder] = None,
    record_chunks: bool = False,
    strict=False,
) -> list[list[SimResult]]:
    """Simulate a config grid with the jitted in-graph campaign engine.

    Drop-in for :func:`repro.core.simulate_batch` — same inputs, same
    per-(config, timestep) results — but every adaptive/worker-dependent
    config whose technique carries a campaign graph form (the generated
    AWF/AF/mAF/BOLD/WF2 family and any plugin bound via
    :func:`bind_campaign_form`) runs inside one jitted program per
    (technique, p) group, under ``jax`` x64.  Everything else falls back
    to the host batch engine: non-adaptive configs to its (already
    vectorized) plan band silently, and graph-*ineligible* adaptive
    configs — prebuilt host instances, 3-arg stateful perturbs, plugins
    without a campaign form, mutex-sync techniques, or
    ``record_chunks=True`` (chunk logs are host-side) — reported via
    ``strict`` (``False`` silent / ``"warn"`` / ``True`` raises), the
    same knob ``simulate_batch`` itself takes.

    Results are tagged ``engine_used="graph"`` on the graph band; see
    the module docstring for the numerical contract vs the host forms.
    """
    if strict not in (False, "warn", True):
        raise ValueError(
            f"strict must be False, 'warn', or True, got {strict!r}")
    if record_chunks:
        _note_fallback(strict, "record_chunks=True needs host-side chunk "
                       "grant logs")
        return simulate_batch(configs, overhead=overhead, profile=profile,
                              recorder=recorder, record_chunks=True)

    results: list[Optional[list[SimResult]]] = [None] * len(configs)
    glanes: list[_GLane] = []
    host_idx: list[int] = []
    memo: dict = {}
    aliases: dict[int, int] = {}

    for ci, cfg in enumerate(configs):
        ov = cfg.overhead if cfg.overhead is not None else overhead
        prof = cfg.profile if cfg.profile is not None else profile
        reason = None
        eligible = False
        if isinstance(cfg.technique, Technique):
            reason = ("prebuilt Technique instance (host state machines "
                      "cannot be traced)")
        else:
            spec = resolve(cfg.technique, chunk_param=cfg.chunk_param)
            if cfg.workload.n <= 0 or cfg.p <= 0:
                raise ValueError(
                    f"need n>0, p>0, got n={cfg.workload.n} p={cfg.p}")
            meta = spec.meta
            gf = spec.entry.graph
            step = gf.step if gf is not None else None
            tdef = step.tdef if isinstance(step, CampaignStep) else None
            if not (meta.adaptive
                    or getattr(meta, "worker_dependent", False)):
                pass  # plan band: vectorized host path, intentional
            elif _stateful_perturb(cfg.perturb):
                reason = ("3-arg stateful perturb callback (per-chunk rng "
                          "draws must replay in event order)")
            elif tdef is None:
                reason = (f"technique {spec.technique!r} has no campaign "
                          f"graph form (bind one with "
                          f"repro.core.graph_sim.bind_campaign_form)")
            elif meta.sync == "mutex":
                reason = (f"technique {spec.technique!r} uses mutex sync "
                          f"(the graph band models the atomic path)")
            else:
                eligible = True
        if not eligible:
            if reason is not None and strict is not False:
                _note_fallback(strict, reason)
            host_idx.append(ci)
            continue
        key = _dedup_key(cfg, spec, ov, prof)
        if key is not None:
            prev = memo.setdefault(key, ci)
            if prev != ci:
                aliases[ci] = prev
                continue
        kw = _technique_kwargs(spec, cfg.workload, cfg.p, ov, cfg.weights,
                               prof, seed=cfg.seed)
        glanes.append(_GLane(config_idx=ci, cfg=cfg, spec=spec, kw=kw,
                             overhead=ov, tdef=tdef))

    if host_idx:
        sub = simulate_batch([configs[i] for i in host_idx],
                             overhead=overhead, profile=profile)
        for i, res in zip(host_idx, sub):
            results[i] = res

    groups: dict[tuple[str, int], list[_GLane]] = {}
    for gl in glanes:
        groups.setdefault((gl.spec.technique, gl.cfg.p), []).append(gl)
    if groups:
        with jax.enable_x64(True):
            for (_, p), group in groups.items():
                _run_group(group, p, results)

    for ci, prev in aliases.items():
        results[ci] = [_copy_result(r) for r in results[prev]]

    if recorder is not None:
        # one record per (config, timestep), in config order
        for per_config in results:
            for res in per_config:
                recorder.add(res.record)
    return results  # type: ignore[return-value]


# ---------------------------------------------------------------------------
# Bind the campaign forms for every TechniqueDef-generated technique
# ---------------------------------------------------------------------------

for _name in list(REGISTRY):
    if REGISTRY[_name].techdef is not None:
        bind_campaign_form(_name)
del _name
