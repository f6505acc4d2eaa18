"""Framework-layer benchmarks: MoE balance ablation, serving DLS
comparison, kernel microbenchmarks, packing efficiency."""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.balance.moe import MoEBalancer, plan_tiles
from repro.configs import ARCHS, smoke_config
from repro.serve.scheduler import Request, simulate_serving

from .common import timeit


def moe_balance() -> list[dict]:
    """Ablation: aux-loss only vs AWF router-bias balancing.

    Drives the real smoke-MoE router on skewed inputs for several steps,
    measuring the max/mean expert load (the serving-time straggler)."""
    from repro.models.moe import init_moe, _route

    cfg = smoke_config(ARCHS["qwen3-moe-30b-a3b"])
    cfg = dataclasses.replace(cfg, compute_dtype="float32")
    params, _ = init_moe(jax.random.key(0), cfg)
    e = cfg.moe.num_experts
    # skewed token stream: cluster structure makes some experts hot
    rng = jax.random.key(1)
    route = jax.jit(lambda p, x: _route(p, cfg, x)[3])

    hot_dir = jax.random.normal(jax.random.fold_in(rng, 999),
                                (1, 1, cfg.d_model))

    def stream(step):
        k = jax.random.fold_in(rng, step)
        base = jax.random.normal(k, (4, 64, cfg.d_model))
        return base + 1.5 * hot_dir  # persistent hot direction

    rows = []
    for use_bias in (False, True):
        bal = MoEBalancer(num_experts=e, bias_strength=0.05)
        p = dict(params)
        p["router_bias"] = jnp.zeros((e,), jnp.float32)
        peaks = []
        for step in range(20):
            load = np.asarray(route(p, stream(step)))
            peaks.append(load.max() / max(load.mean(), 1e-9))
            if use_bias:
                bias = bal.update(load)
                p["router_bias"] = jnp.asarray(bias, jnp.float32)
        rows.append(dict(
            name=f"moe_balance/{'awf_bias' if use_bias else 'aux_only'}",
            us_per_call=0.0,
            first_peak_over_mean=round(float(peaks[0]), 3),
            last_peak_over_mean=round(float(np.mean(peaks[-5:])), 3)))
    return rows


def serving() -> list[dict]:
    """DLS techniques on the serving queue (homogeneous + heterogeneous)."""
    rng = np.random.default_rng(0)
    reqs = [Request(rid=i, arrival=0.0,
                    prompt_len=int(rng.lognormal(6, 1)),
                    max_new_tokens=int(rng.lognormal(4.5, 0.8)))
            for i in range(400)]
    from repro.core import ScheduleSpec

    rows = []
    for speed_name, speed in (("homogeneous", np.ones(8)),
                              ("one_slow_3x", np.array([3.] + [1.] * 7))):
        for t in map(ScheduleSpec.parse, ("static", "ss", "gss", "fac2", "af")):
            r = simulate_serving(reqs, num_workers=8, technique=t,
                                 worker_speed=speed)
            rows.append(dict(name=f"serving/{speed_name}/{t}",
                             us_per_call=r["makespan"] * 1e6,
                             p99_latency_s=round(r["p99"], 4),
                             imbalance=round(r["imbalance"], 4)))
    return rows


def kernels() -> list[dict]:
    """Kernel microbenches (interpret mode: correctness-path timing only;
    the BlockSpec geometry is the TPU artifact)."""
    from repro.kernels.flash_attention.ops import flash_attention
    from repro.kernels.grouped_matmul.ops import grouped_matmul

    rows = []
    q = jnp.ones((1, 512, 4, 64), jnp.float32)
    k = jnp.ones((1, 512, 2, 64), jnp.float32)
    us = timeit(lambda: jax.block_until_ready(
        flash_attention(q, k, k, interpret=True, block_q=128, block_k=128)))
    rows.append(dict(name="kernel/flash_512x4h", us_per_call=us,
                     vmem_tile="(128q,128k,64d)"))
    xe = jnp.ones((8, 64, 128), jnp.float32)
    w = jnp.ones((8, 128, 64), jnp.float32)
    us = timeit(lambda: jax.block_until_ready(
        grouped_matmul(xe, w, block_rows=16, interpret=True)))
    rows.append(dict(name="kernel/gmm_8e", us_per_call=us,
                     vmem_tile="(16r,128d,64f)"))
    # DLS tile-plan balance quality
    rng = np.random.default_rng(0)
    loads = rng.integers(0, 512, 64)
    order = plan_tiles(loads, block_rows=16, p=8)
    rows.append(dict(name="kernel/plan_tiles_64e", us_per_call=0.0,
                     tiles=len(order)))
    return rows


def packing() -> list[dict]:
    from repro.data.pipeline import pack_documents

    rng = np.random.default_rng(0)
    rows = []
    for sigma in (0.4, 0.8, 1.2):
        docs = [rng.integers(2, 100,
                             int(np.clip(rng.lognormal(5.5, sigma), 8, 4096))
                             ).astype(np.int32) for _ in range(256)]
        _, pad = pack_documents(docs, seq_len=1024, rows=64)
        rows.append(dict(name=f"packing/sigma={sigma}", us_per_call=0.0,
                         padding_fraction=round(pad, 4)))
    return rows


def auto_select() -> list[dict]:
    """The paper's future work, realized: bandit selection over the
    portfolio converges to the right technique per regime.  Arm
    evaluation runs on the vectorized batch engine (identical results,
    lower wall-clock — see core.auto.auto_simulate)."""
    import numpy as np
    from repro.core import NOISY_PROFILE, auto_simulate, gromacs_like, sphynx_like, simulate

    rows = []
    # regime 1: fine-granularity regular loop -> STATIC should win
    w = gromacs_like(n=50_000)
    sel, hist = auto_simulate(w, p=20, timesteps=30, profile=NOISY_PROFILE,
                              engine="batch")
    rows.append(dict(name="auto_select/fine_regular", us_per_call=0.0,
                     chosen=str(sel.best),
                     regret_last10=round(float(
                         np.mean([h["t_par"] for h in hist[-10:]])
                         / min(s["mean_t_par"]
                               for s in sel.summary().values()
                               if s["steps"]) - 1), 4)))
    # regime 2: irregular + heterogeneous -> adaptive should win
    w2 = sphynx_like(n=50_000)
    speeds = np.ones(20)
    speeds[:5] = 1.8
    sel2, hist2 = auto_simulate(w2, p=20, timesteps=30, speeds=speeds,
                                engine="batch")
    static_t = simulate("static", w2, p=20, speeds=speeds)[0].record.t_par
    rows.append(dict(name="auto_select/hetero_irregular", us_per_call=0.0,
                     chosen=str(sel2.best),
                     vs_static=round(float(
                         np.mean([h["t_par"] for h in hist2[-10:]])
                         / static_t), 4)))
    # regime 3: the *full* registry as arms — the lockstep band makes
    # the adaptive arms as cheap to explore as the static ones, so the
    # selector can sweep the whole portfolio (the 2025 selection-study
    # regime) in one vectorized exploration pass
    from repro.core import AutoSelector, registry_candidates

    arms = registry_candidates()
    sel3 = AutoSelector(candidates=arms, policy="explore_commit",
                        explore_steps=1)
    import time as _time
    t0 = _time.perf_counter()
    sel3, hist3 = auto_simulate(w2, p=20, timesteps=len(arms) + 10,
                                speeds=speeds, selector=sel3,
                                engine="batch")
    dt = _time.perf_counter() - t0
    rows.append(dict(name="auto_select/full_registry", us_per_call=0.0,
                     arms=len(arms), chosen=str(sel3.best),
                     wall_s=round(dt, 3)))
    return rows
