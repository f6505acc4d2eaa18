"""Substrate tests: optimizer, data pipeline, checkpointing (incl. elastic
restart + corruption detection), trainer failure recovery, serving
scheduler, balance layer."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.balance.accum import AccumPlanner
from repro.balance.moe import MoEBalancer
from repro.checkpoint.store import CheckpointStore
from repro.configs import ARCHS, smoke_config
from repro.data.pipeline import DataConfig, DataLoader, SyntheticCorpus, pack_documents
from repro.optim.adamw import (
    AdamWState,
    OptimizerConfig,
    adamw_init,
    adamw_update,
    lr_schedule,
)
from repro.serve.scheduler import Request, simulate_serving


# -- optimizer ---------------------------------------------------------------


def test_adamw_reduces_quadratic_loss():
    cfg = OptimizerConfig(learning_rate=0.1, warmup_steps=0,
                          total_steps=100, weight_decay=0.0)
    params = {"w": jnp.asarray([3.0, -2.0])}
    state = adamw_init(params)

    def loss(p):
        return jnp.sum(jnp.square(p["w"]))

    for _ in range(60):
        grads = jax.grad(loss)(params)
        params, state, metrics = adamw_update(cfg, grads, state, params)
    assert float(loss(params)) < 0.05
    assert int(state.step) == 60


def test_lr_schedule_warmup_cosine():
    cfg = OptimizerConfig(learning_rate=1.0, warmup_steps=10,
                          total_steps=110, min_lr_ratio=0.1)
    assert float(lr_schedule(cfg, jnp.asarray(0))) == 0.0
    assert abs(float(lr_schedule(cfg, jnp.asarray(10))) - 1.0) < 1e-6
    assert float(lr_schedule(cfg, jnp.asarray(110))) <= 0.1 + 1e-6


def test_grad_clip_applied():
    cfg = OptimizerConfig(learning_rate=1e-3, grad_clip=1.0, warmup_steps=0)
    params = {"w": jnp.zeros(4)}
    state = adamw_init(params)
    huge = {"w": jnp.full(4, 1e6)}
    _, _, m = adamw_update(cfg, huge, state, params)
    assert float(m["grad_norm"]) > 1e5  # reported raw


# -- data --------------------------------------------------------------------


def test_corpus_deterministic():
    cfg = DataConfig(vocab_size=1000, seq_len=64, global_batch=4, seed=7)
    c = SyntheticCorpus(cfg)
    np.testing.assert_array_equal(c.doc(42), c.doc(42))
    assert not np.array_equal(c.doc(1), c.doc(2))


def test_pack_documents_low_padding():
    rng = np.random.default_rng(0)
    docs = [rng.integers(2, 100, rng.integers(20, 400)).astype(np.int32)
            for _ in range(64)]
    toks, pad = pack_documents(docs, seq_len=256, rows=32)
    assert toks.shape == (32, 256)
    assert pad < 0.25


def test_dataloader_restartable():
    cfg = DataConfig(vocab_size=500, seq_len=32, global_batch=2, seed=3)
    l1 = DataLoader(cfg, start_step=0)
    batches = [next(l1) for _ in range(3)]
    l1.close()
    l2 = DataLoader(cfg, start_step=2)
    b2 = next(l2)
    l2.close()
    np.testing.assert_array_equal(batches[2]["tokens"], b2["tokens"])


# -- checkpoint ----------------------------------------------------------------


def test_checkpoint_roundtrip(tmp_path):
    store = CheckpointStore(str(tmp_path), keep=2, async_write=False)
    tree = {"a": jnp.arange(10.0), "b": {"c": jnp.ones((3, 4))}}
    store.save(5, tree, {"next_step": 5})
    out, extra = store.restore(5, tree)
    np.testing.assert_array_equal(np.asarray(out["a"]), np.arange(10.0))
    assert extra["next_step"] == 5


def test_checkpoint_gc_keeps_last_k(tmp_path):
    store = CheckpointStore(str(tmp_path), keep=2, async_write=False)
    tree = {"x": jnp.zeros(3)}
    for s in (1, 2, 3, 4):
        store.save(s, tree)
    assert store.steps() == [3, 4]
    assert store.latest_step() == 4


def test_checkpoint_detects_corruption(tmp_path):
    store = CheckpointStore(str(tmp_path), keep=3, async_write=False)
    tree = {"x": jnp.arange(100.0)}
    store.save(1, tree)
    # corrupt a leaf file
    victim = next((tmp_path / "step_00000001").glob("*.npy"))
    data = bytearray(victim.read_bytes())
    data[-1] ^= 0xFF
    victim.write_bytes(bytes(data))
    with pytest.raises(IOError):
        store.restore(1, tree)


def test_checkpoint_elastic_reshard(tmp_path):
    """Restore onto a different mesh (elastic restart path)."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    store = CheckpointStore(str(tmp_path), keep=1, async_write=False)
    tree = {"w": jnp.arange(16.0).reshape(4, 4)}
    store.save(1, tree)
    mesh = jax.make_mesh((1,), ("data",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    sh = {"w": NamedSharding(mesh, P("data"))}
    out, _ = store.restore(1, tree, shardings=sh)
    np.testing.assert_array_equal(np.asarray(out["w"]),
                                  np.arange(16.0).reshape(4, 4))
    assert out["w"].sharding == sh["w"]


# -- trainer (end-to-end with failure injection) ------------------------------


def test_trainer_end_to_end_with_failure_recovery(tmp_path):
    from repro.train.trainer import Trainer, TrainerConfig

    cfg = dataclasses.replace(smoke_config(ARCHS["stablelm-3b"]),
                              vocab_size=256)
    data_cfg = DataConfig(vocab_size=cfg.vocab_size, seq_len=32,
                          global_batch=4, mean_doc_len=48.0)
    fail_at = {8}

    def failure_hook(step):
        if step in fail_at:
            fail_at.discard(step)
            raise RuntimeError("injected node failure")

    tr = Trainer(cfg, OptimizerConfig(learning_rate=1e-3, warmup_steps=2),
                 TrainerConfig(steps=12, checkpoint_every=4,
                               checkpoint_dir=str(tmp_path), log_every=100),
                 data_cfg, failure_hook=failure_hook)
    hist = tr.run()
    steps_run = [h["step"] for h in hist]
    assert steps_run[-1] == 11
    assert 8 in steps_run  # re-ran after recovery
    # loss decreases overall
    assert hist[-1]["loss"] < hist[0]["loss"] + 0.5
    assert tr.store.latest_step() == 12


# -- serving -------------------------------------------------------------------


def _mk_requests(n=200, seed=0):
    rng = np.random.default_rng(seed)
    return [Request(rid=i, arrival=0.0,
                    prompt_len=int(rng.lognormal(6, 1)),
                    max_new_tokens=int(rng.lognormal(4.5, 0.8)))
            for i in range(n)]


def test_serving_dls_beats_static_split():
    reqs = _mk_requests()
    static = simulate_serving(reqs, num_workers=8, technique="static")
    fac2 = simulate_serving(reqs, num_workers=8, technique="fac2")
    assert fac2["n"] == static["n"] == len(reqs)
    assert fac2["makespan"] <= static["makespan"] * 1.02
    assert fac2["imbalance"] < static["imbalance"] + 0.05


def test_serving_handles_heterogeneous_workers():
    reqs = _mk_requests()
    speed = np.ones(8)
    speed[0] = 3.0  # one slow replica
    ss = simulate_serving(reqs, num_workers=8, technique="ss",
                          worker_speed=speed)
    static = simulate_serving(reqs, num_workers=8, technique="static",
                              worker_speed=speed)
    assert ss["makespan"] < static["makespan"]


@pytest.mark.parametrize("technique", ["awf", "awf_c"])
def test_serving_scheduler_feeds_adaptive_techniques(technique):
    """Regression for the adaptivity gap: `complete(worker, elapsed)` must
    reach the technique's telemetry path, so AWF slot weights move under
    heterogeneous slot throughput (slow slot -> weight < 1 -> smaller
    admission chunks).  Plain AWF adapts at time-step boundaries, which
    at the serving layer are plan re-builds — each `_new_tech` is a new
    execution instance."""
    from repro.serve.scheduler import RequestScheduler

    p = 4
    sched = RequestScheduler(num_workers=p, technique=technique,
                             chunk_param=1)
    all_reqs = _mk_requests(n=600, seed=3)
    # arrivals land in waves, so the plan drains and rebuilds repeatedly
    # (plain AWF only adapts at those time-step boundaries)
    waves = [all_reqs[i:i + 100] for i in range(0, 600, 100)]
    slow = 0
    w = 0
    while sched.backlog or waves:
        if not sched.backlog:
            for r in waves.pop(0):
                sched.submit(r)
            continue
        chunk = sched.pull(w)
        assert chunk, "pull returned empty with a non-empty backlog"
        # slow slot takes 4x per request; elapsed is what DecodeEngine
        # would report (decode steps spent on the admission chunk)
        sched.complete(w, elapsed=len(chunk) * (4.0 if w == slow else 1.0))
        w = (w + 1) % p
    weights = sched._tech.weights
    fast = [i for i in range(p) if i != slow]
    assert weights[slow] < min(weights[i] for i in fast)
    # the learned weighting shows up as less admitted work for the slow
    # slot over the run (equal pull counts, smaller chunks per pull)
    totals = {i: len(sched._assigned[i]) for i in range(p)}
    assert totals[slow] < min(totals[i] for i in fast)


def test_serving_adaptive_state_survives_replans():
    """The admission plan is rebuilt over the refreshed backlog whenever it
    drains; adaptive telemetry must carry over (Technique.inherit) instead
    of restarting cold on every re-plan."""
    from repro.serve.scheduler import RequestScheduler

    sched = RequestScheduler(num_workers=2, technique="awf_c",
                             chunk_param=1)
    first, second = _mk_requests(n=80, seed=1)[:40], \
        _mk_requests(n=80, seed=1)[40:]
    for r in first:
        sched.submit(r)
    planned = []
    w = 0
    while sched.backlog:
        chunk = sched.pull(w)
        if sched._tech not in planned:
            planned.append(sched._tech)
        sched.complete(w, elapsed=len(chunk) * (3.0 if w == 0 else 1.0))
        w = 1 - w
        if second:  # late arrivals: force the plan to drain mid-stream
            for r in second:
                sched.submit(r)
            second = []
    assert len(planned) > 1, "scenario must exercise at least one re-plan"
    last = planned[-1]
    assert last._adapt_k > 0 and last.weights[0] < last.weights[1]


def test_serving_adaptive_state_survives_idle_gap():
    """An empty pull (idle queue) must not reset adaptation: the learned
    weights keep receiving late complete() reports and are inherited by
    the first plan built over the next arrival wave."""
    from repro.serve.scheduler import RequestScheduler

    sched = RequestScheduler(num_workers=2, technique="awf_c",
                             chunk_param=1)
    for r in _mk_requests(n=40, seed=2):
        sched.submit(r)
    w = 0
    while sched.backlog:
        chunk = sched.pull(w)
        sched.complete(w, elapsed=len(chunk) * (5.0 if w == 0 else 1.0))
        w = 1 - w
    assert sched.pull(0) == []  # idle gap
    learned = sched._tech.weights.copy()
    assert learned[0] < learned[1]
    for r in _mk_requests(n=40, seed=9):
        sched.submit(r)
    sched.pull(1)  # new wave: first plan inherits the learned weights
    np.testing.assert_array_equal(sched._tech.weights, learned)


def test_serving_completes_all_requests_with_adaptive_technique():
    """simulate_serving terminates (no spin when a plan drains mid-cycle)
    and serves every request, with the complete() feedback path active."""
    reqs = _mk_requests(n=300, seed=5)
    speed = np.ones(8)
    speed[0] = 4.0
    for tech in ("awf_c", "af", "maf"):
        r = simulate_serving(reqs, num_workers=8, technique=tech,
                             worker_speed=speed)
        assert r["n"] == len(reqs), tech


def test_serving_scheduler_head_cursor_serves_in_order():
    """pull() slices the backlog via a head cursor (no per-pull copy of
    the remaining queue): requests are still handed out exactly once, in
    submission order, across interleaved submits/pulls/compactions."""
    from repro.serve.scheduler import RequestScheduler

    sched = RequestScheduler(num_workers=3, technique="fac2")
    served = []
    rid = 0
    rng = np.random.default_rng(9)
    for wave in range(40):
        for _ in range(int(rng.integers(20, 60))):
            sched.submit(Request(rid=rid, arrival=0.0, prompt_len=8,
                                 max_new_tokens=4))
            rid += 1
        # drain roughly half the backlog, then submit the next wave (the
        # interleaving that exercises cursor compaction mid-queue)
        target = sched.backlog // 2
        while sched.backlog > target:
            chunk = sched.pull(int(rng.integers(3)))
            assert chunk, "empty pull with non-empty backlog"
            served.extend(r.rid for r in chunk)
    while sched.backlog:
        served.extend(r.rid for r in sched.pull(0))
    assert served == list(range(rid))  # exactly once, in order
    assert sched.backlog == 0 and not sched.pull(1)


# -- balance -------------------------------------------------------------------


def test_moe_balancer_biases_against_hot_expert():
    bal = MoEBalancer(num_experts=8)
    load = np.ones(8)
    load[3] = 8.0  # hot expert
    bias = bal.update(load)
    assert bias[3] == bias.min()
    assert np.isclose(bal.weights.sum(), 8.0)
    # repeated updates strengthen the ordering
    for _ in range(3):
        bias = bal.update(load)
    assert bias[3] == bias.min()


def test_accum_planner_shifts_work_from_slow_pod():
    pl = AccumPlanner(num_workers=4, global_batch=64)
    t = np.array([2.0, 1.0, 1.0, 1.0])
    for _ in range(3):
        pl.update(t)
    shares = pl.shares()
    assert shares.sum() == 64
    assert shares[0] == shares.min()
    assert shares[0] < 16  # below the even split


def test_accum_planner_shares_always_cover_batch():
    pl = AccumPlanner(num_workers=3, global_batch=7)
    for _ in range(5):
        pl.update(np.random.default_rng(0).uniform(0.5, 2.0, 3))
        assert pl.shares().sum() == 7
