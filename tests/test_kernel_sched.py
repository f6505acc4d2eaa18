"""Schedule-aware Pallas kernels: every registry technique must leave the
kernel outputs numerically identical (schedules only permute independent
tiles / whole q-block groups), and the tile planner's cost model must
reward DLS chunking on skewed workloads.

Property-tested over specs: the full registry, plus chunk-param variants
and both chunk->core assignment modes.
"""


import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.balance.moe import MoEBalancer, plan_tiles
from repro.core import (
    REGISTRY,
    LoopRecorder,
    ScheduleSpec,
    plan_tiles_for_kernel,
)
from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.grouped_matmul.ops import grouped_matmul
from repro.kernels.grouped_matmul.ref import grouped_matmul_ref

ALL_TECHNIQUES = tuple(REGISTRY)
SPEC_VARIANTS = ALL_TECHNIQUES + ("fac2,4", "gss,2", "ss,8", "static,4")

RNG = np.random.default_rng(11)


# ---------------------------------------------------------------------------
# plan_tiles_for_kernel — the planner contract over the whole registry
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("technique", SPEC_VARIANTS)
@pytest.mark.parametrize("assign", ["greedy", "round_robin"])
def test_plan_is_valid_for_every_spec(technique, assign):
    costs = RNG.integers(1, 65, 47).astype(float)
    ktp = plan_tiles_for_kernel(costs, p=5, technique=technique,
                                assign=assign, overhead_per_chunk=0.5)
    # a permutation of the tiles...
    assert sorted(ktp.order.tolist()) == list(range(47))
    # ...in contiguous per-core spans (the sequential-grid split)
    assert (np.diff(ktp.step_worker) >= 0).all()
    assert ktp.step_cost == pytest.approx(costs[ktp.order])
    # cost conservation: compute + per-chunk overhead
    o_cs = ktp.spec.meta.o_cs * 0.5
    assert ktp.worker_cost.sum() == pytest.approx(
        costs.sum() + o_cs * ktp.n_chunks)
    assert ktp.sched_time == pytest.approx(o_cs * ktp.n_chunks)
    assert ktp.t_par == pytest.approx(ktp.worker_cost.max())


@pytest.mark.parametrize("technique", ALL_TECHNIQUES)
def test_plan_record_telemetry(technique):
    ktp = plan_tiles_for_kernel(RNG.integers(1, 9, 30).astype(float), p=4,
                                technique=technique)
    r = ktp.to_record("kernel_loop", instance=3)
    assert r.loop == "kernel_loop" and r.instance == 3
    assert r.technique == ktp.spec.technique
    assert r.p == 4 and r.n == 30 and r.n_chunks == ktp.n_chunks
    assert r.cov == pytest.approx(ktp.cov)
    assert r.percent_imbalance == pytest.approx(ktp.percent_imbalance)


def test_plan_empty_and_errors():
    ktp = plan_tiles_for_kernel([], p=4)
    assert ktp.n == 0 and ktp.t_par == 0.0 and ktp.order.size == 0
    with pytest.raises(ValueError, match="assign"):
        plan_tiles_for_kernel([1.0], p=2, assign="nope")
    with pytest.raises(ValueError, match="weights"):
        plan_tiles_for_kernel([1.0, 2.0], p=2, weights=[1.0, 1.0, 1.0])
    with pytest.raises(ValueError, match="positive sum"):
        plan_tiles_for_kernel([1.0, 2.0], p=2, weights=[0.0, 0.0])
    with pytest.raises(ValueError, match="finite"):
        plan_tiles_for_kernel([1.0, 2.0], p=2, weights=[np.nan, 1.0])
    with pytest.raises(ValueError, match="1-D"):
        plan_tiles_for_kernel(np.ones((2, 2)), p=2)


def test_plan_cost_fn_hook():
    costs = np.array([1.0, 2.0, 3.0])
    ktp = plan_tiles_for_kernel(costs, p=2, cost_fn=lambda c: c * 10)
    assert ktp.worker_cost.sum() == pytest.approx(60.0)


def test_weighted_assignment_biases_slow_core():
    costs = np.full(40, 1.0)
    ktp = plan_tiles_for_kernel(costs, p=4, technique="ss",
                                weights=[0.25, 1.0, 1.0, 1.0])
    shares = ktp.shares()
    # the 4x-slow core must receive the smallest share
    assert len(shares[0]) == min(len(s) for s in shares)
    assert len(shares[0]) < 10


def test_dls_beats_static_on_skewed_costs():
    """The acceptance property: chunked assignment beats static order on
    a skewed histogram under the cost model."""
    costs = np.r_[np.full(8, 64.0), np.full(56, 8.0)]
    static = plan_tiles_for_kernel(costs, p=8, technique="static")
    for t in ("ss", "fac2", "awf_b"):
        dls = plan_tiles_for_kernel(costs, p=8, technique=t)
        assert dls.t_par < static.t_par
        assert dls.percent_imbalance < static.percent_imbalance


# ---------------------------------------------------------------------------
# grouped matmul — bit-identical for every technique
# ---------------------------------------------------------------------------


E, C, D, F, BM = 4, 16, 16, 24, 8
XE = jnp.asarray(RNG.normal(size=(E, C, D)), jnp.float32)
WE = jnp.asarray(RNG.normal(size=(E, D, F)) * 0.1, jnp.float32)
ROWS = np.array([16, 4, 9, 12])


@pytest.fixture(scope="module")
def gmm_identity():
    return np.asarray(grouped_matmul(XE, WE, block_rows=BM, interpret=True))


@pytest.mark.parametrize("technique", SPEC_VARIANTS)
def test_grouped_matmul_identical_for_every_spec(technique, gmm_identity):
    out = grouped_matmul(XE, WE, block_rows=BM, interpret=True,
                         schedule=technique, expert_rows=ROWS)
    assert np.array_equal(np.asarray(out), gmm_identity)


def test_grouped_matmul_matches_oracle_and_records(gmm_identity):
    rec = LoopRecorder()
    out = grouped_matmul(XE, WE, block_rows=BM, interpret=True,
                         schedule=ScheduleSpec("fac2", chunk_param=2),
                         expert_rows=ROWS, recorder=rec)
    grouped_matmul(XE, WE, block_rows=BM, interpret=True, schedule="ss",
                   expert_rows=ROWS, recorder=rec)
    # repeated wrapper calls into one recorder keep instance ids monotone
    assert [r.instance for r in rec.records] == [0, 1]
    t = E * (C // BM)
    ref = grouped_matmul_ref(
        XE.reshape(t, BM, D), WE,
        jnp.arange(t, dtype=jnp.int32) // (C // BM)).reshape(E, C, F)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-4)
    assert rec.records[0].loop == "grouped_matmul"
    assert rec.records[0].technique == "fac2"


def test_grouped_matmul_schedule_and_order_are_exclusive():
    with pytest.raises(ValueError, match="not both"):
        grouped_matmul(XE, WE, tile_order=jnp.arange(8), schedule="fac2",
                       block_rows=BM, interpret=True)


# ---------------------------------------------------------------------------
# flash attention — bit-identical for every technique, ref-exact ragged
# ---------------------------------------------------------------------------


B, S, H, KVH, HD = 1, 160, 2, 1, 32
Q = jnp.asarray(RNG.normal(size=(B, S, H, HD)), jnp.float32)
K = jnp.asarray(RNG.normal(size=(B, S, KVH, HD)), jnp.float32)
V = jnp.asarray(RNG.normal(size=(B, S, KVH, HD)), jnp.float32)


def _ref(q, k, v, window=0, kv_lens=None):
    b, s, h, hd = q.shape
    kvh = k.shape[2]
    g = h // kvh
    kr = jnp.broadcast_to(k[:, :, :, None, :],
                          (b, s, kvh, g, hd)).reshape(b, s, h, hd)
    vr = jnp.broadcast_to(v[:, :, :, None, :],
                          (b, s, kvh, g, hd)).reshape(b, s, h, hd)

    def flat(x):
        return x.transpose(0, 2, 1, 3).reshape(b * h, s, hd)

    lanes = None if kv_lens is None else np.repeat(np.asarray(kv_lens), h)
    out = attention_ref(flat(q), flat(kr), flat(vr), window=window,
                        kv_lens=lanes)
    return out.reshape(b, h, s, hd).transpose(0, 2, 1, 3)


@pytest.fixture(scope="module")
def flash_baseline():
    return np.asarray(flash_attention(Q, K, V, block_q=64, block_k=64,
                                      interpret=True, schedule="static"))


@pytest.mark.parametrize("technique", SPEC_VARIANTS)
def test_flash_identical_for_every_spec(technique, flash_baseline):
    out = flash_attention(Q, K, V, block_q=64, block_k=64, interpret=True,
                          schedule=technique)
    assert np.array_equal(np.asarray(out), flash_baseline)


def test_flash_sched_matches_dense_kernel_and_ref(flash_baseline):
    dense = flash_attention(Q, K, V, block_q=64, block_k=64, interpret=True)
    assert np.array_equal(np.asarray(dense), flash_baseline)
    np.testing.assert_allclose(flash_baseline, np.asarray(_ref(Q, K, V)),
                               atol=2e-5)


@pytest.mark.parametrize("technique", ("static", "ss", "gss", "fac2"))
def test_flash_ragged_kv_lens_match_ref(technique):
    lens = np.array([97])
    rec = LoopRecorder()
    out = flash_attention(Q, K, V, block_q=64, block_k=64, interpret=True,
                          schedule=technique, kv_lens=lens, recorder=rec)
    ref = _ref(Q, K, V, kv_lens=lens)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)
    assert rec.records[0].loop == "flash_kv"


def test_flash_ragged_multi_lane_gqa():
    b, s, h, kvh, hd = 2, 130, 4, 2, 32
    q = jnp.asarray(RNG.normal(size=(b, s, h, hd)), jnp.float32)
    k = jnp.asarray(RNG.normal(size=(b, s, kvh, hd)), jnp.float32)
    v = jnp.asarray(RNG.normal(size=(b, s, kvh, hd)), jnp.float32)
    lens = np.array([33, 130])
    out = flash_attention(q, k, v, block_q=32, block_k=32, interpret=True,
                          schedule="fac2", kv_lens=lens)
    ref = _ref(q, k, v, kv_lens=lens)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_flash_sched_sliding_window():
    out = flash_attention(Q, K, V, block_q=32, block_k=32, interpret=True,
                          schedule="tap", window=48)
    ref = _ref(Q, K, V, window=48)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_flash_kv_lens_require_schedule():
    with pytest.raises(ValueError, match="kv_lens requires schedule"):
        flash_attention(Q, K, V, interpret=True, kv_lens=np.array([100]))


# ---------------------------------------------------------------------------
# balance / serving threading
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("technique", ALL_TECHNIQUES)
def test_plan_tiles_permutation_for_every_spec(technique):
    rows = np.array([32, 8, 16, 24])
    order = plan_tiles(rows, block_rows=8, p=4, technique=technique)
    assert sorted(order.tolist()) == list(range(16))


def test_plan_tiles_capacity_rows_and_partial_tail():
    rows = np.array([5, 12])
    order, ktp = plan_tiles(rows, block_rows=8, p=2, capacity_rows=16,
                            return_plan=True)
    assert sorted(order.tolist()) == list(range(4))
    # live tiles: e0 tile0 (5 rows), e1 tiles 0+1 (8 + 4 rows)
    assert ktp.n == 3
    assert sorted(ktp.step_cost.tolist()) == [4.0, 5.0, 8.0]


def test_moe_balancer_passes_spec_down_and_records():
    bal = MoEBalancer(num_experts=4, kernel_schedule="gss,2")
    assert bal.kernel_spec == ScheduleSpec("gss", chunk_param=2)
    rows = np.array([32, 8, 16, 24])
    order, ktp = bal.plan_kernel_tiles(rows, block_rows=8, p=4)
    assert ktp.spec.technique == "gss"
    assert sorted(order.tolist()) == list(range(16))
    recs = bal.kernel_recorder.records
    assert len(recs) == 1 and recs[0].loop == "grouped_matmul"
    bal.plan_kernel_tiles(rows, block_rows=8, p=4)
    assert [r.instance for r in bal.kernel_recorder.records] == [0, 1]
