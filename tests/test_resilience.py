"""Resilience layer tests (serve/resilience.py).

Three contracts stacked on each other:

1. **Bit-identity when disabled** — with ``resilience=None`` the serving
   stack runs the original ``simulate_cluster`` physics; the 20-config
   PR-8 fault/elasticity sweep must reproduce its golden sha256 digests
   byte-for-byte (``tests/data/pr8_trial_digests.json``).
2. **Exactly-once under reclamation** — hedged re-execution duplicates
   requests on purpose; first completion wins and every submitted rid
   is served exactly once, across stragglers, gray failures, crash
   loops and scale events.
3. **The breaker arc** — severe degradation quarantines, probes go out,
   a healed replica rejoins with neutralized weights, and a benign
   thermal ramp is absorbed *without* tripping the breaker.
"""

import json
from pathlib import Path

import numpy as np
import pytest

from repro.serve import (
    ClusterRouter,
    HealthTracker,
    ResilienceConfig,
    make_traffic,
    simulate_cluster,
)
from repro.serve.resilience import (
    HEALTHY,
    QUARANTINED,
    SUSPECT,
    simulate_cluster_resilient,
)
from repro.serve.scheduler import Request, RequestScheduler
from repro.trials import (
    Scenario,
    elastic_program,
    failure_program,
    run_trial,
    thermal_program,
)

DATA = Path(__file__).resolve().parent / "data"


def _conserved(out, requests):
    served = sorted(rid for rid, _ in out["completions"])
    submitted = sorted(r.rid for r in requests)
    return served == submitted


# ---------------------------------------------------------------------------
# bit-identity: resilience disabled reproduces the PR-8 golden digests
# ---------------------------------------------------------------------------

#: the PR-8 sweep scenarios, reproduced verbatim (test_trials.FAULTY)
PR8_FAULTY = [
    Scenario(name="kill_recover", traffic="spiky", n=120, num_replicas=3,
             events=failure_program(kill_at=0.05, replicas=(0,),
                                    recover_at=0.2)),
    Scenario(name="kill_forever", traffic="zipf", n=120, num_replicas=3,
             events=failure_program(kill_at=0.05, replicas=(0, 1))),
    Scenario(name="scale_up", traffic="bursty", n=120, num_replicas=2,
             events=elastic_program((0.05, 5))),
    Scenario(name="scale_down", traffic="spiky", n=120, num_replicas=4,
             events=elastic_program((0.05, 2))),
    Scenario(name="thermal", traffic="diurnal", n=120, num_replicas=3,
             events=thermal_program(0, times=(0.05, 0.1),
                                    speeds=(2.0, 5.0))),
]


def test_disabled_resilience_reproduces_pr8_digests():
    gold = json.loads((DATA / "pr8_trial_digests.json").read_text())
    assert len(gold["digests"]) == 20
    for sc in PR8_FAULTY:
        for sp in gold["schedules"]:
            got = run_trial(sc, sp, seed=gold["seed"]).digest()
            assert got == gold["digests"][f"{sc.name}|{sp}"], \
                f"digest drift in {sc.name}|{sp}"


def test_trial_result_digest_ignores_none_resilience_fields():
    sc = Scenario(name="plain", traffic="spiky", n=60, num_replicas=2)
    r = run_trial(sc, "fac2/fac2", seed=0)
    assert r.reclaimed is None and r.duplicates is None
    # the digest payload must not contain the None-valued keys at all
    import dataclasses
    import hashlib
    d = dataclasses.asdict(r)
    d["latencies"] = list(d["latencies"])
    for key in ("reclaimed", "duplicates", "quarantines"):
        del d[key]
    blob = json.dumps(d, sort_keys=True)
    assert r.digest() == hashlib.sha256(blob.encode()).hexdigest()


# ---------------------------------------------------------------------------
# exactly-once under reclamation
# ---------------------------------------------------------------------------


def test_straggler_reclaims_and_conserves():
    reqs = make_traffic("spiky", n=400, seed=0)
    out = simulate_cluster(
        reqs, num_replicas=4, schedule="awf_b/fac2",
        events=thermal_program(1, times=(0.125,), speeds=(10.0,)),
        return_completions=True, resilience=ResilienceConfig())
    r = out["resilience"]
    assert _conserved(out, reqs)
    assert r["reclaimed_requests"] > 0
    assert r["deadline_misses"] > 0
    # duplicates are bounded by the reclaim count (each hedge adds at
    # most one extra completion)
    assert 0 <= r["duplicate_completions"] <= r["reclaimed_requests"]
    assert len(r["reclaims"]) == r["reclaimed_requests"]
    for g in r["reclaims"]:
        assert g["victim"] == 1 and g["attempt"] >= 1


def test_resilient_run_is_deterministic():
    reqs = make_traffic("spiky", n=300, seed=1)
    evs = thermal_program(2, times=(0.1,), speeds=(10.0,))
    outs = [simulate_cluster(reqs, num_replicas=4, schedule="awf_b/fac2",
                             events=evs, return_completions=True,
                             resilience=ResilienceConfig())
            for _ in range(2)]
    assert outs[0]["completions"] == outs[1]["completions"]
    assert outs[0]["resilience"] == outs[1]["resilience"]
    assert outs[0]["makespan"] == outs[1]["makespan"]


def test_resilience_no_events_conserves_and_stays_healthy():
    reqs = make_traffic("diurnal", n=300, seed=2)
    out = simulate_cluster(reqs, num_replicas=4, schedule="awf_b/fac2",
                           return_completions=True,
                           resilience=ResilienceConfig())
    r = out["resilience"]
    assert _conserved(out, reqs)
    assert r["quarantines"] == 0
    assert r["health"] == [HEALTHY] * 4


def test_resilience_with_kill_and_scale_conserves():
    reqs = make_traffic("bursty", n=300, seed=3)
    evs = (failure_program(kill_at=0.1, replicas=(0,), recover_at=0.3)
           + elastic_program((0.2, 6)))
    out = simulate_cluster(reqs, num_replicas=4, schedule="awf_b/fac2",
                           events=evs, return_completions=True,
                           resilience=ResilienceConfig())
    assert _conserved(out, reqs)
    assert len(out["replica_requests"]) == 6


def test_max_hedges_bounds_duplicates():
    reqs = make_traffic("spiky", n=400, seed=0)
    out = simulate_cluster(
        reqs, num_replicas=4, schedule="awf_b/fac2",
        events=thermal_program(1, times=(0.125,), speeds=(10.0,)),
        return_completions=True,
        resilience=ResilienceConfig(max_hedges=1))
    assert _conserved(out, reqs)
    reclaims = out["resilience"]["reclaims"]
    per_rid: dict = {}
    for g in reclaims:
        per_rid[g["rid"]] = per_rid.get(g["rid"], 0) + 1
        assert g["attempt"] <= 1
    assert all(v <= 1 for v in per_rid.values())


# ---------------------------------------------------------------------------
# the breaker arc
# ---------------------------------------------------------------------------


def test_severe_straggler_quarantined():
    reqs = make_traffic("spiky", n=400, seed=0)
    out = simulate_cluster(
        reqs, num_replicas=4, schedule="awf_b/fac2",
        events=thermal_program(1, times=(0.125,), speeds=(10.0,)),
        return_completions=True, resilience=ResilienceConfig())
    r = out["resilience"]
    assert r["quarantines"] >= 1
    assert r["health"][1] == QUARANTINED  # never heals: breaker stays open
    assert _conserved(out, reqs)


def test_gray_failure_quarantine_probe_rejoin():
    # degrade 25x mid-stream, then silently heal: the breaker must open,
    # probe, and close again — final health fully healthy
    reqs = make_traffic("flash_crowd", n=400, seed=0)
    out = simulate_cluster(
        reqs, num_replicas=4, schedule="awf_b/fac2",
        events=thermal_program(2, times=(0.1, 0.275), speeds=(25.0, 1.0)),
        return_completions=True, resilience=ResilienceConfig())
    r = out["resilience"]
    assert _conserved(out, reqs)
    assert r["quarantines"] >= 1
    assert r["probes"] >= 1
    assert r["probe_successes"] >= 1
    assert r["health"] == [HEALTHY] * 4


def test_benign_thermal_ramp_not_quarantined():
    # 2x -> 4x is below quarantine_ratio: reclamation absorbs it, the
    # breaker must NOT trip (no capacity thrown away on a slow-but-live
    # replica)
    reqs = make_traffic("zipf", n=400, seed=0)
    out = simulate_cluster(
        reqs, num_replicas=4, schedule="awf_b/fac2",
        events=thermal_program(0, times=(0.1, 0.3), speeds=(2.0, 4.0)),
        return_completions=True, resilience=ResilienceConfig())
    r = out["resilience"]
    assert _conserved(out, reqs)
    assert r["quarantines"] == 0
    assert r["health"][0] in (HEALTHY, SUSPECT)


def test_crash_loop_probation():
    # third recovery exceeds crash_loop_threshold=2: the replica rejoins
    # quarantined and must probe its way back in
    reqs = make_traffic("spiky", n=400, seed=0)
    evs = (failure_program(0.075, (3,), recover_at=0.15)
           + failure_program(0.225, (3,), recover_at=0.3)
           + failure_program(0.375, (3,), recover_at=0.45))
    out = simulate_cluster(reqs, num_replicas=4, schedule="awf_b/fac2",
                           events=evs, return_completions=True,
                           resilience=ResilienceConfig())
    r = out["resilience"]
    assert _conserved(out, reqs)
    assert r["quarantines"] >= 1
    assert r["probes"] >= 1
    assert r["probe_successes"] >= 1
    assert r["health"][3] == HEALTHY


def test_steal_band_rejected():
    reqs = make_traffic("spiky", n=60, seed=0)
    with pytest.raises(ValueError, match="steal"):
        simulate_cluster(reqs, num_replicas=2, schedule="ws_rr,4/fac2",
                         resilience=ResilienceConfig())


def test_router_continuation_rejected():
    reqs = make_traffic("spiky", n=60, seed=0)
    router = ClusterRouter(2, schedule="awf_b")
    with pytest.raises(ValueError, match="router"):
        simulate_cluster(reqs, num_replicas=2, schedule="awf_b/fac2",
                         router=router, resilience=ResilienceConfig())


# ---------------------------------------------------------------------------
# HealthTracker / ResilienceConfig units
# ---------------------------------------------------------------------------


def test_config_validation():
    with pytest.raises(ValueError, match="ewma_alpha"):
        ResilienceConfig(ewma_alpha=0.0)
    with pytest.raises(ValueError, match="deadline_k"):
        ResilienceConfig(deadline_k=-1.0)
    with pytest.raises(ValueError, match="backoff"):
        ResilienceConfig(backoff=0.5)
    with pytest.raises(ValueError, match="max_hedges"):
        ResilienceConfig(max_hedges=0)
    with pytest.raises(ValueError, match="suspect_ratio"):
        ResilienceConfig(suspect_ratio=6.0, quarantine_ratio=5.0)


def test_health_tracker_observe_and_verdicts():
    cfg = ResilienceConfig(ewma_alpha=0.5, suspect_ratio=2.5,
                           quarantine_ratio=5.0, quarantine_misses=2)
    h = HealthTracker(2, cfg)
    assert h.observe(0, 1.0) == HEALTHY
    assert h.observe(0, 3.0) == SUSPECT          # 3x degradation
    assert h.state[0] == SUSPECT
    # EWMA moved to 0.5*1 + 0.5*3 = 2.0; a 10.1x obs is > 5x prior
    assert h.observe(0, 10.1) == QUARANTINED
    # clean completion is amnesty: suspect heals, misses reset
    h2 = HealthTracker(1, cfg)
    assert h2.on_miss(0) == SUSPECT
    assert h2.misses[0] == 1
    assert h2.observe(0, 1.0) == HEALTHY
    assert h2.misses[0] == 0
    assert h2.on_miss(0) == SUSPECT
    assert h2.on_miss(0) == QUARANTINED


def test_health_tracker_seeded_from_declared_speed():
    # a declared-slow replica is prior knowledge, not a fault signal:
    # observing its declared slowness is deg == 1.0 -> healthy
    h = HealthTracker(2, base_speed=[1.0, 4.0])
    assert h.observe(1, 4.0) == HEALTHY
    assert h.allowed_span(1, span=1.0) > h.allowed_span(0, span=1.0)


def test_health_tracker_relax_and_reset():
    h = HealthTracker(1)
    base = h.allowed_span(0, span=1.0)
    h.relax(0)
    assert h.allowed_span(0, span=1.0) > base
    h.on_miss(0)
    h.reset(0, slowness=2.0)
    assert h.state[0] == HEALTHY and h.misses[0] == 0
    assert h.deadline_scale[0] == 1.0 and h.slowness[0] == 2.0


def test_health_tracker_healthy_slowness_median():
    h = HealthTracker(3, base_speed=[1.0, 2.0, 40.0])
    h.state[2] = QUARANTINED
    assert h.healthy_slowness([0, 1, 2]) == pytest.approx(1.5)
    h.state[0] = h.state[1] = QUARANTINED
    assert h.healthy_slowness([0, 1, 2]) == 1.0


def test_allowed_span_wait_is_additive():
    # the arrival wait must not be scaled by deadline_k: the deadline
    # for (span, wait) is exactly wait more than for (span, 0)
    h = HealthTracker(1)
    a0 = h.allowed_span(0, span=1.0, wait=0.0)
    a1 = h.allowed_span(0, span=1.0, wait=0.7)
    assert a1 == pytest.approx(a0 + 0.7)


# ---------------------------------------------------------------------------
# scheduler / elastic plumbing units
# ---------------------------------------------------------------------------


def _reqs(n, cost_new=8):
    return [Request(rid=i, arrival=0.0, prompt_len=16,
                    max_new_tokens=cost_new) for i in range(n)]


def test_scheduler_take_front():
    s = RequestScheduler(num_workers=2, technique="fac2")
    for r in _reqs(6):
        s.submit(r)
    taken = s.take_front(2)
    assert [r.rid for r in taken] == [0, 1]
    assert s.backlog == 4
    assert s.take_front(0) == []
    assert [r.rid for r in s.take_front(100)] == [2, 3, 4, 5]
    assert s.backlog == 0 and s.take_front(1) == []


def test_cluster_router_take_one():
    router = ClusterRouter(2, schedule="awf_b")
    for r in _reqs(3):
        router.submit(r)
    got = router.take_one()
    assert got is not None and got.rid == 0
    router.take_one(), router.take_one()
    assert router.take_one() is None
    steal = ClusterRouter(2, schedule="ws_rr,4")
    with pytest.raises(ValueError, match="take_one"):
        steal.take_one()


def test_neutralize_worker_state_resets_awf():
    from repro.serve.elastic import neutralize_worker_state
    s = RequestScheduler(num_workers=3, technique="awf_c")
    for r in _reqs(30):
        s.submit(r)
    # run a few pull/complete rounds with worker 2 looking very slow
    for _ in range(4):
        for w in range(3):
            chunk = s.pull(w)
            if chunk:
                cost = sum(r.cost for r in chunk)
                s.complete(w, elapsed=cost * (50.0 if w == 2 else 1.0))
    tech = s._tech
    assert tech is not None
    w_before = np.array(tech.weights, dtype=float)
    assert w_before[2] < w_before[0]  # the slow worker was de-weighted
    changed = neutralize_worker_state(tech, [2])
    assert changed
    w_after = np.array(tech.weights, dtype=float)
    # neutralized to its peers' mean weight, normalized to sum p
    assert w_after[2] == pytest.approx((w_after[0] + w_after[1]) / 2.0)
    assert float(np.sum(w_after)) == pytest.approx(3.0)


def test_scheduler_neutralize_worker_applies_on_next_plan():
    s = RequestScheduler(num_workers=2, technique="awf_c")
    for r in _reqs(20):
        s.submit(r)
    for _ in range(3):
        for w in range(2):
            chunk = s.pull(w)
            if chunk:
                cost = sum(r.cost for r in chunk)
                s.complete(w, elapsed=cost * (20.0 if w else 1.0))
    s.neutralize_worker(1)
    for r in _reqs(10):
        s.submit(r)
    s.pull(0)  # forces the next technique plan; neutralization applies
    w = np.array(s._tech.weights, dtype=float)
    assert w[1] == pytest.approx(w[0])
    with pytest.raises(ValueError):
        s.neutralize_worker(7)


# ---------------------------------------------------------------------------
# direct entry point
# ---------------------------------------------------------------------------


def test_simulate_cluster_resilient_direct_call_matches_dispatch():
    reqs = make_traffic("spiky", n=200, seed=4)
    evs = thermal_program(1, times=(0.1,), speeds=(10.0,))
    cfg = ResilienceConfig()
    a = simulate_cluster_resilient(reqs, num_replicas=3,
                                   schedule="awf_b/fac2", events=evs,
                                   return_completions=True, resilience=cfg)
    b = simulate_cluster(reqs, num_replicas=3, schedule="awf_b/fac2",
                         events=evs, return_completions=True, resilience=cfg)
    assert a["completions"] == b["completions"]
    assert a["resilience"] == b["resilience"]
