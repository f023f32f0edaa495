"""Acquire-on-placement reservation + router admission-control tests.

Covers the resource-lifecycle change (capacity reserved when a cold
start is PLACED, not when it starts): worker/cluster accounting,
``Worker.fits`` and ``Router._load`` seeing committed-but-warming
capacity, conversion/cancellation of reservations, and front-door
admission control (shed / queue) under fleet-wide overload.
"""

import pytest

from repro.core.allocator import Allocation
from repro.core.cluster import Cluster
from repro.core.router import Router
from repro.core.scheduler import ShabariScheduler
from repro.serving import baselines as B
from repro.serving.experiment import make_policy, run_scenario
from repro.serving.profiles import build_input_pool, build_profiles
from repro.serving.simulator import SimConfig, Simulator
from repro.serving.workload import Arrival, ScenarioSpec


# ------------------------------------------------- worker-level accounting
def _worker(cluster=None):
    cl = cluster or Cluster(n_workers=1, vcpus_per_worker=16,
                            mem_mb_per_worker=8192, vcpu_limit=16)
    return cl, cl.workers[0]


def test_reserve_counts_against_fits():
    _, w = _worker()
    assert w.fits(12, 1024)
    w.reserve(12, 1024)
    assert w.used_vcpus == 12 and w.reserved_vcpus == 12
    assert not w.fits(12, 1024)  # warming capacity is committed capacity
    assert w.fits(4, 1024)


def test_commit_keeps_load_until_release():
    _, w = _worker()
    w.reserve(8, 512)
    w.commit_reservation(8, 512)
    # still held — it converted to a running acquisition, not freed
    assert w.used_vcpus == 8 and w.used_mem_mb == 512
    assert w.reserved_vcpus == 0 and w.reserved_mem_mb == 0
    w.release(8, 512)
    assert w.used_vcpus == 0 and w.used_mem_mb == 0


def test_cancel_reservation_frees_capacity():
    _, w = _worker()
    w.reserve(8, 512)
    w.cancel_reservation(8, 512)
    assert w.used_vcpus == 0 and w.used_mem_mb == 0
    assert w.reserved_vcpus == 0 and w.reserved_mem_mb == 0


def test_cluster_aggregates_track_reservations():
    cl, w = _worker()
    w.reserve(8, 512)
    assert (cl.used_vcpus, cl.reserved_vcpus) == (8, 8)
    assert (cl.used_mem_mb, cl.reserved_mem_mb) == (512, 512)
    w.commit_reservation(8, 512)
    assert (cl.used_vcpus, cl.reserved_vcpus) == (8, 0)
    w.release(8, 512)
    assert (cl.used_vcpus, cl.used_mem_mb) == (0, 0)


def test_router_load_sees_reservations():
    clusters = [
        Cluster(n_workers=2, vcpus_per_worker=16, mem_mb_per_worker=8192,
                vcpu_limit=16)
        for _ in range(2)
    ]
    r = Router(clusters, [ShabariScheduler(c) for c in clusters])
    assert r._load(0) == 0.0
    clusters[0].workers[0].reserve(16, 1024)
    assert r._load(0) == pytest.approx(0.5)  # 16 of 32 vCPUs committed
    clusters[0].workers[0].cancel_reservation(16, 1024)
    assert r._load(0) == 0.0


# ------------------------------------------------------- simulator lifecycle
@pytest.fixture(scope="module")
def stack():
    profiles = build_profiles()
    pool = build_input_pool(seed=0)
    slo_table = B.build_slo_table(profiles, pool)
    return profiles, pool, slo_table


def _sim(stack, **cfg_overrides):
    profiles, pool, slo_table = stack
    cfg = SimConfig(n_workers=2, vcpus_per_worker=16, physical_cores=16,
                    mem_mb_per_worker=8 * 1024, vcpu_limit=16, seed=0,
                    **cfg_overrides)
    # static-medium: a deterministic 12-vCPU allocation, no jax dispatch
    policy = make_policy("static-medium", profiles, pool, slo_table, seed=0)
    return Simulator(policy=policy, profiles=profiles, input_pool=pool,
                     slo_table=slo_table, cfg=cfg), sorted(profiles)[0]


def test_cold_placement_reserves_immediately(stack):
    sim, fn = _sim(stack)
    sim._on_arrival(Arrival(0, 0.0, fn, 0), 0.0)
    # the invocation hasn't STARTED (container still warming), but its
    # capacity is already committed
    assert sim.cluster.used_vcpus == 12
    assert sim.cluster.reserved_vcpus == 12
    (c,) = [c for w in sim.cluster.workers for c in w.containers.values()]
    assert c.reserved and c.busy


def test_second_cold_start_not_stacked_onto_reserved_worker(stack):
    sim, fn = _sim(stack)
    sim._on_arrival(Arrival(0, 0.0, fn, 0), 0.0)
    sim._on_arrival(Arrival(1, 0.0, fn, 0), 0.0)
    workers = {c.worker.wid
               for w in sim.cluster.workers for c in w.containers.values()}
    assert len(workers) == 2  # fits() saw the reservation and spread out
    assert sim.cluster.reserved_vcpus == 24


def test_reservation_converts_and_releases_through_full_run(stack):
    sim, fn = _sim(stack)
    results = sim.run([Arrival(0, 0.0, fn, 0), Arrival(1, 0.5, fn, 1)])
    assert len(results) == 2
    assert all(r.cold_start and not r.timed_out for r in results)
    assert sim.cluster.reserved_vcpus == 0 and sim.cluster.reserved_mem_mb == 0
    assert sim.cluster.used_vcpus == 0 and sim.cluster.used_mem_mb == 0


def test_reservation_released_when_cold_start_outlives_timeout(stack):
    # queue timeout shorter than any cold-start latency: the warm_start
    # event must cancel the reservation instead of running the invocation
    sim, fn = _sim(stack, queue_timeout_s=0.05)
    results = sim.run([Arrival(0, 0.0, fn, 0)])
    assert len(results) == 1 and results[0].timed_out
    assert results[0].queued_s > 0.05
    assert sim.cluster.reserved_vcpus == 0 and sim.cluster.used_vcpus == 0
    # the warmed container survives as idle warm capacity
    (c,) = [c for w in sim.cluster.workers for c in w.containers.values()]
    assert not c.busy and not c.reserved


# --------------------------------------------------------- admission control
def _fleet(n_clusters=2, admission="shed", headroom=0.5):
    clusters = [
        Cluster(n_workers=2, vcpus_per_worker=16, mem_mb_per_worker=8192,
                vcpu_limit=16)
        for _ in range(n_clusters)
    ]
    scheds = [ShabariScheduler(c) for c in clusters]
    return clusters, Router(clusters, scheds, admission=admission,
                            admission_headroom=headroom)


def test_admission_sheds_when_every_cluster_over_headroom():
    clusters, r = _fleet()
    for cl in clusters:
        cl.workers[0].reserve(16, 1024)  # both clusters at 0.5 occupancy
    rd = r.route("f", Allocation(4, 512), 0.0)
    assert rd.shed and rd.decision.queued
    assert r.admission_shed == 1


def test_admission_admits_while_any_cluster_under_headroom():
    clusters, r = _fleet()
    clusters[0].workers[0].reserve(16, 1024)  # only one cluster loaded
    rd = r.route("f", Allocation(4, 512), 0.0)
    assert not rd.shed and not rd.decision.queued
    assert r.admission_shed == 0


def test_admission_queue_mode_holds_without_shedding():
    clusters, r = _fleet(admission="queue")
    for cl in clusters:
        cl.workers[0].reserve(16, 1024)
    rd = r.route("f", Allocation(4, 512), 0.0)
    assert not rd.shed and rd.decision.queued
    assert r.admission_queue_events == 1 and r.admission_shed == 0


def test_invalid_admission_rejected():
    clusters = [Cluster(n_workers=1)]
    with pytest.raises(AssertionError):
        Router(clusters, [ShabariScheduler(clusters[0])],
               admission="drop-everything")


def _overload_cfg(**overrides):
    return SimConfig(n_workers=2, n_clusters=2, vcpus_per_worker=16,
                     physical_cores=16, mem_mb_per_worker=8 * 1024,
                     vcpu_limit=16, retry_interval_s=1.0,
                     queue_timeout_s=30.0, seed=0, **overrides)


def test_admission_shed_end_to_end():
    spec = ScenarioSpec(scenario="oversubscribe", rps=3.0, duration_s=60.0,
                        seed=0, params={"load_mult": 3.0})
    res = run_scenario(
        "shabari", spec,
        sim_cfg=_overload_cfg(admission="shed", admission_headroom=0.5),
        keep_results=True,
    )
    assert res.summary["shed_pct"] > 0
    assert res.summary["n"] == len(res.results)
    shed = [r for r in res.results if r.shed]
    assert all(r.slo_violated and not r.timed_out for r in shed)


def test_admission_queue_end_to_end_sheds_nothing():
    spec = ScenarioSpec(scenario="oversubscribe", rps=3.0, duration_s=60.0,
                        seed=0, params={"load_mult": 3.0})
    res = run_scenario(
        "shabari", spec,
        sim_cfg=_overload_cfg(admission="queue", admission_headroom=0.5),
    )
    assert res.summary["shed_pct"] == 0.0
    assert res.summary["n"] > 0
