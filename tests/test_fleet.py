"""Heterogeneous fleet + topology tests (repro.core.fleet).

Three layers of coverage:

* the homogeneous-default EQUIVALENCE contract — an explicit uniform
  FleetSpec with free links reproduces the committed goldens
  byte-for-byte (asserted with exact equality, not tolerance);
* unit behavior of the new vocabulary — Topology transfer math,
  per-machine cold curves, per-worker §5 contention/NIC denominators,
  exec-speed factors, preemptible-last cold placement, clone-pooled
  calibration, per-cluster SLO-admission costs;
* runtime transfer charging — remote placements over non-free links
  start later by the payload's link time; local placements don't.
"""

import json
import os

import pytest

from repro.core.allocator import Allocation
from repro.core.cluster import Cluster
from repro.core.fleet import (
    COLD_JITTER_MEAN,
    ClusterSpec,
    FleetSpec,
    Link,
    MachineType,
    Topology,
)
from repro.core.router import DEFAULT_EXEC_ESTIMATE_S, Router
from repro.core.scheduler import ShabariScheduler
from repro.serving import baselines as B
from repro.serving.experiment import make_policy, run_scenario
from repro.serving.golden import golden_sim_config, golden_specs
from repro.serving.profiles import (
    base_function,
    build_input_pool,
    build_profiles,
)
from repro.serving.simulator import NIC_GBPS, SimConfig, Simulator
from repro.serving.workload import Arrival

ALLOC = Allocation(4, 512)


# ------------------------------------------------------------- vocabulary
def test_link_transfer_math():
    # 1000 MB over 1 Gbps = 8000 Mb / 1000 Mb/s = 8 s, plus latency
    assert Link(gbps=1.0, latency_s=0.05).transfer_s(1000.0) == pytest.approx(
        8.05)
    assert Link(gbps=10.0).transfer_s(125.0) == pytest.approx(0.1)
    # the default link is free
    assert Link().transfer_s(10_000.0) == 0.0
    # zero payload pays only the link latency
    assert Link(gbps=1.0, latency_s=0.02).transfer_s(0.0) == 0.02


def test_topology_lookup_symmetric_with_default_fallback():
    fast = Link(gbps=10.0)
    topo = Topology(default_link=Link(gbps=1.0, latency_s=0.1),
                    links=(((0, 1), fast),))
    assert topo.link(0, 1) is fast
    assert topo.link(1, 0) is fast  # symmetric
    assert topo.link(0, 2).gbps == 1.0  # unlisted pair -> default
    # intra-cluster transfer is always free
    assert topo.transfer_s(1, 1, 1e9) == 0.0
    assert topo.transfer_s(0, 2, 100.0) == pytest.approx(0.1 + 0.8)


def test_topology_is_free_detection():
    assert Topology().is_free()
    assert not Topology(default_link=Link(gbps=1.0)).is_free()
    assert not Topology(links=(((0, 1), Link(latency_s=0.01)),)).is_free()


def test_machine_cold_curve_and_limit():
    m = MachineType(cold_base_s=0.5, cold_per_gb_s=0.2)
    assert m.cold_latency_s(2048) == pytest.approx(0.5 + 0.4)
    assert MachineType(vcpus=64).limit == 64
    assert MachineType(vcpus=64, vcpu_limit=90).limit == 90


def test_fleet_spec_composition():
    a, b = MachineType(name="a"), MachineType(name="b")
    spec = ClusterSpec(machines=((a, 2), (b, 1)))
    assert spec.n_workers == 3
    assert [m.name for m in spec.worker_machines()] == ["a", "a", "b"]
    fleet = FleetSpec.uniform(3, 4, a)
    assert fleet.n_clusters == 3
    assert all(cl.n_workers == 4 for cl in fleet.clusters)
    assert fleet.topology.is_free()
    priced = FleetSpec(clusters=(
        ClusterSpec(machines=((MachineType(price_per_hour=2.0), 2),)),
        ClusterSpec(machines=((MachineType(price_per_hour=0.5), 4),)),
    ))
    assert priced.price_per_hour() == pytest.approx(6.0)


def test_cluster_builds_workers_from_machines():
    small = MachineType(physical_cores=8, vcpus=8, mem_mb=4096, vcpu_limit=12)
    big = MachineType(physical_cores=96, vcpus=90)
    cl = Cluster(machines=[small, big])
    assert [w.total_vcpus for w in cl.workers] == [8, 90]
    assert [w.vcpu_limit for w in cl.workers] == [12, 90]
    assert cl.workers[0].total_mem_mb == 4096
    assert cl.workers[0].machine is small and cl.workers[1].machine is big
    # the legacy uniform path still mirrors the scalar args
    legacy = Cluster(n_workers=2, vcpus_per_worker=16,
                     mem_mb_per_worker=8192, vcpu_limit=20)
    assert all(w.machine.vcpus == 16 and w.vcpu_limit == 20
               for w in legacy.workers)


# ------------------------------------------- homogeneous-default equivalence
@pytest.mark.parametrize("scenario", ["poisson-steady", "multi-cluster"])
def test_explicit_uniform_fleet_matches_golden_exactly(scenario):
    """SimConfig(fleet=<uniform, free links>) must reproduce the
    committed golden summary EXACTLY (==, not tolerance): the fleet
    layer's default arithmetic is inert, the same guarantee the
    byte-identical golden refresh enforces for fleet=None."""
    cfg = golden_sim_config(scenario)
    machine = MachineType(
        physical_cores=cfg.physical_cores,
        vcpus=cfg.vcpus_per_worker,
        mem_mb=cfg.mem_mb_per_worker,
        nic_gbps=NIC_GBPS,
        cold_base_s=cfg.cold_base_s,
        cold_per_gb_s=cfg.cold_per_gb_s,
        vcpu_limit=cfg.vcpu_limit,
    )
    fleet = FleetSpec.uniform(cfg.n_clusters, cfg.n_workers, machine)
    import dataclasses
    got = run_scenario(
        "shabari", golden_specs()[scenario],
        sim_cfg=dataclasses.replace(cfg, fleet=fleet)).summary
    path = os.path.join(os.path.dirname(__file__), "goldens",
                        f"{scenario}.json")
    with open(path) as f:
        want = json.load(f)["summary"]
    assert got == want


def test_default_config_builds_uniform_fleet():
    profiles = build_profiles()
    pool = build_input_pool(seed=0)
    slo = B.build_slo_table(profiles, pool)
    policy = make_policy("shabari", profiles, pool, slo, seed=0)
    sim = Simulator(policy=policy, profiles=profiles, input_pool=pool,
                    slo_table=slo,
                    cfg=SimConfig(n_workers=2, n_clusters=2))
    assert sim.fleet.n_clusters == 2
    assert not sim._charge_transfer
    for cl in sim.clusters:
        for w in cl.workers:
            assert w.machine.physical_cores == 96
            assert w.machine.nic_gbps == NIC_GBPS
            assert w.machine.exec_factor == 1.0


# --------------------------------------------------- per-machine simulation
def _stack():
    profiles = build_profiles()
    pool = build_input_pool(seed=0)
    return profiles, pool, B.build_slo_table(profiles, pool)


def _sim(fleet, **cfg_kwargs):
    profiles, pool, slo = _stack()
    policy = make_policy("shabari", profiles, pool, slo, seed=0)
    return Simulator(policy=policy, profiles=profiles, input_pool=pool,
                     slo_table=slo, cfg=SimConfig(fleet=fleet, **cfg_kwargs))


def test_per_machine_cold_latency():
    slow = MachineType(cold_base_s=0.9, cold_per_gb_s=0.3)
    sim = _sim(FleetSpec.uniform(1, 1, MachineType()), seed=0)
    fast_lat = [sim.cold_latency(4, 1024, MachineType()) for _ in range(64)]
    sim2 = _sim(FleetSpec.uniform(1, 1, MachineType()), seed=0)
    slow_lat = [sim2.cold_latency(4, 1024, slow) for _ in range(64)]
    # identical jitter streams (same seed/draw order), so the ratio is
    # exactly the mean-field curve ratio
    ratio = (0.9 + 0.3) / (0.45 + 0.12)
    for f, s in zip(fast_lat, slow_lat):
        assert s / f == pytest.approx(ratio)


def test_per_worker_contention_denominator():
    """Fewer physical cores -> larger §5 slowdown for the same demand."""
    fleet = FleetSpec(clusters=(ClusterSpec(machines=(
        (MachineType(physical_cores=32, vcpus=32), 1),
        (MachineType(physical_cores=8, vcpus=32), 1),
    )),))
    sim = _sim(fleet)
    big, small = sim.clusters[0].workers
    big.add_active(16.0, 0.0)
    small.add_active(16.0, 0.0)
    assert sim._contention(big, "f", 16.0, 0.0) == pytest.approx(1.0)
    assert sim._contention(small, "f", 16.0, 0.0) == pytest.approx(4.0)


def test_per_worker_nic_clamp_and_net_slowdown():
    """_net_demand clamps at the MACHINE's NIC, and the §5 net slowdown
    divides by it (network-fed functions only)."""
    sim = _sim(FleetSpec.uniform(1, 1, MachineType(nic_gbps=2.0)))
    w = sim.clusters[0].workers[0]
    meta = {"file_size": 5e9}  # 5 GB payload -> 40 Gb over short exec
    assert sim._net_demand("compress", meta, 1.0, w.machine.nic_gbps) == 2.0
    w.add_active(0.0, 4.0)
    assert sim._contention(w, "compress", 0.0, 0.0) == pytest.approx(2.0)
    # non-network-fed functions never see the NIC term
    assert sim._contention(w, "floatops", 0.0, 0.0) == 1.0


def test_exec_factor_scales_exec_time():
    """The same trace on a 2x-slower machine finishes each invocation
    ~2x slower (uncontended), while calibration still records
    reference-normalized times."""
    profiles, pool, slo = _stack()

    def run_on(machine):
        sim = Simulator(policy=B.StaticPolicy(12, 6 * 1024, "s"),
                        profiles=profiles, input_pool=pool, slo_table=slo,
                        cfg=SimConfig(fleet=FleetSpec.uniform(1, 1, machine)))
        return sim, sim.run([Arrival(0, 0.0, "linpack", 0)])[0]

    ref, res_ref = run_on(MachineType())
    slow, res_slow = run_on(MachineType(exec_factor=2.0))
    assert not res_ref.oom_killed
    assert res_slow.exec_s == pytest.approx(2.0 * res_ref.exec_s)
    # observe_exec fed the REFERENCE time on both fleets
    key = base_function("linpack")
    assert slow.router._exec_ewma[key] == pytest.approx(
        ref.router._exec_ewma[key])


# ------------------------------------------------------- transfer charging
def _wan_fleet(gbps=1.0, latency_s=0.0):
    m = MachineType(physical_cores=32, vcpus=32, mem_mb=16 * 1024)
    return FleetSpec(
        clusters=(ClusterSpec(machines=((m, 1),)),
                  ClusterSpec(machines=((m, 1),))),
        topology=Topology(default_link=Link(gbps=gbps, latency_s=latency_s)),
    )


def test_remote_warm_placement_pays_transfer():
    """A warm container on a remote cluster starts only after the
    payload crosses the link; the same warm hit at home starts
    immediately. Driven through the simulator so the xfer_start event
    path is exercised end to end."""
    profiles, pool, slo = _stack()
    fn = "linpack"
    meta = pool[fn][0]
    from repro.serving.profiles import input_size_mb
    mb = input_size_mb(fn, meta)

    def run_with(warm_cluster):
        sim = Simulator(policy=B.StaticPolicy(4, 6 * 1024, "s"),
                        profiles=profiles, input_pool=pool, slo_table=slo,
                        cfg=SimConfig(fleet=_wan_fleet(gbps=1e-4)))
        home = sim.router.home_cluster(fn)
        ci = home if warm_cluster == "home" else 1 - home
        w = sim.clusters[ci].workers[0]
        sim.clusters[ci].new_container(
            w, fn, 4, 6 * 1024, now=0.0, warm_at=0.0)
        # saturate the home cluster so the router must take the remote
        # warm container in the remote case
        if warm_cluster == "remote":
            for hw in sim.clusters[home].workers:
                hw.acquire(hw.vcpu_limit, 0)
        return sim.run([Arrival(0, 0.0, fn, 0)])[0]

    local = run_with("home")
    remote = run_with("remote")
    xfer = Link(gbps=1e-4).transfer_s(mb)
    assert xfer > 0.1  # the link is slow enough to matter
    assert not local.cold_start and not remote.cold_start
    assert remote.start_t - local.start_t == pytest.approx(xfer, rel=1e-6)
    assert remote.queued_s - local.queued_s == pytest.approx(xfer, rel=1e-6)


def test_cold_start_overlaps_transfer():
    """A remote cold spill pays max(cold latency, transfer), not their
    sum — the payload moves while the container warms."""
    profiles, pool, slo = _stack()
    fn = "linpack"

    def run_with(latency_s):
        sim = Simulator(
            policy=B.StaticPolicy(4, 6 * 1024, "s"), profiles=profiles,
            input_pool=pool, slo_table=slo,
            cfg=SimConfig(fleet=_wan_fleet(latency_s=latency_s)))
        # saturate the home cluster: spill-over cold-starts the
        # invocation remotely, which charges the link
        home = sim.router.home_cluster(fn)
        for hw in sim.clusters[home].workers:
            hw.acquire(hw.vcpu_limit, 0)
        return sim.run([Arrival(0, 0.0, fn, 0)])[0]

    # tiny latency: the transfer hides entirely behind the cold start
    hidden = run_with(1e-6)
    # huge latency: the transfer dominates the cold start
    exposed = run_with(30.0)
    assert hidden.cold_start and exposed.cold_start
    assert hidden.start_t == pytest.approx(hidden.cold_latency_s, abs=0.05)
    assert exposed.start_t == pytest.approx(30.0, abs=0.1)


# --------------------------------------------------- router fleet pricing
def _mk_router(fleet, routing="estimate", **kwargs):
    clusters = [Cluster(machines=spec.worker_machines())
                for spec in fleet.clusters]
    scheds = [ShabariScheduler(c) for c in clusters]
    return clusters, Router(clusters, scheds, routing=routing,
                            topology=fleet.topology,
                            network_fed=lambda f: False, **kwargs)


def test_estimate_prices_transfer_on_remote_spill():
    """With the home cluster saturated, the estimate's remote score
    includes the payload's link time — and the transfer-blind A/B arm
    (price_transfer=False) scores the same spill as free."""
    fleet = _wan_fleet(gbps=1.0)
    clusters, r = _mk_router(fleet)
    home = r.home_cluster("f")
    for w in clusters[home].workers:
        w.acquire(w.vcpu_limit, 0)
    est, kind, _ = r._estimate(1 - home, "f", ALLOC, 0.0, input_mb=1000.0)
    blind_clusters, rb = _mk_router(fleet, price_transfer=False)
    for w in blind_clusters[home].workers:
        w.acquire(w.vcpu_limit, 0)
    est_blind, _, _ = rb._estimate(1 - home, "f", ALLOC, 0.0,
                                   input_mb=1000.0)
    # 1000 MB over 1 Gbps = 8 s; cold start ~0.5 s overlaps inside it
    # (the cold term prices the jitter expectation, not the median)
    assert est - est_blind == pytest.approx(
        8.0 - clusters[0].workers[0].machine.cold_latency_s(ALLOC.mem_mb)
        * COLD_JITTER_MEAN)
    assert est > est_blind + 7.0


def test_estimate_prefers_home_when_transfer_dominates():
    """A loaded-but-usable home beats an idle remote once the payload's
    link time exceeds the home penalty; with a tiny payload the idle
    remote wins again (same fleet, same load)."""
    fleet = _wan_fleet(gbps=0.1)  # 10 MB/s-ish: heavy payloads hurt
    clusters, r = _mk_router(fleet)
    home = r.home_cluster("f")
    # home busy enough that a remote cold start would win a free spill
    clusters[home].workers[0].add_active(64.0, 0.0)
    r.observe_exec("f", 1.0)
    heavy = r.route("f", ALLOC, 0.0, input_mb=2000.0)
    assert heavy.cluster_idx == home and not heavy.spilled
    light = r.route("f", ALLOC, 0.0, input_mb=0.001)
    assert light.cluster_idx == 1 - home and light.spilled


def test_estimate_prices_exec_factor_and_cold_curve():
    """Candidate scoring scales exec by the worker's speed factor and
    uses the worker's own cold curve: an idle slow-tier cluster loses
    to an equally idle fast tier."""
    fast = MachineType(physical_cores=32, vcpus=32, mem_mb=16 * 1024)
    slow = MachineType(physical_cores=32, vcpus=32, mem_mb=16 * 1024,
                       exec_factor=3.0, cold_base_s=1.5)
    fleet = FleetSpec(clusters=(ClusterSpec(machines=((fast, 1),)),
                                ClusterSpec(machines=((slow, 1),))))
    clusters, r = _mk_router(fleet)
    r.observe_exec("f", 2.0)
    est_fast, _, _ = r._estimate(0, "f", ALLOC, 0.0)
    est_slow, _, _ = r._estimate(1, "f", ALLOC, 0.0)
    # fast: 0.45 + 0.12*0.5 cold + 2 s exec; slow: 1.5 + 0.18*... + 6 s
    assert est_slow - est_fast == pytest.approx(
        (slow.cold_latency_s(ALLOC.mem_mb)
         - fast.cold_latency_s(ALLOC.mem_mb)) * COLD_JITTER_MEAN
        + (3.0 - 1.0) * 2.0)
    rd = r.route("f", ALLOC, 0.0)
    assert rd.cluster_idx == 0


def test_slo_reject_uses_per_cluster_costs():
    """admission='slo' must not admit on a fantasy mix of one cluster's
    idle worker and another's fast silicon: with the fast tier slammed
    and only a far/slow tier idle, the honest per-cluster minimum
    exceeds the budget and the invocation is shed."""
    from repro.core.ect import ECT_BLIND_SHED_BAND, ECT_SHED_OBS

    fast = MachineType(physical_cores=32, vcpus=64, mem_mb=16 * 1024)
    slow = MachineType(physical_cores=32, vcpus=64, mem_mb=16 * 1024,
                       exec_factor=200.0)
    fleet = FleetSpec(clusters=(ClusterSpec(machines=((fast, 1),)),
                                ClusterSpec(machines=((slow, 1),))))
    clusters, r = _mk_router(fleet, routing="spill-over", admission="slo")
    for _ in range(ECT_SHED_OBS):
        r.observe_exec("f", 1.0)  # mature estimate: ~1 s on reference
    # fast worker 256x oversubscribed -> ~256 s there; slow tier idle
    # but 200x silicon -> ~200 s there. Honest per-cluster min ~200 s,
    # far past the blind-shed band (4 s budget x 32 = 128 s).
    clusters[0].workers[0].add_active(8192.0, 0.0)
    assert 4.0 * ECT_BLIND_SHED_BAND < 200.0
    rd = r.route("f", ALLOC, 0.0, slo_s=4.0)
    assert rd.shed and r.admission_slo_shed == 1
    # the OLD fleet-min bug would have scored: min slowdown over ALL
    # workers (idle slow tier, 1.0) x exec 1 s ~= 1 s < budget ->
    # admitted. Sanity-check that an honest fleet with an idle FAST
    # tier does admit:
    clusters2, r2 = _mk_router(fleet, routing="spill-over", admission="slo")
    for _ in range(ECT_SHED_OBS):
        r2.observe_exec("f", 1.0)
    clusters2[1].workers[0].add_active(8192.0, 0.0)  # slam the SLOW tier
    assert not r2.route("f", ALLOC, 0.0, slo_s=4.0).shed


# ------------------------------------------------- preemptible-last packing
def test_cold_placement_prefers_reliable_workers():
    spot = MachineType(preemptible=True, vcpus=32, mem_mb=16 * 1024)
    firm = MachineType(vcpus=32, mem_mb=16 * 1024)
    cl = Cluster(machines=[spot, spot, firm])
    sched = ShabariScheduler(cl)
    w = sched.cold_candidate("f", 4, 512)
    assert w is cl.workers[2] and not w.machine.preemptible
    # saturate the reliable worker: spot tier becomes the fallback, in
    # walk order
    cl.workers[2].acquire(32, 0)
    w2 = sched.cold_candidate("f", 4, 512)
    assert w2 is not None and w2.machine.preemptible


# ------------------------------------------------- clone-pooled calibration
def test_observe_exec_pools_clone_aliases():
    """With pool_key=base_function (what the Simulator passes), clone
    aliases share one estimator: observations through 'f::1' move the
    estimate 'f::2' sees."""
    cl = Cluster(n_workers=1, vcpus_per_worker=16, mem_mb_per_worker=8192)
    r = Router([cl], [ShabariScheduler(cl)], routing="estimate",
               pool_key=base_function)
    assert r._exec_estimate("f::2") == DEFAULT_EXEC_ESTIMATE_S
    r.observe_exec("f::1", 4.0)
    assert r._exec_estimate("f::2") == pytest.approx(4.0)
    assert r._exec_estimate("f") == pytest.approx(4.0)
    r.observe_exec("f", 2.0)
    assert r._exec_estimate("f::7") == pytest.approx(0.7 * 4.0 + 0.3 * 2.0)
    assert set(r._exec_ewma) == {"f"}
    # without a pool key, aliases stay independent (the old behavior)
    r2 = Router([cl], [ShabariScheduler(cl)], routing="estimate")
    r2.observe_exec("f::1", 4.0)
    assert r2._exec_estimate("f::2") == DEFAULT_EXEC_ESTIMATE_S
