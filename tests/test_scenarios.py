"""Scenario-engine tests: registry coverage, trace shape, determinism,
and the dynamic-contention properties."""

import numpy as np
import pytest

from repro.serving.experiment import expand_function_clones, run_scenario
from repro.serving.profiles import base_function, build_input_pool, build_profiles
from repro.serving.simulator import SimConfig
from repro.serving.workload import (
    ScenarioSpec,
    generate_scenario,
    list_scenarios,
)

SMALL_CFG = dict(
    n_workers=4, vcpus_per_worker=32, physical_cores=32,
    mem_mb_per_worker=16 * 1024, vcpu_limit=32, seed=0,
    # bound the retry backlog so saturating shapes stay test-sized
    retry_interval_s=1.0, queue_timeout_s=45.0,
)


def _fns_and_counts():
    profiles = build_profiles()
    pool = build_input_pool()
    return sorted(profiles), {f: len(pool[f]) for f in profiles}


def test_registry_has_required_scenarios():
    names = list_scenarios()
    assert len(names) >= 7
    for required in ("azure", "poisson-steady", "flash-crowd", "diurnal",
                     "heavy-tail-inputs", "cold-storm", "oversubscribe"):
        assert required in names


def test_unknown_scenario_raises():
    fns, counts = _fns_and_counts()
    with pytest.raises(KeyError, match="unknown scenario"):
        generate_scenario(ScenarioSpec(scenario="nope"), fns, counts)


@pytest.mark.parametrize("scenario", list_scenarios())
def test_traces_well_formed_and_deterministic(scenario):
    """Same ScenarioSpec + seed => the identical Arrival list (ids
    included), sorted by time, within the window, with valid inputs."""
    fns, counts = _fns_and_counts()
    spec = ScenarioSpec(scenario=scenario, rps=2.0, duration_s=90.0, seed=11)
    t1 = generate_scenario(spec, fns, counts)
    t2 = generate_scenario(spec, fns, counts)
    assert t1 == t2
    assert [a.invocation_id for a in t1] == list(range(len(t1)))
    assert all(t1[i].t <= t1[i + 1].t for i in range(len(t1) - 1))
    # azure inherits generate_trace's whole-minute granularity, so the
    # window rounds up to the next minute boundary
    window = 60.0 * np.ceil(spec.duration_s / 60.0)
    for a in t1:
        assert 0.0 <= a.t < window
        assert 0 <= a.input_idx < counts[a.function]


def test_different_seeds_differ():
    fns, counts = _fns_and_counts()
    a = generate_scenario(
        ScenarioSpec(scenario="poisson-steady", rps=3.0, duration_s=120.0,
                     seed=0), fns, counts)
    b = generate_scenario(
        ScenarioSpec(scenario="poisson-steady", rps=3.0, duration_s=120.0,
                     seed=1), fns, counts)
    assert [x.t for x in a] != [x.t for x in b]


def test_flash_crowd_spikes():
    fns, counts = _fns_and_counts()
    spec = ScenarioSpec(scenario="flash-crowd", rps=2.0, duration_s=300.0,
                        seed=0, params={"spike_start_frac": 0.4,
                                        "spike_duration_s": 60.0,
                                        "spike_mult": 8.0})
    trace = generate_scenario(spec, fns, counts)
    t0, t1 = 120.0, 180.0
    in_spike = sum(1 for a in trace if t0 <= a.t < t1)
    outside = len(trace) - in_spike
    spike_rate = in_spike / 60.0
    base_rate = outside / 240.0
    assert spike_rate > 4.0 * base_rate  # ~8x nominally


def test_heavy_tail_skews_large():
    fns, counts = _fns_and_counts()
    base = generate_scenario(
        ScenarioSpec(scenario="poisson-steady", rps=4.0, duration_s=300.0,
                     seed=2), fns, counts)
    heavy = generate_scenario(
        ScenarioSpec(scenario="heavy-tail-inputs", rps=4.0, duration_s=300.0,
                     seed=2), fns, counts)

    def mean_frac(trace):
        return np.mean([a.input_idx / max(counts[a.function] - 1, 1)
                        for a in trace])

    assert mean_frac(heavy) > mean_frac(base) + 0.2


def test_scenario_simulation_deterministic():
    """Same spec + seed => identical summarize() metrics across two
    fresh Simulator runs, for three scenario shapes (satellite req)."""
    for scenario in ("poisson-steady", "flash-crowd", "cold-storm"):
        spec = ScenarioSpec(scenario=scenario, rps=2.0, duration_s=90.0,
                            seed=4)
        s1 = run_scenario("shabari", spec, sim_cfg=SimConfig(**SMALL_CFG))
        s2 = run_scenario("shabari", spec, sim_cfg=SimConfig(**SMALL_CFG))
        assert s1.summary == s2.summary, scenario


def test_dynamic_contention_mode():
    """contention_mode="dynamic" re-times co-runners instead of fixing
    the start-time snapshot; it must stay deterministic, account for
    every arrival, and keep result invariants intact."""
    spec = ScenarioSpec(scenario="flash-crowd", rps=2.0, duration_s=90.0,
                        seed=0)
    # vcpu_limit > physical_cores (the §6 userCPU knob): co-runner
    # demand must be able to exceed the cores for contention to exist
    # at all. With acquire-on-placement accounting, fits() caps
    # committed vCPUs at vcpu_limit, so at vcpu_limit == cores no
    # worker ever runs contended and dynamic == snapshot trivially.
    over_cfg = {**SMALL_CFG, "vcpu_limit": 44}
    cfg = SimConfig(**over_cfg, contention_mode="dynamic")
    r1 = run_scenario("shabari", spec, sim_cfg=cfg, keep_results=True)
    r2 = run_scenario("shabari", spec, sim_cfg=cfg)
    assert r1.summary == r2.summary
    assert r1.summary["n"] == len(r1.results)
    for x in r1.results:
        if not x.timed_out:
            assert x.finish_t >= x.start_t >= x.arrival_t - 1e-9
            assert abs((x.finish_t - x.start_t) - x.exec_s) < 1e-6
    # and it actually differs from the snapshot semantics
    snap = run_scenario(
        "shabari", spec, sim_cfg=SimConfig(**over_cfg)).summary
    assert r1.summary != snap


def test_expand_function_clones_aliases():
    profiles = build_profiles()
    pool = build_input_pool()
    slo = {(fn, i): 1.0 for fn in profiles for i in range(len(pool[fn]))}
    P, L, S = expand_function_clones(profiles, pool, slo, clones=3)
    assert len(P) == 3 * len(profiles)
    assert P["matmult::2"] is profiles["matmult"]
    assert base_function("matmult::2") == "matmult"
    assert S[("matmult::2", 0)] == slo[("matmult", 0)]
    # clones == 1 is the identity
    P1, L1, S1 = expand_function_clones(profiles, pool, slo, clones=1)
    assert P1 is profiles and L1 is pool and S1 is slo
