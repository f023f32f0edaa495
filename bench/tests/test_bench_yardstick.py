"""The benchmark's copies of the program's arithmetic still agree with the
program, and its reference rejects what it must.

Traffic generators, the SLO table and the quality arithmetic are copied
into ``bench/`` so that a change to the program cannot move the
yardstick; these tests say when a copy and its original part ways."""

import dataclasses

import numpy as np
import pytest

from bench import harness, invariants, quality, reference, traffic
from bench.world import (build_slo_table, build_world, digest_pool,
                         digest_slo_table, expand_clones)
from repro.core import cost_functions as CF
from repro.core.agent_arena import ArenaEngine
from repro.core.cost_functions import Observation
from repro.serving import baselines as B
from repro.serving.experiment import expand_function_clones
from repro.serving.profiles import build_input_pool, build_profiles
from repro.serving.simulator import summarize
from repro.serving.workload import ScenarioSpec, generate_scenario, generate_trace

PROFILES = build_profiles()
POOL = build_input_pool(seed=0)
FUNCTIONS = sorted(PROFILES)
IPF = {f: len(POOL[f]) for f in FUNCTIONS}
BENCH = harness.load_json(harness.CHECKOUT, "BENCHMARK.json")


def _rows(arrivals):
    return [(a.t, a.function, a.input_idx) for a in arrivals]


@pytest.mark.parametrize("seed", [0, 7, 2**31 + 11])
def test_azure_copy_reproduces_generate_trace(seed):
    mix = {"shape": "azure", "rps": 5.0, "duration_s": 240.0}
    want = generate_trace(rps=5.0, functions=FUNCTIONS, inputs_per_function=IPF,
                          duration_s=240.0, seed=seed)
    assert _rows(traffic.pass_trace(mix, FUNCTIONS, IPF, seed)) == _rows(want)


@pytest.mark.parametrize("shape,params,clones", [
    ("azure", {}, 1),
    ("uniform-poisson", {}, 6),
    ("hot-surge", {"hot_fns": 2, "hot_frac": 0.7, "spike_mult": 4.0,
                   "spike_start_frac": 0.25, "spike_duration_s": 30.0}, 1),
])
@pytest.mark.parametrize("seed", [1, 99])
def test_shape_copies_reproduce_generate_scenario(shape, params, clones, seed):
    P, L, _ = expand_function_clones(PROFILES, POOL, {
        (f, i): 1.0 for f in POOL for i in range(len(POOL[f]))}, clones)
    fns = sorted(P)
    ipf = {f: len(L[f]) for f in fns}
    mix = {"shape": shape, "rps": 6.0, "duration_s": 90.0, "params": params}
    spec = ScenarioSpec(traffic.PROGRAM_SCENARIO[shape], rps=6.0, duration_s=90.0,
                        seed=seed, params=dict(params))
    want = generate_scenario(spec, fns, ipf)
    got = traffic.pass_trace(mix, fns, ipf, seed)
    assert _rows(got) == _rows(want)
    assert [a.invocation_id for a in got] == [a.invocation_id for a in want]


@pytest.mark.parametrize("mix_name", ["azure"])
def test_seed_draws_the_whole_trace(mix_name):
    mix = traffic.load_mix(mix_name)
    P, L, _ = expand_clones(PROFILES, POOL, {
        (f, i): 1.0 for f in POOL for i in range(len(POOL[f]))},
        int(mix.get("clones", 1)))
    fns = sorted(P)
    ipf = {f: len(L[f]) for f in fns}
    a = traffic.pass_trace(mix, fns, ipf, 5)
    b = traffic.pass_trace(mix, fns, ipf, 2**32 + 5)
    assert _rows(a) == _rows(traffic.pass_trace(mix, fns, ipf, 5))
    # arrival times, the function sequence and the inputs all move
    assert [x.t for x in a] != [x.t for x in b]
    assert [x.function for x in a] != [x.function for x in b]
    assert len(a) == len(b) == round(mix["rps"] * mix["duration_s"])


def test_slo_table_copy_and_recorded_digests():
    mine = build_slo_table(PROFILES, POOL, multiplier=1.4)
    assert mine == B.build_slo_table(PROFILES, POOL, multiplier=1.4)
    for cfg in BENCH["configs"]:
        world = harness.load_json(harness.CHECKOUT, cfg["file"])["world"]
        assert world["digests"] == {"input_pool": digest_pool(POOL),
                                    "slo_table": digest_slo_table(mine)}


def test_changed_world_is_refused():
    world = harness.load_json(harness.BENCH_DIR, "configs",
                              "testbed-16x90.json")["world"]
    bad = dict(world, slo_multiplier=1.5)
    with pytest.raises(RuntimeError, match="digests"):
        build_world(bad, 1)


def test_clone_copy_matches_program():
    slo = build_slo_table(PROFILES, POOL)
    assert expand_clones(PROFILES, POOL, slo, 6) == \
        expand_function_clones(PROFILES, POOL, slo, 6)


@pytest.fixture(scope="module")
def short_pass():
    """One recorded pass of testbed.azure's timed path at 120 simulated
    seconds."""
    cell = harness.Cell(BENCH, "testbed.azure", 3)
    cell.mix["duration_s"] = 120.0
    ipf = {f: len(cell.pool[f]) for f in cell.functions}
    cell.trace = traffic.pass_trace(cell.mix, cell.functions, ipf, 3)
    rec = reference.Recorder()
    with rec.recording(ArenaEngine):
        sim = cell.new_sim()
        results = sim.run(cell.trace)
    (engine, stream), = rec.take()
    return cell, sim, results, engine, stream


def test_quality_copy_equals_summarize(short_pass):
    _, _, results, _, _ = short_pass
    got, want = quality.pass_quality(results), summarize(results)
    for k in ("n", "slo_violation_pct", "wasted_mem_mb_p50", "wasted_vcpus_p50",
              "cold_start_pct"):
        assert got[k] == want[k], k
    assert got["failed"] == sum(r.shed or r.timed_out or r.oom_killed
                                for r in results)


def _replay(engine, stream, **kw):
    return reference.replay(stream, reference.engine_weights(
        engine, reference.updated_functions(stream)), **kw)


def test_reference_accepts_the_program(short_pass):
    _, _, _, engine, stream = short_pass
    got = _replay(engine, stream)
    assert got["served"] > 0 and got["updates"] > 0
    assert got["breaches"] == 0
    assert got["served_gap"] <= reference.LIMITS["served_gap"]
    assert got["weight_dev_p50"] <= reference.LIMITS["weight_dev_p50"]
    assert got["weight_dev_p50"] <= got["weight_dev_max"]


def test_reference_rejects_a_changed_memory_cost_slope(short_pass, monkeypatch):
    _, _, _, engine, stream = short_pass
    monkeypatch.setitem(reference.SLOPES, "mem", (5.0, 1.0))
    got = _replay(engine, stream)
    assert got["weight_dev_p50"] > reference.LIMITS["weight_dev_p50"]


def test_bf16_control_is_not_correct(short_pass):
    _, _, _, engine, stream = short_pass
    ctl = _replay(engine, stream, controls=("bf16",))["control_bf16"]
    assert (ctl["weight_dev_p50"] > reference.LIMITS["weight_dev_p50"]
            or ctl["served_gap"] > reference.LIMITS["served_gap"])


def test_reference_rejects_a_corrupted_served_class(short_pass):
    _, _, _, engine, stream = short_pass
    bad = list(stream)
    i = next(i for i, ev in enumerate(bad) if ev[0] == "predict" and ev[3])
    ev = bad[i]
    bad[i] = ev[:5] + ((ev[5] + 16) % 32,) + ev[6:]
    assert _replay(engine, bad)["served_gap"] > reference.LIMITS["served_gap"]


def test_reference_rejects_a_class_served_early(short_pass):
    _, _, _, engine, stream = short_pass
    bad = list(stream)
    i = next(i for i, ev in enumerate(bad) if ev[0] == "predict" and not ev[4])
    bad[i] = bad[i][:4] + (True,) + bad[i][5:6] + (0,)
    assert _replay(engine, bad)["breaches"] >= 1


def _observations(n, seed=0):
    rng = np.random.default_rng(seed)
    for _ in range(n):
        alloc_v = int(rng.integers(1, 40))
        yield Observation(
            exec_time_s=float(rng.uniform(0.0, 12.0)),
            slo_s=float(rng.uniform(0.1, 10.0)), alloc_vcpus=alloc_v,
            max_vcpus_used=float(rng.uniform(0.0, 1.0)) * alloc_v,
            alloc_mem_mb=int(rng.integers(1, 48)) * 128,
            max_mem_used_mb=float(rng.uniform(0.0, 6000.0)),
            oom_killed=bool(rng.random() < 0.2))


def test_reference_costs_equal_the_papers_cost_functions():
    for obs in _observations(1000):
        got = reference.reference_costs(obs)
        np.testing.assert_array_equal(got["vcpu"], CF.absolute_vcpu_costs(obs, 32))
        np.testing.assert_array_equal(got["mem"], CF.memory_costs(obs, 40, 128))


def test_invariants_copy(short_pass):
    cell, sim, results, _, _ = short_pass
    assert invariants.breaches(sim, cell.trace, results) == []
    assert invariants.breaches(sim, cell.trace, results[:-1])
    dup = results + [dataclasses.replace(results[0])]
    assert "an invocation terminated twice" in invariants.breaches(
        sim, cell.trace, dup)
    w = sim.clusters[0].workers[0]
    w.reserved_vcpus += 1
    try:
        assert any("reservation" in b for b in
                   invariants.breaches(sim, cell.trace, results))
    finally:
        w.reserved_vcpus -= 1
