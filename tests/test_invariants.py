"""Simulator-wide property tests: invariants that must hold for EVERY
(scenario, routing, admission, fleet) cell, not just the golden-pinned
ones, plus the full-registry determinism sweep.

The invariants themselves (accounting, capacity, reservations,
image-cache refs) live in repro.serving.invariants and are checked
after a full run, with the release/unreserve asserts inside
repro.core.cluster guarding the during-run half.

The determinism sweep runs every registered scenario twice per
routing x admission cell assignment and requires byte-identical
summaries — the nondeterminism class of bug goldens only catch on the
cells they pin.

Property tests use hypothesis when available and a seeded parametrize
sweep when not (same pattern as test_agent_arena)."""

import dataclasses
import json

import pytest

try:  # property tests use hypothesis when present, seeded sweeps if not
    import hypothesis
    from hypothesis import strategies as st
    given, settings = hypothesis.given, hypothesis.settings
except ModuleNotFoundError:  # pragma: no cover
    hypothesis = None


def _prop(argnames, hyp_strategies, fallback_cases, max_examples=30):
    """@given(**hyp_strategies) under hypothesis; otherwise a seeded
    pytest.mark.parametrize over ``fallback_cases``."""
    def deco(fn):
        if hypothesis is not None:
            return given(**hyp_strategies)(
                settings(max_examples=max_examples, deadline=None)(fn))
        return pytest.mark.parametrize(argnames, fallback_cases)(fn)
    return deco


from repro.core.router import ADMISSION_POLICIES, ROUTING_POLICIES
from repro.serving import baselines as B
from repro.serving.experiment import make_policy, run_scenario
from repro.serving.golden import GOLDEN_POLICY, golden_sim_config
from repro.serving.invariants import check_invariants
from repro.serving.profiles import build_input_pool, build_profiles
from repro.serving.simulator import Simulator
from repro.serving.workload import (
    ScenarioSpec,
    generate_scenario,
    list_scenarios,
)


@pytest.fixture(scope="module")
def stack():
    profiles = build_profiles()
    pool = build_input_pool(seed=0)
    slo_table = B.build_slo_table(profiles, pool)
    return profiles, pool, slo_table


def _cell(seed):
    """Deterministic (scenario, routing, admission, n_workers) draw —
    the seed is the only free variable so hypothesis shrinking and the
    seeded fallback explore one shared space."""
    names = sorted(list_scenarios())
    return (names[seed % len(names)],
            ROUTING_POLICIES[(seed // 3) % len(ROUTING_POLICIES)],
            ADMISSION_POLICIES[(seed // 7) % len(ADMISSION_POLICIES)],
            2 + 2 * (seed % 2))


def _run_cell(stack, seed, duration_s=40.0):
    profiles, pool, slo_table = stack
    scenario, routing, admission, n_workers = _cell(seed)
    cfg = dataclasses.replace(
        golden_sim_config(scenario), routing=routing, admission=admission)
    if cfg.fleet is None:
        # fleet dimension: shrink the uniform fleet on odd seeds
        # (explicit FleetSpec scenarios keep their pinned hardware)
        cfg = dataclasses.replace(cfg, n_workers=n_workers)
    spec = ScenarioSpec(scenario=scenario, rps=2.0, duration_s=duration_s,
                        seed=seed)
    trace = generate_scenario(
        spec, functions=sorted(profiles),
        inputs_per_function={f: len(pool[f]) for f in profiles})
    pol = make_policy(GOLDEN_POLICY, profiles, pool, slo_table, seed=0)
    sim = Simulator(policy=pol, profiles=profiles, input_pool=pool,
                    slo_table=slo_table, cfg=cfg)
    return sim, trace, sim.run(trace)


@_prop("seed",
       dict(seed=st.integers(0, 10_000)) if hypothesis else None,
       [0, 1, 2, 3, 4, 5, 8, 12],
       max_examples=12)
def test_invariants_hold_across_random_cells(stack, seed):
    sim, trace, results = _run_cell(stack, seed)
    check_invariants(sim, trace, results)


def test_invariants_hold_on_chain_scenarios_explicitly(stack):
    """The randomized draw may or may not land on the chain scenarios;
    pin them (both slack modes) so the accounting invariant always
    covers simulator-spawned invocations."""
    names = sorted(list_scenarios())
    for scenario in ("chain-pipeline", "fan-out-join"):
        seed = names.index(scenario)  # lands _cell on this scenario
        sim, trace, results = _run_cell(stack, seed)
        assert sim._chains is not None and sim._chains.stage_spawned > 0
        check_invariants(sim, trace, results)


# -------------------------------------------------- determinism sweep
def test_determinism_sweep_full_registry_and_matrix():
    """Every registered scenario runs twice under the same seed on its
    assigned routing x admission cells; both passes must serialize to
    byte-identical summaries (including the chain block). Cells are
    dealt round-robin so all 16 combinations and all scenarios are
    exercised without running the full cross product."""
    cells = [(ro, ad) for ro in ROUTING_POLICIES for ad in ADMISSION_POLICIES]
    names = sorted(list_scenarios())
    n = max(len(cells), len(names))
    for i in range(n):
        scenario = names[i % len(names)]
        routing, admission = cells[i % len(cells)]
        cfg = dataclasses.replace(
            golden_sim_config(scenario), routing=routing,
            admission=admission)
        spec = ScenarioSpec(scenario=scenario, rps=1.5, duration_s=60.0,
                            seed=3)
        docs = []
        for _ in range(2):
            res = run_scenario(GOLDEN_POLICY, spec, sim_cfg=cfg)
            docs.append(json.dumps(
                {"summary": res.summary, "chain": res.chain_summary},
                sort_keys=True))
        assert docs[0] == docs[1], (
            f"nondeterminism: {scenario} routing={routing} "
            f"admission={admission}")
