"""Simulator-core throughput: events/sec on a 10k-invocation trace.

* ``incremental`` — the simulator core on heavy-tail-inputs under
  memory-centric scheduling (vCPU oversubscription), which holds
  hundreds of invocations running concurrently; its events/sec floor
  rides benchmarks/baselines.json.

* ``image_cache_on`` — the same trace with
  ``SimConfig(image_cache=ImageCacheSpec())``, so the per-node layer
  cache's per-cold-start overhead has its own floor next to
  ``incremental`` (the cache-off default path).

* allocator engine — the batched agent arena
  (``ResourceAllocator(engine="arena")``, see repro.core.agent_arena)
  vs the per-function-object path (``engine="legacy"``: two jit'd JAX
  dispatches per allocate and two per feedback) with the SHABARI
  policy on the same trace. This is the learning-path throughput
  gate: the arena must be ≥3x events/sec AND bit-identical in summary
  metrics (enforced here, not just printed).

* ``scale`` — the azure-24h cell, one production day at Azure-trace
  scale (~100k invocations under BENCH_QUICK=1, 1M otherwise) whose
  events/sec floor rides benchmarks/baselines.json.

  PYTHONPATH=src python -m benchmarks.sim_bench
"""

from __future__ import annotations

import time

from benchmarks.util import QUICK, emit
from repro.core.image_cache import ImageCacheSpec
from repro.serving import baselines as B
from repro.serving.experiment import make_policy
from repro.serving.profiles import build_input_pool, build_profiles
from repro.serving.simulator import SimConfig, Simulator, summarize
from repro.serving.workload import ScenarioSpec, generate_scenario

N_INVOCATIONS = 2_000 if QUICK else 10_000
DURATION_S = 400.0
SCENARIO = "heavy-tail-inputs"
POLICY = "static-large"


def _run_once(trace, profiles, pool, slo_table, *, policy: str = POLICY):
    # uncapped worker resources: every invocation is admitted, so the
    # event count is pure start/finish work and the running set grows to
    # the hundreds (retry storms would otherwise dominate)
    cfg = SimConfig(seed=0, vcpu_limit=100_000,
                    mem_mb_per_worker=4_000_000)
    pol = make_policy(policy, profiles, pool, slo_table, seed=0)
    sim = Simulator(policy=pol, profiles=profiles, input_pool=pool,
                    slo_table=slo_table, cfg=cfg)
    t0 = time.perf_counter()
    results = sim.run(trace)
    wall = time.perf_counter() - t0
    return sim.events_processed, wall, summarize(results)


# ------------------------------------------------------- image-cache cell
def run_cache_cell(trace, profiles, pool, slo_table) -> None:
    """events/sec with the per-node image/layer cache ENABLED on the
    same uncapped heavy-tail cell as ``incremental`` (floor rides
    benchmarks/baselines.json). The cache adds per-cold-start work —
    a residual-pull rank across the walk plus the pull bookkeeping —
    so this cell prices that overhead next to ``sim_bench.incremental``
    (the identical run with ``image_cache=None``, the zero-overhead
    default)."""
    cfg = SimConfig(seed=0, vcpu_limit=100_000,
                    mem_mb_per_worker=4_000_000,
                    image_cache=ImageCacheSpec())
    pol = make_policy(POLICY, profiles, pool, slo_table, seed=0)
    sim = Simulator(policy=pol, profiles=profiles, input_pool=pool,
                    slo_table=slo_table, cfg=cfg)
    t0 = time.perf_counter()
    results = sim.run(trace)
    wall = time.perf_counter() - t0
    ev = sim.events_processed
    s = summarize(results)
    emit("sim_bench.image_cache_on", wall / ev * 1e6,
         f"n={len(trace)}|events={ev}|events_per_sec={ev / wall:.0f}"
         f"|cold_start_pct={s['cold_start_pct']:.2f}")


# --------------------------------------------------- allocator-engine A/B
def run_engine_ab(trace, profiles, pool, slo_table) -> None:
    """Shabari (learning) policy: agent arena vs per-object agents.

    Hard gates: summary metrics must be BIT-identical (the arena is a
    pure fast path — its NumPy backend is calibrated against the jit
    kernels and its flush ordering reproduces the sequential
    update/predict interleaving), and the arena must clear 3x
    events/sec."""
    # throwaway warm-up: run the arena's one-time backend calibration
    # (NumPy-vs-JAX bit-identity proofs + crossover benchmark, which
    # trace XLA programs) and the legacy jit kernels outside both timed
    # legs — every feature schema is dim 1-6
    from repro.core import agent_arena

    agent_arena.calibrate(range(1, 7))
    warm = trace[: max(len(trace) // 10, 1)]
    _run_once(warm, profiles, pool, slo_table, policy="shabari")
    _run_once(warm, profiles, pool, slo_table,
              policy="shabari-legacy-engine")

    ev_l, wall_l, sum_l = _run_once(
        trace, profiles, pool, slo_table, policy="shabari-legacy-engine")
    ev_a, wall_a, sum_a = _run_once(
        trace, profiles, pool, slo_table, policy="shabari")
    eps_l = ev_l / wall_l
    eps_a = ev_a / wall_a
    emit("sim_bench.shabari_legacy_engine", wall_l / ev_l * 1e6,
         f"n={len(trace)}|events={ev_l}|events_per_sec={eps_l:.0f}")
    emit("sim_bench.shabari_arena", wall_a / ev_a * 1e6,
         f"n={len(trace)}|events={ev_a}|events_per_sec={eps_a:.0f}")
    emit("sim_bench.engine_speedup", 0.0,
         f"x{eps_a / eps_l:.2f}|metrics_identical={sum_a == sum_l}")
    if sum_a != sum_l:
        raise RuntimeError(
            "agent arena changed shabari summary metrics vs the legacy "
            f"engine: {sum_a} != {sum_l}")
    if eps_a < 3.0 * eps_l:
        raise RuntimeError(
            "agent arena below the 3x events/sec target: "
            f"{eps_a:.0f} vs legacy {eps_l:.0f}")


# ------------------------------------------------------------- scale tier
# The azure-24h tier: one production day at Azure-trace scale. Quick mode
# compresses the diurnal cycle into a tenth of a day at the same rate
# (~100k invocations); the full sweep runs the whole 24 h (1M). The
# fleet is deliberately saturated at its peak with queue-mode admission
# holding the backlog at the front door, so the event mix matches what a
# production-scale replay looks like: a long retry tail around the
# diurnal crest plus warm/cold starts everywhere else.
SCALE_N = 100_000 if QUICK else 1_000_000
SCALE_DURATION_S = 8_640.0 if QUICK else 86_400.0


def _scale_config() -> SimConfig:
    return SimConfig(seed=0, n_clusters=10, n_workers=16,
                     admission="queue", admission_headroom=0.85,
                     queue_timeout_s=90.0, retry_interval_s=0.5)


def run_scale(profiles, pool, slo_table) -> None:
    """events/sec on the azure-24h trace (floor in baselines.json)."""
    spec = ScenarioSpec(scenario="azure-24h", rps=SCALE_N / SCALE_DURATION_S,
                        duration_s=SCALE_DURATION_S, seed=11)
    t0 = time.perf_counter()
    trace = generate_scenario(
        spec, functions=sorted(profiles),
        inputs_per_function={f: len(pool[f]) for f in profiles},
    )
    build_wall = time.perf_counter() - t0
    pol = make_policy(POLICY, profiles, pool, slo_table, seed=0)
    sim = Simulator(policy=pol, profiles=profiles, input_pool=pool,
                    slo_table=slo_table, cfg=_scale_config())
    t0 = time.perf_counter()
    results = sim.run(trace)
    wall = time.perf_counter() - t0
    ev = sim.events_processed
    timeouts = sum(r.timed_out for r in results)
    emit("sim_bench.scale_azure24h", wall / ev * 1e6,
         f"n={len(trace)}|events={ev}|events_per_sec={ev / wall:.0f}"
         f"|trace_build_s={build_wall:.2f}|timeouts={timeouts}")


def run() -> None:
    profiles = build_profiles()
    pool = build_input_pool(seed=0)
    slo_table = B.build_slo_table(profiles, pool)
    spec = ScenarioSpec(
        scenario=SCENARIO, rps=N_INVOCATIONS / DURATION_S,
        duration_s=DURATION_S, seed=0,
    )
    trace = generate_scenario(
        spec, functions=sorted(profiles),
        inputs_per_function={f: len(pool[f]) for f in profiles},
    )

    ev, wall, _ = _run_once(trace, profiles, pool, slo_table)
    emit("sim_bench.incremental", wall / ev * 1e6,
         f"n={len(trace)}|events={ev}|events_per_sec={ev / wall:.0f}")

    run_cache_cell(trace, profiles, pool, slo_table)
    run_engine_ab(trace, profiles, pool, slo_table)
    run_scale(profiles, pool, slo_table)


if __name__ == "__main__":
    print("name,us_per_call,derived")
    run()
