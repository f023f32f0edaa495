"""Admission-control sweep.

Five admission modes on the saturating scenarios (oversubscribe,
flash-crowd, multi-cluster) plus the well-provisioned poisson-steady
control, all behind a 2-cluster spill-over front door on the same total
worker footprint. Every mode reserves capacity at placement:

* ``reserve``       — no admission control: placed cold starts reserve
  capacity immediately, so ``Worker.fits`` and ``Router._load`` are
  truthful about committed-but-warming load;
* ``reserve+shed``  — reservation plus front-door shedding when every
  cluster's committed load exceeds the admission headroom;
* ``reserve+queue`` — reservation plus front-door queueing under the
  same condition (arrivals retry without probing any scheduler);
* ``reserve+slo``   — reservation plus SLO-native admission: shed
  exactly the invocations whose best fleet-wide completion-time
  estimate (per-input when calibrated) already exceeds their remaining
  SLO budget, instead of shedding on load alone.

The headline A/B (also a CI gate):

* SLO-native admission must DOMINATE load-headroom shedding on at
  least one saturating cell — no more violations from no more sheds
  (it drops only work that was doomed anyway) — and must stay neutral
  on the half-load control (shed nothing, change nothing).

  PYTHONPATH=src python -m benchmarks.admission_bench
"""

from __future__ import annotations

import time

import numpy as np

from benchmarks.util import QUICK, emit
from repro.serving import baselines as B
from repro.serving.experiment import make_policy
from repro.serving.profiles import build_input_pool, build_profiles
from repro.serving.simulator import SimConfig, Simulator, summarize
from repro.serving.workload import ScenarioSpec, generate_scenario

TOTAL_WORKERS = 8 if QUICK else 16
N_CLUSTERS = 2
DURATION_S = 240.0 if QUICK else 360.0
RPS = 2.0 if QUICK else 4.0  # offered load scales with the fleet
POLICY = "shabari"
HEADROOM = 0.95

# deeply saturating shapes (the admission regime — fleet-wide overload,
# unlike router_bench's hot-cluster-only loads) + a well-provisioned
# poisson-steady control where admission must be neutral.
# Each entry: (scenario params, rps scale) — the control runs at half
# the offered load so it genuinely has headroom.
SCENARIOS = {
    "oversubscribe": ({"load_mult": 4.0}, 1.0),
    "flash-crowd": ({"spike_mult": 8.0}, 1.0),
    "multi-cluster": ({}, 1.0),
    "poisson-steady": ({}, 0.5),
}

# the load-shedding arm the slo-dominance gate compares against: a
# tighter headroom than the default arm so its shed rate brackets
# reserve+slo's from above — the gate then reads "fewer violations
# from no more sheds" at a MATCHED (or conceded) shed rate, not a win
# bought by simply serving more traffic
MATCH_HEADROOM = 0.90

MODES = (
    ("reserve", dict()),
    ("reserve+shed", dict(admission="shed", admission_headroom=HEADROOM)),
    ("reserve+shed@match", dict(admission="shed",
                                admission_headroom=MATCH_HEADROOM)),
    ("reserve+queue", dict(admission="queue", admission_headroom=HEADROOM)),
    ("reserve+slo", dict(admission="slo")),
)
# the cells the slo-dominates-shed gate quantifies over (the control is
# gated separately, for neutrality)
SATURATING = ("oversubscribe", "flash-crowd", "multi-cluster")


def _cfg(**overrides) -> SimConfig:
    # vcpu_limit > physical_cores (the §6 userCPU knob): stacked
    # placements translate into co-runner contention, the failure mode
    # reservation accounting is meant to prevent
    return SimConfig(
        n_workers=TOTAL_WORKERS // N_CLUSTERS,
        n_clusters=N_CLUSTERS,
        routing="spill-over",
        vcpus_per_worker=44,
        physical_cores=32,
        mem_mb_per_worker=16 * 1024,
        vcpu_limit=44,
        retry_interval_s=1.0,
        queue_timeout_s=60.0,
        seed=0,
        **overrides,
    )


def _cold_queue_p99(results) -> float:
    q = [r.queued_s for r in results if r.cold_start]
    return float(np.percentile(q, 99)) if q else 0.0


def _run_cell(trace, profiles, pool, slo_table, overrides):
    policy = make_policy(POLICY, profiles, pool, slo_table, seed=0)
    sim = Simulator(policy=policy, profiles=profiles, input_pool=pool,
                    slo_table=slo_table, cfg=_cfg(**overrides))
    t0 = time.perf_counter()
    results = sim.run(trace)
    wall = time.perf_counter() - t0
    summary = summarize(results)
    summary["cold_queue_p99_s"] = _cold_queue_p99(results)
    eps = sim.events_processed / wall
    return summary, sim.router, eps


def run() -> None:
    profiles = build_profiles()
    pool = build_input_pool(seed=0)
    slo_table = B.build_slo_table(profiles, pool)

    cells = {}
    warmed = False
    for scenario, (params, rps_scale) in SCENARIOS.items():
        spec = ScenarioSpec(scenario=scenario, rps=RPS * rps_scale,
                            duration_s=DURATION_S, seed=0,
                            params=dict(params))
        trace = generate_scenario(
            spec, functions=sorted(profiles),
            inputs_per_function={f: len(pool[f]) for f in profiles},
        )
        if not warmed:
            # throwaway run: trace shabari's jit kernels so the one-time
            # compiles aren't charged to the first timed cell
            _run_cell(trace[: max(len(trace) // 4, 1)],
                      profiles, pool, slo_table, {})
            warmed = True
        for mode, overrides in MODES:
            summary, router, eps = _run_cell(
                trace, profiles, pool, slo_table, overrides)
            cells[(scenario, mode)] = summary
            emit(
                f"admission_bench.{scenario}.{mode}",
                1e6 / max(eps, 1e-9),
                f"n={len(trace)}"
                f"|events_per_sec={eps:.0f}"
                f"|slo_viol_pct={summary['slo_violation_pct']:.2f}"
                f"|cold_start_pct={summary['cold_start_pct']:.2f}"
                f"|cold_queue_p99_s={summary['cold_queue_p99_s']:.3f}"
                f"|wasted_vcpus_p95={summary['wasted_vcpus_p95']:.2f}"
                f"|timeout_pct={summary['timeout_pct']:.2f}"
                f"|shed_pct={summary['shed_pct']:.2f}"
                f"|admission_shed={router.admission_shed}"
                f"|admission_slo_shed={router.admission_slo_shed}"
                f"|admission_queue_events={router.admission_queue_events}",
            )

    steady_reserve = cells[("poisson-steady", "reserve")]

    # CI gates for SLO-native admission. Dominance: on at least one
    # saturating cell, reserve+slo must beat the matched-shed-rate
    # load-headroom arm on SLO violations WITHOUT shedding more —
    # load-headroom shedding drops arrivals blindly when the fleet
    # looks full, so an estimate that sheds only doomed work should
    # serve more and violate less
    dominated = [
        s for s in SATURATING
        if (cells[(s, "reserve+slo")]["slo_violation_pct"]
            < cells[(s, "reserve+shed@match")]["slo_violation_pct"] - 1e-9
            and cells[(s, "reserve+slo")]["shed_pct"]
            <= cells[(s, "reserve+shed@match")]["shed_pct"] + 1e-9)
    ]
    if not dominated:
        raise RuntimeError(
            "slo admission failed to dominate load-headroom shedding "
            "(fewer violations from no more sheds) on any saturating "
            "cell: " + ", ".join(
                f"{s}: slo {cells[(s, 'reserve+slo')]['slo_violation_pct']:.2f}%"
                f"/{cells[(s, 'reserve+slo')]['shed_pct']:.2f}% shed vs "
                f"shed@match "
                f"{cells[(s, 'reserve+shed@match')]['slo_violation_pct']:.2f}%"
                f"/{cells[(s, 'reserve+shed@match')]['shed_pct']:.2f}% shed"
                for s in SATURATING))
    # Neutrality: on the half-load control the estimate clears every
    # SLO, so slo admission must shed nothing and change nothing
    steady_slo = cells[("poisson-steady", "reserve+slo")]
    if steady_slo["shed_pct"] > 0.0:
        raise RuntimeError(
            "slo admission shed servable work on the half-load "
            f"poisson-steady control: shed_pct={steady_slo['shed_pct']:.2f}%")
    if (steady_slo["slo_violation_pct"]
            > steady_reserve["slo_violation_pct"] + 0.5):
        raise RuntimeError(
            "slo admission raised SLO violations on the poisson-steady "
            f"control: {steady_slo['slo_violation_pct']:.2f}% > "
            f"{steady_reserve['slo_violation_pct']:.2f}%")


if __name__ == "__main__":
    print("name,us_per_call,derived")
    run()
