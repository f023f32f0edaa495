"""Benchmark harness: one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows. Set BENCH_QUICK=1 for the
abbreviated sweep (shorter traces, fewer grid points).

  PYTHONPATH=src python -m benchmarks.run [--only fig8,table3]

The CI bench-regression gate (see benchmarks/README.md):

  --json-out PATH       dump every emitted row as JSON (the workflow
                        artifact, so the BENCH_*.json trajectory
                        accumulates across runs); also writes a
                        deterministic BENCH_latest.json next to it
  --check-baseline      compare events/sec + SLO-violation rates against
                        benchmarks/baselines.json; exit non-zero on a
                        >25% events/sec regression or a missing row.
                        A failing row within 2x of its floor re-runs
                        its module (best-of-3, per-row max) before the
                        verdict — flake resistance for loaded runners
  --write-baseline      regenerate benchmarks/baselines.json from this
                        run (intentional re-baselining; commit the diff)
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback

from benchmarks import util
from repro.compile_cache import enable_compile_cache

MODULES = [
    ("measurement", "benchmarks.fig_measurement_study"),
    ("fig6", "benchmarks.fig6_formulations"),
    ("fig7", "benchmarks.fig7_ablations"),
    ("fig8", "benchmarks.fig8_e2e"),
    ("fig9", "benchmarks.fig9_timeline"),
    ("fig10", "benchmarks.fig10_cold_starts"),
    ("fig11_13", "benchmarks.fig11_13_sensitivity"),
    ("table3", "benchmarks.table3_container_sizes"),
    ("scenario_matrix", "benchmarks.scenario_matrix"),
    ("sim_bench", "benchmarks.sim_bench"),
    ("router_bench", "benchmarks.router_bench"),
    ("admission_bench", "benchmarks.admission_bench"),
    ("chain_bench", "benchmarks.chain_bench"),
    ("estimate_bench", "benchmarks.estimate_bench"),
    ("fleet_bench", "benchmarks.fleet_bench"),
    ("registry_bench", "benchmarks.registry_bench"),
    ("kernels", "benchmarks.kernels_bench"),
    ("roofline", "benchmarks.roofline_report"),
]

BASELINE_PATH = os.path.join(os.path.dirname(__file__), "baselines.json")
# >25% events/sec regression against the committed baseline fails CI
EVENTS_PER_SEC_TOLERANCE = 0.25
# SLO-violation drift is informational (warn only): rates move with
# intentional semantics changes, which the golden-drift job already
# forces to be refreshed explicitly
SLO_WARN_PTS = 2.0


def collect_baseline_metrics(rows):
    """Extract the gated metrics from emitted rows.

    events/sec is gated only for sim_bench rows — the designated
    throughput harness, whose multi-second cells are stable enough to
    compare across runs. The SLO/admission sweeps also print
    events_per_sec, but their sub-second cells swing with machine load,
    so they contribute only their (deterministic) SLO-violation rates.

    A best-of-3 re-measure appends duplicate-named rows, so events/sec
    takes the per-name MAX (the machine's least-loaded attempt); the
    deterministic SLO rates just take the latest.
    """
    events, slo = {}, {}
    for row in rows:
        derived = util.parse_derived(str(row["derived"]))
        name = str(row["name"])
        if "events_per_sec" in derived and name.startswith("sim_bench."):
            eps = derived["events_per_sec"]
            if name not in events or eps > events[name]:
                events[name] = eps
        if "slo_viol_pct" in derived:
            slo[name] = derived["slo_viol_pct"]
    return {"events_per_sec": events, "slo_violation_pct": slo}


def check_baseline(rows, attempts: int = 1):
    """Compare this run against benchmarks/baselines.json.

    Returns ``(failures, retry_modules)``: a list of failure strings
    (empty = gate passed) and the module keys whose failing rows came
    in WITHIN 2x of their floor — a plausible machine-load flake worth
    a best-of-3 re-measure rather than an immediate verdict. Rows more
    than 2x under their floor are treated as real regressions and are
    not retried."""
    if not os.path.exists(BASELINE_PATH):
        return ([f"missing {BASELINE_PATH}; run with --write-baseline first"],
                set())
    with open(BASELINE_PATH) as f:
        baseline = json.load(f)
    if baseline.get("bench_quick") != util.QUICK:
        return ([
            f"baseline was captured with bench_quick={baseline.get('bench_quick')}"
            f" but this run has bench_quick={util.QUICK}; quick and full "
            "sweeps use different traces/fleets and are not comparable"
        ], set())
    current = collect_baseline_metrics(rows)
    failures = []
    retry_modules = set()
    best_of = f"best of {attempts} runs" if attempts > 1 else "single run"
    for name, base_eps in sorted(baseline.get("events_per_sec", {}).items()):
        cur_eps = current["events_per_sec"].get(name)
        if cur_eps is None:
            failures.append(
                f"{name}: baselined events/sec row missing from this run")
            continue
        floor = base_eps * (1.0 - EVENTS_PER_SEC_TOLERANCE)
        status = "FAIL" if cur_eps < floor else "ok"
        print(f"# baseline {status}: {name} events/sec "
              f"{cur_eps:.0f} vs {base_eps:.0f} (floor {floor:.0f}, "
              f"{best_of})",
              file=sys.stderr)
        if cur_eps < floor:
            failures.append(
                f"{name}: events/sec regressed >25% "
                f"({cur_eps:.0f} < floor {floor:.0f}, baseline {base_eps:.0f}, "
                f"{best_of})")
            if cur_eps >= floor / 2.0:
                retry_modules.add(name.split(".", 1)[0])
    for name, base_slo in sorted(baseline.get("slo_violation_pct", {}).items()):
        cur_slo = current["slo_violation_pct"].get(name)
        if cur_slo is None:
            # SLO rows are informational; a subset run (--only) simply
            # doesn't produce them all
            continue
        if abs(cur_slo - base_slo) > SLO_WARN_PTS:
            print(f"# baseline WARN: {name} slo_viol_pct moved "
                  f"{base_slo:.2f} -> {cur_slo:.2f} "
                  "(informational; refresh with --write-baseline if intended)",
                  file=sys.stderr)
    return failures, retry_modules


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated subset of module keys")
    ap.add_argument("--json-out", default=None,
                    help="write every emitted row to this JSON file")
    ap.add_argument("--check-baseline", action="store_true",
                    help="fail on >25%% events/sec regression vs "
                         "benchmarks/baselines.json")
    ap.add_argument("--write-baseline", action="store_true",
                    help="regenerate benchmarks/baselines.json from this run")
    args = ap.parse_args()
    only = set(args.only.split(",")) if args.only else None
    enable_compile_cache()

    print("name,us_per_call,derived")
    failures = []
    modules = {}
    for key, modname in MODULES:
        if only and key not in only:
            continue
        t0 = time.time()
        try:
            mod = modules[key] = __import__(modname, fromlist=["run"])
            mod.run()
            print(f"# {key} done in {time.time()-t0:.1f}s", file=sys.stderr)
        except Exception as e:
            failures.append((key, repr(e)))
            traceback.print_exc()

    # flake resistance: a gated row that lands under its floor but
    # within 2x of it gets its whole module re-run (up to best-of-3,
    # per-row max) before the verdict — multi-second cells still swing
    # with machine load on shared CI runners
    gate = []
    if args.check_baseline:
        attempts = 1
        gate, retry = check_baseline(util.ROWS, attempts)
        while retry and attempts < 3:
            attempts += 1
            print(f"# re-measuring {sorted(retry)} (attempt {attempts}/3): "
                  "failing rows were within 2x of their floor",
                  file=sys.stderr)
            for key in sorted(retry):
                mod = modules.get(key)
                if mod is None:
                    break
                try:
                    mod.run()
                except Exception as e:
                    failures.append((key, repr(e)))
                    traceback.print_exc()
            gate, retry = check_baseline(util.ROWS, attempts)

    if args.json_out:
        payload = {"bench_quick": util.QUICK, "rows": util.ROWS}
        with open(args.json_out, "w") as f:
            json.dump(payload, f, indent=2, sort_keys=True)
            f.write("\n")
        print(f"# wrote {len(util.ROWS)} rows to {args.json_out}",
              file=sys.stderr)
        # the deterministic twin: a fixed name the workflow can upload
        # (and humans can diff) without knowing the run id baked into
        # --json-out
        latest = os.path.join(
            os.path.dirname(args.json_out) or ".", "BENCH_latest.json")
        with open(latest, "w") as f:
            json.dump(payload, f, indent=2, sort_keys=True)
            f.write("\n")
        print(f"# wrote {latest}", file=sys.stderr)
    if args.write_baseline:
        # merge into the existing baseline so a subset re-baseline
        # (--only sim_bench) can't silently delete every other gate;
        # a mode switch (quick vs full) starts fresh — the two sweeps
        # use different traces/fleets and must never mix
        doc = {"events_per_sec": {}, "slo_violation_pct": {}}
        if os.path.exists(BASELINE_PATH):
            with open(BASELINE_PATH) as f:
                prior = json.load(f)
            if prior.get("bench_quick") == util.QUICK:
                doc.update(prior)
            else:
                print("# baseline mode changed; starting fresh",
                      file=sys.stderr)
        current = collect_baseline_metrics(util.ROWS)
        doc["bench_quick"] = util.QUICK
        doc["events_per_sec"].update(current["events_per_sec"])
        doc["slo_violation_pct"].update(current["slo_violation_pct"])
        with open(BASELINE_PATH, "w") as f:
            json.dump(doc, f, indent=2, sort_keys=True)
            f.write("\n")
        print(f"# wrote baseline to {BASELINE_PATH}", file=sys.stderr)
    if failures:
        raise SystemExit(f"benchmark failures: {failures}")
    if gate:
        raise SystemExit(
            "bench-regression gate failed:\n  " + "\n  ".join(gate))


if __name__ == "__main__":
    main()
