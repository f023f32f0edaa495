"""Regenerate the golden-metrics snapshots in tests/goldens/.

Run this ONLY when a PR intentionally changes simulated behavior
(allocator, scheduler, workload, simulator); commit the diff so the
review shows exactly which metrics moved and by how much. The CI
golden-drift job reruns this script and fails on any uncommitted diff,
so a semantics change can't sail through on stale snapshots.

    PYTHONPATH=src python scripts/refresh_goldens.py [--only a,b]
                                                     [--out-dir DIR]

Besides the per-scenario snapshots, the A/B scenarios of
``repro.serving.golden`` (``*_SCENARIOS``) are snapshotted a second
time under ``<out-dir>/<subdir>/`` with their one switch flipped:
``legacy-engine/``, ``estimate-routing/``, ``cache-disabled/`` and
``chain-uniform/``.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
from typing import Dict, Optional

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.serving.golden import (  # noqa: E402
    CACHE_DISABLED_SCENARIOS,
    CHAIN_UNIFORM_SCENARIOS,
    ESTIMATE_ROUTING_SCENARIOS,
    GOLDEN_POLICY,
    LEGACY_ENGINE_SCENARIOS,
    golden_specs,
    run_golden,
)

GOLDEN_DIR = os.path.join(os.path.dirname(__file__), "..", "tests", "goldens")
LEGACY_ENGINE_SUBDIR = "legacy-engine"
ESTIMATE_SUBDIR = "estimate-routing"
CACHE_DISABLED_SUBDIR = "cache-disabled"
CHAIN_UNIFORM_SUBDIR = "chain-uniform"


def write_snapshot(scenario: str, out_dir: str, *,
                   legacy_engine: bool = False,
                   estimate_routing: bool = False,
                   cache_disabled: bool = False,
                   chain_uniform: bool = False) -> Dict:
    """Run one golden scenario and write its snapshot JSON; returns the
    written document (the schema tests/test_refresh_goldens.py pins)."""
    os.makedirs(out_dir, exist_ok=True)
    doc = {
        "policy": ("shabari-legacy-engine" if legacy_engine
                   else GOLDEN_POLICY),
        "spec": dataclasses.asdict(golden_specs()[scenario]),
        "summary": run_golden(scenario, legacy_engine=legacy_engine,
                              estimate_routing=estimate_routing,
                              cache_disabled=cache_disabled,
                              chain_uniform=chain_uniform),
    }
    path = os.path.join(out_dir, f"{scenario}.json")
    with open(path, "w") as f:
        json.dump(doc, f, indent=2, sort_keys=True)
        f.write("\n")
    tag = (" (legacy-engine)" if legacy_engine
           else " (estimate-routing)" if estimate_routing
           else " (cache-disabled)" if cache_disabled
           else " (chain-uniform)" if chain_uniform else "")
    print(f"{scenario:>20}{tag}: n={doc['summary']['n']:.0f} "
          f"slo_viol={doc['summary']['slo_violation_pct']:.2f}% -> {path}")
    return doc


def refresh(out_dir: str = GOLDEN_DIR, only: Optional[set] = None) -> None:
    for scenario in sorted(golden_specs()):
        if only and scenario not in only:
            continue
        write_snapshot(scenario, out_dir)
        if scenario in LEGACY_ENGINE_SCENARIOS:
            write_snapshot(
                scenario, os.path.join(out_dir, LEGACY_ENGINE_SUBDIR),
                legacy_engine=True)
        if scenario in ESTIMATE_ROUTING_SCENARIOS:
            write_snapshot(
                scenario, os.path.join(out_dir, ESTIMATE_SUBDIR),
                estimate_routing=True)
        if scenario in CACHE_DISABLED_SCENARIOS:
            write_snapshot(
                scenario, os.path.join(out_dir, CACHE_DISABLED_SUBDIR),
                cache_disabled=True)
        if scenario in CHAIN_UNIFORM_SCENARIOS:
            write_snapshot(
                scenario, os.path.join(out_dir, CHAIN_UNIFORM_SUBDIR),
                chain_uniform=True)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated subset of scenarios")
    ap.add_argument("--out-dir", default=GOLDEN_DIR,
                    help="write snapshots here instead of tests/goldens/")
    args = ap.parse_args(argv)
    only = set(args.only.split(",")) if args.only else None
    if only:
        unknown = only - set(golden_specs())
        if unknown:
            raise SystemExit(f"unknown scenarios: {sorted(unknown)}")
    refresh(args.out_dir, only)


if __name__ == "__main__":
    main()
