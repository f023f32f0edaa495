"""Decision quality of one completed pass, from ``InvocationResult``
fields: the arithmetic of the program's ``serving.simulator.summarize``
(paper §7.1), copied so that the yardstick stays put."""

from __future__ import annotations

from typing import Dict, Sequence

import numpy as np


def violated(r) -> bool:
    if r.timed_out or r.oom_killed or r.shed:
        return True
    return (r.finish_t - r.arrival_t) > r.slo_s + 1e-9


def failed(r) -> bool:
    """Shed, timed out or OOM-killed: the invocation did not complete."""
    return bool(r.shed or r.timed_out or r.oom_killed)


def pass_quality(results: Sequence) -> Dict[str, float]:
    """SLO violations (shed, timed-out and OOM-killed invocations count)
    over every invocation; wasted memory over those that ran."""
    ran = [r for r in results if not (r.shed or r.timed_out)]
    wasted_mem = np.array([max(r.alloc_mem_mb - r.used_mem_mb, 0.0)
                           for r in ran])
    wasted_vcpu = np.array([max(r.alloc_vcpus - r.used_vcpus, 0.0)
                            for r in ran])
    n = len(results)
    return {
        "n": n,
        "failed": sum(failed(r) for r in results),
        "slo_violation_pct": 100.0 * sum(violated(r) for r in results) / n,
        "wasted_mem_mb_p50": (float(np.percentile(wasted_mem, 50))
                              if wasted_mem.size else 0.0),
        "wasted_vcpus_p50": (float(np.percentile(wasted_vcpu, 50))
                             if wasted_vcpu.size else 0.0),
        "cold_start_pct": 100.0 * sum(bool(r.cold_start) for r in results) / n,
    }
