"""End-of-pass guarantees, copied from the program's
``serving.invariants.check_invariants`` so that the yardstick stays put.

* every trace invocation reaches a terminal state exactly once;
* no worker ends over its limits or below zero, and cluster aggregates
  equal the sum over their workers;
* reservations and the active-demand aggregates drain to zero.

The program's image-cache reap is left out: no configuration here
attaches an image cache. Returns a list of breaches, empty when sound.
"""

from __future__ import annotations

from typing import List


def breaches(sim, trace, results) -> List[str]:
    out: List[str] = []
    ids = [r.invocation_id for r in results]
    if len(ids) != len(set(ids)):
        out.append("an invocation terminated twice")
    got, want = set(ids), {a.invocation_id for a in trace}
    if want - got:
        out.append(f"invocations never terminated: {sorted(want - got)[:5]}")
    if got - want:
        out.append(f"phantom invocations: {sorted(got - want)[:5]}")
    for r in results:
        if r.shed and r.timed_out:
            out.append(f"shed and timed out: {r.invocation_id}")
        if (r.shed or r.timed_out) and (r.oom_killed or r.exec_s != 0.0):
            out.append(f"ran though never placed: {r.invocation_id}")
    for cl in sim.clusters:
        for w in cl.workers:
            if not (0 <= w.used_vcpus <= w.vcpu_limit
                    and 0 <= w.used_mem_mb <= w.total_mem_mb):
                out.append(f"worker {w.wid} out of bounds")
            if w.reserved_vcpus or w.reserved_mem_mb:
                out.append(f"reservation left on worker {w.wid}")
            if abs(w.active_demand_vcpus) > 1e-6 or abs(w.active_net_gbps) > 1e-9:
                out.append(f"active demand left on worker {w.wid}")
            if any(c.busy for c in w.containers.values()):
                out.append(f"busy container left on worker {w.wid}")
        if cl.reserved_vcpus or cl.reserved_mem_mb:
            out.append("reservation left on a cluster")
        if (cl.used_vcpus != sum(w.used_vcpus for w in cl.workers)
                or cl.used_mem_mb != sum(w.used_mem_mb for w in cl.workers)):
            out.append("cluster aggregate differs from its workers")
    return out
