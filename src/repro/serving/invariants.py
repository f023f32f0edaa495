"""End-of-run invariants every simulation must satisfy.

:func:`check_invariants` raises ``AssertionError`` on the first breach.
The property tests (``tests/test_invariants.py``) run it on random
cells, and ``chip_smoke.py`` runs it on the chip's main-path run.

* accounting — every trace invocation terminates exactly once
  (completed / shed / timed-out / OOM); chain runs additionally
  account every SPAWNED stage invocation, with ids disjoint from the
  trace block;
* capacity — no worker ends over its vcpu/memory limits or below
  zero, cluster aggregates equal the sum over their workers, and the
  §5 active-demand aggregates drain back to zero;
* reservations — every acquire-on-placement reservation is released
  by completion, cancellation, or timeout: reserved vcpus/memory are
  zero fleet-wide at the end;
* image-cache refs — reaping every surviving container leaves no
  in-use image and no layer with a nonzero refcount.

The reap in the last check removes every container, so the simulator
is spent afterwards.
"""

from __future__ import annotations


def check_invariants(sim, trace, results) -> None:
    # ---- accounting: every invocation terminates exactly once
    ids = [r.invocation_id for r in results]
    assert len(ids) == len(set(ids)), "an invocation terminated twice"
    got = set(ids)
    trace_ids = {a.invocation_id for a in trace}
    assert trace_ids <= got, (
        f"trace invocations unaccounted: {sorted(trace_ids - got)[:5]}")
    extra = got - trace_ids
    if sim._chains is None:
        assert not extra, f"phantom invocations: {sorted(extra)[:5]}"
    else:
        # chain stage spawns mint ids above the trace's 0..n-1 block,
        # and every spawned stage must itself terminate exactly once
        assert all(i >= len(trace) for i in extra)
        assert len(extra) == sim._chains.stage_spawned
    for r in results:
        assert not (r.shed and r.timed_out), r
        if r.shed or r.timed_out:
            assert not r.oom_killed and r.exec_s == 0.0, r

    # ---- capacity + reservations + §5 aggregates drain
    for cl in sim.clusters:
        for w in cl.workers:
            assert 0 <= w.used_vcpus <= w.vcpu_limit, (w.wid, w.used_vcpus)
            assert 0 <= w.used_mem_mb <= w.total_mem_mb
            assert w.reserved_vcpus == 0 and w.reserved_mem_mb == 0, (
                "reservation leaked on worker", w.wid)
            assert abs(w.active_demand_vcpus) <= 1e-6, (
                "active demand left on worker", w.wid, w.active_demand_vcpus)
            assert abs(w.active_net_gbps) <= 1e-9, (
                "network demand left on worker", w.wid, w.active_net_gbps)
            for c in w.containers.values():
                assert not c.busy, ("busy container at sim end", c.cid)
        assert cl.reserved_vcpus == 0 and cl.reserved_mem_mb == 0
        assert cl.used_vcpus == sum(w.used_vcpus for w in cl.workers)
        assert cl.used_mem_mb == sum(w.used_mem_mb for w in cl.workers)

    # ---- image-cache refs: reap everything -> no refs survive
    for cl in sim.clusters:
        for w in cl.workers:
            for c in list(w.containers.values()):
                cl.remove_container(c)
            ic = w.image_cache
            if ic is not None:
                assert not ic._inuse_images, (
                    "image refs leaked", dict(ic._inuse_images))
                assert all(rec[2] == 0 for rec in ic._layers.values()), (
                    "layer refcount leaked")
