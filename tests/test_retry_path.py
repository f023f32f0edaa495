"""Retry/timeout-path tests: the policy is consulted exactly once per
invocation regardless of retries, timed-out invocations never touch it,
and queue accounting is recorded."""

from repro.core.allocator import Allocation
from repro.serving import baselines as B
from repro.serving.profiles import build_input_pool, build_profiles
from repro.serving.simulator import Policy, SimConfig, Simulator
from repro.serving.workload import Arrival

FN = "lrtrain"  # ~2.5 s at 8 vCPUs on its smallest input


class CountingPolicy(Policy):
    """Static allocation + per-invocation allocate-call counter."""

    name = "counting"
    uses_shabari_scheduler = True
    placement = "hashing"

    def __init__(self, vcpus=8, mem_mb=1024):
        self.vcpus, self.mem_mb = vcpus, mem_mb
        self.calls = {}

    def allocate(self, arrival, meta, sim):
        self.calls[arrival.invocation_id] = (
            self.calls.get(arrival.invocation_id, 0) + 1
        )
        return Allocation(self.vcpus, self.mem_mb)


def _one_worker_cfg(**over):
    """One 8-vCPU worker; an 8-vCPU allocation serializes the cluster."""
    base = dict(
        n_workers=1, vcpus_per_worker=8, physical_cores=8,
        mem_mb_per_worker=4096, vcpu_limit=8,
        retry_interval_s=0.5, queue_timeout_s=300.0, seed=0,
    )
    base.update(over)
    return SimConfig(**base)


def _run(policy, arrivals, cfg):
    profiles = build_profiles()
    pool = build_input_pool(seed=0)
    slo = B.build_slo_table(profiles, pool)
    sim = Simulator(policy=policy, profiles=profiles, input_pool=pool,
                    slo_table=slo, cfg=cfg)
    return sim, sim.run(arrivals)


def test_exactly_one_allocate_per_invocation_despite_retries():
    pol = CountingPolicy()
    # one invocation takes the worker; five more arrive while it runs
    # and retry every 0.5 s until the worker frees up
    arrivals = [Arrival(0, 0.0, FN, 0)] + [
        Arrival(i, 1.5, FN, 0) for i in range(1, 6)
    ]
    sim, results = _run(pol, arrivals, _one_worker_cfg())
    assert len(results) == 6
    assert not any(r.timed_out for r in results)
    assert any(r.queued_s > 0 for r in results)  # retries really happened
    assert sim.events_processed > 2 * len(arrivals)  # incl. retry events
    assert pol.calls == {i: 1 for i in range(6)}


def test_timed_out_invocations_use_cached_alloc_and_skip_policy():
    pol = CountingPolicy()
    # queue_timeout shorter than the retry interval: every queued
    # invocation times out on its first retry
    cfg = _one_worker_cfg(queue_timeout_s=0.4)
    arrivals = [Arrival(0, 0.0, FN, 0)] + [
        Arrival(i, 1.5, FN, 0) for i in range(1, 8)
    ]
    sim, results = _run(pol, arrivals, cfg)
    timed = [r for r in results if r.timed_out]
    assert len(results) == 8 and len(timed) == 7
    for r in timed:
        # queue accounting: the full wait is recorded, past the timeout
        assert r.queued_s > cfg.queue_timeout_s
        assert r.queued_s == r.finish_t - r.arrival_t
        assert r.slo_violated
        # the cached first-attempt allocation is what gets reported
        assert (r.alloc_vcpus, r.alloc_mem_mb) == (8, 1024)
    # the policy was consulted exactly once per invocation — retries and
    # the timeout path never re-entered it
    assert pol.calls == {i: 1 for i in range(8)}


def test_timed_out_invocations_release_cached_features():
    """ShabariPolicy caches a feature vector per allocate; the timeout
    path must release it via Policy.forget (feedback never fires for a
    timed-out invocation, so without forget the entry leaks)."""
    pol = B.ShabariPolicy()
    # shabari's learning-phase default is 10 vCPUs; a 12-vCPU worker
    # fits exactly one such invocation at a time
    cfg = _one_worker_cfg(queue_timeout_s=0.4, vcpus_per_worker=12,
                          vcpu_limit=12, physical_cores=12)
    arrivals = [Arrival(0, 0.0, FN, 0)] + [
        Arrival(i, 1.5, FN, 0) for i in range(1, 8)
    ]
    _, results = _run(pol, arrivals, cfg)
    assert sum(r.timed_out for r in results) == 7
    assert not pol._features
