"""The simulator's event loop against frozen references.

The loop keeps the trace's arrivals as a sorted array, scheduled events
in a :class:`CalendarQueue` and retries in a FIFO lane, and must pop
them all in one global ``(t, seq)`` order. Each cell below pins, for
one configuration, the number of results, ``events_processed`` and a
sha256 over every ``InvocationResult`` field in result order (plus the
chain metrics where chains run). Every reference was recorded from two
independent implementations that agreed on all three: this loop and a
single global ``heapq`` over every event, each run both with the
per-worker aggregates and warm-container index and with O(containers)
scans for contention and warm lookups.

The cells cover retries (capacity-queued and front-door-held), queue
timeouts, the three admission modes, warming-soon binds, image-layer
pulls, spawned chain arrivals, dynamic-contention finish re-queues and
remote ``xfer_start`` placements. The cohort test pins the same-
timestamp partition the loop feeds the policy's batch hook, and the
:class:`CalendarQueue` units pin its boundary cases (including pushing
into the bucket currently being drained, and pushing an event EARLIER
than the cached head bucket).
"""

import dataclasses
import hashlib
import heapq
import json
import random
from collections import Counter

import pytest

from repro.serving import baselines as B
from repro.serving.event_queue import CalendarQueue
from repro.serving.experiment import make_policy
from repro.serving.golden import golden_sim_config, golden_specs
from repro.serving.profiles import build_input_pool, build_profiles
from repro.serving.simulator import InvocationResult, SimConfig, Simulator
from repro.serving.workload import Arrival, ScenarioSpec, generate_scenario

FIELDS = [f.name for f in dataclasses.fields(InvocationResult)]


def _build_stack():
    profiles = build_profiles()
    pool = build_input_pool(seed=0)
    slo = B.build_slo_table(profiles, pool)
    return profiles, pool, slo


def _run_loop(policy, spec, cfg):
    """Run one cell; also count the scheduled events by kind."""
    profiles, pool, slo = _build_stack()
    trace = generate_scenario(
        spec, functions=sorted(profiles),
        inputs_per_function={f: len(pool[f]) for f in profiles})
    pol = make_policy(policy, profiles, pool, slo, seed=0)
    sim = Simulator(policy=pol, profiles=profiles, input_pool=pool,
                    slo_table=slo, cfg=cfg)
    kinds = Counter()
    handle = sim._handle_scheduled

    def counting_handle(t, kind, payload):
        kinds[kind] += 1
        handle(t, kind, payload)

    sim._handle_scheduled = counting_handle
    return sim, sim.run(trace), kinds


def _digest(results, chain_summary=None):
    h = hashlib.sha256()
    for r in results:
        h.update(repr(tuple(getattr(r, f) for f in FIELDS)).encode())
    if chain_summary is not None:
        h.update(json.dumps(chain_summary, sort_keys=True).encode())
    return h.hexdigest()


# ---------------------------------------------------- frozen references
_SPECS = golden_specs()
_FLASH_90S = ScenarioSpec(scenario="flash-crowd", rps=2.0, duration_s=90.0,
                          seed=0)
# a 4 x 32-vCPU cluster with a bounded retry backlog
_SMALL = dict(n_workers=4, vcpus_per_worker=32, physical_cores=32,
              mem_mb_per_worker=16 * 1024, vcpu_limit=32, seed=0,
              retry_interval_s=1.0, queue_timeout_s=45.0)


def _golden_cfg(scenario, **over):
    return dataclasses.replace(golden_sim_config(scenario), **over)


def _check_retry_storm(sim, results, kinds):
    assert sim.router.admission_queue_events > 0  # front-door holds
    assert any(r.timed_out for r in results)  # retries actually timed out


def _check_sheds(sim, results, kinds):
    assert sim.router.admission_shed > 0
    assert any(r.shed for r in results)


def _check_binds(sim, results, kinds):
    assert sim.router.binds_warming > 0


def _check_pulls(sim, results, kinds):
    assert sim.cfg.image_cache is not None
    assert sum(w.image_cache.misses
               for cl in sim.clusters for w in cl.workers) > 0


def _check_chains(sim, results, kinds):
    assert sim.chain_summary()["chain_stage_spawned"] > 0
    assert kinds["chain_arrival"] > 0


def _check_warm_hits(sim, results, kinds):
    assert any(not r.cold_start and not r.timed_out for r in results)
    assert any(r.timed_out for r in results)


def _check_retimes(sim, results, kinds):
    # co-runner starts and finishes re-queued finish events
    assert kinds["finish"] > 2 * len(results)


def _check_xfer(sim, results, kinds):
    assert kinds["xfer_start"] > 0


# cell -> (policy, spec, config, check, n results, events_processed,
# sha256 of _digest)
CELLS = {
    # saturating cell with queue-mode admission: capacity-queued and
    # front-door-held retries, timeouts and the retry FIFO lane, under
    # the learning policy
    "oversubscribe-retry-storm": (
        "shabari", _SPECS["oversubscribe"],
        _golden_cfg("oversubscribe", admission="queue",
                    admission_headroom=0.5),
        _check_retry_storm, 502, 16219,
        "04c6a91e6ef5c7ad8b60d492de3b9ff99e2be5b20d9840d159254a833310e490"),
    # shed-mode admission on the spike: terminal front-door drops
    "flash-crowd-sheds": (
        "static-large", _SPECS["flash-crowd"],
        _golden_cfg("flash-crowd", admission="shed", admission_headroom=0.5),
        _check_sheds, 768, 928,
        "5ffaba9086400d9e299aba436478d212c610edb76df90b2c48984a682393c23b"),
    # estimate routing: invocations bound to still-warming containers
    # (pending commits, reservation cancellation on timeout)
    "estimate-routing-warming-binds": (
        "shabari", _SPECS["multi-cluster"],
        _golden_cfg("multi-cluster", routing="estimate"),
        _check_binds, 561, 7032,
        "4d54ce0d0503d69827b8829ed2651c4da0fed68f6b77b098ca0584b11cf6c82e"),
    # image cache on: layer pulls, LRU evictions, affinity placement
    "registry-storm-image-cache": (
        "shabari", _SPECS["registry-storm"], _golden_cfg("registry-storm"),
        _check_pulls, 426, 11225,
        "909a407da78254b7a4c5a0ab77b2fe213331e5bf325452547b60365dbf68b269"),
    # downstream stage arrivals pushed at t == now as "chain_arrival"
    # (through the calendar queue, never the retry lane)
    "chain-pipeline-spawned-arrivals": (
        "shabari", _SPECS["chain-pipeline"], _golden_cfg("chain-pipeline"),
        _check_chains, 522, 1206,
        "9eb40dca68d91149dacb4b5ce4ea20ed21e81cecb46b154539291125cf8aefef"),
    # the per-worker contention aggregates and the warm-container index
    # against the scans they replaced
    "flash-crowd-warm-index": (
        "shabari", _FLASH_90S, SimConfig(**_SMALL),
        _check_warm_hits, 986, 29830,
        "fa467f99c13094488473fac1e9e4bc5ee9f6d64ddd129b0fdd9864913d64e24c"),
    # dynamic contention re-queues finish events as co-runners come and
    # go (vcpu_limit above the cores, so contention exists at all)
    "dynamic-contention": (
        "shabari", _FLASH_90S,
        SimConfig(**{**_SMALL, "vcpu_limit": 44}, contention_mode="dynamic"),
        _check_retimes, 986, 31201,
        "e2458514775725a31e470b52a26a584b7deedf7a9f52fe1d4faf2a26ae7fde57"),
    # SLO-native admission sheds work that cannot meet its budget
    "oversubscribe-slo-admission": (
        "shabari", _SPECS["oversubscribe"],
        _golden_cfg("oversubscribe", admission="slo"),
        _check_sheds, 502, 1876,
        "a9e0f8443f6170b4d5c96f10b77cf3ff45b5b5e4345b786d4337c098e922bfd6"),
    # load-headroom shedding across two clusters behind the router
    "multi-cluster-shed-admission": (
        "shabari", _SPECS["multi-cluster"],
        _golden_cfg("multi-cluster", admission="shed"),
        _check_sheds, 561, 2401,
        "8c8f872e6bf46f3aca8300be1274dc7195aa961e726bf4316515d0b1f195a19e"),
    # remote warm placements wait for their payload: xfer_start events
    "wan-spill-xfer-start": (
        "shabari", _SPECS["wan-spill"], _golden_cfg("wan-spill"),
        _check_xfer, 561, 11309,
        "0516b915c2d4ec692d6228d664e9b0bfc7cb279bcdefc63f6c6eece0d41418b1"),
}


@pytest.mark.parametrize("cell", list(CELLS))
def test_loop_matches_frozen_reference(cell):
    policy, spec, cfg, check, n, events, sha = CELLS[cell]
    sim, results, kinds = _run_loop(policy, spec, cfg)
    check(sim, results, kinds)
    assert (len(results), sim.events_processed) == (n, events)
    assert _digest(results, sim.chain_summary()) == sha


# ------------------------------------------------ cohort partition
def _record_cohorts(sim):
    """Record (a) the flattened order every arrival is processed in and
    (b) the multi-payload cohort partitions handed to the policy batch
    hook. Singleton cohorts are equivalent to a direct ``_on_arrival``
    call (the batch hook only fires for len > 1), and the loop exploits
    that by dispatching lone retries directly — so only the
    multi-payload partitions are pinned, plus the total order."""
    orig_cohort = sim._process_arrival_cohort
    orig_arrival = sim._on_arrival
    order, cohorts = [], []

    def cohort_wrapper(t, payloads):
        if len(payloads) > 1:
            cohorts.append(
                (t, tuple(a.invocation_id for a, _, _, _ in payloads)))
        orig_cohort(t, payloads)

    def arrival_wrapper(arrival, first_seen, alloc=None, aux=None):
        order.append((sim.now, arrival.invocation_id))
        orig_arrival(arrival, first_seen, alloc, aux)

    sim._process_arrival_cohort = cohort_wrapper
    sim._on_arrival = arrival_wrapper
    return order, cohorts


def test_same_timestamp_cohorts_partition_identically():
    """Fresh arrivals sharing a timestamp form one cohort; retries
    landing on that timestamp extend it in seq order. The total order
    and the multi-payload (t, ids) partitions match the frozen
    reference, recorded from this loop and a single global heapq loop,
    which agreed. static-large's allocation never fits the one 8-vCPU
    worker, so all five invocations retry until their 300 s timeout."""
    profiles, pool, slo = _build_stack()
    fn = "lrtrain"
    trace = [Arrival(0, 0.0, fn, 0),
             Arrival(1, 1.0, fn, 0), Arrival(2, 1.0, fn, 0),
             # collides with the t=1.5 retries of invocations 1 and 2
             Arrival(3, 1.5, fn, 0),
             Arrival(4, 9.0, fn, 0)]
    cfg = SimConfig(n_workers=1, vcpus_per_worker=8, physical_cores=8,
                    mem_mb_per_worker=4096, vcpu_limit=8,
                    retry_interval_s=0.5, queue_timeout_s=300.0, seed=0)
    pol = make_policy("static-large", profiles, pool, slo, seed=0)
    sim = Simulator(policy=pol, profiles=profiles, input_pool=pool,
                    slo_table=slo, cfg=cfg)
    order, cohorts = _record_cohorts(sim)
    sim.run(list(trace))
    assert sim.events_processed == 3016
    assert len(order) == 3010 and len(cohorts) == 603
    assert order[:10] == [(0.0, 0), (0.5, 0), (1.0, 1), (1.0, 2), (1.0, 0),
                          (1.5, 3), (1.5, 1), (1.5, 2), (1.5, 0), (2.0, 3)]
    assert cohorts[:3] == [(1.0, (1, 2, 0)), (1.5, (3, 1, 2, 0)),
                           (2.0, (3, 1, 2, 0))]
    assert cohorts[-3:] == [(301.0, (4, 3, 1, 2)), (301.5, (4, 3, 1, 2)),
                            (302.0, (4, 3))]
    assert hashlib.sha256(repr(order).encode()).hexdigest() == (
        "62a6408f08f77790614ef54bcc1a4d7701200dae436359a37fc17a4ce0e67552")
    assert hashlib.sha256(repr(cohorts).encode()).hexdigest() == (
        "b1706668ceaca018442c983054b6f5861a8e45cf95f73e5e1c17607dcb9ebb8e")
    # the trace actually produced a mixed fresh+retry cohort at t=1.5
    mixed = [ids for t, ids in cohorts if t == 1.5]
    assert mixed and set(mixed[0]) >= {1, 2, 3}
    # fresh arrival 3 (virtual seq < any retry seq) leads its cohort
    assert mixed[0][0] == 3


# ------------------------------------------------- CalendarQueue units
def test_calendar_queue_pop_parity_fuzz():
    """Pop order matches a single global heapq over the same pushes,
    with interleaved pops and pushes into already-draining buckets."""
    rng = random.Random(7)
    q = CalendarQueue(bucket_s=1.0)
    ref = []
    seq = 0
    popped = []
    expect = []
    for _ in range(2000):
        if ref and rng.random() < 0.45:
            popped.append(q.pop())
            expect.append(heapq.heappop(ref))
        else:
            ev = (rng.uniform(0.0, 50.0), seq, "k", None)
            seq += 1
            q.push(ev)
            heapq.heappush(ref, ev)
    while ref:
        popped.append(q.pop())
        expect.append(heapq.heappop(ref))
    assert popped == expect
    assert len(q) == 0 and not q


def test_calendar_queue_insert_into_draining_bucket():
    q = CalendarQueue(bucket_s=1.0)
    q.push((0.1, 0, "a", None))
    q.push((0.9, 1, "b", None))
    assert q.pop()[2] == "a"  # bucket 0 is now the draining bucket
    q.push((0.5, 2, "c", None))  # lands in the draining bucket
    assert q.pop()[2] == "c"
    assert q.pop()[2] == "b"


def test_calendar_queue_push_earlier_than_cached_head():
    """A push that OPENS a bucket earlier than the cached head must
    invalidate the cache (regression test for the head-bucket cache)."""
    q = CalendarQueue(bucket_s=1.0)
    q.push((8.2, 0, "late", None))
    assert q.peek()[2] == "late"  # caches bucket 8 as the head
    q.push((5.5, 1, "early", None))
    assert q.peek()[2] == "early"
    assert q.pop()[2] == "early"
    assert q.pop()[2] == "late"


def test_calendar_queue_same_t_orders_by_seq_across_kinds():
    q = CalendarQueue(bucket_s=1.0)
    q.push((2.0, 7, "retry", None))
    q.push((2.0, 5, "finish", None))
    q.push((2.0, 6, "warm_start", None))
    assert [q.pop()[2] for _ in range(3)] == ["finish", "warm_start", "retry"]


def test_calendar_queue_empty_pop_raises():
    q = CalendarQueue()
    with pytest.raises(IndexError):
        q.pop()
    q.push((1.0, 0, "x", None))
    q.pop()
    with pytest.raises(IndexError):
        q.pop()
    assert q.peek() is None
