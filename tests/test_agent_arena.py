"""Agent-arena tests: the batched engine must be indistinguishable —
bit for bit — from the legacy per-object path.

Covers: random interleaved allocate/feedback streams (hypothesis),
capacity growth across the doubling boundary, per-function isolation
after slot release/reuse, the flush ordering rule (updates for F apply
before any predict for F), batched-vs-scalar cost vectors, the
calibrated NumPy backend and the vmapped JAX fallback, same-timestamp
arrival microbatching in the simulator, cloned functions whose resident
agents span two device blocks through the simulator, the resident
arena's stacked rows (one slot for a function's vCPU and memory agents,
each side served only where wanted), the resident path's staging (every
block a call touches in one staged array, at shapes that compile nothing
after warm-up), the retry-payload featurization cache, and the
legacy-engine golden pin."""

import json
import os

import numpy as np
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

from repro.core import agent_arena
from repro.core.agent_arena import AgentArena, _matvec_exact, _update_exact
from repro.core.allocator import OnlineCSC, ResourceAllocator
from repro.core.cost_functions import (
    Observation,
    absolute_vcpu_costs,
    absolute_vcpu_costs_batch,
    memory_costs,
    memory_costs_batch,
    proportional_vcpu_costs,
    proportional_vcpu_costs_batch,
)

GOLDEN_DIR = os.path.join(os.path.dirname(__file__), "goldens")


def _rand_obs(rng) -> Observation:
    alloc_v = int(rng.integers(1, 33))
    return Observation(
        exec_time_s=float(rng.uniform(0.05, 30.0)),
        slo_s=float(rng.uniform(0.1, 20.0)),
        alloc_vcpus=alloc_v,
        max_vcpus_used=float(rng.uniform(0.01, 1.0) * alloc_v),
        alloc_mem_mb=int(rng.integers(128, 8192)),
        max_mem_used_mb=float(rng.uniform(16.0, 6000.0)),
        oom_killed=bool(rng.random() < 0.05),
    )


def _pair(**kw):
    return (ResourceAllocator(engine="arena", **kw),
            ResourceAllocator(engine="legacy", **kw))


def _assert_same_weights(arena_alloc, legacy_alloc, fn):
    vw, vg, mw, mg = arena_alloc._arena.weights(fn)
    ag = legacy_alloc._agents[fn]
    assert np.array_equal(vw, np.asarray(ag.vcpu.w))
    assert np.array_equal(vg, np.asarray(ag.vcpu.g2))
    assert np.array_equal(mw, np.asarray(ag.mem.w))
    assert np.array_equal(mg, np.asarray(ag.mem.g2))


# ---------------------------------------------------------- equivalence
@_prop("seed,n_fns,n_ops",
       dict(seed=st.integers(0, 10_000), n_fns=st.integers(1, 6),
            n_ops=st.integers(5, 60)) if hypothesis else None,
       [(s, 1 + s % 6, 5 + (s * 11) % 56) for s in range(10)],
       max_examples=20)
def test_arena_matches_legacy_on_random_stream(seed, n_fns, n_ops):
    """Random interleaving of allocates and feedbacks over functions of
    mixed feature dims: every served Allocation and every final weight
    tensor must be bit-identical across engines."""
    rng = np.random.default_rng(seed)
    fns = [f"f{i}" for i in range(n_fns)]
    dims = {f: int(rng.integers(1, 7)) for f in fns}
    arena, legacy = _pair(vcpu_confidence=2, mem_confidence=3)
    touched = set()
    for _ in range(n_ops):
        fn = fns[int(rng.integers(n_fns))]
        x = rng.standard_normal(dims[fn]).astype(np.float32)
        if rng.random() < 0.5:
            size = float(rng.uniform(0, 3000))
            a = arena.allocate(fn, x, size)
            b = legacy.allocate(fn, x, size)
            assert a == b
        else:
            obs = _rand_obs(rng)
            arena.feedback(fn, x, obs)
            legacy.feedback(fn, x, obs)
            touched.add(fn)
        assert arena.agent_updates(fn) == legacy.agent_updates(fn)
    for fn in touched:
        _assert_same_weights(arena, legacy, fn)


def test_growth_across_doubling_boundary():
    """More functions than the initial arena capacity: slots grow by
    doubling and predictions stay identical to per-object agents."""
    rng = np.random.default_rng(7)
    arena, legacy = _pair(vcpu_confidence=1, mem_confidence=1)
    fns = [f"g{i}" for i in range(11)]  # initial capacity is 4
    xs = {f: rng.standard_normal(3).astype(np.float32) for f in fns}
    for rep in range(2):
        for f in fns:
            obs = _rand_obs(rng)
            arena.feedback(f, xs[f], obs)
            legacy.feedback(f, xs[f], obs)
    for f in fns:
        assert arena.allocate(f, xs[f]) == legacy.allocate(f, xs[f])
        _assert_same_weights(arena, legacy, f)
    eng = arena._arena
    va, _ = eng._arenas_of(3)  # the vCPU and memory host arenas
    assert va.capacity >= 11 and va.capacity % 4 == 0
    assert len({va.slot(f) for f in fns}) == len(fns)


def test_slot_release_and_reuse_isolation():
    """A released slot's next tenant starts as a FRESH agent, and
    bystander functions' weights are untouched by the reuse."""
    rng = np.random.default_rng(11)
    arena, legacy = _pair(vcpu_confidence=1, mem_confidence=1)
    xa = rng.standard_normal(3).astype(np.float32)
    xb = rng.standard_normal(3).astype(np.float32)
    for _ in range(5):
        obs = _rand_obs(rng)
        for al in (arena, legacy):
            al.feedback("a", xa, obs)
            al.feedback("bystander", xb, obs)
    before = arena._arena.weights("bystander")
    eng = arena._arena
    va, _ = eng._arenas_of(3)
    slot_a = va.slot("a")
    arena.release("a")
    legacy.release("a")
    assert arena.agent_updates("a") == (0, 0) == legacy.agent_updates("a")
    # new function lands in the recycled row...
    obs = _rand_obs(rng)
    arena.feedback("fresh", xa, obs)
    legacy.feedback("fresh", xa, obs)
    assert va.slot("fresh") == slot_a
    # ...and behaves exactly like a from-scratch agent
    assert arena.allocate("fresh", xa) == legacy.allocate("fresh", xa)
    _assert_same_weights(arena, legacy, "fresh")
    after = arena._arena.weights("bystander")
    for b, a in zip(before, after):
        assert np.array_equal(b, a)


# ------------------------------------------------------- flush ordering
def test_update_flushes_before_same_function_predict():
    """The ordering rule: a pending update for F is applied before any
    predict for F — same timestamp, same event-loop flush."""
    rng = np.random.default_rng(3)
    arena, legacy = _pair(vcpu_confidence=1, mem_confidence=1)
    x = rng.standard_normal(4).astype(np.float32)
    obs = _rand_obs(rng)
    arena.feedback("f", x, obs)
    legacy.feedback("f", x, obs)
    assert arena._arena._pending  # deferred, not yet applied
    a = arena.allocate("f", x)  # must flush first
    assert not arena._arena._pending
    assert a == legacy.allocate("f", x)
    _assert_same_weights(arena, legacy, "f")


def test_batch_predict_flushes_pending_and_matches_sequential():
    rng = np.random.default_rng(5)
    arena, legacy = _pair(vcpu_confidence=1, mem_confidence=1)
    xf = rng.standard_normal(3).astype(np.float32)
    xg = rng.standard_normal(6).astype(np.float32)
    for _ in range(3):
        obs = _rand_obs(rng)
        arena.feedback("f", xf, obs)
        legacy.feedback("f", xf, obs)
        obs2 = _rand_obs(rng)
        arena.feedback("g", xg, obs2)
        legacy.feedback("g", xg, obs2)
    batch = arena.allocate_batch([("f", xf, 0.0), ("g", xg, 0.0)])
    seq = [legacy.allocate("f", xf, 0.0), legacy.allocate("g", xg, 0.0)]
    assert batch == seq


def test_deferred_updates_do_not_leak_across_functions():
    """Pending updates for g must not affect a predict for f beyond
    what the sequential path would do (rows are disjoint state)."""
    rng = np.random.default_rng(9)
    arena, legacy = _pair(vcpu_confidence=1, mem_confidence=1)
    x = rng.standard_normal(2).astype(np.float32)
    obs = _rand_obs(rng)
    for al in (arena, legacy):
        al.feedback("f", x, obs)
        al.feedback("g", x, obs)
    assert arena.allocate("f", x) == legacy.allocate("f", x)
    _assert_same_weights(arena, legacy, "g")


# ------------------------------------------------------- cost functions
@_prop("seed,k,n",
       dict(seed=st.integers(0, 100_000), k=st.integers(1, 12),
            n=st.sampled_from([16, 32, 40])) if hypothesis else None,
       [(s * 131, 1 + s % 12, [16, 32, 40][s % 3]) for s in range(15)],
       max_examples=60)
def test_batched_cost_vectors_match_scalar(seed, k, n):
    rng = np.random.default_rng(seed)
    obs = [_rand_obs(rng) for _ in range(k)]
    for scalar, batched in (
        (absolute_vcpu_costs, absolute_vcpu_costs_batch),
        (proportional_vcpu_costs, proportional_vcpu_costs_batch),
    ):
        want = np.stack([scalar(o, n) for o in obs])
        assert np.array_equal(batched(obs, n), want)
    want = np.stack([memory_costs(o, n) for o in obs])
    assert np.array_equal(memory_costs_batch(obs, n), want)


# ------------------------------------------------------------- backends
@pytest.mark.parametrize("dim", [1, 2, 3, 4, 5, 6])
def test_numpy_backend_calibrates_for_all_feature_dims(dim):
    """Every Table-2 feature schema (dims 1-6) must take the
    dispatch-free path on this platform — the engine-speedup gate in
    sim_bench depends on it."""
    assert agent_arena.numpy_backend(dim)


@_prop("seed,dim,n",
       dict(seed=st.integers(0, 100_000), dim=st.integers(1, 6),
            n=st.sampled_from([16, 32, 40])) if hypothesis else None,
       [(s * 977, 1 + s % 6, [16, 32, 40][s % 3]) for s in range(18)],
       max_examples=40)
def test_numpy_kernels_bitwise_equal_reference(seed, dim, n):
    """_matvec_exact/_update_exact vs the jitted reference kernels —
    the property the calibration spot-checks, hammered harder here."""
    import jax.numpy as jnp

    rng = np.random.default_rng(seed)
    w = (rng.standard_normal((n, dim + 1)) * 10).astype(np.float32)
    g2 = (rng.random((n, dim + 1)) * 10).astype(np.float32)
    x = (rng.standard_normal(dim) * 3).astype(np.float32)
    costs = (1.0 + rng.random(n) * 30).astype(np.float32)
    xb = np.concatenate([x, np.ones(1, np.float32)])
    ref_c = np.asarray(agent_arena._csc_predict(jnp.asarray(w),
                                                jnp.asarray(x), n))
    assert np.array_equal(ref_c, _matvec_exact(w.copy(), xb))
    rw, rg = agent_arena._csc_update(
        jnp.asarray(w), jnp.asarray(g2), jnp.asarray(x),
        jnp.asarray(costs), jnp.asarray(np.float32(0.5)))
    gw, gg = _update_exact(w.copy(), g2.copy(), xb, costs, np.float32(0.5))
    assert np.array_equal(np.asarray(rw), gw)
    assert np.array_equal(np.asarray(rg), gg)


def test_jax_fallback_path_matches_legacy(monkeypatch):
    """With the NumPy backend forced off, the vmapped bucketed kernel
    (padding no-ops included) must still be bit-identical."""
    monkeypatch.setattr(agent_arena, "numpy_backend", lambda d: False)
    rng = np.random.default_rng(13)
    arena, legacy = _pair(vcpu_confidence=1, mem_confidence=1)
    fns = ["a", "b", "c"]  # k=3 pads to a 4-bucket
    xs = {f: rng.standard_normal(3).astype(np.float32) for f in fns}
    for _ in range(2):
        for f in fns:
            obs = _rand_obs(rng)
            arena.feedback(f, xs[f], obs)
            legacy.feedback(f, xs[f], obs)
    for f in fns:
        assert arena.allocate(f, xs[f]) == legacy.allocate(f, xs[f])
        _assert_same_weights(arena, legacy, f)
    # the batched predict (one fused vmapped dispatch, bucket-padded
    # 3 -> 4) must match sequential legacy predicts too
    batch = arena.allocate_batch([(f, xs[f], 0.0) for f in fns])
    seq = [legacy.allocate(f, xs[f], 0.0) for f in fns]
    assert batch == seq


def test_jax_fallback_chunks_past_max_bucket(monkeypatch):
    """A flush pass larger than _MAX_BUCKET must chunk into calibrated
    dispatch shapes and still match legacy exactly."""
    monkeypatch.setattr(agent_arena, "numpy_backend", lambda d: False)
    rng = np.random.default_rng(17)
    arena, legacy = _pair(vcpu_confidence=1, mem_confidence=1)
    fns = [f"c{i}" for i in range(agent_arena._MAX_BUCKET + 4)]
    xs = {f: rng.standard_normal(2).astype(np.float32) for f in fns}
    for f in fns:  # one pending update per function -> a 20-item pass
        obs = _rand_obs(rng)
        arena.feedback(f, xs[f], obs)
        legacy.feedback(f, xs[f], obs)
    batch = arena.allocate_batch([(f, xs[f], 0.0) for f in fns])
    seq = [legacy.allocate(f, xs[f], 0.0) for f in fns]
    assert batch == seq
    for f in fns:
        _assert_same_weights(arena, legacy, f)


# ------------------------------------------------- device-resident state
RESIDENT_DIMS = [1, 2, 3, 5, 6]


@pytest.fixture
def resident(monkeypatch):
    """The NumPy backend off, so that every arena is device-resident."""
    monkeypatch.setattr(agent_arena, "numpy_backend", lambda d: False)


def _resident_stream(dim, seed, n_fns=agent_arena._MAX_BUCKET + 5, n_ops=90,
                     check=True):
    """Served allocations of an arena/legacy pair over ``n_fns``
    functions of one dim, with singleton and batched allocations (the
    batches repeat functions) and feedbacks, each allocation checked
    against legacy when ``check``; returns both allocators too."""
    rng = np.random.default_rng(seed)
    arena, legacy = _pair(vcpu_confidence=1, mem_confidence=2)
    fns = [f"r{i}" for i in range(n_fns)]
    for f in fns:  # every function gets a row: past the first block
        obs = _rand_obs(rng)
        x = rng.standard_normal(dim).astype(np.float32)
        arena.feedback(f, x, obs)
        legacy.feedback(f, x, obs)
    served = []
    for _ in range(n_ops):
        r = rng.random()
        if r < 0.25:
            picks = [fns[i] for i in rng.integers(len(fns), size=5)]
            batch = [(f, rng.standard_normal(dim).astype(np.float32), 0.0)
                     for f in picks]
            got = arena.allocate_batch(batch)
            assert not check or got == [legacy.allocate(*b) for b in batch]
            served.append(got)
        else:
            f = fns[int(rng.integers(len(fns)))]
            x = rng.standard_normal(dim).astype(np.float32)
            if r < 0.6:
                got = arena.allocate(f, x, 0.0)
                assert not check or got == legacy.allocate(f, x, 0.0)
                served.append(got)
            else:
                obs = _rand_obs(rng)
                arena.feedback(f, x, obs)
                legacy.feedback(f, x, obs)
    return served, arena, legacy, fns


@pytest.mark.parametrize("dim", RESIDENT_DIMS)
def test_resident_engine_matches_legacy_past_one_block(resident, dim):
    """More than 16 functions of one dim grow the resident state into a
    second block; served classes and weights stay bit-identical."""
    _, arena, legacy, fns = _resident_stream(dim, seed=100 + dim)
    (ar,) = arena._arena._arenas_of(dim)  # both agents, stacked
    assert ar.resident and len(ar.blocks) == 2
    assert ar.n_classes == arena.n_vcpu_classes + arena.n_mem_classes
    for f in fns:
        _assert_same_weights(arena, legacy, f)


@pytest.mark.parametrize("dim", RESIDENT_DIMS)
def test_resident_release_and_reuse_starts_fresh(resident, dim):
    rng = np.random.default_rng(200 + dim)
    arena, legacy = _pair(vcpu_confidence=1, mem_confidence=1)
    xa = rng.standard_normal(dim).astype(np.float32)
    for _ in range(3):
        obs = _rand_obs(rng)
        for al in (arena, legacy):
            al.feedback("a", xa, obs)
            al.feedback("bystander", -xa, obs)
    before = arena._arena.weights("bystander")
    eng = arena._arena
    (ar,) = eng._arenas_of(dim)
    slot_a = ar.slot("a")
    arena.release("a")
    legacy.release("a")
    eng._dim_of("fresh", xa)
    assert ar.slot("fresh") == slot_a
    for row in ar.row("fresh"):
        assert not row.any()  # a fresh zero agent, both halves
    obs = _rand_obs(rng)
    arena.feedback("fresh", xa, obs)
    legacy.feedback("fresh", xa, obs)
    assert arena.allocate("fresh", xa) == legacy.allocate("fresh", xa)
    _assert_same_weights(arena, legacy, "fresh")
    for b, a in zip(before, arena._arena.weights("bystander")):
        assert np.array_equal(b, a)


@pytest.mark.parametrize("dim", RESIDENT_DIMS)
def test_resident_pass_leaves_untouched_rows_bit_identical(resident, dim):
    """A masked block update changes the pass's rows only: every other
    row of the touched blocks, fresh zero rows included, keeps its bits."""
    import jax

    _, arena, _, fns = _resident_stream(dim, seed=300 + dim, n_ops=20)
    eng = arena._arena
    eng.flush()
    arenas = eng._arenas_of(dim)
    before = [jax.device_get(ar.blocks) for ar in arenas]
    rng = np.random.default_rng(dim)
    touched = [fns[0], fns[7], fns[agent_arena._MAX_BUCKET + 1]]
    for f in touched:
        eng.enqueue_update(f, rng.standard_normal(dim).astype(np.float32),
                           _rand_obs(rng))
    eng.flush()
    for ar, old in zip(arenas, before):
        moved = {ar.slot(f) for f in touched}
        for b, (ow, og) in enumerate(old):
            nw, ng = jax.device_get(ar.blocks[b])
            for r in range(agent_arena._MAX_BUCKET):
                s = b * agent_arena._MAX_BUCKET + r
                if s in moved:
                    assert not np.array_equal(nw[r], ow[r])
                else:
                    assert np.array_equal(nw[r], ow[r])
                    assert np.array_equal(ng[r], og[r])


@pytest.mark.parametrize("dim", RESIDENT_DIMS)
def test_resident_cohort_repeating_a_function(resident, dim):
    """Two items of one function in a cohort share a row; served as
    legacy serves them one after the other."""
    rng = np.random.default_rng(400 + dim)
    arena, legacy = _pair(vcpu_confidence=1, mem_confidence=1)
    xs = [rng.standard_normal(dim).astype(np.float32) for _ in range(4)]
    for f in ("p", "q"):
        for x in xs[:2]:
            obs = _rand_obs(rng)
            arena.feedback(f, x, obs)
            legacy.feedback(f, x, obs)
    batch = [("p", xs[0], 0.0), ("q", xs[1], 0.0), ("p", xs[2], 0.0),
             ("p", xs[3], 0.0)]
    assert arena.allocate_batch(batch) == [legacy.allocate(*b) for b in batch]


@pytest.mark.parametrize("dim", RESIDENT_DIMS)
def test_resident_kernels_are_looked_up_at_call_time(resident, monkeypatch,
                                                     dim):
    """A kernel patched into the module after import is the one the
    resident path runs: here one that learns from negated costs."""
    served, *_ = _resident_stream(dim, seed=500 + dim, n_ops=40)
    orig = agent_arena._batched_update
    monkeypatch.setattr(agent_arena, "_batched_update",
                        lambda w, g2, xb, c, lr: orig(w, g2, xb, -c, lr))
    patched, *_ = _resident_stream(dim, seed=500 + dim, n_ops=40,
                                   check=False)
    assert patched != served


@pytest.mark.parametrize("dim", RESIDENT_DIMS)
def test_stacked_resident_engine_matches_legacy_per_wanted_side(resident,
                                                                 dim):
    """Cohorts over two blocks whose items want the vCPU class only, the
    memory class only or both, each repeating a function, between
    feedbacks: the stacked rows serve each wanted side as the legacy
    agent does, nothing for a side not wanted, and keep its weights."""
    rng = np.random.default_rng(600 + dim)
    arena, legacy = _pair(vcpu_confidence=1, mem_confidence=1)
    eng = arena._arena
    fns = [f"s{i}" for i in range(agent_arena._MAX_BUCKET + 3)]

    def feed(f):
        x = rng.standard_normal(dim).astype(np.float32)
        obs = _rand_obs(rng)
        arena.feedback(f, x, obs)
        legacy.feedback(f, x, obs)

    for f in fns:
        feed(f)
    sides = [(True, False), (False, True), (True, True)]
    seen = set()
    for _ in range(12):
        picks = [fns[i] for i in rng.integers(len(fns), size=6)]
        picks[-1] = picks[0]
        items = [(f, rng.standard_normal(dim).astype(np.float32),
                  *sides[int(rng.integers(3))]) for f in picks]
        seen |= {(v, m) for _, _, v, m in items}
        ag = legacy._agents
        assert eng.predict_batch(items) == [
            (ag[f].vcpu.predict(x) if v else None,
             ag[f].mem.predict(x) if m else None) for f, x, v, m in items]
        for f in picks[:2]:
            feed(f)
    assert seen == set(sides)
    for f in fns:
        _assert_same_weights(arena, legacy, f)


@pytest.mark.parametrize("dim", RESIDENT_DIMS)
def test_stacked_row_one_slot_and_release_zeroes_both_halves(resident, dim):
    """One slot holds a function's vCPU and memory agents side by side
    on the class axis; release zeroes the whole row and no other."""
    rng = np.random.default_rng(700 + dim)
    arena, _ = _pair(vcpu_confidence=1, mem_confidence=1)
    eng = arena._arena
    for f in ("a", "b"):
        for _ in range(2):
            eng.enqueue_update(f, rng.standard_normal(dim).astype(np.float32),
                               _rand_obs(rng))
    vw, vg, mw, mg = eng.weights("a")
    (ar,) = eng._arenas_of(dim)
    assert list(eng._arenas.values()) == [ar]
    nv, nm = eng.n_vcpu_classes, eng.n_mem_classes
    assert vw.shape == vg.shape == (nv, dim + 1)
    assert mw.shape == mg.shape == (nm, dim + 1)
    assert all(a.any() for a in (vw, vg, mw, mg))  # both agents learned
    w, g2 = ar.row("a")
    assert np.array_equal(w, np.concatenate([vw, mw]))
    assert np.array_equal(g2, np.concatenate([vg, mg]))
    b, r = divmod(ar.slot("a"), agent_arena._MAX_BUCKET)
    bystander = ar.row("b")
    eng.release("a")
    for a in ar.blocks[b]:
        assert not np.asarray(a[r]).any()
    for old, new in zip(bystander, ar.row("b")):
        assert np.array_equal(old, new)


def _staged_groups(with_costs):
    """Groups of one call over three blocks of dim 2 (rows out of slot
    order, block 1 skipped) and one block of dim 5, each arena stacking
    72 classes, and the ``(16, dim+1)`` inputs and ``(16, 72)`` costs
    of each touched block with the call's rows at their slot offsets."""
    rng = np.random.default_rng(17)
    B = agent_arena._MAX_BUCKET
    groups, want = [], {}
    for dim, slots in ((2, [3, 40, 0, 70, 67, 15]), (5, [9, 2])):
        ar = AgentArena(72, dim)
        fns = [f"d{dim}s{s}" for s in range(max(slots) + 1)]
        for f in fns:
            ar.slot(f)
        xbs = np.concatenate([rng.standard_normal((len(slots), dim)),
                              np.ones((len(slots), 1))], 1).astype(np.float32)
        costs = (rng.random((len(slots), 72)) * 30).astype(np.float32)
        groups.append((ar, [fns[s] for s in slots], xbs,
                       costs if with_costs else None))
        for j, s in enumerate(slots):
            xb, c = want.setdefault((dim, s // B), (
                np.zeros((B, dim + 1), np.float32),
                np.zeros((B, 72), np.float32)))
            xb[s % B] = xbs[j]
            c[s % B] = costs[j]
    return groups, want


@pytest.mark.parametrize("call", ["flush", "predict"])
def test_stage_lays_every_block_into_one_staged_array(resident, call):
    """Three blocks of dim 2 and one of dim 5 go in one staged array, at
    distinct positions; each block's slab holds the block's inputs in its
    first ``dim+1`` columns and, for a flush, its costs in its last 72,
    as a block staged on its own would carry them, and zeros elsewhere."""
    groups, want = _staged_groups(call == "flush")
    staged = agent_arena._stage(groups)
    assert len(staged) == len(want) == 4
    (dev,) = {id(s[5]): s[5] for s in staged}.values()
    width = agent_arena._XB_COLS + (72 if call == "flush" else 0)
    assert dev.shape == (agent_arena._STAGE_BLOCKS, agent_arena._MAX_BUCKET,
                         width)
    host = np.asarray(dev)
    ks = [int(k) for *_, k in staged]
    assert sorted(ks) == list(range(4))
    for (g, ar, b, js, rs, _, _), k in zip(staged, ks):
        xb, c = want[(ar.dim, b)]
        slab = host[k]
        assert np.array_equal(slab[:, :ar.dim + 1], xb)
        if call == "flush":
            assert np.array_equal(slab[:, width - 72:], c)
            assert not slab[:, ar.dim + 1:width - 72].any()
        else:
            assert not slab[:, ar.dim + 1:].any()
    assert not host[4:].any()


def test_resident_calls_compile_nothing_after_warm_up(resident,
                                                      monkeypatch):
    """After calibration and one feedback and predict per dim, flush
    passes and predict calls touching 1, 2, 3 and ``_STAGE_BLOCKS + 1``
    blocks compile nothing: the staged arrays' shape does not follow the
    blocks a call touches."""
    import jax

    B, K = agent_arena._MAX_BUCKET, agent_arena._STAGE_BLOCKS
    rng = np.random.default_rng(23)
    eng = ResourceAllocator(engine="arena")._arena
    fns = {2: [f"a{i}" for i in range((K + 1) * B)], 5: ["b0"]}
    xs = {f: rng.standard_normal(d).astype(np.float32)
          for d, names in fns.items() for f in names}
    agent_arena.calibrate(list(fns))
    for d, names in fns.items():
        agent_arena.vmap_backend(d)
        eng.enqueue_update(names[0], xs[names[0]], _rand_obs(rng))
        eng.predict(names[0], xs[names[0]], True, True)
    for f in xs:  # every slot, so every block, before the count
        eng.enqueue_update(f, xs[f], _rand_obs(rng))
    eng.flush()
    staged = []
    orig = agent_arena._stage
    monkeypatch.setattr(agent_arena, "_stage",
                        lambda g: staged.append(orig(g)) or staged[-1])
    a = fns[2]
    calls = {1: a[:3], 2: [a[0], a[B + 1]], 3: [a[0], a[B], "b0"],
             K + 1: a}
    compiles = []

    def on_event(event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles.append(duration)

    jax.monitoring.register_event_duration_secs_listener(on_event)
    try:
        for n, names in calls.items():
            staged.clear()
            for f in names:
                eng.enqueue_update(f, xs[f], _rand_obs(rng))
            eng.flush()
            eng.predict_batch([(f, xs[f], True, True) for f in names])
            assert [len(s) for s in staged] == [n, n]
    finally:
        jax.monitoring.unregister_event_duration_listener(on_event)
    assert compiles == []


def test_arena_growth_preserves_weights():
    ar = AgentArena(n_classes=4, dim=2, capacity=2)
    s0 = ar.slot("x")
    ar.w[s0] = 1.5
    for name in ("y", "z", "w2", "v"):
        ar.slot(name)
    assert ar.capacity == 8
    assert np.all(ar.w[ar.slot("x")] == 1.5)
    assert np.all(ar.w[ar.slot("v")] == 0.0)


# --------------------------------------------------------- legacy fixes
def test_predict_lazy_defers_host_sync():
    """Satellite fix: the legacy predict issues its dispatch without
    forcing a device->host sync; the int() at the consumption site is
    where the transfer happens — and it matches eager predict."""
    import jax

    rng = np.random.default_rng(1)
    m = OnlineCSC(8, 3)
    x = rng.standard_normal(3).astype(np.float32)
    m.update(x, (1.0 + rng.random(8)).astype(np.float32))
    lazy = m.predict_lazy(x)
    assert isinstance(lazy, jax.Array) and lazy.shape == ()
    assert int(lazy) == m.predict(x)


# --------------------------------------------------- simulator plumbing
def _sim_fixture():
    from repro.serving import baselines as B
    from repro.serving.profiles import build_input_pool, build_profiles

    profiles = build_profiles()
    pool = build_input_pool(seed=0)
    slo = B.build_slo_table(profiles, pool)
    return profiles, pool, slo


def _small_cfg(**over):
    from repro.serving.simulator import SimConfig

    base = dict(n_workers=2, vcpus_per_worker=32, physical_cores=32,
                mem_mb_per_worker=16 * 1024, vcpu_limit=32,
                retry_interval_s=0.5, queue_timeout_s=45.0, seed=0)
    base.update(over)
    return SimConfig(**base)


def _run_shabari(engine, arrivals, profiles, pool, slo, **cfg_over):
    from repro.serving import baselines as B
    from repro.serving.simulator import Simulator

    pol = B.ShabariPolicy(vcpu_confidence=2, mem_confidence=3, engine=engine)
    sim = Simulator(policy=pol, profiles=profiles, input_pool=pool,
                    slo_table=slo, cfg=_small_cfg(**cfg_over))
    return pol, sim.run(arrivals)


def test_engines_identical_through_simulator():
    """Full stack, recorded event stream: every per-invocation field
    identical across engines (not just the summary)."""
    from repro.serving.workload import ScenarioSpec, generate_scenario

    profiles, pool, slo = _sim_fixture()
    spec = ScenarioSpec(scenario="poisson-steady", rps=3.0,
                        duration_s=45.0, seed=0)
    trace = generate_scenario(
        spec, functions=sorted(profiles),
        inputs_per_function={f: len(pool[f]) for f in profiles})
    _, res_a = _run_shabari("arena", trace, profiles, pool, slo)
    _, res_l = _run_shabari("legacy", trace, profiles, pool, slo)
    assert len(res_a) == len(res_l)
    for a, b in zip(res_a, res_l):
        assert a == b


def _drop_updates_past_block_0(monkeypatch):
    """Updates of agents whose slot lies past block 0 never land."""
    orig = agent_arena._update_resident

    def update_resident(groups):
        kept = []
        for ar, fns, xbs, costs in groups:
            js = [j for j, fn in enumerate(fns)
                  if ar.slot(fn) < agent_arena._MAX_BUCKET]
            if js:
                kept.append((ar, [fns[j] for j in js], xbs[js], costs[js]))
        if kept:
            orig(kept)

    monkeypatch.setattr(agent_arena, "_update_resident", update_resident)


@pytest.mark.parametrize("clones,fault,same", [(6, False, True),
                                                (6, True, False),
                                                (1, True, True)])
def test_cloned_functions_through_simulator_past_one_block(
        resident, monkeypatch, clones, fault, same):
    """Six deployments of each profiled function (72 agents, 18-24 of
    them per feature dim in dims 1, 3 and 6) under uniform load on eight
    workers: the
    resident arenas span two blocks and the full stack stays identical
    to the legacy engine. Dropping the updates past block 0 shows there,
    and not with the 12 functions alone, whose arenas hold one block."""
    from repro.serving.experiment import expand_function_clones
    from repro.serving.workload import ScenarioSpec, generate_scenario

    profiles, pool, slo = expand_function_clones(*_sim_fixture(), clones)
    spec = ScenarioSpec(scenario="cold-storm", rps=4.0, duration_s=150.0,
                        seed=5)
    trace = generate_scenario(
        spec, functions=sorted(profiles),
        inputs_per_function={f: len(pool[f]) for f in profiles})
    pol_l, res_l = _run_shabari("legacy", trace, profiles, pool, slo,
                                n_workers=8)
    if fault:
        _drop_updates_past_block_0(monkeypatch)
    pol_a, res_a = _run_shabari("arena", trace, profiles, pool, slo,
                                n_workers=8)
    arenas = pol_a.allocator._arena._arenas.values()
    assert all(ar.resident for ar in arenas)
    assert max(len(ar.blocks) for ar in arenas) == (2 if clones > 1 else 1)
    weights = []
    for fn in profiles:
        if fn in pol_l.allocator._agents:
            try:
                _assert_same_weights(pol_a.allocator, pol_l.allocator, fn)
                weights.append(True)
            except AssertionError:
                weights.append(False)
    assert len(weights) == len(profiles)
    assert (all(weights) and res_a == res_l) is same


def test_same_timestamp_arrivals_batch_identically():
    """The event-loop microbatch (begin_arrival_batch) must serve the
    same allocations as one-by-one processing — including duplicate
    functions inside one timestamp."""
    from repro.serving.workload import Arrival

    profiles, pool, slo = _sim_fixture()
    fns = sorted(profiles)[:3]
    arrivals, iid = [], 0
    for t in (0.0, 0.0, 0.0, 5.0, 5.0, 9.0, 9.0, 9.0, 9.0):
        arrivals.append(Arrival(iid, t, fns[iid % len(fns)], 0))
        iid += 1
    pol_a, res_a = _run_shabari("arena", arrivals, profiles, pool, slo)
    pol_l, res_l = _run_shabari("legacy", arrivals, profiles, pool, slo)
    assert [(r.invocation_id, r.alloc_vcpus, r.alloc_mem_mb, r.finish_t)
            for r in res_a] == \
           [(r.invocation_id, r.alloc_vcpus, r.alloc_mem_mb, r.finish_t)
            for r in res_l]
    assert not pol_a._prealloc and not pol_a._features
    assert not pol_l._prealloc and not pol_l._features


def test_retry_payload_caches_featurization():
    """The featurized input + input size ride the retry payload with
    the cached allocation — the Featurizer runs exactly once per
    invocation no matter how many times it retries."""
    from repro.serving import baselines as B
    from repro.serving.simulator import Simulator
    from repro.serving.workload import Arrival

    profiles, pool, slo = _sim_fixture()
    pol = B.ShabariPolicy(engine="arena")
    calls = []
    orig = pol.featurizer.extract
    pol.featurizer.extract = lambda fn, it, meta, object_id="": (
        calls.append(fn) or orig(fn, it, meta, object_id))

    fn = "lrtrain"
    arrivals = [Arrival(0, 0.0, fn, 0)] + [
        Arrival(i, 1.5, fn, 0) for i in range(1, 6)]
    cfg = _small_cfg(n_workers=1, vcpus_per_worker=12, vcpu_limit=12,
                     physical_cores=12)
    sim = Simulator(policy=pol, profiles=profiles, input_pool=pool,
                    slo_table=slo, cfg=cfg)
    results = sim.run(arrivals)
    assert len(results) == 6
    assert any(r.queued_s > 0 for r in results)  # retries happened
    assert len(calls) == 6  # one featurization per invocation, not per retry


# ------------------------------------------------------------- goldens
def test_legacy_engine_golden_pinned_and_bit_identical():
    """The legacy-engine snapshot must exist AND equal the arena-engine
    golden bit-for-bit — the 'arena is a pure fast path' claim, pinned
    in CI from both sides."""
    scenario = "heavy-tail-inputs"
    with open(os.path.join(GOLDEN_DIR, "legacy-engine",
                           f"{scenario}.json")) as f:
        legacy = json.load(f)
    with open(os.path.join(GOLDEN_DIR, f"{scenario}.json")) as f:
        main = json.load(f)
    assert legacy["policy"] == "shabari-legacy-engine"
    assert legacy["spec"] == main["spec"]
    assert legacy["summary"] == main["summary"]


@pytest.mark.slow
def test_legacy_engine_golden_reproduces():
    from repro.serving.golden import run_golden

    scenario = "heavy-tail-inputs"
    with open(os.path.join(GOLDEN_DIR, "legacy-engine",
                           f"{scenario}.json")) as f:
        want = json.load(f)["summary"]
    got = run_golden(scenario, legacy_engine=True)
    assert got == want
