"""The agent arena's spans and counters with the TPU v5e's backends
forced (no NumPy path; vmapped kernels for dims 1 and 3 only, sequential
kernels elsewhere): every device dispatch is counted once under its
kernel and feature dim, each one's copies, launch and read-back are
spanned, and tracing changes no decision and no weight."""

import collections

import numpy as np
import pytest

from repro import spans
from repro.core import agent_arena
from repro.core.allocator import ResourceAllocator
from repro.core.cost_functions import Observation

DIMS = {"f1": 1, "f2": 2, "f3": 3, "f5": 5, "f6": 6, "g3": 3, "g1": 1}


@pytest.fixture(autouse=True)
def clean():
    spans.disable()
    spans.reset()
    yield
    spans.disable()
    spans.reset()


@pytest.fixture
def v5e(monkeypatch):
    """The v5e's backend table, and kernels that count their calls by
    (kernel, feature dim)."""
    monkeypatch.setattr(agent_arena, "numpy_backend", lambda d: False)
    monkeypatch.setattr(agent_arena, "vmap_backend", lambda d: d in (1, 3))
    calls = collections.Counter()

    def counted(name, fn, dim_of):
        def wrapped(*args):
            calls[(name, dim_of(args))] += 1
            return fn(*args)
        monkeypatch.setattr(agent_arena, "_" + name, wrapped)

    counted("csc_predict", agent_arena._csc_predict, lambda a: a[1].shape[0])
    counted("csc_update", agent_arena._csc_update, lambda a: a[2].shape[0])
    counted("batched_predict", agent_arena._batched_predict,
            lambda a: a[1].shape[1] - 1)
    counted("batched_update", agent_arena._batched_update,
            lambda a: a[2].shape[1] - 1)
    return calls


def _obs(rng) -> Observation:
    v = int(rng.integers(1, 33))
    return Observation(exec_time_s=float(rng.uniform(0.05, 30.0)),
                       slo_s=float(rng.uniform(0.1, 20.0)), alloc_vcpus=v,
                       max_vcpus_used=float(rng.uniform(0.01, 1.0) * v),
                       alloc_mem_mb=int(rng.integers(128, 8192)),
                       max_mem_used_mb=float(rng.uniform(16.0, 6000.0)))


def _stream(seed=11, n_ops=140):
    """Served allocations and final weights of a random stream of single
    and batched allocations and feedbacks."""
    rng = np.random.default_rng(seed)
    alloc = ResourceAllocator(engine="arena", vcpu_confidence=2,
                              mem_confidence=3)
    fns = sorted(DIMS)
    served = []
    for _ in range(n_ops):
        r = rng.random()
        if r < 0.3:
            picks = [fns[i] for i in rng.integers(len(fns), size=4)]
            served.append(alloc.allocate_batch([
                (f, rng.standard_normal(DIMS[f]).astype(np.float32), 100.0)
                for f in picks]))
        else:
            fn = fns[int(rng.integers(len(fns)))]
            x = rng.standard_normal(DIMS[fn]).astype(np.float32)
            if r < 0.6:
                served.append(alloc.allocate(fn, x, 100.0))
            else:
                alloc.feedback(fn, x, _obs(rng))
    weights = {f: alloc._arena.weights(f) for f in fns
               if alloc._arena.updates(f) != (0, 0)}
    return served, weights


def test_dispatch_counters_match_the_kernel_calls(v5e):
    spans.enable()
    _stream()
    snap = spans.snapshot()
    counted = collections.Counter()
    for key, n in snap["counters"].items():
        if key.startswith("arena.dispatch/"):
            _, kernel, dim = key.split("/")
            counted[(kernel, int(dim))] += n
    argmin = {k: n for k, n in counted.items() if k[0] == "argmin"}
    kernels = {k: n for k, n in counted.items() if k[0] != "argmin"}
    assert kernels == dict(v5e)
    # one eager arg-min per sequential predict, none after a batched one
    assert argmin == {("argmin", d): n for (k, d), n in v5e.items()
                      if k == "csc_predict"}
    assert {d for _, d in v5e} == set(DIMS.values())
    assert {k for k, _ in v5e} == {"csc_predict", "csc_update",
                                   "batched_predict", "batched_update"}
    # each dispatch has its copies, its launch and its read-back
    dispatches = sum(counted.values())
    assert snap["spans"]["arena.launch"]["calls"] == dispatches
    copies = dispatches - sum(argmin.values())
    assert snap["spans"]["arena.h2d"]["calls"] == copies
    assert snap["spans"]["arena.d2h"]["calls"] == copies
    c = snap["counters"]
    assert c["arena.flush_rows"] >= c["arena.flush_pass"] > 0
    assert c["arena.flush_cause/own"] > 0 and c["arena.flush_cause/call"] > 0
    assert "arena.flush_cause/cap" not in c  # never 256 pending here
    assert snap["open"] == 0


def test_predicted_costs_dispatch_without_argmin(v5e):
    alloc = ResourceAllocator(engine="arena")
    x = np.ones(2, np.float32)
    alloc.feedback("f2", x, _obs(np.random.default_rng(0)))
    spans.enable()
    vc, mc = alloc._arena.predicted_costs("f2", x)
    assert vc.shape == (alloc._arena.n_vcpu_classes,)
    assert mc.shape == (alloc._arena.n_mem_classes,)
    counters = spans.snapshot()["counters"]
    assert counters["arena.dispatch/csc_predict/2"] == 2
    assert counters["arena.dispatch/csc_update/2"] == 2
    assert not any(k.startswith("arena.dispatch/argmin") for k in counters)


def test_tracing_changes_no_decision_and_no_weight(v5e):
    off = _stream()
    spans.enable(annotate=True)
    on = _stream()
    spans.disable()
    assert spans.snapshot()["spans"]["arena.flush"]["calls"] > 0
    assert on[0] == off[0]
    assert on[1].keys() == off[1].keys() and on[1]
    for fn in off[1]:
        for a, b in zip(on[1][fn], off[1][fn]):
            assert np.array_equal(a, b)
