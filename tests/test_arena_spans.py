"""The agent arena's spans and counters with the TPU v5e's backend table
forced (no NumPy path, so every dimension keeps its state resident on
the device, one arena per dimension whose rows stack a function's vCPU
and memory agents): every device dispatch is a masked 16-row block
kernel, counted once under its kernel and feature dim with the real rows
it carries; a flush pass and a predict call each copy in once, one host
array holding every block they touch (one more per further
``_STAGE_BLOCKS`` blocks), counted by ``arena.h2d_buffers``; a flush
pass launches once per dimension and touched block and reads nothing
back; a predict call launches once per dimension and touched block,
whichever sides its items want, and reads once, a cost block per launch;
``arena.predict_want/*`` count the items a stacked predict serves; the
arena's copy, launch and own host seconds still make up its outermost
seconds; and tracing changes no decision and no weight."""

import collections

import numpy as np
import pytest

from repro import spans
from repro.core import agent_arena
from repro.core.allocator import ResourceAllocator
from repro.core.cost_functions import Observation

DIMS = {"f1": 1, "f2": 2, "f3": 3, "f5": 5, "f6": 6, "g3": 3, "g1": 1}
BLOCK = agent_arena._MAX_BUCKET
STAGED = agent_arena._STAGE_BLOCKS


@pytest.fixture(autouse=True)
def clean():
    spans.disable()
    spans.reset()
    yield
    spans.disable()
    spans.reset()


@pytest.fixture
def v5e(monkeypatch):
    """The v5e's backend table, and kernels that count their launches by
    (kernel, feature dim): a block kernel's launch is handed a 16-row
    block and the call's staged array of ``STAGED`` blocks."""
    monkeypatch.setattr(agent_arena, "numpy_backend", lambda d: False)
    calls = collections.Counter()

    def counted(name, dim_of):
        fn = getattr(agent_arena, "_" + name)

        def wrapped(*args):
            calls[(name, dim_of(args))] += 1
            return fn(*args)
        monkeypatch.setattr(agent_arena, "_" + name, wrapped)

    def launched(name):
        fn = getattr(agent_arena, "_staged_" + name)

        def wrapped(kernel, w, *args):
            assert kernel is getattr(agent_arena, "_batched_" + name)
            assert w.shape[0] == BLOCK
            # after w: (g2, staged, k, lr) or (staged, k)
            staged = args[-3 if name == "update" else -2]
            assert staged.shape[:2] == (STAGED, BLOCK)
            calls[("batched_" + name, w.shape[-1] - 1)] += 1
            return fn(kernel, w, *args)
        monkeypatch.setattr(agent_arena, "_staged_" + name, wrapped)

    counted("csc_predict", lambda a: a[1].shape[0])
    counted("csc_update", lambda a: a[2].shape[0])
    launched("predict")
    launched("update")
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


def _dispatches(counters):
    out = collections.Counter()
    for key, n in counters.items():
        if key.startswith("arena.dispatch/"):
            _, kernel, dim = key.split("/")
            out[(kernel, int(dim))] += n
    return out


def _inside(records, i, name):
    """Whether record ``i`` has an enclosing span called ``name``."""
    parent = records[i][3]
    while parent is not None:
        if records[parent][0] == name:
            return True
        parent = records[parent][3]
    return False


def _engine(n_fns, dim=2, seed=3):
    """An engine whose ``n_fns`` functions of one dim each have one
    applied update: past one block when ``n_fns`` > 16."""
    rng = np.random.default_rng(seed)
    alloc = ResourceAllocator(engine="arena", vcpu_confidence=1,
                              mem_confidence=1)
    eng = alloc._arena
    xs = {f"h{i}": rng.standard_normal(dim).astype(np.float32)
          for i in range(n_fns)}
    for f, x in xs.items():
        eng.enqueue_update(f, x, _obs(rng))
    eng.flush()
    return eng, xs


def test_dispatch_counters_match_the_kernel_calls(v5e):
    spans.enable()
    _stream()
    snap = spans.snapshot()
    counted = _dispatches(snap["counters"])
    assert counted == dict(v5e)
    assert {d for _, d in v5e} == set(DIMS.values())
    # only the masked block kernels run: no per-row kernel, no argmin
    assert {k for k, _ in v5e} == {"batched_predict", "batched_update"}
    # each dispatch is one launch; each block carries 1..16 real rows
    dispatches = sum(counted.values())
    assert snap["spans"]["arena.launch"]["calls"] == dispatches
    rows = snap["counters"]["arena.dispatch_rows"]
    assert dispatches <= rows <= BLOCK * dispatches
    # one copy-in per flush pass and per predict call, one read per
    # predict call, and no read inside a flush
    recs = snap["records"]
    d2h = [i for i, r in enumerate(recs) if r[0] == "arena.d2h"]
    assert d2h and not any(_inside(recs, i, "arena.flush") for i in d2h)
    per_call = collections.Counter(recs[i][3] for i in d2h)
    assert {recs[p][0] for p in per_call} == {"arena.predict_batch"}
    assert set(per_call.values()) == {1}
    c = snap["counters"]
    assert snap["spans"]["arena.h2d"]["calls"] == c["arena.flush_pass"] + len(d2h)
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
    snap = spans.snapshot()
    counters = snap["counters"]
    assert counters["arena.dispatch/batched_predict/2"] == 1
    assert counters["arena.dispatch/batched_update/2"] == 1
    assert counters["arena.dispatch_rows"] == 2
    assert not any(k.startswith("arena.dispatch/argmin") for k in counters)
    assert not any("csc_" in k for k in counters)
    assert snap["spans"]["arena.d2h"]["calls"] == 1


WANTS = {(True, True): "both", (True, False): "vcpu", (False, True): "mem"}


@pytest.fixture
def transfers(monkeypatch):
    """The arrays of each copy-in and of each read, call by call."""
    import jax

    seen = {"in": [], "out": []}

    def counted(key, fn):
        def wrapped(x, *args, **kwargs):
            seen[key].append(len(x) if isinstance(x, list) else 1)
            return fn(x, *args, **kwargs)
        return wrapped

    monkeypatch.setattr(jax, "device_put", counted("in", jax.device_put))
    monkeypatch.setattr(jax, "device_get", counted("out", jax.device_get))
    return seen


@pytest.mark.parametrize("want", list(WANTS))
def test_predict_launches_once_per_wanted_arena_block(v5e, transfers, want):
    """A cohort spanning two blocks of one dim: one launch per touched
    block of the dim's stacked arena, whichever sides the items want,
    one host array copied in for the whole call and exactly one read,
    carrying one cost block per launch."""
    eng, xs = _engine(BLOCK + 4)
    v5e.clear()
    transfers["in"].clear()
    spans.enable()
    items = [(f, x, *want) for f, x in xs.items()]
    picks = eng.predict_batch(items)
    snap = spans.snapshot()
    assert all((v is not None) == want[0] and (m is not None) == want[1]
               for v, m in picks)
    assert dict(_dispatches(snap["counters"])) == {("batched_predict", 2): 2}
    assert v5e == {("batched_predict", 2): 2}
    assert snap["counters"]["arena.dispatch_rows"] == len(items)
    assert snap["spans"]["arena.h2d"]["calls"] == 1
    assert snap["spans"]["arena.d2h"]["calls"] == 1
    assert transfers == {"in": [1], "out": [2]}
    assert {k: n for k, n in snap["counters"].items()
            if k.startswith("arena.predict_want/")} == {
        "arena.predict_want/" + WANTS[want]: len(items)}
    assert "arena.flush" not in snap["spans"]  # nothing was pending


def test_predict_splits_a_repeated_function_into_rounds(v5e):
    """Two items of one function in a cohort share a row: they go in
    successive launches of the same block, still read once."""
    eng, xs = _engine(3)
    v5e.clear()
    spans.enable()
    (f, x), (g, y) = list(xs.items())[:2]
    picks = eng.predict_batch([(f, x, True, True), (g, y, True, False),
                               (f, -x, False, True)])
    snap = spans.snapshot()
    assert v5e == {("batched_predict", 2): 2}
    assert snap["counters"]["arena.dispatch_rows"] == 3
    assert snap["spans"]["arena.d2h"]["calls"] == 1
    assert picks[0] == eng.predict_batch([(f, x, True, True)])[0]
    assert picks[1] == eng.predict_batch([(g, y, True, False)])[0]
    assert picks[2] == eng.predict_batch([(f, -x, False, True)])[0]


def test_flush_pass_launches_once_per_arena_block_and_reads_nothing(
        v5e, transfers):
    """A pass over 20 functions of dim 2 (two blocks) and one of dim 5:
    one launch per dim and touched block, one host array copied in that
    holds every block's inputs and stacked costs, no read."""
    eng, xs = _engine(BLOCK + 4)
    rng = np.random.default_rng(5)
    for f, x in xs.items():
        eng.enqueue_update(f, x, _obs(rng))
    eng.enqueue_update("s5", rng.standard_normal(5).astype(np.float32),
                       _obs(rng))
    v5e.clear()
    transfers["in"].clear()
    spans.enable()
    eng.flush()
    snap = spans.snapshot()
    assert v5e == {("batched_update", 2): 2, ("batched_update", 5): 1}
    assert snap["counters"]["arena.flush_pass"] == 1
    assert snap["counters"]["arena.dispatch_rows"] == len(xs) + 1
    assert snap["spans"]["arena.h2d"]["calls"] == 1
    assert transfers == {"in": [1], "out": []}
    assert snap["counters"]["arena.h2d_buffers"] == 1
    assert "arena.d2h" not in snap["spans"]


@pytest.mark.parametrize("call", ["flush", "predict"])
def test_a_call_past_one_staged_array_copies_in_one_more(v5e, transfers,
                                                         call):
    """A flush pass or a predict call touching ``STAGED + 1`` blocks of
    one dim: two host arrays in one copy-in, inside one ``arena.h2d``
    span, and one launch per block."""
    eng, xs = _engine(STAGED * BLOCK + 1)
    if call == "flush":
        rng = np.random.default_rng(9)
        for f, x in xs.items():
            eng.enqueue_update(f, x, _obs(rng))
    v5e.clear()
    transfers["in"].clear()
    spans.enable()
    if call == "flush":
        eng.flush()
    else:
        eng.predict_batch([(f, x, True, True) for f, x in xs.items()])
    snap = spans.snapshot()
    kernel = "batched_update" if call == "flush" else "batched_predict"
    assert v5e == {(kernel, 2): STAGED + 1}
    assert snap["counters"]["arena.dispatch_rows"] == len(xs)
    assert snap["spans"]["arena.h2d"]["calls"] == 1
    assert transfers["in"] == [2]
    assert snap["counters"]["arena.h2d_buffers"] == 2


def test_h2d_buffers_count_one_array_a_copy_in(v5e):
    """Over a random stream whose calls touch fewer than ``STAGED + 1``
    blocks, every copy-in carries one host array."""
    spans.enable()
    _stream()
    snap = spans.snapshot()
    h2d = snap["spans"]["arena.h2d"]["calls"]
    assert h2d > 0
    assert snap["counters"]["arena.h2d_buffers"] == h2d


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


def _staged(monkeypatch):
    """Blocks that each ``_stage`` call hands back, call by call."""
    staged = []
    orig = agent_arena._stage

    def stage(groups):
        out = orig(groups)
        staged.append(len(out))
        return out

    monkeypatch.setattr(agent_arena, "_stage", stage)
    return staged


THREE_BLOCKS = 2 * BLOCK + 8


@pytest.mark.parametrize("call", ["flush", "predict_vcpu", "predict_both"])
def test_dispatch_counters_count_the_blocks_staged(v5e, monkeypatch, call):
    """Functions of one dim over three blocks: one flush pass, or one
    predict call, stages one block per touched block of the dim's
    stacked arena, whichever sides it wants, copies them in once and
    launches once on each, so the dispatch counters count the blocks a
    call touches."""
    eng, xs = _engine(THREE_BLOCKS)
    rng = np.random.default_rng(7)
    for f, x in xs.items():
        eng.enqueue_update(f, x, _obs(rng))
    if call.startswith("predict"):
        eng.flush()
    staged = _staged(monkeypatch)
    v5e.clear()
    spans.enable()
    if call == "flush":
        eng.flush()
    else:
        eng.predict_batch([(f, x, True, call == "predict_both")
                           for f, x in xs.items()])
    snap = spans.snapshot()
    assert staged == [3]
    assert snap["spans"]["arena.h2d"]["calls"] == 1
    assert sum(n for k, n in snap["counters"].items()
               if k.startswith("arena.dispatch/")) == sum(staged)
    assert sum(v5e.values()) == sum(staged)
    assert "arena.block_grow" not in snap["counters"]  # no new function


def test_block_grow_counts_the_blocks_appended(v5e):
    spans.enable()
    eng, xs = _engine(THREE_BLOCKS)
    grown = sum(len(ar.blocks) for ar in eng._arenas.values())
    assert grown == 3
    assert spans.snapshot()["counters"]["arena.block_grow"] == grown
    # a known function, or a slot freed and taken again, grows nothing
    eng.release("h0")
    eng.enqueue_update("new", np.ones(2, np.float32),
                       _obs(np.random.default_rng(1)))
    eng.enqueue_update("h1", np.ones(2, np.float32),
                       _obs(np.random.default_rng(2)))
    assert spans.snapshot()["counters"]["arena.block_grow"] == grown


def test_block_counters_stay_silent_with_spans_off(v5e):
    eng, xs = _engine(THREE_BLOCKS)
    eng.predict_batch([(f, x, True, True) for f, x in xs.items()])
    assert sum(len(ar.blocks) for ar in eng._arenas.values()) == 3
    assert spans.snapshot()["counters"] == {}


ARENA_CALLS = ("arena.predict", "arena.predict_batch", "arena.flush",
               "arena.enqueue_update")


def test_predict_wants_count_the_items_and_shares_partition_the_arena(
        v5e, monkeypatch):
    """Over a random stream: the ``arena.predict_want/*`` counters sum
    to the items that want a class, one launch serves both sides of
    some, and the arena's copy, launch and own host seconds make up
    the seconds inside its outermost public calls, as before."""
    predicted = [0]
    orig = agent_arena.ArenaEngine.predict_batch

    def predict_batch(self, items):
        predicted[0] += sum(bool(v or m) for _, _, v, m in items)
        return orig(self, items)

    monkeypatch.setattr(agent_arena.ArenaEngine, "predict_batch",
                        predict_batch)
    spans.enable()
    _stream()
    snap = spans.snapshot()
    wants = {k.rsplit("/", 1)[1]: n for k, n in snap["counters"].items()
             if k.startswith("arena.predict_want/")}
    # memory's threshold is the higher here, so no item wants it alone
    assert set(wants) == {"both", "vcpu"}
    assert sum(wants.values()) == predicted[0] > 0
    recs = snap["records"]
    outer = 1e-9 * sum(
        t1 - t0 for name, t0, t1, parent, _ in recs
        if name in ARENA_CALLS
        and (parent is None or recs[parent][0] not in ARENA_CALLS))
    sp = snap["spans"]
    parts = (sum(sp[n]["self_seconds"] for n in ARENA_CALLS if n in sp)
             + sum(sp[n]["seconds"] for n in ("arena.h2d", "arena.d2h",
                                              "arena.launch")))
    assert parts == pytest.approx(outer, rel=1e-6)
