"""The program's span and counter module (``repro.spans``): off by
default and free when off; nesting, parents, outermost and self time,
request ids, counters, reset and snapshot, exceptions, and the profiler
annotations."""

import tracemalloc

import jax.profiler
import pytest

from repro import spans


@pytest.fixture(autouse=True)
def clean():
    spans.disable()
    spans.reset()
    yield
    spans.disable()
    spans.reset()


class FakeClock:
    def __init__(self, ticks):
        self.ticks = iter(ticks)

    def perf_counter_ns(self):
        return next(self.ticks)


def test_off_records_nothing_and_allocates_nothing():
    assert not spans.on
    assert spans.span("a") is spans.span("b", rid=7)
    with spans.span("a"):
        spans.count("c")
    # warm, then look for any block allocated in the module while off
    tracemalloc.start()
    try:
        before = tracemalloc.take_snapshot()
        for i in range(1000):
            with spans.span("arena.flush", rid=i):
                spans.count("arena.flush_pass", 3)
        after = tracemalloc.take_snapshot()
    finally:
        tracemalloc.stop()
    only = [tracemalloc.Filter(True, spans.__file__)]
    grown = [d for d in after.filter_traces(only).compare_to(
        before.filter_traces(only), "lineno") if d.size_diff > 0]
    assert grown == []
    assert spans.snapshot() == {"spans": {}, "counters": {}, "records": [],
                                "open": 0}


def test_nesting_parents_outermost_and_self_seconds(monkeypatch):
    # a [0, 100] holds b [10, 30] and a recursive a [40, 90] holding c [50, 60]
    monkeypatch.setattr(spans, "time",
                        FakeClock([0, 10, 30, 40, 50, 60, 90, 100]))
    spans.enable()
    with spans.span("a"):
        with spans.span("b"):
            pass
        with spans.span("a"):
            with spans.span("c"):
                pass
    snap = spans.snapshot()
    assert snap["records"] == [("a", 0, 100, None, None),
                               ("b", 10, 30, 0, None),
                               ("a", 40, 90, 0, None),
                               ("c", 50, 60, 2, None)]
    ns = 1e-9
    assert snap["spans"]["a"] == {"calls": 2, "seconds": pytest.approx(100 * ns),
                                  "self_seconds": pytest.approx((30 + 40) * ns)}
    assert snap["spans"]["b"] == {"calls": 1, "seconds": pytest.approx(20 * ns),
                                  "self_seconds": pytest.approx(20 * ns)}
    assert snap["spans"]["c"]["self_seconds"] == pytest.approx(10 * ns)
    assert snap["open"] == 0


def test_request_ids_are_kept():
    spans.enable()
    with spans.span("policy.begin_batch", (4, 5)):
        with spans.span("policy.featurize", 4):
            pass
    with spans.span("router.route", 4):
        pass
    assert [(r[0], r[4]) for r in spans.snapshot()["records"]] == [
        ("policy.begin_batch", (4, 5)), ("policy.featurize", 4),
        ("router.route", 4)]


def test_counters():
    spans.count("arena.flush_pass")  # off: nothing
    spans.enable()
    spans.count("arena.flush_pass")
    spans.count("arena.flush_rows", 5)
    spans.count("arena.flush_rows", 2)
    assert spans.snapshot()["counters"] == {"arena.flush_pass": 1,
                                            "arena.flush_rows": 7}


def test_reset_and_snapshot():
    spans.enable()
    with spans.span("a"):
        spans.count("n")
    snap = spans.snapshot()
    assert snap["spans"]["a"]["calls"] == 1 and snap["counters"] == {"n": 1}
    snap["records"].clear()  # a snapshot is a copy
    assert len(spans.snapshot()["records"]) == 1
    outer = spans.span("open across the reset")
    outer.__enter__()
    spans.reset()
    with spans.span("b"):
        pass
    outer.__exit__(None, None, None)  # dropped, and harms nothing
    snap = spans.snapshot()
    assert list(snap["spans"]) == ["b"] and snap["counters"] == {}
    assert snap["records"][0][:1] == ("b",) and snap["records"][0][3] is None
    assert snap["open"] == 0


def test_a_span_left_by_an_exception_closes():
    spans.enable()
    with pytest.raises(KeyError):
        with spans.span("outer"):
            with spans.span("inner"):
                raise KeyError("stop")
    snap = spans.snapshot()
    assert snap["open"] == 0
    assert all(r[2] is not None for r in snap["records"])
    assert {n: s["calls"] for n, s in snap["spans"].items()} == {
        "outer": 1, "inner": 1}


def test_disable_leaves_open_spans_to_close():
    spans.enable()
    with spans.span("a"):
        spans.disable()
    assert spans.snapshot()["spans"]["a"]["calls"] == 1
    assert spans.snapshot()["open"] == 0


def test_annotations_open_only_with_annotate(monkeypatch):
    opened = []

    class Annotation:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            opened.append(self.name)

        def __exit__(self, *exc):
            opened.append("/" + self.name)

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Annotation)
    spans.enable(annotate=False)
    with spans.span("a"):
        pass
    assert opened == []
    spans.enable(annotate=True)
    with spans.span("a"):
        with spans.span("b"):
            pass
    assert opened == ["shabari/a", "shabari/b", "/shabari/b", "/shabari/a"]
    spans.disable()
    with spans.span("c"):
        pass
    assert len(opened) == 4


@pytest.fixture
def tracing(monkeypatch):
    """Whether a profiler trace is being collected, as profiled() sees it."""
    state = {"on": False}
    monkeypatch.setattr(jax.profiler.TraceAnnotation, "is_enabled",
                        staticmethod(lambda: state["on"]))
    monkeypatch.setattr(spans, "_profiling", False)
    return state


def test_profiled_records_nothing_without_a_trace(tracing):
    with spans.profiled():
        assert not spans.on
        with spans.span("a"):
            spans.count("c")
    assert spans.snapshot()["records"] == []


def test_profiled_records_annotated_inside_a_trace(tracing):
    tracing["on"] = True
    with spans.profiled():
        assert spans.on and spans._annotation is not None
        with spans.span("a"):
            spans.count("c")
    assert not spans.on and spans._annotation is None
    snap = spans.snapshot()  # kept after the block
    assert snap["spans"]["a"]["calls"] == 1 and snap["counters"] == {"c": 1}


def test_profiled_blocks_of_one_trace_add_up_and_a_new_trace_resets(tracing):
    tracing["on"] = True
    for _ in range(3):
        with spans.profiled():
            with spans.span("a"):
                pass
    assert spans.snapshot()["spans"]["a"]["calls"] == 3
    tracing["on"] = False
    with spans.profiled():
        with spans.span("a"):
            pass
    assert spans.snapshot()["spans"]["a"]["calls"] == 3
    tracing["on"] = True
    with spans.profiled():
        with spans.span("b"):
            pass
    assert list(spans.snapshot()["spans"]) == ["b"]


def test_profiled_leaves_an_explicit_enable_alone(tracing):
    spans.enable(annotate=False)
    with spans.span("before"):
        pass
    tracing["on"] = True
    with spans.profiled():
        assert spans._annotation is None
    assert spans.on
    assert spans.snapshot()["spans"]["before"]["calls"] == 1


def test_profiled_stops_when_its_block_raises(tracing):
    tracing["on"] = True
    with pytest.raises(KeyError):
        with spans.profiled():
            with spans.span("a"):
                raise KeyError("stop")
    assert not spans.on
    snap = spans.snapshot()
    assert snap["open"] == 0 and snap["spans"]["a"]["calls"] == 1
