"""The trace reduction with the program's own ``shabari/`` spans in the
trace: the trace holds one annotation per recorded span, the accepted
reduction labels idle time with the benchmark's spans as before, and the
program records only while the trace is being collected. The CPU trace
is recorded here: four rounds of a ``shabari/arena.flush`` span that
sleeps 3 ms and holds a ``shabari/arena.launch`` span running one jitted
computation, inside ``bench/arena.flush`` and ``bench/window``, all in a
``spans.profiled()`` block as ``Simulator.run`` opens one. The recorded
fixture of ``test_bench_xplane.py`` still reduces to what it always
did."""

import collections
import os
import time

import jax
import jax.numpy as jnp
import pytest

from bench import xplane
from bench.tests.test_bench_xplane import TRACE, cpu_ops
from repro import spans

ROUNDS = 4


def flush_rounds(step, x):
    for _ in range(ROUNDS):
        with jax.profiler.TraceAnnotation("bench/arena.flush"):
            with spans.span("arena.flush"):
                time.sleep(0.003)
                with spans.span("arena.launch"):
                    step(x).block_until_ready()


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    log_dir = str(tmp_path_factory.mktemp("trace"))
    step = jax.jit(lambda x: x * 2.0 + 1.0)
    x = jnp.ones((256, 256))
    step(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    spans.disable()
    with spans.profiled():  # no trace yet: records nothing
        flush_rounds(step, x)
    before = spans.snapshot()
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation(xplane.WINDOW_SPAN):
            with spans.profiled():
                on_inside = spans.on
                flush_rounds(step, x)
    finally:
        jax.profiler.stop_trace()
    after_block = spans.on
    with spans.profiled():  # the trace has ended: records nothing more
        flush_rounds(step, x)
    snap = spans.snapshot()
    spans.reset()
    return (xplane.load(xplane.find_trace(log_dir)), snap,
            (before, on_inside, after_block))


def test_the_program_records_only_inside_the_trace(recorded):
    _, snap, (before, on_inside, after_block) = recorded
    assert before["records"] == []
    assert on_inside and not after_block and not spans.on
    assert {n: s["calls"] for n, s in snap["spans"].items()} == {
        "arena.flush": ROUNDS, "arena.launch": ROUNDS}
    assert snap["open"] == 0


def test_idle_time_keeps_the_benchmark_span_labels(recorded):
    pd, _, _ = recorded
    red = xplane.reduce_trace(pd, cpu_ops)
    idle = dict(red["idle_by_span"])
    # the sleeps happen inside shabari/arena.flush, itself inside
    # bench/arena.flush: the accepted reduction names the latter
    assert not any(n.startswith("shabari/") for n in idle)
    assert idle["bench/arena.flush"] > 0.012
    assert idle["bench/arena.flush"] == max(idle.values())
    assert sum(idle.values()) + red["busy_s"] == pytest.approx(red["window_s"],
                                                               rel=1e-9)


def test_one_annotation_per_recorded_span(recorded):
    pd, snap, _ = recorded
    host = collections.Counter(
        e.name for p in pd.planes if p.name.startswith("/host:")
        for ln in p.lines for e in ln.events if e.name.startswith("shabari/"))
    assert host == {"shabari/" + n: s["calls"] for n, s in snap["spans"].items()}
    assert host["shabari/arena.flush"] == ROUNDS


def test_the_recorded_fixture_reduces_as_before():
    assert os.path.exists(TRACE)
    assert xplane.reduce_trace(xplane.load(TRACE), cpu_ops) == {
        "devices": 1, "window_s": 0.015199595000000002,
        "busy_s": 0.001651106, "ops": 24.0,
        "top_ops": [("broadcast_multiply_fusion", 0.0016508470000000002),
                    ("end: broadcast_multiply_fusion", 3.788e-06),
                    ("ThunkExecutor::Execute (wait for completion)", 2.59e-07),
                    ("ThreadpoolListener::StartRegion", 0.0),
                    ("ThreadpoolListener::Record", 0.0),
                    ("ThreadpoolListener::StopRegion", 0.0)],
        "idle_by_span": [("bench/route", 0.013312542),
                         ("bench/arena.predict", 0.00023594700000000002)]}
