"""The trace reduction on a small trace recorded on the CPU
(``data/cpu_trace.xplane.pb``: four rounds of a ``bench/route`` span
that sleeps 3 ms and a ``bench/arena.predict`` span that runs one jitted
computation, inside ``bench/window``). On the CPU the computations run
on the XLA client's threads of the host plane, which stand in for a
device plane here."""

import os

import pytest

from bench import xplane

TRACE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                     "cpu_trace.xplane.pb")


def cpu_ops(plane, line):
    return plane == "/host:CPU" and line.startswith("tf_XLAPjRtCpuClient")


@pytest.fixture(scope="module")
def reduced():
    return xplane.reduce_trace(xplane.load(TRACE), cpu_ops)


def test_busy_and_idle_fill_the_window(reduced):
    assert reduced["devices"] == 1
    assert 0.0 < reduced["busy_s"] < reduced["window_s"]
    idle = sum(s for _, s in reduced["idle_by_span"])
    assert idle + reduced["busy_s"] == pytest.approx(reduced["window_s"],
                                                     rel=1e-9)


def test_ops_and_top_ops(reduced):
    names = [n for n, _ in reduced["top_ops"]]
    assert "broadcast_multiply_fusion" in names
    assert reduced["ops"] >= 4
    secs = [s for _, s in reduced["top_ops"]]
    assert secs == sorted(secs, reverse=True)


def test_idle_time_is_labelled_by_the_open_span(reduced):
    idle = dict(reduced["idle_by_span"])
    # the four 3 ms sleeps happen inside bench/route
    assert idle["bench/route"] > 0.012
    assert idle["bench/route"] == max(idle.values())


def test_no_device_lines_and_no_window():
    pd = xplane.load(TRACE)
    assert xplane.reduce_trace(pd)["devices"] == 0  # no TPU planes here
    assert set(xplane.layout(pd)) >= {"/host:CPU"}


def test_union_and_labels():
    assert xplane._union([(0, 2), (1, 3), (5, 6)]) == [(0, 3), (5, 6)]
    spans = [(0, 10, "outer"), (2, 4, "inner"), (6, 7, "late")]
    assert xplane._labels(spans, [1, 3, 5, 6.5, 11]) == [
        "outer", "inner", "outer", "late", xplane.LOOP]
