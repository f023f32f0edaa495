"""A traced run of the harness on the CPU with the TPU v5e's arena
backends forced, so that every prediction and update is a device
dispatch as on the chip: the program's spans and counters reach the
readers, the arena's shares add up to its time, the program's flush
agrees with the benchmark's wrapper, and the stop leaves no span open.

The window holds at least two whole passes of the cell on this path
(about 4 s each on one idle core)."""

import contextlib
import io
import json

import jax
import pytest

from bench import harness, peaks, program
from repro import compile_cache, spans
from repro.core import agent_arena

WINDOW_S = 16
ARENA_SHARES = ("arena_transfer_share_pct", "arena_launch_share_pct",
                "arena_host_share_pct")
NEW = ("arena_dispatches_per_inv",) + ARENA_SHARES


@pytest.fixture(scope="module")
def traced():
    runs = []
    real_run = harness.Run

    def keep(**kwargs):
        runs.append(real_run(**kwargs))
        return runs[-1]

    v5e = peaks.peaks("TPU v5 lite")
    out, err = io.StringIO(), io.StringIO()
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(harness, "require_chips", lambda n: jax.devices()[0])
        mp.setattr(harness.peaks, "peaks", lambda kind: v5e)
        mp.setattr(compile_cache, "enable_compile_cache", lambda: "off")
        mp.setattr(agent_arena, "numpy_backend", lambda d: False)
        mp.setattr(agent_arena, "vmap_backend", lambda d: d in (1, 3))
        mp.setattr(harness, "Run", keep)
        with contextlib.redirect_stdout(out), contextlib.redirect_stderr(err):
            rc = harness.main(["--workload", "testbed.azure",
                               "--seed", str(2**31 + 77),
                               "--seconds", str(WINDOW_S), "--trace", "1"])
    assert rc == 0, err.getvalue()
    snap = program.window()
    spans.reset()
    res = json.loads(out.getvalue().strip().splitlines()[-1])
    return res, runs[0], snap, err.getvalue()


def test_the_new_metrics_are_reported_and_positive(traced):
    res, run, snap, _ = traced
    assert res["correct"] is True, res["checks"]
    assert res["attempted"] >= 1200
    for name in NEW:
        assert res["metrics"][name]["value"] > 0, name


def test_arena_shares_add_up_to_the_arena_time(traced):
    res, run, snap, _ = traced
    shares = sum(res["metrics"][m]["value"] for m in ARENA_SHARES)
    outer = 100.0 * program.arena_outer_s(snap) / run.window_s
    assert shares == pytest.approx(outer, rel=0.01)


def test_program_flush_lies_inside_the_wrapper_flush(traced):
    _, run, snap, _ = traced
    wrapper = run.probe.seconds["arena.flush"]
    inside = program.seconds(snap, ("arena.flush",))
    assert 0.95 * wrapper <= inside <= wrapper
    assert (snap["spans"]["arena.flush"]["calls"]
            == run.probe.calls["arena.flush"])


def test_the_stop_leaves_no_span_open(traced):
    _, run, snap, _ = traced
    assert snap["open"] == 0
    assert all(r[2] is not None for r in snap["records"])
    assert not spans.on


def test_dispatches_and_decisions(traced):
    res, run, snap, err = traced
    assert program.dispatches(snap) == snap["spans"]["arena.launch"]["calls"]
    assert res["metrics"]["arena_dispatches_per_inv"]["value"] == pytest.approx(
        program.dispatches(snap) / run.terminal)
    # one decision per allocation, as the wrapper counts them; the wrapper
    # leaves out a route that the stop ends
    assert 0 <= len(program.decisions_s(snap)) - len(run.probe.decisions_s) <= 1
    assert "program decisions: " in err
    for table in ("program spans: ", "program counters: "):
        assert any(line.startswith("[bench] " + table)
                   for line in err.splitlines())


def test_a_program_without_spans_reads_none(monkeypatch):
    import sys

    import repro

    monkeypatch.setitem(sys.modules, "repro.spans", None)
    monkeypatch.delattr(repro, "spans", raising=False)
    assert program.window() is None
    for name in NEW:
        assert harness.load_reader(name)(None) is None, name


def test_nothing_recorded_reads_none():
    spans.reset()
    assert program.window() is None
