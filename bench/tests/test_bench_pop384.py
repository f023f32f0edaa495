"""The 384-function cell on the CPU, with the TPU v5e's arena backends
forced, so that every agent's state is resident in 16-row device blocks
as on the chip: a traced run is correct, its agents span several blocks
per arena, blocks grow inside the window and are counted, and the
arena's shares still partition its time. A fault that only agents past
the first block can show turns this cell incorrect and leaves
``testbed.azure``, whose arenas hold one block each, correct; one-pass
bfloat16 dots turn this cell incorrect too.

Each run's window holds exactly one whole pass: the probe lets the first
pass run to its end however long it takes and stops the next at its
first call, so a loaded machine cannot end a window with no whole pass
(a pass of this cell takes 1-5 s on one core, longer under load)."""

import contextlib
import io
import json

import jax
import pytest
from test_bench_harness import OnePass, _bf16_dots

from bench import harness, peaks, program
from repro import compile_cache, spans
from repro.core import agent_arena

BLOCK = agent_arena._MAX_BUCKET
ARENA_SHARES = ("arena_transfer_share_pct", "arena_launch_share_pct",
                "arena_host_share_pct")


def _v5e_on_cpu(mp):
    """Let a run take the CPU for a chip, price it as a v5e, keep JAX's
    compile-cache settings of this process, force the v5e's arena
    backends, and hold the window to one pass."""
    v5e = peaks.peaks("TPU v5 lite")
    mp.setattr(harness, "require_chips", lambda n: jax.devices()[0])
    mp.setattr(harness.peaks, "peaks", lambda kind: v5e)
    mp.setattr(compile_cache, "enable_compile_cache", lambda: "off")
    mp.setattr(agent_arena, "numpy_backend", lambda d: False)
    mp.setattr(harness, "Probe", OnePass)


@pytest.fixture
def v5e_on_cpu(monkeypatch):
    _v5e_on_cpu(monkeypatch)


def _run(workload, trace=0, seed=2**31 + 11):
    out, err = io.StringIO(), io.StringIO()
    with contextlib.redirect_stdout(out), contextlib.redirect_stderr(err):
        rc = harness.main(["--workload", workload, "--seed", str(seed),
                           "--seconds", "1", "--trace", str(trace)])
    assert rc == 0, err.getvalue()
    res = json.loads(out.getvalue().strip().splitlines()[-1])
    assert res["attempted"] == 1200  # one whole pass
    return res


@pytest.fixture(scope="module")
def traced():
    """A traced run of ``pop384.azure``: its result line, what the
    readers read, the engine of its pass and the program's record."""
    runs, engines = [], []
    real_run, real_pass = harness.Run, harness.Pass

    def keep_run(**kwargs):
        runs.append(real_run(**kwargs))
        return runs[-1]

    def keep_pass(sim, results, stream, engine):
        engines.append(engine)
        return real_pass(sim, results, stream, engine)

    with pytest.MonkeyPatch.context() as mp:
        _v5e_on_cpu(mp)
        mp.setattr(harness, "Run", keep_run)
        mp.setattr(harness, "Pass", keep_pass)
        res = _run("pop384.azure", trace=1)
    snap = program.window()
    spans.reset()
    return res, runs[0], engines, snap


def test_pop384_is_correct_over_multi_block_arenas(traced):
    res, run, engines, snap = traced
    assert res["correct"] is True, res["checks"]
    (engine,) = engines
    arenas = engine._arenas.values()
    assert all(ar.resident for ar in arenas)
    # some (dimension, arena) holds more than one block
    assert max(len(ar.blocks) for ar in arenas) > 1
    # the pass starts from empty arenas, so all its blocks grow in the window
    assert snap["counters"]["arena.block_grow"] == sum(
        len(ar.blocks) for ar in arenas)


def test_pop384_reads_its_blocks_per_call(traced):
    res, run, _, snap = traced
    got = res["metrics"]["arena_blocks_per_call"]["value"]
    h2d = snap["spans"]["arena.h2d"]["calls"]
    assert got == pytest.approx(program.dispatches(snap) / h2d)
    assert got > 1
    shares = sum(res["metrics"][m]["value"] for m in ARENA_SHARES)
    outer = 100.0 * program.arena_outer_s(snap) / run.window_s
    assert shares == pytest.approx(outer, rel=0.01)
    assert snap["open"] == 0


def _blocks_past_the_first_unchanged(monkeypatch):
    """Updates of agents whose slot lies past block 0 never land."""
    orig = agent_arena._update_resident

    def update_resident(groups):
        kept = []
        for ar, fns, xbs, costs in groups:
            js = [j for j, fn in enumerate(fns) if ar.slot(fn) < BLOCK]
            if js:
                kept.append((ar, [fns[j] for j in js], xbs[js], costs[js]))
        if kept:
            orig(kept)

    monkeypatch.setattr(agent_arena, "_update_resident", update_resident)


@pytest.mark.parametrize("workload,fault,correct", [
    ("pop384.azure", _blocks_past_the_first_unchanged, False),
    ("testbed.azure", _blocks_past_the_first_unchanged, True),
    ("pop384.azure", _bf16_dots, False)])
def test_planted_faults(v5e_on_cpu, monkeypatch, workload, fault, correct):
    fault(monkeypatch)
    res = _run(workload)
    assert res["correct"] is correct, res["checks"]
    assert any(c["value"] > c["limit"]
               for c in res["checks"].values()) is not correct


def _read_blocks_per_call():
    return harness.load_reader("arena_blocks_per_call")(None)


def test_blocks_per_call_reads_none_without_copies_in(monkeypatch):
    monkeypatch.setattr(program, "window", lambda: None)
    assert _read_blocks_per_call() is None
    snap = {"spans": {}, "counters": {}, "records": []}
    monkeypatch.setattr(program, "window", lambda: snap)
    assert _read_blocks_per_call() is None
    snap["spans"]["arena.h2d"] = {"calls": 3, "seconds": 1.0,
                                  "self_seconds": 1.0}
    snap["counters"] = {"arena.dispatch/batched_update/6": 8,
                        "arena.dispatch/batched_predict/1": 4,
                        "arena.dispatch_rows": 20}
    assert _read_blocks_per_call() == 4
