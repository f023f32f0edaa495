"""chip_smoke.py's phases on the CPU.

The script itself refuses any device but a TPU; these tests call its
phase functions directly. Phase B's replay check must accept a real
recorded run (the ``heavy-tail-inputs`` golden spec) against the
float64 reference, and must reject a stream with one corrupted served
class. The reference's own cost vectors must equal the program's
``cost_functions`` on recorded and synthetic observations. Phase A runs
at a small size with the arena's NumPy backend
turned off, so that its kernels take the device path."""

import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402
from repro.core import agent_arena  # noqa: E402
from repro.core import cost_functions as CF  # noqa: E402
from repro.core.cost_functions import Observation  # noqa: E402
from repro.serving.golden import run_golden  # noqa: E402


@pytest.fixture(scope="module")
def recorded():
    with chip_smoke.recording_arena() as streams:
        run_golden("heavy-tail-inputs")
    (engine, stream), = streams.items()
    return engine, stream


def test_replay_accepts_recorded_golden_run(recorded):
    engine, stream = recorded
    kinds = {ev[0] for ev in stream}
    assert kinds == {"predict", "update"}
    st = chip_smoke.replay_reference(engine, stream)
    assert st["ok"], st["mismatches"][:5]
    assert st["predicts"] > 0
    assert st["max_weight_dev"] <= chip_smoke.WEIGHT_RTOL


def _synthetic_observations(n, seed=0):
    rng = np.random.default_rng(seed)
    for _ in range(n):
        alloc_v = int(rng.integers(1, 40))
        alloc_m = int(rng.integers(1, 48)) * 128
        yield Observation(
            exec_time_s=float(rng.uniform(0.0, 12.0)),
            slo_s=float(rng.uniform(0.1, 10.0)),
            alloc_vcpus=alloc_v,
            max_vcpus_used=float(rng.uniform(0.0, 1.0)) * alloc_v,
            alloc_mem_mb=alloc_m,
            max_mem_used_mb=float(rng.uniform(0.0, 6000.0)),
            oom_killed=bool(rng.random() < 0.2))


def test_reference_costs_equal_cost_functions(recorded):
    _, stream = recorded
    observed = [ev[3] for ev in stream if ev[0] == "update"]
    n_v, n_m = chip_smoke.N_CLASSES["vcpu"], chip_smoke.N_CLASSES["mem"]
    for obs in observed + list(_synthetic_observations(2000)):
        got = chip_smoke.reference_costs(obs)
        np.testing.assert_array_equal(got["vcpu"],
                                      CF.absolute_vcpu_costs(obs, n_v))
        np.testing.assert_array_equal(
            got["mem"], CF.memory_costs(obs, n_m, chip_smoke.MEM_CLASS_MB))


def _corrupt_first_vcpu_predict(stream, n_classes):
    out = list(stream)
    for i, ev in enumerate(out):
        if ev[0] == "predict" and ev[3]:
            out[i] = ev[:5] + ((ev[5] + n_classes // 2) % n_classes,) + ev[6:]
            return out
    raise AssertionError("no served vCPU prediction in the stream")


def test_replay_rejects_corrupted_served_class(recorded):
    engine, stream = recorded
    bad = _corrupt_first_vcpu_predict(stream, engine.n_vcpu_classes)
    st = chip_smoke.replay_reference(engine, bad)
    assert not st["ok"]
    assert len(st["mismatches"]) == 1
    assert st["mismatches"][0][1] == "vcpu"


def test_replay_rejects_class_served_unasked(recorded):
    engine, stream = recorded
    out = list(stream)
    i = next(i for i, ev in enumerate(out) if ev[0] == "predict" and not ev[4])
    out[i] = out[i][:6] + (0,)
    st = chip_smoke.replay_reference(engine, out)
    assert not st["ok"]
    assert st["mismatches"][0][2] == "served unasked"


def test_phase_a_and_b_on_the_device_path(monkeypatch, capsys):
    monkeypatch.setattr(agent_arena, "numpy_backend", lambda d: False)
    monkeypatch.setattr(agent_arena, "numpy_crossover_rows",
                        lambda d, n_classes=32: 0)
    ok, engine, stream = chip_smoke.phase_a(rps=4.0, duration_s=120.0)
    out = capsys.readouterr().out
    assert ok, out
    assert "A invariants: every invocation terminated once" in out
    assert chip_smoke.phase_b(engine, stream)
