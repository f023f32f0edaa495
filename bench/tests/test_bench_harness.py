"""Whole runs of the harness on the CPU, with the look for a chip
patched out: the result line's shape, its counts of one whole pass,
faults planted under the timed path, a cell added by files alone, and
the refusals."""

import json
import os
import shutil
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from bench import harness, peaks, traffic
from bench.spans import Probe
from repro import compile_cache
from repro.core import agent_arena
from repro.core.agent_arena import ArenaEngine
from repro.serving.baselines import ShabariPolicy

REQUIRED = ["correct", "attempted", "failed", "metrics", "device"]


@pytest.fixture
def on_cpu(monkeypatch):
    """Let a run take the CPU for a chip, price it as a v5e, and leave
    JAX's compile-cache settings of this process alone."""
    monkeypatch.setattr(harness, "require_chips", lambda n: jax.devices()[0])
    v5e = peaks.peaks("TPU v5 lite")
    monkeypatch.setattr(harness.peaks, "peaks", lambda kind: v5e)
    monkeypatch.setattr(compile_cache, "enable_compile_cache", lambda: "off")


class OnePass(Probe):
    """Ends the window after its first ``whole`` passes, whatever the
    clock says: those run to their end however long they take, and the
    next is stopped at its first call."""
    whole = 1
    passes = 0

    def instrument(self, sim):
        self.passes += 1
        self.deadline = float("inf") if self.passes <= self.whole else 0.0
        super().instrument(sim)


class TwoPasses(OnePass):
    whole = 2


def _run(capsys, workload, seconds=2, trace=0, seed=2**31 + 5):
    rc = harness.main(["--workload", workload, "--seed", str(seed),
                       "--seconds", str(seconds), "--trace", str(trace)])
    out = capsys.readouterr()
    lines = out.out.strip().splitlines()
    return rc, (json.loads(lines[-1]) if lines else None), out.err


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("workload,per_pass", [("testbed.azure", 1200)])
def test_result_line(on_cpu, capsys, monkeypatch, trace, workload,
                     per_pass):
    # one whole pass, so that a loaded machine cannot end the window
    # with none
    monkeypatch.setattr(harness, "Probe", OnePass)
    rc, res, err = _run(capsys, workload, trace=trace)
    assert rc == 0, err
    assert list(res) == REQUIRED + (["breakdown"] if trace else []) + ["checks"]
    assert res["correct"] is True, res["checks"]
    # attempted and failed count the first whole pass
    assert res["attempted"] == per_pass
    assert 0 <= res["failed"] < res["attempted"]
    key = "per_layer" if trace else "end_to_end"
    names = {m["name"] for m in harness.load_json(
        harness.CHECKOUT, "BENCHMARK.json")[key]
        if workload in m.get("workloads", [workload])}
    assert set(res["metrics"]) <= names
    if not trace:
        assert set(res["metrics"]) == names
    assert all(m["value"] > 0 for m in res["metrics"].values())
    assert res["device"]["count"] == 1
    assert "recording for the reference:" in err
    # every number compared is printed beside its limit, last on stderr
    tail = err.strip().splitlines()[-len(res["checks"]):]
    assert all(line.startswith("[bench] check ") and "limit" in line
               for line in tail)


def _state_unchanged(monkeypatch):
    monkeypatch.setattr(ArenaEngine, "_flush_pass", lambda self, batch: None)


def _half_batch(monkeypatch):
    orig = ArenaEngine._flush_pass
    monkeypatch.setattr(ArenaEngine, "_flush_pass", lambda self, batch: orig(
        self, batch[:(len(batch) + 1) // 2]))


def _answer_altered(monkeypatch):
    orig = ArenaEngine.predict

    def predict(self, fn, x, want_v, want_m):
        v, m = orig(self, fn, x, want_v, want_m)
        return (None if v is None else (v + 1) % self.n_vcpu_classes), m

    monkeypatch.setattr(ArenaEngine, "predict", predict)


def _mem_class_down(monkeypatch, shift=2):
    """Every served memory class moved ``shift`` classes down (not below
    the first), where the engine's outermost predict call returns it."""
    def down(vm):
        v, m = vm
        return v, (None if m is None else max(m - shift, 0))

    depth = [0]

    def outermost(orig, fix):
        def call(self, *args):
            depth[0] += 1
            try:
                out = orig(self, *args)
            finally:
                depth[0] -= 1
            return out if depth[0] else fix(out)
        return call

    monkeypatch.setattr(ArenaEngine, "predict",
                        outermost(ArenaEngine.predict, down))
    monkeypatch.setattr(ArenaEngine, "predict_batch", outermost(
        ArenaEngine.predict_batch, lambda out: [down(vm) for vm in out]))


def _one_dim_unchanged(monkeypatch):
    """Updates of feature dimension 5 (speech2text alone) never land."""
    orig = ArenaEngine._flush_pass

    def flush_pass(self, batch):
        keep = [p for p in batch if len(p.xb) - 1 != 5]
        if keep:
            orig(self, keep)

    monkeypatch.setattr(ArenaEngine, "_flush_pass", flush_pass)


def _one_dim_half(monkeypatch):
    """Every second update of feature dimension 2 (sentiment alone) is
    dropped."""
    orig = ArenaEngine._flush_pass
    seen = [0]

    def flush_pass(self, batch):
        keep = []
        for p in batch:
            if len(p.xb) - 1 == 2:
                seen[0] += 1
                if seen[0] % 2 == 0:
                    continue
            keep.append(p)
        if keep:
            orig(self, keep)

    monkeypatch.setattr(ArenaEngine, "_flush_pass", flush_pass)


def _bf16_dots(monkeypatch):
    """The control in the program's place: every arena dot product in
    one bfloat16 pass, as the TPU runs an f32 dot at default precision;
    the NumPy path is switched off so that every dimension takes it."""
    def dot(w, xb):
        return jnp.dot(w.astype(jnp.bfloat16), xb.astype(jnp.bfloat16),
                       preferred_element_type=jnp.float32)

    def xb_of(x):
        return jnp.concatenate([x, jnp.ones((1,), x.dtype)])

    def core(w, g2, xb, costs, lr):
        grad = (dot(w, xb) - costs)[:, None] * xb[None, :]
        g2 = g2 + jnp.square(grad)
        return w - lr * grad / (jnp.sqrt(g2) + 1e-6), g2

    monkeypatch.setattr(agent_arena, "numpy_backend", lambda d: False)
    monkeypatch.setattr(agent_arena, "_csc_predict", jax.jit(
        lambda w, x, n: dot(w, xb_of(x)), static_argnums=(2,)))
    monkeypatch.setattr(agent_arena, "_csc_update", jax.jit(
        lambda w, g2, x, costs, lr: core(w, g2, xb_of(x), costs, lr)))
    monkeypatch.setattr(agent_arena, "_batched_update", jax.jit(
        jax.vmap(core, in_axes=(0, 0, 0, 0, None))))
    monkeypatch.setattr(agent_arena, "_batched_predict", jax.jit(
        jax.vmap(dot, in_axes=(0, 0))))


def _updates_lost(monkeypatch):
    """The policy drops every tenth completion's feedback before it
    reaches the arena."""
    orig = ShabariPolicy.feedback
    seen = [0]

    def feedback(self, *args, **kwargs):
        seen[0] += 1
        if seen[0] % 10:
            return orig(self, *args, **kwargs)

    monkeypatch.setattr(ShabariPolicy, "feedback", feedback)


@pytest.mark.parametrize("fault", [_state_unchanged, _half_batch,
                                   _answer_altered, _mem_class_down,
                                   _one_dim_unchanged,
                                   _one_dim_half, _bf16_dots, _updates_lost])
@pytest.mark.parametrize("workload", ["testbed.azure"])
def test_faults_under_the_timed_path_are_not_correct(on_cpu, capsys,
                                                     monkeypatch, fault,
                                                     workload):
    fault(monkeypatch)
    # long enough for one whole pass of a fault's slower path on the CPU
    rc, res, err = _run(capsys, workload, seconds=8)
    assert rc == 0, err
    assert res["correct"] is False
    assert any(c["value"] > c["limit"] for c in res["checks"].values())


@pytest.mark.parametrize("workload", ["testbed.azure", "pop384.azure"])
def test_counts_are_one_pass_however_many_the_window_holds(
        on_cpu, capsys, monkeypatch, workload):
    """The same seed with one and with two whole passes in the window
    reports the same ``attempted`` and ``failed``: the first pass's."""
    runs = []
    for probe, whole in ((OnePass, 1), (TwoPasses, 2)):
        monkeypatch.setattr(harness, "Probe", probe)
        rc, res, err = _run(capsys, workload)
        assert rc == 0, err
        assert res["correct"] is True, res["checks"]
        assert f"{whole} whole passes," in err
        assert f"in one whole pass, the first of {whole}" in err
        runs.append((res["attempted"], res["failed"]))
    assert runs[0] == runs[1]
    assert runs[0][0] == 1200


def test_a_memory_fault_raises_failed(on_cpu, capsys, monkeypatch):
    """Served memory two classes too small OOM-kills what it serves: at
    seed 2**31 + 5 in ``testbed.azure`` one pass fails 38 invocations
    (4 OOM kills) unfaulted and 496 (450 OOM kills) faulted, on the CPU.
    The count of one pass sees a program that fails more."""
    monkeypatch.setattr(harness, "Probe", OnePass)
    rc, clean, err = _run(capsys, "testbed.azure")
    assert rc == 0, err
    assert clean["correct"] is True, clean["checks"]
    _mem_class_down(monkeypatch)
    rc, res, err = _run(capsys, "testbed.azure")
    assert rc == 0, err
    assert res["attempted"] == clean["attempted"] == 1200
    assert res["failed"] > clean["failed"]
    assert res["correct"] is False
    assert any(c["value"] > c["limit"] for c in res["checks"].values())


def test_a_mix_added_by_files_alone(on_cpu, capsys, monkeypatch, tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(harness.BENCH_DIR, root / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    bench = harness.load_json(harness.CHECKOUT, "BENCHMARK.json")
    bench["workloads"].append({"name": "testbed.steady", "config": "testbed-16x90",
                               "traffic": "steady", "chips": 1,
                               "why": "steady Poisson load"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    (root / "bench" / "traffic" / "steady.json").write_text(json.dumps({
        "shape": "uniform-poisson", "rps": 3.0, "duration_s": 60.0,
        "clones": 1, "params": {}}))
    monkeypatch.setattr(harness, "CHECKOUT", str(root))
    monkeypatch.setattr(harness, "BENCH_DIR", str(root / "bench"))
    monkeypatch.setattr(traffic, "TRAFFIC_DIR", str(root / "bench" / "traffic"))
    rc, res, err = _run(capsys, "testbed.steady", seconds=1)
    assert rc == 0, err
    assert res["correct"] is True and res["attempted"] > 0
    assert "steady" in err or "testbed.steady" in err


def test_refuses_a_cpu(capsys):
    rc, res, err = _run(capsys, "testbed.azure", seconds=1)
    assert rc != 0 and res is None
    assert "refused" in err


def test_fails_with_only_the_benchmark_files(tmp_path):
    root = tmp_path / "bare"
    shutil.copytree(harness.BENCH_DIR, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(harness.CHECKOUT, "BENCHMARK.json"), root)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "testbed.azure",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=root, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
