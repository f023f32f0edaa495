"""Bring-up check: the Shabari allocator path on one TPU chip.

    python chip_smoke.py

One process that starts no children. It asserts that JAX's default
device is a TPU before any phase and exits non-zero anywhere else:
there is no CPU fallback.

A  The main path at the paper's testbed width: ``shabari`` over the
   Azure-shaped trace (rps 5, 600 s, seed 0) on the default SimConfig
   (16 workers x 90 vCPU x 125 GB), built as ``run_experiment`` builds
   it. Checks the end-of-run invariants, that predicted allocations were
   served, and that the agent arena's jitted kernels ran on the device.
B  Decision check: the arena's ordered predict/update stream from A is
   replayed through a float64 NumPy CSOAA reference written from the
   paper's cost definition and update rule, independent of
   ``repro.core.agent_arena`` and ``repro.core.cost_functions``.
C  Golden cross-check, information only: ``heavy-tail-inputs`` against
   its CPU-made snapshot in ``tests/goldens/``.

The last line of stdout is one JSON object naming the device.
"""

from __future__ import annotations

import collections
import contextlib
import json
import math
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.compile_cache import enable_compile_cache  # noqa: E402
from repro.core import agent_arena  # noqa: E402
from repro.core.agent_arena import ArenaEngine  # noqa: E402
from repro.core.featurizer import FEATURE_SCHEMAS  # noqa: E402
from repro.serving import golden  # noqa: E402
from repro.serving.experiment import build_simulator, experiment_inputs  # noqa: E402
from repro.serving.invariants import check_invariants  # noqa: E402
from repro.serving.simulator import SimConfig, summarize  # noqa: E402

# Phase B tolerances. A served class may differ from the reference's
# arg-min only where its reference cost is within TIE_RTOL (relative) of
# the minimum; final weights must agree within WEIGHT_RTOL of each
# agent's largest reference weight.
TIE_RTOL = 1e-4
WEIGHT_RTOL = 1e-3

KERNELS = ("_csc_predict", "_csc_update", "_batched_update",
           "_batched_predict")
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def device_label() -> str:
    d = jax.devices()[0]
    return f"{d.platform}:{d.device_kind} x{jax.device_count()}"


def say(*parts) -> None:
    print(f"[{device_label()}]", *parts, flush=True)


# ----------------------------------------------------------- instruments
class KernelCounter:
    """Counts calls of the arena's jitted kernels per (kernel, dim), and
    per platform the calls whose outputs all landed on it."""

    def __init__(self):
        self.calls = collections.Counter()
        self.platforms = collections.Counter()

    def reset(self):
        self.calls.clear()
        self.platforms.clear()

    def per_dim(self, dim: int) -> int:
        return sum(c for (_, d), c in self.calls.items() if d == dim)


@contextlib.contextmanager
def counting_kernels():
    counter = KernelCounter()
    orig = {name: getattr(agent_arena, name) for name in KERNELS}

    def wrap(name, fn):
        def counted(*args, **kwargs):
            out = fn(*args, **kwargs)
            # w's last axis is dim + 1
            counter.calls[(name, args[0].shape[-1] - 1)] += 1
            platforms = {d.platform for leaf in jax.tree.leaves(out)
                         for d in leaf.devices()}
            counter.platforms["+".join(sorted(platforms))] += 1
            return out
        return counted

    for name, fn in orig.items():
        setattr(agent_arena, name, wrap(name, fn))
    try:
        yield counter
    finally:
        for name, fn in orig.items():
            setattr(agent_arena, name, fn)


class CompileClock:
    """While open, counts backend compiles (persistent-cache reads
    included) and their seconds."""

    def __enter__(self):
        self.n, self.seconds = 0, 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)
        return self

    def __exit__(self, *exc):
        jax.monitoring.unregister_event_duration_listener(self._on_event)

    def _on_event(self, event, duration, **_):
        if event == COMPILE_EVENT:
            self.n += 1
            self.seconds += duration

    def take(self):
        out = (self.n, self.seconds)
        self.n, self.seconds = 0, 0.0
        return out


@contextlib.contextmanager
def recording_arena():
    """Records each ArenaEngine's ordered stream while open, as
    ``{engine: [event, ...]}``. Events are ``("predict", fn, x, want_v,
    want_m, v_cls, m_cls)`` and ``("update", fn, x, obs)``; a predict
    that the engine serves through its own predict_batch counts once."""
    streams = collections.defaultdict(list)
    depth = collections.Counter()
    orig = {name: getattr(ArenaEngine, name)
            for name in ("predict", "predict_batch", "enqueue_update")}

    def predict(self, fn, x, want_v, want_m):
        depth[self] += 1
        try:
            out = orig["predict"](self, fn, x, want_v, want_m)
        finally:
            depth[self] -= 1
        if not depth[self]:
            streams[self].append(("predict", fn, np.array(x, np.float32),
                                  want_v, want_m) + tuple(out))
        return out

    def predict_batch(self, items):
        depth[self] += 1
        try:
            out = orig["predict_batch"](self, items)
        finally:
            depth[self] -= 1
        if not depth[self]:
            for (fn, x, want_v, want_m), cls in zip(items, out):
                streams[self].append(("predict", fn, np.array(x, np.float32),
                                      want_v, want_m) + tuple(cls))
        return out

    def enqueue_update(self, fn, x, obs):
        streams[self].append(("update", fn, np.array(x, np.float32), obs))
        return orig["enqueue_update"](self, fn, x, obs)

    ArenaEngine.predict = predict
    ArenaEngine.predict_batch = predict_batch
    ArenaEngine.enqueue_update = enqueue_update
    try:
        yield streams
    finally:
        for name, fn in orig.items():
            setattr(ArenaEngine, name, fn)


# -------------------------------------------------- float64 reference
# The agents as the ``shabari`` policy configures them (paper §4.3, §6):
# classes are 1..32 vCPUs and 1..40 x 128 MB; AdaGrad rate 0.5.
N_CLASSES = {"vcpu": 32, "mem": 40}
MEM_CLASS_MB = 128
LR = 0.5
# Absolute vCPU costs (§4.3.1): every 0.5 s of SLO violation moves the
# target one class above the vCPUs used, every 1.5 s of slack one class
# below; a violation at under 90% utilization is not the allocation's
# fault. Costs are 1 at the target and grow linearly away from it,
# underprediction more steeply (an OOM kill is worse than an SLO miss).
VIOLATION_S_PER_CLASS = 0.5
SLACK_S_PER_CLASS = 1.5
HIGH_UTIL = 0.9
SLOPES = {"vcpu": (3.0, 1.0), "mem": (6.0, 1.0)}  # (under, over) per class


def reference_costs(obs):
    """{"vcpu": costs, "mem": costs} for one completed invocation."""
    def clamp(i, res):
        return max(0, min(N_CLASSES[res] - 1, i))

    used = clamp(math.ceil(obs.max_vcpus_used) - 1, "vcpu")
    if obs.exec_time_s <= obs.slo_s:
        slack = obs.slo_s - obs.exec_time_s
        v = (min(clamp(obs.alloc_vcpus - 1, "vcpu"), used)
             - int(slack / SLACK_S_PER_CLASS))
    elif obs.max_vcpus_used / max(obs.alloc_vcpus, 1) < HIGH_UTIL:
        v = used
    else:
        violation = obs.exec_time_s - obs.slo_s
        v = used + 1 + int(violation / VIOLATION_S_PER_CLASS)
    if obs.oom_killed:  # the need exceeds the allocation
        m = math.ceil(obs.alloc_mem_mb / MEM_CLASS_MB)
    else:
        m = math.ceil(obs.max_mem_used_mb / MEM_CLASS_MB) - 1
    out = {}
    for res, target in (("vcpu", v), ("mem", m)):
        under, over = SLOPES[res]
        k = np.arange(N_CLASSES[res], dtype=np.float64) - clamp(target, res)
        out[res] = 1.0 + np.where(k < 0, -under * k, over * k)
    return out


def replay_reference(engine, stream):
    """Replay ``stream`` through a float64 CSOAA (paper §4): per function
    and resource one linear regressor per class, predict = arg-min of
    the predicted costs, update = one AdaGrad least-squares step on every
    class toward :func:`reference_costs`. Returns the served-vs-reference
    statistics and the largest relative weight deviation from
    ``engine``'s final state."""
    n = N_CLASSES
    agents = {}  # (fn, resource) -> [w, g2]
    st = {"predicts": 0, "near_ties": 0, "tie_breaks": 0, "mismatches": []}
    config = (engine.n_vcpu_classes, engine.n_mem_classes,
              engine.mem_class_mb, float(engine.lr))
    if config != (n["vcpu"], n["mem"], MEM_CLASS_MB, LR):
        st["mismatches"].append(("engine configuration", config))

    def agent(fn, res, dim):
        return agents.setdefault(
            (fn, res), [np.zeros((n[res], dim + 1)), np.zeros((n[res], dim + 1))])

    for ev in stream:
        fn, xb = ev[1], np.append(ev[2].astype(np.float64), 1.0)
        if ev[0] == "update":
            costs = reference_costs(ev[3])
            for res in ("vcpu", "mem"):
                w, g2 = agent(fn, res, len(ev[2]))
                grad = np.outer(w @ xb - costs[res], xb)
                g2 += grad * grad
                w -= LR * grad / (np.sqrt(g2) + 1e-6)
            continue
        for res, want, served in (("vcpu", ev[3], ev[5]), ("mem", ev[4], ev[6])):
            if not want:
                if served is not None:
                    st["mismatches"].append((fn, res, "served unasked", served))
                continue
            c = agent(fn, res, len(ev[2]))[0] @ xb
            lo2 = np.sort(c)[:2]
            margin = TIE_RTOL * max(abs(lo2[0]), abs(lo2[1]))
            st["predicts"] += 1
            st["near_ties"] += int(lo2[1] - lo2[0] <= margin)
            if served is None or c[served] - lo2[0] > margin:
                st["mismatches"].append(
                    (fn, res, served, int(np.argmin(c)), c[served] - lo2[0]
                     if served is not None else None))
            elif served != int(np.argmin(c)):
                st["tie_breaks"] += 1
    dev = 0.0
    for fn in {f for f, _ in agents}:
        vw, _, mw, _ = engine.weights(fn)
        for res, got in (("vcpu", vw), ("mem", mw)):
            want = agents[(fn, res)][0]
            scale = max(float(np.abs(want).max()), 1e-30)
            dev = max(dev, float(np.abs(got - want).max()) / scale)
    st["max_weight_dev"] = dev
    st["ok"] = not st["mismatches"] and dev <= WEIGHT_RTOL
    return st


def report_replay(name: str, st) -> None:
    say(f"{name}: {st['predicts']} served predictions, near-ties "
        f"{st['near_ties']} (rel margin {TIE_RTOL:g}), tie-breaks off the "
        f"reference arg-min {st['tie_breaks']}, mismatches "
        f"{len(st['mismatches'])}, largest weight deviation "
        f"{st['max_weight_dev']!r} (limit {WEIGHT_RTOL:g})")
    for m in st["mismatches"][:10]:
        say(f"{name}: MISMATCH {m}")


# --------------------------------------------------------------- phases
def phase_a(rps: float = 5.0, duration_s: float = 600.0, seed: int = 0):
    """Main path; returns (ok, arena engine, its recorded stream)."""
    t0 = time.perf_counter()
    profiles, pool, slo_table, trace = experiment_inputs(
        rps=rps, duration_s=duration_s, seed=seed)
    sim = build_simulator("shabari", profiles, pool, slo_table, seed=seed,
                          sim_cfg=SimConfig(seed=seed))
    cfg = sim.cfg
    say(f"A: {len(trace)} invocations, rps {rps}, {duration_s} s, seed "
        f"{seed}; {cfg.n_clusters} x {cfg.n_workers} workers x "
        f"{cfg.vcpus_per_worker} vCPU x {cfg.mem_mb_per_worker // 1024} GB; "
        f"inputs built in {time.perf_counter() - t0:.3f} s")

    fn_dims = {fn: len(FEATURE_SCHEMAS[p.input_type])
               for fn, p in profiles.items()}
    dims = sorted(set(fn_dims.values()))
    with CompileClock() as clock, counting_kernels() as kernels, \
            recording_arena() as streams:
        t0 = time.perf_counter()
        agent_arena.calibrate(dims)
        for d in dims:
            agent_arena.vmap_backend(d)
        cal_s = time.perf_counter() - t0
        n_comp, comp_s = clock.take()
        cal_calls = sum(kernels.calls.values())
        say(f"A set-up: calibration of dims {dims} {cal_s:.3f} s "
            f"({cal_calls} kernel calls), {n_comp} compiles {comp_s:.3f} s")
        kernels.reset()

        served = set()
        allocate = sim.policy.allocate_with_aux

        def counted_allocate(arrival, *args, **kwargs):
            alloc, aux = allocate(arrival, *args, **kwargs)
            if alloc.vcpu_predicted and alloc.mem_predicted:
                served.add(arrival.invocation_id)
            return alloc, aux

        sim.policy.allocate_with_aux = counted_allocate
        t0 = time.perf_counter()
        results = sim.run(trace)
        run_s = time.perf_counter() - t0
        n_comp, comp_s = clock.take()
    engine = sim.policy.allocator._arena
    (stream,) = [s for e, s in streams.items() if e is engine]

    s = summarize(results)
    say(f"A run: {run_s:.3f} s wall, {sim.events_processed} events, "
        f"{sim.events_processed / run_s:.1f} events/s, {n_comp} compiles "
        f"{comp_s:.3f} s inside the run")
    say(f"A summary: SLO violations {s['slo_violation_pct']:.2f}%, wasted "
        f"vCPU p50 {s['wasted_vcpus_p50']:.3f}, wasted memory p50 "
        f"{s['wasted_mem_mb_p50']:.1f} MB, cold starts "
        f"{s['cold_start_pct']:.2f}%, predicted allocations served "
        f"{len(served)}")
    say("A backends: dim | functions | numpy_backend | vmap_backend | "
        "numpy_crossover_rows | kernel calls in the run")
    for d in dims:
        fns = ",".join(sorted(f for f, fd in fn_dims.items() if fd == d))
        say(f"A backends: {d} | {fns} | {agent_arena.numpy_backend(d)} | "
            f"{agent_arena.vmap_backend(d)} | "
            f"{agent_arena.numpy_crossover_rows(d)} | {kernels.per_dim(d)}")
    for (name, d), c in sorted(kernels.calls.items()):
        say(f"A kernel: {name} dim {d}: {c} calls")
    all_calls = sum(kernels.calls.values())
    device_calls = kernels.platforms[jax.devices()[0].platform]
    say(f"A kernel calls in the run by output platform "
        f"{dict(sorted(kernels.platforms.items()))}; {device_calls} of "
        f"{all_calls} on the default device")

    ok = True
    try:
        check_invariants(sim, trace, results)
        say("A invariants: every invocation terminated once; reservations "
            "and active demand drained to zero")
    except AssertionError as e:
        say(f"A invariants: FAILED {e!r}")
        ok = False
    if not served:
        say("A: FAILED no allocation was served past both confidence "
            "thresholds")
        ok = False
    if device_calls == 0 or device_calls != all_calls:
        say("A: FAILED arena kernel calls in the run off the default "
            "device, or none at all")
        ok = False
    return ok, engine, stream


def phase_b(engine, stream) -> bool:
    st = replay_reference(engine, stream)
    report_replay("B", st)
    return st["ok"]


def phase_c() -> None:
    with recording_arena() as streams:
        got = golden.run_golden("heavy-tail-inputs")
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)), "tests",
                        "goldens", "heavy-tail-inputs.json")
    with open(path) as f:
        want = json.load(f)["summary"]
    off = [k for k in want if not math.isclose(
        got.get(k, math.nan), want[k], rel_tol=golden.RTOL,
        abs_tol=golden.ATOL)]
    (engine, stream), = streams.items()
    st = replay_reference(engine, stream)
    say(f"C golden heavy-tail-inputs: matches the CPU golden within rtol "
        f"{golden.RTOL:g}: {not off}; near-ties {st['near_ties']}; "
        f"replay ok {st['ok']}")
    for k in off:
        say(f"C golden: {k} got {got.get(k)!r} golden {want[k]!r}")


def main() -> int:
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: the default JAX device is {dev.platform!r}, "
              "not a TPU", file=sys.stderr)
        return 1
    say(f"cache: {enable_compile_cache()}")
    ok_a, engine, stream = phase_a()
    ok_b = phase_b(engine, stream)
    phase_c()
    if not (ok_a and ok_b):
        say(f"FAILED: phase A ok {ok_a}, phase B ok {ok_b}")
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": jax.device_count()}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
